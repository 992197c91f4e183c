"""FTV101 — integer-datapath purity, checked on the IR.

Invariant: everything feeding a truncation shift (the ``(acc+half) >> t``
of ``truncate_acc``) is integer arithmetic back to the quantization
boundary (``round``), the randomness boundary (``random_*``), or a boolean
predicate; and no value derived from an injected word takes a float
excursion that re-enters the integer path without re-quantizing.

FTL004 enforces this contract on the AST, but only inside the named
datapath files — a float cast hidden behind a helper in another module
(or introduced by an optimization "simplifying" ``truncate_acc``) is
invisible there.  Here the check runs on the flattened jaxpr, so helper
indirection doesn't exist: if a float op's output reaches the shift, it
is flagged no matter which module traced it.

Also checked: every ``dot_general`` on the slice accumulates in >= 32
integer bits (an int8xint8->int8 dot silently overflows the 24-bit
accumulator contract), and injected (xor) words never round-trip through
floats without a ``round`` (a raw ``astype(int32)`` after float math is
truncation toward zero — bit-inexact by construction).
"""
from __future__ import annotations

import jax.numpy as jnp

from tools.ftverify.rules import TraceRule

# float ops sanctioned on the backward walk: the clip half of the quantize
# pattern (round -> clip -> convert) plus value-preserving layout ops
QUANT_OK = frozenset({
    "clip", "max", "min", "convert_element_type", "select_n",
    "broadcast_in_dim", "reshape", "squeeze", "transpose", "slice",
    "concatenate", "expand_dims", "rev", "copy", "stop_gradient",
})
CALL_PRIMS = frozenset({
    "jit", "closed_call", "core_call", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "scan", "while", "cond", "pallas_call",
})
STOP_PRIMS = frozenset({"round", "iota"})

# ops that forward values unchanged for the float-roundtrip forward walk
FWD_PASS = frozenset({
    "reshape", "squeeze", "transpose", "slice", "broadcast_in_dim",
    "concatenate", "expand_dims", "select_n", "copy", "stop_gradient",
    "add", "sub", "mul", "max", "min", "neg",
})


def check_backward_slices(g, finding) -> list:
    """Walk backward from every truncation shift; flag float arithmetic and
    narrow integer dots on the way to the quantize/random/bool boundaries."""
    out = []
    for sra in g.eqns_by_prim("shift_right_arithmetic"):
        if not g.is_int(sra.outvars[0]):
            continue
        seen: set[int] = set()
        work = list(sra.invars)
        flagged: set[int] = set()
        while work:
            v = g.find(work.pop())
            if v in seen or g.is_literal(v) or v in g.const_ids:
                continue
            seen.add(v)
            if g.is_bool(v):
                continue                    # predicates are sanctioned
            pr = g.producer(v)
            if pr is None:
                continue
            pe, _ = pr
            if pe.prim in STOP_PRIMS or pe.prim.startswith("random"):
                continue                    # quantize / randomness boundary
            if pe.prim == "dot_general" and pe.idx not in flagged:
                dt = g.dtype(pe.outvars[0])
                if dt is not None and jnp.issubdtype(dt, jnp.integer) \
                        and jnp.iinfo(dt).bits < 32:
                    flagged.add(pe.idx)
                    out.append(finding(
                        "truncation",
                        f"dot_general accumulates in {dt} (<32 bits) on "
                        f"the path into a truncation shift — pin "
                        f"preferred_element_type=jnp.int32 (24-bit "
                        f"accumulator contract)"))
            if g.is_float(v) and pe.prim not in QUANT_OK \
                    and pe.prim not in CALL_PRIMS:
                if pe.idx not in flagged:
                    flagged.add(pe.idx)
                    out.append(finding(
                        "truncation",
                        f"float '{pe.prim}' feeds the integer datapath "
                        f"into a truncation shift (path {'/'.join(pe.path) or '<top>'}) "
                        f"— the protected slice must be integer-exact "
                        f"back to the round() quantize boundary"))
                continue                    # report the entry, don't recurse
            work.extend(pe.invars)
    return out


def check_injected_roundtrips(g, finding) -> list:
    """Forward from every xor (fault application): an int->float convert
    whose value re-enters an integer dtype without passing ``round`` is a
    float round-trip on injected words — flag it."""
    out = []
    flagged: set[int] = set()
    seen: set[int] = set()
    work = [v for x in g.eqns_by_prim("xor") if g.is_int(x.outvars[0])
            for v in x.outvars]
    while work:
        v = g.find(work.pop())
        if v in seen:
            continue
        seen.add(v)
        for ce, _ in g.consumers(v):
            if ce.prim == "convert_element_type" and g.is_int(v) \
                    and g.is_float(ce.outvars[0]):
                # entering a float excursion: scan forward for a float->int
                # reconvert with no round() in between
                if ce.idx not in flagged \
                        and _reenters_int_without_round(g, ce.outvars[0]):
                    flagged.add(ce.idx)
                    out.append(finding(
                        "injection",
                        "injected (xor) words take a float round-trip "
                        "that re-enters int without a round() — raw "
                        "float->int casts truncate toward zero and break "
                        "bit-exactness"))
            elif ce.prim in FWD_PASS or ce.prim in CALL_PRIMS \
                    or ce.prim in ("and", "or", "xor",
                                   "shift_right_arithmetic",
                                   "shift_left", "dot_general",
                                   "convert_element_type"):
                for ov in ce.outvars:
                    if g.is_int(ov):
                        work.append(ov)
    return out


def _reenters_int_without_round(g, start, depth: int = 8) -> bool:
    seen: set[int] = set()
    work = [(start, 0)]
    while work:
        v, d = work.pop()
        v = g.find(v)
        if v in seen or d > depth:
            continue
        seen.add(v)
        for ce, _ in g.consumers(v):
            if ce.prim == "round":
                continue                     # re-quantization: sanctioned
            if ce.prim == "convert_element_type" \
                    and g.is_int(ce.outvars[0]):
                return True
            for ov in ce.outvars:
                if not g.is_float(ov):
                    continue
                # ce may be a call eqn wrapping the round (jnp.round is a
                # jit); the producer map prefers inner eqns, so a rounded
                # output identifies itself here
                pr = g.producer(ov)
                if pr is not None and pr[0].prim == "round":
                    continue
                work.append((ov, d + 1))
    return False


class IntDatapathRule(TraceRule):
    code = "FTV101"
    name = "integer-datapath-purity"
    invariant = ("the jaxpr slice between fault injection (xor) and "
                 "truncation (shift_right_arithmetic) is integer-exact: no "
                 "float arithmetic, no sub-32-bit accumulation, no raw "
                 "float->int casts on injected words")
    tags = frozenset({"protect"})

    def check_target(self, ctx):
        g = ctx.graph
        if g is None:
            return []

        def finding(scope, msg):
            return ctx.finding(self.code, scope, msg)

        return (check_backward_slices(g, finding)
                + check_injected_roundtrips(g, finding))


RULE = IntDatapathRule()
