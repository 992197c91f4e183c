"""Jaxpr dataflow graph for ftverify.

``build_graph`` flattens a ``ClosedJaxpr`` — descending into ``jit`` /
``scan`` / ``while`` / ``cond`` / ``custom_*`` / ``pallas_call`` sub-jaxprs —
into one global def-use graph.  Sub-jaxpr binders are *aliased* to their
call-site operands with a union-find, so a backward walk from a truncation
shift inside a scan body escapes cleanly to the quantization boundary in the
caller, and a key var threaded through three helper jits still has one root.

The graph deliberately does **not** alias a scan carry's outputs back onto
its inputs: walks stay intra-iteration (rules reason about one step of the
loop), and cross-iteration questions ("does this draw vary per step?") are
answered by the explicit taint pass :meth:`Graph.scan_variant_roots`.

Vars are identified by ``id()`` of the binder object; ``jax.core.Literal``
operands get fresh negative ids (never aliased).  All rule-facing queries
(:meth:`producer`, :meth:`consumers`, :meth:`origin_sig`, the slice walks)
resolve through the union-find first.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Any

import jax
import jax.numpy as jnp

# jaxpr types (jax 0.4.x public-ish surface)
from jax.extend.core import ClosedJaxpr, Jaxpr, Literal  # noqa: F401

RNG_PRIMS = frozenset({
    "random_bits", "random_fold_in", "random_split", "random_wrap",
    "random_unwrap", "random_seed", "threefry2x32",
})

# shape/layout ops that forward their first operand's values unchanged —
# used by key-origin signatures and the rope/bf16 chain walks
PASSTHROUGH_PRIMS = frozenset({
    "reshape", "squeeze", "expand_dims", "broadcast_in_dim", "transpose",
    "slice", "rev", "copy", "stop_gradient", "convert_element_type",
    "random_wrap", "random_unwrap", "sharding_constraint",
})

# call-like primitives whose outputs alias a sub-jaxpr's outputs; concrete
# inner eqns take precedence over these in the producer map (see _finish)
CALL_LIKE_PRIMS = frozenset({
    "jit", "closed_call", "core_call", "remat2", "checkpoint",
    "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr",
    "scan", "while", "cond", "pallas_call",
})


@dataclasses.dataclass
class GEqn:
    """One flattened equation: global var ids + the original eqn."""
    idx: int                     # position in Graph.eqns
    prim: str
    invars: list[int]            # global var ids (literals get fresh ids)
    outvars: list[int]
    eqn: Any                     # the JaxprEqn (params via eqn.params)
    path: tuple[str, ...]        # lexical nesting, e.g. ("jit", "scan")
    scans: tuple[int, ...]       # idx of each enclosing scan GEqn


class Graph:
    def __init__(self) -> None:
        self.eqns: list[GEqn] = []
        self._parent: dict[int, int] = {}           # union-find
        self._aval: dict[int, Any] = {}             # root id -> aval
        self._literal: dict[int, Any] = {}          # var id -> literal value
        self._producers: dict[int, tuple[GEqn, int]] = {}
        self._consumers: dict[int, list[tuple[GEqn, int]]] = {}
        self.invar_ids: list[int] = []              # top-level invars
        self.const_ids: set[int] = set()            # top-level/inner consts
        # per-scan: inner binder ids of the carry+xs section (variant seeds)
        self.scan_variant_seeds: dict[int, list[int]] = {}
        self._ids = itertools.count(1)

    # -------------------------------------------------------- union-find --
    def _new_id(self, var=None) -> int:
        vid = next(self._ids)
        self._parent[vid] = vid
        if var is not None and hasattr(var, "aval"):
            self._aval[vid] = var.aval
        return vid

    def find(self, vid: int) -> int:
        p = self._parent
        root = vid
        while p[root] != root:
            root = p[root]
        while p[vid] != root:
            p[vid], vid = root, p[vid]
        return root

    def union(self, a: int, b: int) -> None:
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self._parent[rb] = ra
            if ra not in self._aval and rb in self._aval:
                self._aval[ra] = self._aval[rb]

    # ------------------------------------------------------------ queries --
    def aval(self, vid: int):
        return self._aval.get(self.find(vid))

    def dtype(self, vid: int):
        a = self.aval(vid)
        return getattr(a, "dtype", None)

    def is_float(self, vid: int) -> bool:
        dt = self.dtype(vid)
        return dt is not None and jnp.issubdtype(dt, jnp.floating)

    def is_int(self, vid: int) -> bool:
        dt = self.dtype(vid)
        return dt is not None and jnp.issubdtype(dt, jnp.integer)

    def is_bool(self, vid: int) -> bool:
        dt = self.dtype(vid)
        return dt is not None and dt == jnp.bool_

    def is_literal(self, vid: int) -> bool:
        return self.find(vid) in self._literal

    def producer(self, vid: int) -> tuple[GEqn, int] | None:
        return self._producers.get(self.find(vid))

    def consumers(self, vid: int) -> list[tuple[GEqn, int]]:
        return self._consumers.get(self.find(vid), [])

    def eqns_by_prim(self, *prims: str) -> list[GEqn]:
        want = set(prims)
        return [e for e in self.eqns if e.prim in want]

    # ------------------------------------------------------------- builds --
    def _finish(self) -> None:
        """Key producer/consumer maps by union-find roots (post-aliasing).

        A call-site output is aliased to the sub-jaxpr's output binder, so
        its root has two producers: the call eqn (appended first) and the
        concrete inner eqn.  The *inner* one wins — backward walks then see
        the real op (and its round/bool boundaries) instead of jumping from
        a call eqn to its operands and skipping the body entirely."""
        for e in self.eqns:
            for i, vid in enumerate(e.outvars):
                r = self.find(vid)
                cur = self._producers.get(r)
                if cur is None or (cur[0].prim in CALL_LIKE_PRIMS
                                   and e.prim not in CALL_LIKE_PRIMS):
                    self._producers[r] = (e, i)
            for i, vid in enumerate(e.invars):
                self._consumers.setdefault(self.find(vid), []).append((e, i))

    # ------------------------------------------------------------- walks --
    def forward_taint(self, seed_ids, within_scan: int | None = None):
        """Set of var roots reachable forward from ``seed_ids``.  When
        ``within_scan`` is a scan eqn idx, propagation stays inside that
        scan's body."""
        tainted = {self.find(v) for v in seed_ids}
        work = list(tainted)
        while work:
            v = work.pop()
            for e, _ in self.consumers(v):
                if within_scan is not None and within_scan not in e.scans:
                    continue
                for out in e.outvars:
                    r = self.find(out)
                    if r not in tainted:
                        tainted.add(r)
                        work.append(r)
        return tainted

    def scan_variant_roots(self, scan_idx: int) -> set[int]:
        """Var roots inside scan body ``scan_idx`` that depend on the carry
        or the scanned-over xs (i.e. genuinely vary across iterations)."""
        seeds = self.scan_variant_seeds.get(scan_idx, [])
        return self.forward_taint(seeds, within_scan=scan_idx)

    def origin_sig(self, vid: int, _depth: int = 0):
        """Canonical origin of a value through pass-through ops.  Two vars
        with equal signatures carry the same bits (same producer, same
        slice/layout params) — the PRNG-key identity used by FTV103."""
        vid = self.find(vid)
        if _depth > 64:
            return ("deep", vid)
        if vid in self._literal:
            return ("lit", repr(self._literal[vid]))
        prod = self.producer(vid)
        if prod is None:
            return ("in", vid)
        e, out_idx = prod
        if e.prim in PASSTHROUGH_PRIMS and e.invars:
            params = e.eqn.params
            keyparams = tuple(sorted(
                (k, str(v)) for k, v in params.items()
                if k in ("start_indices", "limit_indices", "strides",
                         "permutation", "dimensions", "new_dtype",
                         "shape", "broadcast_dimensions", "sizes")))
            return (e.prim, keyparams,
                    self.origin_sig(e.invars[0], _depth + 1))
        return ("eqn", e.idx, out_idx)


# --------------------------------------------------------------------------
# flattening
# --------------------------------------------------------------------------
def _bind(g: Graph, env: dict[int, int], var) -> int:
    """Global id for a jaxpr var occurrence (Literal -> fresh id)."""
    if isinstance(var, Literal):
        vid = g._new_id()
        g._literal[vid] = var.val
        if hasattr(var, "aval"):
            g._aval[vid] = var.aval
        return vid
    key = id(var)
    if key not in env:
        env[key] = g._new_id(var)
    return env[key]


def _flatten(g: Graph, jaxpr: Jaxpr, env: dict[int, int],
             path: tuple[str, ...], scans: tuple[int, ...]) -> None:
    for eqn in jaxpr.eqns:
        in_ids = [_bind(g, env, v) for v in eqn.invars]
        out_ids = [_bind(g, env, v) for v in eqn.outvars]
        node = GEqn(len(g.eqns), eqn.primitive.name, in_ids, out_ids,
                    eqn, path, scans)
        g.eqns.append(node)
        _descend(g, node, path, scans)


def _sub_closed(params: dict, *keys: str):
    for k in keys:
        v = params.get(k)
        if isinstance(v, ClosedJaxpr):
            return v
        if isinstance(v, Jaxpr):
            return ClosedJaxpr(v, [])
    return None


def _enter(g: Graph, closed: ClosedJaxpr, env: dict[int, int]) -> tuple:
    """Fresh binder ids for a sub-jaxpr's constvars (+ record const ids)."""
    sub = closed.jaxpr
    for cv in sub.constvars:
        cid = _bind(g, env, cv)
        g.const_ids.add(g.find(cid))
    return sub


def _descend(g: Graph, node: GEqn, path: tuple[str, ...],
             scans: tuple[int, ...]) -> None:
    # Every descent opens a FRESH binding scope: jax dedupes traced
    # sub-jaxprs, so two jit eqns (e.g. two bernoulli calls) can share one
    # inner Jaxpr *object* — binding its vars in a shared env would union
    # both call sites' operands onto one binder and merge unrelated values.
    prim, params = node.prim, node.eqn.params

    if prim == "scan":
        closed = params["jaxpr"]
        senv: dict[int, int] = {}
        sub = _enter(g, closed, senv)
        n_consts = params.get("num_consts", 0)
        sub_path, sub_scans = path + (prim,), scans + (node.idx,)
        in_ids = [_bind(g, senv, v) for v in sub.invars]
        for a, b in zip(node.invars, in_ids):
            g.union(a, b)
        # carry + xs binders are the per-iteration variant seeds
        g.scan_variant_seeds[node.idx] = in_ids[n_consts:]
        _flatten(g, sub, senv, sub_path, sub_scans)
        out_ids = [_bind(g, senv, v) for v in sub.outvars]
        for a, b in zip(node.outvars, out_ids):
            g.union(a, b)
        return

    if prim == "while":
        cn, bn = params.get("cond_nconsts", 0), params.get("body_nconsts", 0)
        benv: dict[int, int] = {}
        body = _enter(g, params["body_jaxpr"], benv)
        carry_ops = node.invars[cn + bn:]
        in_ids = [_bind(g, benv, v) for v in body.invars]
        for a, b in zip(node.invars[cn:cn + bn] + carry_ops, in_ids):
            g.union(a, b)
        _flatten(g, body, benv, path + (prim,), scans)
        out_ids = [_bind(g, benv, v) for v in body.outvars]
        for a, b in zip(node.outvars, out_ids):
            g.union(a, b)
        cenv: dict[int, int] = {}
        cond = _enter(g, params["cond_jaxpr"], cenv)
        cin = [_bind(g, cenv, v) for v in cond.invars]
        for a, b in zip(node.invars[:cn] + carry_ops, cin):
            g.union(a, b)
        _flatten(g, cond, cenv, path + ("while_cond",), scans)
        return

    if prim == "cond":
        ops = node.invars[1:]                       # invars[0] is the index
        for branch in params["branches"]:
            benv2: dict[int, int] = {}
            sub = _enter(g, branch, benv2)
            in_ids = [_bind(g, benv2, v) for v in sub.invars]
            if len(in_ids) == len(ops):
                for a, b in zip(ops, in_ids):
                    g.union(a, b)
            _flatten(g, sub, benv2, path + (prim,), scans)
            out_ids = [_bind(g, benv2, v) for v in sub.outvars]
            for a, b in zip(node.outvars, out_ids):
                g.union(a, b)
        return

    # generic call-like primitives: jit, closed_call, remat2, custom_*
    closed = _sub_closed(params, "jaxpr", "call_jaxpr", "fun_jaxpr")
    if closed is None:
        return
    senv2: dict[int, int] = {}
    sub = _enter(g, closed, senv2)
    in_ids = [_bind(g, senv2, v) for v in sub.invars]
    # Alias binders to call-site operands only on an exact arity match (true
    # for jit/closed_call; custom_vjp-style prims with implicit extras get
    # no aliasing — walks stop at the boundary, a conservative miss, rather
    # than risking wrong unions that chain-merge unrelated values).
    if len(in_ids) == len(node.invars):
        for a, b in zip(node.invars, in_ids):
            g.union(a, b)
    _flatten(g, sub, senv2, path + (prim,), scans)
    out_ids = [_bind(g, senv2, v) for v in sub.outvars]
    if len(out_ids) == len(node.outvars):
        for a, b in zip(node.outvars, out_ids):
            g.union(a, b)


def build_graph(closed: ClosedJaxpr) -> Graph:
    g = Graph()
    env: dict[int, int] = {}
    for v in closed.jaxpr.constvars:
        g.const_ids.add(g.find(_bind(g, env, v)))
    g.invar_ids = [_bind(g, env, v) for v in closed.jaxpr.invars]
    _flatten(g, closed.jaxpr, env, (), ())
    g._finish()
    return g


def trace_jaxpr(fn, *avals, **kw) -> ClosedJaxpr:
    """``jax.make_jaxpr`` over ShapeDtypeStructs (no execution)."""
    return jax.make_jaxpr(fn, **kw)(*avals)
