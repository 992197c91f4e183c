"""FTV106 — sharding-constraint coverage at the partition-sensitive spots.

Two constraints PR 9 added after chasing real cross-device divergences:

* **Post-rope re-constraint**: rope mixes the head dim in f32; without an
  activation constraint right after it, the residual stream's sequence
  sharding propagates into the kv length dim and the softmax ``p @ v``
  contraction becomes a partitioned float sum — a reordered accumulation
  that is not bitwise partition-invariant.  On the jaxpr this reads: every
  rope output (a 2-way ``concatenate`` of cos/sin-modulated halves) must
  reach a ``sharding_constraint`` before any ``dot_general`` or cache
  write.  Constraint eqns survive tracing even on a 1x1 mesh, so this
  check runs in single-device CI.

* **Paged-pool replication**: paged KV pools index by *global* block id, so
  ``cache_shardings`` must keep the pool and block dims replicated over the
  DP axes (sharding dim 0 as if it were batch breaks every block-table
  lookup) while still sharding kv heads over 'model'.  Checked directly
  against ``cache_shardings`` on a representative paged + dense layout.
"""
from __future__ import annotations

from tools.ftlint.core import Finding
from tools.ftverify.rules import TraceRule

# ops a rope output may legitimately flow through before its constraint
_ALLOWED = frozenset({
    "convert_element_type", "reshape", "broadcast_in_dim", "transpose",
    "squeeze", "expand_dims", "slice", "copy", "stop_gradient",
    "mul", "add", "sub", "concatenate",
})
_BAD = frozenset({"dot_general", "dynamic_update_slice", "scatter",
                  "scatter-add", "gather"})


def _gfind(code: str, scope: str, msg: str) -> Finding:
    return Finding(code, "global://cache_shardings", 0, 0, scope, msg)


def find_rope_concats(g) -> list:
    """Rope outputs: 2-input float concatenates tainted by cos/sin."""
    trig = [v for e in g.eqns_by_prim("cos", "sin") for v in e.outvars]
    if not trig:
        return []
    tainted = g.forward_taint(trig)
    return [e for e in g.eqns_by_prim("concatenate")
            if len(e.invars) == 2 and g.is_float(e.outvars[0])
            and all(g.find(v) in tainted for v in e.invars)]


def check_rope_constraints(g, finding) -> list:
    out = []
    for e in find_rope_concats(g):
        seen: set[int] = set()
        work = [(e.outvars[0], 0)]
        guarded, culprit = True, None
        while work:
            v, d = work.pop()
            v = g.find(v)
            if v in seen or d > 12:
                continue
            seen.add(v)
            for ce, _ in g.consumers(v):
                if ce.prim == "sharding_constraint":
                    continue                    # this path is covered
                if ce.prim in _BAD:
                    guarded, culprit = False, ce
                    break
                if ce.prim in _ALLOWED:
                    for ov in ce.outvars:
                        work.append((ov, d + 1))
            if not guarded:
                break
        if not guarded:
            out.append(finding(
                "post-rope",
                f"rope output (concat eqn{e.idx}@{'/'.join(e.path) or '<top>'}"
                f") reaches '{culprit.prim}' (eqn{culprit.idx}) without a "
                f"sharding_constraint — the attention contraction inherits "
                f"whatever sharding propagates into it, a partition-variant "
                f"float accumulation; re-constrain q/k right after rope"))
    return out


def check_paged_pool_specs(finding) -> list:
    """Drive cache_shardings over a representative paged + dense layout."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from repro.parallel.sharding import cache_shardings

    sds = jax.ShapeDtypeStruct
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    tree = {
        "l0": {"attn": {
            "k": sds((16, 8, 2 * 4), jnp.bfloat16),  # pool (P, bs, KH*Dh)
            "v": sds((16, 8, 2 * 4), jnp.bfloat16),
            "bt": sds((4, 2), jnp.int32),            # per-slot block table
        }},
        "l1": {"attn": {                             # dense (B, C, KH, Dh)
            "k": sds((4, 32, 2, 4), jnp.bfloat16),
            "v": sds((4, 32, 2, 4), jnp.bfloat16),
            "pos": sds((4,), jnp.int32),
        }},
    }
    sh = cache_shardings(tree, mesh, kv_heads=2)
    out = []
    for nm in ("k", "v"):
        spec = sh["l0"]["attn"][nm].spec
        if spec[0] is not None or spec[1] is not None:
            out.append(finding(
                f"paged-pool/{nm}",
                f"cache_shardings shards the paged {nm} pool dims as "
                f"{spec} — block tables hold global block ids, so the pool "
                f"and block dims must stay DP-replicated or every lookup "
                f"reads another shard's rows"))
        if spec[-1] != "model":
            out.append(finding(
                f"paged-pool/{nm}",
                f"paged {nm} pool row (kv heads) dim is {spec[-1]!r}, expected "
                f"'model' — the pool would be fully replicated over TP"))
        bt = sh["l0"]["attn"]["bt"].spec
        if bt and bt[0] not in (("data",), "data", None):
            out.append(finding(
                "paged-pool/bt",
                f"block table shards as {bt} — it is per-slot state and "
                f"must follow the batch (DP) layout"))
    dense = sh["l1"]["attn"]["k"].spec
    if dense[0] is None:
        out.append(finding(
            "dense-cache",
            f"dense cache k shards as {dense} — batch dim should shard "
            f"over the DP axes"))
    return out


class ShardingCoverageRule(TraceRule):
    code = "FTV106"
    name = "sharding-constraint-coverage"
    invariant = ("rope outputs are re-constrained before any contraction or "
                 "cache write; paged KV pools stay DP-replicated with kv "
                 "heads on 'model'")
    tags = frozenset({"mesh"})

    def check_global(self, env):
        def finding(scope, msg):
            return _gfind(self.code, scope, msg)
        return check_paged_pool_specs(finding)

    def check_target(self, ctx):
        g = ctx.graph
        if g is None:
            return []

        def finding(scope, msg):
            return ctx.finding(self.code, scope, msg)

        return check_rope_constraints(g, finding)


RULE = ShardingCoverageRule()
