"""Mixture-of-Experts FFN with partial-sum expert parallelism.

Sharding scheme (DESIGN.md §5): experts live on the TP ('model') axis; token
activations are batch-sharded over the DP axes and replicated over TP (as in
ordinary tensor parallelism).  Each (dp, tp) shard routes its local tokens,
keeps only the assignments that hit its *local* experts, computes them on
capacity-bounded buffers, and scatter-adds weighted outputs; the cross-expert
combine is a single psum over 'model' — the same all-reduce a dense TP FFN
needs, so EP adds **no extra collective**.  Dispatch is sort-based (argsort +
gather/scatter), never a (T, E, C) one-hot einsum, keeping the dispatch
working set O(T*k) instead of O(T*E*C).

Expert weights are additionally FSDP-sharded over the DP axes; the shard_map
boundary performs the per-layer FSDP all-gather.

Fault layer: the router projection is the one MoE site under the paper's
protection stack — it runs through ``common.linear`` (fault-tolerant DLA
path) *outside* the shard_map region, where routing is row-local, so
per-request fault accounting survives and the draws are partition-exact
under GSPMD (counter-based RNG).  The expert einsums stay clean: their
capacity buffers are shard-local (contents depend on the partitioning), so
buffer-addressed fault draws there could never be partition-exact — any
per-shard draws inside shard_map must use ``faults.fold_axis_index``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import activation, dense_init, linear
from repro.parallel import ctx as pctx


def init(key, cfg, dtype):
    D, m = cfg.d_model, cfg.moe
    ks = jax.random.split(key, 4)
    p = {
        "router": dense_init(ks[0], D, m.n_experts, jnp.float32),
        "wi": _expert_init(ks[1], m.n_experts, D, m.d_ff, dtype),
        "wo": _expert_init(ks[2], m.n_experts, m.d_ff, D, dtype),
    }
    if cfg.glu:
        p["wg"] = _expert_init(ks[3], m.n_experts, D, m.d_ff, dtype)
    return p


def _expert_init(key, E, d_in, d_out, dtype):
    ks = jax.random.split(key, E)
    return jnp.stack([dense_init(k, d_in, d_out, dtype) for k in ks])


def _local_moe(x, logits, wi, wg, wo, *, e0, n_experts, top_k, capacity,
               act_name, tp_axis=None):
    """Per-shard MoE over local experts [e0, e0+E_local).  x: (B, S, D);
    logits: (B, S, E) pre-computed router logits (see ``apply``)."""
    B, S, D = x.shape
    E_local = wi.shape[0]
    T = B * S
    x2 = x.reshape(T, D)
    act = activation(act_name)

    logits = logits.astype(jnp.float32).reshape(T, -1)    # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, top_k)              # (T, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    flat_e = topi.reshape(-1)                             # (T*k,)
    flat_w = topw.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(T), top_k)

    rel = flat_e - e0
    mine = (rel >= 0) & (rel < E_local)
    sort_key = jnp.where(mine, rel, E_local)
    order = jnp.argsort(sort_key, stable=True)
    srel = sort_key[order]
    pos = jnp.arange(T * top_k) - jnp.searchsorted(srel, srel, side="left")
    keep = (srel < E_local) & (pos < capacity)
    slot = jnp.where(keep, srel * capacity + pos, E_local * capacity)

    tok = flat_tok[order]
    # slot-indexed dispatch: build a (slots -> token) index table and gather
    # straight into the (E_local*C, D) buffer — never materializes the
    # (T*k, D) flat-assignment tensor (which is 8x the token activations)
    n_slots = E_local * capacity
    slot_tok = jnp.full((n_slots + 1,), T, jnp.int32).at[slot].set(
        tok.astype(jnp.int32), mode="drop")
    slot_valid = slot_tok[:n_slots] < T
    x2p = jnp.concatenate([x2, jnp.zeros((1, D), x2.dtype)], 0)
    buf = (x2p[slot_tok[:n_slots]]
           * slot_valid[:, None].astype(x2.dtype)).reshape(
               E_local, capacity, D)

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    if wg is not None:
        h = act(h) * jnp.einsum("ecd,edf->ecf", buf, wg)
    else:
        h = act(h)
    y = jnp.einsum("ecf,efd->ecd", h, wo).reshape(n_slots, D)
    y = jnp.concatenate([y, jnp.zeros((1, D), y.dtype)], 0)

    # return path: per-token (token, k) -> slot table, then k small gathers
    # accumulated sequentially (k x (T, D) instead of one (T*k, D))
    slot_of = jnp.full((T * top_k,), n_slots, jnp.int32).at[order].set(
        jnp.where(keep, slot, n_slots).astype(jnp.int32)).reshape(T, top_k)
    out = jnp.zeros((T, D), y.dtype)
    for kk in range(top_k):
        out = out + y[slot_of[:, kk]] * topw[:, kk, None].astype(y.dtype)
    if tp_axis is not None:
        out = jax.lax.psum(out, tp_axis)

    # switch-style load-balance aux loss (computed identically on every tp
    # shard from the replicated activations; returned per dp shard)
    one_hot_top1 = jax.nn.one_hot(topi[:, 0], n_experts, dtype=jnp.float32)
    frac = one_hot_top1.mean(0)
    lb = n_experts * jnp.sum(frac * probs.mean(0))
    return out.reshape(B, S, D), lb.reshape(1)


def apply(p, x, cfg, probe=None, ftc=None, name="moe"):
    """Returns (y, aux_loss_scalar)."""
    m = cfg.moe
    ctx = pctx.get_ctx()
    wg = p.get("wg")
    use_shard_map = (
        ctx is not None and m.n_experts % ctx.tp_size == 0
        and (x.shape[0] * ctx.mesh.size) >= 1 and x.shape[0] % ctx.dp_size == 0)

    # router under the fault layer, outside any shard_map: routing is
    # row-local, so per-request (B, 2) key streams apply unchanged, and the
    # draws are identical at TP=1 and TP=N (counter-based RNG).  x cast to
    # f32 keeps the clean path's router numerics (router weights are f32).
    logits = linear(x.astype(jnp.float32), p["router"], ftc=ftc,
                    name=f"{name}/router")

    if not use_shard_map:
        T = x.shape[0] * x.shape[1]
        cap = max(int(m.capacity_factor * T * m.top_k / m.n_experts), 1)
        one = dict(e0=0, n_experts=m.n_experts, top_k=m.top_k, capacity=cap,
                   act_name=cfg.act)
        if ctx is None:
            y, lb = _local_moe(x, logits, p["wi"], wg, p["wo"], **one)
        else:
            # B doesn't divide dp (e.g. a single-request prefill on a dp>1
            # mesh).  GSPMD's uneven-batch padding is NOT safe through the
            # sentinel-indexed sort/scatter dispatch — on a 2-D mesh the
            # auto-partitioned graph routes differently from the meshless
            # one — so run the whole block per-device on replicated
            # operands: bit-identical to the single-shard path by
            # construction (tests/test_serve_sharded.py, MoE scheduler arm).
            wg_arg = jnp.zeros((), x.dtype) if wg is None else wg
            y, lb = jax.shard_map(
                lambda xs, lg, wi, wg_, wo: _local_moe(
                    xs, lg, wi, None if wg is None else wg_, wo, **one),
                mesh=ctx.mesh, in_specs=(P(), P(), P(), P(), P()),
                out_specs=(P(), P()), check_vma=False)(
                    x, logits, p["wi"], wg_arg, p["wo"])
        return y, cfg.moe.aux_coef * lb.mean()

    dp_spec = ctx.resolve("dp")[0]
    tp = ctx.tp
    T_local = (x.shape[0] // ctx.dp_size) * x.shape[1]
    cap = max(int(m.capacity_factor * T_local * m.top_k / m.n_experts), 1)

    def shard_fn(xs, lg, wi, wg_, wo):
        e0 = jax.lax.axis_index(tp) * (m.n_experts // ctx.tp_size)
        return _local_moe(xs, lg, wi, wg_, wo, e0=e0, n_experts=m.n_experts,
                          top_k=m.top_k, capacity=cap, act_name=cfg.act,
                          tp_axis=tp)

    in_specs = (P(dp_spec, None, None), P(dp_spec, None, None),
                P(tp, None, None), P(tp, None, None) if wg is not None else P(),
                P(tp, None, None))
    out_specs = (P(dp_spec, None, None), P(dp_spec))
    if wg is None:
        wg_arg = jnp.zeros((), x.dtype)
    else:
        wg_arg = wg
    y, lb = jax.shard_map(
        lambda xs, lg, wi, wg_, wo: shard_fn(
            xs, lg, wi, None if wg is None else wg_, wo),
        mesh=ctx.mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False)(x, logits, p["wi"], wg_arg, p["wo"])
    return y, cfg.moe.aux_coef * lb.mean()
