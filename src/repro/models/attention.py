"""GQA attention: chunked online-softmax (flash-style, pure JAX so it lowers
on any backend), local/SWA windows, softcaps, rolling KV caches.

Memory discipline: never materializes an (S x S) score tensor — the kv loop
runs as a fori_loop with O(block^2) live scores, which is what lets 32k
prefill compile inside a v5e HBM budget.  Local-attention layers skip kv
blocks outside the window, so SWA costs O(S*W) not O(S^2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.common import dense_init, linear, rope, softcap, tag, ac

NEG = -1e30


def init(key, cfg, dtype):
    D, H, KH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], D, H * Dh, dtype),
        "wk": dense_init(ks[1], D, KH * Dh, dtype),
        "wv": dense_init(ks[2], D, KH * Dh, dtype),
        "wo": dense_init(ks[3], H * Dh, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), dtype)
        p["bk"] = jnp.zeros((KH * Dh,), dtype)
        p["bv"] = jnp.zeros((KH * Dh,), dtype)
    return p


def _scale(cfg) -> float:
    return cfg.attn_scale or cfg.d_head ** -0.5


def _single_block(q, k, v, *, causal, window, cap, q_off=0, k_valid=None):
    """Full-score path for short sequences (smoke tests, per-block math)."""
    B, S, KH, G, Dh = q.shape
    T = k.shape[1]
    s = jnp.einsum("bskgd,btkd->bkgst", q.astype(jnp.float32),
                   k.astype(jnp.float32))
    s = softcap(s, cap)
    pq = q_off + jnp.arange(S)[:, None]
    pk = jnp.arange(T)[None, :]
    m = jnp.ones((S, T), bool)
    if causal:
        m &= pq >= pk
    if window:
        m &= pq - pk < window
    if k_valid is not None:
        m &= k_valid[None, :]
    s = jnp.where(m[None, None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))


def chunked_attention(q, k, v, *, causal=True, window=0, cap=0.0,
                      block=512, differentiable=False):
    """q: (B,S,H,Dh); k,v: (B,T,KH,Dh) -> (B,S,H,Dh) (q assumed pre-scaled).

    Two inner-loop strategies over kv blocks:
      - inference (differentiable=False): fori_loop with *dynamic* bounds —
        skips out-of-causal-range / out-of-window blocks entirely (O(S*W) for
        SWA), but dynamic bounds are not reverse-differentiable.
      - training (differentiable=True): lax.scan over all kv blocks with
        block-level masking.  Baseline cost is the full O(S^2); the flash
        custom-VJP kernel path (see EXPERIMENTS.md §Perf) removes the waste.
    """
    B, S, H, Dh = q.shape
    T, KH = k.shape[1], k.shape[2]
    G = H // KH
    q = q.reshape(B, S, KH, G, Dh)
    if S <= block and T <= block:
        o = _single_block(q, k, v, causal=causal, window=window, cap=cap)
        return o.reshape(B, S, H, Dh).astype(v.dtype)

    assert S % block == 0 and T % block == 0, (S, T, block)
    nq, nk = S // block, T // block
    qb = jnp.moveaxis(q.reshape(B, nq, block, KH, G, Dh), 1, 0)
    kb = k.reshape(B, nk, block, KH, Dh)
    vb = v.reshape(B, nk, block, KH, Dh)
    w_blocks = -(-window // block) if window else nk  # ceil

    def per_q(_, xs):
        i, qi = xs                      # qi: (B, blk, KH, G, Dh)
        qi = qi.astype(jnp.float32)
        acc = jnp.zeros((B, KH, G, block, Dh), jnp.float32)
        m = jnp.full((B, KH, G, block), NEG, jnp.float32)
        den = jnp.zeros((B, KH, G, block), jnp.float32)

        def block_update(j, kj, vj, carry):
            acc, m, den = carry
            s = jnp.einsum("bqkgd,bvkd->bkgqv", qi, kj.astype(jnp.float32))
            s = softcap(s, cap)
            pq = i * block + jnp.arange(block)[:, None]
            pk = j * block + jnp.arange(block)[None, :]
            msk = jnp.ones((block, block), bool)
            if causal:
                msk &= pq >= pk
            if window:
                msk &= pq - pk < window
            s = jnp.where(msk[None, None, None], s, NEG)
            mj = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - mj[..., None])
            corr = jnp.exp(m - mj)
            den2 = den * corr + p.sum(-1)
            acc2 = acc * corr[..., None] + jnp.einsum(
                "bkgqv,bvkd->bkgqd", p, vj.astype(jnp.float32))
            return acc2, mj, den2

        if differentiable:
            def body(carry, xs2):
                j, kj, vj = xs2
                return block_update(j, kj, vj, carry), None
            # remat each kv block: the backward pass recomputes the (blk x
            # blk) score tile instead of saving O(S^2/blk^2) of them
            (acc, m, den), _ = jax.lax.scan(
                jax.checkpoint(body, prevent_cse=False), (acc, m, den),
                (jnp.arange(nk), jnp.moveaxis(kb, 1, 0),
                 jnp.moveaxis(vb, 1, 0)))
        else:
            def body(j, carry):
                kj = jax.lax.dynamic_index_in_dim(kb, j, 1, keepdims=False)
                vj = jax.lax.dynamic_index_in_dim(vb, j, 1, keepdims=False)
                return block_update(j, kj, vj, carry)
            hi = jnp.minimum(i + 1, nk) if causal else nk
            lo = jnp.maximum(i + 1 - w_blocks, 0) if window else 0
            acc, m, den = jax.lax.fori_loop(lo, hi, body, (acc, m, den))
        o = acc / jnp.maximum(den[..., None], 1e-30)
        return None, jnp.moveaxis(o, 3, 1)   # (B, blk, KH, G, Dh)

    _, o = jax.lax.scan(per_q, None, (jnp.arange(nq), qb))
    o = jnp.moveaxis(o, 0, 1).reshape(B, S, KH, G, Dh)
    return o.reshape(B, S, H, Dh).astype(v.dtype)


def apply(p, x, *, cfg, run, kind, positions, probe=None, ftc=None,
          name="attn", cache=None, mode="train", enc_kv=None):
    """Attention sub-layer.  Returns (out, new_cache).

    modes: train (no cache) | prefill (build cache) | decode (1-token step).
    enc_kv: (k, v) from the encoder for cross-attention (positions=None keys).
    """
    H, Dh = cfg.n_heads, cfg.d_head
    window = cfg.window if kind == "L" else 0
    cross = enc_kv is not None

    q = linear(x, p["wq"], p.get("bq"), ftc=ftc, name=f"{name}/wq")
    if cross:
        k, v = enc_kv
    else:
        k = linear(x, p["wk"], p.get("bk"), ftc=ftc, name=f"{name}/wk")
        v = linear(x, p["wv"], p.get("bv"), ftc=ftc, name=f"{name}/wv")
    # between the projections and wo: rope, the cache write, the paged
    # gather and the softmax, under one named scope
    with jax.named_scope("attention"):
        o, new_cache = _attend(q, k, v, cfg=cfg, run=run, window=window,
                               positions=positions, cross=cross, cache=cache,
                               mode=mode, dtype=x.dtype)
        o = tag(probe, f"{name}/out", o)
    y = linear(o.reshape(*x.shape[:-1], H * Dh), p["wo"], ftc=ftc,
               name=f"{name}/wo")
    return y, new_cache


def _attend(q, k, v, *, cfg, run, window, positions, cross, cache, mode,
            dtype):
    """Attention from projected q/k/v: (o (B, S, H, Dh), new cache); q is
    scaled in ``dtype``, the sub-layer input's."""
    B = q.shape[0]
    H, KH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    lead = q.shape[:-1]
    q = q.reshape(*lead, H, Dh)
    if not cross:
        k = k.reshape(*lead, KH, Dh)
        v = v.reshape(*lead, KH, Dh)
        k = rope(k, positions, cfg.rope_theta)
        # head-shard k/v like q: without this the residual stream's
        # sequence sharding propagates into the kv length dim, turning the
        # softmax p@v contraction into a partitioned float sum — a
        # reordered accumulation that is not bitwise partition-invariant
        # (the sharded-serving determinism contract, tests/
        # test_serve_sharded.py)
        k = ac(k, "dp", None, "tp", None)
        v = ac(v, "dp", None, "tp", None)
    if not cross:
        q = rope(q, positions, cfg.rope_theta)
    q = (q * _scale(cfg)).astype(dtype)
    q = ac(q, "dp", None, "tp", None)

    new_cache = cache
    if mode == "decode" and not cross and "bt" in cache:
        # paged cache: the slot's logical position maps through the block
        # table to a row of the shared block pool.  Rows whose table entry
        # is the trash block (id 0 — evicted/idle slots) write garbage
        # nobody reads; rows with real blocks own them exclusively.  Inside
        # the layer scan the pools arrive whole, stacked over layers, with
        # the layer index beside them (``layer``): the row write and the
        # block gather then address the stack in place, so no layer's pool
        # is ever copied out and back
        pool_k, pool_v, bt = cache["k"], cache["v"], cache["bt"]
        lay = (cache["layer"],) if "layer" in cache else ()
        bs = pool_k.shape[-2]
        eff_cap = bt.shape[1] * bs
        pos = positions[:, 0]                                        # (B,)
        slot = pos % window if window else jnp.minimum(pos, eff_cap - 1)
        at = (*lay, bt[jnp.arange(B), slot // bs], slot % bs)
        kp = pool_k.at[at].set(k[:, 0].reshape(B, KH * Dh))
        vp = pool_v.at[at].set(v[:, 0].reshape(B, KH * Dh))
        new_cache = {"k": kp, "v": vp, "bt": bt}
        # gather this row's blocks back into slot order and run the same
        # count-masked decode attention as the dense layout (bit-identical:
        # masked tail slots never contribute).  The pool is replicated over
        # DP (global block ids) but the gathered per-row view is batch-major
        # again — constrain it like the dense layout so attention runs
        # DP/TP-sharded
        kc = ac(kp[(*lay, bt)].reshape(B, eff_cap, KH, Dh),
                "dp", None, "tp", None)                      # (B, C, KH, Dh)
        vc = ac(vp[(*lay, bt)].reshape(B, eff_cap, KH, Dh),
                "dp", None, "tp", None)
        n_valid = jnp.minimum(pos + 1, window if window else eff_cap)
        o = _decode_attn(q, kc, vc, n_valid, cap=cfg.attn_softcap)
    elif mode == "decode" and not cross:
        # write this token into the (possibly rolling) cache.  positions may
        # differ per batch row (continuous batching: every slot serves its
        # own request), so the write is a per-row dynamic update and the
        # valid-length mask is per-row too.
        cap_len = cache["k"].shape[1]
        pos = positions[:, 0]                                        # (B,)
        slot = pos % cap_len if window else jnp.minimum(pos, cap_len - 1)
        upd = lambda c, u, s: jax.lax.dynamic_update_slice_in_dim(c, u, s, 0)
        kc = jax.vmap(upd)(cache["k"], k, slot)
        vc = jax.vmap(upd)(cache["v"], v, slot)
        new_cache = {"k": kc, "v": vc}
        n_valid = jnp.minimum(pos + 1, cap_len)                      # (B,)
        o = _decode_attn(q, kc, vc, n_valid, cap=cfg.attn_softcap)
    elif mode == "decode" and cross:
        # per-row "cn" counts (continuous batching: each slot's encoder
        # context has its own length) fall back to the full buffer length
        o = _decode_attn(q, cache["ck"], cache["cv"],
                         cache.get("cn", cache["ck"].shape[1]),
                         cap=cfg.attn_softcap)
    else:
        o = chunked_attention(q, k, v, causal=not cross, window=window,
                              cap=cfg.attn_softcap, block=run.attn_block,
                              differentiable=(mode == "train"))
        if mode == "prefill" and not cross:
            new_cache = _build_cache(k, v, window)
    return ac(o, "dp", None, "tp", None), new_cache


def _decode_attn(q, kc, vc, n_valid, cap=0.0):
    """One-token attention over a cache.  q: (B,1,H,Dh), kc: (B,C,KH,Dh).
    n_valid: scalar or per-row (B,) count of populated cache slots."""
    B, _, H, Dh = q.shape
    KH = kc.shape[2]
    G = H // KH
    qg = q.reshape(B, KH, G, Dh).astype(jnp.float32)
    s = jnp.einsum("bkgd,bckd->bkgc", qg, kc.astype(jnp.float32))
    s = softcap(s, cap)
    n_valid = jnp.reshape(n_valid, (-1, 1))           # () -> (1,1); (B,)->(B,1)
    valid = jnp.arange(kc.shape[1])[None] < n_valid
    s = jnp.where(valid[:, None, None], s, NEG)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgc,bckd->bkgd", p, vc.astype(jnp.float32))
    return o.reshape(B, 1, H, Dh).astype(vc.dtype)


def _build_cache(k, v, window):
    """Prefill cache: last `window` tokens for local layers (rolling-buffer
    layout: position p lives at slot p % window), all tokens for global."""
    S = k.shape[1]
    if window and S > window:
        k, v = k[:, -window:], v[:, -window:]
        shift = S % window
        if shift:
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
    elif window and S < window:
        pad = window - S
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return {"k": k, "v": v}


def init_paged_cache(cfg, kind, batch, cap_len, block_size, n_blocks, dtype):
    """Paged KV cache for one attention layer: a pool of `n_blocks` physical
    blocks of `block_size` token slots, plus a per-row block table mapping
    logical slots to blocks.  Block 0 is the trash block — every table entry
    starts there, and evicted slots are pointed back at it, so idle rows'
    decode writes land in memory nobody reads.  Rolling (window) layers keep
    the same slot map as the dense layout (position p at slot p % window),
    just block-indexed; their table is window-sized.

    A slot's row is lane-dense, ``KH * Dh`` wide with the heads major: a
    ``(KH, Dh)`` row would pad ``Dh`` to the TPU's 128 lanes and make the
    compiler lay the stacked pool out block-dim minor, so every layer's
    slice of it became a transposing copy."""
    window = cfg.window if kind == "L" else 0
    cap = window if window else cap_len
    width = -(-cap // block_size)                    # ceil
    shp = (n_blocks, block_size, cfg.n_kv_heads * cfg.d_head)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype),
            "bt": jnp.zeros((batch, width), jnp.int32)}


def init_cache(cfg, kind, batch, cap_len, dtype):
    # rolling caches are always window-sized: position p lives at slot
    # p % window (matching _build_cache and the decode write), so a shorter
    # capacity would break the slot mapping
    window = cfg.window if kind == "L" else 0
    C = window if window else cap_len
    shp = (batch, C, cfg.n_kv_heads, cfg.d_head)
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}
