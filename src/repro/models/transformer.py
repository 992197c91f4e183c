"""Decoder LM / encoder-decoder assembly over heterogeneous layer blocks.

Layers are grouped into *super-blocks* (one period of cfg.block_pattern) and
scanned with stacked parameters, so HLO size is O(1) in depth; reduced
configs set cfg.unroll for python-loop layers (needed by the importance probe
and FT instrumentation).  Modes: train | prefill | decode.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention, mlp, moe, rglru, ssm
from repro.models.common import (ac, dtype_of, embed_init, linear, rms_norm,
                                 softcap)

MIXERS = {"G": attention, "L": attention, "E": attention,
          "R": rglru, "S": ssm}


# ------------------------------------------------------------------ init ---
def init_layer(key, cfg, kind, dtype, cross=False):
    ks = jax.random.split(key, 6)
    D = cfg.d_model
    p = {"ln1": jnp.zeros((D,), jnp.float32)}
    if kind in ("G", "L", "E"):
        p["attn"] = attention.init(ks[0], cfg, dtype)
    elif kind == "R":
        p["rglru"] = rglru.init(ks[0], cfg, dtype)
    elif kind == "S":
        p["ssd"] = ssm.init(ks[0], cfg, dtype)
    if cfg.post_norm:
        p["ln1_post"] = jnp.zeros((D,), jnp.float32)
    if cross:
        p["lnx"] = jnp.zeros((D,), jnp.float32)
        p["xattn"] = attention.init(ks[1], cfg, dtype)
    if cfg.d_ff > 0 or cfg.moe is not None:
        p["ln2"] = jnp.zeros((D,), jnp.float32)
        p["ffn"] = (moe.init(ks[2], cfg, dtype) if cfg.moe is not None
                    else mlp.init(ks[2], cfg, dtype))
        if cfg.post_norm:
            p["ln2_post"] = jnp.zeros((D,), jnp.float32)
    return p


def init_params(key, cfg, run):
    dtype = dtype_of(run.param_dtype)
    ks = jax.random.split(key, 8)
    params = {
        "embed": embed_init(ks[0], cfg.vocab, cfg.d_model, dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(ks[1], cfg.vocab, cfg.d_model, dtype)
    cross = cfg.enc_dec

    if cfg.unroll:
        layers = {}
        for i, kind in enumerate(_layer_kinds(cfg)):
            layers[f"l{i}"] = init_layer(
                jax.random.fold_in(ks[2], i), cfg, kind, dtype, cross=cross)
        params["layers"] = layers
    else:
        for si, (pattern, n_rep) in enumerate(cfg.segments):
            def one_block(k, pattern=pattern):
                kb = jax.random.split(k, len(pattern))
                return {f"s{j}": init_layer(kb[j], cfg, kind, dtype,
                                            cross=cross)
                        for j, kind in enumerate(pattern)}
            params[f"seg{si}"] = jax.vmap(one_block)(
                jax.random.split(jax.random.fold_in(ks[3], si), n_rep))

    if cfg.enc_dec:
        def enc_block(k):
            return {"s0": init_layer(k, cfg, "E", dtype)}
        if cfg.unroll:
            params["enc_layers"] = {
                f"l{i}": init_layer(jax.random.fold_in(ks[5], i), cfg, "E", dtype)
                for i in range(cfg.n_enc_layers)}
        else:
            params["enc_blocks"] = jax.vmap(enc_block)(
                jax.random.split(ks[5], cfg.n_enc_layers))
        params["enc_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return params


def _layer_kinds(cfg):
    return list(cfg.block_pattern) * cfg.n_blocks + list(cfg.tail)


# ----------------------------------------------------------------- layer ---
def apply_layer(p, x, *, kind, cfg, run, mode="train", cache=None,
                positions=None, probe=None, ftc=None, name="blk",
                enc_out=None):
    """One residual layer.  Returns (x, new_cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = dict(cache) if isinstance(cache, dict) else {}

    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind in ("G", "L", "E"):
        m, c = attention.apply(
            p["attn"], h, cfg=cfg, run=run, kind=kind,
            positions=positions, probe=probe, ftc=ftc, name=f"{name}/attn",
            cache=None if cache is None else cache.get("attn"), mode=mode)
        if c is not None:
            new_cache["attn"] = c
    elif kind == "R":
        m, c = rglru.apply(p["rglru"], h, cfg=cfg, run=run,
                           positions=positions, probe=probe, ftc=ftc,
                           name=f"{name}/rglru",
                           cache=None if cache is None else cache.get("rglru"),
                           mode=mode)
        if c is not None:
            new_cache["rglru"] = c
    elif kind == "S":
        m, c = ssm.apply(p["ssd"], h, cfg=cfg, run=run, positions=positions,
                         probe=probe, ftc=ftc, name=f"{name}/ssd",
                         cache=None if cache is None else cache.get("ssd"),
                         mode=mode)
        if c is not None:
            new_cache["ssd"] = c
    if cfg.post_norm:
        m = rms_norm(m, p["ln1_post"], cfg.norm_eps)
    # SP: sub-layer outputs reduce-scatter into the sequence-sharded residual
    # domain instead of all-reducing the full activation (train/prefill only;
    # decode has seq=1)
    if mode != "decode":
        m = ac(m, "dp", "tp", None)
        x = ac(x, "dp", "tp", None)
    x = x + m

    has_cross_cache = cache is not None and "cross" in cache
    if "xattn" in p and (enc_out is not None or has_cross_cache):
        h = rms_norm(x, p["lnx"], cfg.norm_eps)
        ek = cache.get("cross") if cache else None
        if ek is None:
            ekv = _cross_kv(p["xattn"], enc_out, cfg, ftc, name)
        else:
            ekv = (ek["ck"], ek["cv"])
        xcache = {"ck": ekv[0], "cv": ekv[1]}
        if ek is not None and "cn" in ek:
            # per-row encoder valid lengths (serving slots) ride along
            xcache["cn"] = ek["cn"]
        m, _ = attention.apply(
            p["xattn"], h, cfg=cfg, run=run, kind="G", positions=positions,
            probe=probe, ftc=ftc, name=f"{name}/xattn",
            cache=xcache if mode == "decode" else None,
            mode=mode, enc_kv=ekv)
        if mode in ("prefill", "decode"):
            new_cache["cross"] = xcache
        x = x + m

    if "ffn" in p:
        h = rms_norm(x, p["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            f, a = moe.apply(p["ffn"], h, cfg, probe=probe, ftc=ftc,
                             name=f"{name}/moe")
            aux = aux + a
        else:
            f = mlp.apply(p["ffn"], h, cfg, probe=probe, ftc=ftc,
                          name=f"{name}/mlp")
        if cfg.post_norm:
            f = rms_norm(f, p["ln2_post"], cfg.norm_eps)
        if mode != "decode":
            f = ac(f, "dp", "tp", None)
        x = x + f
    return x, (new_cache if new_cache else None), aux


def _cross_kv(pa, enc_out, cfg, ftc, name):
    KH, Dh = cfg.n_kv_heads, cfg.d_head
    k = linear(enc_out, pa["wk"], pa.get("bk"), ftc=ftc, name=f"{name}/xk")
    v = linear(enc_out, pa["wv"], pa.get("bv"), ftc=ftc, name=f"{name}/xv")
    return (k.reshape(*enc_out.shape[:-1], KH, Dh),
            v.reshape(*enc_out.shape[:-1], KH, Dh))


# -------------------------------------------------------------- backbone ---
def _layer_caches(cs, i):
    """Layer ``i``'s view of scan-stacked caches.  A paged attention cache
    (``k``/``v`` pools beside a ``bt`` block table) keeps its pools whole,
    stacked ``(n_rep, n_blocks, block_size, KH*Dh)``, with ``layer`` = i
    beside them; every other leaf is sliced to its layer."""
    if "bt" in cs:
        return {"k": cs["k"], "v": cs["v"], "bt": cs["bt"][i], "layer": i}
    return {n: _layer_caches(c, i) if isinstance(c, dict) else c[i]
            for n, c in cs.items()}


def _write_layer(cs, nc, i):
    """Inverse of ``_layer_caches``: the pools layer ``i`` updated in place
    replace the stacked ones; sliced leaves are written back at ``i``.
    Decode never changes a block table, so ``bt`` is kept as it was."""
    if "bt" in cs:
        return {"k": nc["k"], "v": nc["v"], "bt": cs["bt"]}
    return {n: (_write_layer(c, nc[n], i) if isinstance(c, dict) else
                jax.lax.dynamic_update_index_in_dim(c, nc[n], i, 0))
            for n, c in cs.items()}


def backbone(params, x, *, cfg, run, mode="train", caches=None,
             positions=None, probe=None, ftc=None, enc_out=None):
    """Apply all layers.  Returns (hidden, new_caches, aux_loss_sum)."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.unroll:
        kinds = _layer_kinds(cfg)
        new_caches = {}
        for i, kind in enumerate(kinds):
            c = None if caches is None else caches.get(f"l{i}")
            x, nc, aux = apply_layer(
                params["layers"][f"l{i}"], x, kind=kind, cfg=cfg, run=run,
                mode=mode, cache=c, positions=positions, probe=probe,
                ftc=ftc, name=f"l{i}", enc_out=enc_out)
            if nc is not None:
                new_caches[f"l{i}"] = nc
            aux_total += aux
        return x, (new_caches or None), aux_total

    # scanned super-block segments
    new_caches: dict | None = None
    for si, (pattern, n_rep) in enumerate(cfg.segments):
        def sb(carry, inp, pattern=pattern):
            x, aux = carry
            # sequence-parallel residual boundary: the per-block saved
            # residual (stacked by scan for the backward pass) shards over
            # BOTH the data axes (batch) and 'model' (sequence) — 16x less
            # residual memory, and the TP all-reduce decomposes into
            # all-gather + reduce-scatter at identical wire cost (Megatron-SP)
            x = ac(x, "dp", "tp", None)
            blk_p, blk_c = inp
            new_c = {}
            for j, kind in enumerate(pattern):
                c = None if blk_c is None else blk_c.get(f"s{j}")
                x, nc, a = apply_layer(
                    blk_p[f"s{j}"], x, kind=kind, cfg=cfg, run=run, mode=mode,
                    cache=c, positions=positions, probe=probe, ftc=ftc,
                    name=f"sb{si}/s{j}", enc_out=enc_out)
                aux = aux + a
                if nc is not None:
                    new_c[f"s{j}"] = nc
            return (x, aux), (new_c if new_c else None)

        body = sb
        if run.remat == "block":
            body = jax.checkpoint(sb, prevent_cse=False)
        if caches is not None:
            # decode carries the stacked caches through the layer scan:
            # emitted as scan outputs they would be a fresh copy of the
            # whole KV cache per step, which a decode loop's while-carry
            # multiplies (~5 copies at full width — more than a 16 GB chip
            # holds).  Paged pools stay whole in the carry and attention
            # writes and gathers its layer's rows in place (see
            # _layer_caches); every other leaf — block tables, R/S state,
            # cross-attention buffers, dense KV — is a per-layer slice
            # written back into the stack
            def dec(carry, inp, sb=sb):
                x, aux, cs = carry
                blk_p, i = inp
                (x, aux), nc = sb((x, aux), (blk_p, _layer_caches(cs, i)))
                return (x, aux, _write_layer(cs, nc, i)), None

            (x, aux_total, seg_caches), _ = jax.lax.scan(
                dec, (x, aux_total, caches[f"seg{si}"]),
                (params[f"seg{si}"], jnp.arange(n_rep)))
        else:
            (x, aux_total), seg_caches = jax.lax.scan(
                lambda c, p: body(c, (p, None)), (x, aux_total),
                params[f"seg{si}"])
        if seg_caches is not None:
            new_caches = dict(new_caches or {})
            new_caches[f"seg{si}"] = seg_caches
    return x, new_caches, aux_total


def encode(params, frames, *, cfg, run, probe=None, ftc=None):
    """Encoder stack over precomputed frontend frame embeddings."""
    x = ac(frames, "dp", None, None)
    B, S, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    if cfg.unroll:
        for i in range(cfg.n_enc_layers):
            x, _, _ = apply_layer(params["enc_layers"][f"l{i}"], x, kind="E",
                                  cfg=cfg, run=run, mode="train", probe=probe,
                                  ftc=ftc, name=f"enc{i}", positions=positions)
    else:
        def sb(x, blk_p):
            x, _, _ = apply_layer(blk_p["s0"], x, kind="E", cfg=cfg, run=run,
                                  mode="train", name="enc", positions=positions)
            return x, None
        body = jax.checkpoint(sb, prevent_cse=False) if run.remat == "block" else sb
        x, _ = jax.lax.scan(body, x, params["enc_blocks"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


# ------------------------------------------------------------- embedding ---
def embed_tokens(params, cfg, tokens):
    e = jnp.take(params["embed"], tokens, axis=0)
    if cfg.scale_embeds:
        e = e * jnp.asarray(cfg.d_model ** 0.5, e.dtype)
    return ac(e, "dp", None, None)


def assemble_inputs(params, cfg, batch):
    """Family-specific input embedding.  Returns (x, labels, mask, enc_out)
    where labels/mask are aligned to predict labels[t] from hidden[t]."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    enc_out = None
    if cfg.frontend == "vision":
        patches = batch["patch_embeds"].astype(x.dtype)
        if cfg.scale_embeds:
            patches = patches * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        P = patches.shape[1]
        labels = jnp.concatenate(
            [jnp.full((B, P - 1), -1, jnp.int32), tokens], axis=1)
        mask = labels >= 0
    else:
        labels = tokens[:, 1:]
        mask = jnp.ones_like(labels, bool)
    if cfg.enc_dec:
        enc_out = batch["frames"].astype(x.dtype)
    return x, labels, mask, enc_out


# ------------------------------------------------------------------ loss ---
def chunked_xent(params, cfg, run, h, labels, mask):
    """Cross-entropy over vocab-sharded logits, scanned over token chunks so
    the unsharded (tokens, vocab) tensor never materializes."""
    emb = params.get("unembed", params["embed"])
    # gather the FSDP-sharded unembed ONCE outside the chunk scan: the remat
    # wrapper otherwise re-gathers it per chunk in fwd AND bwd (measured at
    # ~7x params of collective traffic on seamless — EXPERIMENTS.md §Perf)
    emb = ac(emb, "tp", None)
    B = h.shape[0]
    hs = h[:, :labels.shape[1]]
    Sm = labels.shape[1]
    C = min(run.loss_chunk, Sm)
    n = -(-Sm // C)
    pad = n * C - Sm
    if pad:
        hs = jnp.pad(hs, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    hs = jnp.moveaxis(hs.reshape(B, n, C, -1), 1, 0)
    labels = jnp.moveaxis(labels.reshape(B, n, C), 1, 0)
    mask = jnp.moveaxis(mask.reshape(B, n, C), 1, 0)

    def body(carry, xs):
        tot, cnt = carry
        hc, lc, mc = xs
        logits = jax.lax.dot_general(
            hc, emb, (((2,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # (B, C, V)
        logits = softcap(logits, cfg.logit_softcap)
        logits = ac(logits, "dp", None, "tp")
        lse = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        nll = (lse - ll) * mc
        return (tot + nll.sum(), cnt + mc.sum()), None

    # remat: recompute each chunk's logits in backward instead of saving the
    # full (tokens, vocab) tensor
    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(body, prevent_cse=False),
        (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (hs, labels, mask))
    return tot / jnp.maximum(cnt, 1.0)


def last_logits(params, cfg, h, index=None):
    """Logits at the last position, or — for right-padded (bucketed)
    prompts — at a per-row `index` (B,) of the final real token."""
    emb = params.get("unembed", params["embed"])
    hl = h[:, -1] if index is None else jnp.take_along_axis(
        h, jnp.asarray(index, jnp.int32)[:, None, None], axis=1)[:, 0]
    logits = jax.lax.dot_general(hl, emb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    return softcap(logits, cfg.logit_softcap)
