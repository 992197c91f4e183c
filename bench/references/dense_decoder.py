"""Plain float32 reference of a dense decoder: GQA attention (global or
sliding window), rotary positions, optional QKV bias, gated SiLU MLP,
RMSNorm, tied or untied LM head.  Llama/Mistral/Qwen2 as published.

Straight ``jax.numpy`` at ``Precision.HIGHEST``, one layer at a time, with
no cache, no batching and no kernel.  Several sequences are packed into one
row with a segment mask, so a check runs one compiled layer.  It imports
nothing of the program: weights are drawn again from the seed leaf by leaf
(``bench.weights.layer_leaf``), in the dtype the model serves them in, and
upcast to float32.

Layout notes (the program stores these, the published model does not):
a norm's weight is held as an offset, the scale is ``1 + w``; projection
matrices are (in, out).

``quant`` lowers the precision of every projection and of the LM head, for
the control: both operands are rounded to ``quant`` bits, symmetric, the
activations per row and the weights per output column, and multiplied in
float32.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import NO_LAYER, layer_leaf

HI = jax.lax.Precision.HIGHEST
NEG = -1e30


def _fq(x, bits, axis):
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def _mm(x, w, quant):
    if quant:
        x, w = _fq(x, quant, -1), _fq(w, quant, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq            # (T, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("m", "quant", "qblock"))
def _layer(x, seg, pos, w, *, m, quant, qblock=512):
    m = dict(m)
    T = x.shape[0]
    H, KH, Dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    h = _rms(x, w["ln1"], m["norm_eps"])
    q = _mm(h, w["attn/wq"], quant)
    k = _mm(h, w["attn/wk"], quant)
    v = _mm(h, w["attn/wv"], quant)
    if m.get("qkv_bias"):
        q, k, v = q + w["attn/bq"], k + w["attn/bk"], v + w["attn/bv"]
    q = _rope(q.reshape(T, H, Dh), pos, m["rope_theta"]) * Dh ** -0.5
    k = _rope(k.reshape(T, KH, Dh), pos, m["rope_theta"])
    v = v.reshape(T, KH, Dh)
    g = H // KH
    window = m.get("window", 0) if "L" in m["block_pattern"] else 0

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qblock, qblock)
        si = jax.lax.dynamic_slice_in_dim(seg, i * qblock, qblock)
        pi = jax.lax.dynamic_slice_in_dim(pos, i * qblock, qblock)
        s = jnp.einsum("qkgd,tkd->kgqt", qi.reshape(qblock, KH, g, Dh), k,
                       precision=HI)
        ok = (si[:, None] == seg[None, :]) & (pi[:, None] >= pos[None, :])
        if window:
            ok &= pi[:, None] - pos[None, :] < window
        p = jax.nn.softmax(jnp.where(ok, s, NEG), axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p, v, precision=HI)
        return o.reshape(qblock, H * Dh)

    o = jax.lax.map(block, jnp.arange(T // qblock)).reshape(T, H * Dh)
    x = x + _mm(o, w["attn/wo"], quant)
    h = _rms(x, w["ln2"], m["norm_eps"])
    f = jax.nn.silu(_mm(h, w["ffn/wi"], quant)) * _mm(h, w["ffn/wg"], quant)
    return x + _mm(f, w["ffn/wo"], quant)


@partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, rows, norm, table, *, eps, quant):
    h = _rms(x[rows], norm, eps)
    return _mm(h, table.T, quant)


def layer_shapes(m: dict) -> dict:
    D, H, KH, Dh, F = (m["d_model"], m["n_heads"], m["n_kv_heads"],
                       m["d_head"], m["d_ff"])
    s = {"ln1": (D,), "ln2": (D,), "attn/wq": (D, H * Dh),
         "attn/wk": (D, KH * Dh), "attn/wv": (D, KH * Dh),
         "attn/wo": (H * Dh, D), "ffn/wi": (D, F), "ffn/wg": (D, F),
         "ffn/wo": (F, D)}
    if m.get("qkv_bias"):
        s.update({"attn/bq": (H * Dh,), "attn/bk": (KH * Dh,),
                  "attn/bv": (KH * Dh,)})
    return s


def _dtype(name, param_dtype):
    return jnp.float32 if name.endswith(("ln1", "ln2", "norm")) else param_dtype


def _leaf(seed, name, layer, shape, param_dtype):
    return layer_leaf(seed, name, layer, shape,
                      _dtype(name, param_dtype)).astype(jnp.float32)


def logits(conf: dict, seed: int, seqs: list, score: list, quant: int = 0,
           qblock: int = 512):
    """Float32 logits of the reference at the scored positions.

    seqs: token-id lists, one per sequence.  score: for each sequence the
    positions whose next-token logits are wanted.  Returns a list of
    (len(score[i]), vocab) float32 numpy arrays.
    """
    m = dict(conf["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    mkey = tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items() if k != "name"))
    pdt = jnp.dtype(conf["run"]["param_dtype"])
    total = sum(len(s) for s in seqs)
    T = -(-total // 1024) * 1024
    tok = np.zeros(T, np.int32)
    seg = np.full(T, -1, np.int32)
    pos = np.zeros(T, np.int32)
    rows, at = [], 0
    for i, s in enumerate(seqs):
        n = len(s)
        tok[at:at + n], seg[at:at + n], pos[at:at + n] = s, i, np.arange(n)
        rows.extend(at + p for p in score[i])
        at += n
    D, V = m["d_model"], m["vocab"]
    embed = _leaf(seed, "embed", NO_LAYER, (V, D), pdt)
    x = embed[jnp.asarray(tok)]
    if m.get("tie_embeddings", True):
        table = embed
    else:
        del embed
        table = None
    shapes = layer_shapes(m)
    segj, posj = jnp.asarray(seg), jnp.asarray(pos)
    for layer in range(m["n_layers"]):
        w = {n: _leaf(seed, n, layer, s, pdt) for n, s in shapes.items()}
        x = _layer(x, segj, posj, w, m=mkey, quant=quant,
                   qblock=qblock)
        del w
    if table is None:
        table = _leaf(seed, "unembed", NO_LAYER, (V, D), pdt)
    norm = _leaf(seed, "final_norm", NO_LAYER, (D,), pdt)
    out = np.asarray(_head(x, jnp.asarray(rows, jnp.int32), norm, table,
                           eps=m["norm_eps"], quant=quant))
    res, at = [], 0
    for sc in score:
        res.append(out[at:at + len(sc)])
        at += len(sc)
    return res
