"""``fused_protect_linear`` — the full ``protect_linear`` semantics on the
fused decode kernel (``backend="fused"``).

The split of responsibilities that keeps this bit-exact with the reference
backend:

  * *Outside the kernel* (here): quantization (the only float↔int
    boundaries), the policy's key schedule — identical splits and draw
    shapes to ``ft.api._protect_reference`` — and the packing of every
    fault draw into int32 flip words (``repro.core.faults.flip_word``).
  * *Inside the kernel*: pure integer math on those operands — matmul,
    saturate, in-kernel truncation-LSB selection, XOR, select.

Because the draws are identical and the integer datapath is deterministic,
``fused_protect_linear(key, ...) == _protect_reference(key, ...)`` holds
bitwise for every registry policy, global or per-row keys, with or without
weight faults, and with traced ``dyn`` knob overrides.  That contract is
also what lets a call whose static shapes exceed the kernel's VMEM plan
(``kernel.vmem_bytes`` over ``kernel.VMEM_LIMIT``: prefill rows, per-row
weight faults at model widths) run the reference datapath instead.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import faults
from repro.core import quantization as Q
from repro.kernels.fused_decode.kernel import (VMEM_LIMIT, fused_decode,
                                               vmem_bytes)


def _pad_to(a: jax.Array, mults: tuple[int, ...]) -> jax.Array:
    pads = [(0, -s % m) for s, m in zip(a.shape, mults)]
    if any(p for _, p in pads):
        a = jnp.pad(a, pads)
    return a


def _up(v: int, mult: int) -> int:
    return -(-v // mult) * mult


def kernel_fits(m: int, n: int, *, dppu_src: str = "none",
                perrow_wf: bool = False) -> bool:
    """Whether a call with m rows and n output columns runs the kernel: its
    VMEM plan at the padded shape stays within ``VMEM_LIMIT``."""
    return vmem_bytes(_up(m, 8), _up(n, 128), dppu_src=dppu_src,
                      perrow_wf=perrow_wf) <= VMEM_LIMIT


@partial(jax.jit, static_argnames=("layer_protected", "interpret"))
def fused_protect_linear(key: jax.Array, x: jax.Array, w: jax.Array,
                         policy, important: jax.Array | None = None, *,
                         layer_protected: bool = True, dyn=None,
                         interpret: bool | None = None) -> jax.Array:
    """Fault-tolerant linear on the fused kernel: float in/out.

    Accepts everything ``protect_linear`` does — a single key or an (M, 2)
    per-row key batch, all registry policies (weight faults included, also
    per-row), ``important`` masks, ``layer_protected`` and traced ``dyn``
    overrides — and matches the reference backend bit-for-bit.  A call the
    kernel's VMEM plan cannot hold (``kernel_fits``) runs the reference.
    """
    orig_shape = x.shape
    x2 = x.reshape(-1, orig_shape[-1])
    m, n = x2.shape[0], w.shape[1]
    per_row = getattr(key, "ndim", 1) == 2
    perrow_wf = policy.weight_faults and per_row
    if policy.arch.recompute and important is not None:
        dppu_src = ("w" if perrow_wf          # wq operand is clean
                    else "wcl" if policy.weight_faults  # wq pre-faulted
                    else "reuse")             # no weight faults: acc reused
    else:
        dppu_src = "none"
    if not kernel_fits(m, n, dppu_src=dppu_src, perrow_wf=perrow_wf):
        from repro.ft.api import _protect_reference
        return _protect_reference(key, x, w, policy, important,
                                  layer_protected, dyn)
    if per_row:
        ks = jax.vmap(lambda k: jax.random.split(k, 3))(key)   # (M, 3, 2)
        kw, ka, kd = ks[:, 0], ks[:, 1], ks[:, 2]
    else:
        kw, ka, kd = jax.random.split(key, 3)
    alg, arch, circ = policy.algorithm, policy.arch, policy.circuit
    dyn = dyn or {}
    ib_th = dyn.get("ib_th", circ.ib_th)
    nb_th = dyn.get("nb_th", circ.nb_th)
    q_scale = dyn.get("q_scale", alg.q_scale)

    xq, sx = Q.quantize(x2, axis=1 if per_row else None)
    wq, sw = Q.quantize(w)

    # weight-fault flip words — same draws as inject_weight_faults
    wq_k, wq_clean, wflips = wq, None, None
    if policy.weight_faults:
        if per_row:
            wflips = jax.vmap(lambda k: faults.flip_word(
                k, wq.shape, policy.ber, Q.OUT_BITS))(kw)      # (M, K, N)
        else:
            wq_k = faults.inject_weight_faults(kw, wq, policy.ber)
            wq_clean = wq

    # output flip words — protection folded into the draw's residual rates
    imp = jnp.zeros((n,), bool) if important is None else important
    protect = jnp.where(imp, ib_th, nb_th).astype(jnp.int32)
    if arch.whole_layer_tmr and layer_protected:
        protect = jnp.full((n,), Q.OUT_BITS, jnp.int32)
    pmask = faults.protect_mask(protect, Q.OUT_BITS)
    if per_row:
        oflips = jax.vmap(lambda k: faults.flip_word(
            k, (n,), policy.ber, Q.OUT_BITS, pmask))(ka)
    else:
        oflips = faults.flip_word(ka, (m, n), policy.ber, Q.OUT_BITS, pmask)

    # DPPU recompute flip words
    dflips, imp_arr = None, None
    if dppu_src != "none":
        dmask = faults.protect_mask(
            jnp.broadcast_to(jnp.asarray(ib_th, jnp.int32), (n,)), Q.OUT_BITS)
        if per_row:
            dflips = jax.vmap(lambda k: faults.flip_word(
                k, (n,), policy.ber, Q.OUT_BITS, dmask))(kd)
        else:
            dflips = faults.flip_word(kd, (m, n), policy.ber, Q.OUT_BITS,
                                      dmask)
        imp_arr = important.astype(jnp.int32)

    # tile-align (zero pads are exact for the integer datapath; padded rows
    # have absmax 0 so they never move a per-row or global t)
    xq8 = _pad_to(xq.astype(jnp.int8), (8, 128))
    wq8 = _pad_to(wq_k.astype(jnp.int8), (128, 128))
    mp, np_ = xq8.shape[0], wq8.shape[1]
    kw_args = dict(per_row=per_row, dppu_src=dppu_src, perrow_wf=perrow_wf,
                   interpret=interpret)
    if dppu_src == "wcl":
        kw_args["wq_clean"] = _pad_to(wq_clean.astype(jnp.int8), (128, 128))
    if perrow_wf:
        kw_args["wflips"] = _pad_to(wflips, (8, 128, 128))
    if dppu_src != "none":
        kw_args["dflips"] = _pad_to(dflips, (8, 128))
        kw_args["imp"] = _pad_to(imp_arr, (128,)).reshape(1, np_)
    qs = jnp.asarray(q_scale, jnp.int32).reshape(1, 1)

    yq8, tcol = fused_decode(xq8, wq8, _pad_to(oflips, (8, 128)), qs,
                             **kw_args)
    yq = yq8[:m, :n].astype(jnp.int32)
    t = tcol[:m] if per_row else tcol[0, 0]
    scale = sx * sw * (2.0 ** t.astype(jnp.float32))
    y = yq.astype(jnp.float32) * scale
    return y.reshape(*orig_shape[:-1], n)
