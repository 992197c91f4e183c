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

**Under a tensor-parallel mesh** (``tp``: a mesh whose ``model`` axis holds
more than one device, as the scheduler serves under) the contract covers
the sharded call too: the tokens equal the one-device run's bit for bit.
The kernel cannot be partitioned by GSPMD, so only the kernel and its
exchange run in a ``shard_map`` over the mesh; quantization and the fault
draws stay outside, where GSPMD partitions them.  The flip words are drawn
under the projection's own sharding (columns over ``model`` for a
column-parallel weight, replicated for a row-parallel one), so that with
``jax_threefry_partitionable`` each chip draws exactly its slice of the
one-device draw, keyed by global row and column.  Inside:

  * column-parallel (N split: ``wq``/``wk``/``wv``/``wi``/``wg``): each
    chip accumulates its columns; the per-row absmax is a ``pmax`` over
    ``model``, from which every chip finishes its own columns;
  * row-parallel (K split: ``wo``): each chip's unsaturated partial int32
    sum (a 4736-deep partial of int8 products stays far inside int32) is a
    ``psum`` over ``model``, then saturated and finished on the whole row,
    replicated.

Both finish through ``kernel.epilogue``, the function the kernel's own
epilogue calls.  The ``pmax``/``psum`` sit under the named scope
``exchange``.  Serving's variants take this path: per-row or global keys,
DPPU ``none`` or ``reuse``.  Under such a mesh every other call (weight
faults, a DPPU recompute from weights, a weight that is not divided or
whose divided dimension does not split evenly, a VMEM plan the kernel
refuses) runs the bitwise-equal reference datapath, which GSPMD
partitions: outside a ``shard_map`` the kernel does not lower for more
than one device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import faults
from repro.core import quantization as Q
from repro.kernels.fused_decode.kernel import (VMEM_LIMIT, absmax, epilogue,
                                               fused_decode, saturate,
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


def _shardable(tp, k: int, n: int, policy, dppu_src: str) -> bool:
    """Whether a call under ``tp`` runs the kernel in a ``shard_map``: a
    weight divided evenly over the axis, no weight faults, and a DPPU
    source that needs no second accumulator."""
    mesh, axis, split = tp
    dim = {"column": n, "row": k}.get(split)
    return (dim is not None and dim % mesh.shape[axis] == 0
            and not policy.weight_faults and dppu_src in ("none", "reuse"))


@partial(jax.jit, static_argnames=("layer_protected", "interpret", "tp"))
def fused_protect_linear(key: jax.Array, x: jax.Array, w: jax.Array,
                         policy, important: jax.Array | None = None, *,
                         layer_protected: bool = True, dyn=None,
                         interpret: bool | None = None,
                         tp=None) -> jax.Array:
    """Fault-tolerant linear on the fused kernel: float in/out.

    Accepts everything ``protect_linear`` does — a single key or an (M, 2)
    per-row key batch, all registry policies (weight faults included, also
    per-row), ``important`` masks, ``layer_protected`` and traced ``dyn``
    overrides — and matches the reference backend bit-for-bit.  A call the
    kernel's VMEM plan cannot hold (``kernel_fits``) runs the reference.

    ``tp``: ``(mesh, axis, split)`` under a mesh whose ``axis`` holds more
    than one device, ``split`` how the weight is divided over it: "column"
    (its N), "row" (its K) or None; see the module docstring.
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
    sharded = tp is not None and _shardable(tp, w.shape[0], n, policy,
                                            dppu_src)
    if (not kernel_fits(m, n, dppu_src=dppu_src, perrow_wf=perrow_wf)
            or (tp is not None and not sharded)):
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
    if sharded:
        oflips = _like_output(oflips, tp)

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
        if sharded:
            dflips = _like_output(dflips, tp)
            imp_arr = _like_output(imp_arr.reshape(1, n), tp)

    if sharded:
        yq, t = _sharded(xq.astype(jnp.int8), wq.astype(jnp.int8), oflips,
                         q_scale, dflips, imp_arr, per_row=per_row, tp=tp,
                         interpret=interpret)
    else:
        yq, t = _one_device(xq, wq_k, oflips, q_scale, wq_clean, wflips,
                            dflips, imp_arr, per_row=per_row,
                            dppu_src=dppu_src, perrow_wf=perrow_wf,
                            interpret=interpret)
    scale = sx * sw * (2.0 ** t.astype(jnp.float32))
    y = yq.astype(jnp.float32) * scale
    return y.reshape(*orig_shape[:-1], n)


def _one_device(xq, wq_k, oflips, q_scale, wq_clean, wflips, dflips,
                imp_arr, *, per_row, dppu_src, perrow_wf, interpret):
    """The kernel on whole operands: (M, N) int8-valued int32 outputs and
    ``t``."""
    m, n = xq.shape[0], wq_k.shape[1]
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
    return yq, (tcol[:m] if per_row else tcol[0, 0])


def _specs(tp):
    """(x, w, output) partition specs of a call divided as ``tp`` says."""
    _, axis, split = tp
    if split == "column":
        return P(), P(None, axis), P(None, axis)
    return P(None, axis), P(axis, None), P()


def _like_output(a, tp):
    """``a``, an (M, N) operand of the epilogue, held as the output is: its
    columns over the axis for a column-parallel call, else replicated."""
    return jax.lax.with_sharding_constraint(
        a, NamedSharding(tp[0], _specs(tp)[2]))


def _sharded(xq, wq, oflips, q_scale, dflips, imp, *, per_row, tp,
             interpret):
    """The kernel and its exchange in a ``shard_map`` over the mesh:
    (M, N) int8-valued int32 outputs, divided as ``_specs`` says, and
    ``t``."""
    mesh, axis, split = tp
    x_spec, w_spec, out_spec = _specs(tp)
    qs = jnp.asarray(q_scale, jnp.int32).reshape(1, 1)
    args, specs = [xq, wq, oflips, qs], [x_spec, w_spec, out_spec, P()]
    if dflips is not None:
        args += [dflips, imp]
        specs += [out_spec, out_spec]

    def body(x, w, of, q, *dppu):
        m, n = x.shape[0], w.shape[1]
        x8, w8 = _pad_to(x, (8, 128)), _pad_to(w, (128, 128))
        if split == "column":
            acc, amax = fused_decode(x8, w8, per_row=per_row, mode="acc",
                                     interpret=interpret)
            acc = acc[:m, :n]
            with jax.named_scope("exchange"):
                amax = jax.lax.pmax(amax[:m], axis)
            if not per_row:
                amax = amax[0, 0]
        else:
            part = fused_decode(x8, w8, per_row=per_row, mode="partial",
                                interpret=interpret)
            with jax.named_scope("exchange"):
                acc = jax.lax.psum(part[:m, :n], axis)
            acc = saturate(acc)
            amax = absmax(acc, per_row)
        kw = dict(dflips=dppu[0], imp=dppu[1]) if dppu else {}
        return epilogue(acc, amax, q, of, **kw)

    # unchecked: a Pallas call's outputs carry no record of the axes they
    # vary over; ``t`` (and a row-parallel output) are whole after the
    # exchange, the same on every chip
    y, t = jax.shard_map(body, mesh=mesh, in_specs=tuple(specs),
                         out_specs=(out_spec, P()), check_vma=False)(*args)
    return y.astype(jnp.int32), t
