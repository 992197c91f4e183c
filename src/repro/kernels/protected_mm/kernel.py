"""Pallas TPU kernel: the FlexHyCA PE array as one fused op.

int8 x int8 MXU matmul -> 24-bit saturating accumulate -> Q_scale-constrained
8-bit window -> soft-error injection with selective protection:

  * ordinary channels: 2-D-array result, top-NB_TH bits TMR'd
  * important channels (mask input): DPPU recompute (independent fault draw),
    top-IB_TH bits TMR'd, overrides the array result

This is the TPU-native rendering of the paper's architecture+circuit layers:
the "DPPU" recompute costs one extra fault-draw + select inside the tile that
is already VMEM-resident, instead of a second pass over HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

ACC_BITS = 24
OUT_BITS = 8


def _flip(ux, rnd_ref, prot, thresh, bits):
    flips = jnp.zeros_like(ux)
    for b in range(bits):
        flip = rnd_ref[b] < thresh
        unprot = b < (bits - prot)
        flips = flips | jnp.where(flip & unprot, 1 << b, 0)
    return ux ^ flips


def _kernel(x_ref, w_ref, rnd_o_ref, rnd_i_ref, imp_ref, o_ref, acc_ref, *,
            t: int, ber: float, ib: int, nb: int, bits: int, nk: int,
            acc_bits: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == nk - 1)
    def _finish():
        lo = -(1 << (acc_bits - 1))
        hi = (1 << (acc_bits - 1)) - 1
        acc = jnp.clip(acc_ref[...], lo, hi)
        half = (1 << (t - 1)) if t > 0 else 0
        qmax = (1 << (OUT_BITS - 1)) - 1
        yq = jnp.clip((acc + half) >> t, -qmax - 1, qmax)

        thresh = jnp.uint32(min(int(ber * (1 << 32)), (1 << 32) - 1))
        mask_all = (1 << bits) - 1
        ux = yq & mask_all
        y_ord = _flip(ux, rnd_o_ref, jnp.int32(nb), thresh, bits)
        y_imp = _flip(ux, rnd_i_ref, jnp.int32(ib), thresh, bits)
        uy = jnp.where(imp_ref[...] != 0, y_imp, y_ord)
        sign = 1 << (bits - 1)
        sy = jnp.where((uy & sign) != 0, uy - (1 << bits), uy)
        o_ref[...] = sy.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=(
    "t", "ber", "ib", "nb", "bits", "bm", "bn", "bk", "acc_bits",
    "interpret"))
def protected_mm(xq, wq, rnd_ord, rnd_imp, imp_mask, *, t: int, ber: float,
                 ib: int = 2, nb: int = 1, bits: int = 8,
                 bm: int = 128, bn: int = 128, bk: int = 128,
                 acc_bits: int = ACC_BITS, interpret: bool | None = None):
    """xq (M,K) int8; wq (K,N) int8; rnd_* (bits,M,N) uint32;
    imp_mask (N,) int32 -> (M,N) int8."""
    M, K = xq.shape
    _, N = wq.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, t=t, ber=ber, ib=ib, nb=nb, bits=bits,
                          nk=nk, acc_bits=acc_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bits, bm, bn), lambda i, j, k: (0, i, j)),
            pl.BlockSpec((bits, bm, bn), lambda i, j, k: (0, i, j)),
            pl.BlockSpec((1, bn), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(xq, wq, rnd_ord, rnd_imp, imp_mask.reshape(1, N))
