"""Pallas TPU kernel: int8 x int8 DLA matmul with 24-bit saturating
accumulator and Q_scale-constrained 8-bit window truncation.

Tiling: (bm x bk) @ (bk x bn) MXU tiles with an int32 VMEM accumulator
scratch; K is the innermost (sequential) grid dim.  int8 operands hit the
MXU's native int8 path with int32 accumulation on real TPUs; interpret mode
executes the same program on CPU for validation.
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


def _kernel(x_ref, w_ref, o_ref, acc_ref, *, t: int, nk: int, acc_bits: int):
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
        acc = jnp.clip(acc_ref[...], lo, hi)        # saturating 24-bit acc
        half = (1 << (t - 1)) if t > 0 else 0
        r = (acc + half) >> t                        # window truncation
        qmax = (1 << (OUT_BITS - 1)) - 1
        o_ref[...] = jnp.clip(r, -qmax - 1, qmax).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("t", "bm", "bn", "bk",
                                             "acc_bits", "interpret"))
def qmatmul(xq, wq, t: int, bm: int = 128, bn: int = 128, bk: int = 128,
            acc_bits: int = ACC_BITS, interpret: bool | None = None):
    """xq: (M, K) int8; wq: (K, N) int8 -> (M, N) int8."""
    M, K = xq.shape
    _, N = wq.shape
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K)
    nk = K // bk
    grid = (M // bm, N // bn, nk)
    return pl.pallas_call(
        functools.partial(_kernel, t=t, nk=nk, acc_bits=acc_bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int8),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=resolve_interpret(interpret),
    )(xq, wq)
