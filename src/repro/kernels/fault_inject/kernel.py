"""Pallas TPU kernel: BER bit-flip injection with per-channel bit protection.

Models the DLA substrate's soft errors on quantized neuron outputs: each of
the low `bits` bits flips with probability `ber`, except the top
`protect[col]` bits which are TMR-voted (immune; the O(ber^2) residual is
modelled at the simulation layer, see repro.core.faults.residual_ber).

Randomness arrives as uint32 planes (generated with jax.random in ops.py) so
the kernel is deterministic and bit-exactly testable against ref.py; on a
real TPU deployment the planes can be replaced by pltpu.prng_random_bits
in-kernel (not available in CPU interpret mode).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _kernel(x_ref, rnd_ref, prot_ref, o_ref, *, ber: float, bits: int):
    thresh = jnp.uint32(min(int(ber * (1 << 32)), (1 << 32) - 1))
    mask_all = (1 << bits) - 1
    ux = x_ref[...] & mask_all
    prot = prot_ref[...]                       # (1, bn) int32
    flips = jnp.zeros_like(ux)
    for b in range(bits):
        flip = rnd_ref[b] < thresh
        unprot = b < (bits - prot)             # broadcast (1, bn)
        flips = flips | jnp.where(flip & unprot, 1 << b, 0)
    ux = ux ^ flips
    sign = 1 << (bits - 1)
    o_ref[...] = jnp.where((ux & sign) != 0, ux - (1 << bits), ux)


@functools.partial(jax.jit, static_argnames=("ber", "bits", "bm", "bn",
                                             "interpret"))
def fault_inject(x, rnd, protect, ber: float, bits: int = 8,
                 bm: int = 256, bn: int = 128, interpret: bool | None = None):
    """x: (M,N) int32; rnd: (bits,M,N) uint32; protect: (N,) int32."""
    M, N = x.shape
    bm, bn = min(bm, M), min(bn, N)
    assert M % bm == 0 and N % bn == 0
    grid = (M // bm, N // bn)
    return pl.pallas_call(
        functools.partial(_kernel, ber=ber, bits=bits),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bits, bm, bn), lambda i, j: (0, i, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(x, rnd, protect.reshape(1, N))
