"""Pallas TPU kernel: fused inject→protect→qmatmul for the decode hot path.

One pass over the integer datapath replaces the three-dispatch composition
(`kernels/fault_inject` + `kernels/protected_mm` + `kernels/qmatmul`):

  int8 MXU matmul → int32 accumulate over K (sequential grid) → 24-bit
  saturate → truncation LSB ``t`` derived *in-kernel* from the accumulator's
  integer bit-length (Q_scale-constrained, per-row or global) → 8-bit
  round-to-nearest window → XOR pre-drawn packed flip words → sign-extend
  [→ DPPU recompute on a second clean accumulator, select important] → int8.

Differences from ``protected_mm`` that make this the serving kernel:

  * Fault randomness arrives as *packed* flip words (one int32 carries all 8
    bit planes, protection already folded into the draw) instead of 8 uint32
    planes per stream — 8x less HBM traffic per fault stream, and the kernel
    epilogue is a single XOR instead of per-bit threshold compares.
  * ``t`` is computed from data inside the kernel (integer popcount over
    threshold compares), so the kernel works under jit/scan with traced
    operands — no statically calibrated ``t``, no per-layer recompiles.
  * ``q_scale`` is an SMEM-style scalar operand, so traced dyn-leaf
    overrides (the batched-DSE path) ride straight into the kernel.
  * Optional per-row weight flip words give each batch row its own faulty
    weight view — the capability that lifts the scheduler's
    ``weight_faults=False`` restriction.

Decode-shaped by design: the whole (M, N) accumulator lives in VMEM and the
grid is sequential over K only, which assumes small M (a decode batch) and
moderate N.  ``vmem_bytes`` is the kernel's VMEM plan for a padded shape;
``fused_protect_linear`` sends a call whose plan exceeds ``VMEM_LIMIT`` to
the bitwise-equal reference datapath instead.  At h2o-danube widths the
plan admits up to M=96 rows at N=6912 (272 at N=2560, 1080 at N=640), so
decode rows run the kernel, while 512-row prefill of the wide projections
and their per-row weight faults (an (M, bk, N) int32 flip block) run the
reference.  ``tests/test_tpu_compile.py`` checks the plan against the v5e
compiler.
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

# The compiler's default scoped-VMEM limit on v5e ("limit 16.00M" in its
# refusals); a kernel whose plan exceeds it does not compile.
VMEM_LIMIT = 16 * 2**20


def vmem_bytes(m: int, n: int, *, dppu_src: str = "none",
               perrow_wf: bool = False, bk: int = 128) -> int:
    """VMEM the kernel needs for tile-aligned M rows and N columns; K
    streams through in ``bk``-row tiles and does not enter.

    Blocks are double-buffered: x, w (and the clean w), the (M, N) int32
    output flip words (and the DPPU flip words and mask), the int8 output
    and the lane-padded t column.  The (M, N) int32 accumulator scratch is
    single (two with a recompute from weights), the epilogue holds two
    (M, N) int32 temporaries, and per-row weight faults add their
    (M, bk, N) int32 flip block and faulty-weight product.
    """
    mn = m * n * 4
    blocks = m * bk + bk * n + mn + m * n + m * 128 * 4
    if dppu_src == "wcl":
        blocks += bk * n
    if dppu_src != "none":
        blocks += mn + 8 * n * 4
    wf = m * bk * n * 4 if perrow_wf else 0
    scratch = mn * (2 if dppu_src in ("w", "wcl") else 1)
    return 2 * (blocks + wf) + scratch + 2 * mn + wf


def _sign_extend(u, bits):
    sign = 1 << (bits - 1)
    return jnp.where((u & sign) != 0, u - (1 << bits), u)


def _trunc(acc, t, out_bits):
    half = jnp.where(t > 0, 1 << jnp.maximum(t - 1, 0), 0)
    qmax = (1 << (out_bits - 1)) - 1
    return jnp.clip((acc + half) >> t, -qmax - 1, qmax)


def _kernel(*refs, nk: int, per_row: bool, dppu_src: str, perrow_wf: bool,
            bits: int, acc_bits: int, out_bits: int):
    it = iter(refs)
    x_ref = next(it)
    w_ref = next(it)
    wcl_ref = next(it) if dppu_src == "wcl" else None
    wflips_ref = next(it) if perrow_wf else None
    oflip_ref = next(it)
    dflip_ref = next(it) if dppu_src != "none" else None
    imp_ref = next(it) if dppu_src != "none" else None
    qs_ref = next(it)
    o_ref = next(it)
    t_ref = next(it)
    acc_ref = next(it)
    accd_ref = next(it) if dppu_src in ("w", "wcl") else None

    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if accd_ref is not None:
            accd_ref[...] = jnp.zeros_like(accd_ref)

    if perrow_wf:
        # Row-private faulty weights: XOR the packed flip word into the
        # shared weight tile, sign-extend, and accumulate on the VPU
        # (decode M is small, so the broadcast product is cheap).
        w = w_ref[...].astype(jnp.int32)
        wf = _sign_extend((w[None, :, :] & ((1 << bits) - 1))
                          ^ wflips_ref[...], bits)
        x = x_ref[...].astype(jnp.int32)
        acc_ref[...] += jnp.sum(x[:, :, None] * wf, axis=1)
    else:
        acc_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    if dppu_src == "w":
        accd_ref[...] += jax.lax.dot_general(
            x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    elif dppu_src == "wcl":
        accd_ref[...] += jax.lax.dot_general(
            x_ref[...], wcl_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(0) == nk - 1)
    def _finish():
        lo = -(1 << (acc_bits - 1))
        hi = (1 << (acc_bits - 1)) - 1
        acc = jnp.clip(acc_ref[...], lo, hi)
        m = acc.shape[0]
        if per_row:
            absmax = jnp.max(jnp.abs(acc), axis=1, keepdims=True)  # (M, 1)
        else:
            absmax = jnp.max(jnp.abs(acc))
        # t from the accumulator's integer bit-length: popcount over
        # threshold compares — bit-identical to Q.choose_trunc_lsb.
        a = jnp.maximum(absmax, 1)
        need = jnp.zeros_like(a)
        for b in range(acc_bits):
            need += (a >= (1 << b)).astype(jnp.int32)
        t = jnp.maximum(need - (out_bits - 1), 0)
        t = jnp.clip(t, qs_ref[0, 0], acc_bits - out_bits)

        mask_all = (1 << bits) - 1
        uy = (_trunc(acc, t, out_bits) & mask_all) ^ oflip_ref[...]
        if dppu_src != "none":
            acc_d = acc if dppu_src == "reuse" else jnp.clip(
                accd_ref[...], lo, hi)
            ud = (_trunc(acc_d, t, out_bits) & mask_all) ^ dflip_ref[...]
            uy = jnp.where(imp_ref[...] != 0, ud, uy)
        o_ref[...] = _sign_extend(uy, bits).astype(jnp.int8)
        t_ref[...] = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (m, 1))


@functools.partial(jax.jit, static_argnames=(
    "per_row", "dppu_src", "perrow_wf", "bk", "bits", "acc_bits", "out_bits",
    "interpret"))
def fused_decode(xq, wq, oflips, q_scale, *, wq_clean=None, wflips=None,
                 dflips=None, imp=None, per_row: bool = False,
                 dppu_src: str = "none", perrow_wf: bool = False,
                 bk: int = 128, bits: int = 8, acc_bits: int = ACC_BITS,
                 out_bits: int = OUT_BITS, interpret: bool | None = None):
    """One fused decode step.

    Args:
      xq: (M, K) int8.  wq: (K, N) int8 (pre-faulted in shared-fault mode).
      oflips: (M, N) int32 packed output flip words.
      q_scale: (1, 1) int32 — minimum truncation LSB (traceable dyn leaf).
      wq_clean: (K, N) int8 clean weights (dppu_src="wcl" only).
      wflips: (M, K, N) int32 per-row weight flip words (perrow_wf only).
      dflips: (M, N) int32 DPPU flip words; imp: (1, N) int32 mask
        (dppu_src != "none" only).
      per_row: per-row truncation LSB instead of one global t.
      dppu_src: "none" | "reuse" (clean acc == faulty acc: no weight
        faults) | "w" (recompute from `wq`, which is clean in per-row
        weight-fault mode) | "wcl" (recompute from `wq_clean`).
    Returns:
      (y, t): (M, N) int8 outputs and (M, 1) int32 truncation LSBs
      (all rows equal when per_row=False).
    """
    M, K = xq.shape
    _, N = wq.shape
    assert M % 8 == 0 and N % 128 == 0 and K % bk == 0, (
        "fused_decode operands must be tile-aligned (pad in ops.py)")
    nk = K // bk
    grid = (nk,)

    operands = [xq, wq]
    in_specs = [
        pl.BlockSpec((M, bk), lambda k: (0, k)),
        pl.BlockSpec((bk, N), lambda k: (k, 0)),
    ]
    if dppu_src == "wcl":
        operands.append(wq_clean)
        in_specs.append(pl.BlockSpec((bk, N), lambda k: (k, 0)))
    if perrow_wf:
        operands.append(wflips)
        in_specs.append(pl.BlockSpec((M, bk, N), lambda k: (0, k, 0)))
    operands.append(oflips)
    in_specs.append(pl.BlockSpec((M, N), lambda k: (0, 0)))
    if dppu_src != "none":
        operands.extend([dflips, imp])
        in_specs.extend([pl.BlockSpec((M, N), lambda k: (0, 0)),
                         pl.BlockSpec((1, N), lambda k: (0, 0))])
    operands.append(q_scale)
    in_specs.append(pl.BlockSpec((1, 1), lambda k: (0, 0)))

    scratch = [pltpu.VMEM((M, N), jnp.int32)]
    if dppu_src in ("w", "wcl"):
        scratch.append(pltpu.VMEM((M, N), jnp.int32))

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk, per_row=per_row, dppu_src=dppu_src,
                          perrow_wf=perrow_wf, bits=bits, acc_bits=acc_bits,
                          out_bits=out_bits),
        grid=grid,
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((M, N), lambda k: (0, 0)),
                   pl.BlockSpec((M, 1), lambda k: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct((M, N), jnp.int8),
                   jax.ShapeDtypeStruct((M, 1), jnp.int32)],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
        name="fused_decode",
    )(*operands)
