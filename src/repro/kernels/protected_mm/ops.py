"""jit'd wrapper: float-in/float-out fault-tolerant linear on the fused
FlexHyCA kernel — the TPU-optimized twin of repro.core.flexhyca.ft_linear.

The truncation LSB `t` is per-layer deployment configuration on the DLA
(chosen once at calibration), so it is a static argument here; use
``calibrate_t`` to derive it from sample data.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.kernels.fault_inject.ops import random_planes
from repro.kernels.protected_mm.kernel import protected_mm


def calibrate_t(x, w, q_scale: int = 7) -> int:
    """Pick the per-layer truncation LSB from calibration data."""
    xq, _ = Q.quantize(x)
    wq, _ = Q.quantize(w)
    acc = Q.saturate(jnp.matmul(xq, wq, preferred_element_type=jnp.int32))
    return int(Q.choose_trunc_lsb(jnp.max(jnp.abs(acc)), q_scale=q_scale))


@partial(jax.jit, static_argnames=("t", "ber", "ib", "nb", "interpret"))
def ft_linear_fused(key, x, w, important, *, t: int, ber: float, ib: int = 2,
                    nb: int = 1, interpret: bool | None = None):
    """x: (M, K) float; w: (K, N) float; important: (N,) bool."""
    xq, sx = Q.quantize(x)
    wq, sw = Q.quantize(w)
    k1, k2 = jax.random.split(key)
    rnd_o = random_planes(k1, x.shape[:1] + w.shape[1:])
    rnd_i = random_planes(k2, x.shape[:1] + w.shape[1:])
    yq = protected_mm(xq.astype(jnp.int8), wq.astype(jnp.int8), rnd_o, rnd_i,
                      important.astype(jnp.int32), t=t, ber=ber, ib=ib, nb=nb,
                      interpret=interpret)
    scale = sx * sw * (2.0 ** t)
    return yq.astype(jnp.float32) * scale
