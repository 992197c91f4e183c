"""jit'd wrapper: random-plane generation + the fault-injection kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fault_inject.kernel import fault_inject


def random_planes(key, shape, bits: int = 8):
    return jax.random.bits(key, (bits,) + tuple(shape), jnp.uint32)


@partial(jax.jit, static_argnames=("ber", "bits", "interpret"))
def inject(key, x, protect, ber: float, bits: int = 8,
           interpret: bool | None = None):
    """Inject faults into int8-window values x (M,N) at BER `ber`."""
    rnd = random_planes(key, x.shape, bits)
    return fault_inject(x, rnd, protect, ber, bits, interpret=interpret)
