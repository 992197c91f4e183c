"""jit'd wrapper for the quantized DLA matmul kernel."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import quantization as Q
from repro.kernels.qmatmul.kernel import qmatmul


@partial(jax.jit, static_argnames=("t", "interpret"))
def quant_linear(x, w, t: int, interpret: bool | None = None):
    """Float-in/float-out linear through the int8 DLA datapath kernel."""
    xq, sx = Q.quantize(x)
    wq, sw = Q.quantize(w)
    yq = qmatmul(xq.astype(jnp.int8), wq.astype(jnp.int8), t,
                 interpret=interpret)
    return yq.astype(jnp.float32) * (sx * sw * (2.0 ** t))
