"""Production mesh builders.

Functions, not module-level constants, so importing never touches jax device
state (device count is locked at first jax init)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """A mesh with Auto axes: the model code places activations with
    ``with_sharding_constraint``, which only accepts Auto mesh axes
    (``jax.make_mesh`` defaults to Explicit ones)."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh():
    """1x1 mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))
