"""Seeded random weights, made by the benchmark and not by the program.

The program's parameter tree gives only the layout (leaf names, shapes and
dtypes, read with ``jax.eval_shape``).  Every value is drawn here, so the
plain reference can draw any single leaf of any layer again from the seed
(``layer_leaf``) without taking anything the program made.

Leaf ``name`` of layer ``layer`` draws from
``fold_in(fold_in(key(seed), crc32(name)), layer)`` (layer ``NO_LAYER`` for
leaves outside the layer stack):

* matrices (K, N): truncated normal on [-2, 2], std 1/sqrt(K);
* embedding tables (V, D): truncated normal, std 1/sqrt(D);
* vectors (norm offsets, biases): normal, std 0.1.

Stacked layers are drawn with ``lax.map`` over the layer index, the same
per-layer function the reference calls, so both sides hold the same values.
"""
from __future__ import annotations

import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

VECTOR_STD = 0.1
NO_LAYER = 0x7FFFFFFF      # the layer index of embed, unembed, final_norm


def jax_seed(seed: int, tag: int = 0) -> int:
    """A 31-bit seed for JAX from any whole-number seed."""
    s = np.random.SeedSequence([int(seed) & (2**64 - 1), tag])
    return int(s.generate_state(1, dtype=np.uint32)[0] & 0x7FFFFFFF)


def _kind(name: str, shape) -> str:
    if len(shape) == 1:
        return "vector"
    return "table" if name in ("embed", "unembed") else "matrix"


@partial(jax.jit, static_argnames=("shape", "dtype", "kind"))
def _draw(seed31, name_crc, layer, *, shape, dtype, kind):
    key = jax.random.fold_in(jax.random.fold_in(
        jax.random.PRNGKey(seed31), name_crc), layer)
    if kind == "vector":
        v = jax.random.normal(key, shape, jnp.float32) * VECTOR_STD
    else:
        fan = shape[1] if kind == "table" else shape[0]
        v = (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
             * fan ** -0.5)
    return v.astype(dtype)


def _crc(name: str) -> int:
    return zlib.crc32(name.encode()) & 0x7FFFFFFF


def layer_leaf(seed: int, name: str, layer: int, shape, dtype):
    """One layer's leaf (``layer=NO_LAYER`` for leaves outside the layers)."""
    shape = tuple(shape)
    return _draw(jax_seed(seed, 1), _crc(name), layer, shape=shape,
                 dtype=jnp.dtype(dtype), kind=_kind(name, shape))


def _layout(path, cfg):
    """(leaf name, stacked layer offset and stride, or a fixed layer)."""
    keys = [str(getattr(k, "key", k)) for k in path]
    if keys[0].startswith("seg"):
        si, j = int(keys[0][3:]), int(keys[1][1:])
        offset = sum(len(p) * n for p, n in cfg.segments[:si])
        period = len(cfg.segments[si][0])
        return "/".join(keys[2:]), (offset + j, period)
    if keys[0] == "layers":
        return "/".join(keys[2:]), int(keys[1][1:])
    return "/".join(keys), NO_LAYER


def make_params(model, seed: int, shardings=None):
    """The program's parameter tree, drawn on the device in one jitted call.
    The seed is an argument of that call, so every seed runs one compiled
    program.  ``shardings`` (a tree of shardings like the parameters) places
    each leaf as it is drawn, so each device draws only its own shard; the
    values are those of the unsharded draw, because threefry is
    partitionable (JAX's default, which ``repro.core.faults`` sets too)."""
    cfg = model.cfg
    spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(spec)

    def build(seed31):
        out = []
        for path, sd in leaves:
            name, where = _layout(path, cfg)
            if isinstance(where, tuple):
                first, period = where
                shape = tuple(sd.shape[1:])
                layers = first + period * jnp.arange(sd.shape[0])
                out.append(jax.lax.map(
                    lambda i, name=name, shape=shape, dt=sd.dtype: _draw(
                        seed31, _crc(name), i, shape=shape, dtype=dt,
                        kind=_kind(name, shape)), layers))
            else:
                shape = tuple(sd.shape)
                out.append(_draw(seed31, _crc(name), where, shape=shape,
                                 dtype=sd.dtype, kind=_kind(name, shape)))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=shardings)(jnp.int32(jax_seed(seed, 1)))
