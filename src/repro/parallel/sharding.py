"""Parameter / batch / cache PartitionSpec rules.

Every weight is sharded 2-D: the tensor-parallel dim over 'model' and an FSDP
dim over the data axes (('pod','data') on the multi-pod mesh).  Dims that do
not divide the axis size are left unsharded (replicated) — e.g. seamless'
vocab 256206 on a 16-way axis.
"""
from __future__ import annotations

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.tree_util import DictKey

from repro.parallel.ctx import MeshCtx


def make_ctx(mesh: Mesh) -> MeshCtx:
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return MeshCtx(mesh=mesh, dp=dp, tp="model")


def _axsize(mesh, axes) -> int:
    if isinstance(axes, str):
        return mesh.shape[axes]
    return int(__import__("math").prod(mesh.shape[a] for a in axes))


def _maybe(mesh, dim: int, axes):
    """Shard `dim` over `axes` only when it divides evenly."""
    if axes is None or dim % _axsize(mesh, axes) != 0:
        return None
    return axes if isinstance(axes, str) else tuple(axes)


# rule tables: name -> (spec builder over unstacked dims)
_IN_PROJ = {"wq", "wk", "wv", "wi", "wg", "in_proj", "w_x", "w_gate"}
_OUT_PROJ = {"wo", "out_proj", "w_out"}
_SQUARE = {"w_a", "w_i"}


def param_spec(path, leaf, mesh: Mesh) -> P:
    names = [k.key for k in path if isinstance(k, DictKey)]
    name = names[-1]
    stacked = names[0].startswith("seg") or names[0] == "enc_blocks"
    fsdp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    tp = "model"
    shape = leaf.shape[1:] if stacked else leaf.shape
    nd = len(shape)

    def spec(*entries):
        entries = list(entries) + [None] * (nd - len(entries))
        if stacked:
            entries = [None] + entries
        return P(*entries)

    if name in ("embed", "unembed"):
        return spec(_maybe(mesh, shape[0], tp), _maybe(mesh, shape[1], fsdp))
    if name in _IN_PROJ and nd == 2:
        return spec(_maybe(mesh, shape[0], fsdp), _maybe(mesh, shape[1], tp))
    if name in _IN_PROJ and nd == 3:     # MoE experts (E, D, F)
        return spec(_maybe(mesh, shape[0], tp), _maybe(mesh, shape[1], fsdp))
    if name in _OUT_PROJ and nd == 2:
        return spec(_maybe(mesh, shape[0], tp), _maybe(mesh, shape[1], fsdp))
    if name in _OUT_PROJ and nd == 3:    # MoE experts (E, F, D)
        return spec(_maybe(mesh, shape[0], tp), _maybe(mesh, shape[1], fsdp))
    if name in _SQUARE:   # block-diagonal RG-LRU gates (heads, bw, bw)
        return spec(_maybe(mesh, shape[0], tp), None,
                    _maybe(mesh, shape[2], fsdp) if nd > 2 else None)
    if name == "conv_w":
        return spec(None, _maybe(mesh, shape[1], tp))
    return spec()  # norms, biases, scalars: replicated


def param_shardings(param_tree, mesh: Mesh, no_fsdp: bool = False):
    """no_fsdp: serving layout — weights sharded over 'model' only and
    replicated over the DP axes (kills the per-step FSDP/partial-sum
    collectives when the TP-sharded copy fits HBM)."""
    fsdp_names = {a for a in ("pod", "data") if a in mesh.axis_names}

    def _clean(e):
        if e is None:
            return None
        if isinstance(e, tuple):
            return None if set(e) & fsdp_names else e
        return None if e in fsdp_names else e

    def one(p, x):
        spec = param_spec(p, x, mesh)
        if no_fsdp:
            spec = P(*[_clean(e) for e in spec])
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map_with_path(one, param_tree)


def batch_shardings(batch_tree, mesh: Mesh):
    """Batch dim over the DP axes (replicated if it doesn't divide)."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def one(x):
        entry = _maybe(mesh, x.shape[0], dp)
        return NamedSharding(mesh, P(*([entry] + [None] * (x.ndim - 1))))
    return jax.tree.map(one, batch_tree)


def cache_shardings(cache_tree, mesh: Mesh, unrolled: bool = False,
                    kv_heads: int | None = None):
    """KV/state caches: batch over DP, head/width dims over 'model' when they
    divide.  Cache layouts (leading 'blocks' stack dim unless unrolled):
      attn k/v: (B, C, KH, Dh); rglru h: (B, W), conv: (B, K-1, W);
      ssd state: (B, H, P, N), conv: (B, K-1, C).

    Paged attention caches (a ``bt`` block table beside ``k``/``v``) store a
    *pool* ``(n_blocks, block_size, KH*Dh)``: block tables hold **global**
    block ids, so the pool dim (and the block dim) must stay replicated over
    the DP axes — sharding dim 0 as if it were batch would break every
    table lookup.  A pool row holds its heads major and ``Dh`` minor, so
    splitting its last dim over 'model' is a split over kv heads; it is made
    only when ``kv_heads`` (the model's ``n_kv_heads``) divides the axis,
    so no head is cut (no split-K fallback either: the in-block dim is
    ``block_size``, not cache length).  Without ``kv_heads`` pools stay
    replicated.  The table itself is per-slot state and shards with the
    batch."""
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # paged pool detection: any cache dict holding a block table holds pools
    leaves = jax.tree_util.tree_flatten_with_path(cache_tree)[0]
    pooled = {tuple(k.key for k in p[:-1] if isinstance(k, DictKey))
              for p, _ in leaves
              if isinstance(p[-1], DictKey) and p[-1].key == "bt"}

    def one(path, x):
        names = [k.key for k in path if isinstance(k, DictKey)]
        stacked = (not unrolled) and names[0].startswith("seg")
        shape = x.shape[1:] if stacked else x.shape
        name = names[-1]
        paged = tuple(names[:-1]) in pooled
        if paged and name in ("k", "v"):
            # (n_blocks, block_size, KH*Dh): pool + block dims replicated,
            # whole kv heads over 'model'
            entries = [None] * len(shape)
            if kv_heads and _maybe(mesh, kv_heads, "model"):
                entries[-1] = "model"
        else:
            entries = [_maybe(mesh, shape[0], dp)] + [None] * (len(shape) - 1)
            if not paged and name in ("k", "v", "ck", "cv") and len(shape) == 4:
                # (B, C, KH, Dh): prefer sharding kv heads; for archs whose
                # few kv heads don't divide the TP axis, shard the cache
                # length instead (flash-decoding split-K: per-shard partial
                # softmax + tiny psums) so the cache is never TP-replicated.
                if _maybe(mesh, shape[2], "model"):
                    entries[2] = "model"
                else:
                    entries[1] = _maybe(mesh, shape[1], "model")
            elif name == "state" and len(shape) == 4:
                entries[1] = _maybe(mesh, shape[1], "model")
            elif name in ("h",) and len(shape) == 2:
                entries[1] = _maybe(mesh, shape[1], "model")
            elif name == "conv" and len(shape) == 3:
                entries[2] = _maybe(mesh, shape[2], "model")
        if stacked:
            entries = [None] + entries
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(one, cache_tree)
