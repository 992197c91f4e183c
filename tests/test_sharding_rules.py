"""Sharding-rule unit tests on the abstract production mesh (no devices)."""
import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, PartitionSpec as P
from jax.tree_util import DictKey

from repro.parallel import sharding as S

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH_MP = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def spec_of(names, shape, mesh=MESH):
    path = tuple(DictKey(n) for n in names)
    leaf = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    return S.param_spec(path, leaf, mesh)


def test_in_proj_2d_sharded():
    assert spec_of(("seg0", "s0", "attn", "wq"), (23, 4608, 4096)) == \
        P(None, ("data",), "model")


def test_out_proj_transposed():
    assert spec_of(("seg0", "s0", "attn", "wo"), (23, 4096, 4608)) == \
        P(None, "model", ("data",))


def test_multipod_fsdp_axes():
    s = spec_of(("seg0", "s0", "ffn", "wi"), (23, 4608, 36864), MESH_MP)
    assert s == P(None, ("pod", "data"), "model")


def test_moe_experts_over_model():
    s = spec_of(("seg0", "s0", "ffn", "wi"), (94, 128, 4096, 1536))
    assert s == P(None, "model", ("data",), None)


def test_indivisible_dims_replicated():
    # seamless vocab 256206 doesn't divide 16 => replicated on that dim
    s = spec_of(("embed",), (256206, 1024))
    assert s == P(None, ("data",))


def test_norms_replicated():
    assert spec_of(("seg0", "s0", "ln1"), (23, 4608)) == P(None, None)


def test_unstacked_tail_params():
    assert spec_of(("final_norm",), (4608,)) == P(None)


def sds(shape):
    # ShapeDtypeStructs, NOT real arrays — these are full-scale cache shapes
    return jax.ShapeDtypeStruct(shape, jnp.bfloat16)


def test_serving_layout_drops_fsdp():
    tree = {"seg0": {"s0": {"attn": {"wq": sds((2, 64, 64))}}}}
    sh = S.param_shardings(tree, MESH, no_fsdp=True)
    spec = jax.tree.leaves(sh)[0].spec
    assert spec == P(None, None, "model")


def test_cache_split_k_when_heads_indivisible():
    # glm4: kv=2 heads can't shard 16 ways => cache length sharded instead
    cache = {"seg0": {"s0": {"attn": {"k": sds((40, 128, 32768, 2, 128))}}}}
    sh = S.cache_shardings(cache, MESH)
    spec = jax.tree.leaves(sh)[0].spec
    assert spec == P(None, ("data",), "model", None, None)


def test_cache_heads_preferred_when_divisible():
    cache = {"seg0": {"s0": {"attn": {"k": sds((23, 128, 32768, 16, 128))}}}}
    sh = S.cache_shardings(cache, MESH)
    spec = jax.tree.leaves(sh)[0].spec
    assert spec == P(None, ("data",), None, "model", None)


def test_paged_pool_never_dp_sharded():
    """Regression: a paged pool leaf (n_blocks, block_size, KH*Dh) used to
    match the dense (B, C, KH, Dh) branch and get its *pool* dim DP-sharded
    as if it were batch — but block tables hold global block ids, so any
    sharding of dims 0/1 breaks paged lookup.  Pools shard their heads-major
    rows over 'model' only; the block table itself shards with the batch."""
    pool = (4096, 16, 16 * 128)    # divisible by 16 on dims 0/1/2: tempting
    cache = {"seg0": {"s0": {"attn": {
        "k": sds((23,) + pool), "v": sds((23,) + pool),
        "bt": jax.ShapeDtypeStruct((23, 256, 32), jnp.int32)}}}}
    sh = S.cache_shardings(cache, MESH, kv_heads=16)
    attn = jax.tree.leaves(sh["seg0"]["s0"]["attn"]["k"])[0].spec
    assert attn == P(None, None, None, "model")
    assert jax.tree.leaves(sh["seg0"]["s0"]["attn"]["v"])[0].spec == attn
    # the per-slot block table is batch-major state: batch over DP
    assert jax.tree.leaves(sh["seg0"]["s0"]["attn"]["bt"])[0].spec == \
        P(None, ("data",), None)


def test_paged_pool_heads_indivisible_stays_replicated():
    # no split-K fallback for pools: the in-block dim is block_size, not
    # cache length, so an indivisible head count leaves the pool replicated
    # — even where the row width KH*Dh (2 x 128) divides the axis, a split
    # would cut heads
    pool = (4096, 16, 2 * 128)
    cache = {"l0": {"attn": {"k": sds(pool), "v": sds(pool),
                             "bt": jax.ShapeDtypeStruct((8, 32), jnp.int32)}}}
    sh = S.cache_shardings(cache, MESH, kv_heads=2)
    assert jax.tree.leaves(sh["l0"]["attn"]["k"])[0].spec == \
        P(None, None, None)
    # dense siblings (cross-attn buffers etc.) keep the dense rules
    cache["l0"]["cross"] = {"ck": sds((32, 128, 16, 128))}
    sh = S.cache_shardings(cache, MESH, kv_heads=2)
    assert jax.tree.leaves(sh["l0"]["cross"]["ck"])[0].spec == \
        P(("data",), None, "model", None)
