"""The benchmark's plain float32 reference against repro.models at a
reduced size, both in float32 on the weights the benchmark draws."""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_cells
from bench import harness, weights
from bench.references import dense_decoder as ref


@pytest.mark.parametrize("model", [
    dict(tiny_cells.MODEL),
    dict(tiny_cells.MODEL, block_pattern=["G"], window=0, qkv_bias=False,
         tie_embeddings=True, n_layers=3),
    dict(tiny_cells.MODEL, window=8),
], ids=["window_bias_untied", "global_tied", "window_8"])
def test_reference_matches_program_float32(model):
    from repro.configs.base import ModelConfig, RunConfig
    from repro.models import build
    m = dict(model, block_pattern=tuple(model["block_pattern"]))
    prog = build(ModelConfig(**m), RunConfig(param_dtype="float32",
                                             compute_dtype="float32"))
    params = weights.make_params(prog, 11)
    conf = {"model": model, "run": {"param_dtype": "float32"}}
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, model["vocab"], n).tolist() for n in (20, 7)]
    got = ref.logits(conf, 11, seqs, [range(len(s)) for s in seqs])
    for s, g in zip(seqs, got):
        for p in (0, len(s) // 2, len(s) - 1):
            toks = jnp.asarray([s[:p + 1]], jnp.int32)
            _, lg = prog.prefill(params, {"tokens": toks})
            np.testing.assert_allclose(np.asarray(lg[0]), g[p], atol=2e-4,
                                       rtol=2e-4)


def test_stacked_weights_equal_leaf_draws():
    conf = {"model": tiny_cells.MODEL, "run": tiny_cells.SCHED}
    from repro.configs.base import ModelConfig, RunConfig
    from repro.models import build
    m = dict(tiny_cells.MODEL, block_pattern=("L",))
    prog = build(ModelConfig(**m), RunConfig())
    params = weights.make_params(prog, 2**33 + 1)
    shapes = ref.layer_shapes(conf["model"])
    for name in ("attn/wq", "attn/bv", "ln2", "ffn/wo"):
        a, b = name.split("/") if "/" in name else (name, None)
        stacked = params["seg0"]["s0"][a]
        stacked = stacked if b is None else stacked[b]
        for layer in range(m["n_layers"]):
            leaf = weights.layer_leaf(2**33 + 1, name, layer, shapes[name],
                                      stacked.dtype)
            assert (np.asarray(leaf) == np.asarray(stacked[layer])).all()
    assert harness.rid_base(2**33 + 1) < 2**29
