"""The peak table and the work counts, against hand-worked values at
h2o-danube-1.8b widths."""
import pytest

import tiny_cells  # noqa: F401
from bench import harness, work
from bench.peaks import peaks

DANUBE = harness.load_json(harness.BENCH / "configs" / "danube-1.8b.json")["model"]


def test_v5e_peaks():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9 and p["hbm_bytes"] == 16 * 2**30


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks("cpu")


def test_danube_params_and_flops():
    # per layer: q 2560*2560 + k,v 2*2560*640 + o 2560*2560 + 3*2560*6912
    layer = 6553600 + 3276800 + 6553600 + 53084160
    assert work.layer_params(DANUBE) == layer == 69468160
    assert work.head_params(DANUBE) == 81920000
    # one decode token at context 1000: 2 * (24 layers + head) + attention
    # 4 * 32 heads * 80 * 1000 * 24 layers
    want = 2.0 * (24 * layer + 81920000) + 4.0 * 32 * 80 * 1000 * 24
    assert work.decode_flops(DANUBE, 1000) == want
    # the window caps the context
    assert work.attn_flops(DANUBE, 5000) == work.attn_flops(DANUBE, 4096)
    # a 3-token prompt: matmuls for 3 tokens, attention to 1, 2, 3, one head row
    assert work.prefill_flops(DANUBE, 3) == (
        2.0 * 24 * layer * 3 + 4.0 * 32 * 80 * 24 * (1 + 2 + 3) + 2.0 * 81920000)
    assert work.request_decode_flops(DANUBE, 3, 1) == 0


def test_danube_bytes():
    assert work.weight_bytes(DANUBE) == 2 * (24 * 69468160 + 81920000)
    # k and v, 8 heads of 80, bf16, 24 layers, per position
    assert work.kv_bytes(DANUBE, 1) == 2 * 2 * 8 * 80 * 24 == 61440
    # two steps serving one request of prompt 10 and 3 tokens: decode tokens
    # 1 and 2 read 11 and 12 positions
    assert work.decode_bytes(DANUBE, [(10, 3)], 2) == (
        2 * work.weight_bytes(DANUBE) + 61440 * 23)
