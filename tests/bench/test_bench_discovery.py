"""Cells, configurations, traffic mixes and metrics are data found by name:
a new one is new files and entries, with no edit to code."""
import json

import pytest

import tiny_cells
from bench import harness


def test_new_config_traffic_and_metric_found_by_name(tmp_path):
    root = tiny_cells.make_root(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    conf = dict(json.loads((root / "bench/configs/tiny-clean.json").read_text()),
                name="tiny-wide")
    conf["model"] = dict(conf["model"], d_ff=256)
    (root / "bench/configs/tiny-wide.json").write_text(json.dumps(conf))
    (root / "bench/traffic/burst.json").write_text(json.dumps(
        dict(tiny_cells.TRAFFIC, wave=3)))
    (root / "bench/limits/tiny-wide-burst.json").write_text(
        json.dumps({"limits": {"logit_gap": 0.5}, "control_bits": 8}))
    b["configs"].append({"name": "tiny-wide", "source": "test", "reduced": [],
                         "file": "bench/configs/tiny-wide.json", "why": "t"})
    b["workloads"].append({"name": "tiny-wide-burst", "config": "tiny-wide",
                           "traffic": "burst", "chips": 1, "why": "t"})
    # a per-layer metric of its own, in a metrics directory of its own
    (root / "bench/metrics").unlink()
    (root / "bench/metrics").mkdir()
    (root / "bench/metrics/front.prefills_per_request.py").write_text(
        "def read(rec):\n"
        "    return rec['stats']['prefill_calls'] / len(rec['requests'])\n")
    (root / "bench/metrics/nothing.py").write_text(
        "def read(rec):\n    return None\n")
    b["per_layer"] = [
        {"name": "front.prefills_per_request", "unit": "1/request",
         "better": "lower", "source": "program_counter", "layer": "front end",
         "moves": "gen_tokens_per_s", "workloads": ["tiny-wide-burst"]},
        {"name": "nothing", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "device",
         "moves": "gen_tokens_per_s"}]
    (root / "BENCHMARK.json").write_text(json.dumps(b))

    w = harness.cell("tiny-wide-burst", root)
    assert w["conf"]["model"]["d_ff"] == 256
    assert w["traffic_spec"]["wave"] == 3
    assert [m["name"] for m in w["per_layer"]] == [
        "front.prefills_per_request", "nothing"]
    assert [m["name"] for m in harness.cell("tiny-clean", root)["per_layer"]] == ["nothing"]
    rec = {"stats": {"prefill_calls": 6}, "requests": [(1, 2)] * 3}
    got = harness.read_metrics(w["per_layer"], rec, root)
    # a reader that finds nothing leaves its metric out
    assert got == {"front.prefills_per_request": {"value": 2.0,
                                                  "unit": "1/request"}}


def test_unknown_workload(tmp_path):
    root = tiny_cells.make_root(tmp_path)
    with pytest.raises(KeyError, match="unknown workload"):
        harness.cell("nope", root)


def test_benchmark_json_names_existing_files():
    b = harness.benchmark()
    for c in b["configs"]:
        conf = harness.load_json(harness.ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert (harness.BENCH / "references" / f"{conf['reference']}.py").exists()
    for w in b["workloads"]:
        cell = harness.cell(w["name"])
        assert cell["limits"]["limits"] and all(
            v > 0 for v in cell["limits"]["limits"].values())
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.reader(m["name"]))
