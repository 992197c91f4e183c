"""A benchmark root at a size the CPU test run can hold: two tiny cells of
the dense decoder (sliding window, QKV bias, untied head), clean and under
crt3, with the real metric readers and reference.

Their limits are set from readings on the CPU, as the cells' limits are on
the chip: ``mean_logit_gap`` over one wave, seeds 1-6 and 2**31 + 77.
crt3: the program 0.0014-0.0127, its int4 control 0.162-0.321, the program
with voting off (policy ``base``, seeds 1-3 and 2**31 + 77) 0.137-0.270;
limit 0.06.  Clean: the program 0-0.00061; its int8 control (0-0.0016)
does not separate at this size, so the clean cell is held against its
faults (a token altered where produced, a step that keeps its state) and the
control test uses the crt3 cell; limit 0.005.
"""
import json
import os
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

MODEL = {"name": "tiny", "family": "dense", "n_layers": 2, "d_model": 64,
         "n_heads": 4, "n_kv_heads": 2, "d_head": 16, "d_ff": 128,
         "vocab": 512, "block_pattern": ["L"], "window": 64, "act": "silu",
         "glu": True, "rope_theta": 10000.0, "norm_eps": 1e-05,
         "tie_embeddings": False, "qkv_bias": True}
SCHED = {"max_batch": 4, "buckets": [16, 32], "max_new_tokens": 16,
         "decode_chunk": 4, "block_size": 8}
CRT3 = {"policy": "crt3", "ber": 1e-3, "weight_faults": False,
        "backend": "fused"}
TRAFFIC = {"why": "tiny", "wave": 8, "check_tokens": 40,
           "prompt": {"median": 12, "sigma": 0.6, "min": 4, "max": 30},
           "output": {"median": 8, "sigma": 0.5, "min": 4, "max": 16}}
LIMITS = {"tiny-clean": {"limits": {"mean_logit_gap": 0.005},
                         "control_bits": 8},
          "tiny-crt3": {"limits": {"mean_logit_gap": 0.06}, "control_bits": 4}}


def _dump(path: Path, obj):
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(obj, indent=1))


def make_root(tmp: Path) -> Path:
    """A checkout-like root: BENCHMARK.json and bench/ data for the tiny
    cells; metric readers and references are the repository's own."""
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    bench["configs"], bench["workloads"] = [], []
    for name, prot in (("clean", None), ("crt3", CRT3)):
        conf = {"name": f"tiny-{name}", "reference": "dense_decoder",
                "model": MODEL, "scheduler": SCHED, "protection": prot,
                "run": {"param_dtype": "bfloat16",
                        "compute_dtype": "bfloat16"}}
        _dump(tmp / "bench" / "configs" / f"tiny-{name}.json", conf)
        bench["configs"].append({
            "name": f"tiny-{name}", "source": "test", "reduced": [],
            "file": f"bench/configs/tiny-{name}.json", "why": "test"})
        bench["workloads"].append({
            "name": f"tiny-{name}", "config": f"tiny-{name}",
            "traffic": "tiny", "chips": 1, "why": "test"})
        _dump(tmp / "bench" / "limits" / f"tiny-{name}.json",
              LIMITS[f"tiny-{name}"])
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    _dump(tmp / "BENCHMARK.json", bench)
    _dump(tmp / "bench" / "traffic" / "tiny.json", TRAFFIC)
    for d in ("metrics", "references"):
        os.symlink(REPO / "bench" / d, tmp / "bench" / d)
    return tmp


def run_module():
    """bench/run.py as a module (it is a script, not part of a package)."""
    from bench import study
    return study.run_module()
