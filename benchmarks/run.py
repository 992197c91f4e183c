"""Benchmark harness: one entry per paper table/figure + the roofline table.
Prints ``name,us_per_call,derived`` CSV and writes benchmarks/results.json.

Usage: PYTHONPATH=src python -m benchmarks.run [--only NAME] [--fast]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

RESULTS_PATH = os.path.join(os.path.dirname(__file__), "results.json")


def _benchmarks():
    from benchmarks import paper_figs as F
    from benchmarks import roofline as R
    from benchmarks.dse_batch import dse_batched_vs_sequential
    from benchmarks.train_bench import fat_dse, fat_vs_baseline

    def roofline_single():
        rows = R.full_table("single")
        return rows, R.summarize(rows)

    def roofline_multi():
        rows = R.full_table("multi")
        return rows, R.summarize(rows)

    return {
        "fig5_layer_sensitivity": F.fig5_layer_sensitivity,
        "fig6_cumulative_protection": F.fig6_cumulative_protection,
        "fig7_strategy_accuracy": F.fig7_strategy_accuracy,
        "fig8_strategy_perf": F.fig8_strategy_perf,
        "fig9_strategy_area": F.fig9_strategy_area,
        "fig10_neuron_bits": F.fig10_neuron_bits,
        "fig11_qscale": F.fig11_qscale,
        "fig12_dppu_area": F.fig12_dppu_area,
        "fig13_io_overhead": F.fig13_io_overhead,
        "fig14_bit_area": F.fig14_bit_area,
        "fig15_table2_dse": F.fig15_table2_dse,
        "dse_batched_vs_sequential": dse_batched_vs_sequential,
        "fat_vs_baseline": fat_vs_baseline,
        "fat_dse": fat_dse,
        "roofline_single_pod": roofline_single,
        "roofline_multi_pod": roofline_multi,
    }


# DSE entries rerun fault injection many times; the batched-vs-sequential
# comparison deliberately includes a slow sequential arm.
FAST_SKIP = {"fig15_table2_dse", "dse_batched_vs_sequential", "fat_dse"}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true")
    args, _ = ap.parse_known_args()

    benches = _benchmarks()
    if args.only:
        benches = {k: v for k, v in benches.items() if args.only in k}
    if args.fast:
        benches = {k: v for k, v in benches.items() if k not in FAST_SKIP}
    out = {}
    print("name,us_per_call,derived")
    for name, fn in benches.items():
        import jax
        jax.clear_caches()  # each fig compiles many distinct FT configs
        t0 = time.time()
        rows, derived = fn()
        dt_us = (time.time() - t0) * 1e6
        out[name] = {"rows": rows, "derived": derived,
                     "seconds": round(dt_us / 1e6, 2)}
        d = derived if not isinstance(derived, dict) else json.dumps(derived)
        print(f"{name},{dt_us:.0f},{d}", flush=True)
    if os.path.exists(RESULTS_PATH):  # merge with prior (--only reruns)
        prior = json.load(open(RESULTS_PATH))
        prior.update(out)
        out = prior
    with open(RESULTS_PATH, "w") as f:
        json.dump(out, f, indent=1, default=str)
    print(f"# wrote {RESULTS_PATH}")


if __name__ == "__main__":
    main()
