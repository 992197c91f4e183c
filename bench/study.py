#!/usr/bin/env python3
"""Readings that set a cell's correctness limit, on the chip, in one process.

  python3 bench/study.py --workload <cell> --seeds 1 2 ... [--control-seeds 1 2 3]
                         [--seconds 0] [--policy <name>] [--out <file.jsonl>]

For each seed of ``--seeds`` it runs the cell through ``run.run_cell``, the
benchmark's own path, with a window of ``--seconds`` (0: one wave), and
prints the numbers its check compared: the lower reading of each is the
largest over sound seeds.  For each seed of ``--control-seeds`` it also
reads the control on the same checked sample: the reference in the lower
precision that the limits file names (``control_bits``), judged against the
same limits; the upper reading is the smallest of those.  ``--policy``
serves the cell under another protection policy of the same rate and
backend (``base``: faults with no voting), a fault the limits must catch.
One JSON line per seed.  Neither reading belongs to a benchmark run.
"""
import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness  # noqa: E402


def run_module():
    """``bench/run.py``, which is a script, as a module."""
    spec = importlib.util.spec_from_file_location(
        "bench_run_script", ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, w: dict, seed: int, seconds: float, control: bool, devices,
         root: Path = ROOT, log=print) -> dict:
    """One seed's readings: the run's checked numbers and, with ``control``,
    the control's on the same sample, each beside its limit."""
    res, checked = run.run_cell(w, seed, seconds, False, devices,
                               t_start=time.perf_counter(), root=root,
                               log=log)
    sample = checked["sample"]
    line = {"seed": seed, "correct": res["correct"], "failed": res["failed"],
            "sample": len(sample),
            "served_tokens": sum(len(g) for _, _, g in sample),
            **checked["numbers"], "check": res["check"]}
    if control:
        ref = harness.reference(w["conf"]["reference"], root)
        bits = w["limits"]["control_bits"]
        nums = check.control_gaps(ref, w["conf"], seed, sample, bits)
        line[f"control_int{bits}"] = nums
        line["control_check"] = check.judge(nums, w["limits"]["limits"])
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--policy")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    w = harness.cell(args.workload)
    if args.policy:
        w["conf"] = dict(w["conf"], protection=dict(
            w["conf"]["protection"], policy=args.policy))
    import jax
    devices = jax.devices()[:w["chips"]]
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        print(f"study: the cell needs {w['chips']} TPU chip(s)",
              file=sys.stderr)
        return 2
    harness.use_compile_cache()
    run = run_module()
    for seed in args.seeds:
        line = {"workload": args.workload, "policy": args.policy,
                **read(run, w, seed, args.seconds,
                       seed in args.control_seeds, devices,
                       log=lambda s: print(s, file=sys.stderr, flush=True))}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
