"""serve_scan_vs_python / serve_scaling — serving-path throughput.

Measures the three serving paths on the reduced configs of three workload
families (dense LM, MoE, vision-frontend VLM), clean and under a registry
protection policy:

  * ``python`` — the legacy per-token dispatch loop (1 jit call per token),
  * ``scan``   — the fused ``lax.scan`` decode loop (1 jit call per
    generation; fault keys folded inside the scan),
  * ``sched``  — the continuous-batching scheduler on top of the fused
    chunked loop (per-request fault streams, bucketed prefill).

Reports tokens/sec (steady-state: compile excluded by a warmup call) and
host roundtrips (jitted executable invocations) per generation.  The scan
path must cut roundtrips by >=5x vs the python loop at equal (bit-identical
at temperature 0) outputs — that equality is enforced by
tests/test_serve_engine.py; this benchmark measures the speed side.

``serve_scaling`` measures sharded-serving throughput 1 -> N devices
(dense vs MoE, clean vs crt3).  All arms run in this one process, each on
a pure-DP (N, 1) mesh over the first N of ``jax.devices()`` — one process
holds the chips, as an accelerator requires — with a batch that grows with
the device count.  On the CPU backend (``--scaling`` gives it four host
devices) that is **weak scaling**: all N "devices" share the same cores, so
per-device work is held constant and throughput rises as the batch
amortizes the fixed per-step dispatch overhead.  The snapshot's meta block
records the regime.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import ft
from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models import build
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, Scheduler, SchedulerConfig

CONFIGS = (
    ("dense", "h2o-danube-1.8b"),
    ("moe", "qwen3-moe-235b-a22b"),
    ("vision", "paligemma-3b"),
)
POLICIES = (None, "crt3")
BATCH = 2
PROMPT = 8
NEW = 16
REPS = 2


def _policy(name):
    if name is None:
        return None
    # weight_faults=False: the per-request scheduler arm requires it (shared
    # ECC weight SRAM), and the arms must serve the same design
    return ft.get_policy(name, ber=1e-3, weight_faults=False)


def _batch_for(cfg, key):
    batch = {"tokens": jax.random.randint(key, (BATCH, PROMPT), 0, cfg.vocab)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(key, 1),
            (BATCH, cfg.n_frontend_tokens, cfg.d_model), jnp.bfloat16)
    return batch


def _time_engine(model, params, policy, loop, batch):
    eng = Engine(model, params, cfg=ServeConfig(max_new_tokens=NEW),
                 policy=policy, loop=loop)
    jax.block_until_ready(eng.generate(batch, seed=0))     # compile
    t0 = time.perf_counter()
    for r in range(REPS):
        jax.block_until_ready(eng.generate(batch, seed=r))
    dt = time.perf_counter() - t0
    return (REPS * eng.stats.tokens) / dt, eng.stats.roundtrips


def _time_sched(model, params, policy, cfg):
    front = (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)

    def reqs():
        out = []
        for i in range(2 * BATCH):
            key = jax.random.PRNGKey(100 + i)
            toks = [int(t) for t in jax.random.randint(
                key, (PROMPT - (i % 3),), 0, cfg.vocab)]
            extras = None
            if cfg.frontend == "vision":
                extras = {"patch_embeds": jax.random.normal(
                    jax.random.fold_in(key, 1),
                    (front, cfg.d_model), jnp.bfloat16)}
            out.append(Request(rid=i, tokens=toks, max_new_tokens=NEW,
                               extras=extras))
        return out

    sched = Scheduler(model, params,
                      SchedulerConfig(max_batch=BATCH, buckets=(PROMPT,),
                                      max_new_tokens=NEW, decode_chunk=8),
                      policy=policy)
    sched.run(reqs())                                      # compile
    t0 = time.perf_counter()
    done = sched.run(reqs())
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.generated) for r in done.values())
    return n_tok / dt, sched.stats.roundtrips


def serve_scan_vs_python():
    rows = []
    ratios, uplifts = [], []
    for fam, arch in CONFIGS:
        cfg = get_config(arch, reduced=True)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        batch = _batch_for(cfg, jax.random.PRNGKey(1))
        for pname in POLICIES:
            pol = _policy(pname)
            tps_py, rt_py = _time_engine(model, params, pol, "python", batch)
            tps_sc, rt_sc = _time_engine(model, params, pol, "scan", batch)
            tps_sd, rt_sd = _time_sched(model, params, pol, cfg)
            ratios.append(rt_py / rt_sc)
            uplifts.append(tps_sc / tps_py)
            rows.append(dict(
                family=fam, policy=pname or "clean",
                python_tok_s=round(tps_py, 1), scan_tok_s=round(tps_sc, 1),
                sched_tok_s=round(tps_sd, 1),
                python_roundtrips=rt_py, scan_roundtrips=rt_sc,
                sched_roundtrips=rt_sd,
                roundtrip_ratio=round(rt_py / rt_sc, 1),
                tok_s_uplift=round(tps_sc / tps_py, 2)))
    derived = dict(
        min_roundtrip_ratio=round(min(ratios), 1),
        min_tok_s_uplift=round(min(uplifts), 2),
        geomean_tok_s_uplift=round(
            float(jnp.exp(jnp.mean(jnp.log(jnp.asarray(uplifts))))), 2))
    return rows, derived


# ------------------------------------------------------- serve_scaling ----
SCALE_DEVICES = (1, 2, 4)
SCALE_CONFIGS = (("dense", "h2o-danube-1.8b"), ("moe", "qwen3-moe-235b-a22b"))
SCALE_BASE_BATCH = 4
SCALE_REPS = 3



def _scale_arm(arch, pname, devices):
    cfg = get_config(arch, reduced=True)
    if cfg.moe is not None:
        # capacity is per-shard: give headroom so no partitioning drops
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    mesh = make_mesh((devices, 1), ("data", "model"),
                     devices=jax.devices()[:devices])
    B = SCALE_BASE_BATCH * devices                  # weak scaling
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                          (B, PROMPT), 0, cfg.vocab)}
    eng = Engine(model, params, mesh=mesh,
                 cfg=ServeConfig(max_new_tokens=NEW), policy=_policy(pname))
    jax.block_until_ready(eng.generate(batch, seed=0))      # compile
    rates = []
    for r in range(SCALE_REPS):
        t0 = time.perf_counter()
        jax.block_until_ready(eng.generate(batch, seed=r))
        rates.append(eng.stats.tokens / (time.perf_counter() - t0))
    return sorted(rates)[len(rates) // 2]


def serve_scaling():
    """Tokens/sec 1 -> N devices for the sharded Engine (weak scaling on the
    host-platform backend; see module docstring)."""
    if len(jax.devices()) < max(SCALE_DEVICES):
        raise RuntimeError(
            f"serve_scaling needs {max(SCALE_DEVICES)} devices, found "
            f"{len(jax.devices())}; on the CPU backend set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={max(SCALE_DEVICES)} "
            "before JAX starts (python -m benchmarks.serve_bench --scaling "
            "does)")
    rows = []
    derived = {}
    for fam, arch in SCALE_CONFIGS:
        for pname in POLICIES:
            tps = [_scale_arm(arch, pname, d) for d in SCALE_DEVICES]
            label = f"{fam}_{pname or 'clean'}"
            for d, t in zip(SCALE_DEVICES, tps):
                rows.append(dict(family=fam, policy=pname or "clean",
                                 devices=d,
                                 batch=SCALE_BASE_BATCH * d,
                                 tok_s=round(t, 1)))
            derived[f"{label}_monotonic"] = bool(
                all(b > a for a, b in zip(tps, tps[1:])))
            derived[f"{label}_scaling_{SCALE_DEVICES[-1]}x"] = round(
                tps[-1] / tps[0], 2)
    return rows, derived


def scaling_snapshot(path="BENCH_serve_scaling.json"):
    """Commit-able snapshot of the serve_scaling sweep."""
    rows, derived = serve_scaling()
    meta = dict(
        regime="weak",
        note="host-platform devices share one CPU: batch grows with the "
             "device count, so throughput rises by amortizing fixed "
             "per-step dispatch overhead; on real accelerators the same "
             "harness measures strong scaling",
        backend=f"one process, {len(jax.devices())} "
                f"{jax.devices()[0].platform} devices; an N-device arm "
                "uses the first N",
        devices=list(SCALE_DEVICES), base_batch=SCALE_BASE_BATCH,
        prompt=PROMPT, new_tokens=NEW, mesh="(devices, 1) = (data, model)")
    with open(path, "w") as f:
        json.dump(dict(suite="serve_scaling", meta=meta, rows=rows,
                       derived=derived), f, indent=1)
        f.write("\n")
    return path


def pin_scaling_flags():
    """Append, before JAX starts its backend, the CPU device count the
    scaling arms need and the excess-precision pin of the sharded-serving
    determinism battery (the measured executables are the ones whose
    outputs the tests pin down)."""
    os.environ["XLA_FLAGS"] = " ".join([
        os.environ.get("XLA_FLAGS", ""),
        f"--xla_force_host_platform_device_count={max(SCALE_DEVICES)}",
        "--xla_allow_excess_precision=false"]).strip()


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scaling", action="store_true",
                    help="run serve_scaling and write BENCH_serve_scaling.json")
    args = ap.parse_args()
    if args.scaling:
        pin_scaling_flags()
        p = scaling_snapshot()
        print(f"# wrote {p}")
        print(open(p).read())
    else:
        rows, derived = serve_scan_vs_python()
        for r in rows:
            print(r)
        print(json.dumps(derived))
