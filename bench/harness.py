"""Find a cell's pieces by name and build the system under test.

Everything that belongs to one cell is data found by name under ``bench/``:

  BENCHMARK.json            the cell: its configuration, traffic and chips
  bench/configs/<c>.json    the configuration: model sizes, scheduler,
                            protection policy, and which reference checks it
  bench/traffic/<t>.json    the traffic mix (read by ``bench.traffic``)
  bench/references/<r>.py   the plain reference a configuration names
  bench/metrics/<m>.py      one reader per metric: ``read(rec) -> value``
  bench/limits/<cell>.json  the limits of the cell's correctness check

A cell on several chips is one deployment sharded over them: a mesh of
``data`` 1 by ``model`` ``chips`` (the axis names of ``repro.launch.mesh``
and ``repro.parallel.ctx``) over the devices the cell was given, each weight
drawn straight into its shard of the serving layout (``Scheduler``'s,
tensor-parallel over ``model``), and the scheduler serving under the mesh.
A cell on one chip is served with no mesh.

A later cell, configuration, traffic mix or metric is added as new files and
entries, with no edit to code that is here.
"""
from __future__ import annotations

import importlib.util
import json
import os
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def cell(name: str, root: Path = ROOT) -> dict:
    """The workload entry, with its configuration entry, the configuration's
    file, the traffic file, the limits and the metrics it reports."""
    b = benchmark(root)
    wl = {w["name"]: w for w in b["workloads"]}
    if name not in wl:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = dict(wl[name])
    confs = {c["name"]: c for c in b["configs"]}
    w["conf"] = load_json(root / confs[w["config"]]["file"])
    w["traffic_spec"] = load_json(root / "bench" / "traffic"
                                  / f"{w['traffic']}.json")
    w["limits"] = load_json(root / "bench" / "limits" / f"{name}.json")

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    w["end_to_end"] = [m for m in b["end_to_end"] if applies(m)]
    w["per_layer"] = [m for m in b["per_layer"] if applies(m)]
    return w


def _module(path: Path, modname: str):
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT):
    """The ``read(rec)`` function of ``bench/metrics/<metric>.py``."""
    return _module(root / "bench" / "metrics" / f"{metric}.py",
                   "bench_metric_" + metric.replace(".", "_")).read


def reference(name: str, root: Path = ROOT):
    return _module(root / "bench" / "references" / f"{name}.py",
                   "bench_reference_" + name)


def read_metrics(metrics: list, rec: dict, root: Path = ROOT) -> dict:
    """Each metric its reader finds something for, with its unit; a reader
    that returns None leaves its metric out."""
    out = {}
    for m in metrics:
        v = reader(m["name"], root)(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def build_system(conf: dict, seed: int, devices):
    """(model, params, scheduler) for a configuration file, weights drawn
    from the seed (``bench.weights``); sharded over ``devices`` where there
    are several."""
    import jax
    from repro import ft
    from repro.configs.base import ModelConfig, RunConfig
    from repro.launch.mesh import make_mesh
    from repro.models import build
    from repro.parallel import sharding as S
    from repro.serve.scheduler import Scheduler, SchedulerConfig

    from bench.weights import make_params

    m = dict(conf["model"])
    for k in ("block_pattern", "tail"):
        if k in m:
            m[k] = tuple(m[k])
    model = build(ModelConfig(**m), RunConfig(**conf["run"]))
    mesh = shardings = None
    if len(devices) > 1:
        mesh = make_mesh((1, len(devices)), ("data", "model"), devices=devices)
        shardings = S.param_shardings(
            jax.eval_shape(model.init, jax.random.PRNGKey(0)), mesh,
            no_fsdp=True)
    params = make_params(model, seed, shardings)
    s = dict(conf["scheduler"])
    s["buckets"] = tuple(s["buckets"])
    # the scheduler's own seed is a constant of its compiled programs, so it
    # stays fixed; each run's fault streams differ through its request ids
    # (``rid_base``), which the programs take as arguments
    scfg = SchedulerConfig(seed=0, **s)
    prot = conf.get("protection")
    policy, backend = None, "reference"
    if prot:
        policy = ft.get_policy(prot["policy"], ber=prot["ber"],
                               weight_faults=prot["weight_faults"])
        backend = prot["backend"]
    return model, params, Scheduler(model, params, scfg, policy=policy,
                                    ft_backend=backend, mesh=mesh)


def rid_base(seed: int) -> int:
    """First request id of a run: drawn from the seed, so that each seed
    draws other per-request fault streams, and below 2**30 so that every id
    fits in int32."""
    from bench.weights import jax_seed
    return jax_seed(seed, 4) & (2**29 - 1)


def cache_dir(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout."""
    return os.fspath(root / ".jax_cache")


def use_compile_cache():
    """Keep every program in the persistent compilation cache at
    ``cache_dir()``, so that only a checkout's first run compiles."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
