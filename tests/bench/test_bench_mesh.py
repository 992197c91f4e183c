"""A cell sharded over its chips: a mesh over the cell's devices, weights
drawn straight into the serving layout, and rooflines and MFU over all the
cell's chips.

The sharded cases run in two subprocesses side by side, each with four CPU
host devices (``--xla_force_host_platform_device_count``; the main process
keeps its one device): the draw on a ``(1, 4)`` mesh is bit-equal to the unsharded one and
lands in the scheduler's layout, and tiny clean and crt3 (``fused``) cells
with 8 query and 4 KV heads, so that the paged pools head-shard, are
``correct`` through ``run.run_cell`` on four devices.  The same clean cell
with the exchange between chips left out (each row-parallel projection keeps
only the first chip's partial sum) is not.  Here the Pallas kernel is
interpreted, a loop XLA can partition; compiled for the chip, it cannot be
(``PERF.md``, section 7)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import tiny_cells
from bench import harness, work

MODEL = dict(tiny_cells.MODEL, n_heads=8, n_kv_heads=4)
SEED = 2**31 + 91


def make_root(tmp):
    """The tiny root, with the clean and crt3 cells on 4 chips
    (``tiny-*-tp4``)."""
    root = tiny_cells.make_root(tmp)
    b = json.loads((root / "BENCHMARK.json").read_text())
    for base in ("clean", "crt3"):
        name = f"{base}-tp4"
        conf = json.loads((root / f"bench/configs/tiny-{base}.json").read_text())
        # one prompt bucket: half the programs to compile
        conf.update(name=f"tiny-{name}", model=MODEL,
                    scheduler=dict(conf["scheduler"], buckets=[32]))
        (root / f"bench/configs/tiny-{name}.json").write_text(json.dumps(conf))
        (root / f"bench/limits/tiny-{name}.json").write_text(
            json.dumps(tiny_cells.LIMITS[f"tiny-{base}"]))
        b["configs"].append({"name": f"tiny-{name}", "source": "test",
                             "reduced": [], "why": "test",
                             "file": f"bench/configs/tiny-{name}.json"})
        b["workloads"].append({"name": f"tiny-{name}", "config": f"tiny-{name}",
                               "traffic": "tiny", "chips": 4, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


# part "clean": the draw, the clean cell, the clean cell with no exchange;
# part "crt3": the crt3 cell.  The two run side by side.
_SHARDED = """
    import json, sys, time
    import jax, numpy as np
    from pathlib import Path
    root, part = Path(sys.argv[1]), sys.argv[2]
    from bench import harness, study, weights
    from repro.models import common
    from repro.parallel import sharding as S
    out = {}
    devices = jax.devices()[:4]

    if part == "clean":
        # the draw: sharded == unsharded, leaf by leaf, in the serving layout
        conf = harness.cell("tiny-clean-tp4", root)["conf"]
        model, params, sched = harness.build_system(conf, %(seed)d, devices)
        plain = weights.make_params(model, %(seed)d)
        want = S.param_shardings(plain, sched.mesh, no_fsdp=True)
        out["leaves"] = len(jax.tree.leaves(params))
        out["equal"] = all(
            np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
            zip(jax.tree.leaves(params), jax.tree.leaves(plain)))
        out["layout"] = all(
            a.sharding.is_equivalent_to(s, a.ndim) for a, s in
            zip(jax.tree.leaves(params), jax.tree.leaves(want)))
        out["split"] = sum(not a.sharding.is_fully_replicated
                           for a in jax.tree.leaves(params))
        out["sched_same"] = all(a is b for a, b in zip(
            jax.tree.leaves(params), jax.tree.leaves(sched.params)))
        del model, params, sched, plain

    run = study.run_module()
    build = harness.build_system
    seen = []

    def watched(conf, seed, devs):
        model, params, sched = build(conf, seed, devs)
        seen.append(sorted({d.id for a in jax.tree.leaves(sched.params)
                            for d in a.sharding.device_set}))
        return model, params, sched

    harness.build_system = watched

    def cell(name):
        w = harness.cell(name, root)
        res, checked = run.run_cell(w, %(seed)d, 0.01, False, devices,
                                    t_start=time.perf_counter(), root=root,
                                    log=lambda s: None)
        return {"correct": res["correct"], "failed": res["failed"],
                "attempted": res["attempted"], "device": res["device"],
                "check": res["check"], "devices": seen[-1],
                "served_tokens": sum(len(g) for _, _, g in checked["sample"])}

    if part == "crt3":
        out["crt3"] = cell("tiny-crt3-tp4")
    else:
        out["clean"] = cell("tiny-clean-tp4")

        # the exchange between chips left out: a row-parallel projection
        # (wo) keeps only the partial sum of the first chip's rows
        linear = common.linear

        def unreduced(x, w, b=None, *, ftc=None, name=""):
            if name.endswith("/wo"):
                k = w.shape[0] // 4
                x, w = x[..., :k], w[:k]
            return linear(x, w, b, ftc=ftc, name=name)

        from repro.models import attention, mlp
        attention.linear = mlp.linear = unreduced
        out["no_exchange"] = cell("tiny-clean-tp4")
    print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def sharded(tmp_path_factory):
    root = make_root(tmp_path_factory.mktemp("mesh"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(tiny_cells.REPO), str(tiny_cells.REPO / "src")])
    code = textwrap.dedent(_SHARDED % {"seed": SEED})
    procs = [subprocess.Popen([sys.executable, "-c", code, str(root), part],
                              stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                              text=True, env=env)
             for part in ("clean", "crt3")]
    got = {}
    try:
        for p in procs:
            out, err = p.communicate(timeout=240)
            assert p.returncode == 0, err[-4000:]
            line = [ln for ln in out.splitlines() if ln.startswith("RESULT ")]
            got.update(json.loads(line[-1][len("RESULT "):]))
    finally:
        for p in procs:
            p.kill()
            p.wait()
    return got


def test_sharded_draw_is_the_unsharded_draw(sharded):
    assert sharded["leaves"] > 0 and sharded["equal"]
    # each leaf carries param_shardings' sharding, so the scheduler's own
    # device_put moves nothing
    assert sharded["layout"] and sharded["sched_same"]
    assert sharded["split"] > 0


@pytest.mark.parametrize("policy", ["clean", "crt3"])
def test_sharded_cell_is_correct_on_four_devices(sharded, policy):
    got = sharded[policy]
    assert got["correct"] and got["failed"] == 0 and got["attempted"] >= 8
    assert got["served_tokens"] > 0
    assert got["device"]["count"] == 4 and got["devices"] == [0, 1, 2, 3]


def test_exchange_left_out_is_caught(sharded):
    got = sharded["no_exchange"]
    c = got["check"]["mean_logit_gap"]
    assert got["failed"] == 0 and not got["correct"]
    assert c["value"] > c["limit"]


def _rec(chips):
    """A traced record of a decode-heavy slice, hand-made."""
    conf = {"model": tiny_cells.MODEL, "scheduler": tiny_cells.SCHED}
    return {"conf": conf, "requests": [(12, 8), (20, 16), (6, 4)],
            "stats": {"chunk_calls": 7, "prefill_calls": 3},
            "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12},
            "chips": chips,
            "trace": {"window_s": 0.5, "modules": {"_chunk": 0.021},
                      "module_calls": {"_chunk": 6.0, "_prefill_one": 2.5}}}


def test_rooflines_and_mfu_are_over_the_cells_chips():
    one, four = _rec(1), _rec(4)
    m, reqs = one["conf"]["model"], one["requests"]
    step_s = 0.021 / (6.0 * 4)
    steps = 7 * 4
    # one chip: the formulas as they were
    want = {
        "decode.hbm_roofline": 100.0 * work.decode_bytes(m, reqs, steps) / steps
        / 819e9 / step_s,
        "decode.mfu": 100.0 * sum(work.request_decode_flops(m, p, n)
                                  for p, n in reqs) / steps / (step_s * 197e12),
        "model.mfu": 100.0 * (
            sum(work.prefill_flops(m, p) for p, _ in reqs) / 3 * 2.5
            + sum(work.request_decode_flops(m, p, n) for p, n in reqs)
            / steps * 6.0 * 4) / (0.5 * 197e12),
    }
    for name, v in want.items():
        read = harness.reader(name)
        assert read(one) == pytest.approx(v, rel=1e-12)
        assert read(four) == pytest.approx(read(one) / 4, rel=1e-12)


def test_one_chip_builds_no_mesh(monkeypatch):
    """Every configuration of the benchmark, on one chip, is served as
    before: no shardings for the draw and no ``mesh=`` for the scheduler."""
    import jax

    import bench.weights
    import repro.serve.scheduler
    got = {}

    def draw(model, seed, shardings=None):
        got["shardings"] = shardings
        return "params"

    class Sched:
        def __init__(self, model, params, cfg, **kw):
            got.update(kw)

    monkeypatch.setattr(bench.weights, "make_params", draw)
    monkeypatch.setattr(repro.serve.scheduler, "Scheduler", Sched)
    for c in harness.benchmark()["configs"]:
        got.clear()
        conf = harness.load_json(harness.ROOT / c["file"])
        harness.build_system(conf, 1, jax.devices()[:1])
        assert got["shardings"] is None and got["mesh"] is None, c["name"]
