"""The trace reduction, on hand-made events and on a small trace recorded on
the CPU."""
import time

import jax
import jax.numpy as jnp
import pytest

import tiny_cells  # noqa: F401
from bench import trace as tr


def test_reduce_hand_made():
    # two devices; ops in ns.  dev0 busy [0,10) [10,30) [50,60); dev1 [0,40)
    # two devices, ns.  d0: a _chunk module run [0, 30) holding a while loop
    # [0, 30) whose body ops are [0, 10) and [10, 25); a _prefill_one run
    # [50, 60) cut by the window at 55.  d1: one _chunk run [0, 40).
    devs = {
        "d0": {"mods": [(0, 30, "_chunk"), (50, 60, "_prefill_one")],
               "ops": [(0, 30, "_chunk", "while.1"),
                       (0, 10, "_chunk", "fused_decode.1"),
                       (10, 25, "_chunk", "fusion.2"),
                       (50, 60, "_prefill_one", "fusion.3")]},
        "d1": {"mods": [(0, 40, "_chunk")],
               "ops": [(0, 40, "_chunk", "fusion.2")]},
    }
    host = [(0, 100, "bench.wave"), (30, 50, "PjitFunction(_prefill_one)")]
    r = tr.reduce(devs, host, 0, 55)
    assert r["window_s"] == 55e-9
    assert abs(r["busy_s"] - (35 + 40) / 2 * 1e-9) < 1e-18
    assert abs(r["modules"]["_chunk"] - (30 + 40) / 2 * 1e-9) < 1e-18
    assert r["module_calls"] == {"_chunk": 1.0, "_prefill_one": 0.25}
    # self time: the loop keeps 30 - 10 - 15 = 5 ns on d0
    assert abs(r["ops"]["_chunk/while.1"] - 5 / 2 * 1e-9) < 1e-18
    assert abs(r["op_total"]["_chunk/while.1"] - 30 / 2 * 1e-9) < 1e-18
    assert abs(r["ops"]["_chunk/fusion.2"] - (15 + 40) / 2 * 1e-9) < 1e-18
    assert r["op_calls"]["_chunk/fusion.2"] == 1.0
    assert r["device_ops"][0][0] == "_chunk/fusion.2"
    # gaps: 20 ns on dev0 under the prefill dispatch are labelled by it only
    # when long enough; all of these are shorter than MIN_GAP_NS
    assert dict(r["idle_gaps"])[tr.SHORT_GAPS] > 0


def test_gap_labels_innermost_host_span():
    us = 1000
    devs = {"d0": {"mods": [], "ops": [(0, 10 * us, "m", "a"),
                                       (60 * us, 70 * us, "m", "b")]}}
    host = [(0, 100 * us, "bench.wave"), (20 * us, 50 * us, "PjitFunction(f)")]
    r = tr.reduce(devs, host, 0, 100 * us)
    gaps = dict(r["idle_gaps"])
    assert abs(gaps["PjitFunction(f)"] - 50e-6) < 1e-12        # [10, 60) us
    assert abs(gaps["bench.wave"] - 30e-6) < 1e-12             # [70, 100) us


def test_names():
    assert tr.module_name("jit__chunk(123)") == "_chunk"
    assert tr.module_name("jit_step") == "step"
    assert tr.op_name("%fused_decode.3 = (s8[8,6912]{1,0}) custom-call(%x)") == (
        "fused_decode.3")


def test_self_times_nested():
    got = {n: own for _, _, _, n, own in tr.self_times(
        [(0, 100, "m", "outer"), (10, 20, "m", "a"), (30, 60, "m", "mid"),
         (35, 40, "m", "b")])}
    assert got == {"outer": 60, "a": 10, "mid": 25, "b": 5}


def test_cpu_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.slice"):
        for _ in range(3):
            f(x).block_until_ready()
        time.sleep(0.02)
    jax.profiler.stop_trace()
    t = tr.load(tr.xplane_file(str(tmp_path)))
    r = tr.reduce(t.devices, t.host, *t.marks["bench.slice"])
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["modules"]["_lambda"] > 0
    assert sum(n for k, n in r["op_calls"].items() if "dot" in k) >= 6
    # the sleep at the end of the span is idle time under the harness's span
    assert dict(r["idle_gaps"]).get("bench.slice", 0) >= 0.015


def test_planes_of_other_chips_are_left_out():
    """A chip that the cell does not use adds nothing to the means over the
    cell's chips: a trace with one more, idle, plane reads as without it."""
    us = 1000
    used = {"/device:TPU:0": {"mods": [(0, 30 * us, "jit__chunk(3)")],
                              "ops": [(0, 30 * us, "jit__chunk(3)", "fusion.2")]}}
    host = [(0, 100 * us, "bench.slice")]
    spans = [(0, 100 * us, "bench.slice")]
    alone = tr.reduce(used, host, 0, 100 * us, spans=spans)
    idle_chip = dict(used, **{"/device:TPU:1": {"mods": [], "ops": []}})
    assert tr.reduce(tr.on_devices(idle_chip, {0}), host, 0, 100 * us,
                     spans=spans) == alone
    # read over both planes, every time would be halved
    both = tr.reduce(idle_chip, host, 0, 100 * us, spans=spans)
    assert both["modules"]["_chunk"] == pytest.approx(
        alone["modules"]["_chunk"] / 2)
