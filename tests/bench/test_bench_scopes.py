"""The scheduler's host spans and counters, and ``bench/scopes.py``: a tiny
crt3 cell served under the profiler on the CPU, and hand-made events."""
import jax
import pytest

import tiny_cells
from bench import harness, scopes
from bench import trace as tr
from repro.serve.scheduler import PHASES, Request

RIDS = (11, 12, 13)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(scheduler stats, trace path) of three requests of the tiny crt3
    cell served under the profiler, once compiled."""
    conf = {"model": tiny_cells.MODEL, "scheduler": tiny_cells.SCHED,
            "protection": tiny_cells.CRT3,
            "run": {"param_dtype": "bfloat16", "compute_dtype": "bfloat16"}}
    _, _, sched = harness.build_system(conf, 5, jax.devices()[:1])

    def reqs(base):
        return [Request(base + r, [1 + i] * (4 + 5 * i), max_new_tokens=5)
                for i, r in enumerate(RIDS)]

    sched.run(reqs(100))
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.slice"):
        sched.run(reqs(0))
    jax.profiler.stop_trace()
    return sched.stats, tr.xplane_file(d)


def _host_events(path):
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            out.extend((e.name, tr._stats(e)) for line in plane.lines
                       for e in line.events if e.name.startswith("serve."))
    return out


def test_trace_holds_the_serving_spans(served):
    _, path = served
    events = _host_events(path)
    admits = [st for name, st in events if name == "serve.admit"]
    assert sorted(st["rid"] for st in admits) == sorted(RIDS)
    names = {name for name, _ in events}
    assert {"serve.chunk", "serve.readback", "serve.prefill",
            "serve.first_token", "serve.insert", "serve.harvest"} <= names
    assert all(0 < st["active"] <= len(RIDS)
               for name, st in events if name == "serve.chunk")


def test_stats_count_every_phase(served):
    stats, _ = served
    assert set(stats.host_s) == set(PHASES) == set(stats.host_max_s)
    for p in PHASES:
        assert 0 < stats.host_max_s[p] <= stats.host_s[p]
    # admit holds its prefill, first-token wait and insert
    assert stats.host_s["serve.admit"] >= (
        stats.host_s["serve.prefill"] + stats.host_s["serve.insert"])
    # four copies a chunk, one first token a request
    assert stats.readbacks == 4 * stats.chunk_calls + stats.prefill_calls


def test_scopes_split_the_chunk(served):
    _, path = served
    t = tr.load(path)
    maps = scopes.program_scopes(t.xspace)
    assert any(p.startswith("jit__chunk(") for p in maps)
    lo, hi = scopes.window(t.spans, t.devices)
    got = scopes.scope_times(t.devices, maps, lo, hi)
    chunk, prefill = got["_chunk"], got["_prefill_one"]
    assert chunk["linear/protect"] > 0 and chunk["attention"] > 0
    assert prefill["linear/protect"] > 0 and prefill["attention"] > 0
    assert scopes.NO_HLO not in chunk
    idle = tr.reduce(t.devices, t.host, lo, hi, spans=t.spans)["idle_by_span"]
    assert idle.get("serve.readback", 0) > 0
    assert tr.NO_SPAN not in idle          # the slice covers the whole run


def test_scope_of():
    assert scopes.scope_of("jit(_chunk)/while/body/linear/protect/dot") == (
        "linear/protect")
    assert scopes.scope_of("jit(f)/attention/exp") == "attention"
    assert scopes.scope_of("jit(f)/linear/protect/jit(g)/jit(f)/linear/"
                           "protect/add") == "linear/protect"
    assert scopes.scope_of("jit(f)/jit(fused_protect_linear)/add") == (
        scopes.OTHER)


def test_scope_times_hand_made():
    # one _chunk run, ns: a loop [0, 100) whose body ops are a projection
    # [0, 40), attention [40, 70) and a copy [70, 90); the loop keeps 10
    hlo = {"jit__chunk(3)": {"while.1": scopes.OTHER, "fusion.2": "linear",
                             "fusion.3": "attention", "copy-done": scopes.OTHER}}
    ops = [(0, 100, "jit__chunk(3)", "while.1"),
           (0, 40, "jit__chunk(3)", "fusion.2"),
           (40, 70, "jit__chunk(3)", "fusion.3"),
           (70, 90, "jit__chunk(3)", "copy-done"),
           (120, 130, "jit__prefill_one(4)", "fusion.9")]
    devices = {"d0": {"mods": [(0, 100, "jit__chunk(3)"),
                               (120, 130, "jit__prefill_one(4)")], "ops": ops}}
    got = scopes.scope_times(devices, hlo, 0, 200)
    chunk = got["_chunk"]
    assert chunk == pytest.approx({"linear": 40e-9, "attention": 30e-9,
                                   scopes.OTHER: 30e-9}, abs=1e-18)
    # the parts sum to the module's time, as bench/trace.py counts it
    red = tr.reduce({"d0": {"mods": [(s, e, tr.module_name(m))
                                     for s, e, m in devices["d0"]["mods"]],
                            "ops": [(s, e, tr.module_name(m), o)
                                    for s, e, m, o in ops]}}, [], 0, 200)
    assert sum(chunk.values()) == pytest.approx(red["modules"]["_chunk"],
                                                abs=1e-18)
    assert got["_prefill_one"] == {scopes.NO_HLO: pytest.approx(10e-9)}


def test_idle_by_span_hand_made():
    us = 1000
    devices = {"d0": {"mods": [(0, 10 * us, "m"), (60 * us, 70 * us, "m")],
                      "ops": []}}
    spans = [(0, 100 * us, "bench.slice"), (5 * us, 40 * us, "serve.readback"),
             (40 * us, 65 * us, "serve.admit"), (45 * us, 50 * us, "serve.prefill")]
    got = tr.reduce(devices, [], 0, 110 * us, spans=spans)["idle_by_span"]
    assert got == pytest.approx({"serve.readback": 30e-6, "serve.admit": 15e-6,
                                 "serve.prefill": 5e-6, "bench.slice": 30e-6,
                                 tr.NO_SPAN: 10e-6}, abs=1e-15)
    assert sum(got.values()) == pytest.approx((110 - 20) * 1e-6)
