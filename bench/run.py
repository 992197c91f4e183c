#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix, each a
data file under ``bench/`` (see ``bench/harness.py``).  A run

1. draws the weights on the device from ``--seed`` in one jitted call and
   serves one request per prompt bucket the traffic uses, so that every
   program the window runs is compiled (or read from the compile cache at
   ``.jax_cache/`` in the checkout) before the window opens: ``setup_s``;
2. hands ``Scheduler.run`` waves of requests from the traffic generator,
   back to back.  The measured window is the first ``--seconds`` seconds of
   serving: a thread polls each request's token list about every
   millisecond and stamps when it grows, as a streaming client would see
   it, and the tokens it has seen by the close are the window's.  No wave
   starts after the close; the one running then is served to its end, so
   that every request is checked and has its time per output token;
3. checks what the window served against the configuration's plain
   reference (``bench/check.py``), after the program's state is freed;
4. prints the metrics: with ``--trace 0`` the cell's end-to-end metrics, with
   ``--trace 1`` its per-layer metrics, read from a profiler trace of the
   first ``SLICE_S`` seconds of its one wave (that run serves one wave),
   divided by the cell's chips.

The last line of standard output is one JSON object; the numbers compared
for ``correct`` come last in it, under ``check``, and as the last lines of
standard error.  Without a TPU, or with fewer chips than the cell asks for,
it prints no result and exits 2.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import check, harness, traffic  # noqa: E402

WARM_RID = 1 << 30     # request ids of the warm-up, apart from the window's
# the traced slice, the first seconds of the wave, on one chip.  Stopping the
# profiler takes time in proportion to the device events it holds, which
# grow with the chips traced (10 s of one chip took 60-200 s to stop, of four
# 614 s), so a cell on n chips traces SLICE_S / n seconds.
SLICE_S = 10.0


class CompileClock:
    """Seconds and count of XLA backend compiles, from JAX's own monitoring
    events (tracing is left out: nested jits would count twice)."""

    def __init__(self):
        import jax
        self.seconds, self.count = 0.0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.count += 1


class Poller(threading.Thread):
    """Stamps when each watched request's token count grows.

    With ``trace_dir`` it also takes the profiler trace of one slice: from
    its first poll, just before the wave starts, for ``slice_s`` seconds or
    to the end of the wave, under a ``bench.slice`` span.  A slice, not the
    whole wave, keeps the trace small enough to write and read within a
    run."""

    def __init__(self, period: float = 1e-3, trace_dir: str | None = None,
                 slice_s: float = SLICE_S):
        super().__init__(daemon=True)
        self.period, self.reqs, self.seen = period, [], {}
        self.t_close, self.count, self.in_window = float("inf"), 0, 0
        self.first, self.last = {}, {}
        self.late, self._stop_evt = [], threading.Event()
        self.worst, self.worst_at = 0.0, 0.0    # the latest poll, and when
        self._lock = threading.Lock()
        self.trace_dir, self._slice, self._t_slice = trace_dir, None, None
        self.slice_s = slice_s
        self.sliced = False

    def watch(self, reqs):
        with self._lock:
            self.reqs = list(reqs)

    def open_window(self, seconds: float) -> float:
        """Open the measured window now; tokens seen by ``t_close`` are its."""
        with self._lock:
            t_open = time.perf_counter()
            self.t_close = t_open + seconds
            return t_open

    def poll(self):
        with self._lock:
            now = time.perf_counter()
            for r in self.reqs:
                n = len(r.generated)
                if n != self.seen.get(r.rid, 0):
                    self.count += n - self.seen.get(r.rid, 0)
                    self.seen[r.rid] = n
                    self.first.setdefault(r.rid, now)
                    self.last[r.rid] = now
            if now <= self.t_close:
                self.in_window = self.count
            if self.trace_dir and not self.sliced:
                if self._slice is None:
                    self._start_slice(now)
                elif now - self._t_slice >= self.slice_s:
                    self._end_slice()

    def _start_slice(self, now):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # host spans are TraceMe only
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        self._slice = jax.profiler.TraceAnnotation("bench.slice")
        self._slice.__enter__()
        self._t_slice = now

    def _end_slice(self):
        import jax
        self._slice.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.sliced = True

    def end_slice(self):
        """Close a slice still open at the end of the wave."""
        with self._lock:
            if self._slice is not None and not self.sliced:
                self._end_slice()

    def run(self):
        prev = time.perf_counter()
        while not self._stop_evt.is_set():
            self.poll()
            time.sleep(self.period)
            now = time.perf_counter()
            self.late.append(now - prev - self.period)
            if self.late[-1] > self.worst:
                self.worst, self.worst_at = self.late[-1], now
            prev = now

    def stop(self):
        self._stop_evt.set()
        self.join()

    def tpot_ms(self, rid, n):
        """(last - first) / (n - 1) of a request that produced n tokens."""
        if n < 2 or rid not in self.first:
            return None
        return (self.last[rid] - self.first[rid]) / (n - 1) * 1e3


def warmup_requests(conf: dict, spec: dict) -> list:
    """One request per prompt bucket the traffic reaches, at the longest
    traffic prompt in that bucket, producing one decode chunk and a
    retirement."""
    from repro.serve.scheduler import Request
    buckets = sorted(conf["scheduler"]["buckets"])
    longest = {}
    for n in traffic.quantile_lengths(spec["prompt"], spec["wave"]):
        b = next(b for b in buckets if n <= b)
        longest[b] = max(longest.get(b, 0), n)
    new = conf["scheduler"]["decode_chunk"] + 1
    return [Request(WARM_RID + i, [1] * n, max_new_tokens=new)
            for i, (_, n) in enumerate(sorted(longest.items()))]


def _sum_stats(stats) -> dict:
    """The waves' ``SchedStats`` as one: counts and host seconds per phase
    summed, peaks and each phase's longest span the largest."""
    keys = ("prefill_calls", "insert_calls", "chunk_calls", "retire_calls",
            "tokens", "roundtrips", "readbacks")
    out = {k: sum(getattr(s, k) for s in stats) for k in keys}
    out["blocks_in_use_peak"] = max(s.blocks_in_use_peak for s in stats)
    out["host_s"], out["host_max_s"] = {}, {}
    for s in stats:
        for k, v in s.host_s.items():
            out["host_s"][k] = out["host_s"].get(k, 0.0) + v
        for k, v in s.host_max_s.items():
            out["host_max_s"][k] = max(out["host_max_s"].get(k, 0.0), v)
    return out


def _memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def run_cell(w: dict, seed: int, seconds: float, trace: bool, devices,
             peak: dict | None = None, t_start: float | None = None,
             root: Path = ROOT, log=print):
    """Serve the cell's window and check it.  Returns the result object that
    the last line prints (with ``check`` last), and what was checked: the
    ``sample`` of served requests, ``(rid, prompt, generated)`` each, and
    every ``number`` read from it."""
    import jax
    from repro.serve.scheduler import Request

    t_start = T_START if t_start is None else t_start
    clock = CompileClock()
    conf, spec = w["conf"], w["traffic_spec"]
    vocab = conf["model"]["vocab"]
    t0 = time.perf_counter()
    model, params, sched = harness.build_system(conf, seed, devices)
    jax.block_until_ready(params)
    t1 = time.perf_counter()
    sched.run(warmup_requests(conf, spec))
    t2 = time.perf_counter()
    setup_s = t2 - t_start
    setup_compiles, setup_compile_s = clock.count, clock.seconds
    log(f"setup: setup_s={setup_s} before_build_s={t0 - t_start} "
        f"weights_and_build_s={t1 - t0} warmup_s={t2 - t1} "
        f"compile_s={setup_compile_s} compiles={setup_compiles}")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    poller = Poller(trace_dir=trace_dir, slice_s=SLICE_S / len(devices))
    poller.start()
    gen = traffic.waves(spec, seed, vocab, harness.rid_base(seed))
    done, stats, n_waves = [], [], 0
    # no collection pauses while serving: what set-up made is frozen out of
    # the collector's sight, and the window's own garbage waits to the end
    gc.collect()
    gc.freeze()
    gc.disable()
    t_open = poller.open_window(seconds)
    try:
        while True:
            reqs = [Request(rid, toks, max_new_tokens=o)
                    for rid, toks, o in next(gen)]
            poller.watch(reqs)
            with jax.profiler.TraceAnnotation("bench.wave"):
                sched.run(reqs)
            poller.poll()
            t_end = time.perf_counter()
            stats.append(sched.stats)
            n_waves += 1
            done.extend(reqs)
            if trace or t_end >= poller.t_close:
                break
    finally:
        gc.enable()
        gc.unfreeze()
        poller.end_slice()
        poller.stop()
    window_s = seconds if not trace else t_end - t_open
    tokens = poller.in_window if not trace else poller.count
    window_compiles = clock.count - setup_compiles

    failed = [r for r in done if r.finish_reason != "length"
              or len(r.generated) != r.max_new_tokens]
    tpot = [poller.tpot_ms(r.rid, len(r.generated)) for r in done]
    tpot = [t for t in tpot if t is not None]
    late = sorted(poller.late) or [0.0]
    log(f"window: waves={n_waves} sent={len(done)} "
        f"completed={len(done) - len(failed)} failed={len(failed)} "
        f"tokens_in_window={tokens} window_s={window_s} "
        f"served_tokens={sum(len(r.generated) for r in done)} "
        f"served_s={t_end - t_open} "
        f"compiles_in_window={window_compiles} poller_polls={len(late)} "
        f"poller_late_ms_p99={late[int(0.99 * (len(late) - 1))] * 1e3} "
        f"poller_late_ms_max={late[-1] * 1e3} "
        f"poller_late_max_at_s={poller.worst_at - t_open}")

    mem_peak = _memory_peak(devices)
    bad = {r.rid for r in failed}
    served = [(r.rid, list(r.tokens), list(r.generated)) for r in done
              if r.rid not in bad]
    rec = {
        "conf": conf, "traffic": spec, "setup_s": setup_s,
        "window_s": window_s, "tokens": tokens, "tpot_ms": tpot,
        "requests": [(len(r.tokens), len(r.generated)) for r in done],
        "stats": _sum_stats(stats), "memory_peak_bytes": mem_peak,
        "peaks": peak, "chips": len(devices), "trace": None,
    }
    log(f"counters: {json.dumps(rec['stats'])}")
    del model, params, sched, done, reqs
    gc.collect()

    reqs = check.sample(served, seed, spec["check_tokens"])
    t3 = time.perf_counter()
    ref = harness.reference(conf["reference"], root)
    nums = (check.logit_gaps(ref, conf, seed, reqs) if reqs else
            {k: float("inf") for k in w["limits"]["limits"]})
    judged = check.judge(nums, w["limits"]["limits"])
    log(f"check: {json.dumps(nums)} sample={len(reqs)} requests, "
        f"{sum(len(g) for _, _, g in reqs)} served tokens, "
        f"reference_s={time.perf_counter() - t3}")

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": mem_peak}
    ok = all(c["value"] <= c["limit"] for c in judged.values())
    result = {"correct": bool(not failed and ok),
              "attempted": len(rec["requests"]), "failed": len(failed)}
    if trace:
        from bench import scopes
        from bench import trace as tr
        t4 = time.perf_counter()
        t = tr.load(tr.xplane_file(trace_dir), {d.id for d in devices})
        lo, hi = t.marks["bench.slice"]
        rec["trace"] = tr.reduce(t.devices, t.host, lo, hi, spans=t.spans)
        rec["trace"]["scopes"] = scopes.scope_times(
            t.devices, scopes.program_scopes(t.xspace), lo, hi)
        del t
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        log(f"trace: read_s={time.perf_counter() - t4} "
            f"modules={json.dumps(rec['trace']['modules'])} "
            f"calls={json.dumps(rec['trace']['module_calls'])} "
            f"scopes={json.dumps(rec['trace']['scopes'])} "
            f"idle_by_span={json.dumps(rec['trace']['idle_by_span'])}")
        result["metrics"] = harness.read_metrics(w["per_layer"], rec, root)
        result["breakdown"] = {k: rec["trace"][k]
                               for k in ("device_ops", "idle_gaps")}
    else:
        result["metrics"] = harness.read_metrics(w["end_to_end"], rec, root)
    result["device"] = device
    result["check"] = judged
    return result, {"sample": reqs, "numbers": nums}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    w = harness.cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        print(f"bench: the cell needs {w['chips']} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    harness.use_compile_cache()
    from bench.peaks import peaks
    devices = devices[:w["chips"]]
    peak = peaks(devices[0].device_kind)
    result, _ = run_cell(w, args.seed, args.seconds, bool(args.trace), devices,
                      peak=peak)
    for name, c in result["check"].items():
        print(f"check {name}: value={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
