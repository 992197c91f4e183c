"""Read a profiler trace (``.xplane.pb``) once, and reduce it to device
busy time, time and calls per compiled module, self time per operation, and
idle gaps labelled by what the host was doing.

On the TPU each ``/device:TPU:<n>`` plane has an ``XLA Modules`` line (one
event per run of an executable) and an ``XLA Ops`` line (one event per
operation run, nested: a loop's event holds its body's).  A module's time is
the union of its module events; the device is busy where any module or op
event runs.  Each op is given the module whose event contains it, and its
self time (its length less that of the ops nested in it).  Op events are
named by their HLO text; the name kept is the instruction's
(``%fused_decode.3 = ...`` -> ``fused_decode.3``).  The host's activity is
the events of its main thread's lines: the harness's ``TraceAnnotation``
spans and the runtime's own.  Program spans are the host events named
``serve.*`` (the scheduler's) or ``bench.*`` (the harness's), on any line.

A trace of the CPU backend is read the same way (operations are the events
with an ``hlo_op`` stat, and no module events exist, so a module's time is
the union of its ops), so the reduction is tested without a chip.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

MIN_GAP_NS = 10_000          # shorter idle gaps are summed, not labelled
SHORT_GAPS = "(gaps under 10 us)"
HOST_LINES = ("python", "main")
PROGRAM_SPANS = ("serve.", "bench.")
NO_SPAN = "(no program span)"
TPU_PLANE = re.compile(r"/device:TPU:(\d+)")


def module_name(raw: str) -> str:
    """``jit__chunk(123)`` -> ``_chunk``."""
    raw = re.sub(r"\(\d+\)$", "", raw or "")
    return raw[4:] if raw.startswith("jit_") else raw


def op_name(raw: str) -> str:
    """``%fused_decode.3 = (s8[8,6912]...) custom-call(...)`` ->
    ``fused_decode.3``."""
    return raw.split(" = ", 1)[0].lstrip("%")


def _stats(e) -> dict:
    try:
        return dict(e.stats)
    except Exception:
        return {}


def xplane_file(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


class Trace(NamedTuple):
    """What ``load`` reads from a trace.

    devices: {plane: {"mods": [(start_ns, end_ns, program)], "ops":
    [(start_ns, end_ns, program, op)]}}, programs named as the profiler names
    them (``jit__chunk(12)``); host: (start_ns, end_ns, name) of the main
    thread's events; spans: the program spans; marks: {name: (start_ns,
    end_ns)} of the first program span of each ``bench.*`` name; xspace: the
    serialized trace, for its HLO protos (``bench/scopes.py``)."""
    devices: dict
    host: list
    spans: list
    marks: dict
    xspace: bytes


def load(path: str, device_ids=None) -> Trace:
    """Read a trace once.  With ``device_ids``, only the TPU planes of those
    device ids are kept, so that chips a cell does not use add nothing to
    the means over its chips."""
    from jax.profiler import ProfileData
    raw = Path(path).read_bytes()
    pd = ProfileData.from_serialized_xspace(raw)
    devices, host, spans, marks, cpu_ops = {}, [], [], {}, []
    on_tpu = any(TPU_PLANE.match(p.name) for p in pd.planes)
    for plane in pd.planes:
        if TPU_PLANE.match(plane.name):
            mods, ops = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend((e.start_ns, e.start_ns + e.duration_ns, e.name)
                                for e in line.events)
                elif line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns, "",
                                op_name(e.name)) for e in line.events)
            devices[plane.name] = {"mods": mods, "ops": _attribute(ops, mods)}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                main = line.name.startswith(HOST_LINES)
                for e in line.events:
                    span = (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    if main:
                        host.append(span)
                    if e.name.startswith(PROGRAM_SPANS):
                        spans.append(span)
                        if e.name.startswith("bench."):
                            marks.setdefault(e.name, span[:2])
                        continue
                    st = {} if on_tpu else _stats(e)
                    if "hlo_op" in st:
                        prog = f"{st.get('hlo_module', '')}({st.get('program_id', '')})"
                        cpu_ops.append((span[0], span[1], prog, op_name(e.name)))
    if not devices and cpu_ops:
        devices["cpu"] = {"mods": [], "ops": sorted(cpu_ops)}
    if device_ids is not None:
        devices = on_devices(devices, device_ids)
    return Trace(devices, host, spans, marks, raw)


def on_devices(devices: dict, device_ids) -> dict:
    """``devices`` without the TPU planes of other device ids than
    ``device_ids`` (the plane ``/device:TPU:<n>`` is device id n)."""
    out = {}
    for name, dev in devices.items():
        tpu = TPU_PLANE.match(name)
        if not tpu or int(tpu.group(1)) in device_ids:
            out[name] = dev
    return out


def _attribute(ops, mods):
    """Give each op without a module the module event that contains it."""
    mods = sorted(mods)
    out, j = [], 0
    for s, e, mod, name in sorted(ops):
        if not mod:
            while j < len(mods) and mods[j][1] < s:
                j += 1
            if j < len(mods) and mods[j][0] <= s:
                mod = mods[j][2]
        out.append((s, e, mod, name))
    return out


def self_times(ops):
    """Each op's length less the length of the ops nested directly in it.
    ops: (start, end, module, name), sorted or not."""
    out, stack = [], []          # stack of [start, end, module, name, child]
    for s, e, mod, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[1], top[2], top[3], top[1] - top[0] - top[4]))
        if stack:
            stack[-1][4] += e - s
        stack.append([s, e, mod, name, 0])
    while stack:
        top = stack.pop()
        out.append((top[0], top[1], top[2], top[3], top[1] - top[0] - top[4]))
    return out


def union(intervals, lo, hi):
    """Merged (start, end) intervals clipped to [lo, hi]."""
    out = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _covered(iv) -> float:
    return float(sum(e - s for s, e in iv))


def _overlap(s, e, lo, hi) -> float:
    return max(0, min(e, hi) - max(s, lo))


class _HostIndex:
    """Host spans binned by time, to find the innermost span (the shortest
    one) that covers a point."""

    BIN = 10_000_000          # 10 ms

    def __init__(self, host):
        self.bins = defaultdict(list)
        for s, e, name in host:
            for b in range(int(s // self.BIN), int(e // self.BIN) + 1):
                self.bins[b].append((s, e, name))

    def label(self, t) -> str:
        best = None
        for s, e, name in self.bins.get(int(t // self.BIN), ()):
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, name)
        return best[2] if best else "(no host span)"


def reduce(devices: dict, host: list, lo: int, hi: int, top: int = 10,
           spans: list = ()):
    """Over the window [lo, hi] ns, averaged over devices: busy seconds,
    seconds and calls (a call cut by the window counts by its share) per
    module (named by ``module_name``), self seconds, whole seconds and calls
    per op (keyed ``module/op``), the ``top`` ops by self time, the ``top``
    host activities under idle gaps, and ``idle_by_span``: each idle gap
    split, by time, over the innermost (shortest) program span of ``spans``
    covering each part of it (``NO_SPAN`` where none does), so that its
    values sum to the window less the busy time.  The runtime's own spans
    nested in a program span (a device-to-host copy, a dispatch) are not
    program spans: a gap under ``serve.readback`` counts there whatever the
    runtime was doing."""
    n = max(len(devices), 1)
    index = _HostIndex([h for h in host if h[1] >= lo and h[0] <= hi])
    spans = [sp for sp in spans if sp[1] > lo and sp[0] < hi]
    by_span = defaultdict(float)
    busy = 0.0
    modules, calls = defaultdict(float), defaultdict(float)
    ops, op_calls, gaps = defaultdict(float), defaultdict(float), defaultdict(float)
    op_total = defaultdict(float)
    for dev in devices.values():
        mods, evs = dev["mods"], dev["ops"]
        iv = union([(s, e) for s, e, _ in mods]
                   + [(s, e) for s, e, _, _ in evs], lo, hi)
        busy += _covered(iv)
        by_mod = defaultdict(list)
        for s, e, mod in mods:
            mod = module_name(mod)
            if _overlap(s, e, lo, hi):
                by_mod[mod].append((s, e))
                calls[mod] += _overlap(s, e, lo, hi) / max(e - s, 1) / n
        if not mods:                         # CPU: modules from their ops
            for s, e, mod, _ in evs:
                by_mod[module_name(mod)].append((s, e))
        for mod, v in by_mod.items():
            modules[mod] += _covered(union(v, lo, hi)) / 1e9 / n
        for s, e, mod, name, own in self_times(evs):
            if _overlap(s, e, lo, hi):
                key = f"{module_name(mod)}/{name}"
                ops[key] += own * _overlap(s, e, lo, hi) / max(e - s, 1) / 1e9 / n
                op_total[key] += _overlap(s, e, lo, hi) / 1e9 / n
                op_calls[key] += 1 / n
        prev = lo
        for s, e in iv + [[hi, hi]]:
            if s > prev:
                for name, t in _split(spans, prev, s):
                    by_span[name] += t / 1e9 / n
            if s - prev >= MIN_GAP_NS:
                gaps[index.label((s + prev) // 2)] += (s - prev) / 1e9 / n
            elif s > prev:
                gaps[SHORT_GAPS] += (s - prev) / 1e9 / n
            prev = max(prev, e)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n,
        "modules": dict(modules),
        "module_calls": dict(calls),
        "ops": dict(ops),
        "op_total": dict(op_total),
        "op_calls": dict(op_calls),
        "device_ops": [[k, v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v] for k, v in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
        "idle_by_span": dict(by_span),
    }


def _split(spans, a, b):
    """(innermost span name, ns) for each part of [a, b] between the
    boundaries of the spans that overlap it."""
    over = [sp for sp in spans if sp[0] < b and sp[1] > a]
    cuts = sorted({a, b} | {t for sp in over for t in sp[:2] if a < t < b})
    for x, y in zip(cuts, cuts[1:]):
        inner = [sp for sp in over if sp[0] <= x and sp[1] >= y]
        yield (min(inner, key=lambda sp: sp[1] - sp[0])[2] if inner
               else NO_SPAN), y - x
