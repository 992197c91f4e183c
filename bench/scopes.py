#!/usr/bin/env python3
"""Put a profiler trace's device time down to the model's named scopes, and
its idle time down to the serving phases of ``Scheduler.run``.

  python3 bench/scopes.py <trace dir or .xplane.pb> [--window <span>]

prints one JSON object: ``scopes``, self seconds per module and scope, and
``idle_by_span``, idle seconds per program span (``bench/trace.py``'s
``reduce``), over the first ``<span>`` of the trace (by default
``bench.slice``, the benchmark's traced slice; the whole trace where there is
none), averaged over devices.

**Scopes.** ``jax.named_scope`` lands in the ``metadata.op_name`` of each
compiled HLO instruction (a fusion carries its root's).  The profiler writes
the HLO of every program that ran while it traced into the trace's
``/host:metadata`` plane: one event metadata per program, named like the
program's module events (``jit__chunk(12)``), with an ``Hlo Proto`` stat
that holds the serialized ``xla.HloProto``.  ``jax.profiler.ProfileData``
does not expose that plane's metadata, so this module reads the few fields
it needs with ``google.protobuf`` and a schema of just those fields.  An
op's scope is the path of the names in ``SCOPES`` along its ``op_name``
(``linear``, ``linear/protect``, ``attention``), or ``OTHER`` outside them;
its self time is as in ``bench/trace.py``.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace as tr  # noqa: E402

SCOPES = ("linear", "protect", "attention")
OTHER = "(other)"
NO_HLO = "(no hlo)"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


# ---- the few protobuf fields read -------------------------------------------
# Field numbers of tsl/profiler/protobuf/xplane.proto and xla/service/hlo.proto;
# every other field is kept as unknown bytes and never read.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane", True)],
    "XPlane": [("name", 2, str, False),
               ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
               ("stat_metadata", 5, "XPlane.StatMetadataEntry", True)],
    "XPlane.EventMetadataEntry": [("key", 1, int, False),
                                  ("value", 2, "XEventMetadata", False)],
    "XPlane.StatMetadataEntry": [("key", 1, int, False),
                                 ("value", 2, "XStatMetadata", False)],
    "XEventMetadata": [("name", 2, str, False), ("stats", 5, "XStat", True)],
    "XStatMetadata": [("name", 2, str, False)],
    "XStat": [("metadata_id", 1, int, False), ("bytes_value", 6, bytes, False)],
    "HloProto": [("hlo_module", 1, "HloModuleProto", False)],
    "HloModuleProto": [("computations", 3, "HloComputationProto", True)],
    "HloComputationProto": [("instructions", 2, "HloInstructionProto", True)],
    "HloInstructionProto": [("name", 1, str, False),
                            ("metadata", 7, "OpMetadata", False)],
    "OpMetadata": [("op_name", 2, str, False)],
}
_PACKAGE = "bench_scopes"
_classes = {}


def _message(name: str):
    """The message class of one ``_SCHEMA`` entry, built on first use."""
    if not _classes:
        from google.protobuf import descriptor_pb2, descriptor_pool, message_factory
        F = descriptor_pb2.FieldDescriptorProto
        kinds = {str: F.TYPE_STRING, int: F.TYPE_INT64, bytes: F.TYPE_BYTES}
        fdp = descriptor_pb2.FileDescriptorProto(
            name="bench_scopes.proto", package=_PACKAGE, syntax="proto3")
        for msg in sorted(_SCHEMA, key=lambda m: m.count(".")):
            outer, _, inner = msg.rpartition(".")
            if outer:
                d = next(m for m in fdp.message_type if m.name == outer).nested_type.add(
                    name=inner)
                d.options.map_entry = True
            else:
                d = fdp.message_type.add(name=msg)
            for fname, num, kind, rep in _SCHEMA[msg]:
                f = d.field.add(name=fname, number=num,
                                label=F.LABEL_REPEATED if rep else F.LABEL_OPTIONAL)
                if isinstance(kind, str):
                    f.type, f.type_name = F.TYPE_MESSAGE, f".{_PACKAGE}.{kind}"
                else:
                    f.type = kinds[kind]
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fdp)
        for msg in _SCHEMA:
            if "." not in msg:
                _classes[msg] = message_factory.GetMessageClass(
                    pool.FindMessageTypeByName(f"{_PACKAGE}.{msg}"))
    return _classes[name]


def hlo_protos(xspace: bytes) -> dict:
    """{program name (``jit__chunk(12)``): serialized ``HloProto``} from the
    ``/host:metadata`` plane of a serialized ``XSpace``."""
    space = _message("XSpace").FromString(xspace)
    out = {}
    for plane in space.planes:
        if plane.name != METADATA_PLANE:
            continue
        hlo_ids = {k for k, v in plane.stat_metadata.items() if v.name == HLO_STAT}
        for ev in plane.event_metadata.values():
            for st in ev.stats:
                if st.metadata_id in hlo_ids:
                    out[ev.name] = st.bytes_value
    return out


def scope_of(op_name: str) -> str:
    """``jit(_chunk)/while/body/linear/protect/dot_general`` ->
    ``linear/protect``; ``OTHER`` when no scope is on the path.  A scope
    met again further down the path (an inlined call repeats its caller's
    path) counts once."""
    path = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in path:
            path.append(part)
    return "/".join(path) or OTHER


def scope_map(hlo: bytes) -> dict:
    """{instruction name: scope} of every instruction of a serialized
    ``HloProto``."""
    module = _message("HloProto").FromString(hlo).hlo_module
    return {ins.name: scope_of(ins.metadata.op_name)
            for comp in module.computations for ins in comp.instructions}


# ---- the trace --------------------------------------------------------------
def program_scopes(xspace: bytes) -> dict:
    """{program: {instruction: scope}} of every program whose HLO a
    serialized trace holds."""
    return {p: scope_map(b) for p, b in hlo_protos(xspace).items()}


def scope_times(devices: dict, maps: dict, lo: int, hi: int) -> dict:
    """{module: {scope: self seconds}} over [lo, hi] ns, averaged over
    devices; maps: {program: {instruction: scope}} (``scope_map`` of each
    HLO proto); modules named as ``bench/trace.py`` names them (``_chunk``).
    An op of a program with no map counts under ``NO_HLO``."""
    n = max(len(devices), 1)
    out = defaultdict(lambda: defaultdict(float))
    for dev in devices.values():
        for s, e, prog, op, own in tr.self_times(dev["ops"]):
            cut = max(0, min(e, hi) - max(s, lo))
            if cut:
                ops = maps.get(prog)
                scope = NO_HLO if ops is None else ops.get(op, OTHER)
                out[prog][scope] += own * cut / max(e - s, 1) / 1e9 / n
    merged = defaultdict(lambda: defaultdict(float))
    for prog, by_scope in out.items():
        for scope, t in by_scope.items():
            merged[tr.module_name(prog)][scope] += t
    return {m: dict(v) for m, v in merged.items()}


def window(spans: list, devices: dict, name: str = "bench.slice"):
    """[lo, hi] ns of the first span called ``name``, else of the whole
    trace's device events."""
    for s, e, n in sorted(spans):
        if n == name:
            return s, e
    pts = [t for d in devices.values() for ev in d["mods"] + d["ops"]
           for t in ev[:2]]
    return (min(pts), max(pts)) if pts else (0, 0)


def summarize(path: str, span: str = "bench.slice") -> dict:
    t = tr.load(path)
    lo, hi = window(t.spans, t.devices, span)
    maps = program_scopes(t.xspace)
    return {"window_s": (hi - lo) / 1e9, "hlo_programs": len(maps),
            "scopes": scope_times(t.devices, maps, lo, hi),
            "idle_by_span": tr.reduce(t.devices, t.host, lo, hi,
                                      spans=t.spans)["idle_by_span"]}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb file")
    ap.add_argument("--window", default="bench.slice",
                    help="the span whose time is reduced (default: %(default)s)")
    args = ap.parse_args(argv)
    path = args.trace if args.trace.endswith(".pb") else tr.xplane_file(args.trace)
    print(json.dumps(summarize(path, args.window)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
