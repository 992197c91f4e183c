"""Device self time of the operations under the ``linear`` named scope
(``linear/protect`` inside it) in the prefill program (``_prefill_one``),
per call, in the traced slice (``bench/scopes.py``).  On several chips the
time is the mean over the chips."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_prefill_one", 0.0) if t else 0.0
    by_scope = t.get("scopes", {}).get("_prefill_one", {}) if calls else {}
    secs = sum(v for k, v in by_scope.items() if k.split("/")[0] == "linear")
    return secs / calls * 1e3 if secs else None
