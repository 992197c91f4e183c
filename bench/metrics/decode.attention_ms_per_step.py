"""Device self time of the operations under the ``attention`` named scope
(the paged cache write, gather and softmax; the q/k/v projections and ``wo``
are outside it) in the decode-chunk program (``_chunk``), per decode step,
in the traced slice (``bench/scopes.py``).  On several chips the time is the
mean over the chips."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    by_scope = t.get("scopes", {}).get("_chunk", {}) if calls else {}
    secs = sum(v for k, v in by_scope.items()
               if k.split("/")[0] == "attention")
    steps = calls * rec["conf"]["scheduler"]["decode_chunk"]
    return secs / steps * 1e3 if secs else None
