"""The decode step's device time outside the ``linear`` and ``attention``
named scopes: the decode-chunk program's (``_chunk``) time per step, as
``decode.step_ms`` reads it, less the self time of the operations under
either scope (``bench/scopes.py``); the LM head, sampling, fault keys and
the loop's own work.  On several chips every time is the mean over the
chips."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    by_scope = t.get("scopes", {}).get("_chunk") if calls else None
    if not by_scope:
        return None
    scoped = sum(v for k, v in by_scope.items()
                 if k.split("/")[0] in ("linear", "attention"))
    steps = calls * rec["conf"]["scheduler"]["decode_chunk"]
    return (t["modules"]["_chunk"] - scoped) / steps * 1e3
