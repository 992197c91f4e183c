"""Device time of the prefill program (``_prefill_one``) per call, in the
traced slice."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_prefill_one", 0.0) if t else 0.0
    return t["modules"]["_prefill_one"] / calls * 1e3 if calls else None
