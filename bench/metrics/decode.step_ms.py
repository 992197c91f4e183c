"""Device time of the decode-chunk program (``_chunk``) per decode step, in
the traced slice."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    steps = calls * rec["conf"]["scheduler"]["decode_chunk"]
    return t["modules"]["_chunk"] / steps * 1e3 if steps else None
