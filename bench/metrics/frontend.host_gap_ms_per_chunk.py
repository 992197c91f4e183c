"""Device idle time under the scheduler's host spans (``serve.*``: admission
with its prefill and insert, the chunk's dispatch, the readback of its
tokens, harvest, retirement), per call of the decode-chunk program
(``_chunk``), in the traced slice (``bench/trace.py``'s ``idle_by_span``).
On several chips the idle time and the calls are the mean over the chips."""


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    idle = t.get("idle_by_span", {}) if calls else {}
    secs = sum(v for k, v in idle.items() if k.startswith("serve."))
    return secs / calls * 1e3 if secs else None
