"""Device time of the fused protected-linear kernel per decode step: the
Mosaic calls named ``fused_decode*`` inside the decode-chunk program
(``_chunk``), over the decode steps of that program, in the traced slice.

A time, not a roofline: the kernel's bytes over its events' time read above
the HBM bandwidth in the first chip runs, and which of the program's
asynchronous copies serve the kernel is not yet attributed."""

KERNEL = "_chunk/fused_decode"


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    steps = calls * rec["conf"]["scheduler"]["decode_chunk"]
    secs = sum(v for k, v in t["op_total"].items()
               if k.startswith(KERNEL)) if steps else 0.0
    return secs / steps * 1e3 if secs else None
