"""Model FLOPs over the traced slice's length times the bf16 peak of all
the cell's chips (``chips`` times one chip's): the FLOPs the model needs per
prefill call and per decode step, averaged over the wave (real prompt and
generated tokens; padding and protection's redundant work left out), times
the calls of the prefill and decode-chunk programs in the slice.  On several
chips the calls are the mean over the chips (``trace.reduce``), the same on
each chip of one sharded program."""
from bench import work


def read(rec):
    t = rec["trace"]
    if not t:
        return None
    m, st = rec["conf"]["model"], rec["stats"]
    chunk = rec["conf"]["scheduler"]["decode_chunk"]
    pre = sum(work.prefill_flops(m, p) for p, _ in rec["requests"])
    dec = sum(work.request_decode_flops(m, p, n) for p, n in rec["requests"])
    flops = (pre / st["prefill_calls"] * t["module_calls"].get("_prefill_one", 0.0)
             + dec / (st["chunk_calls"] * chunk)
             * t["module_calls"].get("_chunk", 0.0) * chunk)
    if not flops:
        return None
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (t["window_s"] * peak)
