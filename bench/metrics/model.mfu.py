"""Model FLOPs over the traced slice's length times the chip's bf16 peak:
the FLOPs the model needs per prefill call and per decode step, averaged
over the wave (real prompt and generated tokens; padding and protection's
redundant work left out), times the calls of the prefill and decode-chunk
programs in the slice."""
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
    return 100.0 * flops / (t["window_s"] * rec["peaks"]["bf16_flops_per_s"])
