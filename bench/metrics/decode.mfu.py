"""The decode step's share of the bf16 peak of all the cell's chips: model
FLOPs of the wave's decoded tokens per step (matmul parameters, attention at
each token's real context, the LM head; averaged over the wave's steps) over
the device time per step of the decode-chunk program (``_chunk``) in the
traced slice, times ``chips`` times one chip's peak.  On several chips the
step time is the mean over the chips (``trace.reduce``)."""
from bench import work


def read(rec):
    t = rec["trace"]
    chunk = rec["conf"]["scheduler"]["decode_chunk"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    steps = rec["stats"]["chunk_calls"] * chunk
    if not calls or not steps:
        return None
    step_s = t["modules"]["_chunk"] / (calls * chunk)
    m = rec["conf"]["model"]
    flops = sum(work.request_decode_flops(m, p, n) for p, n in rec["requests"]) / steps
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * flops / (step_s * peak)
