"""Share of the HBM roofline that a decode step reaches: the least bytes a
step of the wave needs on average (bf16 weights once, plus each decoded
token's keys and values at its real context, over the wave's steps), over
the HBM bandwidth of all the cell's chips, divided by the device time per
step of the decode-chunk program (``_chunk``) in the traced slice.

On several chips the step time is the mean over the chips
(``trace.reduce``) and the bandwidth is ``chips`` times one chip's, so a
model sharded over four chips reads its share of the four chips' roofline."""
from bench import work


def read(rec):
    t = rec["trace"]
    chunk = rec["conf"]["scheduler"]["decode_chunk"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    steps = rec["stats"]["chunk_calls"] * chunk
    if not calls or not steps:
        return None
    step_s = t["modules"]["_chunk"] / (calls * chunk)
    need = work.decode_bytes(rec["conf"]["model"], rec["requests"], steps) / steps
    bandwidth = rec["chips"] * rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * need / bandwidth / step_s
