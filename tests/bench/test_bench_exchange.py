"""``decode.exchange_ms_per_step``: the device self time of the collectives
in the decode-chunk program, per decode step, on a hand-made record."""
import pytest

import tiny_cells
from bench import harness

READ = harness.reader("decode.exchange_ms_per_step")


def _rec(ops):
    conf = {"model": tiny_cells.MODEL, "scheduler": tiny_cells.SCHED}
    return {"conf": conf, "chips": 4,
            "trace": {"window_s": 2.5, "modules": {"_chunk": 0.4},
                      "module_calls": {"_chunk": 10.0, "_prefill_one": 1.0},
                      "ops": ops}}


def test_reads_the_collectives_of_the_chunk():
    ops = {
        # collectives of the decode chunk: XLA's names after the opcode,
        # JAX's after the primitive, async halves
        "_chunk/all-reduce.41": 0.010,
        "_chunk/psum.18": 0.020,
        "_chunk/all-gather.6": 0.001,
        "_chunk/collective-permute-start.2": 0.002,
        "_chunk/collective-permute-done.2": 0.003,
        "_chunk/all-reduce-start": 0.004,
        "_chunk/reduce-scatter.1": 0.005,
        "_chunk/all-to-all.3": 0.006,
        # not collectives, or not in the chunk
        "_chunk/fusion.613": 0.300,
        "_chunk/fused_decode.8": 0.050,
        "_chunk/reduce.12": 0.007,
        "_prefill_one/psum.24": 0.500,
        "_insert/all-reduce.1": 0.500,
    }
    steps = 10.0 * tiny_cells.SCHED["decode_chunk"]
    want = (0.010 + 0.020 + 0.001 + 0.002 + 0.003 + 0.004 + 0.005
            + 0.006) / steps * 1e3
    assert READ(_rec(ops)) == pytest.approx(want, rel=1e-12)


def test_reads_nothing_without_collectives():
    # a one-chip program: the kernel and its fusions, no exchange
    assert READ(_rec({"_chunk/fusion.613": 0.3,
                      "_chunk/fused_decode.3": 0.05})) is None
    assert READ(_rec({"_prefill_one/psum.24": 0.5})) is None
    assert READ(dict(_rec({}), trace=None)) is None
