"""Device self time of the collective operations in the decode-chunk program
(``_chunk``), per decode step, in the traced slice: the cross-chip exchange
of a sharded decode step.  On several chips the time is the mean over the
chips; a program with no collective reads nothing.

That holds the protected datapath's own exchange (the ``pmax`` of each
column-parallel projection's row maxima and the ``psum`` of each
row-parallel projection's accumulators, under the ``exchange`` scope) and
the collectives GSPMD puts in: the quantization maxima of sharded operands,
the vocabulary-sharded embedding and the argmax.

An op counts as a collective by its instruction name, the name the trace
gives it (``bench/trace.py``): XLA names the collectives it inserts or
combines after their opcode (``all-reduce.41``, ``all-gather.6``,
``collective-permute-start.2``), and JAX lowers its own collectives under
their primitive's name (``psum.18``)."""

OPCODES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
           "collective-permute")
PRIMITIVES = ("psum", "pmax", "pmin", "ppermute", "all_gather",
              "all_to_all", "psum_scatter", "reduce_scatter")
PROGRAM = "_chunk/"


def is_collective(op: str) -> bool:
    """Whether an instruction name (``psum.18``) names a collective."""
    base = op.split(".", 1)[0]
    return base.startswith(OPCODES) or base in PRIMITIVES or any(
        base.startswith(p + "-") for p in PRIMITIVES)


def read(rec):
    t = rec["trace"]
    calls = t["module_calls"].get("_chunk", 0.0) if t else 0.0
    secs = sum(v for k, v in t["ops"].items() if k.startswith(PROGRAM)
               and is_collective(k[len(PROGRAM):])) if calls else 0.0
    steps = calls * rec["conf"]["scheduler"]["decode_chunk"]
    return secs / steps * 1e3 if secs else None
