"""The chip's published peaks, keyed by the ``device_kind`` JAX reports
(``bench/peaks.json``).  A kind that is not in the table is an error."""
from __future__ import annotations

from bench.harness import BENCH, load_json


def peaks(device_kind: str, table=None) -> dict:
    table = table if table is not None else load_json(BENCH / "peaks.json")
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; the "
                       f"table holds {sorted(table)}")
    return table[device_kind]
