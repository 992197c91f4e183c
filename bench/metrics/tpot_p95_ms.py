"""95th percentile over every request the run served of (time of the last
token - time of the first) / (tokens - 1), as the harness's poller saw the
tokens arrive (host clock)."""
import numpy as np


def read(rec):
    return float(np.percentile(rec["tpot_ms"], 95)) if rec["tpot_ms"] else None
