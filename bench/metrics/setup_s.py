"""Process start to window open: imports, weights, compiles or compile-cache
reads, warm-up (host clock)."""


def read(rec):
    return rec["setup_s"]
