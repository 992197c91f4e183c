"""Tokens the poller saw arrive within the measured window, over the
window's length (host clock); nothing for a window of no length."""


def read(rec):
    return rec["tokens"] / rec["window_s"] if rec["window_s"] else None
