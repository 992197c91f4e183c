"""1 - (time in which a module or an operation ran on the device) / the
traced slice."""


def read(rec):
    t = rec["trace"]
    return 1.0 - t["busy_s"] / t["window_s"] if t else None
