"""Peak device memory in use (``peak_bytes_in_use``, fullest chip) over the
chip's HBM from the peak table."""


def read(rec):
    if not rec["peaks"] or not rec["memory_peak_bytes"]:
        return None
    return rec["memory_peak_bytes"] / rec["peaks"]["hbm_bytes"]
