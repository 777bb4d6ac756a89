"""Bytes the traced window's decode-only steps must move (``cb.work``:
weights, chosen experts, KV at each request's length, head), over the
seconds in which an operation ran on the device during those steps (from
the trace) and the chip's HBM bandwidth (``peaks.json``), as a percentage."""
from cb.measures import decode_only_work


def read(run):
    nb, _, secs = decode_only_work(run)
    if secs <= 0:
        return None
    return 100.0 * nb / (secs * run["peaks"]["hbm_bytes_per_s"]
                         * run["chips_used"])
