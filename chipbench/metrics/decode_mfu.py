"""Operations the traced window's decode-only steps require (``cb.work``),
over the seconds in which an operation ran on the device during those
steps (from the trace) and the chips' bf16 peak (``peaks.json``), as a
percentage: the whole step's share of the peak."""
from cb.measures import decode_only_work


def read(run):
    _, nf, secs = decode_only_work(run)
    if secs <= 0:
        return None
    return 100.0 * nf / (secs * run["peaks"]["bf16_flops_per_s"]
                         * run["chips_used"])
