"""Median of time to first token over every request due in the
window, each timed from the instant it was due (open loop), so time held
before submission and in the engine's queue counts."""
from cb.measures import percentile


def read(run):
    t0, t_close = run["window"][0], run["window_open_until"]
    ttft = [r.times[0] - r.due for r in run["requests"]
            if r.phase == "window" and t0 <= r.due < t_close and r.times]
    if not ttft:
        return None
    return 1e3 * percentile(ttft, 50)
