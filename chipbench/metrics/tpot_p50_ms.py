"""Median over requests of the mean time per output token inside the
window: ``(last - first) / (n - 1)`` of each request's token times in the
window, for every request with at least 8 of them (so each reading spans
about a second of the host's clock, not one step)."""
from cb.measures import percentile, token_times_in_window, window_requests


def read(run):
    per_req = []
    for r in window_requests(run):
        t = token_times_in_window(run, r)
        if len(t) >= 8:
            per_req.append((t[-1] - t[0]) / (len(t) - 1))
    if not per_req:
        return None
    return 1e3 * percentile(per_req, 50)
