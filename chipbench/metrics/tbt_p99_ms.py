"""99th percentile of the gaps between consecutive output tokens of every
request, over all gaps that lie inside the window.  The tail is the steps
in which prompt passes stall decode."""
from cb.measures import percentile, token_times_in_window, window_requests


def read(run):
    gaps = []
    for r in window_requests(run):
        t = token_times_in_window(run, r)
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    if len(gaps) < 100:
        return None
    return 1e3 * percentile(gaps, 99)
