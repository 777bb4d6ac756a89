"""Shared arithmetic of the metric readers in ``chipbench/metrics/``."""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def window_steps(run: Dict) -> List:
    """Steps that began inside the measured window."""
    t0, t_close = run["window"][0], run["window_open_until"]
    return [s for s in run["steps"] if t0 <= s.t0 < t_close]


def window_requests(run: Dict) -> List:
    """Requests of the mix (not warm-up) that exist by the window's end."""
    return [r for r in run["requests"] if r.phase != "warmup"]


def token_times_in_window(run: Dict, r) -> List[float]:
    t0, t1 = run["window"]
    return [t for t in r.times if t0 <= t <= t1]


def decode_only_work(run: Dict):
    """(bytes, operations, device seconds) summed over the traced steps of
    the window that ran no prompt pass: the work from the shapes and each
    request's context length, the time from the trace."""
    from cb.work import decode_step_work
    nb = nf = secs = 0.0
    for s in window_steps(run):
        if s.prefill or not s.ctx or s.device_s is None:
            continue
        b, f = decode_step_work(run["shapes"], s.ctx)
        nb, nf, secs = nb + b, nf + f, secs + s.device_s
    return nb, nf, secs
