"""Reduce a profiler trace to device busy time, idle gaps and top ops.

The profiler writes ``<dir>/plugins/profile/<run>/*.xplane.pb``.
:func:`load_xplane` turns it into plain lists (``{"planes": [{"name",
"lines": [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``),
the same form the committed fixture holds, and :func:`reduce_trace` works
on that form only:

* the window is the host span named ``window_span`` (the harness wraps the
  traced steps in it);
* busy time is the union of the op intervals on one device's ``XLA Ops``
  line, clipped to the window; idle share is one minus busy over window;
* ``device_ops`` sums each op's device time in the window (top ten);
* ``idle_gaps`` sums the idle time of the window by the innermost host span
  (name starting with ``host_prefix``) open at each gap's midpoint (top
  ten), so a gap is charged to what the host was doing;
* ``steps`` gives, for each host span named ``step_span`` that begins in
  the window, in order, the seconds of that span in which an op ran.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
#: An op's name in a breakdown is cut to this many characters (the HLO
#: instruction, its shape and the start of its operands).
NAME_CHARS = 160


def load_xplane(path: str, keep_prefixes=("/device:", "/host:CPU")) -> Dict:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        if not pl.name.startswith(keep_prefixes):
            continue
        lines = [{"name": ln.name,
                  "events": [[ev.name, float(ev.start_ns),
                              float(ev.duration_ns)] for ev in ln.events]}
                 for ln in pl.lines]
        planes.append({"name": pl.name, "lines": lines})
    return {"planes": planes}


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _busy_within(busy: List[Tuple[float, float]], starts: List[float],
                 a: float, b: float) -> float:
    """Length of the union ``busy`` (sorted, disjoint) inside ``[a, b]``."""
    i = max(0, bisect.bisect_right(starts, a) - 1)
    total = 0.0
    while i < len(busy) and busy[i][0] < b:
        total += max(0.0, min(b, busy[i][1]) - max(a, busy[i][0]))
        i += 1
    return total


def reduce_trace(trace: Dict, *, device: str = "/device:TPU:0",
                 window_span: str = "cb.window", host_prefix: str = "cb.",
                 step_span: str = "cb.step", top: int = 10
                 ) -> Optional[Dict]:
    """Busy/idle/op/gap summary of one device over the traced window, or
    None when the trace holds no window or no op ran on that device."""
    host_spans: List[Tuple[str, float, float]] = []
    window = None
    ops: List[Tuple[str, float, float]] = []
    for pl in trace["planes"]:
        for ln in pl["lines"]:
            if pl["name"] == device and ln["name"] == OPS_LINE:
                ops.extend((n, s, s + d) for n, s, d in ln["events"])
            elif pl["name"].startswith("/host:"):
                for n, s, d in ln["events"]:
                    if n == window_span:
                        window = (s, s + d)
                    elif n.startswith(host_prefix):
                        host_spans.append((n, s, s + d))
    if window is None or not ops:
        return None
    w0, w1 = window
    clipped = [(n, max(a, w0), min(b, w1)) for n, a, b in ops
               if b > w0 and a < w1]
    if not clipped:
        return None
    busy = _union([(a, b) for _, a, b in clipped])
    busy_ns = sum(b - a for a, b in busy)
    by_op: Dict[str, float] = defaultdict(float)
    for n, a, b in clipped:
        by_op[n] += b - a
    gaps, t = [], w0
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if t < w1:
        gaps.append((t, w1))
    by_host: Dict[str, float] = defaultdict(float)
    for a, b in gaps:
        mid = 0.5 * (a + b)
        open_spans = [(s, n) for n, s, e in host_spans if s <= mid < e]
        label = max(open_spans)[1] if open_spans else "no host span"
        by_host[label] += b - a
    window_ns = w1 - w0
    starts = [a for a, _ in busy]
    steps = sorted((s, e) for n, s, e in host_spans
                   if n == step_span and w0 <= s < w1)
    return {
        "busy_s": busy_ns * 1e-9,
        "window_s": window_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns,
        "device_ops": [[n[:NAME_CHARS], v * 1e-9] for n, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, v * 1e-9] for n, v in sorted(
            by_host.items(), key=lambda kv: -kv[1])[:top]],
        "steps": [_busy_within(busy, starts, s, e) * 1e-9
                  for s, e in steps],
        "n_ops": len(clipped),
    }


def trim(trace: Dict, *, device: str = "/device:TPU:0",
         window_span: str = "cb.window", host_prefix: str = "cb.",
         max_ops: int = 400) -> Dict:
    """A small copy of a trace for a fixture: the window span, the host
    spans and the first ``max_ops`` device ops inside the window (each
    named by its HLO instruction alone), the window cut to end where the
    last kept op ends."""
    red_window = None
    host, ops = [], []
    for pl in trace["planes"]:
        for ln in pl["lines"]:
            if pl["name"] == device and ln["name"] == OPS_LINE:
                ops.extend(ln["events"])
            elif pl["name"].startswith("/host:"):
                for ev in ln["events"]:
                    if ev[0] == window_span:
                        red_window = ev
                    elif ev[0].startswith(host_prefix):
                        host.append(ev)
    w0 = red_window[1]
    ops = sorted((e for e in ops if e[1] >= w0), key=lambda e: e[1])
    ops = [[n.split(" = ")[0], s, d] for n, s, d in ops[:max_ops]]
    w1 = max(s + d for _, s, d in ops)
    host = [e for e in host if e[1] < w1 and e[1] + e[2] > w0]
    return {"planes": [
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [[window_span, w0, w1 - w0]]
             + host}]},
        {"name": device, "lines": [{"name": OPS_LINE, "events": ops}]},
    ]}
