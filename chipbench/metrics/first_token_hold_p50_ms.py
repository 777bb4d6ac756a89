"""Median time a request's first token waits inside the engine step that
made it: from the end of the engine's ``prefill`` span that carries the
request's id to the end of the engine ``step`` span around it (the rest
of the step, mostly both models' decode), over the requests whose first
token came in the window, in ms.  Read from the engine tracer's events
over the whole run (``run["engine_events"]``: ``SpanTracer.events``)."""
import bisect

from cb.measures import percentile, window_requests

ENGINE_TRACK = "engine/step-loop"


def holds(events, rids):
    """Seconds from the end of the prompt pass of each request in
    ``rids`` to the end of the step around it."""
    track = next((e["tid"] for e in events if e["ph"] == "M"
                  and e["args"].get("name") == ENGINE_TRACK), None)
    step_ends = sorted(e["ts"] for e in events if e["ph"] == "E"
                       and e["name"] == "step" and e["tid"] == track)
    out = []
    for e in events:
        if e["ph"] != "X" or e["name"] != "prefill":
            continue
        done = e["ts"] + e["dur"]
        i = bisect.bisect_left(step_ends, done)
        if i == len(step_ends):
            continue
        for rid in e["args"].get("rids", ()):
            if rid in rids:
                out.append((step_ends[i] - done) * 1e-6)
    return out


def read(run):
    events = run.get("engine_events")
    if not events:
        return None
    t0, t1 = run["window"]
    rids = {r.rid for r in window_requests(run)
            if r.times and t0 <= r.times[0] <= t1}
    got = holds(events, rids)
    return 1e3 * percentile(got, 50) if got else None
