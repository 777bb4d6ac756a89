"""Device self time by stage scope, from a profiler trace.

The program opens a ``jax.named_scope`` per stage of its step programs
(:data:`SCOPES`; ``repro.core.split_exec``) and ``cp.<span>`` host spans
for its engine phases while a profiler records.  Each device op carries
its scope in its HLO instruction's ``op_name`` metadata.  The TPU
profiler of this JAX puts no ``tf_op`` stat on the ``XLA Ops`` events, so
the op name comes from the compiled program's text
(``Compiled.as_text()``): the program an op belongs to is the ``XLA
Modules`` event it runs in, and the instruction is the op event's name
up to `` = ``.

:func:`load_scoped_xplane` reads a trace into the plain form of
``cb.trace`` (so ``reduce_trace`` reads it as before), with additions:
on the device's ``XLA Ops`` line ``op_names`` (the distinct ``op_name``
and program strings) and per event ``op_name_of`` and ``program_of``
(indices into it, -1 for none); on a host line ``args``, each span's
stats.  :func:`step_scopes` reduces that form to seconds per scope in
each traced step:

* only self time is counted: an op that contains others on the line (a
  layer loop's ``while``) keeps only the time in which none of them runs,
  so the scopes of a step sum to its busy time;
* an op's scope is the chain of stage scopes in its ``op_name``
  (``attn/kv_write``, ``ffn/unpack``, ``head``); the ``loop`` wrapper
  counts only where no stage scope is open;
* the TPU compiler gives some ops it makes itself no ``op_name``: the
  whole-pool relayout copies and the KV write's scatter fusion among
  them.  A program runs its ops in its schedule's order, and such an op
  is scheduled right after the op it serves, so by default it takes the
  scope of the op before it in the same program (``inherit``); an op
  with no such op, or every op without its own ``op_name`` when
  ``inherit`` is off, is ``unscoped``.
"""
from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from cb.trace import OPS_LINE

#: The stage scopes of the program (``repro.core.split_exec.SCOPES``),
#: fixed here so that what a metric reads does not move with the program.
SCOPES = ("embed", "attn", "kv_write", "kv_view", "attention", "ffn",
          "unpack", "router", "experts", "mlp", "head", "loop",
          "prefill_attn", "prefill_ffn", "kv_prompt_write")
UNSCOPED = "unscoped"
#: The stat that holds an op's ``op_name`` on the device's op events, where
#: the profiler gives one; the device line of program runs
#: (``<module>(<fingerprint>)``).
OP_NAME_STAT = "tf_op"
MODULES_LINE = "XLA Modules"
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: The scopes of the pool's relayouts: the new token's write and the typed
#: views of the whole pool.
RELAYOUT = ("kv_write", "kv_view")


def scope_of(op_name: str) -> str:
    """``attn/kv_write`` for ``jit(decode_m)/loop/while/body/.../attn/
    kv_write/scatter``: the stage scopes in the name, in order, each once,
    ``loop`` dropped where a stage scope follows it."""
    chain: List[str] = []
    for part in op_name.split("/"):
        if part in SCOPES and part not in chain:
            chain.append(part)
    if len(chain) > 1 and chain[0] == "loop":
        chain = chain[1:]
    return "/".join(chain) if chain else UNSCOPED


def program_op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """``(module name, {"%instruction": op_name})`` of a compiled
    program's text, for the instructions that carry an ``op_name``."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    out = {}
    for m in _INSTRUCTION.finditer(hlo_text):
        op = _OP_NAME.search(m.group(2))
        if op is not None:
            out[m.group(1)] = op.group(1)
    return module, out


def load_scoped_xplane(path: str, programs: Sequence[str] = (),
                       device: str = "/device:TPU:0",
                       host_prefixes=("cb.", "cp.")) -> Dict:
    """``cb.trace.load_xplane``'s form of one device's ops and the host
    spans named with ``host_prefixes``, with each op's ``op_name`` and
    program and each host span's stats.  ``programs``: the compiled
    texts of the programs whose op names the events lack."""
    from jax.profiler import ProfileData
    texts = dict(program_op_names(t) for t in programs)
    pd = ProfileData.from_file(path)
    planes = []
    for pl in pd.planes:
        if pl.name == device:
            lines = {ln.name: list(ln.events) for ln in pl.lines}
            runs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                           ev.name.split("(")[0])
                          for ev in lines.get(MODULES_LINE, ()))
            run_starts = [r[0] for r in runs]
            events, of, program_of = [], [], []
            names, index = [], {}

            def intern(value):
                if not value:
                    return -1
                if value not in index:
                    index[value] = len(names)
                    names.append(value)
                return index[value]
            for ev in lines.get(OPS_LINE, ()):
                events.append([ev.name, float(ev.start_ns),
                               float(ev.duration_ns)])
                k = bisect.bisect_right(run_starts, ev.start_ns) - 1
                module = runs[k][2] if k >= 0 and ev.start_ns < runs[k][1] \
                    else ""
                op = dict(ev.stats).get(OP_NAME_STAT) or texts.get(
                    module, {}).get(ev.name.split(" = ")[0], "")
                of.append(intern(str(op)))
                program_of.append(intern(module))
            planes.append({"name": pl.name, "lines": [{
                "name": OPS_LINE, "events": events, "op_names": names,
                "op_name_of": of, "program_of": program_of}]})
        elif pl.name.startswith("/host:"):
            spans = [ev for ln in pl.lines for ev in ln.events
                     if ev.name.startswith(host_prefixes)]
            planes.append({"name": pl.name, "lines": [{
                "name": "spans",
                "events": [[ev.name, float(ev.start_ns),
                            float(ev.duration_ns)] for ev in spans],
                "args": [{k: str(v) for k, v in ev.stats}
                         for ev in spans]}]})
    return {"planes": planes}


def _ops(trace: Dict, device: str, inherit: bool = True
         ) -> List[Tuple[float, float, str]]:
    """``(start, end, scope)`` of each op on the device's op line; an op
    with no ``op_name`` takes the scope of the op before it in the same
    program where ``inherit`` is on (the module docstring says why)."""
    out = []
    for pl in trace["planes"]:
        if pl["name"] != device:
            continue
        for ln in pl["lines"]:
            if ln["name"] != OPS_LINE:
                continue
            n = len(ln["events"])
            names = ln.get("op_names", [])
            of = ln.get("op_name_of", [-1] * n)
            program = ln.get("program_of", [-1] * n)
            last: Dict[int, str] = {}       # program -> scope of its last op
            for j in sorted(range(n), key=lambda j: ln["events"][j][1]):
                _, s, d = ln["events"][j]
                if of[j] >= 0:
                    scope = last[program[j]] = scope_of(names[of[j]])
                else:
                    scope = last.get(program[j], UNSCOPED) if inherit \
                        else UNSCOPED
                out.append((s, s + d, scope))
    return out


def self_segments(ops: Sequence[Tuple[float, float, str]]
                  ) -> List[Tuple[float, float, str]]:
    """The intervals in which each op runs with none of the ops nested in
    it running, sorted by start: ``(start, end, scope)``.  An op nested in
    another starts and ends inside it (as a loop's body ops do in its
    ``while``)."""
    segs: List[Tuple[float, float, str]] = []
    stack: List[List] = []          # [end, scope, cursor] of open ops

    def close_until(t: float) -> None:
        while stack and stack[-1][0] <= t:
            end, scope, cur = stack.pop()
            if end > cur:
                segs.append((cur, end, scope))
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for s, e, scope in sorted(ops, key=lambda o: (o[0], -o[1])):
        close_until(s)
        if stack:
            parent = stack[-1]
            if s > parent[2]:
                segs.append((parent[2], s, parent[1]))
            parent[2] = max(parent[2], s)
            e = min(e, parent[0])
        stack.append([e, scope, s])
    close_until(float("inf"))
    segs.sort()
    return segs


def step_scopes(trace: Dict, *, device: str = "/device:TPU:0",
                window_span: str = "cb.window", step_span: str = "cb.step",
                inherit: bool = True) -> Optional[List[Dict[str, float]]]:
    """Seconds of device self time per scope in each ``step_span`` that
    begins in the window, in order (the steps of ``reduce_trace``'s
    ``steps``), each op's time clipped to the step and the window; None
    without a window or ops."""
    window, steps = None, []
    for pl in trace["planes"]:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            for n, s, d in ln["events"]:
                if n == window_span:
                    window = (s, s + d)
                elif n == step_span:
                    steps.append((s, s + d))
    ops = _ops(trace, device, inherit)
    if window is None or not ops:
        return None
    w0, w1 = window
    segs = self_segments(ops)
    starts = [a for a, _, _ in segs]
    out = []
    for s, e in sorted(x for x in steps if w0 <= x[0] < w1):
        e = min(e, w1)
        by: Dict[str, float] = defaultdict(float)
        i = max(0, bisect.bisect_right(starts, s) - 1)
        while i < len(segs) and segs[i][0] < e:
            a, b, scope = segs[i]
            if b > s:
                by[scope] += (min(b, e) - max(a, s)) * 1e-9
            i += 1
        out.append(dict(by))
    return out


def decode_only_scopes(run: Dict) -> List[Tuple[object, Dict[str, float]]]:
    """``(step, scope seconds)`` of the traced decode-only steps (the steps
    ``cb.measures.decode_only_work`` counts), the seconds from
    ``run["step_scopes"]`` (one entry per traced step, in order)."""
    from cb.measures import window_steps
    per_step = run.get("step_scopes")
    traced = [s for s in run["steps"] if s.device_s is not None]
    if not per_step or len(per_step) != len(traced):
        return []
    of = {id(s): sc for s, sc in zip(traced, per_step)}
    return [(s, of[id(s)]) for s in window_steps(run)
            if not s.prefill and s.ctx and id(s) in of]


def seconds_under(scopes: Dict[str, float], *names: str) -> float:
    """Self time of the scopes whose chain holds any of ``names``."""
    return sum(v for k, v in scopes.items()
               if any(n in k.split("/") for n in names))


def trim_scoped(trace: Dict, *, device: str = "/device:TPU:0",
                window_span: str = "cb.window", step_span: str = "cb.step",
                max_ops: int = 3000) -> Dict:
    """A small copy of a scoped trace for a fixture, as ``cb.trace.trim``
    makes one: the ops from the window's start, at most ``max_ops``, cut
    back to whole steps (each op named by its HLO instruction alone, with
    its ``op_name`` and program), the host spans that overlap them with
    their stats, and the window ending where the first step left out
    begins."""
    w0 = None
    host, ops = [], []
    for pl in trace["planes"]:
        for ln in pl["lines"]:
            if pl["name"] == device and ln["name"] == OPS_LINE:
                names = ln["op_names"]
                programs = ln.get("program_of", [-1] * len(ln["events"]))
                ops.extend((e, names[i] if i >= 0 else None,
                            names[p] if p >= 0 else None)
                           for e, i, p in zip(ln["events"], ln["op_name_of"],
                                              programs))
            elif pl["name"].startswith("/host:"):
                args = ln.get("args", [{}] * len(ln["events"]))
                for ev, a in zip(ln["events"], args):
                    if ev[0] == window_span:
                        w0 = ev[1]
                    else:
                        host.append((ev, a))
    ops = sorted((o for o in ops if o[0][1] >= w0),
                 key=lambda o: o[0][1])[:max_ops]
    last = ops[-1][0][1]
    cut = max((e[1] for e, _ in host
               if e[0] == step_span and w0 < e[1] <= last), default=None)
    if cut is None:
        w1 = max(s + d for (_, s, d), _, _ in ops)
    else:
        w1 = cut
        ops = [o for o in ops if o[0][1] < cut]
    names = sorted({n for o in ops for n in o[1:] if n is not None})
    index = {n: i for i, n in enumerate(names)}
    host = [(e, a) for e, a in host if e[1] < w1 and e[1] + e[2] > w0]
    return {"planes": [
        {"name": "/host:CPU", "lines": [{
            "name": "spans",
            "events": [[window_span, w0, w1 - w0]] + [e for e, _ in host],
            "args": [{}] + [a for _, a in host]}]},
        {"name": device, "lines": [{
            "name": OPS_LINE,
            "events": [[n.split(" = ")[0], s, d] for (n, s, d), _, _ in ops],
            "op_names": names,
            "op_name_of": [index[n] if n is not None else -1
                           for _, n, _ in ops],
            "program_of": [index[p] if p is not None else -1
                           for _, _, p in ops]}]},
    ]}
