"""Drive the engine's session API under a traffic mix and time it.

Every time here is the host's clock (``time.perf_counter``).  A token is
stamped when the ``step`` that produced it returns: the engine blocks on
its results inside ``step``, so that is when a client could see it.

Every request is submitted to the engine at the first step boundary after
it is due, so the engine's admission and batcher see the mix's own
arrivals: prompts of one model and prefill bucket that wait together are
coalesced into one ``[B, S]`` pass.  Set-up warms every such pass the mix
reaches, up to ``warm_batch`` prompts (:meth:`Load.warm_up`).
"""
from __future__ import annotations

import sys
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Dict, List, Optional

import numpy as np

from cb.reference import prompt_bucket
from cb.traffic import Arrival, Traffic, prompt_ids


@dataclass
class Req:
    rid: int
    model: str
    prompt: np.ndarray
    max_new: int
    due: float
    client: int = -1
    phase: str = "window"          # warmup | lead | window
    submitted: Optional[float] = None
    times: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    rejected: bool = False


@dataclass
class Step:
    t0: float
    t1: float
    prefill: bool
    ctx: Dict[str, List[int]]
    n_tokens: int
    #: seconds an operation ran on the device during the step (traced
    #: steps only)
    device_s: Optional[float] = None


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


class _NullSpan:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


class Load:
    def __init__(self, engine, traffic: Traffic, seed: int,
                 vocab: Dict[str, int], annotate: bool = False):
        self.engine = engine
        self.traffic = traffic
        self.seed = seed
        self.vocab = vocab
        self.reqs: Dict[int, Req] = {}
        self.pending: Dict[str, deque] = {m: deque() for m in traffic.models}
        self.steps: List[Step] = []
        self.kv_mapped: List[int] = []
        self.t_base = time.perf_counter()
        self._next_id = 0
        #: called with the time at the top of every window iteration (the
        #: traced run closes its profiler window from here)
        self.tick = None
        if annotate:
            from jax.profiler import TraceAnnotation
            self.span = TraceAnnotation
        else:
            self.span = lambda name: _NullSpan()

    # ------------------------------------------------------------------
    def new_request(self, model: str, plen: int, olen: int, due: float,
                    phase: str, client: int = -1) -> Req:
        rid = self._next_id
        self._next_id += 1
        r = Req(rid, model, prompt_ids(self.seed, rid, plen,
                                       self.vocab[model]),
                olen, due, client, phase)
        self.reqs[rid] = r
        self.pending[model].append(r)
        return r

    def submit_ready(self, now: float, per_model: int = 0) -> None:
        """Submit every pending request that is due (at most ``per_model``
        of each model, where that is set)."""
        from repro.runtime.request import Request
        from repro.runtime.session import HandleState
        for q in self.pending.values():
            n = 0
            while q and q[0].due <= now and not (per_model and n >= per_model):
                r = q.popleft()
                n += 1
                r.submitted = now
                self.engine.advance(now - self.t_base)
                h = self.engine.submit(Request(
                    r.rid, r.model, len(r.prompt), r.max_new,
                    r.due - self.t_base, prompt_ids=r.prompt))
                if h.state is HandleState.REJECTED:
                    r.rejected = r.done = True

    def active_ctx(self) -> Dict[str, List[int]]:
        ctx: Dict[str, List[int]] = {}
        for r in self.reqs.values():
            if r.times and not r.done:
                ctx.setdefault(r.model, []).append(
                    len(r.prompt) + len(r.tokens) - 1)
        return ctx

    def step(self) -> List[Req]:
        """One engine step; returns the requests it finished."""
        ctx = self.active_ctx()
        t0 = time.perf_counter()
        with self.span("cb.step"):
            events = self.engine.step(t0 - self.t_base)
        t1 = time.perf_counter()
        finished, prefill = [], False
        with self.span("cb.book"):
            for e in events:
                r = self.reqs[e.request_id]
                r.times.append(t1)
                r.tokens.append(int(e.token))
                prefill |= bool(e.first)
                if e.done:
                    r.done = True
                    finished.append(r)
            self.steps.append(Step(t0, t1, prefill, ctx, len(events)))
            self.kv_mapped.append(self.engine.virt.mapped_pages)
        return finished

    def busy(self) -> bool:
        return any(r.submitted is not None and not r.done
                   for r in self.reqs.values())

    def run_until(self, pred, limit_s: float = 600.0,
                  per_model: int = 0) -> None:
        t_end = time.perf_counter() + limit_s
        while not pred():
            if time.perf_counter() > t_end:
                raise RuntimeError("engine made no progress")
            self.submit_ready(time.perf_counter(), per_model)
            self.step()

    # ------------------------------------------------------------------
    def warm_passes(self):
        """The prompt passes set-up runs: ``(bucket, B, lengths)`` with one
        prompt length per row, so that for every bucket and every ``B`` up
        to the mix's ``warm_batch`` each of the bucket's lengths lands at
        each row."""
        by_bucket: Dict[int, List[int]] = {}
        for plen in sorted(set(self.traffic.prompt_levels())):
            by_bucket.setdefault(prompt_bucket(plen, self.traffic.max_ctx),
                                 []).append(plen)
        for bucket, lens in by_bucket.items():
            for B in range(1, self.traffic.warm_batch + 1):
                for j in range(max(len(lens), B)):
                    yield bucket, B, [lens[(j + i) % len(lens)]
                                      for i in range(B)]

    def warm_up(self) -> None:
        """Compile every program the mix can reach.  A prompt pass is one
        program per model, bucket and batch size, and the pool write of a
        row one per prompt length and row index (:meth:`warm_passes`), for
        every model.  Decode steps with both models active run meanwhile."""
        now = time.perf_counter()
        t, last = now, None
        for bucket, B, lens in self.warm_passes():
            if last is not None and last != (bucket, B):
                log(f"[warm-up] bucket {last[0]} B {last[1]}: "
                    f"{time.perf_counter() - t:.1f} s")
                t = time.perf_counter()
            last = (bucket, B)
            for m in self.traffic.models:
                for plen in lens:
                    self.new_request(m, plen, 2, now, "warmup")
            self.run_until(lambda: all(r.done for r in self.reqs.values()))
        if last is not None:
            log(f"[warm-up] bucket {last[0]} B {last[1]}: "
                f"{time.perf_counter() - t:.1f} s")

    def precompile(self, model_cfgs) -> int:
        """Compile the programs of :meth:`warm_passes` ahead of it, in
        parallel threads, into the persistent compilation cache.

        A prompt pass compiles its stage programs (embedding, attention,
        FFN over the arena, residual, logits) per model, bucket and batch,
        and its prompt-KV write per (batch, bucket, prompt length, row);
        compiled one at a time as the passes reach them they do not fit a
        first run's time.  Here each is lowered for the shapes the pass
        hands it, so that the pass finds it in the cache.  Best effort:
        where the program differs, a job fails or compiles what is never
        used, and the warm-up compiles as it goes.  Returns how many
        programs were compiled."""
        import os
        from concurrent.futures import ThreadPoolExecutor

        import jax
        import jax.numpy as jnp
        try:
            from repro.core.virtualizer import _pool_pair_scatter
            pool = self.engine.virt.pool
            dev = pool.sharding
            runners = {m: self.engine.runners[m].prefill_step
                       for m in self.traffic.models}
            arenas = {}
            for m, sp in runners.items():
                arena = sp.pooled.arena
                view = arena.views[m]
                # the [n_layers, slabs_per_layer] slot table, on the
                # arena's device where it has one
                table = jax.ShapeDtypeStruct(
                    (view.n_layers, view.slabs_per_layer), jnp.int32,
                    sharding=None if arena.device is None else
                    jax.sharding.SingleDeviceSharding(arena.device))
                # the FFN's input crosses to the arena's chip where the
                # arena has one of its own (the host-driven pipeline)
                ffn_dev = dev if arena.device is None or \
                    arena.device in dev.device_set else \
                    jax.sharding.SingleDeviceSharding(arena.device)
                arenas[m] = (arena.arena, table, ffn_dev)
        except (ImportError, AttributeError, KeyError) as e:
            log(f"[warm-up] nothing compiled ahead: {e!r}")
            return 0

        def on_dev(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=dev)

        def write_job(m, S, B, n, row):
            mc = model_cfgs[m]

            def aval(*shape):
                return on_dev(jax.ShapeDtypeStruct((B, S) + shape,
                                                   jnp.dtype(mc.dtype)))
            if mc.attention == "mla":
                a, b = aval(mc.mla.kv_lora_rank), \
                    aval(mc.mla.qk_rope_head_dim)
            else:
                a = b = aval(mc.n_kv_heads, mc.head_dim)
            idx = jax.ShapeDtypeStruct((n,), jnp.int32)
            return lambda: _pool_pair_scatter.lower(
                on_dev(pool), a, b, idx, idx, n_tokens=n, batch_index=row,
                mla=mc.attention == "mla")

        def stage_jobs(m, S, B):
            sp, (arena, slots, ffn_dev) = runners[m], arenas[m]
            p_kv = sp.pooled.kv_params
            tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
            layer = jax.ShapeDtypeStruct((), jnp.int32, weak_type=True)
            x = on_dev(jax.eval_shape(sp._embed, p_kv, tok))
            ffn_in = jax.eval_shape(sp._attn, p_kv, x, layer)[1]
            ffn_in = jax.ShapeDtypeStruct(ffn_in.shape, ffn_in.dtype,
                                          sharding=ffn_dev)
            ffn_out = on_dev(jax.eval_shape(sp._ffn, arena, slots, ffn_in,
                                            layer))
            idx = jax.ShapeDtypeStruct(() if B == 1 else (B,), jnp.int32)
            return [lambda: sp._embed.lower(p_kv, tok),
                    lambda: sp._attn.lower(p_kv, x, layer),
                    lambda: sp._ffn.lower(arena, slots, ffn_in, layer),
                    lambda: sp._combine.lower(x, ffn_out),
                    lambda: sp._logits.lower(p_kv, x, idx)]

        stages, writes = set(), set()
        for bucket, B, lens in self.warm_passes():
            for m in self.traffic.models:
                stages.add((m, bucket, B))
                for row, plen in enumerate(lens):
                    writes.add((m, bucket, B, plen, row))
        t = time.perf_counter()
        try:
            # the largest passes first: they take longest to compile
            jobs = [j for key in sorted(stages, key=lambda k: -k[1] * k[2])
                    for j in stage_jobs(*key)]
            jobs += [write_job(*key) for key in sorted(writes)]
        except Exception as e:  # noqa: BLE001 - the warm-up compiles instead
            log(f"[warm-up] nothing compiled ahead: {e!r}")
            return 0

        def run(job):
            try:
                job().compile()
                return True
            except Exception as e:  # noqa: BLE001
                log(f"[warm-up] a program compiled ahead failed: {e!r}")
                return False

        threads = max(1, min(12, (os.cpu_count() or 2) - 1))
        with ThreadPoolExecutor(threads) as ex:
            done = sum(ex.map(run, jobs))
        log(f"[warm-up] {done} of {len(jobs)} programs compiled ahead in "
            f"{time.perf_counter() - t:.1f} s ({threads} threads)")
        return done

    def lead_in(self, queues: Dict[str, List[Arrival]],
                lead_s: float) -> None:
        """Closed loop: every client's first request, submitted one per
        model per step, until all have their first token.  Open loop:
        the schedule's arrivals for ``lead_s`` seconds before the window."""
        now = time.perf_counter()
        self._queues = {m: deque(q) for m, q in queues.items()}
        for m, n in self.traffic.client_models().items():
            for c in range(n):
                a = self._queues[m].popleft()
                self.new_request(m, a.prompt_len, a.output_len, now,
                                 "lead", client=c)
        self.run_until(lambda: all(
            r.times for r in self.reqs.values() if r.phase == "lead"),
            per_model=1)
        if self.traffic.loop == "open":
            self.run_open(self.traffic.open_schedule(self.seed + 1, lead_s),
                          lead_s, phase="lead")

    def _next_for_clients(self, finished: List[Req]) -> None:
        """Each finished client request sends that client's next one."""
        for r in finished:
            if r.client >= 0:
                a = self._queues[r.model].popleft()
                self.new_request(r.model, a.prompt_len, a.output_len,
                                 time.perf_counter(), "window", r.client)

    def run_open(self, schedule: List[Arrival], seconds: float,
                 phase: str = "window") -> float:
        t0 = time.perf_counter()
        todo = deque(schedule)
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if self.tick is not None:
                self.tick(t0, now)
            with self.span("cb.generate"):
                while todo and t0 + todo[0].due <= now:
                    a = todo.popleft()
                    self.new_request(a.model, a.prompt_len, a.output_len,
                                     t0 + a.due, phase)
                self.submit_ready(now)
            if self.busy():
                self._next_for_clients(self.step())
                continue
            nxt = t0 + todo[0].due if todo else t0 + seconds
            with self.span("cb.idle"):
                time.sleep(max(0.0, min(nxt, t0 + seconds) - now))
        return t0

    def run_closed(self, seconds: float) -> float:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            if self.tick is not None:
                self.tick(t0, time.perf_counter())
            with self.span("cb.generate"):
                self.submit_ready(time.perf_counter())
            self._next_for_clients(self.step())
        return t0

    def finish_first_tokens(self, t_w0: float, t_w1: float,
                            limit_s: float = 60.0) -> None:
        """Step (submitting only what was due in the window) until every
        request due in the window has its first token."""
        due = [r for r in self.reqs.values()
               if r.phase == "window" and t_w0 <= r.due < t_w1]
        self.run_until(lambda: all(r.times or r.rejected for r in due),
                       limit_s)
