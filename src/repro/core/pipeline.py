"""Layer-wise pipeline scheduler: two in-flight batches across two pools.

Paper §3.2 / Fig. 4: the scheduler holds up to two in-flight batches, each
with its own model id, layer cursor and completion state.  While batch B1
runs attention for a layer in the KV-cache pool, B2's previous-layer hidden
states are processed by FFN in the weights pool.  There is NO global layer
barrier: batches may come from different models with different layer
counts; when one finishes, its tokens are published, its slot is released
and refilled from the request queues (early exit + refill).

All batches read and write KV through ONE shared paged pool (the
virtualizer's device array): each :class:`InflightBatch` carries only its
page tables and lengths, and the scheduler threads the pool buffer through
every attention stage — batches touch disjoint pages, so interleaving
order cannot corrupt KV state.  FFN weights come from the ONE shared
weights arena the same way (models own disjoint slabs), and the scheduler
extends the paper's transfer hiding from hidden states to weights: while
batch B's layer-L attention is in flight in the KV pool, layer L+1's
weight slabs are prefetched into the arena (``WeightArena
.prefetch_layer``), so cold-model upload traffic hides behind compute.

Since the prefill-through-arena change the scheduler also takes PREFILL
batches (``InflightBatch(prefill=True)``): full-sequence attention per
layer, each layer's prompt KV scattered into the shared pool via the
batch's ``kv_writer``, FFN through the same arena gather — so a cold
model's prompt phase interleaves with other models' decode stages and its
own streaming weight uploads (DESIGN.md §6).

The scheduler always advances ONE token per decode batch pass — the
multi-step K-tokens-per-dispatch path (DESIGN.md §9) lives in the fused
lowering (``control.MultiStepFusedStep``), which replaces per-layer
interleaving with a single device-resident program; the two are
alternative lowerings of the same engine step, never composed.

Execution is asynchronous: every stage issue returns a lazy jax value, so
stages bound to the two pool devices genuinely overlap; the scheduler's job
is to *issue* stages in an order that keeps both pools busy.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Tuple

import jax

from repro.core.control import HostDrivenStep, logit_index
from repro.core.pools import PooledModel, transfer


@dataclass
class InflightBatch:
    """One batch's layer-granular execution state (the paper's state machine:
    model id, layer cursor, completion).  KV lives in the shared pool; the
    batch owns only its page-table view of it.

    ``prefill=True`` runs the batch through the prompt-phase stage programs
    instead: ``tokens`` is ``[B, S]`` prompt ids, ``page_tables``/``lengths``
    are unused (full-sequence attention attends over the prompt itself),
    each layer's KV is handed to ``kv_writer(layer, layer_kv, pool) ->
    pool`` for scattering into the shared pool, and ``logits`` is the
    unpadded last position (``true_len - 1``).  Prefill and decode batches
    interleave freely: a cold model's prefill attention overlaps another
    model's FFN AND its own next layer's slab upload."""

    batch_id: int
    model: str
    tokens: jax.Array                 # [B] next-token ids ([B,S] prefill)
    page_tables: Optional[jax.Array] = None   # [L, B, max_pages] int32
    lengths: Optional[jax.Array] = None       # [B] current context lengths
    layer: int = 0                    # layer cursor
    phase: str = "embed"              # embed -> attn -> ffn -> combine -> done
    x: Optional[jax.Array] = None     # residual stream
    ffn_in: Optional[jax.Array] = None
    ffn_out: Optional[jax.Array] = None
    logits: Optional[jax.Array] = None
    # prompt-phase extras
    prefill: bool = False
    # unpadded prompt length: host int, or a length-B sequence when the
    # batch coalesces several same-model prompts into one [B,S] pass
    true_len: object = 0
    kv_writer: Optional[Callable] = None

    @property
    def done(self) -> bool:
        return self.phase == "done"


class LayerPipelineScheduler:
    """Interleaves attention and FFN stages of two in-flight batches."""

    def __init__(self, pooled: Dict[str, PooledModel], kv_device, w_device,
                 steps: Optional[Dict[str, HostDrivenStep]] = None):
        self.pooled = pooled
        self.kv_device = kv_device
        self.w_device = w_device
        self.steps: Dict[str, HostDrivenStep] = steps or {
            name: HostDrivenStep(pm, kv_device, w_device)
            for name, pm in pooled.items()
            if pm.stage_fns is not None
        }
        # the ONE shared weights arena (every pooled model carries the
        # same object); None only for accounting-only pool builds
        self.arena = next(
            (pm.arena for pm in pooled.values() if pm.arena is not None),
            None)
        self.stage_log: List[Tuple[int, str, str, int]] = []  # (batch,model,stage,layer)

    # ------------------------------------------------------------------
    def _advance(self, b: InflightBatch, pool: jax.Array) -> jax.Array:
        """Issue exactly one stage of one batch (non-blocking).

        Returns the (possibly updated) shared pool."""
        step = self.steps[b.model]
        fns = self.pooled[b.model].stage_fns
        p_kv = self.pooled[b.model].kv_params
        arena = self.arena
        if b.phase == "embed":
            # map the model's slabs (upload streams in layer by layer);
            # layer 0 is pulled eagerly so the first FFN never stalls
            arena.activate(b.model, upload=False)
            arena.prefetch_layer(b.model, 0)
            b.x = (step._pembed if b.prefill else step._embed)(p_kv, b.tokens)
            b.phase = "attn"
        elif b.phase == "attn":
            if b.prefill:
                b.x, ffn_in, layer_kv = step._pattn(p_kv, b.x, b.layer)
                if b.kv_writer is not None:     # prompt KV -> shared pool
                    pool = b.kv_writer(b.layer, layer_kv, pool)
            else:
                b.x, ffn_in, pool = step._attn(
                    p_kv, b.x, pool, b.page_tables, b.lengths, b.layer)
            # transfer hiding, weights edition: issue layer L+1's slab
            # upload while layer L's attention is in flight
            arena.prefetch_layer(b.model, b.layer + 1)
            b.ffn_in = transfer(ffn_in, self.w_device)       # A-to-F
            self.stage_log.append((b.batch_id, b.model, "attn", b.layer))
            b.phase = "ffn"
        elif b.phase == "ffn":
            arena.prefetch_layer(b.model, b.layer)   # no-op once prefetched
            ffn = step._pffn if b.prefill else step._ffn
            out = ffn(arena.arena, arena.slot_table(b.model), b.ffn_in,
                      b.layer)
            b.ffn_out = transfer(out, self.kv_device)        # F-to-A
            self.stage_log.append((b.batch_id, b.model, "ffn", b.layer))
            b.phase = "combine"
        elif b.phase == "combine":
            b.x = (step._pcombine if b.prefill else step._combine)(
                b.x, b.ffn_out)
            b.layer += 1
            if b.layer >= fns.n_layers:
                b.logits = (step._plogits(p_kv, b.x,
                                          logit_index(b.true_len))
                            if b.prefill else step._logits(p_kv, b.x))
                b.phase = "done"                              # early exit
            else:
                b.phase = "attn"
        return pool

    # ------------------------------------------------------------------
    def run(self, batches: List[InflightBatch], pool: jax.Array, *,
            refill: Optional[Callable[[], Optional[InflightBatch]]] = None,
            max_inflight: int = 2
            ) -> Tuple[List[InflightBatch], jax.Array]:
        """Drive batches to completion, keeping ``max_inflight`` slots busy.

        ``pool`` is the shared physical KV pool; it is threaded through
        every attention stage and the final buffer is returned alongside
        the completed batches.  ``refill`` is polled whenever a slot frees
        (the paper's fetch from the per-model request queue).  Returns
        (completed batches in completion order, updated pool).
        """
        queue = list(batches)
        slots: List[Optional[InflightBatch]] = [None] * max_inflight
        finished: List[InflightBatch] = []

        def fill(i):
            if queue:
                slots[i] = queue.pop(0)
            elif refill is not None:
                slots[i] = refill()
            else:
                slots[i] = None

        for i in range(max_inflight):
            fill(i)

        # round-robin issue: one stage per live slot per cycle, so batch A's
        # FFN (weights pool) is issued right after batch B's attention
        # (KV pool) — the two devices' queues stay jointly populated.
        while any(s is not None for s in slots):
            for i, s in enumerate(slots):
                if s is None:
                    continue
                pool = self._advance(s, pool)
                if s.done:
                    finished.append(s)
                    fill(i)
        return finished, pool

    # ------------------------------------------------------------------
    def run_serial(self, batches: List[InflightBatch], pool: jax.Array
                   ) -> Tuple[List[InflightBatch], jax.Array]:
        """Pipeline OFF baseline: one batch at a time, stages still split
        across the two pools (transfers exposed)."""
        return self.run(batches, pool, max_inflight=1)

    def overlap_fraction(self) -> float:
        """Fraction of adjacent issued stages that alternate pools — a
        proxy for how much attention/FFN overlap the schedule exposes."""
        if len(self.stage_log) < 2:
            return 0.0
        alt = sum(1 for a, b in zip(self.stage_log, self.stage_log[1:])
                  if a[2] != b[2])
        return alt / (len(self.stage_log) - 1)
