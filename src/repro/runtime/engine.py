"""CrossPool serving engine: an online, continuously-batched session API.

End-to-end path (paper §3/§4), now event-driven (DESIGN.md §7):

  submit(request) -> AdmissionController verdict (planner budget,
           queue-or-reject) surfaced on the returned RequestHandle
  step(now)
        -> drain the front-door queue (requests whose resources freed)
        -> PrefillBatcher: coalesce admitted same-model arrivals into ONE
           [B, S] StreamingPrefill pass per (model, prompt-bucket) group;
           prompt KV is scattered into the SHARED paged pool pages mapped
           at admission
        -> decode: one dispatch per active model over the pool
             lowering=fused : one compiled paged step per model committing
                              K tokens with on-device sampling
                              ("persistent kernel" analogue,
                              ``MultiStepFusedStep``; DESIGN.md §9)
             lowering=host  : per-layer attention/FFN dispatches across
                              the disaggregated pools
             pipeline=True  : the active models' batches kept in flight so
                              attention and FFN overlap (paper Fig. 4)
        -> completions: release slot + pages + weight pin, so the NEXT
           step's drain can admit what was queued behind them
        -> list[TokenEvent] (per-token streaming callbacks fire inline)
  cancel(handle) -> atomically frees KV pages and drops the weight pin
  drain() -> step until quiescent

Requests join and leave decode batches BETWEEN steps — there is no
global barrier and no offline trace: ``run(requests)`` survives only as
a thin compatibility wrapper that submits arrivals when due and calls
``step``.

The virtualizer's device page pool is the SINGLE source of KV truth for
every dense/moe/vlm model: total device KV bytes are fixed by
``page_budget`` alone, independent of how many models are colocated.
Families outside split execution (SSM/hybrid/enc-dec/SWA) fall back to a
fused dense-cache path; their pool pages are accounting-only.

With ``elastic=ElasticConfig(...)`` the KV/weights split is no longer
frozen: per-step telemetry feeds a windowed Eq. (1)-(2) re-plan and the
two pools are live-repartitioned at step boundaries — the KV pool
shrinks through a host swap tier (in-flight requests' cold pages fault
back on next touch), the arena shrinks by LRU-evicting idle models, and
total device bytes are conserved (DESIGN.md §8).

The weights side is symmetric (PR 2/3): FFN/MoE weights live in ONE
shared slab arena whose device bytes are fixed by ``slot_budget`` alone;
prefill streams each layer's slabs in behind the previous layer's
attention, so a cold model's first token overlaps its own upload in BOTH
lowering modes, and ``ModelRunner`` holds NO full param tree.  Admission
is arena-aware: a cold-model request whose slabs are not reachable
without revoking another admitted model's weights queues at the front
door instead of thrashing the LRU.

Engine-scale model set = the paper's colocation trio at smoke scale; the
production-mesh behaviour of the same code paths is proven by the dry-run.
"""
from __future__ import annotations

import collections
import math
import os
import time
import warnings
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (DEFAULT_DECODE_STEPS_PER_DISPATCH,
                                CacheConfig, ElasticConfig, EngineConfig,
                                ModelConfig)
from repro.core.admission import (AdmissionController, AdmissionStats,
                                  PendingRequest)
from repro.core.control import (HostDrivenStep, MultiStepFusedStep,
                                StreamingPrefill, jit_program)
from repro.analysis.sanitizer import PoolSanitizer, PoolSanitizerError
from repro.core.elastic import ElasticRebalancer
from repro.core.errors import PoolAccountingError
from repro.core.hooks import CompositeHooks
from repro.core.pipeline import InflightBatch, LayerPipelineScheduler
from repro.core import split_exec
from repro.core.pools import build_pools
from repro.core.prefix_cache import PrefixCache
from repro.core.virtualizer import (DEFAULT_PAGE_BYTES, KVVirtualizer,
                                    OutOfPagesError)
from repro.core.weight_pool import DEFAULT_SLAB_BYTES, OutOfSlabsError
from repro.models import build_model
from repro.models.moe import expert_capacity
from repro.runtime.flightrec import (FlightRecorder, ReplayDivergence,
                                     engine_header, pool_snapshot)
from repro.runtime.observe import (NO_SPAN, EngineObserver, MetricsRegistry,
                                   SLOMonitor)
from repro.runtime.request import Phase, Request
from repro.runtime.sampler import sample
from repro.runtime.session import (HandleState, PrefillBatcher, PrefillGroup,
                                   RebalanceEvent, RequestHandle, TokenEvent,
                                   prompt_bucket)
from repro.runtime.telemetry import DemandTelemetry


@dataclass
class EngineMode:
    pipeline: bool = True
    lowering: bool = True          # fused step vs host-driven per-layer
    # decode tokens committed per host dispatch (persistent multi-step
    # decode, DESIGN.md §9).  Only the fused lowering can run K>1 — one
    # ``MultiStepFusedStep`` dispatch samples on device and returns
    # [K, B] token ids; host-driven mode and fallback families silently
    # clamp to 1 so the ablation baseline keeps its per-token dispatch
    # train and both lowering modes still gate parity.
    decode_steps_per_dispatch: int = DEFAULT_DECODE_STEPS_PER_DISPATCH


@dataclass
class EngineStats:
    tokens_out: int = 0
    wall_s: float = 0.0
    tbt: List[float] = field(default_factory=list)
    ttft: List[float] = field(default_factory=list)
    step_times: Dict[str, List[float]] = field(default_factory=dict)
    slow_steps: int = 0            # straggler-mitigation counter
    cancelled: int = 0             # requests cancelled through the session
    # batch size of every executed prefill pass (B > 1 = coalesced)
    prefill_batch_sizes: List[int] = field(default_factory=list)
    # live view of the admission controller's counters (global + per model)
    admission: Optional[AdmissionStats] = None
    # weights-arena counters (activations/evictions/uploads)
    weights_pool: Dict[str, float] = field(default_factory=dict)
    # applied elastic boundary moves (empty when elastic is off)
    rebalance_events: List[RebalanceEvent] = field(default_factory=list)
    # telemetry + rebalancer snapshot folded in by finalize()
    elastic: Dict[str, float] = field(default_factory=dict)

    @property
    def throughput(self) -> float:
        return self.tokens_out / self.wall_s if self.wall_s else 0.0


class ModelRunner:
    """Per-model batch slots + compiled prefill/decode programs.

    ``paged=True`` (dense/moe/vlm): NO per-model KV allocation AND no
    per-model param tree — prefill streams prompt KV into the
    virtualizer's pool pages layer by layer while FFN weights are gathered
    from the shared arena (``prefill_step``); decode steps read and write
    through page tables.  ``params`` must be ``None``: the only full
    copies are the pooled kv_params (non-FFN) and the arena's packed host
    masters.  ``paged=False`` (fused fallback families): a contiguous
    per-model cache and a device-resident ``params`` tree as before.

    Prefill consumes :class:`~repro.runtime.session.PrefillGroup`s — one
    ``[B, S]`` pass per same-model same-bucket group, committing each row
    into its own batch slot.
    """

    def __init__(self, name: str, cfg: ModelConfig, params,
                 virt: KVVirtualizer, *, max_batch: int, max_ctx: int,
                 mode: EngineMode, pooled=None,
                 prefill_step: Optional[StreamingPrefill] = None):
        self.name = name
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_ctx = max_ctx
        self.mode = mode
        self.virt = virt
        self.pooled = pooled
        self.paged = pooled is not None and pooled.stage_fns is not None
        self.lengths = np.zeros(max_batch, np.int32)
        self.slots: List[Optional[Request]] = [None] * max_batch
        self.next_tokens = np.zeros(max_batch, np.int32)
        # prefix cache wiring (DESIGN.md §11), set by the engine after
        # construction: the shared tree and the engine's live
        # request_id -> (fork, prefix_routes) admission outcomes.
        # NOT named ``cache``: that attribute is the dense-KV fallback
        # slot, and its absence is the paged path's acceptance gate
        self.prefix_cache: Optional[PrefixCache] = None
        self.prefix_info: Dict[int, Tuple[int, Optional[np.ndarray]]] = {}

        if self.paged:
            assert params is None, \
                f"{name}: paged models must not hold a full param tree"
            assert prefill_step is not None
            self.params = None
            self.prefill_step = prefill_step
            self.view = virt.views[name]
            self.max_pages = max(
                1, math.ceil(max_ctx / self.view.tokens_per_page))
            # K decode tokens per dispatch; host-driven lowering keeps the
            # per-token dispatch train, so K>1 is fused-only
            self.decode_steps = (max(1, int(mode.decode_steps_per_dispatch))
                                 if mode.lowering else 1)
            self.fused: Optional[MultiStepFusedStep] = (
                MultiStepFusedStep(pooled, k=self.decode_steps)
                if mode.lowering else None)
        else:
            self.params = params
            self.decode_steps = 1          # dense-cache fallback stays K=1
            mdl = build_model(cfg)
            self.cache = mdl.init_cache(max_batch, max_ctx)

            def _prefill_dense(params, tokens, cache, slot, true_len):
                one = jax.tree.map(
                    lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1),
                    cache)
                logits, one = mdl.prefill(params, tokens, one,
                                          logit_index=true_len - 1)
                cache = jax.tree.map(
                    lambda c, o: jax.lax.dynamic_update_slice_in_dim(
                        c, o.astype(c.dtype), slot, axis=1),
                    cache, one)
                return logits, cache

            self._prefill = jit_program(_prefill_dense, "prefill", name)

            def _decode(params, tokens, cache, lengths):
                logits, cache = mdl.decode_step(params, tokens, cache, lengths)
                return sample(logits), cache

            self._decode = jit_program(_decode, "decode", name,
                                       donate_argnums=(2,))

    # ------------------------------------------------------------------
    def free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s is None:
                return i
        return None

    @property
    def active(self) -> bool:
        return any(s is not None for s in self.slots)

    def _active_slots(self) -> List[int]:
        return [i for i, s in enumerate(self.slots) if s is not None]

    # ------------------------------------------------------------------
    # prefill: one [B, S] pass per coalesced group
    # ------------------------------------------------------------------
    def _group_writer(self, group: PrefillGroup):
        """Per-layer pool writer scattering EVERY row's prompt KV to its
        own request's pages (the writer threads the donated pool buffer
        through B scatters per layer).  A suffix group's rows land at
        absolute positions starting at the fork (``group.fork`` is 0 for
        full-prompt groups)."""

        def writer(layer, layer_kv, pool):
            for i, (req, n_w) in enumerate(zip(group.requests,
                                               group.n_writes)):
                pool = self.virt.write_prompt_layer(
                    pool, self.name, req.request_id, layer, layer_kv, n_w,
                    batch_index=i, start=group.fork)
            return pool

        return writer

    @staticmethod
    def _keep_logits(reqs: List[Request], logits: jax.Array) -> None:
        """Hand each opted-in request the [vocab] logits row its first
        token was sampled from."""
        for i, req in enumerate(reqs):
            if req.keep_prompt_logits:
                req.prompt_logits = np.asarray(logits[i])

    def _commit_prefill(self, req: Request, tok: int) -> int:
        slot = self.free_slot()
        assert slot is not None
        self.slots[slot] = req
        self.lengths[slot] = req.prompt_tokens
        self.next_tokens[slot] = tok
        req.phase = Phase.DECODE
        req.output_ids.append(tok)       # the prefill-sampled first token
        return slot

    def _commit_group(self, group: PrefillGroup, logits: jax.Array
                      ) -> List[int]:
        toks = np.asarray(sample(logits))
        self._keep_logits(group.requests, logits)
        return [self._commit_prefill(req, int(toks[i]))
                for i, req in enumerate(group.requests)]

    def cache_insert_candidate(self, group: PrefillGroup) -> bool:
        """Whether this group's committed prompt should be indexed in the
        prefix tree.  Insertion is restricted to B=1 streaming groups with
        REAL untruncated prompt ids: coalesced rows run under a vmapped
        MoE whose captured routing is not guaranteed bit-identical to the
        B=1 replay, and synthetic prompts are silently cache-cold."""
        cache = self.prefix_cache
        if cache is None or self.name not in cache.models:
            return False
        if group.batch_size != 1:
            return False
        req = group.requests[0]
        return (req.cache and req.prompt_ids is not None
                and 0 < req.prompt_tokens <= group.bucket)

    def _prefill_suffix(self, group: PrefillGroup, capture: bool):
        """Run the uncached-suffix pass of a prefix-cache hit: the cached
        KV rows are gathered through the request's (shared) page table and
        the suffix executes at absolute positions ``[fork, prompt)`` with
        the producing pass's KV extent and (MoE) expert-capacity slots, so
        every written row is bit-exact with a cold full pass."""
        req = group.requests[0]
        fork = group.fork
        prefix_rows = self.virt.gather_prompt_rows(
            self.name, req.request_id, fork)
        slot_offsets, capacity = None, 0
        if self.cfg.is_moe:
            routes = self.prefix_info.get(req.request_id, (0, None))[1]
            assert routes is not None and len(routes) >= fork, \
                "MoE suffix prefill needs the prefix's captured routing"
            E = self.cfg.n_experts
            # per-layer routed-pair counts of the prefix tokens: the
            # suffix tokens' dispatch slots start BEHIND them, exactly
            # where the producing full pass's cumsum placed them
            slot_offsets = np.stack([
                np.bincount(np.asarray(routes[:fork, l, :],
                                       np.int64).ravel(),
                            minlength=E).astype(np.int32)
                for l in range(self.cfg.n_layers)])
            capacity = expert_capacity(group.bucket, self.cfg)
        return self.prefill_step.suffix(
            jnp.asarray(group.tokens()), group.true_lens(), fork,
            group.bucket, prefix_rows, self.virt.pool,
            self._group_writer(group), slot_offsets, capacity,
            capture_routes=capture)

    def _cache_insert(self, group: PrefillGroup) -> None:
        """Index a just-committed prompt in the prefix tree: the request's
        page-table entries become shared chunk pages (refcount +1 each via
        ``insert``), with the captured MoE routing attached so later
        suffix passes can replay dispatch exactly."""
        req = group.requests[0]
        routes = None
        if self.cfg.is_moe:
            cap = self.prefill_step.captured_routes
            if cap is None:
                return
            if group.fork > 0:
                pre = self.prefix_info.get(req.request_id, (0, None))[1]
                if pre is None:
                    return
                routes = np.concatenate(
                    [np.asarray(pre[:group.fork]),
                     cap[:req.prompt_tokens - group.fork]], axis=0)
            else:
                routes = cap[:req.prompt_tokens]
        ids = np.asarray(req.prompt_ids,
                         np.int32).reshape(-1)[:req.prompt_tokens]
        rp = self.virt.requests[req.request_id]
        L = self.view.n_kv_layers
        n_chunks = math.ceil(req.prompt_tokens / self.view.tokens_per_page)
        chunk_pages = [[rp.tables[layer][c] for layer in range(L)]
                       for c in range(n_chunks)]
        self.prefix_cache.insert(self.name, group.bucket, ids, chunk_pages,
                                 routes)

    def prefill_group(self, group: PrefillGroup) -> List[int]:
        """Execute one coalesced prompt pass and commit each row to a
        batch slot; returns the slots in row order."""
        # check BEFORE any device work: a full batch must fail here, not
        # after the prompt KV has already been scattered into the pool
        free = sum(1 for s in self.slots if s is None)
        assert group.batch_size <= free, (group.batch_size, free)
        if self.paged:
            for req in group.requests:
                # admission-mapped pages may have been swapped while the
                # request waited for a slot; prompt-KV scatters need them
                # device-resident (their contents are still unwritten)
                self.virt.ensure_resident(req.request_id)
            insert = self.cache_insert_candidate(group)
            if group.fork > 0:
                # prefix-cache hit: prefill ONLY the uncached suffix
                logits, self.virt.pool = self._prefill_suffix(group, insert)
            else:
                # streaming prompt phase: per-layer attention with the next
                # layer's arena slabs uploading behind it; every row's
                # prompt KV is scattered into pool pages as each layer
                # completes
                logits, self.virt.pool = self.prefill_step(
                    jnp.asarray(group.tokens()), group.true_lens(),
                    self.virt.pool, self._group_writer(group),
                    capture_routes=insert)
            if insert:
                self._cache_insert(group)
            return self._commit_group(group, logits)
        # fallback families: per-slot dense prefill, one row at a time
        slots = []
        for ids, req in zip(group.ids, group.requests):
            slot = self.free_slot()
            assert slot is not None
            logits, self.cache = self._prefill(
                self.params, jnp.asarray(ids[None, :]), self.cache,
                jnp.int32(slot), jnp.int32(req.prompt_tokens))
            self._keep_logits([req], logits)
            slots.append(self._commit_prefill(
                req, int(sample(logits)[0])))
        return slots

    def make_prefill_batch(self, group: PrefillGroup,
                           batch_id: int) -> InflightBatch:
        """Package one group's prompt phase for the layer-wise scheduler
        (interleaves with other models' prefill/decode stages)."""
        if self.paged:
            for req in group.requests:
                self.virt.ensure_resident(req.request_id)
        return InflightBatch(
            batch_id=batch_id, model=self.name,
            tokens=jnp.asarray(group.tokens()), prefill=True,
            true_len=group.true_lens(), kv_writer=self._group_writer(group))

    def apply_prefill_result(self, batch: InflightBatch,
                             group: PrefillGroup) -> List[int]:
        return self._commit_group(group, batch.logits)

    # ------------------------------------------------------------------
    # decode: issue (non-blocking dispatch) / commit (block + bookkeeping)
    # ------------------------------------------------------------------
    def _reserve_decode_block(self) -> Tuple[List[int], np.ndarray]:
        """Pre-map every active request's pages for this dispatch's token
        block (paged models map BEFORE the step; DESIGN.md §9).

        Per active row the block is ``min(decode_steps, remaining declared
        output, context headroom)`` tokens — never more than admission
        reserved, so the PR-5 ``reserve_pages`` pressure accounting still
        bounds decode-time needs.  Ordering: swapped pages fault back in
        (``ensure_resident``) FIRST, then the block is reserved, then the
        batch tables are built — the device program indexes into the
        pre-extended table, so no host table mutation happens
        mid-dispatch.  ``req.tokens`` is NOT advanced here: the commit
        after the dispatch advances it by the tokens actually emitted and
        returns unused reserved pages.

        Atomic across the batch: the total page need is checked up front,
        so a pool exhausted mid-serve raises with NO per-request token
        drift (active pages are never revoked — paper §3.1; with the
        admission controller's output reservation this is unreachable
        unless the budget is under-planned).
        """
        act = self._active_slots()
        steps = np.zeros(self.max_batch, np.int32)
        for i in act:
            # the swap tier's "next touch": pages a shrink pushed to the
            # host fault back in before this step's tables are built
            self.virt.ensure_resident(self.slots[i].request_id)
        for i in act:
            req = self.slots[i]
            steps[i] = max(1, min(self.decode_steps,
                                  req.max_new_tokens - req.generated,
                                  self.max_ctx - int(self.lengths[i])))
        need = sum(self.virt.pages_needed_for_extend(
            self.slots[i].request_id, int(steps[i])) for i in act)
        if need > self.virt.free_pages:
            raise OutOfPagesError(
                f"{self.name}: decode block needs {need} pages, "
                f"{self.virt.free_pages} free — raise page_budget or plan "
                f"with a higher quantile")
        for i in act:
            self.virt.reserve_decode_block(self.slots[i].request_id,
                                           int(steps[i]))
        return act, steps

    def prepare_step(self) -> Tuple[jax.Array, jax.Array, jax.Array,
                                    List[int], np.ndarray]:
        """(tokens, page_tables [L,B,P], lengths, active slots,
        per-slot step budget [max_batch])."""
        act, steps = self._reserve_decode_block()
        rids = [s.request_id if s is not None else None for s in self.slots]
        tables = self.virt.batch_tables(self.name, rids, self.max_pages)
        return (jnp.asarray(self.next_tokens), tables,
                jnp.asarray(self.lengths), act, steps)

    def _eos_ids(self) -> np.ndarray:
        eos = np.full(self.max_batch, -1, np.int32)
        for i, req in enumerate(self.slots):
            if req is not None and req.eos_id is not None:
                eos[i] = req.eos_id
        return eos

    def issue_decode(self, host_step: Optional[HostDrivenStep] = None
                     ) -> Tuple[jax.Array, List[int], np.ndarray]:
        """Dispatch one decode block for all slots; returns
        (token ids [K, B] — still lazy, not blocked on — active slots,
        per-slot step budgets)."""
        if self.paged:
            tokens, tables, lengths, act, steps = self.prepare_step()
            if host_step is not None:
                # ablation baseline: per-layer host dispatches, K=1, with
                # logits returned to the host and sampled there
                logits, pool = host_step(tokens, self.virt.pool, tables,
                                         lengths)
                toks = sample(logits)[None, :]
            else:
                toks, pool = self.fused(
                    tokens, self.virt.pool, tables, lengths,
                    jnp.asarray(steps), jnp.asarray(self._eos_ids()))
            self.virt.pool = pool
            return toks, act, steps
        act = self._active_slots()
        toks, self.cache = self._decode(
            self.params, jnp.asarray(self.next_tokens), self.cache,
            jnp.asarray(self.lengths))
        steps = np.zeros(self.max_batch, np.int32)
        steps[act] = 1
        return toks[None, :], act, steps

    def commit_decode(self, pending: Tuple[jax.Array, List[int], np.ndarray]
                      ) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """Block on a dispatched block and commit it: token/length state,
        page-table commit (unused reserved pages return to the pool).
        Returns (tokens [B, K], per-slot valid counts, active slots) —
        valid tokens are a strict prefix of each row; -1 marks the tail
        of a row frozen early (EOS / per-row budget)."""
        toks_dev, act, steps = pending
        toks = np.asarray(jax.block_until_ready(toks_dev)).T   # [B, K]
        counts = np.zeros(self.max_batch, np.int64)
        for i in act:
            row = toks[i]
            n = int((row >= 0).sum())
            counts[i] = n
            if n:
                self.lengths[i] += n
                self.next_tokens[i] = row[n - 1]
            rid = self.slots[i].request_id
            if self.paged:
                self.virt.commit_decode_block(rid, n)
            else:
                # fallback families: page accounting AFTER the step (their
                # KV lives in the dense cache; pages track budget only)
                self.virt.extend_request(rid, n)
        return toks, counts, act

    def decode_once(self, host_step: Optional[HostDrivenStep] = None
                    ) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """One decode dispatch for all active slots; returns
        (tokens [B, K], valid counts, slots)."""
        return self.commit_decode(self.issue_decode(host_step))

    # ------------------------------------------------------------------
    def make_inflight_batch(self, batch_id: int) -> Tuple[InflightBatch, List[int]]:
        """Package this model's slots for the layer-wise scheduler."""
        tokens, tables, lengths, act, _ = self.prepare_step()
        return InflightBatch(
            batch_id=batch_id, model=self.name, tokens=tokens,
            page_tables=tables, lengths=lengths), act

    def apply_pipeline_result(self, batch: InflightBatch, act: List[int]
                              ) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """Write back an InflightBatch completed by the scheduler (KV is
        already in the pool; only token/length state lives here).  The
        layer-wise scheduler is host-driven and therefore always K=1."""
        toks = np.asarray(sample(batch.logits))
        counts = np.zeros(self.max_batch, np.int64)
        for i in act:
            self.lengths[i] += 1
            self.next_tokens[i] = toks[i]
            counts[i] = 1
            self.virt.commit_decode_block(self.slots[i].request_id, 1)
        return toks[:, None], counts, act

    def release(self, slot: int) -> Request:
        req = self.slots[slot]
        self.slots[slot] = None
        return req


class CrossPoolEngine:
    """The serving session: ``submit`` / ``step`` / ``cancel`` / ``drain``.

    One engine instance IS one continuously-batched serving session over
    the shared pools.  ``run(requests)`` remains as a thin offline
    wrapper that submits arrivals when due and steps to completion.
    """

    def __init__(self, models: Dict[str, ModelConfig], *,
                 page_budget: int, page_bytes: int = DEFAULT_PAGE_BYTES,
                 slot_budget: Optional[int] = None,
                 slab_bytes: int = DEFAULT_SLAB_BYTES,
                 max_batch: int = 4, max_ctx: int = 256,
                 config: Optional[EngineConfig] = None,
                 mode: Optional[EngineMode] = None, seed: int = 0,
                 slow_step_factor: float = 4.0,
                 elastic: Optional[ElasticConfig] = None,
                 observer: Optional[EngineObserver] = None):
        # ``config=EngineConfig(...)`` is the canonical construction
        # surface; the loose ``mode=`` / ``elastic=`` kwargs that accreted
        # across PRs remain as deprecated aliases for one release
        cache_cfg: Optional[CacheConfig] = None
        slo_cfg = None
        rec_cfg = None
        if config is not None:
            if mode is not None or elastic is not None:
                raise TypeError(
                    "pass mode/elastic inside config=EngineConfig(...); "
                    "the loose kwargs are aliases, not overrides")
            mode = config.mode
            elastic = config.elastic
            cache_cfg = config.cache
            slo_cfg = config.slo
            rec_cfg = config.flightrec
        elif mode is not None or elastic is not None:
            warnings.warn(
                "CrossPoolEngine(mode=..., elastic=...) is deprecated; "
                "pass config=EngineConfig(mode=..., elastic=..., "
                "cache=...) instead",
                DeprecationWarning, stacklevel=2)
        self.models = models
        self.mode = mode or EngineMode()
        self.max_ctx = max_ctx
        self.rng = np.random.default_rng(seed)
        devs = jax.devices()
        self.kv_device, self.w_device = devs[0], devs[-1]

        # each model's params are built, split and offloaded in turn, so
        # one full tree is on the device at a time; fused-fallback
        # families keep theirs for their runners
        keys = {n: jax.random.PRNGKey(i) for i, n in enumerate(models)}
        fallback_params: Dict[str, Dict] = {}

        def init_params(name: str) -> Dict:
            tree = build_model(models[name]).init(keys[name])
            if not split_exec.supports_split(models[name]):
                fallback_params[name] = tree
            return tree

        # the pool dtype is the lowest common denominator of the colocated
        # models (heterogeneous models reinterpret the same untyped pages)
        pool_dtype = (jnp.float32
                      if any(c.dtype == "float32" for c in models.values())
                      else jnp.bfloat16)
        # a live device pool is only needed when some model decodes through
        # it; an all-fallback engine keeps host-side page accounting only
        any_split = any(split_exec.supports_split(c) for c in models.values())
        self.kv_pool, self.w_pool, self.pooled = build_pools(
            models, init_params, kv_device=self.kv_device,
            w_device=self.w_device,
            page_budget=page_budget, page_bytes=page_bytes,
            pool_dtype=pool_dtype, allocate_device_pool=any_split,
            slot_budget=slot_budget, slab_bytes=slab_bytes,
            # the fused step is ONE program with a single placement, so the
            # arena must be colocated with the KV pool when lowering is on;
            # host-driven mode keeps it in the weights pool, where FFN runs
            arena_device=(self.kv_device if self.mode.lowering
                          else self.w_device),
            # engine-managed activation: models become resident when their
            # first request reaches a batch slot (cold-model activation)
            activate_resident=False)
        self.virt = self.kv_pool.virtualizer
        self.arena = self.w_pool.arena if any_split else None
        # arena-aware admission: cold-model bursts queue at the front door
        # instead of thrashing the arena LRU between admitted models
        self.admission = AdmissionController(self.virt, arena=self.arena)
        # radix-tree prefix cache over the shared pool (DESIGN.md §11) —
        # OFF by default; cacheable models are the split-execution subset
        # (fallback families' pool pages are accounting-only, there is no
        # KV to share).  The tree registers itself as the virtualizer's
        # cache_provider so elastic shrink/compaction see its pages.
        self.cache: Optional[PrefixCache] = None
        if cache_cfg is not None and cache_cfg.enabled and any_split:
            cacheable = [n for n in models
                         if self.pooled[n].stage_fns is not None]
            self.cache = PrefixCache(self.virt, cache_cfg,
                                     models=cacheable)
            self.admission.cache = self.cache
        # observability (DESIGN.md §10): the observer is OPTIONAL — every
        # step-loop site is guarded by ``observer is not None`` so the
        # disabled path allocates and calls nothing — but a lightweight
        # metrics registry is always on (it backs ``report()``'s
        # structured-event lines); with an observer the engine shares its
        # registry, so /metrics and report() read the same counters.
        self.observer = observer
        self.metrics = (observer.metrics if observer is not None
                        else MetricsRegistry())
        # pool shadow-sanitizer (DESIGN.md §12): pure checking, attached
        # only on request — ``EngineConfig(sanitize=True)`` or the
        # ``CROSSPOOL_SANITIZE=1`` env var (CI's sanitized tier-1 leg).
        # It rides the same hook stream as the observer (CompositeHooks
        # fans out, sanitizer last so the observer sees the event even
        # when the sanitizer raises) and audits at step boundaries.
        self.sanitizer: Optional[PoolSanitizer] = None
        want_sanitize = ((config is not None and config.sanitize)
                         or os.environ.get("CROSSPOOL_SANITIZE", "") == "1")
        if want_sanitize:
            self.sanitizer = PoolSanitizer(
                self.virt, arena=self.arena, admission=self.admission,
                cache=self.cache)
        # SLO engine (DESIGN.md §13): declarative burn-rate objectives,
        # evaluated once per step over engine-virtual-time samples.  It
        # shares the engine registry, so breach counters/events land next
        # to the latency histograms they judge.
        self.slo: Optional[SLOMonitor] = None
        if slo_cfg is not None and slo_cfg.objectives:
            self.slo = SLOMonitor(slo_cfg, registry=self.metrics)
        # flight recorder (DESIGN.md §13): the session black box.  Built
        # AFTER the pools (its dumps snapshot final accounting) and wired
        # into the hook stream between the observer and the sanitizer, so
        # a raising audit cannot hide the event that tripped it.
        self.recorder: Optional[FlightRecorder] = None
        if rec_cfg is not None and rec_cfg.enabled:
            self.recorder = FlightRecorder(
                rec_cfg,
                header=engine_header(
                    models=models, page_budget=page_budget,
                    page_bytes=page_bytes, slot_budget=slot_budget,
                    slab_bytes=slab_bytes, max_batch=max_batch,
                    max_ctx=max_ctx, seed=seed, mode=self.mode,
                    elastic=elastic, cache=cache_cfg,
                    sanitize=want_sanitize, slo=slo_cfg,
                    flightrec=rec_cfg),
                virt=self.virt, arena=self.arena, cache=self.cache)
        sinks = [s for s in (observer, self.recorder, self.sanitizer)
                 if s is not None]
        sink = (sinks[0] if len(sinks) == 1
                else CompositeHooks(*sinks) if sinks else None)
        # the fan-out target for engine-originated events too (SLO
        # breaches), so observer/recorder/sanitizer see one stream
        self._sink = sink
        if sink is not None:
            self.virt.hooks = sink
            if self.arena is not None:
                self.arena.hooks = sink
            self.admission.hooks = sink
            if self.cache is not None:
                self.cache.hooks = sink
        # elastic boundary (DESIGN.md §8): windowed demand telemetry +
        # step-boundary KV<->weights repartitioning.  Telemetry observes
        # even with rebalancing disabled IF a config is passed; both stay
        # None on the default (frozen-split) path.
        self.telemetry: Optional[DemandTelemetry] = None
        self.rebalancer: Optional[ElasticRebalancer] = None
        if elastic is not None and self.arena is not None:
            self.telemetry = DemandTelemetry(models, elastic,
                                             gauges=observer)
            self.rebalancer = ElasticRebalancer(
                self.virt, self.arena, admission=self.admission,
                telemetry=self.telemetry, cfg=elastic, seed=seed)
            # cache-aware re-plan: the tree's hit-token fraction
            # discounts windowed KV demand (shared pages map free)
            self.rebalancer.cache = self.cache
            if sink is not None:
                self.rebalancer.hooks = sink

        self.host_steps = None
        self.scheduler = None
        if not self.mode.lowering:
            self.host_steps = {
                n: HostDrivenStep(self.pooled[n], self.kv_device,
                                  self.w_device)
                for n in models if self.pooled[n].stage_fns is not None
            }
            self.scheduler = LayerPipelineScheduler(
                self.pooled, self.kv_device, self.w_device,
                steps=self.host_steps)
        # streaming prompt-phase executors (per-layer transfers follow the
        # arena's placement: colocated with the KV pool under lowering=ON);
        # in host mode they SHARE the HostDrivenStep's jitted stage
        # programs — one trace/compile cache per model
        prefill_steps = {
            n: StreamingPrefill(
                self.pooled[n], kv_device=self.kv_device,
                w_device=self.w_pool.arena.device,
                share=None if self.host_steps is None
                else self.host_steps.get(n))
            for n in models if self.pooled[n].stage_fns is not None
        }
        # paged models hold NO full param tree: each init-time tree was
        # split into pooled kv_params + the arena's packed host masters,
        # and its full copy dropped (fallback families keep theirs)
        self.runners = {
            n: ModelRunner(
                n, c,
                fallback_params.get(n), self.virt,
                max_batch=max_batch, max_ctx=max_ctx,
                mode=self.mode, pooled=self.pooled[n],
                prefill_step=prefill_steps.get(n))
            for n, c in models.items()
        }
        self.stats = EngineStats(step_times={n: [] for n in models},
                                 admission=self.admission.stats)
        # admission-time prefix-cache outcomes for live requests:
        # request_id -> (fork, captured prefix routes) — the batcher's
        # fork map and the suffix pass's dispatch replay read this
        self._prefix_info: Dict[int, Tuple[int, Optional[np.ndarray]]] = {}
        for r in self.runners.values():
            r.prefix_cache = self.cache
            r.prefix_info = self._prefix_info

        # --- session state -------------------------------------------------
        self.now = 0.0
        self.batcher = PrefillBatcher(observer=observer)
        self.handles: Dict[int, RequestHandle] = {}
        self.waiting: List[Request] = []     # admitted, no batch slot yet
        self._submitted: Dict[int, Request] = {}
        self._window: set = set()            # request ids in the stats window
        self._events: List[TokenEvent] = []
        self._in_step = False
        self._deferred_cancels: List[RequestHandle] = []
        self._step_index = 0               # monotone step counter
        # replay clock (flightrec): when attached, dispatch dt comes from
        # the recorded stream instead of time.perf_counter — the ONLY
        # nondeterministic input the engine folds into virtual time
        self._replay_dts: Optional[collections.deque] = None

    # ------------------------------------------------------------------
    # the session API
    # ------------------------------------------------------------------
    def advance(self, now: float) -> float:
        """Move the session clock forward (it never runs backwards).
        External drivers advance to an arrival's due time BEFORE
        submitting it, so admission/queue-wait bookkeeping is stamped
        with the arrival clock — exactly as the ``run()`` wrapper does."""
        self.now = max(self.now, float(now))
        if self.recorder is not None:
            self.recorder.record_op("advance", now=self.now)
        return self.now

    def submit(self, req: Request, on_token=None) -> RequestHandle:
        """Offer one request to the front door at the engine's current
        time; the admission verdict is on the returned handle."""
        assert req.request_id not in self._submitted, \
            f"request id {req.request_id} already submitted"
        if self.recorder is not None:
            # recorded BEFORE any mutation: the op is the causal input,
            # whatever verdict admission hands back
            self.recorder.record_submit(req, self.now)
        self._submitted[req.request_id] = req
        self._window.add(req.request_id)
        if self.telemetry is not None:
            self.telemetry.note_arrival(req.model, self.now)
        outcome = self._admit(req, self.now)
        if outcome == "admitted":
            req.admit_time = self.now
            self.waiting.append(req)
            state = HandleState.ADMITTED
        elif outcome == "queued":
            state = HandleState.QUEUED
        else:
            state = HandleState.REJECTED
        info = self._prefix_info.get(req.request_id)
        handle = RequestHandle(request=req, admission=outcome, state=state,
                               on_token=on_token,
                               cached_tokens=info[0] if info else 0,
                               cache_hit=bool(info and info[0] > 0),
                               _engine=self)
        self.handles[req.request_id] = handle
        if self.observer is not None:
            self.observer.request_submitted(req, outcome)
        if self.slo is not None and outcome == "admitted":
            # immediate admissions are zero-wait queue samples: without
            # them one slow drain would read as a 100% bad window
            self.slo.note("queue_wait", req.model, 0.0, self.now)
        if self.sanitizer is not None and not self._in_step:
            try:
                self.sanitizer.audit()  # admission mapping is quiescent too
            except (PoolSanitizerError, PoolAccountingError) as err:
                if self.recorder is not None:
                    self.recorder.note_failure(self._step_index, err)
                raise
        return handle

    def step(self, now: Optional[float] = None) -> List[TokenEvent]:
        """One engine step: drain -> batched prefill -> decode ->
        completions.  Returns the tokens generated this step (streaming
        callbacks fire inline as each batch commits)."""
        if now is not None:
            self.now = max(self.now, float(now))
        self._step_index += 1
        rec = self.recorder
        if rec is not None:
            rec.record_step(self._step_index, self.now)
        self._events = []
        self._in_step = True
        obs = self.observer
        if obs is not None:
            obs.step_begin(self.now)
        try:
            try:
                self._step_phases()
            finally:
                if obs is not None:
                    obs.step_end()
                self._in_step = False
                deferred, self._deferred_cancels = \
                    self._deferred_cancels, []
                for handle in deferred:     # reentrant cancels, now safe
                    self.cancel(handle, _deferred=True)
            if self.sanitizer is not None:
                # quiescent point: no cross-object handoff is mid-flight
                # here, so the full structural walk (SAN01..SAN08) is sound
                self.sanitizer.audit()
        except (PoolSanitizerError, PoolAccountingError) as err:
            # black-box the incident before surfacing it: the dumped
            # record replays to this same failing step (DESIGN.md §13)
            if rec is not None:
                rec.note_failure(self._step_index, err)
            raise
        if rec is not None:
            # breach auto-dumps land HERE, not at the breach itself: the
            # step has fully retired, so the record's final accounting is
            # a state replay can reproduce (DESIGN.md §13)
            rec.maybe_breach_dump()
        return self._events

    def _drain_front_door(self) -> None:
        obs = self.observer
        for p in self.admission.drain(self.now):
            req = self._submitted[p.request_id]
            req.admit_time = self.now
            if self.slo is not None:
                self.slo.note("queue_wait", p.model,
                              self.now - p.enqueue_time, self.now)
            handle = self.handles[req.request_id]
            handle.state = HandleState.ADMITTED
            if self.cache is not None:
                self._prefix_info[p.request_id] = (p.cached_tokens,
                                                   p.prefix_routes)
                handle.cached_tokens = p.cached_tokens
                handle.cache_hit = p.cached_tokens > 0
            self.waiting.append(req)
            if obs is not None:
                obs.request_admitted(req)

    def _step_phases(self) -> None:
        obs = self.observer
        # --- drain the front-door queue (resources freed last step) ------
        if obs is not None:
            obs.phase_begin("admission_drain")
        self._drain_front_door()
        if obs is not None:
            obs.phase_end("admission_drain")
            obs.phase_begin("batcher")

        # --- prefill: coalesce admitted arrivals into [B, S] groups ------
        forks = None
        if self.cache is not None:
            forks = {rid: info[0] for rid, info in self._prefix_info.items()
                     if info[0] > 0}
        groups, self.waiting = self.batcher.plan(
            self.waiting, self.runners, self.rng, self._try_activate, forks)
        if obs is not None:
            obs.phase_end("batcher")
        if groups:
            if obs is not None:
                obs.phase_begin("prefill")
            self.now = self._prefill_groups(groups, self.now)
            if obs is not None:
                obs.phase_end("prefill")

        # --- decode: one step per active model ---------------------------
        active = [n for n, r in self.runners.items() if r.active]
        if self.mode.pipeline and len(active) >= 2:
            self.now = self._decode_pipelined(active, self.now)
        else:
            for n in active:
                self.now = self._decode_model(n, self.now)

        # --- completions -------------------------------------------------
        if obs is not None:
            obs.phase_begin("completions")
        for n, runner in self.runners.items():
            for slot, req in enumerate(runner.slots):
                if req is not None and req.done:
                    runner.release(slot)
                    self._finish(req, self.now)
        if obs is not None:
            obs.phase_end("completions")
            obs.phase_begin("rebalance")

        # --- elastic boundary (step-boundary ONLY: no batch is in flight,
        #     so page tables and slot tables can remap atomically) --------
        self._observe_and_rebalance()
        if obs is not None:
            obs.phase_end("rebalance")

        # --- SLO burn-rate scan + pool timelines/snapshots ---------------
        # (after rebalance so breaches and snapshots see the step's final
        # pool shape; all guarded — observer=None + recorder-off pays two
        # ``is not None`` checks and allocates nothing)
        if self.slo is not None:
            for breach in self.slo.evaluate(self.now):
                if self._sink is not None:
                    self._sink.slo_breach(breach)
        rec = self.recorder
        snap_due = rec is not None and rec.snapshot_due(self._step_index)
        if obs is not None or snap_due:
            snap = pool_snapshot(self.virt, self.arena, self.cache)
            if obs is not None:
                obs.pool_counters(snap)
            if snap_due:
                rec.snapshot(self._step_index, self.now, snap)

    def _observe_and_rebalance(self) -> None:
        """Fold this step into the telemetry window and let the
        rebalancer repartition the device-byte boundary if the windowed
        Eq. (1)-(2) estimate says so (DESIGN.md §8)."""
        if self.observer is not None:
            # gauges refresh BEFORE telemetry folds its EWMAs, so the
            # gauge-fed fold sees THIS step's occupancy/queue values
            self.observer.sample(self.virt, self.arena, self.admission,
                                 len(self.waiting))
        if self.telemetry is None:
            return
        self.telemetry.observe(self.now, self.virt, self.arena,
                               self.admission)
        if self.rebalancer is None:
            return
        protected: Dict[int, int] = {}
        live: Optional[Dict[str, list]] = None
        if self.rebalancer.would_evaluate():
            # slotted requests with their REMAINING declared output: the
            # KV shrink floor reserves their whole lifetime, same as
            # admission did.  Assembled only on re-plan steps — the common
            # step pays one counter check, not an O(slots+queued) walk.
            protected = {
                req.request_id: max(req.max_new_tokens - req.generated, 1)
                for runner in self.runners.values()
                for req in runner.slots if req is not None}
            live = {}
            for req in self.waiting:
                live.setdefault(req.model, []).append(
                    (req.prompt_tokens, req.max_new_tokens))
            for runner in self.runners.values():
                for req in runner.slots:
                    if req is not None:
                        live.setdefault(req.model, []).append(
                            (req.prompt_tokens, req.max_new_tokens))
            # queued requests are the clearest demand signal of all —
            # they are EXACTLY what the old split could not admit
            for q in self.admission.queues.values():
                for p in q:
                    live.setdefault(p.model, []).append(
                        (p.prompt_tokens, p.expected_output))
        decision = self.rebalancer.step(self.now, protected=protected,
                                        live_requests=live)
        if decision is not None:
            # the budgets just changed: re-drain the front door NOW, so a
            # session where everything was queued behind the old split
            # makes progress this step (run()/drain() exit when a step
            # produces no events and nothing is admitted — without this,
            # a grow that frees room for queued-only load would be
            # followed by the loop breaking before its next drain)
            self._drain_front_door()
            # the registry's bounded event log is report()'s ONLY source
            # for move lines, so text report and exported metrics agree
            self.metrics.log_event(
                "rebalance", step=decision.step, time=decision.now,
                page_budget=(decision.old_page_budget,
                             decision.new_page_budget),
                slot_budget=(decision.old_slot_budget,
                             decision.new_slot_budget),
                swapped_out=decision.swapped_out,
                evicted_models=decision.evicted_models,
                reason=decision.reason)
            self.stats.rebalance_events.append(RebalanceEvent(
                step=decision.step, time=decision.now,
                page_budget=(decision.old_page_budget,
                             decision.new_page_budget),
                slot_budget=(decision.old_slot_budget,
                             decision.new_slot_budget),
                kv_delta_bytes=(decision.new_page_budget
                                - decision.old_page_budget)
                * self.virt.page_bytes,
                swapped_out=decision.swapped_out,
                evicted_models=decision.evicted_models,
                reason=decision.reason))

    def cancel(self, handle: Union[RequestHandle, int], *,
               _deferred: bool = False) -> bool:
        """Abort a submitted request, atomically returning its resources.

        Unpins weight slabs and frees KV pages in one host-side
        transaction (no device work, nothing can fail part-way):
        queued requests hold nothing and just leave the queue; admitted
        requests release their admission-time pages and drop the arena
        pin via ``AdmissionController.finish`` — the same teardown a
        natural completion uses — whether they are still waiting for a
        slot (mid-prefill) or already decoding.

        Reentrancy: a cancel issued from inside an ``on_token`` callback
        (the "stop at token X" pattern) lands while the step's commit
        loops are mid-flight, so it is DEFERRED to the step boundary —
        the request may emit the rest of this step's tokens first, and a
        request that completes within the same step stays FINISHED.
        """
        if isinstance(handle, int):
            handle = self.handles[handle]
        if self.recorder is not None and (_deferred or not self._in_step):
            # ringed at APPLICATION time, not request time: a mid-step
            # cancel is deferred to the step boundary, and recording it
            # there keeps the ring position one a replayed session (which
            # applies the op after the step retires) lands on exactly
            self.recorder.record_cancel(handle.request.request_id,
                                        self.now, in_step=self._in_step)
        if handle.state.terminal:
            return False
        if self._in_step:
            if handle not in self._deferred_cancels:
                self._deferred_cancels.append(handle)
            return True
        req = handle.request
        if handle.state is HandleState.QUEUED:
            self.admission.cancel_queued(req.request_id)
        else:
            if handle.state is HandleState.DECODING:
                runner = self.runners[req.model]
                for slot, r in enumerate(runner.slots):
                    if r is req:
                        runner.release(slot)
                        break
            else:                            # ADMITTED: waiting for a slot
                self.waiting = [r for r in self.waiting
                                if r.request_id != req.request_id]
            # pages + pin go back together: the KV release and the
            # admission-side unpin are both pure bookkeeping, so there is
            # no window in which a cancelled request still holds memory
            self.virt.release_request(req.request_id)
            self.admission.finish(req.model)
            self._prefix_info.pop(req.request_id, None)
        req.phase = Phase.CANCELLED
        req.finish_time = self.now
        handle.state = HandleState.CANCELLED
        self.stats.cancelled += 1
        if self.observer is not None:
            self.observer.request_cancelled(req)
        return True

    def drain(self, *, max_steps: int = 10_000) -> EngineStats:
        """Step until every submitted request finished (or nothing can
        make progress / ``max_steps``); returns the finalized stats."""
        steps = 0
        while (self.waiting or self.admission.queued_count()
               or self._any_active()):
            if steps >= max_steps:
                break
            steps += 1
            events = self.step()
            if not events and not self.waiting and not self._any_active():
                # only queued requests remain and the pools are at rest:
                # nothing in flight can free pages/slabs, so drain() can
                # never make progress — exit instead of spinning
                break
        return self.finalize()

    def finalize(self) -> EngineStats:
        """Fold per-request latency samples into the stats snapshot."""
        self.stats.wall_s = self.now
        self.stats.tbt = [t for rid in self._window
                          for t in self._submitted[rid].tbt_samples()]
        if self.arena is not None:
            self.stats.weights_pool = self.arena.utilization()
        if self.telemetry is not None:
            self.stats.elastic = self.telemetry.snapshot()
            if self.rebalancer is not None:
                self.stats.elastic.update(self.rebalancer.snapshot())
        return self.stats

    def reset_stats(self) -> EngineStats:
        """Open a fresh measurement window on a live session (long-running
        sessions measure in windows: warmup/steady-state, per-tenant
        SLOs).  Step-time logs, token counters and per-request latency
        folds restart; the admission controller's lifetime counters keep
        accumulating and stay visible on the new snapshot.  Terminal
        requests and their handles are PRUNED here — this is the point
        that bounds a long-lived session's memory — so a session that
        never resets retains every handle it ever created."""
        if self.recorder is not None:
            # causal: pruning changes later admission-assert behavior and
            # the stats window, so a replay must reset at the same point
            self.recorder.record_op("reset_stats", now=self.now)
        self.stats = EngineStats(step_times={n: [] for n in self.models},
                                 admission=self.admission.stats)
        for rid, handle in list(self.handles.items()):
            if handle.state.terminal:
                del self.handles[rid]
                del self._submitted[rid]
        self._window.clear()
        if self.observer is not None:
            self.observer.reset_window()
        if self.slo is not None:
            # windowed SLO state follows the windowed histograms
            self.slo.reset()
        return self.stats

    # ------------------------------------------------------------------
    # offline compatibility wrapper
    # ------------------------------------------------------------------
    def run(self, requests: List[Request], *,
            max_steps: int = 10_000) -> EngineStats:
        """Serve a pre-generated trace to completion (or max_steps): a
        thin wrapper that submits arrivals when due and calls ``step`` —
        there is no second serving loop."""
        pending = sorted(requests, key=lambda r: r.arrival_time)
        steps = 0
        while (pending or self.waiting or self.admission.queued_count()
               or self._any_active()):
            if steps >= max_steps:
                break
            steps += 1
            # jump virtual time to the next arrival if idle
            if not self.waiting and not self._any_active() and pending:
                self.advance(pending[0].arrival_time)
            due = [r for r in pending if r.arrival_time <= self.now]
            pending = [r for r in pending if r.arrival_time > self.now]
            for r in due:
                self.submit(r)
            events = self.step()
            if (not events and not self.waiting and not pending
                    and not self._any_active()):
                # only queued requests remain (see ``drain``)
                break
        return self.finalize()

    # ------------------------------------------------------------------
    @property
    def busy(self) -> bool:
        """Whether stepping can make progress right now: requests are in
        batch slots or admitted-waiting (queued-only backpressure is
        visible via ``admission.queued_count()`` instead)."""
        return bool(self.waiting) or self._any_active()

    def _any_active(self) -> bool:
        return any(r.active for r in self.runners.values())

    def _activate_model(self, name: str) -> None:
        """Map a cold model's slabs before its first prefill — WITHOUT
        uploading: the streaming prompt phase prefetches layer L+1's slabs
        behind layer L's attention in BOTH lowering modes, so by the first
        decode step every layer is resident and the fused step's
        ``acquire`` has zero upload work left.  The per-request PIN was
        already taken at ADMISSION (``AdmissionController.try_admit``) and
        is released by ``admission.finish`` — so LRU eviction (triggered
        by some OTHER model's activation under slab pressure) can never
        revoke weights an admitted request still needs, even in the
        window before this activation makes the model resident.
        """
        if self.arena is None or not self.runners[name].paged:
            return
        self.arena.activate(name, upload=False)

    def _try_activate(self, req: Request) -> bool:
        """Residency gate for the prefill batcher: False keeps the
        request waiting (resident models' pins drop as they finish)."""
        name = req.model
        try:
            self._activate_model(name)
        except OutOfSlabsError:
            # every resident model is pinned by in-flight requests; those
            # pins drop as they finish, so the request stays waiting —
            # UNLESS the model can never fit even an empty arena
            if self.arena.views[name].total_slabs > self.arena.slot_budget:
                raise
            return False
        if self.runners[name].paged:
            try:
                # pages swapped to the host tier while the request waited
                # fault back in HERE, where deferral is graceful — inside
                # prefill_group a failed fault would abort the whole step
                self.virt.ensure_resident(req.request_id)
            except OutOfPagesError:
                return False
        return True

    # ------------------------------------------------------------------
    def _admit(self, req: Request, now: float) -> str:
        pending = PendingRequest(req.request_id, req.model,
                                 req.prompt_tokens, req.max_new_tokens, now)
        if self.cache is not None:
            if req.prompt_ids is not None:
                pending.prompt_ids = np.asarray(req.prompt_ids,
                                                np.int32).reshape(-1)
            pending.cache = req.cache
            pending.bucket = prompt_bucket(req.prompt_tokens, self.max_ctx)
        outcome = self.admission.offer(pending, now)
        if outcome == "admitted" and self.cache is not None:
            self._prefix_info[req.request_id] = (pending.cached_tokens,
                                                 pending.prefix_routes)
        if outcome == "rejected":
            req.phase = Phase.REJECTED
        return outcome

    def _finish(self, req: Request, now: float) -> None:
        req.phase = Phase.FINISHED
        req.finish_time = now
        if self.telemetry is not None:
            self.telemetry.note_finish(
                req.model, req.prompt_tokens, req.generated,
                req.admit_time, now)
        self.virt.release_request(req.request_id)
        # drops the admission-time pin too: idle models become evictable
        self.admission.finish(req.model)
        self._prefix_info.pop(req.request_id, None)
        handle = self.handles.get(req.request_id)
        if handle is not None:
            handle.state = HandleState.FINISHED
        if self.observer is not None:
            self.observer.request_finished(req)

    # ------------------------------------------------------------------
    def report(self) -> str:
        """Human-readable serving report: throughput, per-model admission
        outcomes, KV-pool and weights-arena utilization."""
        s = self.stats
        lines = [f"tokens={s.tokens_out} wall={s.wall_s:.2f}s "
                 f"throughput={s.throughput:.1f} tok/s "
                 f"slow_steps={s.slow_steps}"]
        adm = self.admission.stats
        lines.append(f"admission: admitted={adm.admitted} "
                     f"queued={adm.queued} rejected={adm.rejected} "
                     f"(pressure: pages={adm.page_pressure_queued} "
                     f"weights={adm.weight_pressure_queued}, "
                     f"reserve={self.admission.reserve_pages} pages)")
        for name in self.models:
            m = adm.per_model.get(name)
            if m is not None:
                lines.append(f"  {name}: admitted={m.admitted} "
                             f"queued={m.queued} rejected={m.rejected}")
        coalesced = [b for b in s.prefill_batch_sizes if b > 1]
        lines.append(f"prefill: {len(s.prefill_batch_sizes)} passes, "
                     f"{len(coalesced)} coalesced "
                     f"(max B = {max(s.prefill_batch_sizes, default=0)})")
        u = self.virt.utilization()
        lines.append(f"kv pool: peak {u['peak_mapped']}/"
                     f"{self.virt.page_budget} pages, "
                     f"frag {u['internal_frag_bytes'] / 1024:.1f} KiB, "
                     f"swap {u['swap_out_pages']} out / "
                     f"{u['swap_in_pages']} in "
                     f"({u['swapped_pages']} held), "
                     f"{u['resizes']} resizes")
        if self.cache is not None:
            c = self.cache.snapshot()
            lines.append(
                f"prefix cache: {int(c['hits'])} hits / "
                f"{int(c['misses'])} misses "
                f"({c['hit_token_fraction']:.1%} of prompt tokens cached), "
                f"{int(c['device_pages_held'])} pages held, "
                f"{int(c['shed_pages'])} shed / {int(c['faulted_pages'])} "
                f"re-faulted, {int(c['evicted_pages'])} evicted")
        if self.telemetry is not None:
            t = self.telemetry.snapshot()
            lines.append(
                f"elastic: occupancy EWMA kv={t['kv_occupancy_ewma']:.3f} "
                f"slabs={t['slab_occupancy_ewma']:.3f} "
                f"queue={t['queue_depth_ewma']:.2f}")
            if self.rebalancer is not None:
                r = self.rebalancer.snapshot()
                lines.append(
                    f"  rebalancer: {int(r['rebalances'])} applied / "
                    f"{int(r['evaluations'])} evaluated "
                    f"(hysteresis skips {int(r['skipped_hysteresis'])}, "
                    f"cooldown {int(r['skipped_cooldown'])}, "
                    f"aborted {int(r['aborted'])}); live split "
                    f"{int(r['page_budget'])} pages / "
                    f"{int(r['slot_budget'])} slabs")
                # rendered from the registry's event log (NOT EngineStats
                # lists), so this text can never disagree with /metrics
                for e in self.metrics.recent_events("rebalance", 3):
                    lines.append(
                        f"  move @step {e['step']}: pages "
                        f"{e['page_budget'][0]}->{e['page_budget'][1]}, "
                        f"slabs {e['slot_budget'][0]}->"
                        f"{e['slot_budget'][1]} "
                        f"({e['reason']}, swapped {e['swapped_out']}, "
                        f"evicted {e['evicted_models']})")
        if self.arena is not None:
            w = self.arena.utilization()
            lines.append(
                f"weights arena: {w['resident_slabs']}/{w['slot_budget']} "
                f"slabs resident ({w['resident_models']} models), "
                f"{w['activations']} activations, {w['evictions']} "
                f"evictions, {w['layer_uploads']} layer uploads")
            lines.append(
                f"  device FFN bytes (prefill AND decode): "
                f"{w['device_bytes'] / 2 ** 20:.1f} MiB — slot_budget x "
                f"slab_bytes, no full-tree phase remains")
        if self.slo is not None:
            lines.append(self.slo.report_line(self.now))
            for e in self.metrics.recent_events("slo_breach", 3):
                lines.append(
                    f"  breach @{e['time']:.2f}s: {e['model']} "
                    f"{e['metric']} > {e['threshold_ms']:g}ms "
                    f"(burn {e['long_burn']:.1f}x long / "
                    f"{e['short_burn']:.1f}x short, "
                    f"window value {e['window_value_ms']:.1f}ms)")
        dropped = self.metrics.events_dropped()
        if dropped:
            # the event log is bounded: consumers of recent_events() must
            # be able to see that the lines above may be truncated
            lines.append("event log overflow: " + ", ".join(
                f"{kind} dropped {n}"
                for kind, n in sorted(dropped.items())))
        if self.recorder is not None:
            lines.append(
                f"flight recorder: {len(self.recorder.ring)} events "
                f"ringed, {len(self.recorder.snapshots)} snapshots, "
                f"{self.recorder.dumps} dumps")
        return "\n".join(lines)

    # ------------------------------------------------------------------
    # virtual-clock folding (the engine's ONLY nondeterministic input)
    # ------------------------------------------------------------------
    def attach_replay_clock(self, entries) -> None:
        """Replay mode: dispatch durations come from this recorded
        ``(tag, dt)`` stream instead of the host clock.  Everything else
        in the engine is deterministic, so consuming the stream in order
        reproduces the original session bit-exactly (flightrec/replay)."""
        self._replay_dts = collections.deque(entries)

    def _clocked(self, tag: str, t0: float) -> float:
        """Wall duration of one dispatch since ``t0``: replay-injectable
        (a replaying engine consumes the recorded stream) and recorded
        (a recording engine logs the dt ACTUALLY USED, post-injection,
        so a replay re-records a bit-identical clock stream)."""
        dt = time.perf_counter() - t0  # cp: allow(CP006) real dispatch duration
        if self._replay_dts is not None:
            dt = self._next_replay_dt(tag)
        if self.recorder is not None:
            self.recorder.record_dt(tag, dt)
        return dt

    def _next_replay_dt(self, tag: str) -> float:
        if not self._replay_dts:
            raise ReplayDivergence(
                f"clock stream exhausted at '{tag}' "
                f"(step {self._step_index}): the replay dispatched more "
                f"work than the record")
        rec_tag, dt = self._replay_dts.popleft()
        if rec_tag != tag:
            raise ReplayDivergence(
                f"clock stream diverged at step {self._step_index}: "
                f"recorded '{rec_tag}', live '{tag}'")
        return float(dt)

    def _record_step(self, name: str, dt: float) -> None:
        log = self.stats.step_times[name]
        if len(log) > 8 and dt > np.median(log) * 4.0:
            self.stats.slow_steps += 1     # straggler flag
        log.append(dt)
        if self.observer is not None:
            # same per-model attribution as step_times, so the exported
            # dispatch histogram mirrors the stats log exactly
            self.observer.decode_dispatch(name, dt)

    def _host_step(self, name: str) -> Optional[HostDrivenStep]:
        if self.host_steps is None:
            return None
        return self.host_steps.get(name)

    def _emit(self, event: TokenEvent) -> None:
        self._events.append(event)
        handle = self.handles.get(event.request_id)
        if handle is not None and handle.on_token is not None:
            handle.on_token(event)

    def _book_tokens(self, runner: ModelRunner, toks: np.ndarray,
                     counts: np.ndarray, act: List[int], start: float,
                     dt: float) -> None:
        """Fan one committed decode block out into per-token events.

        ``toks`` is [B, K] with each row's valid tokens a strict prefix
        of length ``counts[i]``.  The dispatch's wall time ``dt`` is
        interpolated across a row's tokens (token t of n stamps at
        ``start + dt*(t+1)/n``) so TBT reflects the amortised per-token
        cost — at K=1 this degenerates to the seed's ``start + dt``.
        Streaming callbacks fire per token, preserving the K=1 contract.
        """
        obs = self.observer
        rec = self.recorder
        slo = self.slo
        for i in act:
            req = runner.slots[i]
            n = int(counts[i])
            if n:
                if obs is not None:
                    obs.decode_block(req, n, dt)
                if rec is not None:
                    rec.record_commit(req.request_id, req.model, n, dt)
            for t in range(n):
                tok = int(toks[i, t])
                req.generated += 1
                req.output_ids.append(tok)
                when = start + dt * (t + 1) / n
                # the same pairwise gap tbt_samples() reconstructs — the
                # shared TBT histogram, EngineStats.tbt and the SLO
                # window all hold identical values
                gap = when - req.token_times[-1]
                if obs is not None:
                    obs.token(req, gap)
                if slo is not None:
                    slo.note("tbt", req.model, gap, when)
                req.token_times.append(when)
                if rec is not None:
                    rec.note_token(req.request_id, req.model, tok, when)
                self.stats.tokens_out += 1
                if req.eos_id is not None and tok == req.eos_id:
                    req.eos_seen = True
                self._emit(TokenEvent(
                    request_id=req.request_id, model=req.model,
                    token=tok, index=req.generated - 1, time=when,
                    done=req.done))

    def _book_first_token(self, req: Request, now: float) -> None:
        req.first_token_time = now
        req.token_times.append(now)
        req.generated += 1
        self.stats.tokens_out += 1
        self.stats.ttft.append(now - req.arrival_time)
        if self.observer is not None:
            self.observer.first_token(req, now - req.arrival_time)
        if self.slo is not None:
            self.slo.note("ttft", req.model, now - req.arrival_time, now)
        if self.recorder is not None:
            self.recorder.record_commit(req.request_id, req.model, 1,
                                        0.0, first=True)
            self.recorder.note_token(req.request_id, req.model,
                                     req.output_ids[-1], now)
        handle = self.handles.get(req.request_id)
        if handle is not None:
            handle.state = HandleState.DECODING
        self._emit(TokenEvent(
            request_id=req.request_id, model=req.model,
            token=req.output_ids[-1], index=0, time=now, first=True,
            done=req.done))

    # ------------------------------------------------------------------
    # prefill phase
    # ------------------------------------------------------------------
    def _prefill_groups(self, groups: List[PrefillGroup],
                        now: float) -> float:
        """Execute the coalesced groups.  In host-driven pipeline mode,
        distinct models' prompt phases interleave through the layer-wise
        scheduler (model A's layer-L attention overlaps model B's FFN and
        each model's own layer-L+1 slab upload); everything else runs the
        sequential streaming path — one [B, S] pass per group."""
        self.stats.prefill_batch_sizes.extend(g.batch_size for g in groups)
        if self.scheduler is not None and self.mode.pipeline:
            first: Dict[str, PrefillGroup] = {}
            rest: List[PrefillGroup] = []
            for g in groups:
                runner = self.runners[g.model]
                # suffix groups and tree-insert candidates stay on the
                # sequential streaming path: the scheduler has no suffix
                # stage and no route capture
                if (runner.paged and g.model not in first and g.fork == 0
                        and not runner.cache_insert_candidate(g)):
                    first[g.model] = g
                else:
                    rest.append(g)
            if len(first) >= 2:
                now = self._prefill_pipelined(list(first.values()), now)
                groups = rest
        obs = self.observer
        for g in groups:
            runner = self.runners[g.model]
            t0 = time.perf_counter()  # cp: allow(CP006) real dispatch duration
            with (obs.prefill_pass(g) if obs is not None else NO_SPAN):
                runner.prefill_group(g)
            dt = self._clocked("prefill", t0)
            now += dt
            if obs is not None:
                obs.prefill(g.model, g.batch_size, dt, g.requests)
            for req in g.requests:
                self._book_first_token(req, now)
        return now

    def _prefill_pipelined(self, groups: List[PrefillGroup],
                           now: float) -> float:
        """Concurrent cold-model prompt phases through the scheduler."""
        t0 = time.perf_counter()  # cp: allow(CP006) real dispatch duration
        batches = [self.runners[g.model].make_prefill_batch(g, i)
                   for i, g in enumerate(groups)]
        done, pool = self.scheduler.run(batches, self.virt.pool,
                                        max_inflight=2)
        self.virt.pool = pool
        dt = self._clocked("prefill_pipe", t0)
        now += dt
        by_model = {g.model: g for g in groups}
        for b in done:
            g = by_model[b.model]
            self.runners[b.model].apply_prefill_result(b, g)
            if self.observer is not None:
                self.observer.prefill(g.model, g.batch_size, dt, g.requests)
            for req in g.requests:
                self._book_first_token(req, now)
        return now

    # ------------------------------------------------------------------
    # decode phase
    # ------------------------------------------------------------------
    def _decode_model(self, name: str, now: float) -> float:
        runner = self.runners[name]
        obs = self.observer
        t0 = time.perf_counter()  # cp: allow(CP006) real dispatch duration
        if obs is not None:
            obs.phase_begin("dispatch")
        pending = runner.issue_decode(self._host_step(name))
        if obs is not None:
            obs.phase_end("dispatch")
            obs.phase_begin("commit")
        toks, counts, act = runner.commit_decode(pending)
        if obs is not None:
            obs.phase_end("commit")
        dt = self._clocked("decode", t0)
        self._record_step(name, dt)
        self._book_tokens(runner, toks, counts, act, now, dt)
        return now + dt

    def _decode_pipelined(self, active: List[str], now: float) -> float:
        """Two (or more) models stepped with overlapping execution.

        lowering=ON : every model's fused paged step is ISSUED before any
        is blocked on — async dispatch overlaps the programs (the shared
        pool buffer is threaded through the dispatch chain).
        lowering=OFF: the layer-wise pipeline scheduler interleaves the
        models' attention/FFN stages across the two pools (paper Fig. 4)."""
        if not self.mode.lowering:
            return self._decode_pipelined_host(active, now)
        obs = self.observer
        t0 = time.perf_counter()  # cp: allow(CP006) real dispatch duration
        if obs is not None:
            obs.phase_begin("dispatch")
        issued = [(n, self.runners[n].issue_decode(None)) for n in active]
        if obs is not None:
            obs.phase_end("dispatch")
            obs.phase_begin("commit")
        dt_all = 0.0
        for n, pending in issued:
            runner = self.runners[n]
            toks, counts, act = runner.commit_decode(pending)
            # one clock read per model commit: each is a replay-injection
            # point, consumed in model order
            dt_all = self._clocked("decode_pipe", t0)
            self._book_tokens(runner, toks, counts, act, now, dt_all)
        if obs is not None:
            obs.phase_end("commit")
        for n in active:
            self._record_step(n, dt_all / len(active))
        return now + dt_all

    def _decode_pipelined_host(self, active: List[str], now: float) -> float:
        """Layer-wise two-batch pipeline over the disaggregated pools."""
        obs = self.observer
        t0 = time.perf_counter()  # cp: allow(CP006) real dispatch duration
        if obs is not None:
            obs.phase_begin("dispatch")
        paged = [n for n in active if self.runners[n].paged]
        fallback = [n for n in active if not self.runners[n].paged]
        batches, acts = [], {}
        for i, n in enumerate(paged):
            batch, act = self.runners[n].make_inflight_batch(i)
            batches.append(batch)
            acts[n] = act
        done, pool = self.scheduler.run(batches, self.virt.pool,
                                        max_inflight=2)
        self.virt.pool = pool
        dt_all = self._clocked("decode_host", t0)
        if obs is not None:
            obs.phase_end("dispatch")
            obs.phase_begin("commit")
        for b in done:
            runner = self.runners[b.model]
            toks, counts, act = runner.apply_pipeline_result(b, acts[b.model])
            self._book_tokens(runner, toks, counts, act, now, dt_all)
            self._record_step(b.model, dt_all / max(len(paged), 1))
        if obs is not None:
            obs.phase_end("commit")
        now += dt_all
        for n in fallback:          # families outside split execution
            now = self._decode_model(n, now)
        return now


#: Back-compat alias: the ISSUE's name for the session-capable engine.
ServingSession = CrossPoolEngine
