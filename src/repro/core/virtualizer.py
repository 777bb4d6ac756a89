"""KV-cache virtualizer: paged virtualization of one shared physical pool.

TPU adaptation of the paper's CUDA-VMM design (DESIGN.md §2): XLA has no
virtual-memory API, so the pool is ONE pre-allocated device array of
fixed-size pages, and "mapping" is page-table bookkeeping on the host —
identical bytes, identical slow-path/fast-path split:

  * fast path (per token, on device): attention kernels read K/V through a
    page table (``repro.kernels.paged_attention``), writes go to
    (page, slot) coordinates — no allocation on the critical path;
  * slow path (per ~page, on host): ``register_request`` /
    ``extend_request`` / ``release_request`` update the free list and
    per-request page tables against the planner's budget.

Heterogeneity (C1): the pool is untyped (flat elements of one pool dtype).
Each model views a page as ``tokens_per_page(M)`` tokens of ONE layer's K+V
(or MLA latent+rope, or SSM state), so models with different KV layouts
share the same physical pages.  ``tokens_per_page`` = page_elems //
per-token-elems, with the remainder as internal fragmentation — as in any
real pager.

Device-side state is maintained incrementally:

  * writes are ONE jitted scatter per call (``write_tokens`` /
    ``write_prompt_from_cache``) with the pool buffer donated — no
    per-token Python loop, no whole-pool rebind per token;
  * ``batch_tables`` returns a cached ``[n_layers, B, max_pages]`` device
    array and re-uploads only the rows whose page mapping actually changed
    (a request that decodes within its last page does not dirty its row).

Elasticity (DESIGN.md §8): the pool is no longer frozen at session start.
``resize`` grows or shrinks ``page_budget`` at step boundaries, and a
**host swap tier** makes shrinking safe for in-flight requests: instead of
killing a request whose pages no longer fit, its coldest pages move to a
host-resident buffer (``swap_out``), survivors are compacted into the
retained prefix of the pool with ONE jitted gather, page tables are
remapped atomically under the existing revision counter, and swapped
pages fault back in on next touch (``ensure_resident``).  Swapped table
entries are encoded in-place as ``-2 - host_slot`` so the page tables
stay the single source of mapping truth; ``-1`` remains the batch-table
padding sentinel and never appears in a request's own tables.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.errors import PoolAccountingError, check
from repro.kernels.ops import paged_kv_write

#: The single page-size constant shared by the virtualizer, the pools and
#: the engine.  16 KiB balances internal fragmentation (half a page per
#: request per layer on average) against page-table length for long
#: contexts; it matches the paper's CUDA-VMM granularity choice.
DEFAULT_PAGE_BYTES = 16 * 1024


class OutOfPagesError(RuntimeError):
    pass


# re-exported for callers that treat the virtualizer as the accounting
# surface; defined in ``repro.core.errors`` so the weights arena shares it
__all__ = ["KVVirtualizer", "OutOfPagesError", "PoolAccountingError"]


@dataclass
class ModelView:
    """How one model interprets physical pages."""

    name: str
    per_token_elems: int          # one layer's K+V (or latent) elems per token
    tokens_per_page: int
    n_kv_layers: int
    kv_shape: Tuple[int, ...]     # per-token per-layer logical shape

    def pages_for(self, tokens: int) -> int:
        """Physical pages to hold ``tokens`` across all KV layers."""
        if self.tokens_per_page == 0:
            return 0
        per_layer = math.ceil(tokens / self.tokens_per_page)
        return per_layer * self.n_kv_layers


def make_view(cfg: ModelConfig, page_elems: int) -> ModelView:
    if cfg.attn_free:
        return ModelView(cfg.name, 0, 0, 0, ())
    if cfg.attention == "mla":
        m = cfg.mla
        per_tok = m.kv_lora_rank + m.qk_rope_head_dim
        shape = (per_tok,)
    else:
        per_tok = 2 * cfg.n_kv_heads * cfg.head_dim
        shape = (2, cfg.n_kv_heads, cfg.head_dim)
    tpp = page_elems // per_tok
    if tpp == 0:
        raise ValueError(
            f"{cfg.name}: per-token KV ({per_tok} elems) exceeds page size "
            f"({page_elems} elems); increase page_bytes")
    return ModelView(cfg.name, per_tok, tpp, cfg.n_decoder_attn_layers, shape)


#: Swapped page-table encoding: entry ``-2 - host_slot``.  ``-1`` stays
#: the batch-table padding sentinel, so any entry <= _SWAP_BASE is a
#: swapped page and ``_SWAP_BASE - entry`` recovers the host slot.
_SWAP_BASE = -2


def _swap_encode(host_slot: int) -> int:
    return _SWAP_BASE - host_slot


def _swap_decode(entry: int) -> int:
    return _SWAP_BASE - entry


@dataclass
class RequestPages:
    """Per-request mapping: page_table[layer][chunk] -> physical page id.

    Entries >= 0 are device pages; entries <= -2 encode pages swapped to
    the host tier (see ``_swap_encode``) — ``n_swapped`` counts them so
    residency checks stay O(1).
    """

    request_id: int
    model: str
    tokens: int = 0
    tables: List[List[int]] = field(default_factory=list)   # [layer][chunk]
    state_pages: List[int] = field(default_factory=list)    # SSM constant state
    # globally monotonic mapping revision (assigned by the virtualizer):
    # unique per registration AND per page-mapping change, so a reused
    # request id can never alias a stale cached batch table
    rev: int = -1
    last_touch: int = 0            # virtualizer idle clock (swap victim order)
    n_swapped: int = 0             # table entries currently in the host tier

    def device_entries(self):
        """Yield (table, index, page) for every device-resident entry."""
        for tab in self.tables:
            for i, p in enumerate(tab):
                if p >= 0:
                    yield tab, i, p
        for i, p in enumerate(self.state_pages):
            if p >= 0:
                yield self.state_pages, i, p

    def swapped_entries(self):
        """Yield (table, index, host_slot) for every swapped entry."""
        for tab in self.tables:
            for i, p in enumerate(tab):
                if p <= _SWAP_BASE:
                    yield tab, i, _swap_decode(p)
        for i, p in enumerate(self.state_pages):
            if p <= _SWAP_BASE:
                yield self.state_pages, i, _swap_decode(p)



def _pack_write(pool, a, b, pages, slots, n_tokens, batch_index, mla):
    with jax.named_scope("kv_prompt_write"):
        a = a[batch_index, :n_tokens]
        b = b[batch_index, :n_tokens]
        kv = (jnp.concatenate([a, b], axis=-1) if mla
              else jnp.stack([a, b], axis=1))
        return paged_kv_write(pool, kv.reshape(n_tokens, -1), pages, slots)


#: One donated-buffer scatter of ``n`` token rows into the flat pool.
_pool_scatter = jax.jit(paged_kv_write, donate_argnums=0)

#: Slice one batch row out of a layer's ``(a, b)`` KV pair, pack it to
#: token rows, and scatter — ONE jitted dispatch with the pool donated.
#: The packing (slice to ``n_tokens``, MLA concat / GQA stack, flatten)
#: used to run as eager ops before the scatter; on the prefill path that
#: is several host dispatches PER LAYER, which is exactly the fixed
#: per-turn cost a cache-hit suffix prefill is trying to shed.
_pool_pair_scatter = jax.jit(_pack_write, static_argnames=(
    "n_tokens", "batch_index", "mla"), donate_argnums=0)

#: Scatter whole page rows (fault-in from the host swap tier).
_pool_row_scatter = jax.jit(lambda p, i, r: p.at[i].set(r), donate_argnums=0)

#: Gather whole page rows: ONE compiled gather builds the compacted /
#: resized pool buffer (also used to read rows out for swap-out).
_pool_row_gather = jax.jit(lambda p, i: p[i])


class KVVirtualizer:
    """Host-side pager over one device-resident physical pool."""

    def __init__(self, models: Dict[str, ModelConfig], *,
                 page_budget: int, page_bytes: int = DEFAULT_PAGE_BYTES,
                 dtype=jnp.bfloat16, allocate_device_pool: bool = True,
                 device=None):
        self.page_bytes = page_bytes
        self.dtype = jnp.dtype(dtype)
        self.page_elems = page_bytes // self.dtype.itemsize
        self.page_budget = page_budget
        self.views = {n: make_view(c, self.page_elems)
                      for n, c in models.items()}
        self.configs = dict(models)
        self.free_list: List[int] = list(range(page_budget - 1, -1, -1))
        self.requests: Dict[int, RequestPages] = {}
        self.pool: Optional[jax.Array] = None
        if allocate_device_pool:
            # co-locate with the KV pool's attention params (``device`` is
            # KVCachePool's device; None = jax default), allocated there
            self.pool = jnp.zeros((page_budget, self.page_elems), dtype,
                                  device=device)
        # incremental device page-table cache: key -> {buf, revs, dev}
        self._batch_cache: Dict[tuple, dict] = {}
        self._rev_counter = 0
        # host swap tier: page rows evicted by a shrink live here until the
        # next touch faults them back in (lazily allocated, grows 2x)
        self.swap_buffer: Optional[np.ndarray] = None
        self.swap_free: List[int] = []
        self._touch_clock = 0
        # stats
        self.peak_mapped = 0
        self.map_events = 0
        self.unmap_events = 0
        self.swap_out_pages = 0
        self.swap_in_pages = 0
        self.resizes = 0
        self.swapped_now = 0           # entries currently in the host tier
        # per-page share counts for prefix-cached pages (DESIGN.md §11).
        # Absent = refcount 1 (sole owner, the common case): the dict only
        # holds pages currently shared between holders (requests and/or
        # the prefix tree), so the cache-off path never touches it.
        self._refs: Dict[int, int] = {}
        # prefix-cache provider (core.prefix_cache.PrefixCache): owns
        # device pages outside any request table, so shrink-compaction and
        # idle swap must consult it (``device_pages``/``remap``/``shed``)
        self.cache_provider = None
        # optional observability sink (core.hooks.CoreHooks); every hook
        # fires AFTER the matching stat counter above has been updated
        self.hooks = None

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------
    @property
    def mapped_pages(self) -> int:
        return self.page_budget - len(self.free_list)

    @property
    def free_pages(self) -> int:
        return len(self.free_list)

    def can_admit(self, model: str, prompt_tokens: int,
                  expected_output: int = 0, reserve: int = 0,
                  discount_pages: int = 0) -> bool:
        """``reserve`` pages are held back from admission — the elastic
        rebalancer's pressure signal (pages promised to a pending shrink
        or kept as fault-in headroom for the swap tier).

        ``discount_pages`` is the prefix cache's net credit: pages the
        request will map read-only from DEVICE-RESIDENT cached chunks
        instead of taking from the free list.  Cached-but-swapped chunks
        get no credit — their fault-in takes a fresh page each, exactly
        like a cold chunk — and a swapped copy-on-write SOURCE makes the
        value negative (its fault pages are on top of the cold-path
        need), so the verdict still honors ``reserve``."""
        return self.admission_deficit(model, prompt_tokens, expected_output,
                                      reserve, discount_pages) == 0

    def admission_deficit(self, model: str, prompt_tokens: int,
                          expected_output: int = 0, reserve: int = 0,
                          discount_pages: int = 0) -> int:
        """Pages MISSING for this admission (0 = admissible).  The
        admission controller uses the deficit as the prefix cache's shed
        target: reclaiming that many refcount-0 tree pages makes the
        request fit without touching any live request."""
        view = self.views[model]
        cfg = self.configs[model]
        need = view.pages_for(prompt_tokens + expected_output) if view.n_kv_layers \
            else 0
        need = max(need - discount_pages, 0)
        need += math.ceil(cfg.state_bytes_per_request() / self.page_bytes)
        return max(need - (self.free_pages - max(reserve, 0)), 0)

    # ------------------------------------------------------------------
    # per-page refcounts (prefix sharing, DESIGN.md §11)
    # ------------------------------------------------------------------
    def page_refs(self, page: int) -> int:
        return self._refs.get(page, 1)

    def retain_page(self, page: int) -> None:
        """Add one holder to a device page (prefix tree or a sharing
        request); freshly ``_take``n pages carry an implicit refcount 1."""
        check(page >= 0, f"cannot retain non-device entry {page}")
        self._refs[page] = self._refs.get(page, 1) + 1

    def _unref(self, page: int) -> bool:
        """Drop one holder; True when the page is fully released (the
        caller returns it to the free list) — a page is only ever freed
        at refcount 0, never while another holder maps it."""
        c = self._refs.get(page, 1) - 1
        if c == 0:
            self._refs.pop(page, None)
            return True
        if c == 1:
            self._refs.pop(page, None)   # back to implicit sole owner
        else:
            self._refs[page] = c
        return False

    # ------------------------------------------------------------------
    # slow path: map / unmap
    # ------------------------------------------------------------------
    def _next_rev(self) -> int:
        self._rev_counter += 1
        return self._rev_counter

    def _take(self, n: int) -> List[int]:
        """Atomically pop ``n`` pages: raises BEFORE mutating any state."""
        if n > len(self.free_list):
            raise OutOfPagesError(
                f"need {n} pages, {len(self.free_list)} free "
                f"(budget {self.page_budget})")
        pages = [self.free_list.pop() for _ in range(n)]
        self.map_events += n
        self.peak_mapped = max(self.peak_mapped, self.mapped_pages)
        return pages

    def register_request(self, request_id: int, model: str,
                         prompt_tokens: int) -> RequestPages:
        """Map pages for a request's prompt KV (+ SSM state).

        Atomic: the total page count is taken in ONE ``_take``, so an
        ``OutOfPagesError`` leaves the free list untouched (no partially
        mapped request to roll back).
        """
        view = self.views[model]
        cfg = self.configs[model]
        chunks = math.ceil(max(prompt_tokens, 1) / view.tokens_per_page) \
            if view.n_kv_layers else 0
        state_pages = math.ceil(cfg.state_bytes_per_request() / self.page_bytes)
        pages = self._take(chunks * view.n_kv_layers + state_pages)
        req = RequestPages(request_id, model)
        for layer in range(view.n_kv_layers):
            req.tables.append(pages[layer * chunks:(layer + 1) * chunks])
        if state_pages:
            req.state_pages = pages[view.n_kv_layers * chunks:]
        req.tokens = prompt_tokens
        req.rev = self._next_rev()
        self.requests[request_id] = req
        self.touch(request_id)
        return req

    def register_request_with_prefix(
            self, request_id: int, model: str, prompt_tokens: int,
            shared_chunks: Sequence[Sequence[int]],
            cow_chunk: Optional[Sequence[int]] = None) -> RequestPages:
        """Map a request whose leading prompt chunks are already cached.

        ``shared_chunks[c][layer]`` are device page ids of cached FULL
        chunks mapped read-only (refcount +1 each); ``cow_chunk[layer]``
        is the source of the partially-reused boundary chunk, copied
        page-for-page into fresh pages (copy-on-write at the fork point:
        the request appends suffix KV into its private copy while the
        cached original stays immutable).  All ids must be
        device-resident — the caller faults swapped chunks first.

        Atomic like ``register_request``: all fresh pages (suffix chunks,
        the CoW destination, SSM state) come from ONE ``_take``, and the
        retains/copies happen only after it succeeds.
        """
        view = self.views[model]
        cfg = self.configs[model]
        L = view.n_kv_layers
        chunks = math.ceil(max(prompt_tokens, 1) / view.tokens_per_page) \
            if L else 0
        n_shared = len(shared_chunks)
        check(n_shared + (1 if cow_chunk is not None else 0) <= chunks,
              f"prefix covers {n_shared} shared chunks"
              f"{' + a CoW chunk' if cow_chunk is not None else ''} but the "
              f"prompt only spans {chunks}")
        state_pages = math.ceil(cfg.state_bytes_per_request() / self.page_bytes)
        fresh_per_layer = chunks - n_shared
        pages = self._take(fresh_per_layer * L + state_pages)
        req = RequestPages(request_id, model)
        for layer in range(L):
            fresh = pages[layer * fresh_per_layer:
                          (layer + 1) * fresh_per_layer]
            req.tables.append(
                [shared_chunks[c][layer] for c in range(n_shared)] + fresh)
        if state_pages:
            req.state_pages = pages[L * fresh_per_layer:]
        for c in range(n_shared):
            for layer in range(L):
                self.retain_page(shared_chunks[c][layer])
        if cow_chunk is not None:
            # chunk ``n_shared`` of every layer is the private boundary
            # copy: one vectorized device row copy, byte-exact
            srcs = [int(cow_chunk[layer]) for layer in range(L)]
            dsts = [req.tables[layer][n_shared] for layer in range(L)]
            check(all(s >= 0 for s in srcs),
                  f"copy-on-write source chunk is not device-resident: {srcs}")
            if self.pool is not None:
                rows = _pool_row_gather(self.pool,
                                        jnp.asarray(np.asarray(srcs, np.int32)))
                self.pool = _pool_row_scatter(
                    self.pool, jnp.asarray(np.asarray(dsts, np.int32)), rows)
        req.tokens = prompt_tokens
        req.rev = self._next_rev()
        self.requests[request_id] = req
        self.touch(request_id)
        return req

    def gather_prompt_rows(self, model: str, request_id: int,
                           n_tokens: int) -> jax.Array:
        """KV rows of tokens ``[0, n_tokens)`` for EVERY layer, read
        through the request's own page table and stacked into one
        ``[n_kv_layers, n_tokens, *kv_shape]`` device array.  One gather
        for all layers: the suffix prefill is host-dispatch-bound, and
        the per-layer rows are sliced INSIDE its jitted attention stage.
        Shared pages are read in place (never copied)."""
        view = self.views[model]
        req = self.requests[request_id]
        check(req.n_swapped == 0,
              f"request {request_id} has swapped pages; call ensure_resident "
              f"before gathering prefix KV")
        typed = self.typed_pages(model)
        toks = np.arange(n_tokens)
        chunk = toks // view.tokens_per_page
        slots = (toks % view.tokens_per_page).astype(np.int32)
        pages = np.stack([np.asarray(req.tables[layer], np.int32)[chunk]
                          for layer in range(view.n_kv_layers)])
        return typed[jnp.asarray(pages), jnp.asarray(slots)[None, :]]

    def pages_needed_for_extend(self, request_id: int,
                                new_tokens: int = 1) -> int:
        """Pages a (would-be) ``extend_request`` would map, without mutating
        anything — lets callers make a multi-request extension atomic by
        checking the batch total against ``free_pages`` first."""
        req = self.requests[request_id]
        view = self.views[req.model]
        if not view.n_kv_layers:
            return 0
        have = len(req.tables[0])
        need = math.ceil(max(req.tokens + new_tokens, 1)
                         / view.tokens_per_page)
        return max(need - have, 0) * view.n_kv_layers

    def extend_request(self, request_id: int, new_tokens: int = 1) -> None:
        """Grow a request by ``new_tokens`` (decode); maps pages on demand.

        Atomic: the pages for every layer are taken in ONE ``_take``, so an
        ``OutOfPagesError`` leaves every layer table at its old (equal)
        length and the token count unchanged.
        """
        req = self.requests[request_id]
        view = self.views[req.model]
        if view.n_kv_layers:
            have = len(req.tables[0])
            need = math.ceil(max(req.tokens + new_tokens, 1)
                             / view.tokens_per_page)
            delta = need - have
            if delta > 0:
                pages = self._take(delta * view.n_kv_layers)
                for layer, tab in enumerate(req.tables):
                    tab.extend(pages[layer * delta:(layer + 1) * delta])
                req.rev = self._next_rev()
        req.tokens += new_tokens
        self.touch(request_id)

    def reserve_decode_block(self, request_id: int, k: int = 1) -> int:
        """Pre-map pages covering the next ``k`` decode tokens WITHOUT
        committing them (multi-step decode, DESIGN.md §9).

        Extends every layer table to cover ``tokens + k`` while leaving
        ``req.tokens`` untouched — the tokens are committed only after
        the dispatch returns (``commit_decode_block``), so a dispatch
        that stops early (EOS mid-block) never leaves phantom tokens in
        the accounting.  The revision bumps when pages are added, so the
        next ``batch_tables`` upload carries the reserved entries and
        the device scan can append KV without any host table mutation
        mid-dispatch.

        Ordering contract: the caller faults swapped pages back in FIRST
        (``ensure_resident``) — reserving on top of a swapped table would
        interleave fresh device ids with host-slot encodings and the
        batch-table build would reject the row anyway.

        Atomic like ``extend_request``: one ``_take`` for all layers, so
        ``OutOfPagesError`` leaves every table at its old length.
        Returns the number of pages mapped.
        """
        req = self.requests[request_id]
        view = self.views[req.model]
        if not view.n_kv_layers:
            self.touch(request_id)
            return 0
        check(req.n_swapped == 0,
              f"request {request_id} has swapped pages; call ensure_resident "
              f"before reserving a decode block")
        have = len(req.tables[0])
        need = math.ceil(max(req.tokens + k, 1) / view.tokens_per_page)
        delta = need - have
        if delta <= 0:
            self.touch(request_id)
            return 0
        pages = self._take(delta * view.n_kv_layers)
        for layer, tab in enumerate(req.tables):
            tab.extend(pages[layer * delta:(layer + 1) * delta])
        req.rev = self._next_rev()
        self.touch(request_id)
        if self.hooks is not None:
            self.hooks.kv_reserved(len(pages))
        return len(pages)

    def commit_decode_block(self, request_id: int, n_committed: int) -> int:
        """Commit ``n_committed`` tokens of a reserved decode block and
        return the unused reserved pages to the free list.

        The inverse of ``reserve_decode_block``: ``req.tokens`` advances
        by the tokens the device actually emitted (EOS / per-row budget
        may stop a K-block early) and any reserved chunk beyond
        ``ceil(tokens / tokens_per_page)`` is unmapped — trimmed pages go
        back in reverse order so the free list keeps handing out the
        lowest ids first (allocation order stays deterministic).  The
        revision bumps only when pages were trimmed.  Returns the number
        of pages returned.
        """
        req = self.requests[request_id]
        view = self.views[req.model]
        req.tokens += n_committed
        if not view.n_kv_layers:
            self.touch(request_id)
            return 0
        keep = math.ceil(max(req.tokens, 1) / view.tokens_per_page)
        if len(req.tables[0]) <= keep:
            self.touch(request_id)
            return 0
        trimmed = 0
        for tab in req.tables:
            extra = tab[keep:]
            del tab[keep:]
            for p in reversed(extra):
                if p <= _SWAP_BASE:      # reserved page swapped meanwhile
                    self.swap_free.append(_swap_decode(p))
                    req.n_swapped -= 1
                    self.swapped_now -= 1
                else:
                    self.free_list.append(p)
            trimmed += len(extra)
        self.unmap_events += trimmed
        req.rev = self._next_rev()
        self.touch(request_id)
        if self.hooks is not None and trimmed:
            self.hooks.kv_trimmed(trimmed)
        return trimmed

    def release_request(self, request_id: int) -> None:
        req = self.requests.pop(request_id)
        n = 0
        for _, _, page in req.device_entries():
            # prefix-shared pages survive until their LAST holder (other
            # sharing requests or the prefix tree) drops them
            if self._unref(page):
                self.free_list.append(page)
            n += 1
        for _, _, slot in req.swapped_entries():
            self.swap_free.append(slot)
            self.swapped_now -= 1
            n += 1
        self.unmap_events += n

    # ------------------------------------------------------------------
    # elastic boundary: host swap tier + live resize (DESIGN.md §8)
    # ------------------------------------------------------------------
    def touch(self, request_id: int) -> None:
        """Mark a request recently used (swap victims are least-recent)."""
        self._touch_clock += 1
        self.requests[request_id].last_touch = self._touch_clock

    def _swap_slots(self, n: int) -> List[int]:
        """Take ``n`` host-tier slots, growing the swap buffer on demand."""
        while len(self.swap_free) < n:
            old = 0 if self.swap_buffer is None else len(self.swap_buffer)
            cap = max(old * 2, n, 16)
            buf = np.zeros((cap, self.page_elems), self.dtype)
            if self.swap_buffer is not None:
                buf[:old] = self.swap_buffer
            self.swap_buffer = buf
            self.swap_free.extend(range(cap - 1, old - 1, -1))
        return [self.swap_free.pop() for _ in range(n)]

    def swap_out(self, request_id: int, max_pages: Optional[int] = None
                 ) -> int:
        """Move up to ``max_pages`` of a request's device pages to the host
        tier (coldest — lowest token chunks — first); returns the count.

        The freed device ids go straight back to the free list; table
        entries are rewritten to the swapped encoding and the request's
        revision bumps, so any cached batch table is invalidated.  Page
        CONTENTS move with the page (one device gather + one host copy),
        so a later fault-in is bit-for-bit invisible to attention.
        """
        req = self.requests[request_id]
        victims: List[Tuple[List[int], int, int]] = []
        view = self.views[req.model]
        chunks = len(req.tables[0]) if req.tables else 0
        # chunk-major: the lowest (oldest-token) chunk of every layer goes
        # first, so partial swaps shed the coldest KV across layers evenly
        for c in range(chunks):
            for layer in range(view.n_kv_layers):
                p = req.tables[layer][c]
                # prefix-shared pages never swap through a request: the
                # other holders (tree / sharing requests) still read them
                if p >= 0 and self.page_refs(p) == 1:
                    victims.append((req.tables[layer], c, p))
        for i, p in enumerate(req.state_pages):
            if p >= 0:
                victims.append((req.state_pages, i, p))
        if max_pages is not None:
            victims = victims[:max_pages]
        if not victims:
            return 0
        ids = np.asarray([p for _, _, p in victims], np.int32)
        slots = self._swap_slots(len(victims))
        if self.pool is not None:
            rows = np.asarray(_pool_row_gather(self.pool, jnp.asarray(ids)))
            self.swap_buffer[np.asarray(slots)] = rows
        for (tab, i, page), slot in zip(victims, slots):
            tab[i] = _swap_encode(slot)
            self.free_list.append(page)
        req.rev = self._next_rev()
        req.n_swapped += len(victims)
        self.swapped_now += len(victims)
        self.swap_out_pages += len(victims)
        if self.hooks is not None:
            self.hooks.kv_swap_out(len(victims))
        return len(victims)

    def ensure_resident(self, request_id: int) -> int:
        """Fault a request's swapped pages back onto the device (the
        "next touch" of the swap tier); returns how many were faulted.

        Atomic like every other mapping change: the device pages are taken
        in ONE ``_take``, so ``OutOfPagesError`` leaves the tables, the
        swap tier and the free list untouched.
        """
        req = self.requests[request_id]
        if req.n_swapped == 0:
            return 0
        entries = list(req.swapped_entries())
        pages = self._take(len(entries))
        if self.pool is not None:
            rows = self.swap_buffer[
                np.asarray([s for _, _, s in entries])].copy()
            self.pool = _pool_row_scatter(
                self.pool, jnp.asarray(np.asarray(pages, np.int32)),
                jnp.asarray(rows))
        for (tab, i, slot), page in zip(entries, pages):
            tab[i] = page
            self.swap_free.append(slot)
        req.rev = self._next_rev()
        req.n_swapped = 0
        self.swapped_now -= len(entries)
        self.swap_in_pages += len(entries)
        self.touch(request_id)
        if self.hooks is not None:
            self.hooks.kv_swap_in(len(entries))
        return len(entries)

    def swap_pages_out(self, pages: Sequence[int]) -> List[int]:
        """Move table-less device pages (prefix-tree leaves) to the host
        tier; returns their swapped encodings in order.

        The caller guarantees every page is SOLE-owned by it (refcount 1
        and in no request table) — the second-chance cache tier's shed
        path.  Contents move with the page, so a later fault-in is
        bit-exact."""
        if not pages:
            return []
        check(all(p >= 0 and self.page_refs(p) == 1 for p in pages),
              f"swap_pages_out requires sole-owned device pages: {list(pages)}")
        slots = self._swap_slots(len(pages))
        if self.pool is not None:
            ids = jnp.asarray(np.asarray(list(pages), np.int32))
            rows = np.asarray(_pool_row_gather(self.pool, ids))
            self.swap_buffer[np.asarray(slots)] = rows
        for p in pages:
            self.free_list.append(p)
        self.swapped_now += len(pages)
        self.swap_out_pages += len(pages)
        if self.hooks is not None:
            self.hooks.kv_swap_out(len(pages))
        return [_swap_encode(s) for s in slots]

    def fault_pages_in(self, encoded: Sequence[int]) -> List[int]:
        """Fault host-tier pages back onto the device (second-chance
        cache hit); returns the fresh device ids in order.  Atomic: ONE
        ``_take``, so ``OutOfPagesError`` leaves the swap tier intact."""
        if not encoded:
            return []
        check(all(e <= _SWAP_BASE for e in encoded),
              f"fault_pages_in takes swapped encodings only: {list(encoded)}")
        slots = [_swap_decode(e) for e in encoded]
        pages = self._take(len(slots))
        if self.pool is not None:
            rows = self.swap_buffer[np.asarray(slots)].copy()
            self.pool = _pool_row_scatter(
                self.pool, jnp.asarray(np.asarray(pages, np.int32)),
                jnp.asarray(rows))
        self.swap_free.extend(slots)
        self.swapped_now -= len(slots)
        self.swap_in_pages += len(slots)
        if self.hooks is not None:
            self.hooks.kv_swap_in(len(slots))
        return pages

    def release_cached_page(self, entry: int) -> bool:
        """Drop the prefix tree's hold on one table entry (device id or
        swapped encoding); True when a DEVICE page was actually freed."""
        if entry <= _SWAP_BASE:
            self.swap_free.append(_swap_decode(entry))
            self.swapped_now -= 1
            return False
        if self._unref(entry):
            self.free_list.append(entry)
            self.unmap_events += 1
            return True
        return False

    def swap_out_idle(self, need: int, protected=()) -> int:
        """Free ``need`` device pages by swapping the coldest pages of the
        longest-idle requests (skipping ``protected`` ids); returns how
        many were actually freed.

        The prefix cache sheds FIRST: its refcount-0 LRU leaves hold no
        in-flight work, so an elastic shrink reclaims them (to the
        second-chance swap tier) before touching any live request."""
        freed = 0
        if self.cache_provider is not None and need > 0:
            freed = self.cache_provider.shed(need)
        protected = set(protected)
        order = sorted(self.requests.values(), key=lambda r: r.last_touch)
        for req in order:
            if freed >= need:
                break
            if req.request_id in protected:
                continue
            freed += self.swap_out(req.request_id, need - freed)
        return freed

    def resize(self, new_budget: int, protected=()) -> Dict[str, int]:
        """Live-repartition entry point: grow or shrink the pool to
        ``new_budget`` pages at a step boundary.

        Growing copies the old buffer into the prefix of a larger one and
        appends fresh ids to the free list.  Shrinking swaps out the
        coldest pages of the longest-idle (non-``protected``) requests
        until the survivors fit, then compacts survivors into the retained
        prefix with ONE jitted gather and remaps every table atomically
        (all revisions bump; the batch-table cache drops).  Raises
        ``OutOfPagesError`` — with NO state change beyond completed swaps —
        when protected requests alone exceed the new budget.
        """
        new_budget = int(new_budget)
        check(new_budget >= 1, f"page budget must be >= 1, got {new_budget}")
        old_budget = self.page_budget
        if new_budget == old_budget:
            return {"page_budget": old_budget, "swapped_out": 0, "moved": 0}
        swapped = 0
        if new_budget > old_budget:
            if self.pool is not None:
                pad = jnp.zeros((new_budget - old_budget, self.page_elems),
                                self.pool.dtype, device=self.pool.sharding)
                self.pool = jnp.concatenate([self.pool, pad], axis=0)
            # new ids go to the FRONT of the (pop-from-the-end) free list,
            # so existing low ids keep being preferred — allocation order
            # stays deterministic across grows
            self.free_list = list(range(new_budget - 1, old_budget - 1, -1)) \
                + self.free_list
            self.page_budget = new_budget
            self.resizes += 1
            if self.hooks is not None:
                self.hooks.kv_resize(old_budget, new_budget, 0, 0)
            return {"page_budget": new_budget, "swapped_out": 0, "moved": 0}

        # --- shrink ----------------------------------------------------
        device_mapped = self.mapped_pages
        if device_mapped > new_budget:
            swapped = self.swap_out_idle(device_mapped - new_budget,
                                         protected)
        if self.mapped_pages > new_budget:
            raise OutOfPagesError(
                f"cannot shrink to {new_budget} pages: {self.mapped_pages} "
                f"still mapped after swapping {swapped} (protected "
                f"requests hold too many pages)")
        # compact survivors into [0, new_budget): deterministic order —
        # requests by id, then layer-major table order, then cache-held
        # pages not in any table.  A prefix-shared page appears in many
        # tables but moves ONCE (one new id for all holders).
        old_ids: List[int] = []
        mapping: Dict[int, int] = {}
        entries: List[Tuple[List[int], int, int]] = []
        for rid in sorted(self.requests):
            req = self.requests[rid]
            for tab, i, page in req.device_entries():
                entries.append((tab, i, page))
                if page not in mapping:
                    mapping[page] = len(old_ids)
                    old_ids.append(page)
        if self.cache_provider is not None:
            for page in self.cache_provider.device_pages():
                if page not in mapping:
                    mapping[page] = len(old_ids)
                    old_ids.append(page)
        k = len(old_ids)
        perm = np.zeros(new_budget, np.int32)
        perm[:k] = np.asarray(old_ids, np.int32) if k else []
        if self.pool is not None:
            self.pool = _pool_row_gather(self.pool, jnp.asarray(perm))
        for tab, i, page in entries:
            tab[i] = mapping[page]
        self._refs = {mapping[p]: c for p, c in self._refs.items()
                      if p in mapping}
        if self.cache_provider is not None:
            self.cache_provider.remap(mapping)
        for req in self.requests.values():
            req.rev = self._next_rev()
        self._batch_cache.clear()
        self.free_list = list(range(new_budget - 1, k - 1, -1))
        self.page_budget = new_budget
        self.resizes += 1
        if self.hooks is not None:
            self.hooks.kv_resize(old_budget, new_budget, swapped, k)
        return {"page_budget": new_budget, "swapped_out": swapped,
                "moved": k}

    # ------------------------------------------------------------------
    # fast path: device views
    # ------------------------------------------------------------------
    def page_table_array(self, request_ids: List[int], layer: int,
                         max_pages: int) -> jax.Array:
        """[B, max_pages] int32 physical ids (-1 = unmapped) for one layer."""
        out = np.full((len(request_ids), max_pages), -1, np.int32)
        for i, rid in enumerate(request_ids):
            tab = self.requests[rid].tables[layer]
            out[i, : min(len(tab), max_pages)] = tab[: max_pages]
        return jnp.asarray(out)

    def batch_tables(self, model: str,
                     request_ids: Sequence[Optional[int]],
                     max_pages: int) -> jax.Array:
        """[n_layers, B, max_pages] int32 table for a batch of slots.

        ``None`` entries (empty batch slots) map to all ``-1`` rows.  The
        device array is cached per (model, slot assignment, max_pages) and
        re-uploaded only when a row's page mapping actually changed — a
        request decoding within its current last page reuses the cached
        array with zero host work.
        """
        view = self.views[model]
        key = (model,
               tuple(-1 if r is None else r for r in request_ids),
               max_pages)
        for rid in request_ids:
            if rid is not None and rid in self.requests:
                check(self.requests[rid].n_swapped == 0,
                      f"request {rid} has swapped pages; call "
                      f"ensure_resident before building batch tables")
        revs = tuple(
            -1 if rid is None or rid not in self.requests
            else self.requests[rid].rev
            for rid in request_ids)
        entry = self._batch_cache.get(key)
        if entry is not None and entry["revs"] == revs:
            return entry["dev"]
        if entry is None:
            buf = np.full((view.n_kv_layers, len(request_ids), max_pages),
                          -1, np.int32)
            old_revs: tuple = (None,) * len(request_ids)
        else:
            buf, old_revs = entry["buf"], entry["revs"]
        for i, rid in enumerate(request_ids):
            if old_revs[i] == revs[i]:
                continue
            buf[:, i, :] = -1
            if rid is not None and rid in self.requests:
                for layer, tab in enumerate(self.requests[rid].tables):
                    m = min(len(tab), max_pages)
                    buf[layer, i, :m] = tab[:m]
        # jnp.array COPIES: jnp.asarray may zero-copy-alias the numpy buffer
        # on CPU, and ``buf`` is mutated in place on later mapping changes —
        # an aliased upload would retroactively corrupt tables already
        # handed to in-flight steps.
        dev = jnp.array(buf)
        if len(self._batch_cache) > 64:     # bound stale slot assignments
            self._batch_cache.clear()
        self._batch_cache[key] = {"buf": buf, "revs": revs, "dev": dev}
        return dev

    def typed_pages(self, model: str) -> jax.Array:
        """The pool viewed as ``[n_pages, tokens_per_page, *kv_shape]``.

        Zero-copy reshape of the shared flat pool; the slack elements at the
        end of each page are invisible to the kernel.
        """
        view = self.views[model]
        used = view.tokens_per_page * view.per_token_elems
        return self.pool[:, :used].reshape(
            (self.page_budget, view.tokens_per_page) + view.kv_shape)

    def _token_coords(self, req: RequestPages, view: ModelView,
                      tokens: np.ndarray, layer: Optional[int] = None
                      ) -> Tuple[np.ndarray, np.ndarray]:
        """(pages, slots) int32 arrays for token indices of one request.

        ``layer=None`` vectorizes over ALL layers: ``tokens`` is [n] and the
        result is [n_layers * n] in layer-major order.
        """
        check(req.n_swapped == 0,
              f"request {req.request_id} has swapped pages; call "
              f"ensure_resident before writing KV")
        chunk = tokens // view.tokens_per_page
        slots = (tokens % view.tokens_per_page).astype(np.int32)
        if layer is not None:
            tab = np.asarray(req.tables[layer], np.int32)
            return tab[chunk], slots
        tabs = np.asarray(req.tables, np.int32)        # [L, chunks]
        pages = tabs[:, chunk].reshape(-1)             # [L * n]
        return pages, np.tile(slots, view.n_kv_layers)

    def write_tokens(self, model: str, layer: int, request_id: int,
                     start_token: int, kv: jax.Array) -> None:
        """Write ``kv [n_new, *kv_shape]`` at token offset ``start_token``.

        One jitted, donated-buffer scatter for the whole token range — the
        pool buffer is updated in place rather than rebound per token.
        """
        view = self.views[model]
        req = self.requests[request_id]
        n = kv.shape[0]
        flat = kv.reshape(n, view.per_token_elems)
        toks = np.arange(start_token, start_token + n)
        pages, slots = self._token_coords(req, view, toks, layer)
        self.pool = _pool_scatter(self.pool, flat, jnp.asarray(pages),
                                  jnp.asarray(slots))

    def write_prompt_layer(self, pool: jax.Array, model: str,
                           request_id: int, layer: int, layer_kv,
                           n_tokens: int, batch_index: int = 0,
                           start: int = 0) -> jax.Array:
        """Seed ONE layer's prompt KV from full-sequence attention outputs.

        ``layer_kv`` is the per-layer pair a streaming (layer-at-a-time)
        prefill produces: ``(k, v)`` each ``[B,S,KV,hd]`` for GQA or
        ``(latent, rope)`` ``[B,S,·]`` for MLA — the same bytes
        ``write_prompt_from_cache`` scatters, one layer at a time so KV
        lands in the pool while later layers are still executing.

        Pure with respect to the pool: takes and returns the (donated)
        buffer instead of touching ``self.pool``, so a pipeline scheduler
        can thread it through interleaved prefill/decode stages.

        ``start`` offsets the destination tokens: a cache-hit suffix
        prefill computes KV only for tokens ``[fork, true_len)`` and
        writes them at absolute positions starting at the fork
        (DESIGN.md §11) — rows of ``layer_kv`` stay 0-based.
        """
        view = self.views[model]
        req = self.requests[request_id]
        a, b = layer_kv
        toks = np.arange(start, start + n_tokens)
        pages, slots = self._token_coords(req, view, toks, layer)
        return _pool_pair_scatter(pool, a, b, jnp.asarray(pages),
                                  jnp.asarray(slots), n_tokens=n_tokens,
                                  batch_index=batch_index,
                                  mla=len(view.kv_shape) == 1)

    def write_prompt_from_cache(self, model: str, request_id: int,
                                cache: Dict, n_tokens: int,
                                batch_index: int = 0) -> None:
        """Seed a request's mapped pages from a dense prefill cache.

        ``cache`` is the model's contiguous decode-cache pytree (GQA
        ``{"k","v": [L,B,T,KV,hd]}`` or MLA ``{"latent","rope"}``); tokens
        ``[0, n_tokens)`` of row ``batch_index`` are scattered into the
        request's pages across ALL layers in one device dispatch.
        """
        view = self.views[model]
        req = self.requests[request_id]
        if "k" in cache:
            k = cache["k"][:, batch_index, :n_tokens]      # [L,n,KV,hd]
            v = cache["v"][:, batch_index, :n_tokens]
            kv = jnp.stack([k, v], axis=2)                 # [L,n,2,KV,hd]
        else:
            kv = jnp.concatenate(
                [cache["latent"][:, batch_index, :n_tokens],
                 cache["rope"][:, batch_index, :n_tokens]], axis=-1)
        L = kv.shape[0]
        check(L == view.n_kv_layers,
              f"prefill cache has {L} layers, view expects {view.n_kv_layers}")
        flat = kv.reshape(L * n_tokens, view.per_token_elems)
        toks = np.arange(n_tokens)
        pages, slots = self._token_coords(req, view, toks)
        self.pool = _pool_scatter(self.pool, flat, jnp.asarray(pages),
                                  jnp.asarray(slots))

    # ------------------------------------------------------------------
    def accounting_snapshot(self) -> Dict[str, int]:
        """Integer holder-class partition of the page budget — the pool
        half of a flight-recorder snapshot (DESIGN.md §13).  Pure reads;
        every field is deterministic given the session's input stream, so
        a replayed session must reproduce it bit-exactly.
        """
        live = set()
        for req in self.requests.values():
            for _, _, page in req.device_entries():
                live.add(page)
        return {
            "page_budget": self.page_budget,
            "free_pages": self.free_pages,
            "mapped_pages": self.mapped_pages,
            "request_pages": len(live),     # device pages held by live tables
            "swapped_now": self.swapped_now,
            "peak_mapped": self.peak_mapped,
            "swap_out_pages": self.swap_out_pages,
            "swap_in_pages": self.swap_in_pages,
            "resizes": self.resizes,
            # refcount summary (prefix sharing): pages with >1 holder and
            # the total explicit holder count across them
            "shared_pages": sum(1 for c in self._refs.values() if c > 1),
            "ref_total": sum(self._refs.values()),
        }

    def utilization(self) -> Dict[str, float]:
        frag = 0.0
        for rid, req in self.requests.items():
            view = self.views[req.model]
            if not view.n_kv_layers:
                continue
            used = req.tokens * view.per_token_elems * view.n_kv_layers
            held = sum(len(t) for t in req.tables) * self.page_elems
            frag += held - used
        return {
            "mapped_pages": self.mapped_pages,
            "free_pages": self.free_pages,
            "peak_mapped": self.peak_mapped,
            "internal_frag_bytes": frag * self.dtype.itemsize,
            # elastic-boundary signals (DESIGN.md §8)
            "page_budget": self.page_budget,
            "occupancy": self.mapped_pages / max(self.page_budget, 1),
            "swapped_pages": self.swapped_now,
            "swap_out_pages": self.swap_out_pages,
            "swap_in_pages": self.swap_in_pages,
            "swap_tier_bytes": (0 if self.swap_buffer is None
                                else self.swap_buffer.nbytes),
            "resizes": self.resizes,
        }
