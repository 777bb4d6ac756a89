"""Control lowering: host-driven vs device-resident decode step execution.

The paper's §3.3 persistent kernels keep the per-layer control loop on the
GPU.  The TPU/XLA analogue (DESIGN.md §2): a *fused* decode step — one XLA
program that scans over layers — is dispatched ONCE per token per batch;
layer transitions, the attention->FFN ping-pong and its collectives all
live inside the compiled program, exactly like a persistent kernel that
dispatches captured subgraphs.  The host keeps only admission and page
mapping, the paper's split.

Both lowering modes now serve decode from the virtualizer's SHARED paged
KV pool: steps take ``(tokens, pool, page_tables, lengths)`` and thread
the (donated) pool buffer through every layer's attention stage.  FFN
weights are symmetric: both modes gather each layer's expert / dense-MLP
slabs from the SHARED weights arena (``repro.core.weight_pool``) through
the model's slot table — there is no per-model device ``w_params`` tree;
the arena buffer and slot table are fetched from the pooled model's arena
at call time, so activating/evicting cold models never recompiles a step.

``HostDrivenStep`` is the ablation baseline (Table 3 row 1): every layer
issues separate attention-stage and FFN-stage dispatches with host Python
in between — 2L+2 dispatches/token instead of 1, plus 2L inter-pool
device transfers driven from the host.

``PagedFusedStep`` is lowering=ON over the pool: embed, every layer's
paged attention + proxy boundary + FFN, and the final logits are ONE
compiled ``lax.scan`` program consuming the same pooled param split
(kv_params / w_params) as the host-driven path.

``StreamingPrefill`` is the prompt-phase twin: per-layer full-sequence
attention + arena FFN with layer L+1's weight slabs uploading behind layer
L's attention, so a cold model's first token overlaps its own weight
upload instead of waiting for it (DESIGN.md §6).

Families that bypass split execution (SSM/hybrid/enc-dec/SWA) decode
through the fused dense-cache ``model.decode_step`` program compiled by
``runtime.engine.ModelRunner`` — there is no separate step class for them.

Every program compiles under a stable name of its own
(:func:`program_name`: ``decode_<model>`` for a fused step,
``decode_<stage>_<model>`` / ``prefill_<stage>_<model>`` for the stage
programs), and inside it the stage functions open their device scopes
(``split_exec.SCOPES``), so a profiler trace names the model, the stage
and the kernel of every device op.
"""
from __future__ import annotations

import functools
import re
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pools import PooledModel, transfer


def program_name(kind: str, model: str) -> str:
    """``<kind>_<model>`` with the model name's punctuation as ``_``: the
    program is ``jit_<name>`` in the HLO and in the profiler's events."""
    return f"{kind}_{re.sub(r'[^0-9A-Za-z_]', '_', model)}"


def jit_program(fn: Callable, kind: str, model: str, **jit_kw):
    """``jax.jit(fn, **jit_kw)`` compiled under :func:`program_name`."""
    @functools.wraps(fn)
    def program(*args, **kwargs):
        return fn(*args, **kwargs)

    program.__name__ = program.__qualname__ = program_name(kind, model)
    return jax.jit(program, **jit_kw)


class HostDrivenStep:
    """Per-layer host dispatch across the two pools (lowering OFF)."""

    def __init__(self, pooled: PooledModel, kv_device, w_device):
        self.pooled = pooled
        self.kv_device = kv_device
        self.w_device = w_device
        fns = pooled.stage_fns
        m = pooled.cfg.name
        # execution placement follows the committed pool buffers: attention
        # stages run where kv_params live, FFN stages where the arena lives.
        self._embed = jit_program(fns.embed, "decode_embed", m)
        self._attn = jit_program(fns.attn_stage, "decode_attn", m,
                                 donate_argnums=2)
        self._ffn = jit_program(fns.ffn_stage, "decode_ffn", m)
        self._combine = jit_program(fns.combine, "decode_combine", m)
        self._logits = jit_program(fns.logits, "decode_logits", m)
        # prompt-phase stage programs (the pipeline scheduler interleaves
        # prefill batches through the same per-layer machinery)
        self._pembed = jit_program(fns.prefill_embed, "prefill_embed", m)
        self._pattn = jit_program(fns.prefill_attn, "prefill_attn", m)
        self._pffn = jit_program(fns.prefill_ffn, "prefill_ffn", m)
        self._pcombine = jit_program(fns.prefill_combine, "prefill_combine",
                                     m)
        self._plogits = jit_program(fns.prefill_logits, "prefill_logits", m)

    def __call__(self, tokens, pool, page_tables, lengths
                 ) -> Tuple[jax.Array, jax.Array]:
        """tokens [B]; pool [n_pages, page_elems]; page_tables [L,B,P];
        lengths [B].  Returns (logits [B,V], updated pool)."""
        p_kv = self.pooled.kv_params
        abuf, slot_table = self.pooled.arena.acquire(self.pooled.cfg.name)
        x = self._embed(p_kv, tokens)
        for layer in range(self.pooled.stage_fns.n_layers):
            x, ffn_in, pool = self._attn(
                p_kv, x, pool, page_tables, lengths, layer)
            ffn_in_w = transfer(ffn_in, self.w_device)      # A-to-F
            ffn_out = self._ffn(abuf, slot_table, ffn_in_w, layer)
            ffn_out_kv = transfer(ffn_out, self.kv_device)  # F-to-A
            x = self._combine(x, ffn_out_kv)
        return self._logits(p_kv, x), pool

    def stage_generator(self, tokens, pool, page_tables, lengths):
        """Yield one pipeline stage at a time (for the layer-wise scheduler).

        Yields ("attn"|"ffn", layer) after issuing that stage's dispatch;
        the final return carries (logits, pool) in ``self.result``.
        """
        p_kv = self.pooled.kv_params
        abuf, slot_table = self.pooled.arena.acquire(self.pooled.cfg.name)
        x = self._embed(p_kv, tokens)
        for layer in range(self.pooled.stage_fns.n_layers):
            x, ffn_in, pool = self._attn(
                p_kv, x, pool, page_tables, lengths, layer)
            yield ("attn", layer)
            ffn_in_w = transfer(ffn_in, self.w_device)
            ffn_out = self._ffn(abuf, slot_table, ffn_in_w, layer)
            yield ("ffn", layer)
            ffn_out_kv = transfer(ffn_out, self.kv_device)
            x = self._combine(x, ffn_out_kv)
        yield ("logits", -1)
        self.result = (self._logits(p_kv, x), pool)


def logit_index(true_len):
    """Last-prompt-position index for ``prefill_logits``.

    A host int (every row shares one unpadded length) stays a scalar —
    the seed trace shape — while a per-row sequence from a coalesced
    same-model batch becomes a [B] int32 vector.
    """
    if isinstance(true_len, (int, np.integer)):
        return jnp.int32(int(true_len) - 1)
    return jnp.asarray(np.asarray(true_len, np.int32).reshape(-1) - 1)


class StreamingPrefill:
    """Arena-bounded prompt-phase execution with streamed weight uploads.

    The prompt phase used to be the one place a model's FULL param tree had
    to be device-resident; this runs it through the same ``(arena,
    slot_table)`` protocol as decode (DESIGN.md §6).  Per layer, the host
    issues:

      1. full-sequence attention for layer L (KV-pool side),
      2. the async upload of layer L+1's weight slabs
         (``WeightArena.prefetch_layer``) — hidden behind 1.,
      3. the layer's prompt KV scatter into the shared paged pool
         (``writer``), and
      4. the FFN gather out of the arena (``ffn_stage``) for layer L.

    Because uploads stream layer-by-layer behind attention, a COLD model's
    first token no longer waits for a monolithic weight upload: activation
    maps slots only (``upload=False``) and by the time prefill finishes
    every layer is resident, so the fused decode step (``PagedFusedStep``)
    dispatches with zero remaining upload work.  Used by BOTH lowering
    modes — prefill is per-request, off the per-token critical path, so
    per-layer dispatches cost nothing while buying the overlap.
    """

    def __init__(self, pooled: PooledModel, kv_device=None, w_device=None,
                 share: Optional[HostDrivenStep] = None):
        self.pooled = pooled
        self.kv_device = kv_device
        self.w_device = w_device
        if share is not None:
            # host-driven mode already jitted the same stage programs in
            # its HostDrivenStep — reuse them (one trace/compile cache per
            # model, whether a prompt runs here or via the scheduler)
            self._embed = share._pembed
            self._attn = share._pattn
            self._ffn = share._pffn
            self._combine = share._pcombine
            self._logits = share._plogits
        else:
            fns = pooled.stage_fns
            m = pooled.cfg.name
            self._embed = jit_program(fns.prefill_embed, "prefill_embed", m)
            self._attn = jit_program(fns.prefill_attn, "prefill_attn", m)
            self._ffn = jit_program(fns.prefill_ffn, "prefill_ffn", m)
            self._combine = jit_program(fns.prefill_combine,
                                        "prefill_combine", m)
            self._logits = jit_program(fns.prefill_logits, "prefill_logits",
                                       m)
        # prefix-cache programs (DESIGN.md §11) are StreamingPrefill-local
        # in BOTH lowering modes: suffix shapes depend on the fork point,
        # never on the decode lowering, so there is nothing to share
        fns = pooled.stage_fns
        m = pooled.cfg.name
        self._suffix_attn = None
        self._suffix_ffn = None
        self._route = None
        if getattr(fns, "suffix_attn", None) is not None:
            self._suffix_attn = jit_program(
                fns.suffix_attn, "prefill_suffix_attn", m,
                static_argnums=(5,))
            self._suffix_ffn = jit_program(
                fns.suffix_ffn, "prefill_suffix_ffn", m, static_argnums=(5,))
        if getattr(fns, "prefill_route", None) is not None:
            self._route = jit_program(fns.prefill_route, "prefill_route", m)
        #: per-layer routing of the last captured prompt pass:
        #: np.ndarray [S, L, k] (None when not captured / dense)
        self.captured_routes = None

    def __call__(self, tokens, true_len, pool, writer=None, *,
                 capture_routes: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
        """tokens [B,S] prompt ids; ``true_len`` the unpadded length whose
        last position's logits are returned — a host int shared by every
        row, or a length-B sequence for a coalesced same-model batch where
        each row carries its own prompt; ``writer(layer, layer_kv, pool)
        -> pool`` scatters one layer's prompt KV into the shared pool
        (None skips KV capture).  Returns (logits [B,V], pool)."""
        name = self.pooled.cfg.name
        arena = self.pooled.arena
        fns = self.pooled.stage_fns
        p_kv = self.pooled.kv_params
        arena.activate(name, upload=False)
        arena.prefetch_layer(name, 0)        # first FFN never stalls
        captured = [] if (capture_routes and self._route is not None) \
            else None
        self.captured_routes = None
        x = self._embed(p_kv, tokens)
        for layer in range(fns.n_layers):
            x, ffn_in, layer_kv = self._attn(p_kv, x, layer)
            # transfer hiding, weights edition: layer L+1's slabs upload
            # while layer L's attention is in flight
            arena.prefetch_layer(name, layer + 1)
            if writer is not None:
                pool = writer(layer, layer_kv, pool)
            if self.w_device is not None:
                ffn_in = transfer(ffn_in, self.w_device)     # A-to-F
            if captured is not None:
                captured.append(self._route(
                    arena.arena, arena.slot_table(name), ffn_in, layer))
            ffn_out = self._ffn(arena.arena, arena.slot_table(name),
                                ffn_in, layer)
            if self.kv_device is not None:
                ffn_out = transfer(ffn_out, self.kv_device)  # F-to-A
            x = self._combine(x, ffn_out)
        if captured is not None:
            # [L][B=1,S,k] -> [S, L, k] (the prefix tree's per-token axis);
            # ONE device_get at the end — a per-layer np.asarray would
            # sync the dispatch pipeline once per layer
            self.captured_routes = np.stack(
                [c[0] for c in jax.device_get(captured)], axis=1)
        return self._logits(p_kv, x, logit_index(true_len)), pool

    def suffix(self, tokens, true_suffix_len, fork, kv_extent, prefix_rows,
               pool, writer=None, slot_offsets=None, capacity: int = 0,
               capture_routes: bool = False) -> Tuple[jax.Array, jax.Array]:
        """Prefill ONLY the uncached suffix of a prompt (DESIGN.md §11).

        tokens: [1, S_suf] suffix ids zero-padded to the suffix bucket;
        ``true_suffix_len`` the unpadded suffix length (logits row);
        ``fork`` the cached-prefix length; ``kv_extent`` the PRODUCING
        pass's prefill bucket — the static KV reduction extent;
        ``prefix_rows``: the ``[n_kv_layers, fork, *kv_shape]`` device
        stack from ``KVVirtualizer.gather_prompt_rows`` (per-layer rows
        are sliced inside the jitted attention stage);
        ``writer(layer, layer_kv, pool)`` scatters suffix KV starting at
        token offset ``fork``; ``slot_offsets`` ([L, E] int32) the
        prefix's per-layer routed-pair counts and ``capacity`` the
        producing pass's expert capacity (MoE only).  Suffix groups are
        B=1 singletons.  Returns (logits [1, V], pool).

        The host loop is kept dispatch-lean on purpose — warm-turn TTFT
        is this loop: prefix rows and slot offsets upload ONCE (layer
        extraction happens in-program) and captured routes come back in
        one ``device_get`` at the end.
        """
        assert self._suffix_attn is not None, "model has no suffix path"
        name = self.pooled.cfg.name
        arena = self.pooled.arena
        fns = self.pooled.stage_fns
        p_kv = self.pooled.kv_params
        arena.activate(name, upload=False)
        arena.prefetch_layer(name, 0)
        B, S_suf = tokens.shape
        positions = jnp.broadcast_to(
            jnp.arange(S_suf, dtype=jnp.int32)[None, :] + jnp.int32(fork),
            (B, S_suf))
        off_all = None if slot_offsets is None else \
            jnp.asarray(np.asarray(slot_offsets, np.int32))
        captured = [] if (capture_routes and self._route is not None) \
            else None
        self.captured_routes = None
        x = self._embed(p_kv, tokens)
        for layer in range(fns.n_layers):
            x, ffn_in, layer_kv = self._suffix_attn(
                p_kv, x, prefix_rows, positions, layer, int(kv_extent))
            arena.prefetch_layer(name, layer + 1)
            if writer is not None:
                pool = writer(layer, layer_kv, pool)
            if self.w_device is not None:
                ffn_in = transfer(ffn_in, self.w_device)
            if captured is not None:
                captured.append(self._route(
                    arena.arena, arena.slot_table(name), ffn_in, layer))
            ffn_out = self._suffix_ffn(arena.arena, arena.slot_table(name),
                                       ffn_in, layer, off_all, int(capacity))
            if self.kv_device is not None:
                ffn_out = transfer(ffn_out, self.kv_device)
            x = self._combine(x, ffn_out)
        if captured is not None:
            self.captured_routes = np.stack(
                [c[0] for c in jax.device_get(captured)], axis=1)
        return self._logits(p_kv, x, logit_index(true_suffix_len)), pool


class PagedFusedStep:
    """Device-resident control (lowering ON) over the shared paged pool.

    One dispatch per token per batch: ``lax.scan`` over layer indices with
    the pool threaded through the carry, consuming the same pooled param
    split (kv_params on the KV device, w_params on the weights device)
    as :class:`HostDrivenStep` — the persistent-kernel analogue.

    ``postprocess`` (e.g. greedy sampling) is compiled into the same
    program so the host sees exactly one dispatch per decode step.
    """

    def __init__(self, pooled: PooledModel,
                 postprocess: Optional[Callable] = None, device=None):
        self.pooled = pooled
        fns = pooled.stage_fns
        # the attention-side params are committed to ONE device (the KV
        # pool's, where the page pool lives) so the fused program has a
        # single placement; FFN weights arrive per call as the shared
        # arena buffer + slot table (the engine colocates the arena with
        # the KV pool when lowering is on) — lowering=ON trades placement
        # freedom for one dispatch
        if device is None:
            leaves = jax.tree.leaves(pooled.kv_params)
            device = (next(iter(leaves[0].devices())) if leaves
                      else jax.devices()[0])
        self._p_kv = jax.device_put(pooled.kv_params, device)

        def step(p_kv, arena, slot_table, tokens, pool, page_tables,
                 lengths):
            x = fns.embed(p_kv, tokens)

            def body(carry, layer):
                x, pool = carry
                x, ffn_in, pool = fns.attn_stage(
                    p_kv, x, pool, page_tables, lengths, layer)
                ffn_out = fns.ffn_stage(arena, slot_table, ffn_in, layer)
                x = fns.combine(x, ffn_out)
                return (x, pool), None

            with jax.named_scope("loop"):
                (x, pool), _ = jax.lax.scan(
                    body, (x, pool), jnp.arange(fns.n_layers))
            logits = fns.logits(p_kv, x)
            with jax.named_scope("head"):
                out = (postprocess(logits) if postprocess is not None
                       else logits)
            return out, pool

        self._step = jit_program(step, "decode", pooled.cfg.name,
                                 donate_argnums=4)

    def __call__(self, tokens, pool, page_tables, lengths
                 ) -> Tuple[jax.Array, jax.Array]:
        abuf, slot_table = self.pooled.arena.acquire(self.pooled.cfg.name)
        return self._step(self._p_kv, abuf, slot_table,
                          tokens, pool, page_tables, lengths)


class MultiStepFusedStep:
    """Persistent multi-step decode: K tokens per host dispatch.

    An outer ``lax.scan`` over :class:`PagedFusedStep`'s body carrying
    ``(tokens, pool, lengths, done_mask)`` for K inner steps, with
    sampling (``runtime.sampler.sample_on_device``) compiled into the
    same program — logits NEVER leave the device; the host sees one
    dispatch returning ``[K, B]`` sampled token ids.

    Page tables are NOT in the carry: the virtualizer pre-reserves a
    block of pages covering all K tokens (``reserve_decode_block``)
    before dispatch, so the scan body indexes into the pre-extended
    table and no host table mutation happens mid-dispatch.  Per-row
    freezing (DESIGN.md §9):

    * ``done0 = steps_left <= 0`` freezes inactive batch-padding rows
      from step 0;
    * a row that samples its ``eos_id`` (or exhausts its per-row step
      budget) flips ``done`` — subsequent inner steps re-run its
      forward with frozen ``(token, length)`` but every state write is
      masked: emitted token is -1 (host trims by valid count), length
      and next-token freeze, and the spurious KV write beyond the
      frozen length lands either on a -1 table entry (dropped by the
      paged writer) or in a reserved page that attention never reads
      (reads stop at ``lengths``) and that ``commit_decode_block``
      returns to the free list.

    Valid tokens are therefore a strict prefix of each ``[K]`` row;
    the host commits ``(row >= 0).sum()`` tokens per request.  Greedy
    sampling never traces PRNG ops; with ``temperature > 0`` the inner
    step index is folded into ``key`` so a dispatch is replayable.
    """

    def __init__(self, pooled: PooledModel, k: int,
                 temperature: float = 0.0, top_k: int = 0, device=None):
        from repro.runtime.sampler import sample_on_device
        self.pooled = pooled
        self.k = int(k)
        assert self.k >= 1
        fns = pooled.stage_fns
        if device is None:
            leaves = jax.tree.leaves(pooled.kv_params)
            device = (next(iter(leaves[0].devices())) if leaves
                      else jax.devices()[0])
        self._p_kv = jax.device_put(pooled.kv_params, device)
        n_steps = self.k

        def step(p_kv, arena, slot_table, tokens, pool, page_tables,
                 lengths, steps_left, eos_ids, key):
            def inner(carry, t):
                toks, pool, lens, done = carry
                x = fns.embed(p_kv, toks)

                def body(c, layer):
                    x, pool = c
                    x, ffn_in, pool = fns.attn_stage(
                        p_kv, x, pool, page_tables, lens, layer)
                    ffn_out = fns.ffn_stage(arena, slot_table, ffn_in,
                                            layer)
                    x = fns.combine(x, ffn_out)
                    return (x, pool), None

                with jax.named_scope("loop"):
                    (x, pool), _ = jax.lax.scan(
                        body, (x, pool), jnp.arange(fns.n_layers))
                logits = fns.logits(p_kv, x)
                with jax.named_scope("head"):
                    sampled = sample_on_device(
                        logits, key, t, temperature=temperature,
                        top_k=top_k)
                    out_tok = jnp.where(done, jnp.int32(-1), sampled)
                    next_tok = jnp.where(done, toks, sampled)
                    new_len = jnp.where(done, lens, lens + 1)
                    hit_eos = ((~done) & (eos_ids >= 0)
                               & (sampled == eos_ids))
                    new_done = done | hit_eos | (steps_left <= t + 1)
                return (next_tok, pool, new_len, new_done), out_tok

            # the two scans' own control (counters, the layer index, the
            # rows' done mask) is the ``loop`` scope
            with jax.named_scope("loop"):
                done0 = steps_left <= 0
                (_, pool, _, _), out = jax.lax.scan(
                    inner, (tokens, pool, lengths, done0),
                    jnp.arange(n_steps))
            return out, pool

        self._step = jit_program(step, "decode", pooled.cfg.name,
                                 donate_argnums=4)

    def __call__(self, tokens, pool, page_tables, lengths, steps_left,
                 eos_ids=None, key=None) -> Tuple[jax.Array, jax.Array]:
        """tokens [B]; pool; page_tables [L,B,P] PRE-EXTENDED to cover K
        tokens; lengths [B]; steps_left [B] int32 per-row token budget
        for this dispatch (0 freezes the row entirely); eos_ids [B]
        int32, -1 disables EOS for that row.  Returns
        (token ids [K,B] int32 with -1 past each row's valid prefix,
        updated pool)."""
        abuf, slot_table = self.pooled.arena.acquire(self.pooled.cfg.name)
        if eos_ids is None:
            eos_ids = jnp.full(tokens.shape, -1, jnp.int32)
        if key is None:
            key = jax.random.PRNGKey(0)   # greedy path never reads it
        return self._step(self._p_kv, abuf, slot_table, tokens, pool,
                          page_tables, lengths, steps_left, eos_ids, key)


def dispatch_count(n_layers: int, fused: bool,
                   decode_steps: int = 1) -> int:
    """Host dispatches to commit ``decode_steps`` decode tokens (the
    ablation's control metric).  Fused lowering commits the whole
    K-token block in ONE dispatch (``MultiStepFusedStep``); host-driven
    mode pays the full per-layer dispatch train per token."""
    if fused:
        return 1
    # embed + (attn + ffn + combine + 2 transfers) per layer + logits
    return (2 + n_layers * 5) * decode_steps
