"""Prefill-through-arena acceptance: the PR-3 tentpole invariants.

* streaming prefill parity: prompt-phase FFN served from the shared slab
  arena with layer-by-layer weight uploads reproduces the seed full-tree
  ``model.prefill`` BIT-EXACTLY — logits AND the prompt KV scattered into
  the shared pool;
* streaming activation: a cold model's prefill starts with ZERO layers
  uploaded and finishes fully resident, one layer upload per layer;
* scheduler interleave: two models' prompt phases through the layer-wise
  pipeline scheduler reproduce the sequential streaming results exactly;
* pin/unpin mid-stream: a model evicted between prefill and its first
  decode is transparently re-activated (bit-identical logits), and a
  PINNED model can never be evicted in that window;
* the engine holds NO device-resident full param tree for paged models —
  device FFN bytes are slot_budget-bounded for prefill AND decode;
* arena-aware admission: a cold-model burst that cannot co-reside queues
  at the front door (no LRU thrash) and drains as pins drop.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PAPER_COLOC_SET, get_smoke_config
from repro.core.admission import AdmissionController, PendingRequest
from repro.core.control import PagedFusedStep, StreamingPrefill
from repro.core.pipeline import LayerPipelineScheduler
from repro.core.pools import build_pools
from repro.core.weight_pool import OutOfSlabsError, slabs_for_config
from repro.models import build_model
from repro.runtime.engine import CrossPoolEngine, EngineMode
from repro.runtime.request import Request

MOE, MLA = "qwen3-moe-235b-a22b", "minicpm3-4b"


def _build(names, dtype="float32", slot_budget=None, slab_bytes=4096,
           page_budget=256, activate=False):
    models = {n: get_smoke_config(n).replace(dtype=dtype) for n in names}
    params = {n: build_model(c).init(jax.random.PRNGKey(i))
              for i, (n, c) in enumerate(models.items())}
    kv_pool, w_pool, pooled = build_pools(
        models, params, page_budget=page_budget, page_bytes=4096,
        pool_dtype=jnp.float32 if dtype == "float32" else jnp.bfloat16,
        slot_budget=slot_budget, slab_bytes=slab_bytes,
        activate_resident=activate)
    return models, params, kv_pool, w_pool, pooled


def _prompt(cfg, seq, bucket, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg.vocab_size, bucket).astype(np.int32)
    return jnp.asarray(ids[None, :]), seq


def _writer(virt, name, rid, n_tokens):
    def write(layer, layer_kv, pool):
        return virt.write_prompt_layer(pool, name, rid, layer, layer_kv,
                                       n_tokens)
    return write


# ---------------------------------------------------------------------------
# bit-exact parity vs the seed full-tree prefill
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [MOE, MLA])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_streaming_prefill_matches_full_tree_bit_exact(name, dtype):
    """Arena prefill == full-tree prefill, bit for bit: the returned
    logits AND every prompt-KV byte landing in the shared pool."""
    models, params, kv_pool, w_pool, pooled = _build((name,), dtype=dtype)
    cfg = models[name]
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    model = build_model(cfg)
    seq, bucket = 7, 16
    tokens, _ = _prompt(cfg, seq, bucket)

    # seed path: fused full-sequence prefill over the FULL param tree,
    # dense transient cache scattered into pool pages afterwards
    cache = model.init_cache(1, bucket)
    want, cache = model.prefill(params[name], tokens, cache,
                                logit_index=seq - 1)
    virt.register_request(0, name, seq)
    virt.write_prompt_from_cache(name, 0, cache, seq)

    # arena path: per-layer streaming with NO full tree anywhere
    virt.register_request(1, name, seq)
    assert not arena.is_resident(name)
    uploads0 = arena.layer_uploads
    sp = StreamingPrefill(pooled[name])
    got, virt.pool = sp(tokens, seq, virt.pool, _writer(virt, name, 1, seq))

    # bf16 streams are bit-exact.  In float32 the fused full-tree program
    # and the per-layer stage programs are two different XLA programs,
    # which may order a reduction differently: XLA promises no bit
    # equality between them, and they differ by ~2e-7 relative (a couple
    # of f32 ulps).  A wrong slab or page moves logits by O(1).
    tol = (dict(rtol=1e-5, atol=1e-5) if dtype == "float32"
           else dict(rtol=0, atol=0))
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), **tol,
        err_msg=f"{name}/{dtype}: streaming arena prefill logits != full-tree")
    # streaming activation: started cold, ended fully uploaded, one layer
    # upload per layer
    assert arena.residency[name].uploaded.all()
    assert arena.layer_uploads - uploads0 == cfg.n_layers
    # the prompt KV bytes in the pool must be identical page-for-page
    pool_np = np.asarray(virt.pool)
    r0, r1 = virt.requests[0], virt.requests[1]
    for t0, t1 in zip(r0.tables, r1.tables):
        for p0, p1 in zip(t0, t1):
            # the same two-program f32 reassociation reaches the KV of
            # every layer after the first
            np.testing.assert_allclose(
                pool_np[p1], pool_np[p0], **tol,
                err_msg=f"{name}/{dtype}: prompt KV differs in the pool")


def test_scheduler_prefill_interleaves_and_matches_sequential():
    """Two cold models' prompt phases through the pipeline scheduler:
    logits identical to sequential streaming prefill, stages interleaved,
    uploads streamed (never a monolithic upload)."""
    from repro.core.pipeline import InflightBatch
    models, params, kv_pool, w_pool, pooled = _build((MOE, MLA))
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    devs = jax.devices()
    seq, bucket = 7, 16

    # sequential reference
    seq_logits = {}
    for rid, name in enumerate(models):
        tokens, _ = _prompt(models[name], seq, bucket, seed=rid)
        virt.register_request(rid, name, seq)
        sp = StreamingPrefill(pooled[name])
        seq_logits[name], virt.pool = sp(tokens, seq, virt.pool,
                                         _writer(virt, name, rid, seq))
    for name in models:
        arena.unpin(name)
        arena.evict(name)               # back to cold

    sched = LayerPipelineScheduler(pooled, devs[0], devs[-1])
    batches = []
    for i, name in enumerate(models):
        tokens, _ = _prompt(models[name], seq, bucket, seed=i)
        rid = 10 + i
        virt.register_request(rid, name, seq)
        batches.append(InflightBatch(
            batch_id=i, model=name, tokens=tokens, prefill=True,
            true_len=seq, kv_writer=_writer(virt, name, rid, seq)))
    done, virt.pool = sched.run(batches, virt.pool, max_inflight=2)
    assert len(done) == 2
    for b in done:
        assert np.array_equal(np.asarray(seq_logits[b.model]),
                              np.asarray(b.logits)), b.model
        assert arena.residency[b.model].uploaded.all()
    # the round-robin issue order must actually interleave the two pools
    assert sched.overlap_fraction() > 0.3
    models_in_log = {e[1] for e in sched.stage_log}
    assert models_in_log == set(models)


# ---------------------------------------------------------------------------
# pin/unpin correctness between prefill and the first decode
# ---------------------------------------------------------------------------

def test_eviction_between_prefill_and_first_decode():
    """A model evicted mid-stream (after prefill, before its first decode)
    is re-activated transparently by the decode step's ``acquire`` and
    produces bit-identical logits; while PINNED it cannot be evicted."""
    models, params, kv_pool, w_pool, pooled = _build((MOE, MLA))
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    name, cfg = MOE, models[MOE]
    seq, bucket = 7, 16
    tokens, _ = _prompt(cfg, seq, bucket)
    virt.register_request(0, name, seq)
    sp = StreamingPrefill(pooled[name])
    arena.pin(name)                      # the engine's per-request pin
    _, virt.pool = sp(tokens, seq, virt.pool, _writer(virt, name, 0, seq))
    virt.extend_request(0, 1)

    view = virt.views[name]
    max_pages = max(1, math.ceil(32 / view.tokens_per_page))
    tables = virt.batch_tables(name, [0], max_pages)
    lengths = jnp.full((1,), seq, jnp.int32)
    next_tok = jnp.zeros((1,), jnp.int32)
    step = PagedFusedStep(pooled[name])

    # pinned: the prefill-to-first-decode window is eviction-proof
    with pytest.raises(ValueError):
        arena.evict(name)
    logits1, virt.pool = step(next_tok, virt.pool, tables, lengths)

    # now simulate the mid-stream eviction: pins dropped (request aborted
    # elsewhere / accounting bug being defended against), model evicted
    arena.unpin(name)
    arena.evict(name)
    assert not arena.is_resident(name)
    logits2, virt.pool = step(next_tok, virt.pool, tables, lengths)
    assert arena.is_resident(name) and arena.residency[name].uploaded.all()
    assert np.array_equal(np.asarray(logits1), np.asarray(logits2)), \
        "re-activation after mid-stream eviction changed decode logits"


# ---------------------------------------------------------------------------
# the engine holds no full tree; device FFN bytes phase-invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lowering", [True, False])
def test_engine_paged_models_hold_no_full_tree(lowering):
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in PAPER_COLOC_SET}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096, slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=lowering))
    for n, runner in engine.runners.items():
        assert runner.paged, n
        assert runner.params is None, \
            f"{n}: paged runner still holds a device-resident param tree"
    assert engine.arena.device_bytes() == \
        engine.arena.slot_budget * engine.arena.slab_bytes
    reqs = [Request(request_id=i, model=n, prompt_tokens=6,
                    max_new_tokens=2, arrival_time=0.0)
            for i, n in enumerate(models)]
    stats = engine.run(reqs)
    assert all(r.finish_time > 0 for r in reqs)
    assert stats.tokens_out == sum(r.max_new_tokens for r in reqs)
    assert "no full-tree phase remains" in engine.report()


def test_engine_pipelined_prefill_host_mode():
    """pipeline=ON / lowering=OFF: concurrent cold-model prompt phases go
    through the layer-wise scheduler and still serve to completion."""
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in (MOE, MLA)}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096, slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=False))
    reqs = [Request(request_id=i, model=n, prompt_tokens=6,
                    max_new_tokens=3, arrival_time=0.0)
            for i, n in enumerate(models)]
    stats = engine.run(reqs)
    assert all(r.finish_time > 0 for r in reqs)
    assert stats.tokens_out > 0
    # prefill stages went through the scheduler's log
    assert any(e[2] == "attn" for e in engine.scheduler.stage_log)


# ---------------------------------------------------------------------------
# arena-aware admission
# ---------------------------------------------------------------------------

def test_admission_queues_cold_burst_under_arena_pressure():
    """With a one-model arena, the second cold model's request QUEUES at
    admission (weights pressure) and drains once the first finishes."""
    models, params, kv_pool, w_pool, pooled = _build(
        (MOE, MLA), page_budget=4096,
        slot_budget=max(slabs_for_config(
            get_smoke_config(n).replace(dtype="float32"), 4096)
            for n in (MOE, MLA)))
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    adm = AdmissionController(virt, arena=arena)

    r_moe = PendingRequest(0, MOE, 8, 4, 0.0)
    r_mla = PendingRequest(1, MLA, 8, 4, 0.0)
    assert adm.offer(r_moe, 0.0) == "admitted"
    # admission takes the pin immediately — BEFORE the model is resident
    assert arena.pins.get(MOE) == 1
    # MOE not activated yet, but its slabs are PROMISED: MLA must queue
    assert adm.offer(r_mla, 0.0) == "queued"
    assert adm.stats.weight_pressure_queued == 1
    arena.activate(MOE, upload=False)
    assert adm.drain(1.0) == []          # still pinned + in flight
    assert adm.drain(1.5) == []          # drain retries do NOT inflate
    assert adm.stats.weight_pressure_queued == 1
    adm.finish(MOE)                      # drops the pin + in-flight count
    assert MOE not in arena.pins
    drained = adm.drain(2.0)
    assert [p.request_id for p in drained] == [1]
    assert adm.stats.admitted == 2 and adm.stats.queued == 1


def test_admission_pin_protects_lru_victim_before_prefill():
    """A model with an admitted-but-not-yet-prefilled request cannot be
    picked as an LRU eviction victim by another activation: the pin is
    taken at ADMISSION, closing the admission-to-prefill window."""
    models, params, kv_pool, w_pool, pooled = _build(
        (MOE, MLA), page_budget=4096,
        slot_budget=max(slabs_for_config(
            get_smoke_config(n).replace(dtype="float32"), 4096)
            for n in (MOE, MLA)))
    arena = w_pool.arena
    adm = AdmissionController(kv_pool.virtualizer, arena=arena)
    arena.activate(MOE, upload=False)    # resident, idle, LRU-oldest
    assert adm.offer(PendingRequest(0, MOE, 8, 4, 0.0), 0.0) == "admitted"
    # cold MLA activation under pressure must NOT evict MOE (whose
    # admitted request has not prefilled yet) — it fails atomically
    with pytest.raises(OutOfSlabsError):
        arena.activate(MLA, upload=False)
    assert arena.is_resident(MOE)
    adm.finish(MOE)
    arena.activate(MLA, upload=False)    # now MOE is a legal victim
    assert arena.is_resident(MLA) and not arena.is_resident(MOE)


def test_engine_cold_burst_queues_not_thrash():
    """Engine-level: two cold models arriving together through a one-model
    arena both complete; the loser is queued by the admission controller
    (not busy-waited against the LRU) and each model activates exactly
    once — no ping-pong eviction."""
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in (MOE, MLA)}
    need = {n: slabs_for_config(c, 4096) for n, c in models.items()}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=True))
    reqs = [Request(request_id=0, model=MOE, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=0.0),
            Request(request_id=1, model=MLA, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=0.0)]
    stats = engine.run(reqs)
    assert all(r.finish_time > 0 for r in reqs), "a request was dropped"
    assert stats.admission.weight_pressure_queued >= 1
    assert stats.weights_pool["activations"] == 2, \
        "cold burst must not thrash the arena LRU"
    assert stats.weights_pool["evictions"] == 1
    assert not engine.arena.pins
