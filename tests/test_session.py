"""Online serving session acceptance: the PR-4 tentpole invariants.

* pre-refactor parity: ``run()`` — now a thin wrapper over
  ``submit``/``step`` — reproduces the token streams captured from the
  seed offline driver BIT-EXACTLY on a fixed seed/trace, in both
  lowering modes, while same-model arrivals coalesce into [B>1, S]
  prefill passes;
* session parity: driving ``submit``/``step`` by hand produces the same
  streams as the ``run()`` wrapper;
* batched prefill parity: one coalesced [B, S] StreamingPrefill pass is
  bit-exact with B separate [1, S] passes — logits AND every prompt-KV
  byte landing in the shared pool (per-request expert routing);
* cancellation: ``cancel()`` frees KV pages and drops the arena pin
  atomically, mid-prefill (admitted, pages mapped, no slot yet) and
  mid-decode (in a batch slot), returning pool/arena accounting to
  baseline while the rest of the session keeps serving;
* backpressure on the handle: admit/queue/reject is visible at submit
  time, queued handles drain to ADMITTED, and per-token callbacks
  stream TokenEvents with first/done marks.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import PAPER_COLOC_SET, get_smoke_config
from repro.core.control import StreamingPrefill
from repro.core.pools import build_pools
from repro.models import build_model
from repro.runtime.engine import CrossPoolEngine, EngineMode, ServingSession
from repro.runtime.request import Phase, Request
from repro.runtime.session import HandleState

MOE, MLA, MOON = "qwen3-moe-235b-a22b", "minicpm3-4b", "moonshot-v1-16b-a3b"
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "pre_refactor_token_streams.json")


def _models(names=PAPER_COLOC_SET):
    return {n: get_smoke_config(n).replace(dtype="float32") for n in names}


def _engine(names=PAPER_COLOC_SET, lowering=True, **kw):
    kw.setdefault("page_budget", 2048)
    kw.setdefault("page_bytes", 4096)
    kw.setdefault("slab_bytes", 4096)
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_ctx", 64)
    kw.setdefault("seed", 0)
    return CrossPoolEngine(_models(names),
                           mode=EngineMode(pipeline=True, lowering=lowering),
                           **kw)


def _trace_fused():
    return [Request(0, MOE, 6, 3, 0.0), Request(1, MOE, 7, 3, 0.0),
            Request(2, MOE, 9, 4, 0.0), Request(3, MLA, 5, 3, 0.0),
            Request(4, MLA, 6, 2, 0.0), Request(5, MOON, 20, 3, 0.0)]


def _trace_host():
    return [Request(0, MOE, 6, 3, 0.0), Request(1, MLA, 5, 2, 0.0),
            Request(2, MOON, 20, 3, 0.0)]


def _streams(reqs):
    return {str(r.request_id): list(map(int, r.output_ids)) for r in reqs}


# ---------------------------------------------------------------------------
# bit-exact parity with the pre-refactor offline driver
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("key,lowering,mk", [
    ("fused_pipeline", True, _trace_fused),
    ("host_pipeline", False, _trace_host),
])
def test_run_matches_pre_refactor_driver(key, lowering, mk):
    """The compat wrapper (submit/step underneath) reproduces the token
    streams captured from the seed monolithic ``run()`` loop, bit for
    bit — and the fused trace witnesses B>1 coalesced prefill."""
    with open(FIXTURE) as f:
        want = json.load(f)[key]
    engine = _engine(lowering=lowering)
    reqs = mk()
    stats = engine.run(reqs)
    assert _streams(reqs) == want["streams"]
    assert stats.tokens_out == want["tokens_out"]
    if key == "fused_pipeline":
        # same-model same-bucket arrivals in one step window ran as ONE
        # [B, S] pass with B > 1 (the two t=0 MOE and the two MLA
        # requests), and the late joiner ran B=1 — continuous batching
        assert max(stats.prefill_batch_sizes) > 1
        assert stats.prefill_batch_sizes.count(2) == 2


def test_session_api_matches_run_wrapper():
    """Driving submit/step by hand == the run() wrapper, bit for bit."""
    ref_engine = _engine()
    ref_reqs = _trace_fused()
    ref_engine.run(ref_reqs)

    engine = _engine()
    reqs = _trace_fused()
    handles = [engine.submit(r) for r in reqs]
    assert all(h.admission == "admitted" for h in handles)
    steps = 0
    while any(not h.done for h in handles):
        engine.step()
        steps += 1
        assert steps < 100
    assert _streams(reqs) == _streams(ref_reqs)
    assert all(h.state is HandleState.FINISHED for h in handles)
    # ServingSession is the same front-end
    assert ServingSession is CrossPoolEngine


def test_streaming_callbacks_and_events():
    """Per-token callbacks fire in stream order with first/done marks and
    agree with the events returned by step()."""
    engine = _engine(names=(MOE, MLA))
    seen = []
    h = engine.submit(Request(0, MOE, 6, 3, 0.0),
                      on_token=lambda e: seen.append(e))
    all_events = []
    while not h.done:
        all_events.extend(engine.step())
    assert [e.token for e in seen] == h.tokens
    assert [e.index for e in seen] == [0, 1, 2]
    assert seen[0].first and not seen[0].done
    assert seen[-1].done and not seen[-1].first
    assert all(e.model == MOE for e in seen)
    assert [e.token for e in all_events if e.request_id == 0] == h.tokens
    # event times are the request's token times (TBT bookkeeping source)
    assert [e.time for e in seen] == h.request.token_times


# ---------------------------------------------------------------------------
# batched same-model prefill parity vs B=1
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [MOE, MLA])
def test_batched_prefill_bit_exact_vs_solo(name):
    """One [B=2, S] coalesced pass == two [1, S] passes: the returned
    logits AND every prompt-KV byte landing in the shared pool."""
    models = _models((name,))
    cfg = models[name]
    params = {name: build_model(cfg).init(jax.random.PRNGKey(0))}
    kv_pool, w_pool, pooled = build_pools(
        models, params, page_budget=256, page_bytes=4096,
        pool_dtype=jnp.float32, slab_bytes=4096, activate_resident=False)
    virt = kv_pool.virtualizer
    seq, bucket = 7, 16
    rng = np.random.default_rng(0)
    ids = [rng.integers(0, cfg.vocab_size, bucket).astype(np.int32)
           for _ in range(2)]
    sp = StreamingPrefill(pooled[name])

    def writer(rid, n, batch_index=0):
        def write(layer, layer_kv, pool):
            return virt.write_prompt_layer(pool, name, rid, layer, layer_kv,
                                           n, batch_index=batch_index)
        return write

    # solo reference passes
    solo = []
    for i in range(2):
        virt.register_request(i, name, seq)
        logits, virt.pool = sp(jnp.asarray(ids[i][None]), seq, virt.pool,
                               writer(i, seq))
        solo.append(np.asarray(logits[0]))

    # one coalesced pass into fresh requests
    virt.register_request(10, name, seq)
    virt.register_request(11, name, seq)

    def batched_writer(layer, layer_kv, pool):
        pool = writer(10, seq, 0)(layer, layer_kv, pool)
        return writer(11, seq, 1)(layer, layer_kv, pool)

    logits, virt.pool = sp(jnp.asarray(np.stack(ids)), [seq, seq],
                           virt.pool, batched_writer)
    got = np.asarray(logits)
    for i in range(2):
        assert np.array_equal(solo[i], got[i]), \
            f"{name}: batched prefill row {i} logits != solo pass"
    # prompt KV bytes identical page-for-page
    pool_np = np.asarray(virt.pool)
    for solo_rid, batch_rid in ((0, 10), (1, 11)):
        r_s, r_b = virt.requests[solo_rid], virt.requests[batch_rid]
        for t_s, t_b in zip(r_s.tables, r_b.tables):
            for p_s, p_b in zip(t_s, t_b):
                assert np.array_equal(pool_np[p_s], pool_np[p_b]), \
                    f"{name}: prompt KV bytes differ in the pool"


def test_mixed_length_group_uses_per_row_logit_index():
    """Rows of one coalesced pass keep their own unpadded lengths: a
    [2, S] group with different true lengths matches the two solo passes
    at those lengths."""
    models = _models((MLA,))
    cfg = models[MLA]
    params = {MLA: build_model(cfg).init(jax.random.PRNGKey(0))}
    _, _, pooled = build_pools(
        models, params, page_budget=256, page_bytes=4096,
        pool_dtype=jnp.float32, slab_bytes=4096, activate_resident=False)
    rng = np.random.default_rng(0)
    ids = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
           for _ in range(2)]
    sp = StreamingPrefill(pooled[MLA])
    want0, _ = sp(jnp.asarray(ids[0][None]), 5, None, None)
    want1, _ = sp(jnp.asarray(ids[1][None]), 9, None, None)
    got, _ = sp(jnp.asarray(np.stack(ids)), [5, 9], None, None)
    assert np.array_equal(np.asarray(want0[0]), np.asarray(got[0]))
    assert np.array_equal(np.asarray(want1[0]), np.asarray(got[1]))


# ---------------------------------------------------------------------------
# cancellation correctness
# ---------------------------------------------------------------------------

def _accounting(engine):
    return {
        "mapped_pages": engine.virt.mapped_pages,
        "live_requests": sorted(engine.virt.requests),
        "pins": dict(engine.arena.pins) if engine.arena is not None else {},
        "inflight": dict(engine.admission.inflight),
        "queued": engine.admission.queued_count(),
    }


def test_cancel_mid_prefill_and_mid_decode_restores_accounting():
    """cancel() unpins weight slabs and frees KV pages atomically: after
    a mid-prefill cancel (admitted: pages mapped + pin held, no slot yet)
    and a mid-decode cancel (in a batch slot), pool and arena accounting
    return to baseline and the session still serves new work."""
    engine = _engine(names=(MOE, MLA))
    baseline = _accounting(engine)

    # --- mid-prefill: admission mapped pages and took the pin ----------
    h0 = engine.submit(Request(0, MOE, 6, 4, 0.0))
    assert h0.state is HandleState.ADMITTED
    assert engine.virt.mapped_pages > baseline["mapped_pages"]
    assert engine.arena.pins.get(MOE) == 1
    assert engine.cancel(h0)
    assert h0.state is HandleState.CANCELLED
    assert h0.request.phase is Phase.CANCELLED
    assert _accounting(engine) == baseline
    assert not engine.cancel(h0)            # idempotent on terminal states

    # --- mid-decode: prefilled into a slot, tokens already streaming ---
    h1 = engine.submit(Request(1, MOE, 6, 50, 0.0))
    h2 = engine.submit(Request(2, MLA, 5, 3, 0.0))
    engine.step()
    engine.step()
    assert h1.state is HandleState.DECODING
    assert len(h1.tokens) >= 2
    assert engine.runners[MOE].active
    assert engine.cancel(h1)
    assert not engine.runners[MOE].active
    # the co-resident request is untouched and drains to completion
    stats = engine.drain()
    assert h2.state is HandleState.FINISHED
    assert len(h2.tokens) == 3
    assert _accounting(engine) == baseline
    assert stats.cancelled == 2


def test_cancel_queued_request_leaves_queue():
    """A request queued by arena backpressure cancels out of the queue
    (it holds no resources) and the session drains without it."""
    from repro.core.weight_pool import slabs_for_config
    models = _models((MOE, MLA))
    need = {n: slabs_for_config(c, 4096) for n, c in models.items()}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=True))
    h_moe = engine.submit(Request(0, MOE, 8, 3, 0.0))
    h_mla = engine.submit(Request(1, MLA, 8, 3, 0.0))
    assert h_moe.admission == "admitted"
    assert h_mla.admission == "queued"      # weights-arena backpressure
    assert h_mla.state is HandleState.QUEUED
    assert engine.cancel(h_mla)
    assert engine.admission.queued_count() == 0
    engine.drain()
    assert h_moe.state is HandleState.FINISHED
    assert h_mla.state is HandleState.CANCELLED
    assert len(h_mla.tokens) == 0
    assert not engine.arena.pins and not engine.admission.inflight


def test_queued_handle_drains_to_admitted_and_finishes():
    """Backpressure lifecycle on the handle: queued at submit, ADMITTED
    once the blocking request finishes, FINISHED at end of stream."""
    from repro.core.weight_pool import slabs_for_config
    models = _models((MOE, MLA))
    need = {n: slabs_for_config(c, 4096) for n, c in models.items()}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64,
        mode=EngineMode(pipeline=True, lowering=True))
    h_moe = engine.submit(Request(0, MOE, 8, 2, 0.0))
    h_mla = engine.submit(Request(1, MLA, 8, 2, 0.0))
    assert h_mla.state is HandleState.QUEUED
    engine.drain()
    assert h_moe.state is HandleState.FINISHED
    assert h_mla.state is HandleState.FINISHED
    assert len(h_mla.tokens) == 2
    assert engine.stats.admission.weight_pressure_queued >= 1


def test_cancel_from_on_token_callback_defers_to_step_boundary():
    """The "stop at token X" pattern: a cancel issued from inside a
    streaming callback must not corrupt the in-flight commit loops — it
    defers to the step boundary, then tears down atomically."""
    engine = _engine(names=(MOE, MLA))
    baseline = _accounting(engine)
    h_victim = engine.submit(Request(0, MOE, 6, 50, 0.0))
    h_trigger = engine.submit(
        Request(1, MOE, 7, 50, 0.0),
        on_token=lambda e: e.index >= 2 and h_victim.cancel())
    engine.step()                        # prefill both (coalesced) + decode:
    assert h_victim.state is HandleState.DECODING      # indices 0 and 1
    engine.step()                        # trigger's token 2 cancels victim
    assert h_victim.state is HandleState.CANCELLED
    assert engine.cancel(h_trigger)      # direct cancel outside a step
    assert _accounting(engine) == baseline
    assert engine.stats.cancelled == 2


def test_real_prompt_ids_round_trip_and_length_contract():
    """``prompt_ids`` drives the prefill when provided; a length that
    disagrees with ``prompt_tokens`` (the page-mapping contract) fails
    loudly instead of scattering KV past the mapped pages."""
    engine = _engine(names=(MOE, MLA))
    rng = np.random.default_rng(3)
    ids = rng.integers(0, _models()[MOE].vocab_size, 6).astype(np.int32)
    h = engine.submit(Request(0, MOE, 6, 2, 0.0, prompt_ids=ids))
    engine.drain()
    assert h.state is HandleState.FINISHED and len(h.tokens) == 2

    engine.submit(Request(1, MOE, 9, 2, 0.0, prompt_ids=ids))  # 6 != 9
    with pytest.raises(AssertionError, match="prompt_ids length"):
        engine.step()


def test_kept_prompt_logits_match_unpooled_forward():
    """A request that asks for its first-token logits gets the row its
    first token was sampled from, and in float32 that row is the model's
    own unpooled forward (same ``PRNGKey(i)`` weights) — pooled pages and
    arena slabs add no error.  Prompts are a whole prefill bucket, so the
    MoE routes with the same expert capacity in both."""
    names = (MOE, MLA)
    engine = _engine(names=names)
    rng = np.random.default_rng(5)
    reqs = [Request(i, name, 16, 2, 0.0, keep_prompt_logits=True,
                    prompt_ids=rng.integers(0, _models()[name].vocab_size,
                                            16).astype(np.int32))
            for i, name in enumerate(names)]
    reqs.append(Request(2, MOE, 16, 2, 0.0, prompt_ids=reqs[0].prompt_ids))
    for r in reqs:
        engine.submit(r)
    engine.drain()
    assert reqs[2].prompt_logits is None
    for i, (name, cfg) in enumerate(_models(names).items()):
        model = build_model(cfg)
        want, _ = model.forward(model.init(jax.random.PRNGKey(i)),
                                jnp.asarray(reqs[i].prompt_ids)[None])
        got = reqs[i].prompt_logits
        assert int(np.argmax(got)) == reqs[i].output_ids[0]
        np.testing.assert_allclose(got, np.asarray(want[0, -1]),
                                   rtol=1e-5, atol=1e-5)


def test_reset_stats_opens_window_and_prunes_terminal_handles():
    """reset_stats() starts a fresh latency window and prunes terminal
    handles (the memory bound for long-lived sessions)."""
    engine = _engine(names=(MOE, MLA))
    engine.submit(Request(0, MOE, 6, 3, 0.0))
    stats = engine.drain()
    assert stats.tokens_out == 3 and len(stats.tbt) == 2
    engine.reset_stats()
    assert not engine.handles and not engine._submitted
    h = engine.submit(Request(1, MOE, 6, 2, 0.0))
    stats = engine.drain()
    assert h.state is HandleState.FINISHED
    assert stats.tokens_out == 2 and len(stats.tbt) == 1   # window-scoped


def test_rejection_visible_on_handle():
    """The front door's reject verdict lands on the handle at submit."""
    engine = _engine(names=(MLA,), page_budget=8)
    handles = [engine.submit(Request(i, MLA, 4096, 4, 0.0))
               for i in range(engine.admission.max_queue + 1)]
    assert all(h.state is HandleState.QUEUED for h in handles[:-1])
    assert handles[-1].state is HandleState.REJECTED
    assert handles[-1].admission == "rejected"
    assert handles[-1].request.phase is Phase.REJECTED
    # nothing can ever drain these; the session exits instead of spinning
    stats = engine.drain()
    assert stats.tokens_out == 0


def test_synthetic_prompts_are_silently_cache_cold():
    """A request without ``prompt_ids`` has no token content to key the
    radix tree — with the cache ON it must run cache-cold (no hit, no
    insert) and serve the exact same stream as a cache-off engine."""
    from repro.configs.base import CacheConfig, EngineConfig

    streams = []
    for cache_on in (True, False):
        cfg = EngineConfig(mode=EngineMode(pipeline=True, lowering=True),
                           cache=CacheConfig(enabled=cache_on))
        engine = CrossPoolEngine(_models((MLA,)), page_budget=2048,
                                 page_bytes=4096, max_batch=2, max_ctx=64,
                                 config=cfg, seed=0)
        handles = [engine.submit(Request(i, MLA, 6, 3, 0.0))
                   for i in range(2)]
        engine.drain()
        assert all(h.state is HandleState.FINISHED for h in handles)
        assert all(not h.cache_hit and h.cached_tokens == 0
                   for h in handles)
        if cache_on:
            snap = engine.cache.snapshot()
            assert snap["hits"] == 0 and snap["inserted_chunks"] == 0
        streams.append([list(h.tokens) for h in handles])
    assert streams[0] == streams[1]
