"""Weights-arena acceptance: the PR-2 tentpole invariants.

* FFN-stage bit-for-bit parity: expert / dense-MLP weights gathered out of
  the shared slab arena reproduce the resident-``w_params`` FFN outputs
  exactly (f32 AND bf16 — the untyped byte slabs round-trip every dtype);
* multi-model decode parity through the arena for both lowering modes
  (GQA+moe and MLA+dense colocated in ONE arena);
* device FFN bytes are fixed by ``slot_budget`` alone — constant as the
  colocated model count grows (the weights twin of the PR-1 KV claim);
* evict + re-activate of an idle model reproduces identical logits;
* property test: activate/evict/pin sequences, including ones that hit
  ``OutOfSlabsError`` mid-sequence, never leak slabs, never double-map,
  and failed activations leave the arena byte-for-byte unchanged;
* `split_params`/`merge_params` round-trip: leaf-exact over every config,
  no leaf in both trees (the boundary the arena's accounting relies on).
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import ARCH_NAMES, PAPER_COLOC_SET, get_smoke_config
from repro.core import split_exec
from repro.core.control import HostDrivenStep, PagedFusedStep
from repro.core.pools import build_pools
from repro.core.weight_pool import (OutOfSlabsError, WeightArena,
                                    slabs_for_config)
from repro.models import build_model, layers as layers_mod, moe as moe_mod

MOE, MLA = "qwen3-moe-235b-a22b", "minicpm3-4b"


def _build(names, dtype="float32", slot_budget=None, slab_bytes=4096,
           page_budget=256, activate=True):
    models = {n: get_smoke_config(n).replace(dtype=dtype) for n in names}
    params = {n: build_model(c).init(jax.random.PRNGKey(i))
              for i, (n, c) in enumerate(models.items())}
    kv_pool, w_pool, pooled = build_pools(
        models, params, page_budget=page_budget, page_bytes=4096,
        pool_dtype=jnp.float32 if dtype == "float32" else jnp.bfloat16,
        slot_budget=slot_budget, slab_bytes=slab_bytes,
        activate_resident=activate)
    return models, params, kv_pool, w_pool, pooled


# ---------------------------------------------------------------------------
# bit-for-bit FFN parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", [MOE, MLA])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_ffn_stage_bit_for_bit(name, dtype):
    """Arena-gathered FFN weights must reproduce the resident-tree FFN
    outputs EXACTLY — the gather/bitcast path may not perturb one bit."""
    models, params, kv_pool, w_pool, pooled = _build((name,), dtype=dtype)
    cfg = models[name]
    pm = pooled[name]
    arena = pm.arena
    table = arena.slot_table(name)
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 1, cfg.d_model),
                          jnp.float32).astype(pm.kv_params["embed"]["tok"].dtype)
    _, w_tree = split_exec.split_params(params[name], cfg)
    for layer in range(cfg.n_layers):
        got = pm.stage_fns.ffn_stage(arena.arena, table, x, layer)
        p_l = jax.tree.map(lambda a, l=layer: a[l], w_tree["layers"])
        if cfg.is_moe:
            want, _ = moe_mod.apply_moe(p_l["moe"], x, cfg)
        else:
            want = layers_mod.apply_mlp(p_l["mlp"], x, cfg.mlp_kind)
        assert np.array_equal(np.asarray(got), np.asarray(want)), \
            f"{name}/{dtype} layer {layer}: arena FFN != resident FFN"


def test_ffn_stage_single_expert_moe():
    """n_experts == 1 keeps its stacked [E=1, ...] expert axis through the
    arena unpacker (apply_moe expects the init_moe layout)."""
    cfg = get_smoke_config(MOE).replace(dtype="float32", n_experts=1,
                                        experts_per_token=1)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    kv_pool, w_pool, pooled = build_pools(
        {cfg.name: cfg}, {cfg.name: params}, page_budget=64,
        page_bytes=4096, pool_dtype=jnp.float32, slab_bytes=4096)[0:3]
    pm = pooled[cfg.name]
    table = pm.arena.slot_table(cfg.name)
    x = jnp.ones((2, 1, cfg.d_model), jnp.float32)
    _, w_tree = split_exec.split_params(params, cfg)
    for layer in range(cfg.n_layers):
        got = pm.stage_fns.ffn_stage(pm.arena.arena, table, x, layer)
        p_l = jax.tree.map(lambda a, l=layer: a[l], w_tree["layers"])
        want, _ = moe_mod.apply_moe(p_l["moe"], x, cfg)
        assert np.array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("lowering", [True, False])
def test_multi_model_arena_decode_matches_dense(lowering):
    """GQA+moe and MLA+dense colocated in ONE arena: paged decode through
    arena-gathered weights matches the dense-cache fused model for both."""
    models, params, kv_pool, w_pool, pooled = _build((MOE, MLA))
    virt = kv_pool.virtualizer
    B, seq, max_len, n_steps = 2, 8, 16, 3
    devs = jax.devices()
    for mi, name in enumerate(models):
        cfg = models[name]
        model = build_model(cfg)
        rng = np.random.default_rng(mi)
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, seq)),
                             jnp.int32)
        cache = model.init_cache(B, max_len)
        _, cache = model.prefill(params[name], tokens, cache)
        rids = (10 * mi, 10 * mi + 1)
        for row, rid in enumerate(rids):
            virt.register_request(rid, name, seq)
            virt.write_prompt_from_cache(name, rid, cache, seq,
                                         batch_index=row)
        view = virt.views[name]
        max_pages = max(1, math.ceil(max_len / view.tokens_per_page))
        step = (PagedFusedStep(pooled[name]) if lowering
                else HostDrivenStep(pooled[name], devs[0], devs[-1]))
        next_tok = jnp.zeros((B,), jnp.int32)
        for t in range(n_steps):
            length = seq + t
            want, cache = model.decode_step(params[name], next_tok, cache,
                                            jnp.int32(length))
            for rid in rids:
                virt.extend_request(rid, 1)
            tables = virt.batch_tables(name, list(rids), max_pages)
            got, virt.pool = step(next_tok, virt.pool, tables,
                                  jnp.full((B,), length, jnp.int32))
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)
            next_tok = jnp.argmax(want, axis=-1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# device bytes fixed by slot_budget; evict/re-activate determinism
# ---------------------------------------------------------------------------

def test_device_ffn_bytes_fixed_by_slot_budget():
    """Arena bytes stay constant as colocated models grow 1 -> 3, and the
    weights pool holds NO per-model device FFN trees."""
    budget = 256
    _, _, _, w_one, _ = _build(PAPER_COLOC_SET[:1], slot_budget=budget)
    _, _, _, w_three, _ = _build(PAPER_COLOC_SET, slot_budget=budget)
    assert w_one.total_bytes() == w_three.total_bytes() \
        == budget * w_one.arena.slab_bytes
    assert w_one.arena.arena.nbytes == w_three.arena.arena.nbytes
    # split models keep ONE host master (the packed slabs), no unpacked
    # device or host FFN tree
    assert not w_three.ffn_params
    assert set(w_three.arena.host_slabs) == set(PAPER_COLOC_SET)


def test_evict_reactivate_reproduces_identical_logits():
    """Masters live on the host, so an evict/re-activate round trip must be
    bit-for-bit invisible to decode."""
    models, params, kv_pool, w_pool, pooled = _build((MOE, MLA))
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    name, cfg = MOE, models[MOE]
    model = build_model(cfg)
    B, seq, max_len = 2, 8, 16
    tokens = jnp.zeros((B, seq), jnp.int32)
    cache = model.init_cache(B, max_len)
    _, cache = model.prefill(params[name], tokens, cache)
    for rid in (0, 1):
        virt.register_request(rid, name, seq)
        virt.write_prompt_from_cache(name, rid, cache, seq, batch_index=rid)
        virt.extend_request(rid, 1)
    view = virt.views[name]
    max_pages = max(1, math.ceil(max_len / view.tokens_per_page))
    tables = virt.batch_tables(name, [0, 1], max_pages)
    step = PagedFusedStep(pooled[name])
    pool0 = virt.pool
    lengths = jnp.full((B,), seq, jnp.int32)
    next_tok = jnp.zeros((B,), jnp.int32)

    # the step donates its pool: thread the returned buffer through
    logits1, pool1 = step(next_tok, pool0, tables, lengths)
    rev1 = arena.residency[name].rev
    arena.evict(name)                    # both models idle -> evictable
    arena.evict(MLA)
    assert not arena.is_resident(name)
    assert arena.free_slabs == arena.slot_budget
    arena.activate(MLA)                  # reshuffle the free list
    arena.activate(name)                 # re-upload from host masters
    assert arena.residency[name].rev != rev1
    logits2, _ = step(next_tok, pool1, tables, lengths)
    assert np.array_equal(np.asarray(logits1), np.asarray(logits2))


def test_lru_eviction_respects_pins():
    """Activation under slab pressure evicts the LRU idle model, never a
    pinned one; an impossible activation raises without evicting."""
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in PAPER_COLOC_SET}
    params = {n: build_model(c).init(jax.random.PRNGKey(i))
              for i, (n, c) in enumerate(models.items())}
    trees = {n: split_exec.split_params(params[n], c)[1]
             for n, c in models.items()}
    slabs = {n: None for n in models}
    arena = WeightArena(slab_bytes=4096)
    for n, c in models.items():
        arena.add_model(n, c, jax.tree.map(np.asarray, trees[n]))
        slabs[n] = arena.views[n].total_slabs
    a, b, c = PAPER_COLOC_SET
    # budget: the two big MoE models cannot be resident together
    arena.finalize(max(slabs[a], slabs[b]) + slabs[c], allocate=False)
    arena.activate(a)
    arena.activate(c)
    arena.pin(c)
    arena.activate(b)                    # must evict idle a, not pinned c
    assert arena.is_resident(b) and arena.is_resident(c)
    assert not arena.is_resident(a)
    arena.pin(b)
    with pytest.raises(OutOfSlabsError):
        arena.activate(a)                # everything else pinned
    assert arena.is_resident(b) and arena.is_resident(c)
    arena.unpin(b)
    arena.activate(a)                    # now b is the LRU victim
    assert arena.is_resident(a) and not arena.is_resident(b)


# ---------------------------------------------------------------------------
# property: atomic map/evict under OutOfSlabsError
# ---------------------------------------------------------------------------

_PROP_STATE = {}


def _prop_trees():
    if not _PROP_STATE:
        models = {n: get_smoke_config(n).replace(dtype="float32")
                  for n in PAPER_COLOC_SET}
        params = {n: build_model(c).init(jax.random.PRNGKey(i))
                  for i, (n, c) in enumerate(models.items())}
        _PROP_STATE["models"] = models
        _PROP_STATE["trees"] = {
            n: jax.tree.map(np.asarray,
                            split_exec.split_params(params[n], c)[1])
            for n, c in models.items()}
    return _PROP_STATE["models"], _PROP_STATE["trees"]


def _snapshot(arena):
    return (sorted(arena.free_list),
            {n: r.slots.copy() for n, r in arena.residency.items()},
            dict(arena.pins))


def _check_invariants(arena, budget):
    assigned = [int(s) for r in arena.residency.values()
                for s in r.slots.ravel()]
    assert len(assigned) == len(set(assigned)), "double-mapped slab"
    assert len(assigned) + arena.free_slabs == budget, "slab leak"
    for n, r in arena.residency.items():
        v = arena.views[n]
        assert r.slots.shape == (v.n_layers, v.slabs_per_layer)
        assert r.slots.min() >= 0 and r.slots.max() < budget


@settings(max_examples=25, deadline=None)
@given(ops=st.lists(
    st.tuples(st.sampled_from(["activate", "evict", "pin", "unpin"]),
              st.sampled_from(list(PAPER_COLOC_SET))),
    min_size=1, max_size=40))
def test_property_atomic_map_evict_no_leaks(ops):
    """Random activate/evict/pin interleavings over a budget too small for
    full residency: no slab is ever double-mapped or leaked, and an op
    that raises leaves the arena state EXACTLY as it was."""
    models, trees = _prop_trees()
    arena = WeightArena(slab_bytes=4096)
    for n, c in models.items():
        arena.add_model(n, c, trees[n])
    sizes = sorted(v.total_slabs for v in arena.views.values())
    budget = sizes[-1] + sizes[0]         # biggest + smallest, not all three
    arena.finalize(budget, allocate=False)
    for op, name in ops:
        before = _snapshot(arena)
        try:
            if op == "activate":
                arena.activate(name)
            elif op == "evict":
                if arena.is_resident(name):
                    arena.evict(name)
            elif op == "pin":
                if arena.is_resident(name):
                    arena.pin(name)
            else:
                arena.unpin(name)
        except (OutOfSlabsError, ValueError):
            after = _snapshot(arena)
            assert after[0] == before[0], "failed op changed the free list"
            assert after[2] == before[2], "failed op changed pins"
            assert after[1].keys() == before[1].keys()
            for n in after[1]:
                assert np.array_equal(after[1][n], before[1][n]), \
                    "failed op moved a resident model's slabs"
        _check_invariants(arena, budget)


# ---------------------------------------------------------------------------
# split_params / merge_params round trip (the residency boundary)
# ---------------------------------------------------------------------------

def _paths(tree, prefix=()):
    out = set()
    for k, v in tree.items():
        if isinstance(v, dict):
            out |= _paths(v, prefix + (k,))
        else:
            out.add(prefix + (k,))
    return out


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_split_merge_roundtrip_all_configs(name):
    """Leaf-exact round trip over EVERY assigned arch; the two halves are
    disjoint and jointly exhaustive — what arena residency accounting
    (host masters vs kv params) relies on."""
    cfg = get_smoke_config(name)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    kv_t, w_t = split_exec.split_params(params, cfg)
    assert not (_paths(kv_t) & _paths(w_t)), "leaf present in both pools"
    assert (_paths(kv_t) | _paths(w_t)) == _paths(params), "leaf dropped"
    merged = split_exec.merge_params(kv_t, w_t)
    assert jax.tree.structure(merged) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(params)):
        assert a is b, "round trip must be leaf-identical, not a copy"


_key = st.sampled_from(["mlp", "moe", "attn", "ln1", "embed", "wg", "head"])


@st.composite
def _trees(draw, depth=0):
    n = draw(st.integers(1, 3))
    out = {}
    for _ in range(n):
        k = draw(_key)
        if depth < 2 and draw(st.booleans()):
            out[k] = draw(_trees(depth=depth + 1))
        else:
            out[k] = np.arange(draw(st.integers(1, 4)), dtype=np.float32)
    return out


@settings(max_examples=50, deadline=None)
@given(tree=_trees())
def test_property_split_merge_roundtrip_random_trees(tree):
    cfg = get_smoke_config(MOE)          # split_params keys off paths only
    kv_t, w_t = split_exec.split_params(tree, cfg)
    assert not (_paths(kv_t) & _paths(w_t))
    assert (_paths(kv_t) | _paths(w_t)) == _paths(tree)
    merged = split_exec.merge_params(kv_t, w_t)
    assert _paths(merged) == _paths(tree)
    for p in _paths(tree):
        a, b = tree, merged
        for k in p:
            a, b = a[k], b[k]
        assert a is b


# ---------------------------------------------------------------------------
# streaming prefetch + engine-level activation/eviction
# ---------------------------------------------------------------------------

def test_pipeline_streaming_prefetch_matches_eager_upload():
    """activate(upload=False) + the scheduler's layer prefetch must produce
    the same logits as an eagerly uploaded arena."""
    from repro.core.pipeline import InflightBatch, LayerPipelineScheduler
    models, params, kv_pool, w_pool, pooled = _build((MLA,))
    name, cfg = MLA, models[MLA]
    model = build_model(cfg)
    virt = kv_pool.virtualizer
    arena = w_pool.arena
    B, seq, max_len = 2, 8, 16
    devs = jax.devices()

    def make_batch(bid, base):
        tokens = jnp.zeros((B, seq), jnp.int32)
        cache = model.init_cache(B, max_len)
        _, cache = model.prefill(params[name], tokens, cache)
        rids = (base, base + 1)
        for row, rid in enumerate(rids):
            virt.register_request(rid, name, seq)
            virt.write_prompt_from_cache(name, rid, cache, seq,
                                         batch_index=row)
            virt.extend_request(rid, 1)
        view = virt.views[name]
        max_pages = max(1, math.ceil(max_len / view.tokens_per_page))
        return InflightBatch(
            batch_id=bid, model=name, tokens=jnp.zeros((B,), jnp.int32),
            page_tables=virt.batch_tables(name, list(rids), max_pages),
            lengths=jnp.full((B,), seq, jnp.int32))

    sched = LayerPipelineScheduler(pooled, devs[0], devs[-1])
    eager, virt.pool = sched.run([make_batch(0, 0)], virt.pool)

    arena.evict(name)                    # back to cold
    arena.activate(name, upload=False)   # slots mapped, nothing uploaded
    assert not arena.residency[name].uploaded.any()
    uploads_before = arena.layer_uploads
    sched2 = LayerPipelineScheduler(pooled, devs[0], devs[-1])
    streamed, virt.pool = sched2.run([make_batch(1, 10)], virt.pool)
    assert arena.residency[name].uploaded.all()
    assert arena.layer_uploads - uploads_before == cfg.n_layers
    assert np.array_equal(np.asarray(eager[0].logits),
                          np.asarray(streamed[0].logits))


def test_engine_cold_activation_and_eviction():
    """Two models served far apart in time through a one-model arena: the
    engine activates on demand, evicts the idle model, and the report
    surfaces per-model admission counters."""
    from repro.runtime.engine import CrossPoolEngine, EngineMode
    from repro.runtime.request import Request
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in (MOE, MLA)}
    need = {n: slabs_for_config(c.replace(dtype="float32"), 4096)
            for n, c in models.items()}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64, mode=EngineMode(pipeline=True,
                                                 lowering=True))
    reqs = [Request(request_id=0, model=MOE, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=0.0),
            Request(request_id=1, model=MLA, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=10_000.0)]
    stats = engine.run(reqs)
    assert stats.tokens_out > 0
    w = stats.weights_pool
    assert w["activations"] >= 2 and w["evictions"] >= 1
    assert engine.arena.is_resident(MLA) and not engine.arena.is_resident(MOE)
    rep = engine.report()
    assert MOE in rep and "admitted=1" in rep and "evictions" in rep
    assert stats.admission.per_model[MOE].admitted == 1


def test_engine_overlapping_requests_wait_out_arena_pressure():
    """Two models arriving together through a one-model arena: the second
    request WAITS while the first model is pinned (no crash), then serves
    after the first drains and is evicted."""
    from repro.runtime.engine import CrossPoolEngine, EngineMode
    from repro.runtime.request import Request
    models = {n: get_smoke_config(n).replace(dtype="float32")
              for n in (MOE, MLA)}
    need = {n: slabs_for_config(c, 4096) for n, c in models.items()}
    engine = CrossPoolEngine(
        models, page_budget=2048, page_bytes=4096,
        slot_budget=max(need.values()), slab_bytes=4096,
        max_batch=2, max_ctx=64, mode=EngineMode(pipeline=True,
                                                 lowering=True))
    reqs = [Request(request_id=0, model=MOE, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=0.0),
            Request(request_id=1, model=MLA, prompt_tokens=8,
                    max_new_tokens=3, arrival_time=0.0)]
    stats = engine.run(reqs)
    assert all(r.finish_time > 0 for r in reqs), "a request was dropped"
    assert stats.weights_pool["evictions"] >= 1
    assert not engine.arena.pins                  # all pins released
