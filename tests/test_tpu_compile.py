"""Compiles of the served path for a described (not attached) TPU v5e.

The TPU compiler is installed with jax, so the decode stage programs and
kernels are compiled at PUBLISHED widths for one v5e chip without a chip:
what the chip's compiler refuses, or a program whose scratch would not fit,
fails here.  Nothing runs, so nothing here is a time or a result.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compilation cache is off around the compiles
(an entry written for a described chip cannot be read back without one).
"""
import math
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import split_exec, weight_pool
from repro.core.virtualizer import KVVirtualizer
from repro.kernels import paged_attention as pk
from repro.kernels.ops import paged_kv_write
from repro.models import build_model

MOONSHOT, MINICPM = "moonshot-v1-16b-a3b", "minicpm3-4b"
PAGE_BYTES = 64 * 1024          # the chip smoke's page: 8 moonshot tokens
B, MAX_PAGES = 8, 16            # decode batch, pages per request per layer


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no TPU lib"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _one_layer(name, sharding, page_budget=B * MAX_PAGES):
    """Stage fns + sharded shapes of ONE layer at published widths."""
    cfg = get_config(name).replace(n_layers=1)
    kv_shapes, w_shapes = split_exec.split_params(
        jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0)), cfg)
    view = weight_pool.build_view(name, cfg, w_shapes,
                                  slab_bytes=weight_pool.DEFAULT_SLAB_BYTES)
    virt = KVVirtualizer({name: cfg}, page_budget=page_budget,
                         page_bytes=PAGE_BYTES, allocate_device_pool=False)
    fns = split_exec.make_stage_fns(cfg, virt.views[name], view)
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    pool = S((virt.page_budget, virt.page_elems), jnp.bfloat16)
    return cfg, fns, view, _on(sharding, kv_shapes), pool, S


def _ffn_bytes(view) -> int:
    """Device bytes of one layer's FFN weights as the arena holds them."""
    return sum(leaf.rows * weight_pool.ARENA_LANES * 2 * u.count
               for u in view.units for leaf in u.leaves)


@pytest.mark.parametrize("name", [MOONSHOT, MINICPM])
def test_ffn_stage_compiles_within_twice_its_weights(name, one_chip):
    """One layer's decode FFN stage unpacks its weights from the arena.
    Its scratch stays within twice the layer's FFN bytes, and it compiles
    in seconds (a byte-level unpack of the 64-expert layer took minutes
    and 4 GB of scratch)."""
    cfg, fns, view, _, _, S = _one_layer(name, one_chip)
    args = (S((view.total_slabs, view.slab_rows, weight_pool.ARENA_LANES),
              weight_pool.ARENA_WORD),
            S((1, view.slabs_per_layer), jnp.int32),
            S((B, 1, cfg.d_model), jnp.bfloat16), S((), jnp.int32))
    t0 = time.perf_counter()
    compiled = jax.jit(fns.ffn_stage).lower(*args).compile()
    seconds = time.perf_counter() - t0
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 2 * _ffn_bytes(view), (temp, _ffn_bytes(view))
    assert seconds < 120, seconds


@pytest.mark.parametrize("name", [MOONSHOT, MINICPM])
def test_attn_stage_compiles(name, one_chip):
    cfg, fns, _, kv_params, pool, S = _one_layer(name, one_chip)
    args = (kv_params, S((B, 1, cfg.d_model), jnp.bfloat16), pool,
            S((1, B, MAX_PAGES), jnp.int32), S((B,), jnp.int32),
            S((), jnp.int32))
    jax.jit(fns.attn_stage, donate_argnums=2).lower(*args).compile()


#: The served pool: 16384 pages of 64 KiB, 1 GiB of bf16.
POOL_PAGES = 16384
#: Instructions that copy or relayout what they produce (``copy-done``
#: ends an asynchronous copy).
_RELAYOUT = re.compile(r"= \w+\[([\d,]*)\]\S* "
                       r"(copy|copy-done|reshape|slice|transpose|fusion"
                       r"|scatter)\(")


def _whole_pool_relayouts(text: str, e: int) -> list:
    """Instructions of a compiled program that copy, relayout or scatter a
    result as large as the pool's typed tokens (every page's ``e``-element
    rows): the pool's own in-place update (``dynamic-update-slice``) and
    the buffer passed through (parameters, tuples) are not counted."""
    typed = POOL_PAGES * (PAGE_BYTES // 2 // e) * e
    return [line.strip()[:160] for line in text.splitlines()
            if (m := _RELAYOUT.search(line))
            and math.prod(int(d) for d in m.group(1).split(",") if d)
            >= typed]


def _attn_stage_1gib(name, sharding):
    """One layer's decode attention stage over the 1 GiB pool, B = 16."""
    cfg, fns, _, kv_params, pool, S = _one_layer(name, sharding, POOL_PAGES)
    args = (kv_params, S((16, 1, cfg.d_model), jnp.bfloat16), pool,
            S((1, 16, MAX_PAGES), jnp.int32), S((16,), jnp.int32),
            S((), jnp.int32))
    e = (cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
         if cfg.attention == "mla" else 2 * cfg.n_kv_heads * cfg.head_dim)
    return jax.jit(fns.attn_stage, donate_argnums=2), args, e


def _kv_write(n, e, sharding):
    """``paged_kv_write`` of ``n`` rows of ``e`` elements into the pool."""
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    args = (S((POOL_PAGES, PAGE_BYTES // 2), jnp.bfloat16),
            S((n, e), jnp.bfloat16), S((n,), jnp.int32), S((n,), jnp.int32))
    return jax.jit(paged_kv_write, donate_argnums=0), args, e


@pytest.mark.parametrize("case", [
    lambda sh: _attn_stage_1gib(MOONSHOT, sh),
    lambda sh: _attn_stage_1gib(MINICPM, sh),
    lambda sh: _kv_write(16, 288, sh),          # a decode step's rows (MLA)
    lambda sh: _kv_write(512, 2 * 16 * 128, sh),  # a moonshot prompt's layer
], ids=["attn-stage-moonshot", "attn-stage-minicpm3", "kv-write-b16",
        "kv-write-n512"])
def test_pool_is_never_relayouted(case, one_chip):
    """At the served 1 GiB pool, the decode attention stage and the KV
    write compile with no copy, reshape, slice, transpose, fusion or
    scatter of the whole pool: the write updates it in place and the
    attention types only the pages it gathered."""
    fn, args, e = case(one_chip)
    text = fn.lower(*args).compile().as_text()
    assert _whole_pool_relayouts(text, e) == []


@pytest.mark.parametrize("page_size", [8, 32])
def test_pallas_paged_gqa_kernel_compiles(page_size, one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    H = KV = 16
    D = 128
    args = (S((B, 1, H, D), jnp.bfloat16),
            S((256, page_size, 2, KV, D), jnp.bfloat16),
            S((B, MAX_PAGES), jnp.int32), S((B,), jnp.int32))
    fn = lambda q, kv, pt, ln: pk.paged_decode_attention(
        q, kv, pt, ln, scale=D ** -0.5, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_pallas_paged_mla_kernel_compiles(one_chip):
    S = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    mla = get_config(MINICPM).mla
    H, r = 40, mla.kv_lora_rank
    e = r + mla.qk_rope_head_dim
    args = (S((B, 1, H, e), jnp.bfloat16), S((256, 32, e), jnp.bfloat16),
            S((B, MAX_PAGES), jnp.int32), S((B,), jnp.int32))
    fn = lambda q, kv, pt, ln: pk.paged_mla_decode_attention(
        q, kv, pt, ln, latent_dim=r, scale=e ** -0.5, interpret=False)
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
