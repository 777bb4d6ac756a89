"""Pallas TPU decode attention: contiguous and paged (virtualized) KV.

``paged_decode_attention`` is the KV-cache-pool hot loop: attention reads
K/V through a *page table*, the TPU-native analogue of the paper's CUDA-VMM
virtualized paging (DESIGN.md §2).  The page table is passed as a
**scalar-prefetch** operand (``pltpu.PrefetchScalarGridSpec``) so the
``kv_pages`` BlockSpec index_map can select the physical page for each grid
step — indirection happens at the DMA level, not as a gather in the compute.

Grid: ``(batch, page_blocks)``, page dimension sequential, online-softmax
state in VMEM scratch across pages of one request.

``repro.kernels.ops.paged_kv_write`` is the matching write-side primitive:
one window scatter that lands ``n`` token rows at their (page, slot)
coordinates in the flat pool, in place on the donated buffer.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Contiguous decode attention (cache [B,T,KV,D], per-row lengths)
# ---------------------------------------------------------------------------

def _decode_kernel(lengths_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, block_t: int, n_kv: int):
    b = pl.program_id(0)
    t = pl.program_id(1)
    nt = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(t * block_t < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [H, D]
        k = k_ref[0].astype(jnp.float32)                     # [bt, KV, D]
        v = v_ref[0].astype(jnp.float32)
        H, D = q.shape
        G = H // n_kv
        qg = q.reshape(n_kv, G, D)
        t_valid = (t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (block_t, 1, 1), 0)) < length
        v = jnp.where(t_valid, v, 0.0)   # 0 * OOB-garbage guard
        s = jnp.einsum("kgd,tkd->kgt", qg, k)                # [KV,G,bt]
        pos = t * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, G, block_t), 2)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:, :, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))     # [KV,G]
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[..., None])                    # [KV,G,bt]
        l_ref[:, :, 0] = l_ref[:, :, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jnp.einsum("kgt,tkd->kgd", p, v))
        m_ref[:, :, 0] = m_cur

    @pl.when(t == nt - 1)
    def _finish():
        l = l_ref[:, :, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / safe[..., None]                 # [KV,G,D]
        H = out.shape[0] * out.shape[1]
        o_ref[0, 0] = out.reshape(H, -1).astype(o_ref.dtype)


def contiguous_decode_attention(q: jax.Array, cache_k: jax.Array,
                                cache_v: jax.Array, lengths: jax.Array, *,
                                scale: float, block_t: int = 256,
                                interpret: Optional[bool] = None) -> jax.Array:
    """q: [B,1,H,D]; cache: [B,T,KV,D]; lengths: [B] -> [B,1,H,D]."""
    B, _, H, D = q.shape
    T, KV = cache_k.shape[1], cache_k.shape[2]
    block_t = min(block_t, T)
    nt = pl.cdiv(T, block_t)
    # fold scale into q once (cheaper than per-block multiply)
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    kernel = functools.partial(_decode_kernel, block_t=block_t, n_kv=KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nt),
        in_specs=[
            pl.BlockSpec((1, 1, H, D), lambda b, t, L: (b, 0, 0, 0)),
            pl.BlockSpec((1, block_t, KV, D), lambda b, t, L: (b, t, 0, 0)),
            pl.BlockSpec((1, block_t, KV, D), lambda b, t, L: (b, t, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, D), lambda b, t, L: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, H // KV, D), jnp.float32),
            pltpu.VMEM((KV, H // KV, 128), jnp.float32),
            pltpu.VMEM((KV, H // KV, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(lengths.astype(jnp.int32), qs, cache_k, cache_v)


# ---------------------------------------------------------------------------
# Paged decode attention (page-table indirection via scalar prefetch)
# ---------------------------------------------------------------------------

def _paged_kernel(page_table_ref, lengths_ref, q_ref, pages_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page_size: int, n_kv: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mapped = page_table_ref[b, p] >= 0

    @pl.when((p * page_size < length) & mapped)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [H, D]
        kv = pages_ref[0].astype(jnp.float32)                # [ps, 2, KV, D]
        k, v = kv[:, 0], kv[:, 1]                            # [ps, KV, D]
        H, D = q.shape
        G = H // n_kv
        qg = q.reshape(n_kv, G, D)
        t_valid = (p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1, 1), 0)) < length
        v = jnp.where(t_valid, v, 0.0)   # 0 * OOB-garbage guard
        s = jnp.einsum("kgd,tkd->kgt", qg, k)
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (n_kv, G, page_size), 2)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[:, :, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        pmat = jnp.exp(s - m_cur[..., None])
        l_ref[:, :, 0] = l_ref[:, :, 0] * alpha + jnp.sum(pmat, axis=-1)
        acc_ref[...] = (acc_ref[...] * alpha[..., None]
                        + jnp.einsum("kgt,tkd->kgd", pmat, v))
        m_ref[:, :, 0] = m_cur

    @pl.when(p == np_ - 1)
    def _finish():
        l = l_ref[:, :, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        out = acc_ref[...] / safe[..., None]
        H = out.shape[0] * out.shape[1]
        o_ref[0, 0] = out.reshape(H, -1).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, kv_pages: jax.Array,
                           page_table: jax.Array, lengths: jax.Array, *,
                           scale: float, interpret: Optional[bool] = None) -> jax.Array:
    """Decode attention through the virtualizer's page table.

    q:          [B,1,H,D]
    kv_pages:   [N_pages, page_size, 2, KV, D]  (physical pool)
    page_table: [B, max_pages] int32, -1 = unmapped
    lengths:    [B]
    """
    B, _, H, D = q.shape
    page_size, KV = kv_pages.shape[1], kv_pages.shape[3]
    max_pages = page_table.shape[1]
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    kernel = functools.partial(_paged_kernel, page_size=page_size, n_kv=KV)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, H, D), lambda b, p, pt, L: (b, 0, 0, 0)),
            # physical page selected via the prefetched page table — the DMA
            # engine follows the indirection, not the compute.
            pl.BlockSpec((1, page_size, 2, KV, D),
                         lambda b, p, pt, L: (jnp.maximum(pt[b, p], 0), 0, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, D), lambda b, p, pt, L: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, H // KV, D), jnp.float32),
            pltpu.VMEM((KV, H // KV, 128), jnp.float32),
            pltpu.VMEM((KV, H // KV, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qs, kv_pages)


# ---------------------------------------------------------------------------
# Paged MLA decode attention (absorbed form over [latent | rope] pages)
# ---------------------------------------------------------------------------
#
# In the absorbed MLA decode the per-token cache row is the concatenation
# [latent (r) | rope key (rp)], and with the absorbed query
# q = [q_lat | q_rope] the scores are a single dot product against the full
# row while the value is the latent prefix alone:
#
#   s(t)   = q_lat . latent_t + q_rope . rope_t = q . kv_t
#   ctx    = softmax(s) @ latent
#
# so one untyped page layout [ps, r + rp] serves both reads.

def _paged_mla_kernel(page_table_ref, lengths_ref, q_ref, pages_ref, o_ref,
                      acc_ref, m_ref, l_ref, *, page_size: int,
                      latent_dim: int):
    b = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)
    length = lengths_ref[b]

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    mapped = page_table_ref[b, p] >= 0

    @pl.when((p * page_size < length) & mapped)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)                  # [H, r+rp]
        kv = pages_ref[0].astype(jnp.float32)                # [ps, r+rp]
        v = kv[:, :latent_dim]                               # [ps, r]
        pos_t = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)
        v = jnp.where(pos_t < length, v, 0.0)   # 0 * OOB-garbage guard
        s = q @ kv.T                                         # [H, ps]
        pos_s = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page_size), 1)
        s = jnp.where(pos_s < length, s, NEG_INF)

        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))     # [H]
        alpha = jnp.exp(m_prev - m_cur)
        pmat = jnp.exp(s - m_cur[:, None])                   # [H, ps]
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(pmat, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pmat @ v
        m_ref[:, 0] = m_cur

    @pl.when(p == np_ - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def paged_mla_decode_attention(q: jax.Array, kv_pages: jax.Array,
                               page_table: jax.Array, lengths: jax.Array, *,
                               latent_dim: int, scale: float,
                               interpret: Optional[bool] = None) -> jax.Array:
    """Absorbed-MLA decode attention through the virtualizer's page table.

    q:          [B,1,H, r+rp]  absorbed query [q_latent | q_rope]
    kv_pages:   [N_pages, page_size, r+rp]  (physical pool, typed view)
    page_table: [B, max_pages] int32, -1 = unmapped
    lengths:    [B]
    Returns the latent context [B,1,H,r]; the caller applies W_uv / W_o.
    """
    B, _, H, e = q.shape
    page_size = kv_pages.shape[1]
    max_pages = page_table.shape[1]
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)

    kernel = functools.partial(_paged_mla_kernel, page_size=page_size,
                               latent_dim=latent_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, H, e), lambda b, p, pt, L: (b, 0, 0, 0)),
            pl.BlockSpec((1, page_size, e),
                         lambda b, p, pt, L: (jnp.maximum(pt[b, p], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, latent_dim),
                               lambda b, p, pt, L: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, latent_dim), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
            pltpu.VMEM((H, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, H, latent_dim), q.dtype),
        interpret=resolve_interpret(interpret),
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qs, kv_pages)
