"""Public jit'd wrappers for the kernel layer.

Implementation selection:
  * ``xla``     — pure-jnp reference (ref.py).  Default; used by the
                  distributed dry-run so cost_analysis sees real FLOPs.
  * ``pallas``  — pl.pallas_call TPU kernels: compiled on a TPU, run in
                  interpret mode elsewhere (``resolve_interpret``).

Select globally via :func:`set_default_impl` or per-call via ``impl=``.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ref

_DEFAULT_IMPL = "xla"


def set_default_impl(impl: str) -> None:
    global _DEFAULT_IMPL
    assert impl in ("xla", "pallas")
    _DEFAULT_IMPL = impl


def get_default_impl() -> str:
    return _DEFAULT_IMPL


def _resolve(impl: Optional[str]) -> str:
    return impl or _DEFAULT_IMPL


# --- flash attention -------------------------------------------------------

def flash_attention(q, k, v, *, scale: float, impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import flash_attention as fk
        return fk.flash_attention(q, k, v, scale=scale)
    return ref.flash_attention(q, k, v, scale)


# --- decode attention ------------------------------------------------------

def decode_attention(q, cache_k, cache_v, lengths, *, scale: float,
                     impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import paged_attention as pk
        return pk.contiguous_decode_attention(q, cache_k, cache_v, lengths,
                                              scale=scale)
    return ref.decode_attention(q, cache_k, cache_v, lengths, scale)


def _typed_pages(pool, page_table, page_shape, pallas: bool):
    """The pool's pages typed as ``page_shape`` (scope ``kv_view``).

    XLA: the table's pages are gathered from the untyped pool first
    (``[B, max_pages, page_elems]``, read under ``attention``, as the
    gather inside the attention always was), and only that block is
    sliced and reshaped: nothing copies or relayouts the whole
    ``[n_pages, page_elems]`` pool.  Pallas: the kernels follow the table
    themselves, so they take the whole pool typed in place.
    """
    n = math.prod(page_shape)
    if pallas:
        with jax.named_scope("kv_view"):
            return pool[:, :n].reshape(pool.shape[0], *page_shape)
    with jax.named_scope("attention"):
        pages = ref.gather_pages(pool, page_table)
    with jax.named_scope("kv_view"):
        return pages[..., :n].reshape(*page_table.shape, *page_shape)


def paged_decode_attention(q, pool, page_table, lengths, *, page_shape,
                           scale: float, impl: Optional[str] = None):
    """GQA decode attention over the untyped pool through a page table.

    pool: [n_pages, page_elems]; page_shape: ``(page_size, 2, KV, D)``,
    one typed page; page_table: [B, max_pages] (-1 = unmapped).
    """
    pallas = _resolve(impl) == "pallas"
    kv = _typed_pages(pool, page_table, page_shape, pallas)
    with jax.named_scope("attention"):
        if pallas:
            from repro.kernels import paged_attention as pk
            return pk.paged_decode_attention(q, kv, page_table, lengths,
                                             scale=scale)
        return ref.gathered_decode_attention(q, kv, lengths, scale)


def paged_mla_decode_attention(q, pool, page_table, lengths, *, page_shape,
                               latent_dim: int, scale: float,
                               impl: Optional[str] = None):
    """Absorbed-MLA decode attention over the untyped pool through a page
    table; page_shape: ``(page_size, r + rp)``, one typed page."""
    pallas = _resolve(impl) == "pallas"
    kv = _typed_pages(pool, page_table, page_shape, pallas)
    with jax.named_scope("attention"):
        if pallas:
            from repro.kernels import paged_attention as pk
            return pk.paged_mla_decode_attention(
                q, kv, page_table, lengths, latent_dim=latent_dim,
                scale=scale)
        return ref.gathered_mla_decode_attention(q, kv, lengths, latent_dim,
                                                 scale)


# --- paged KV write (window scatter; pure-jnp, no Pallas variant) -----------

def paged_kv_write(pool, kv_flat, pages, slots):
    """Write per-token KV rows into the flat page pool.

    pool:    [n_pages, page_elems]  the shared physical pool
    kv_flat: [n, per_token_elems]   one row per token (one layer's K+V,
                                    or MLA latent+rope)
    pages:   [n] int32 physical page ids (< 0 = drop the row)
    slots:   [n] int32 token slot within the page

    Returns the updated pool.  Each row is one window of ``e`` elements at
    ``(page, slot * e)`` of a window scatter; rows sharing a page (a
    prompt's) fill disjoint windows of it.  Rows whose page id is negative
    (unmapped / inactive batch slots) go to the out-of-range page
    ``n_pages`` and are dropped whole, so callers can pass a full
    fixed-size batch without masking on the host.

    On the TPU the window scatter lowers to a loop of in-place
    dynamic-update-slices on the pool's own tiled layout: the donated pool
    is neither copied nor relayouted, and a write moves its ``n`` rows.
    The element scatter it replaces (``pool.at[rows, cols]``, one index per
    element, no window) was lowered on a flat 1-D copy of the pool and
    reshaped back: two relayouts of the whole pool per call.
    """
    n_pages = pool.shape[0]
    e = kv_flat.shape[-1]
    rows = pages.astype(jnp.int32)
    rows = jnp.where(rows >= 0, rows, n_pages)
    starts = jnp.stack([rows, slots.astype(jnp.int32) * e], axis=-1)
    dims = lax.ScatterDimensionNumbers(update_window_dims=(1,),
                                       inserted_window_dims=(0,),
                                       scatter_dims_to_operand_dims=(0, 1))
    return lax.scatter(pool, starts, kv_flat.astype(pool.dtype), dims,
                       mode=lax.GatherScatterMode.FILL_OR_DROP)


# --- grouped expert GEMM ---------------------------------------------------

def moe_gemm(x, w, group_sizes, *, impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import moe_gemm as mk
        return mk.moe_gemm(x, w, group_sizes)
    return ref.moe_gemm(x, w, group_sizes)


# --- Mamba2 SSD ------------------------------------------------------------

def ssd_scan(x, dt, A, B_, C_, *, chunk: int = 64, h0=None,
             impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import ssd_scan as sk
        return sk.ssd_scan(x, dt, A, B_, C_, chunk=chunk, h0=h0)
    from repro.kernels.ssd_chunked import ssd_scan_chunked
    return ssd_scan_chunked(x, dt, A, B_, C_, chunk=chunk, h0=h0)
