"""Public jit'd wrappers for the kernel layer.

Implementation selection:
  * ``xla``     — pure-jnp reference (ref.py).  Default; used by the
                  distributed dry-run so cost_analysis sees real FLOPs.
  * ``pallas``  — pl.pallas_call TPU kernels: compiled on a TPU, run in
                  interpret mode elsewhere (``resolve_interpret``).

Select globally via :func:`set_default_impl` or per-call via ``impl=``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

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


def paged_decode_attention(q, kv_pages, page_table, lengths, *, scale: float,
                           impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import paged_attention as pk
        return pk.paged_decode_attention(q, kv_pages, page_table, lengths,
                                         scale=scale)
    return ref.paged_decode_attention(q, kv_pages, page_table, lengths, scale)


def paged_mla_decode_attention(q, kv_pages, page_table, lengths, *,
                               latent_dim: int, scale: float,
                               impl: Optional[str] = None):
    if _resolve(impl) == "pallas":
        from repro.kernels import paged_attention as pk
        return pk.paged_mla_decode_attention(
            q, kv_pages, page_table, lengths, latent_dim=latent_dim,
            scale=scale)
    return ref.paged_mla_decode_attention(q, kv_pages, page_table, lengths,
                                          latent_dim, scale)


# --- paged KV write (pool scatter; pure-jnp, no Pallas variant) ------------

def paged_kv_write(pool, kv_flat, pages, slots):
    """Scatter per-token KV rows into the flat page pool.

    pool:    [n_pages, page_elems]  the shared physical pool
    kv_flat: [n, per_token_elems]   one row per token (one layer's K+V,
                                    or MLA latent+rope)
    pages:   [n] int32 physical page ids (< 0 = drop the row)
    slots:   [n] int32 token slot within the page

    Returns the updated pool.  Rows whose page id is negative (unmapped /
    inactive batch slots) are dropped by the scatter, so callers can pass
    a full fixed-size batch without masking on the host.  One XLA scatter;
    jit- and donation-friendly (the pool aliases in place under jit).

    Indices are 2-D (page row, element column) rather than flattened, so
    they stay far inside int32 range even for pools past 2^31 elements.
    """
    n_pages, page_elems = pool.shape
    e = kv_flat.shape[-1]
    rows = pages.astype(jnp.int32)
    # out-of-range sentinel for unmapped rows -> dropped by mode="drop"
    rows = jnp.where(rows >= 0, rows, n_pages)
    cols = ((slots.astype(jnp.int32) * e)[:, None]
            + jnp.arange(e, dtype=jnp.int32)[None, :])
    return pool.at[rows[:, None], cols].set(
        kv_flat.astype(pool.dtype), mode="drop")


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
