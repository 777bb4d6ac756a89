"""Pure-jnp oracles for every Pallas kernel.

These are the ground truth for the kernel allclose sweeps AND the
implementation used by the distributed dry-run (XLA-visible FLOPs for the
roofline; Pallas calls are opaque to ``cost_analysis``).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Flash attention (prefill): causal GQA
# ---------------------------------------------------------------------------

def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    scale: float) -> jax.Array:
    """Causal grouped attention. q:[B,S,H,D], k/v:[B,T,KV,D] -> [B,S,H,D]."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    qg = q.reshape(B, S, KV, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    q_pos = jnp.arange(S)[:, None]
    k_pos = jnp.arange(T)[None, :]
    mask = (q_pos + (T - S)) >= k_pos           # causal with prefix offset
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, S, H, D)


# ---------------------------------------------------------------------------
# Decode attention over a contiguous cache with per-row lengths
# ---------------------------------------------------------------------------

def decode_attention(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                     lengths: jax.Array, scale: float) -> jax.Array:
    """q:[B,1,H,D]; cache:[B,T,KV,D]; lengths:[B] valid prefix -> [B,1,H,D]."""
    B, _, H, D = q.shape
    T, KV = cache_k.shape[1], cache_k.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, cache_k,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(T)[None, :] < lengths[:, None])[:, None, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(cache_v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, cache_v)
    return out.reshape(B, 1, H, D)


# ---------------------------------------------------------------------------
# Paged decode attention (page-table indirection, the virtualizer's view)
# ---------------------------------------------------------------------------

def gather_pages(pool: jax.Array, page_table: jax.Array) -> jax.Array:
    """The table's pages: ``[B, max_pages, *page]`` from ``[N_pages, *page]``.

    Unmapped entries (-1) read page 0; the lengths mask those tokens.
    """
    return pool[jnp.maximum(page_table, 0)]


def paged_decode_attention(q: jax.Array, kv_pages: jax.Array,
                           page_table: jax.Array, lengths: jax.Array,
                           scale: float) -> jax.Array:
    """Decode attention reading K/V through a page table.

    q:          [B,1,H,D]
    kv_pages:   [N_pages, page_size, 2, KV, D]  (the physical pool)
    page_table: [B, max_pages] int32 physical page ids (-1 = unmapped)
    lengths:    [B] tokens valid per sequence
    """
    return gathered_decode_attention(q, gather_pages(kv_pages, page_table),
                                     lengths, scale)


def gathered_decode_attention(q: jax.Array, kv: jax.Array,
                              lengths: jax.Array, scale: float) -> jax.Array:
    """Decode attention over each request's pages, already gathered.

    q:       [B,1,H,D]
    kv:      [B, max_pages, page_size, 2, KV, D]
    lengths: [B] tokens valid per sequence
    """
    B, P, page_size, _, KV, D = kv.shape
    T = P * page_size
    k = kv[:, :, :, 0].reshape(B, T, KV, D)
    v = kv[:, :, :, 1].reshape(B, T, KV, D)
    return decode_attention(q, k, v, lengths, scale)


def paged_mla_decode_attention(q: jax.Array, kv_pages: jax.Array,
                               page_table: jax.Array, lengths: jax.Array,
                               latent_dim: int, scale: float) -> jax.Array:
    """Absorbed-MLA decode attention through a page table.

    q:          [B,1,H, r+rp]  absorbed query [q_latent | q_rope]
    kv_pages:   [N_pages, page_size, r+rp]  (the pool's MLA-typed view)
    page_table: [B, max_pages] int32 physical page ids (-1 = unmapped)
    lengths:    [B] tokens valid per sequence
    Returns the latent context [B,1,H,latent_dim].
    """
    return gathered_mla_decode_attention(
        q, gather_pages(kv_pages, page_table), lengths, latent_dim, scale)


def gathered_mla_decode_attention(q: jax.Array, kv: jax.Array,
                                  lengths: jax.Array, latent_dim: int,
                                  scale: float) -> jax.Array:
    """Absorbed-MLA decode attention over each request's gathered pages.

    q:       [B,1,H, r+rp]
    kv:      [B, max_pages, page_size, r+rp]
    lengths: [B] tokens valid per sequence
    Returns the latent context [B,1,H,latent_dim].
    """
    B, P, page_size, e = kv.shape
    T = P * page_size
    rows = kv.reshape(B, T, e)                      # [B,T, r+rp]
    scores = jnp.einsum("bshe,bte->bhst", q, rows,
                        preferred_element_type=jnp.float32) * scale
    mask = (jnp.arange(T)[None, :] < lengths[:, None])[:, None, None, :]
    scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    ctx = jnp.einsum("bhst,btr->bshr", w, rows[..., :latent_dim])
    return ctx


# ---------------------------------------------------------------------------
# Grouped expert GEMM (token-sorted MoE)
# ---------------------------------------------------------------------------

def moe_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Token-sorted grouped matmul.

    x: [N, K] tokens sorted by expert; w: [E, K, M]; group_sizes: [E] with
    sum == N.  Token i uses expert e(i) = bucket of i under group_sizes.
    """
    N = x.shape[0]
    E = w.shape[0]
    bounds = jnp.cumsum(group_sizes)
    expert_of = jnp.searchsorted(bounds, jnp.arange(N), side="right")
    w_tok = w[expert_of]                            # [N, K, M]
    return jnp.einsum("nk,nkm->nm", x, w_tok)


# ---------------------------------------------------------------------------
# Mamba2 SSD chunked scan
# ---------------------------------------------------------------------------

def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C_: jax.Array, chunk: int = 64,
             h0: Optional[jax.Array] = None,
             ) -> Tuple[jax.Array, jax.Array]:
    """Reference SSD via the *sequential* per-token recurrence.

    x:  [B,S,H,P]   inputs per head
    dt: [B,S,H]     discretization steps (post-softplus)
    A:  [H]         negative decay rates (A = -exp(A_log))
    B_: [B,S,G,N]   input projections (G groups broadcast onto H)
    C_: [B,S,G,N]   output projections
    h0: [B,H,P,N]   optional initial state
    Returns (y [B,S,H,P], h_final [B,H,P,N]).
    """
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    rep = H // G
    Bh = jnp.repeat(B_, rep, axis=2)                # [B,S,H,N]
    Ch = jnp.repeat(C_, rep, axis=2)
    if h0 is None:
        h0 = jnp.zeros((Bb, H, P, N), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp                       # [B,H,P],[B,H],[B,H,N],[B,H,N]
        dA = jnp.exp(dtt * A[None, :])              # [B,H]
        h = h * dA[..., None, None] + (dtt[..., None, None]
                                       * xt[..., :, None] * bt[..., None, :])
        y = jnp.einsum("bhpn,bhn->bhp", h, ct)
        return h, y

    xs = (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
          jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Bh.astype(jnp.float32), 1, 0),
          jnp.moveaxis(Ch.astype(jnp.float32), 1, 0))
    h_final, ys = jax.lax.scan(step, h0, xs)
    y = jnp.moveaxis(ys, 0, 1).astype(x.dtype)      # [B,S,H,P]
    return y, h_final
