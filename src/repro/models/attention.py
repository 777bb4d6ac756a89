"""Attention variants: GQA/MHA/MQA, MLA (DeepSeek/MiniCPM3), sliding-window.

Each variant provides ``init_*`` (params), ``*_full`` (whole-sequence, used
by train/prefill) and ``*_decode`` (single-token against a cache).  The
decode cache layouts are exactly what the CrossPool KV-cache pool manages.
The paged decodes name their pool work for the profiler: ``kv_write`` (the
new token's window write into the pool), ``kv_view`` (the typing of the
pages the step gathered, ``kernels/ops._typed_pages``) and ``attention``
(the gather of the table's pages and the score/context kernel).  Neither
touches the whole ``[page_budget, page_elems]`` pool: the write moves the
new rows, the gather whole page rows.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.hooks import Hooks, IDENTITY_HOOKS
from repro.kernels import ops as kops


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def causal_mask(q_pos: jax.Array, k_pos: jax.Array,
                window: int = 0) -> jax.Array:
    """Boolean [.., S, T] mask: True = attend.  ``window``>0 adds locality."""
    m = q_pos[..., :, None] >= k_pos[..., None, :]
    if window > 0:
        m &= q_pos[..., :, None] - k_pos[..., None, :] < window
    return m


# ---------------------------------------------------------------------------
# Core grouped attention
# ---------------------------------------------------------------------------

def attention_core(q: jax.Array, k: jax.Array, v: jax.Array,
                   mask: Optional[jax.Array], scale: float,
                   impl: str = "xla") -> jax.Array:
    """Grouped-query attention.

    q: [B,S,H,D]; k/v: [B,T,KV,D]; mask: broadcastable to [B,KV,G,S,T]
    (pass [B,1,1,S,T] or [1,1,1,S,T]).  Returns [B,S,H,D].
    Softmax statistics in f32.
    """
    if k.dtype.itemsize == 1:           # fp8 KV cache: dequantize on-chip
        k = k.astype(jnp.bfloat16)
        v = v.astype(jnp.bfloat16)
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    if impl == "flash" and mask is None:
        raise ValueError("flash impl requires causal mask semantics")
    qg = q.reshape(B, S, KV, G, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask, scores, NEG_INF)
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, S, H, v.shape[-1])   # v head dim may differ (MLA)


# ---------------------------------------------------------------------------
# GQA (covers MHA: KV==H, and MQA: KV==1)
# ---------------------------------------------------------------------------

def init_gqa(key, cfg: ModelConfig, dtype) -> Dict:
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": layers.dense_init(ks[0], (d, H * hd), dtype),
        "wk": layers.dense_init(ks[1], (d, KV * hd), dtype),
        "wv": layers.dense_init(ks[2], (d, KV * hd), dtype),
        "wo": layers.dense_init(ks[3], (H * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    return p


def _project_qkv(p: Dict, cfg: ModelConfig, x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = (x @ p["wq"]).reshape(B, S, H, hd)
    k = (x @ p["wk"]).reshape(B, S, KV, hd)
    v = (x @ p["wv"]).reshape(B, S, KV, hd)
    if cfg.qk_norm:
        q = layers.head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = layers.head_rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def gqa_full(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             *, window: int = 0, hooks: Hooks = IDENTITY_HOOKS,
             kv_positions: Optional[jax.Array] = None,
             kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
             causal: bool = True, impl: str = "xla",
             ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Whole-sequence self-attention (or cross-attention via kv_override).

    Returns (output [B,S,D_model], (k, v) for cache seeding).
    """
    q, k, v = _project_qkv(p, cfg, x)
    if kv_override is not None:
        k, v = kv_override
    kv_pos = positions if kv_positions is None else kv_positions
    if cfg.rope_theta > 0:
        sin_q, cos_q = layers.rope_sin_cos(positions, cfg.head_dim, cfg.rope_theta)
        q = layers.apply_rope(q, sin_q, cos_q)
        if kv_override is None:
            sin_k, cos_k = layers.rope_sin_cos(kv_pos, cfg.head_dim, cfg.rope_theta)
            k = layers.apply_rope(k, sin_k, cos_k)
    q = hooks.attn_q(q)
    k, v = hooks.kv(k), hooks.kv(v)
    scale = cfg.head_dim ** -0.5
    if causal:
        if impl == "flash" and window == 0 and kv_override is None:
            out = kops.flash_attention(q, k, v, scale=scale)
        else:
            mask = causal_mask(positions, kv_pos, window)[:, None, None, :, :]
            out = attention_core(q, k, v, mask, scale)
    else:
        out = attention_core(q, k, v, None, scale)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return hooks.attn_out(out @ p["wo"]), (k, v)


def _pad_to_extent(arr: jax.Array, extent: int) -> jax.Array:
    """Zero-pad or truncate axis 1 to exactly ``extent`` rows.

    The suffix-prefill paths pin their KV reduction extent to the
    PRODUCING pass's bucket so softmax sums run over the identical span:
    padded rows are masked to ``NEG_INF`` (exact 0.0 softmax weight) and
    truncated rows are pad rows no real query attends.
    """
    T = arr.shape[1]
    if T == extent:
        return arr
    if T > extent:
        return arr[:, :extent]
    pad = [(0, 0)] * arr.ndim
    pad[1] = (0, extent - T)
    return jnp.pad(arr, pad)


def gqa_suffix(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
               prefix_k: jax.Array, prefix_v: jax.Array, kv_extent: int,
               *, hooks: Hooks = IDENTITY_HOOKS,
               ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Suffix-only prefill attention against a cached prompt prefix.

    x: [B,S_suf,D] post-norm hidden of the UNCACHED suffix tokens;
    positions: [B,S_suf] absolute positions (``fork + i``); prefix_k /
    prefix_v: [B,fork,KV,hd] gathered from the pool (post-RoPE, exactly
    the full pass's rows); ``kv_extent``: static KV length = the
    producing pass's prefill bucket.

    Bit-exactness with the full-prompt pass, for every row whose output
    is consumed (absolute position < true prompt length): the suffix
    K/V at those rows reproduce the full pass's (same inputs, same
    per-row math), the concatenated KV is truncated/zero-padded to the
    full pass's reduction extent, and the causal mask over absolute
    positions makes every pad/truncated disagreement masked to the same
    ``NEG_INF`` both sides of the comparison.
    Returns (out [B,S_suf,D_model], (k_suf, v_suf) for pool writing).
    """
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.rope_theta > 0:
        sin, cos = layers.rope_sin_cos(positions, cfg.head_dim, cfg.rope_theta)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    q = hooks.attn_q(q)
    k, v = hooks.kv(k), hooks.kv(v)
    k_all = _pad_to_extent(
        jnp.concatenate([prefix_k.astype(k.dtype), k], axis=1), kv_extent)
    v_all = _pad_to_extent(
        jnp.concatenate([prefix_v.astype(v.dtype), v], axis=1), kv_extent)
    kv_pos = jnp.arange(kv_extent)[None, :]
    mask = causal_mask(positions, kv_pos)[:, None, None, :, :]
    out = attention_core(q, k_all, v_all, mask, cfg.head_dim ** -0.5)
    B, S = x.shape[:2]
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    return hooks.attn_out(out @ p["wo"]), (k, v)


def write_kv_cache(cache_k: jax.Array, cache_v: jax.Array,
                   k_new: jax.Array, v_new: jax.Array,
                   lengths) -> Tuple[jax.Array, jax.Array]:
    """Insert one new KV per sequence.

    ``lengths``: scalar (uniform write index — fast path, in-place
    dynamic-update-slice, used by the dry-run decode step) or [B] vector
    (per-request index — engine path at small scale).
    cache: [B,T,KV,hd]; new: [B,1,KV,hd].
    """
    if jnp.ndim(lengths) == 0:
        idx = lengths.astype(jnp.int32) if hasattr(lengths, "astype") else jnp.int32(lengths)
        ck = jax.lax.dynamic_update_slice(cache_k, k_new.astype(cache_k.dtype),
                                          (0, idx, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache_v, v_new.astype(cache_v.dtype),
                                          (0, idx, 0, 0))
        return ck, cv
    T = cache_k.shape[1]
    slot = jnp.arange(T)[None, :] == lengths[:, None]          # [B,T]
    slot = slot[:, :, None, None]
    ck = jnp.where(slot, k_new.astype(cache_k.dtype), cache_k)
    cv = jnp.where(slot, v_new.astype(cache_v.dtype), cache_v)
    return ck, cv


def gqa_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
               cache_k: jax.Array, cache_v: jax.Array, lengths,
               *, hooks: Hooks = IDENTITY_HOOKS, impl: str = "xla",
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode against a contiguous cache.

    x: [B,1,D]; cache: [B,T,KV,hd]; lengths: scalar or [B] = current context
    length (the new token is written at this index).
    Returns (out [B,1,D], new_cache_k, new_cache_v).
    """
    B = x.shape[0]
    T = cache_k.shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    pos = (jnp.broadcast_to(jnp.asarray(lengths), (B,))[:, None]
           if jnp.ndim(lengths) > 0 else
           jnp.full((B, 1), lengths, dtype=jnp.int32))
    if cfg.rope_theta > 0:
        sin, cos = layers.rope_sin_cos(pos, cfg.head_dim, cfg.rope_theta)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    cache_k, cache_v = write_kv_cache(cache_k, cache_v, k, v, lengths)
    cache_k, cache_v = hooks.kv(cache_k), hooks.kv(cache_v)
    kv_pos = jnp.arange(T)[None, :]
    mask = (kv_pos <= pos)[:, None, None, :, None].swapaxes(-1, -2)  # [B,1,1,1,T]
    scale = cfg.head_dim ** -0.5
    lengths_incl = jnp.broadcast_to(jnp.asarray(lengths) + 1, (B,))
    if hooks.decode_attn is not None:
        # crosspool: sequence-sharded partial-softmax attention over the pool
        out = hooks.decode_attn(q, cache_k, cache_v, lengths_incl)
    elif impl == "paged":
        out = kops.decode_attention(q, cache_k, cache_v, lengths_incl,
                                    scale=scale)
    else:
        out = attention_core(q, cache_k, cache_v, mask, scale)
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return hooks.attn_out(out @ p["wo"]), cache_k, cache_v


def gqa_paged_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
                     pool: jax.Array, page_table: jax.Array, lengths,
                     *, tokens_per_page: int, hooks: Hooks = IDENTITY_HOOKS,
                     impl: Optional[str] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """One-token GQA decode against the shared paged KV pool.

    x: [B,1,D]; pool: [n_pages, page_elems] (the untyped physical pool);
    page_table: [B, max_pages] int32 for THIS layer (-1 = unmapped);
    lengths: [B] current context length — the new token's K/V is written at
    (page_table[b, lengths[b] // tpp], lengths[b] % tpp) and attention reads
    lengths+1 tokens back through the page table.
    Returns (out [B,1,D], updated pool).  Rows whose write page is unmapped
    (inactive batch slots) are dropped by the scatter.
    """
    B = x.shape[0]
    KV, hd = cfg.n_kv_heads, cfg.head_dim
    per_tok = 2 * KV * hd
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    pos = lengths[:, None]
    q, k, v = _project_qkv(p, cfg, x)
    if cfg.rope_theta > 0:
        sin, cos = layers.rope_sin_cos(pos, cfg.head_dim, cfg.rope_theta)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    kv_tok = jnp.stack([k[:, 0], v[:, 0]], axis=1).reshape(B, per_tok)
    chunk = lengths // tokens_per_page
    page = jnp.take_along_axis(page_table, chunk[:, None], axis=1)[:, 0]
    # drop writes past the table horizon (mirrors the dense cache's clamp)
    page = jnp.where(chunk < page_table.shape[1], page, -1)
    with jax.named_scope("kv_write"):
        pool = kops.paged_kv_write(pool, kv_tok, page,
                                   lengths % tokens_per_page)
    out = kops.paged_decode_attention(
        q, pool, page_table, lengths + 1,
        page_shape=(tokens_per_page, 2, KV, hd), scale=cfg.head_dim ** -0.5,
        impl=impl)
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim).astype(x.dtype)
    return hooks.attn_out(out @ p["wo"]), pool


def mla_paged_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
                     pool: jax.Array, page_table: jax.Array, lengths,
                     *, tokens_per_page: int, hooks: Hooks = IDENTITY_HOOKS,
                     impl: Optional[str] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """One-token absorbed-MLA decode against the shared paged KV pool.

    The per-token page row is [latent (r) | rope key (rp)] — the same
    untyped pool the GQA models page into, reinterpreted (Type II sharing).
    """
    m = cfg.mla
    B = x.shape[0]
    H = cfg.n_heads
    per_tok = m.kv_lora_rank + m.qk_rope_head_dim
    lengths = jnp.broadcast_to(jnp.asarray(lengths, jnp.int32), (B,))
    pos = lengths[:, None]
    q_nope, q_rope = _mla_queries(p, cfg, x, pos)
    latent_new, rope_new = _mla_latent(p, cfg, x, pos)
    kv_tok = jnp.concatenate([latent_new[:, 0], rope_new[:, 0]], axis=-1)
    chunk = lengths // tokens_per_page
    page = jnp.take_along_axis(page_table, chunk[:, None], axis=1)[:, 0]
    # drop writes past the table horizon (mirrors the dense cache's clamp)
    page = jnp.where(chunk < page_table.shape[1], page, -1)
    with jax.named_scope("kv_write"):
        pool = kops.paged_kv_write(pool, kv_tok, page,
                                   lengths % tokens_per_page)
    # absorb W_uk into q; score against [latent | rope] rows directly
    wuk = p["wuk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wuk)
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)      # [B,1,H,r+rp]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    ctx = kops.paged_mla_decode_attention(
        q_cat, pool, page_table, lengths + 1,
        page_shape=(tokens_per_page, per_tok), latent_dim=m.kv_lora_rank,
        scale=scale, impl=impl)
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhv->bshv", ctx, wuv)
    out = out.reshape(B, 1, H * m.v_head_dim).astype(x.dtype)
    return hooks.attn_out(out @ p["wo"]), pool


# ---------------------------------------------------------------------------
# Sliding-window decode (ring-buffer cache; gemma3 local layers)
# ---------------------------------------------------------------------------

def swa_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
               cache_k: jax.Array, cache_v: jax.Array, cache_pos: jax.Array,
               cur_len, *, hooks: Hooks = IDENTITY_HOOKS,
               ) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Decode with a ring-buffer window cache.

    cache: [B,W,KV,hd]; cache_pos: [B,W] absolute positions (-1 = empty);
    ``cur_len`` scalar (uniform) or [B].
    """
    B = x.shape[0]
    W = cache_k.shape[1]
    q, k, v = _project_qkv(p, cfg, x)
    cur = (jnp.broadcast_to(jnp.asarray(cur_len), (B,))
           if jnp.ndim(cur_len) > 0 else jnp.full((B,), cur_len, jnp.int32))
    pos = cur[:, None]
    if cfg.rope_theta > 0:
        sin, cos = layers.rope_sin_cos(pos, cfg.head_dim, cfg.rope_theta)
        q = layers.apply_rope(q, sin, cos)
        k = layers.apply_rope(k, sin, cos)
    slot = (cur % W)                                            # [B]
    hit = jnp.arange(W)[None, :] == slot[:, None]               # [B,W]
    cache_k = jnp.where(hit[:, :, None, None], k.astype(cache_k.dtype), cache_k)
    cache_v = jnp.where(hit[:, :, None, None], v.astype(cache_v.dtype), cache_v)
    cache_pos = jnp.where(hit, pos, cache_pos)
    cache_k, cache_v = hooks.kv(cache_k), hooks.kv(cache_v)
    valid = (cache_pos >= 0) & (cache_pos > (cur[:, None] - W))  # [B,W]
    mask = valid[:, None, None, None, :]
    out = attention_core(q, cache_k, cache_v, mask, cfg.head_dim ** -0.5)
    out = out.reshape(B, 1, cfg.n_heads * cfg.head_dim)
    return hooks.attn_out(out @ p["wo"]), cache_k, cache_v, cache_pos


# ---------------------------------------------------------------------------
# MLA — Multi-head Latent Attention (DeepSeek-V2 / MiniCPM3)
# ---------------------------------------------------------------------------

def init_mla(key, cfg: ModelConfig, dtype) -> Dict:
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 8)
    p = {}
    if m.q_lora_rank:
        p["wdq"] = layers.dense_init(ks[0], (d, m.q_lora_rank), dtype)
        p["q_ln"] = jnp.zeros((m.q_lora_rank,), dtype)
        p["wuq"] = layers.dense_init(ks[1], (m.q_lora_rank, H * qk_dim), dtype)
    else:
        p["wuq"] = layers.dense_init(ks[1], (d, H * qk_dim), dtype)
    p["wdkv"] = layers.dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype)
    p["kv_ln"] = jnp.zeros((m.kv_lora_rank,), dtype)
    p["wuk"] = layers.dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim), dtype)
    p["wuv"] = layers.dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim), dtype)
    p["wo"] = layers.dense_init(ks[5], (H * m.v_head_dim, d), dtype)
    return p


def _mla_queries(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """Returns (q_nope [B,S,H,nope], q_rope [B,S,H,rope]) with RoPE applied."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qk_dim = m.qk_nope_head_dim + m.qk_rope_head_dim
    if m.q_lora_rank:
        cq = layers.rms_norm(x @ p["wdq"], p["q_ln"], cfg.norm_eps)
        q = (cq @ p["wuq"]).reshape(B, S, H, qk_dim)
    else:
        q = (x @ p["wuq"]).reshape(B, S, H, qk_dim)
    q_nope = q[..., : m.qk_nope_head_dim]
    q_rope = q[..., m.qk_nope_head_dim:]
    sin, cos = layers.rope_sin_cos(positions, m.qk_rope_head_dim, cfg.rope_theta)
    q_rope = layers.apply_rope(q_rope, sin, cos)
    return q_nope, q_rope


def _mla_latent(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array
                ) -> Tuple[jax.Array, jax.Array]:
    """Compressed KV: (latent [B,S,r] post-norm, k_rope [B,S,rope] post-RoPE).

    These two tensors are *the entire KV cache* — the paper's Type II case.
    """
    m = cfg.mla
    ckv = x @ p["wdkv"]
    latent = layers.rms_norm(ckv[..., : m.kv_lora_rank], p["kv_ln"], cfg.norm_eps)
    k_rope = ckv[..., m.kv_lora_rank:]
    sin, cos = layers.rope_sin_cos(positions, m.qk_rope_head_dim, cfg.rope_theta)
    k_rope = layers.apply_rope(k_rope[:, :, None, :], sin, cos)[:, :, 0, :]
    return latent, k_rope


def mla_full(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
             *, hooks: Hooks = IDENTITY_HOOKS,
             ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Whole-sequence MLA in the expanded (prefill/train) form."""
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_queries(p, cfg, x, positions)
    latent, k_rope = _mla_latent(p, cfg, x, positions)
    latent, k_rope = hooks.kv(latent), hooks.kv(k_rope)
    k_nope = (latent @ p["wuk"]).reshape(B, S, H, m.qk_nope_head_dim)
    v = (latent @ p["wuv"]).reshape(B, S, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    mask = causal_mask(positions, positions)[:, None, None, :, :]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (B, S, H, m.qk_rope_head_dim))],
                        axis=-1)
    out = attention_core(q, k, v, mask, scale)
    out = out.reshape(B, S, H * m.v_head_dim)
    return hooks.attn_out(out @ p["wo"]), (latent, k_rope)


def mla_suffix(p: Dict, cfg: ModelConfig, x: jax.Array, positions: jax.Array,
               prefix_latent: jax.Array, prefix_rope: jax.Array,
               kv_extent: int, *, hooks: Hooks = IDENTITY_HOOKS,
               ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Suffix-only expanded-form MLA against a cached prompt prefix.

    x: [B,S_suf,D] suffix hidden; positions: [B,S_suf] absolute;
    prefix_latent: [B,fork,r] / prefix_rope: [B,fork,rope] — the pool's
    compressed rows (post-norm latent, post-RoPE key) for the cached
    prefix; ``kv_extent``: the producing pass's bucket.  Same exactness
    argument as :func:`gqa_suffix` — the ``latent @ wuk`` / ``@ wuv``
    expansions are per-row, so padded latent rows only produce masked
    scores.  Returns (out, (latent_suf, rope_suf) for pool writing).
    """
    m = cfg.mla
    B, S = x.shape[:2]
    H = cfg.n_heads
    q_nope, q_rope = _mla_queries(p, cfg, x, positions)
    latent, k_rope = _mla_latent(p, cfg, x, positions)
    latent, k_rope = hooks.kv(latent), hooks.kv(k_rope)
    latent_all = _pad_to_extent(
        jnp.concatenate([prefix_latent.astype(latent.dtype), latent], axis=1),
        kv_extent)
    rope_all = _pad_to_extent(
        jnp.concatenate([prefix_rope.astype(k_rope.dtype), k_rope], axis=1),
        kv_extent)
    k_nope = (latent_all @ p["wuk"]).reshape(B, kv_extent, H,
                                             m.qk_nope_head_dim)
    v = (latent_all @ p["wuv"]).reshape(B, kv_extent, H, m.v_head_dim)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    kv_pos = jnp.arange(kv_extent)[None, :]
    mask = causal_mask(positions, kv_pos)[:, None, None, :, :]
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(rope_all[:, :, None, :],
                                  (B, kv_extent, H, m.qk_rope_head_dim))],
        axis=-1)
    out = attention_core(q, k, v, mask, scale)
    out = out.reshape(B, S, H * m.v_head_dim)
    return hooks.attn_out(out @ p["wo"]), (latent, k_rope)


def mla_decode(p: Dict, cfg: ModelConfig, x: jax.Array,
               cache_latent: jax.Array, cache_rope: jax.Array, lengths,
               *, hooks: Hooks = IDENTITY_HOOKS,
               ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token MLA decode in the *absorbed* form.

    cache_latent: [B,T,r]; cache_rope: [B,T,rope].  Attention reads only the
    compressed latent — per-token KV bytes = (r + rope) * 2, independent of
    the 40 query heads.
    """
    m = cfg.mla
    B = x.shape[0]
    T = cache_latent.shape[1]
    H = cfg.n_heads
    pos = (jnp.broadcast_to(jnp.asarray(lengths), (B,))[:, None]
           if jnp.ndim(lengths) > 0 else jnp.full((B, 1), lengths, jnp.int32))
    q_nope, q_rope = _mla_queries(p, cfg, x, pos)
    latent_new, rope_new = _mla_latent(p, cfg, x, pos)
    # write to cache
    if jnp.ndim(lengths) == 0:
        idx = jnp.int32(lengths)
        cache_latent = jax.lax.dynamic_update_slice(
            cache_latent, latent_new.astype(cache_latent.dtype), (0, idx, 0))
        cache_rope = jax.lax.dynamic_update_slice(
            cache_rope, rope_new.astype(cache_rope.dtype), (0, idx, 0))
    else:
        slot = (jnp.arange(T)[None, :] == lengths[:, None])[:, :, None]
        cache_latent = jnp.where(slot, latent_new.astype(cache_latent.dtype), cache_latent)
        cache_rope = jnp.where(slot, rope_new.astype(cache_rope.dtype), cache_rope)
    cache_latent = hooks.kv(cache_latent)
    cache_rope = hooks.kv(cache_rope)
    # absorb W_uk into q:  q_lat [B,1,H,r]
    wuk = p["wuk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, wuk)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if hooks.decode_attn_mla is not None:
        lengths_incl = jnp.broadcast_to(jnp.asarray(lengths) + 1, (B,))
        ctx_lat = hooks.decode_attn_mla(q_lat, q_rope, cache_latent,
                                        cache_rope, lengths_incl)
    else:
        if cache_latent.dtype.itemsize == 1:   # fp8 latent cache
            cache_latent = cache_latent.astype(jnp.bfloat16)
            cache_rope = cache_rope.astype(jnp.bfloat16)
        scores = (jnp.einsum("bshr,btr->bhst", q_lat, cache_latent,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshp,btp->bhst", q_rope, cache_rope,
                               preferred_element_type=jnp.float32))
        scores = scores * scale
        kv_pos = jnp.arange(T)[None, None, None, :]
        mask = kv_pos <= pos[:, None, :, None]   # [B,1,1,T] vs scores [B,H,1,T]
        scores = jnp.where(mask, scores, NEG_INF)
        w = jax.nn.softmax(scores, axis=-1).astype(cache_latent.dtype)
        ctx_lat = jnp.einsum("bhst,btr->bshr", w, cache_latent)
    wuv = p["wuv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
    out = jnp.einsum("bshr,rhv->bshv", ctx_lat, wuv)
    out = out.reshape(B, 1, H * m.v_head_dim)
    return hooks.attn_out(out @ p["wo"]), cache_latent, cache_rope
