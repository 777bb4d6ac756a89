"""Split layer execution: attention sub-block vs FFN sub-block per layer.

This mirrors the paper's §4 runtime integration: in the KV-cache pool the
FFN of every transformer layer is replaced by a *proxy* — the attention
stage returns the post-attention hidden states, the FFN stage (running in
the weights pool, possibly another device) consumes them, and the combine
step resumes the residual stream.  ``attn_stage``/``ffn_stage``/``combine``
are the units the layer-wise pipeline scheduler interleaves.

The attention stage reads and writes KV through the virtualizer's SHARED
paged pool: it takes ``(x, pool, page_tables, lengths)`` instead of dense
per-model caches, writes the new token's K/V at its (page, slot)
coordinate and attends through ``repro.kernels.paged_attention``.  The
pool is the single source of KV truth for every split-execution model;
dense contiguous caches survive only in the fused fallback path
(``repro.models.decode``) used by the SSM/hybrid/enc-dec/SWA families.

The FFN stage is symmetric on the weights side: it does NOT close over a
per-model ``w_params`` tree.  It takes ``(arena, slot_table, ffn_in,
layer)`` and gathers the layer's expert / dense-MLP slabs out of the
SHARED weights arena (``repro.core.weight_pool.WeightArena``) through the
model's slot table, so FFN weights are read exactly like KV pages and
cold models can be activated/evicted without recompiling the stages.

Supported families: dense / moe / vlm with GQA or MLA attention — the
paper's serving targets.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import weight_pool
from repro.core.virtualizer import ModelView
from repro.models import attention as attn
from repro.models import layers, moe as moe_mod
from repro.models import transformer as tfm
from repro.models.hooks import IDENTITY_HOOKS


class StageFns(NamedTuple):
    embed: Callable          # (params, tokens [B])            -> x [B,1,D]
    attn_stage: Callable     # (params, x, pool, page_tables [L,B,P],
    #                           lengths [B], layer)
    #                           -> (x_resid, ffn_input, pool)
    ffn_stage: Callable      # (arena [S,slab], slot_table [L,spl],
    #                           ffn_input, layer)              -> ffn_out
    combine: Callable        # (x_resid, ffn_out)              -> x
    logits: Callable         # (params, x)                     -> [B,V]
    # prompt-phase (prefill) variants over the SAME arena: full-sequence
    # attention per layer, the identical ffn_stage consuming [B,S,D]
    prefill_embed: Callable  # (params, tokens [B,S])          -> x [B,S,D]
    prefill_attn: Callable   # (params, x [B,S,D], layer)
    #                           -> (x_resid, ffn_input, layer_kv)
    #                        layer_kv: (k, v) [B,S,KV,hd] for GQA or
    #                                  (latent, rope) [B,S,·] for MLA
    prefill_logits: Callable  # (params, x [B,S,D],
    #                           logit_index scalar | [B])      -> [B,V]
    n_layers: int
    # prefix-cache suffix prefill (DESIGN.md §11): attention over
    # (cached prefix KV ++ fresh suffix KV) at the producing pass's
    # reduction extent, and the FFN with the producing pass's expert
    # capacity + the prefix's routed-pair slot offsets
    suffix_attn: Optional[Callable] = None
    # (params, x [B,S_suf,D], prefix_rows [L, fork, *kv_shape],
    #  positions [B,S_suf], layer, kv_extent static)
    #                              -> (x_resid, ffn_input, layer_kv)
    suffix_ffn: Optional[Callable] = None
    # (arena, slot_table, ffn_input, layer, slot_offsets [L,E]|None,
    #  capacity static)            -> ffn_out
    prefill_route: Optional[Callable] = None
    # (arena, slot_table, ffn_input, layer) -> experts [B,S,k]
    #  (MoE only; recomputes the router's top-k choice so the prompt's
    #   routing can be captured without touching the FFN program)
    prefill_ffn: Optional[Callable] = None      # ffn_stage's prompt twin
    prefill_combine: Optional[Callable] = None  # combine's prompt twin


#: The device scopes (``jax.named_scope``) the stage functions open, so a
#: profiler trace charges each device op to the stage that issued it.
#: Decode: ``embed``, ``attn`` (children ``kv_write``, ``kv_view``,
#: ``attention``), ``ffn`` (children ``unpack``, ``router``, ``experts``,
#: ``mlp``) and ``head``; ``loop`` holds the fused step's loop control
#: (``core/control.py``).  Prompt path: ``embed``, ``prefill_attn``,
#: ``prefill_ffn`` (same children as ``ffn``), ``kv_prompt_write``
#: (``core/virtualizer.py``) and ``head``.
SCOPES = ("embed", "attn", "kv_write", "kv_view", "attention", "ffn",
          "unpack", "router", "experts", "mlp", "head", "loop",
          "prefill_attn", "prefill_ffn", "kv_prompt_write")


def scoped(name: str, fn: Callable) -> Callable:
    """``fn`` traced under the device scope ``name`` (HLO metadata only:
    the ops and their fusion are the same)."""
    @functools.wraps(fn)
    def run(*args, **kwargs):
        with jax.named_scope(name):
            return fn(*args, **kwargs)
    return run


def _layer_params(params: Dict, layer) -> Dict:
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
        params["layers"])


def supports_split(cfg: ModelConfig) -> bool:
    """Whether a model runs the split (paged-pool) decode path.

    Everything else — SSM, hybrid, enc-dec audio, sliding-window patterns —
    falls back to the fused dense-cache path.
    """
    return (cfg.family in ("dense", "moe", "vlm")
            and not cfg.attn_free
            and cfg.swa_pattern == 0
            and cfg.attention in ("gqa", "mla"))


def make_stage_fns(cfg: ModelConfig, view: ModelView,
                   w_view: "weight_pool.ModelArenaView") -> StageFns:
    """Stage functions over the shared paged pool + the weights arena.

    ``view`` is the virtualizer's :class:`ModelView` for this model — it
    fixes the static page geometry (``tokens_per_page``) the stage programs
    compile against.  ``w_view`` is the weights arena's
    :class:`~repro.core.weight_pool.ModelArenaView` — it fixes the static
    slab geometry the FFN stage's gather/bitcast unpacker compiles against.
    """
    if not supports_split(cfg):
        raise ValueError(
            f"split execution supports dense/moe/vlm with gqa/mla attention; "
            f"{cfg.name} ({cfg.family}) uses the fused path")
    tpp = view.tokens_per_page

    def embed(params, tokens):
        return layers.embed_tokens(params["embed"], tokens[:, None])

    def attn_stage(params, x, pool, page_tables, lengths, layer):
        p_l = _layer_params(params, layer)
        table = jax.lax.dynamic_index_in_dim(page_tables, layer, 0,
                                             keepdims=False)
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        if cfg.attention == "mla":
            out, pool = attn.mla_paged_decode(p_l["attn"], cfg, h, pool,
                                              table, lengths,
                                              tokens_per_page=tpp)
        else:
            out, pool = attn.gqa_paged_decode(p_l["attn"], cfg, h, pool,
                                              table, lengths,
                                              tokens_per_page=tpp)
        x = x + out
        # the proxy boundary: pre-FFN norm runs in the KV pool, the
        # normalized hidden states are what crosses to the weights pool
        ffn_in = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        return x, ffn_in, pool

    def ffn_stage(arena, slot_table, ffn_in, layer):
        row = jax.lax.dynamic_index_in_dim(slot_table, layer, 0,
                                           keepdims=False)
        p_l = w_view.unpack_layer(arena, row)
        if cfg.is_moe:
            B, S = ffn_in.shape[0], ffn_in.shape[1]
            if B > 1 and S > 1:
                # batched (coalesced) prefill: route each request's prompt
                # independently, so expert capacity is per request and a
                # [B,S] pass is bit-exact with B separate [1,S] passes —
                # one request's tokens can never evict another's from an
                # expert's capacity window (decode keeps the batch-global
                # formulation: its rows are single tokens)
                out, _ = jax.vmap(
                    lambda r: moe_mod.apply_moe(p_l["moe"], r[None], cfg)
                )(ffn_in)
                out = out[:, 0]
            else:
                out, _ = moe_mod.apply_moe(p_l["moe"], ffn_in, cfg)
        else:
            with jax.named_scope("mlp"):
                out = layers.apply_mlp(p_l["mlp"], ffn_in, cfg.mlp_kind)
        return out

    def combine(x, ffn_out):
        return x + ffn_out

    def logits(params, x):
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        return layers.unembed(params["embed"], x)[:, 0]

    # ---- prompt phase (prefill) over the same arena ----------------------
    # The attention stage runs the FULL-sequence attention of
    # ``models.transformer`` (bit-identical math to the fused dense
    # prefill), but the FFN boundary is the same proxy as decode: the
    # normalized hidden states cross to the weights side and ``ffn_stage``
    # gathers the layer's slabs from the shared arena — no per-model
    # ``w_params`` tree exists at prompt time either.

    def prefill_embed(params, tokens):
        return layers.embed_tokens(params["embed"], tokens)

    def prefill_attn(params, x, layer):
        p_l = _layer_params(params, layer)
        B, S = x.shape[0], x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        x, layer_kv = tfm._attn_full(p_l, cfg, x, positions, 0,
                                     IDENTITY_HOOKS, "xla")
        ffn_in = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        return x, ffn_in, layer_kv

    def prefill_logits(params, x, logit_index):
        # ``logit_index`` is scalar (one shared unpadded length) or [B]
        # (a coalesced batch where every row has its own true length)
        idx = jnp.asarray(logit_index, jnp.int32)
        if idx.ndim == 0:
            x_last = jax.lax.dynamic_slice_in_dim(x, idx, 1, axis=1)
        else:
            x_last = jnp.take_along_axis(x, idx[:, None, None], axis=1)
        x_last = layers.rms_norm(x_last, params["final_norm"], cfg.norm_eps)
        return layers.unembed(params["embed"], x_last)[:, 0]

    # ---- prefix-cache suffix prefill (DESIGN.md §11) ---------------------
    # Attention concatenates the cached prefix KV (gathered from the pool)
    # with the suffix's fresh KV and pins the reduction extent to the
    # PRODUCING pass's bucket; the FFN reuses the producing capacity with
    # the prefix's routed-pair counts as slot offsets — together the
    # suffix rows reproduce the full-prompt pass bit-for-bit at every
    # consumed position.

    def suffix_attn(params, x, prefix_rows, positions, layer, kv_extent):
        # ``prefix_rows`` is the [L, fork, *kv_shape] stack from
        # ``gather_prompt_rows``: the layer extraction and the K/V (or
        # MLA latent/rope) split happen here, inside the compiled stage,
        # so the host loop dispatches no eager slices per layer
        p_l = _layer_params(params, layer)
        rows = jax.lax.dynamic_index_in_dim(prefix_rows, layer, 0,
                                            keepdims=False)
        if cfg.attention == "mla":
            r = cfg.mla.kv_lora_rank
            prefix_a, prefix_b = rows[None, :, :r], rows[None, :, r:]
        else:
            prefix_a, prefix_b = rows[None, :, 0], rows[None, :, 1]
        h = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
        if cfg.attention == "mla":
            out, layer_kv = attn.mla_suffix(p_l["attn"], cfg, h, positions,
                                            prefix_a, prefix_b, kv_extent)
        else:
            out, layer_kv = attn.gqa_suffix(p_l["attn"], cfg, h, positions,
                                            prefix_a, prefix_b, kv_extent)
        x = x + out
        ffn_in = layers.rms_norm(x, p_l["ln2"], cfg.norm_eps)
        return x, ffn_in, layer_kv

    def suffix_ffn(arena, slot_table, ffn_in, layer, slot_offsets, capacity):
        row = jax.lax.dynamic_index_in_dim(slot_table, layer, 0,
                                           keepdims=False)
        p_l = w_view.unpack_layer(arena, row)
        if cfg.is_moe:
            # suffix groups are B=1 singletons, so the plain (non-vmapped)
            # dispatch is the bit-exact counterpart of the producing pass;
            # slot_offsets is the [L, E] stack, sliced in-program
            offset = None if slot_offsets is None else \
                jax.lax.dynamic_index_in_dim(slot_offsets, layer, 0,
                                             keepdims=False)
            out, _ = moe_mod.apply_moe(p_l["moe"], ffn_in, cfg,
                                       capacity=capacity,
                                       slot_offset=offset)
        else:
            with jax.named_scope("mlp"):
                out = layers.apply_mlp(p_l["mlp"], ffn_in, cfg.mlp_kind)
        return out

    if cfg.is_moe:
        def prefill_route(arena, slot_table, ffn_in, layer):
            row = jax.lax.dynamic_index_in_dim(slot_table, layer, 0,
                                               keepdims=False)
            p_l = w_view.unpack_layer(arena, row)
            B, S, d = ffn_in.shape
            _, experts, _ = moe_mod.route(p_l["moe"], ffn_in.reshape(-1, d),
                                          cfg)
            return experts.reshape(B, S, cfg.experts_per_token)
    else:
        prefill_route = None

    return StageFns(
        scoped("embed", embed), scoped("attn", attn_stage),
        scoped("ffn", ffn_stage), scoped("ffn", combine),
        scoped("head", logits), scoped("embed", prefill_embed),
        scoped("prefill_attn", prefill_attn),
        scoped("head", prefill_logits), cfg.n_layers,
        scoped("prefill_attn", suffix_attn),
        scoped("prefill_ffn", suffix_ffn),
        None if prefill_route is None
        else scoped("prefill_ffn", prefill_route),
        scoped("prefill_ffn", ffn_stage), scoped("prefill_ffn", combine))


def split_params(params: Dict, cfg: ModelConfig) -> Tuple[Dict, Dict]:
    """Partition a param tree into (kv_pool_params, weights_pool_params).

    FFN/MoE weights go to the weights pool (the dominant MoE bytes, paper
    Table 1); embeddings, norms and attention stay with the KV pool.
    """
    ffn_keys = ("mlp", "moe")

    def is_ffn(path):
        return any(k in path for k in ffn_keys)

    kv_tree = {}
    w_tree = {}

    def walk(src, kv_dst, w_dst, path=()):
        for k, v in src.items():
            p = path + (k,)
            if isinstance(v, dict):
                kv_sub, w_sub = {}, {}
                walk(v, kv_sub, w_sub, p)
                if kv_sub:
                    kv_dst[k] = kv_sub
                if w_sub:
                    w_dst[k] = w_sub
            else:
                (w_dst if is_ffn(p) else kv_dst)[k] = v

    walk(params, kv_tree, w_tree)
    return kv_tree, w_tree


def merge_params(kv_tree: Dict, w_tree: Dict) -> Dict:
    out: Dict = {}

    def walk(src, dst):
        for k, v in src.items():
            if isinstance(v, dict):
                dst.setdefault(k, {})
                walk(v, dst[k])
            else:
                dst[k] = v

    walk(kv_tree, out)
    walk(w_tree, out)
    return out
