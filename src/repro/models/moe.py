"""Mixture-of-Experts FFN: top-k router + capacity-based expert dispatch.

This is the module the CrossPool *weights pool* consolidates: expert weights
are stored once (stacked ``[E, ...]``) and shardable over any mesh axis via
``hooks.moe_inputs`` / ``hooks.moe_hidden``.  The dispatch is the standard
capacity-factor formulation (GShard/Switch): each expert processes at most
``C = ceil(N * k * capacity_factor / E)`` tokens; overflow tokens fall back
to the residual path (dropped from the FFN), which matches the router
semantics serving engines use at low batch.

Two FLOPs-relevant properties (they matter for the §Roofline tables):
  * compiled FLOPs scale with E*C ≈ N*k*cf — i.e. *active* expert compute,
    not all-expert compute;
  * the gather/scatter dispatch is data movement, not matmul FLOPs.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers
from repro.models.hooks import Hooks, IDENTITY_HOOKS
from repro.kernels import ops as kops

#: Leaves of ``init_moe`` stacked over the leading expert axis ``[E, ...]``.
#: The weights-pool virtualizer slices these per expert into arena slab
#: units (``repro.core.weight_pool``); everything else in the tree (router,
#: shared experts) is per-layer.  Keep in sync with :func:`init_moe`.
EXPERT_STACKED_LEAVES = ("wg", "wu", "wd")


def init_moe(key, cfg: ModelConfig, dtype) -> Dict:
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": layers.dense_init(ks[0], (d, E), jnp.float32),
        "wg": layers.dense_init(ks[1], (E, d, f), dtype, in_axis=1),
        "wu": layers.dense_init(ks[2], (E, d, f), dtype, in_axis=1),
        "wd": layers.dense_init(ks[3], (E, f, d), dtype, in_axis=1),
    }
    if cfg.n_shared_experts:
        p["shared"] = layers.init_mlp(ks[4], d, cfg.n_shared_experts * f,
                                      "swiglu", dtype)
    return p


def expert_capacity(n_tokens: int, cfg: ModelConfig) -> int:
    """Per-expert token capacity C (static — shapes must not depend on data)."""
    c = math.ceil(n_tokens * cfg.experts_per_token * cfg.capacity_factor
                  / cfg.n_experts)
    # MXU alignment: round C up to a multiple of 8 (sublane) when large enough.
    return max(8, ((c + 7) // 8) * 8) if c > 8 else max(c, 1)


def route(p: Dict, x: jax.Array, cfg: ModelConfig
          ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing. x: [N,D] -> (gates [N,k], experts [N,k], router_probs [N,E])."""
    logits = (x.astype(jnp.float32) @ p["router"])          # [N,E]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, experts = jax.lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)  # renormalize top-k
    return gates, experts, probs


def dispatch_indices(experts: jax.Array, n_experts: int, capacity: int,
                     offset: Optional[jax.Array] = None,
                     ) -> Tuple[jax.Array, jax.Array]:
    """Compute each (token, k) pair's slot within its expert.

    experts: [N,k] int32.  Returns (slot [N,k] int32 position-in-expert,
    keep [N,k] bool — False when over capacity).
    Pure cumsum formulation: position of pair (n,j) within expert e equals
    the number of *earlier* pairs routed to e (row-major (n,j) order).

    ``offset`` ([E] int32) pre-counts pairs routed to each expert by tokens
    that come BEFORE this call's tokens in the same logical sequence —
    the suffix-prefill path passes the cached prefix's routed-pair counts
    so the suffix's slots (and hence capacity drops) land exactly where a
    full-prompt pass would have put them.
    """
    N, k = experts.shape
    flat = experts.reshape(-1)                               # [N*k]
    onehot = jax.nn.one_hot(flat, n_experts, dtype=jnp.int32)  # [N*k, E]
    pos = jnp.cumsum(onehot, axis=0) - onehot                # exclusive cumsum
    slot = jnp.take_along_axis(pos, flat[:, None], axis=1)[:, 0]
    if offset is not None:
        slot = slot + jnp.take(offset.astype(slot.dtype), flat)
    keep = slot < capacity
    return slot.reshape(N, k), keep.reshape(N, k)


def apply_moe(p: Dict, x: jax.Array, cfg: ModelConfig, *,
              hooks: Hooks = IDENTITY_HOOKS,
              capacity: Optional[int] = None,
              slot_offset: Optional[jax.Array] = None,
              ) -> Tuple[jax.Array, jax.Array]:
    """Routed expert FFN.

    x: [B,S,D] (or [N,D]).  Returns (out same shape, aux_loss scalar —
    the Switch load-balance loss, used by the training substrate).

    ``slot_offset`` ([E]) shifts each expert's dispatch slots as if that
    many pairs were already routed there (see :func:`dispatch_indices`);
    pair it with the producing pass's ``capacity`` for prefix-cached
    suffix prefill.
    """
    orig_shape = x.shape
    d = cfg.d_model
    xf = x.reshape(-1, d)                                    # [N,D]
    N = xf.shape[0]
    E, k = cfg.n_experts, cfg.experts_per_token
    C = capacity or expert_capacity(N, cfg)

    with jax.named_scope("router"):
        gates, experts, probs = route(p, xf, cfg)            # [N,k]x2, [N,E]
        slot, keep = dispatch_indices(experts, E, C, offset=slot_offset)

    with jax.named_scope("experts"):
        # ---- dispatch: scatter tokens into [E, C, D] -----------------------
        flat_expert = experts.reshape(-1)                    # [N*k]
        flat_slot = slot.reshape(-1)
        flat_keep = keep.reshape(-1)
        flat_dst = jnp.where(flat_keep, flat_expert * C + flat_slot, E * C)
        token_ids = jnp.repeat(jnp.arange(N), k)             # [N*k]
        x_src = xf[token_ids]                                # [N*k, D]
        buf = jnp.zeros((E * C + 1, d), xf.dtype)
        buf = buf.at[flat_dst].set(x_src)                    # drop row E*C
        expert_in = buf[: E * C].reshape(E, C, d)
        expert_in = hooks.moe_inputs(expert_in)

        # ---- expert computation (stacked SwiGLU over the E axis) -----------
        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, p["wg"])) \
            * jnp.einsum("ecd,edf->ecf", expert_in, p["wu"])
        h = hooks.moe_hidden(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, p["wd"])  # [E,C,D]
        expert_out = hooks.moe_inputs(expert_out)

        # ---- combine: gather back and weight by gates ----------------------
        flat_out = expert_out.reshape(E * C, d)
        safe_dst = jnp.minimum(flat_dst, E * C - 1)
        y_pairs = flat_out[safe_dst] * (gates.reshape(-1)
                                        * flat_keep)[:, None]
        y = jax.ops.segment_sum(y_pairs.astype(jnp.float32), token_ids,
                                num_segments=N).astype(x.dtype)

    # ---- shared experts (always-on residual experts; DeepSeek-style) -------
    if cfg.n_shared_experts:
        with jax.named_scope("mlp"):
            y = y + layers.apply_mlp(p["shared"], xf, "swiglu",
                                     hook=hooks.ffn_hidden)

    # ---- load-balance aux loss (Switch): E * sum_e f_e * P_e ---------------
    with jax.named_scope("router"):
        pair_onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)  # [N,k,E]
        frac_tokens = jnp.mean(jnp.sum(pair_onehot, axis=1), axis=0)  # [E]
        frac_probs = jnp.mean(probs, axis=0)
        aux = E * jnp.sum(frac_tokens * frac_probs) / k

    return y.reshape(orig_shape), aux


def make_moe_a2a(mesh, cfg: ModelConfig, *, expert_axis: str = "data",
                 tp_axis: str = "model", batch_axes=None,
                 capacity_mult: float = 1.25, f8_dispatch: bool = False):
    """Explicit all-to-all expert dispatch via shard_map (beyond-paper opt).

    The XLA-SPMD formulation of ``apply_moe`` lets the partitioner choose
    the dispatch collectives; on cold-decode batches it emits full-buffer
    all-gathers + all-reduces (~16 MB/layer/device).  This version pins the
    MegaScale-Infer-style schedule explicitly:

      tokens sharded over ``expert_axis`` | experts sharded over the same
      axis | per-(src,dst) send buffers | ONE all_to_all out (payload =
      each token travels once) | local capacity-dispatch to the shard's own
      experts (f sharded over ``tp_axis``) | psum over tp | ONE all_to_all
      back | weighted combine.

    Collective payload per layer: 2 * N * d * itemsize / shards + the tp
    psum — ~8x less than the SPMD-chosen schedule at decode batch sizes.

    Returns fn(params_moe, x [B,S,d]) -> (out, aux) with the same routing
    semantics as ``apply_moe`` (top-k, renormalized gates, capacity drop).
    """
    from jax.sharding import PartitionSpec as P
    try:
        from jax import shard_map as _sm

        def _shard_map(f, in_specs, out_specs):
            return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)
    except ImportError:  # pragma: no cover
        from jax.experimental.shard_map import shard_map as _sm

        def _shard_map(f, in_specs, out_specs):
            return _sm(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_rep=False)

    n_shards = mesh.shape[expert_axis]
    E, k, d = cfg.n_experts, cfg.experts_per_token, cfg.d_model
    assert E % n_shards == 0, (E, n_shards)
    E_loc = E // n_shards

    def local(p_router, wg, wu, wd, x):
        # x: [B_loc, S, d] tokens of this expert-axis shard (replicated
        # over tp); wg/wu/wd: [E_loc, d, f_loc]
        Bl, S, _ = x.shape
        xf = x.reshape(-1, d)
        Nl = xf.shape[0]
        logits = xf.astype(jnp.float32) @ p_router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, experts = jax.lax.top_k(probs, k)              # [Nl,k]
        gates = gates / jnp.sum(gates, axis=-1, keepdims=True)

        owner = experts // E_loc                              # dst shard
        local_eid = experts % E_loc
        # send-slot within (this shard -> dst) buffer
        C2 = max(8, int(math.ceil(Nl * k / n_shards * capacity_mult)))
        flat_owner = owner.reshape(-1)
        onehot = jax.nn.one_hot(flat_owner, n_shards, dtype=jnp.int32)
        slot = (jnp.cumsum(onehot, axis=0) - onehot)[
            jnp.arange(Nl * k), flat_owner]
        keep = slot < C2
        dst = jnp.where(keep, flat_owner * C2 + slot, n_shards * C2)
        tok_ids = jnp.repeat(jnp.arange(Nl), k)

        # fp8 dispatch transport (DeepSeek-V3 style: fp8 out, bf16 back):
        # halves the dominant a2a payload; expert inputs are dequantized
        # before the GEMMs.
        xmit_dt = jnp.float8_e4m3fn if f8_dispatch else x.dtype
        send_x = jnp.zeros((n_shards * C2 + 1, d), xmit_dt)
        send_x = send_x.at[dst].set(xf[tok_ids].astype(xmit_dt))[:-1]
        send_meta = jnp.full((n_shards * C2 + 1,), -1, jnp.int32)
        send_meta = send_meta.at[dst].set(local_eid.reshape(-1))[:-1]

        # one hop: each (token,k) pair travels once
        recv_x = jax.lax.all_to_all(
            send_x.reshape(n_shards, C2, d), expert_axis, 0, 0,
            tiled=False).astype(x.dtype)
        recv_meta = jax.lax.all_to_all(
            send_meta.reshape(n_shards, C2), expert_axis, 0, 0, tiled=False)
        recv_x = recv_x.reshape(n_shards * C2, d)
        recv_meta = recv_meta.reshape(n_shards * C2)

        # local capacity dispatch to this shard's E_loc experts
        valid = recv_meta >= 0
        eid = jnp.where(valid, recv_meta, 0)
        C3 = max(8, int(math.ceil(n_shards * C2 / max(E_loc, 1)
                                  * capacity_mult)))
        oh = jax.nn.one_hot(eid, E_loc, dtype=jnp.int32) \
            * valid[:, None].astype(jnp.int32)
        pos = (jnp.cumsum(oh, axis=0) - oh)[jnp.arange(n_shards * C2), eid]
        keep3 = valid & (pos < C3)
        dst3 = jnp.where(keep3, eid * C3 + pos, E_loc * C3)
        buf = jnp.zeros((E_loc * C3 + 1, d), x.dtype)
        buf = buf.at[dst3].set(recv_x)[:-1]
        ein = buf.reshape(E_loc, C3, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ein, wg)) \
            * jnp.einsum("ecd,edf->ecf", ein, wu)
        out = jnp.einsum("ecf,efd->ecd", h, wd)               # partial (f/tp)

        # undo local dispatch, send back PARTIAL sums (the tp reduction
        # commutes with the linear combine, so it happens on the tiny
        # token-space output below instead of the padded expert space —
        # [Bl,S,d] vs [E_loc,C3,d] psum payload, ~30x less)
        flat = out.astype(x.dtype).reshape(E_loc * C3, d)
        back = jnp.where(keep3[:, None],
                         flat[jnp.minimum(dst3, E_loc * C3 - 1)], 0.0)
        ret = jax.lax.all_to_all(
            back.reshape(n_shards, C2, d), expert_axis, 0, 0, tiled=False)
        ret = ret.reshape(n_shards * C2, d)
        y_pairs = jnp.where(keep[:, None],
                            ret[jnp.minimum(dst, n_shards * C2 - 1)], 0.0)
        w_pairs = gates.reshape(-1) * keep
        y = jax.ops.segment_sum(
            (y_pairs * w_pairs[:, None]).astype(jnp.float32), tok_ids,
            num_segments=Nl)
        # psum in bf16: halves the payload; the f32 accumulation above
        # already absorbed the k-way gate-weighted sum
        y = jax.lax.psum(y.astype(x.dtype), tp_axis)

        pair_onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)
        # pmean the FACTORS (not the product): the load-balance loss uses
        # global token fractions x global router probs
        frac_tokens = jax.lax.pmean(
            jnp.mean(jnp.sum(pair_onehot, axis=1), axis=0), expert_axis)
        mean_probs = jax.lax.pmean(jnp.mean(probs, axis=0), expert_axis)
        aux = E * jnp.sum(frac_tokens * mean_probs) / k
        return y.reshape(Bl, S, d), aux

    B_spec = batch_axes if batch_axes else expert_axis

    def apply(p: Dict, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        fn = _shard_map(
            local,
            in_specs=(P(None, None), P(expert_axis, None, tp_axis),
                      P(expert_axis, None, tp_axis),
                      P(expert_axis, tp_axis, None), P(B_spec, None, None)),
            out_specs=(P(B_spec, None, None), P()),
        )
        y, aux = fn(p["router"], p["wg"], p["wu"], p["wd"], x)
        if cfg.n_shared_experts:
            y = y + layers.apply_mlp(p["shared"], x.reshape(-1, d),
                                     "swiglu").reshape(x.shape)
        return y, aux

    return apply


def apply_moe_grouped(p: Dict, x: jax.Array, cfg: ModelConfig, *,
                      hooks: Hooks = IDENTITY_HOOKS) -> Tuple[jax.Array, jax.Array]:
    """Token-sorted grouped-GEMM MoE path (uses the ``moe_gemm`` kernel).

    Sorts (token,k) pairs by expert, runs a ragged grouped matmul (no
    capacity drop), and unsorts.  Used on the single-host engine path where
    exact no-drop semantics are preferred; the capacity path above is the
    SPMD/dry-run path.
    """
    orig_shape = x.shape
    d = cfg.d_model
    xf = x.reshape(-1, d)
    N = xf.shape[0]
    E, k = cfg.n_experts, cfg.experts_per_token

    gates, experts, probs = route(p, xf, cfg)
    flat_expert = experts.reshape(-1)                        # [N*k]
    order = jnp.argsort(flat_expert)
    token_ids = jnp.repeat(jnp.arange(N), k)[order]
    x_sorted = xf[token_ids]                                 # [N*k, D]
    group_sizes = jnp.bincount(flat_expert, length=E)

    h = jax.nn.silu(kops.moe_gemm(x_sorted, p["wg"], group_sizes)) \
        * kops.moe_gemm(x_sorted, p["wu"], group_sizes)
    out_sorted = kops.moe_gemm(h, p["wd"], group_sizes)      # [N*k, D]

    w_sorted = gates.reshape(-1)[order]
    y = jax.ops.segment_sum((out_sorted * w_sorted[:, None]).astype(jnp.float32),
                            token_ids, num_segments=N).astype(x.dtype)
    if cfg.n_shared_experts:
        y = y + layers.apply_mlp(p["shared"], xf, "swiglu",
                                 hook=hooks.ffn_hidden)
    pair_onehot = jax.nn.one_hot(experts, E, dtype=jnp.float32)
    frac_tokens = jnp.mean(jnp.sum(pair_onehot, axis=1), axis=0)
    aux = E * jnp.sum(frac_tokens * jnp.mean(probs, axis=0)) / k
    return y.reshape(orig_shape), aux
