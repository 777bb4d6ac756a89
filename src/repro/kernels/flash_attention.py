"""Pallas TPU flash attention (prefill): causal GQA with online softmax.

Grid layout: ``(batch, q_head, q_blocks, kv_blocks)`` with the kv-block
dimension innermost and sequential ("arbitrary"), carrying the online-softmax
state (m, l, acc) in VMEM scratch.  Causally-masked-out kv blocks are skipped
with ``pl.when`` — on real TPU this prunes ~half the grid.

Block shapes are the VMEM working set:
  q block   [1, block_q, 1, D]
  k/v block [1, block_k, 1, D]   (the kv head of the current q head)
  scratch   acc [block_q, D] f32, m/l [block_q, 128] f32

``D`` and the block sizes should be multiples of 128 for MXU alignment on
hardware; the kernel itself is shape-generic and is validated on CPU in
interpret mode against ``ref.flash_attention``.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, seq_q: int,
                  seq_k: int, causal: bool):
    i = pl.program_id(2)              # q block index
    j = pl.program_id(3)              # kv block index
    nk = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal offset: query at row r attends keys <= r + (seq_k - seq_q)
    offset = seq_k - seq_q
    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)

    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)           # [bq, D]
        k = k_ref[0, :, 0, :].astype(jnp.float32)           # [bk, D]
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        # zero the OOB kv padding rows: p is 0 there, but 0 * garbage = NaN
        k_valid = (j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_k
        v = jnp.where(k_valid, v, 0.0)
        s = (q @ k.T) * scale                               # [bq, bk]
        if causal:
            s = jnp.where(q_pos + offset >= k_pos, s, NEG_INF)
        s = jnp.where(k_pos < seq_k, s, NEG_INF)            # kv padding

        m_prev = m_ref[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[:, 0] = l_ref[:, 0] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + p @ v
        m_ref[:, 0] = m_cur

    if causal:
        # skip kv blocks fully above the diagonal
        pl.when(j * block_k <= (i + 1) * block_q - 1 + offset)(_compute)
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        l = l_ref[:, 0]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, 0, :] = (acc_ref[...] / safe[:, None]).astype(o_ref.dtype)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    scale: float, block_q: int = 128, block_k: int = 128,
                    causal: bool = True, interpret: Optional[bool] = None) -> jax.Array:
    """q: [B,S,H,D]; k/v: [B,T,KV,D] -> [B,S,H,D] (causal, GQA)."""
    B, S, H, D = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, T)
    nq = pl.cdiv(S, block_q)
    nk = pl.cdiv(T, block_k)

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        seq_q=S, seq_k=T, causal=causal)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
            pl.BlockSpec((1, block_k, 1, D), lambda b, h, i, j: (b, j, h // G, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
