"""Pallas TPU grouped expert GEMM (megablox-style) for the weights pool.

``out[i] = x[i] @ w[expert_of(i)]`` over token-sorted ``x`` with ragged
per-expert group sizes.  Grid ``(row_blocks, col_blocks, experts)`` with the
expert dimension innermost/sequential: each (i, j) output block accumulates
contributions from every expert whose row range overlaps row block i —
non-overlapping experts are skipped with ``pl.when``, so on hardware the
effective grid is ~(row_blocks + E) x col_blocks matmuls.

Group offsets arrive via scalar prefetch so both the skip predicate and the
row masking are resolved before the DMA.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _moe_gemm_kernel(offsets_ref, x_ref, w_ref, o_ref, acc_ref, *,
                     block_n: int):
    i = pl.program_id(0)          # row block
    g = pl.program_id(2)          # expert (innermost, sequential)
    ne = pl.num_programs(2)

    @pl.when(g == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    start = offsets_ref[g]
    end = offsets_ref[g + 1]
    row0 = i * block_n

    @pl.when((start < row0 + block_n) & (end > row0))
    def _compute():
        x = x_ref[...].astype(jnp.float32)                   # [bn, K]
        w = w_ref[0].astype(jnp.float32)                     # [K, bm]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_n, 1), 0)
        mask = (rows >= start) & (rows < end)                # [bn,1]
        acc_ref[...] += jnp.where(mask, x, 0.0) @ w

    @pl.when(g == ne - 1)
    def _finish():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def moe_gemm(x: jax.Array, w: jax.Array, group_sizes: jax.Array, *,
             block_n: int = 128, block_m: int = 128,
             interpret: Optional[bool] = None) -> jax.Array:
    """x: [N,K] token-sorted; w: [E,K,M]; group_sizes: [E] -> [N,M]."""
    N, K = x.shape
    E, _, M = w.shape
    block_n = min(block_n, N)
    block_m = min(block_m, M)
    nn = pl.cdiv(N, block_n)
    nm = pl.cdiv(M, block_m)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(group_sizes).astype(jnp.int32)])

    kernel = functools.partial(_moe_gemm_kernel, block_n=block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nn, nm, E),
        in_specs=[
            pl.BlockSpec((block_n, K), lambda i, j, g, off: (i, 0)),
            pl.BlockSpec((1, K, block_m), lambda i, j, g, off: (g, 0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_m),
                               lambda i, j, g, off: (i, j)),
        scratch_shapes=[pltpu.VMEM((block_n, block_m), jnp.float32)],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((N, M), x.dtype),
        interpret=resolve_interpret(interpret),
    )(offsets, x, w)
