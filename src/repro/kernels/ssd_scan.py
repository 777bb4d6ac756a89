"""Pallas TPU Mamba2 SSD chunked scan.

Implements the state-space-duality chunk decomposition (arXiv:2405.21060 §6)
with the chunk dimension as the innermost sequential grid axis, carrying the
recurrent state ``h [bh, P, N]`` in f32 VMEM scratch across chunks:

  intra:  Y[t] += sum_{s<=t} (C_t.B_s) exp(La_t - La_s) dt_s x_s   (quadratic
          within the chunk -> MXU matmuls)
  state:  h <- exp(La_L) h + sum_s exp(La_L - La_s) dt_s (B_s (x) x_s)
  inter:  Y[t] += C_t . (exp(La_t) h_prev)

Grid ``(batch, head_blocks, chunks)``.  B/C group projections are expanded
to per-head upstream in the wrapper (cheap: N is small) so the kernel blocks
stay rectangular.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _ssd_kernel(x_ref, dt_ref, A_ref, B_ref, C_ref, h0_ref,
                y_ref, hout_ref, h_ref, *, chunk: int):
    c = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = h0_ref[0].astype(jnp.float32)           # [bh,P,N]

    x = x_ref[0].astype(jnp.float32)                         # [L,bh,P]
    dt = dt_ref[0].astype(jnp.float32)                       # [L,bh]
    A = A_ref[...].astype(jnp.float32)                       # [bh]
    Bm = B_ref[0].astype(jnp.float32)                        # [L,bh,N]
    Cm = C_ref[0].astype(jnp.float32)                        # [L,bh,N]

    a = dt * A[None, :]                                      # [L,bh] log decay
    La = jnp.cumsum(a, axis=0)
    La_tot = La[-1]                                          # [bh]

    # --- intra-chunk (quadratic in L) -----------------------------------
    diff = La[:, None, :] - La[None, :, :]                   # [L,S,bh]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    decay = jnp.where(tri[..., None], jnp.exp(diff), 0.0)    # [L,S,bh]
    scores = jnp.einsum("lhn,shn->lsh", Cm, Bm) * decay
    y = jnp.einsum("lsh,sh,shp->lhp", scores, dt, x)

    # --- inter-chunk from carried state ----------------------------------
    h = h_ref[...]                                           # [bh,P,N]
    y += jnp.einsum("lhn,hpn->lhp", Cm * jnp.exp(La)[..., None], h)
    y_ref[0] = y.astype(y_ref.dtype)

    # --- state update ------------------------------------------------------
    decay_to_end = jnp.exp(La_tot[None, :] - La)             # [L,bh]
    S_c = jnp.einsum("sh,shn,shp->hpn", dt * decay_to_end, Bm, x)
    h_ref[...] = h * jnp.exp(La_tot)[:, None, None] + S_c

    @pl.when(c == nc - 1)
    def _emit():
        hout_ref[0] = h_ref[...]


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C_: jax.Array, *, chunk: int = 64,
             h0: Optional[jax.Array] = None, block_h: int = 8,
             interpret: Optional[bool] = None) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ``ref.ssd_scan``.

    x: [B,S,H,P]; dt: [B,S,H]; A: [H]; B_/C_: [B,S,G,N]; h0: [B,H,P,N].
    S must be divisible by ``chunk``; H by ``block_h`` (or block_h clamps).
    """
    Bb, S, H, P = x.shape
    G, N = B_.shape[2], B_.shape[3]
    assert S % chunk == 0, f"seq {S} % chunk {chunk} != 0"
    nc = S // chunk
    block_h = min(block_h, H)
    while H % block_h:
        block_h -= 1
    nh = H // block_h

    rep = H // G
    Bh = jnp.repeat(B_, rep, axis=2)                         # [B,S,H,N]
    Ch = jnp.repeat(C_, rep, axis=2)
    if h0 is None:
        h0 = jnp.zeros((Bb, H, P, N), jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(Bb, nh, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_h, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, block_h), lambda b, h, c: (b, c, h)),
            pl.BlockSpec((block_h,), lambda b, h, c: (h,)),
            pl.BlockSpec((1, chunk, block_h, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, chunk, block_h, N), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, block_h, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_h, P), lambda b, h, c: (b, c, h, 0)),
            pl.BlockSpec((1, block_h, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(x.shape, x.dtype),
            jax.ShapeDtypeStruct((Bb, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_h, P, N), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(x, dt, A, Bh, Ch, h0)
    return y, h_final
