"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060): one grid step per
(batch·head, chunk); the inter-chunk state h (P×N) lives in VMEM scratch and
is carried across the chunk axis (minor, sequential on TPU).  Intra-chunk
work is two MXU matmuls (C·Bᵀ masked by the cumulative-decay matrix, then
against x) plus rank-1 decay scalings — no recurrence at token granularity.

Grid: (B·H, nc)  — nc minor/sequential.
Blocks: x (Q, P); dA (1, Q) of a (B·H, 1, S) array, so the block's last
two dims are tiling-aligned for Q a multiple of 128; B,C (Q, N) indexed by
batch only (heads share B/C for n_groups=1, expressed in the index_map).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, da_ref, b_ref, c_ref, y_ref, h_scr, *, Q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)          # (Q, P)
    da = da_ref[0].astype(jnp.float32)        # (1, Q) row
    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0].astype(jnp.float32)          # (Q, N)

    # Inclusive cumulative sum as lower-triangular-ones matmuls (Mosaic has
    # no cumsum), in both orientations so no vector transpose is needed.
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    mask = row >= col
    tri = mask.astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum = jax.lax.dot_general(tri, da, (((1,), (1,)), ((), ())), precision=hi,
                              preferred_element_type=jnp.float32)  # (Q, 1)
    cum_row = jax.lax.dot_general(da, tri, (((1,), (1,)), ((), ())),
                                  precision=hi,
                                  preferred_element_type=jnp.float32)  # (1,Q)
    cum_last = jnp.sum(da, axis=1, keepdims=True)                 # (1, 1)
    # intra-chunk: L[i,j] = exp(cum_i - cum_j) for i >= j
    L = jnp.where(mask, jnp.exp(cum - cum_row), 0.0)
    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y = jax.lax.dot_general(cb * L, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (Q,P)
    # inter-chunk: y += exp(cum) C · h_prev
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        c, h_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (Q,P)
    # state update: h = exp(cum_Q) h + sum_j exp(cum_Q - cum_j) x_jᵀ B_j
    decay_end = jnp.exp(cum_last - cum)                           # (Q, 1)
    h_scr[...] = h_scr[...] * jnp.exp(cum_last) + jax.lax.dot_general(
        x * decay_end, b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                       # (P,N)
    y_ref[0] = y.astype(y_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B_: jax.Array,
             C: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """x: (B,S,H,P); dt: (B,S,H) post-softplus; A: (H,) negative;
    B_/C: (B,S,N).  Returns y: (B,S,H,P) — D-skip/gating applied outside."""
    Bb, S, H, P = x.shape
    N = B_.shape[-1]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    dA = (dt * A[None, None, :]).astype(jnp.float32)     # (B,S,H)
    xdt = (x * dt[..., None].astype(x.dtype))

    # flatten to (B·H, S, ·)
    xf = xdt.transpose(0, 2, 1, 3).reshape(Bb * H, S, P)
    daf = dA.transpose(0, 2, 1).reshape(Bb * H, 1, S)
    grid = (Bb * H, nc)

    from jax.experimental.pallas import tpu as pltpu
    y = pl.pallas_call(
        functools.partial(_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, Q), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, Q, N), lambda bh, ci, H=H: (bh // H, ci, 0)),
            pl.BlockSpec((1, Q, N), lambda bh, ci, H=H: (bh // H, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, P), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((Bb * H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xf, daf, B_, C)
    return y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
