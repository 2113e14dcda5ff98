"""RWKV-6 WKV linear recurrence as a Pallas TPU kernel.

Chunk-parallel formulation: within a chunk the stabilized decay matrix
(all exponent differences ≤ 0) turns the recurrence into two small matmuls;
the (dk × dv) state is carried across chunks in VMEM scratch (minor grid
axis = sequential on TPU).  This is the TPU-native equivalent of the CUDA
wkv6 kernel's per-timestep loop — the token loop disappears into the
decay-matrix matmul, which the MXU executes densely.

Grid: (B·H, nc)  — nc minor/sequential.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mm(a, b, contract=((1,), (0,))):
    """f32 matmul at full precision (the cumulative sums feed exp())."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, s_scr, *, Q: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0].astype(jnp.float32)          # (Q, dk)
    k = k_ref[0].astype(jnp.float32)          # (Q, dk)
    v = v_ref[0].astype(jnp.float32)          # (Q, dv)
    w = w_ref[0].astype(jnp.float32)          # (Q, dk) log-decay ≤ 0
    u = u_ref[0].astype(jnp.float32)          # (1, dk) bonus

    # Inclusive cumulative sum over the chunk as a lower-triangular-ones
    # matmul (Mosaic has no cumsum); the column sums give the chunk total.
    row = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    cw = _mm((row >= col).astype(jnp.float32), w)            # (Q, dk)
    cw_last = _mm(w, jnp.ones((Q, 1), jnp.float32),
                  ((0,), (0,)))                               # (dk, 1)
    # intra: scores[t,i] = Σ_c r[t,c]·e^{cw[t]-w[t]-cw[i]}·k[i,c], i < t.
    # The exponent cw[t]-w[t]-cw[i] ≤ 0 for i ≤ t-1, so exp() never
    # overflows (the factored e^{-cw[i]} alone would).
    rd = r * jnp.exp(cw - w)                  # (Q, dk)
    expo = (cw - w)[:, None, :] - cw[None, :, :]              # (Q, Q, dk)
    past = jax.lax.broadcasted_iota(jnp.int32, expo.shape, 0) > \
        jax.lax.broadcasted_iota(jnp.int32, expo.shape, 1)    # i < t
    dec = jnp.where(past, jnp.exp(expo), 0.0)
    scores = jnp.sum(r[:, None, :] * dec * k[None, :, :], axis=-1)  # (Q, Q)
    y = _mm(scores, v)
    # diagonal bonus
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)          # (Q, 1)
    y = y + diag * v
    # inter-chunk: y += (r ⊙ e^{cw-w}) S_prev
    y = y + _mm(rd, s_scr[...])
    # state: S = diag(e^{cw_last}) S + Σ_i e^{cw_last - cw_i} k_i ⊗ v_i
    kdec = k * jnp.exp(cw[Q - 1:Q, :] - cw)                   # (Q, dk)
    s_scr[...] = s_scr[...] * jnp.exp(cw_last) + _mm(kdec, v, ((0,), (0,)))
    y_ref[0] = y.astype(y_ref.dtype)


def wkv6(r: jax.Array, k: jax.Array, v: jax.Array, logw: jax.Array,
         u: jax.Array, *, chunk: int = 32,
         interpret: bool = False) -> jax.Array:
    """r,k,v,logw: (B,S,H,hd); u: (H,hd).  Returns y (B,S,H,hd) f32."""
    B, S, H, hd = r.shape
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q

    def flat(t):
        return t.transpose(0, 2, 1, 3).reshape(B * H, S, hd)

    uf = jnp.broadcast_to(u[None], (B, H, hd)).reshape(B * H, 1, hd)
    grid = (B * H, nc)
    from jax.experimental.pallas import tpu as pltpu
    y = pl.pallas_call(
        functools.partial(_kernel, Q=Q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, Q, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, Q, hd), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, hd), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Q, hd), lambda bh, ci: (bh, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(flat(r), flat(k), flat(v), flat(logw), uf)
    return y.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
