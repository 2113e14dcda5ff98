"""Multi-head Latent Attention (DeepSeek-V3, arXiv:2412.19437).

Queries through a rank-``q_lora_rank`` bottleneck; keys/values through a
rank-``kv_lora_rank`` compressed latent ``c_kv`` plus a shared rope key.
Training/prefill decompresses to per-head K/V and calls
``attention.attention``.  Decode caches ONLY (c_kv, k_pe) — the MLA memory
win — and uses the absorbed-weight formulation so scores are computed
directly in latent space.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.attention import attention


def mla_init(key, cfg: ModelConfig, n_stack: int, dtype) -> dict:
    ks = jax.random.split(key, 7)
    D, H = cfg.d_model, cfg.n_heads
    qr, kvr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dvh = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "q_a": nn.stacked_dense_init(ks[0], n_stack, D, qr, dtype),
        "q_norm": jnp.zeros((n_stack, qr), dtype),
        "q_b": nn.stacked_dense_init(ks[1], n_stack, qr, H * (dn + dr), dtype),
        "kv_a": nn.stacked_dense_init(ks[2], n_stack, D, kvr + dr, dtype),
        "kv_norm": jnp.zeros((n_stack, kvr), dtype),
        "kv_b": nn.stacked_dense_init(ks[3], n_stack, kvr, H * (dn + dvh), dtype),
        "wo": nn.stacked_dense_init(ks[4], n_stack, H * dvh, D, dtype),
    }


def _project_q(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    H, dn, dr = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q = nn.rmsnorm(x @ p["q_a"], p["q_norm"], cfg.norm_eps) @ p["q_b"]
    q = q.reshape(B, S, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    q_pe = nn.apply_rope(q_pe, positions, cfg.rope_theta)
    return q_nope, q_pe


def _compress_kv(p, x, cfg: ModelConfig, positions):
    """Returns the decode-cacheable latents: c_kv (B,S,kvr), k_pe (B,S,dr)."""
    kvr, dr = cfg.kv_lora_rank, cfg.qk_rope_dim
    ckv = x @ p["kv_a"]                                     # (B,S,kvr+dr)
    c_kv = nn.rmsnorm(ckv[..., :kvr], p["kv_norm"], cfg.norm_eps)
    k_pe = nn.apply_rope(ckv[..., kvr:][:, :, None, :], positions,
                         cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_pe


def mla_attention(p, x, cfg: ModelConfig, positions):
    """Full (train/prefill) MLA.  x: (B,S,D) -> (B,S,D)."""
    B, S, _ = x.shape
    H, dn, dr, dvh = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    q_nope, q_pe = _project_q(p, x, cfg, positions)
    c_kv, k_pe = _compress_kv(p, x, cfg, positions)
    kv = (c_kv @ p["kv_b"]).reshape(B, S, H, dn + dvh)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :], (B, S, H, dr))], axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    o = attention(q, k, v, causal=True, chunk_q=cfg.attn_chunk_q,
                  chunk_k=cfg.attn_chunk_k, scale=1.0 / math.sqrt(dn + dr))
    return o.reshape(B, S, H * dvh) @ p["wo"]


def mla_decode(p, x, cfg: ModelConfig, c_cache, pe_cache, length):
    """Absorbed decode step.  x: (B,1,D); caches: (B,Smax,kvr)/(B,Smax,dr).

    scores_h = q_nope_h · (W_uk_h c) + q_pe_h · k_pe   — computed in latent
    space; output latent re-expanded through W_uv.  Returns (out, new caches).
    """
    B = x.shape[0]
    H, dn, dr, dvh = cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kvr = cfg.kv_lora_rank
    positions = jnp.full((B, 1), length, jnp.int32)
    q_nope, q_pe = _project_q(p, x, cfg, positions)          # (B,1,H,·)
    c_kv, k_pe = _compress_kv(p, x, cfg, positions)          # (B,1,kvr)/(B,1,dr)
    c_cache = jax.lax.dynamic_update_slice(c_cache, c_kv.astype(c_cache.dtype),
                                           (0, length, 0))
    pe_cache = jax.lax.dynamic_update_slice(pe_cache, k_pe.astype(pe_cache.dtype),
                                            (0, length, 0))

    w_kv = p["kv_b"].reshape(kvr, H, dn + dvh)
    w_uk, w_uv = w_kv[..., :dn], w_kv[..., dn:]              # (kvr,H,dn)/(kvr,H,dvh)
    # absorb: q' = q_nope @ W_uk^T  -> latent-space query (B,H,kvr)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope[:, 0], w_uk)
    s = jnp.einsum("bhc,bsc->bhs", q_lat.astype(jnp.float32),
                   c_cache.astype(jnp.float32))
    s = s + jnp.einsum("bhd,bsd->bhs", q_pe[:, 0].astype(jnp.float32),
                       pe_cache.astype(jnp.float32))
    s = s / math.sqrt(dn + dr)
    valid = jnp.arange(c_cache.shape[1]) <= length
    s = jnp.where(valid[None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhs,bsc->bhc", pr, c_cache.astype(jnp.float32))
    o = jnp.einsum("bhc,chv->bhv", o_lat, w_uv.astype(jnp.float32))
    out = o.reshape(B, 1 * H * dvh).astype(x.dtype)[:, None, :] @ p["wo"]
    return out, c_cache, pe_cache
