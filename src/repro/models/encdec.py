"""Encoder-decoder transformer (SeamlessM4T backbone).

Speech frontend is a STUB per the assignment: the encoder consumes
precomputed frame embeddings (batch, n_frames, d_model).  Decoder has causal
self-attention (RoPE) + cross-attention to the encoder output; decode caches
self-KV per step and cross-KV once at prefill.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import nn
from repro.models.attention import attention, decode_attention
from repro.models.transformer import (ModelOpts, _qkv, attn_apply,
                                      attn_decode, attn_init, _ring_write)
from repro.parallel.axes import shard


def encdec_init(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or nn.dtype_of(cfg.dtype)
    ks = jax.random.split(key, 10)
    E, L, D = cfg.enc_layers, cfg.n_layers, cfg.d_model
    return {
        "emb": nn.embed_init(ks[0], cfg.vocab_size, D, dtype),
        "frame_proj": nn.dense_init(ks[1], D, D, dtype),      # frontend stub
        "enc_pos": (jax.random.normal(ks[2], (cfg.n_frames, D), jnp.float32)
                    * 0.02).astype(dtype),
        "enc_layers": {
            "ln1": jnp.zeros((E, D), dtype),
            "attn": attn_init(ks[3], cfg, E, dtype),
            "ln2": jnp.zeros((E, D), dtype),
            "mlp": nn.ffn_init(ks[4], D, cfg.d_ff, cfg.act, dtype, n_stack=E),
        },
        "enc_ln_f": jnp.zeros((D,), dtype),
        "dec_layers": {
            "ln1": jnp.zeros((L, D), dtype),
            "attn": attn_init(ks[5], cfg, L, dtype),
            "lnx": jnp.zeros((L, D), dtype),
            "xattn": attn_init(ks[6], cfg, L, dtype),
            "ln2": jnp.zeros((L, D), dtype),
            "mlp": nn.ffn_init(ks[7], D, cfg.d_ff, cfg.act, dtype, n_stack=L),
        },
        "ln_f": jnp.zeros((D,), dtype),
        "head": nn.dense_init(ks[8], D, cfg.vocab_size, dtype),
    }


def encode(params, frames, cfg: ModelConfig, opts: ModelOpts):
    """frames: (B, F, D) precomputed embeddings -> (B, F, D)."""
    x = frames.astype(params["frame_proj"].dtype) @ params["frame_proj"]
    x = x + params["enc_pos"][None, : x.shape[1], :]
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[1])[None, :]

    def body(x, lp):
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        x = x + attn_apply(lp["attn"], h, cfg, positions, causal=False)
        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        return x + nn.ffn_apply(lp["mlp"], h, cfg.act), None

    body = jax.checkpoint(body) if opts.remat == "full" else body
    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return nn.rmsnorm(x, params["enc_ln_f"], cfg.norm_eps)


def _cross_kv(lp_x, enc_out, cfg):
    """Cross-attention K/V from encoder output.  (B,F,Hkv,hd) each."""
    B, F, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ lp_x["wk"]).reshape(B, F, cfg.n_kv_heads, hd)
    v = (enc_out @ lp_x["wv"]).reshape(B, F, cfg.n_kv_heads, hd)
    return k, v


def _decoder_block(lp, x, enc_out, cfg, positions):
    h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    x = x + attn_apply(lp["attn"], h, cfg, positions, causal=True)
    h = nn.rmsnorm(x, lp["lnx"], cfg.norm_eps)
    k, v = _cross_kv(lp["xattn"], enc_out, cfg)
    B, S, _ = h.shape
    hd = cfg.resolved_head_dim
    q = (h @ lp["xattn"]["wq"]).reshape(B, S, cfg.n_heads, hd)
    o = attention(q, k, v, causal=False, chunk_q=cfg.attn_chunk_q,
                  chunk_k=cfg.attn_chunk_k)
    x = x + o.reshape(B, S, -1) @ lp["xattn"]["wo"]
    h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    return x + nn.ffn_apply(lp["mlp"], h, cfg.act)


def encdec_forward(params, batch, cfg: ModelConfig, opts: ModelOpts):
    enc_out = encode(params, batch["frames"], cfg, opts)
    tokens = batch["tokens"]
    x = nn.embed_lookup(params["emb"], tokens)
    x = shard(x, "batch", "seq", "embed")
    positions = jnp.arange(x.shape[1])[None, :]

    def body(x, lp):
        return _decoder_block(lp, x, enc_out, cfg, positions), None

    body = jax.checkpoint(body) if opts.remat == "full" else body
    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    return nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)


def encdec_loss(params, batch, cfg: ModelConfig, opts: ModelOpts):
    tokens = batch["tokens"]
    h = encdec_forward(params, batch, cfg, opts)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    loss = nn.cross_entropy_loss(lambda hh: hh @ params["head"], h, labels,
                                 mask, chunk=opts.loss_chunk)
    return loss, {"ce": loss}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def encdec_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or nn.dtype_of(cfg.dtype)
    L = cfg.n_layers
    hd = cfg.resolved_head_dim
    return {
        "pos": jnp.zeros((), jnp.int32),
        "self_k": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "self_v": jnp.zeros((L, batch, max_len, cfg.n_kv_heads, hd), dtype),
        "cross_k": jnp.zeros((L, batch, cfg.n_frames, cfg.n_kv_heads, hd), dtype),
        "cross_v": jnp.zeros((L, batch, cfg.n_frames, cfg.n_kv_heads, hd), dtype),
    }


def encdec_prefill(params, cache, batch, cfg: ModelConfig, opts: ModelOpts):
    """Encode frames, precompute cross-KV, prefill decoder self-KV."""
    enc_out = encode(params, batch["frames"], cfg, opts)
    tokens = batch["tokens"]
    x = nn.embed_lookup(params["emb"], tokens)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]

    def body(carry, i):
        x, sk, sv, ck, cv = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), params["dec_layers"])
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        q, k, v = _qkv(lp["attn"], h, cfg, positions)
        B = x.shape[0]
        o = attention(q, k, v, causal=True, chunk_q=cfg.attn_chunk_q,
                      chunk_k=cfg.attn_chunk_k)
        x = x + o.reshape(B, S, -1) @ lp["attn"]["wo"]
        sk_l = jax.lax.dynamic_index_in_dim(sk, i, 0, keepdims=False)
        sv_l = jax.lax.dynamic_index_in_dim(sv, i, 0, keepdims=False)
        sk = jax.lax.dynamic_update_index_in_dim(sk, _ring_write(sk_l, k, 0), i, 0)
        sv = jax.lax.dynamic_update_index_in_dim(sv, _ring_write(sv_l, v, 0), i, 0)

        h = nn.rmsnorm(x, lp["lnx"], cfg.norm_eps)
        kx, vx = _cross_kv(lp["xattn"], enc_out, cfg)
        hd = cfg.resolved_head_dim
        qx = (h @ lp["xattn"]["wq"]).reshape(B, S, cfg.n_heads, hd)
        o = attention(qx, kx, vx, causal=False, chunk_q=cfg.attn_chunk_q,
                      chunk_k=cfg.attn_chunk_k)
        x = x + o.reshape(B, S, -1) @ lp["xattn"]["wo"]
        ck = jax.lax.dynamic_update_index_in_dim(ck, kx.astype(ck.dtype), i, 0)
        cv = jax.lax.dynamic_update_index_in_dim(cv, vx.astype(cv.dtype), i, 0)

        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + nn.ffn_apply(lp["mlp"], h, cfg.act)
        return (x, sk, sv, ck, cv), None

    (x, sk, sv, ck, cv), _ = jax.lax.scan(
        body, (x, cache["self_k"], cache["self_v"], cache["cross_k"],
               cache["cross_v"]), jnp.arange(cfg.n_layers))
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, -1] @ params["head"]
    return {"pos": jnp.asarray(S, jnp.int32), "self_k": sk, "self_v": sv,
            "cross_k": ck, "cross_v": cv}, logits


def encdec_decode_step(params, cache, tokens, cfg: ModelConfig,
                       opts: ModelOpts):
    pos = cache["pos"]
    x = nn.embed_lookup(params["emb"], tokens[:, None])

    def body(carry, i):
        x, sk, sv = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), params["dec_layers"])
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        sk_l = jax.lax.dynamic_index_in_dim(sk, i, 0, keepdims=False)
        sv_l = jax.lax.dynamic_index_in_dim(sv, i, 0, keepdims=False)
        a, sk_l, sv_l = attn_decode(lp["attn"], h, cfg, sk_l, sv_l, pos)
        x = x + a
        sk = jax.lax.dynamic_update_index_in_dim(sk, sk_l, i, 0)
        sv = jax.lax.dynamic_update_index_in_dim(sv, sv_l, i, 0)

        h = nn.rmsnorm(x, lp["lnx"], cfg.norm_eps)
        hd = cfg.resolved_head_dim
        B = x.shape[0]
        qx = (h[:, 0] @ lp["xattn"]["wq"]).reshape(B, cfg.n_heads, hd)
        ck_l = jax.lax.dynamic_index_in_dim(cache["cross_k"], i, 0, keepdims=False)
        cv_l = jax.lax.dynamic_index_in_dim(cache["cross_v"], i, 0, keepdims=False)
        o = decode_attention(qx, ck_l, cv_l, jnp.asarray(ck_l.shape[1]))
        x = x + (o.reshape(B, 1, -1) @ lp["xattn"]["wo"])

        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        x = x + nn.ffn_apply(lp["mlp"], h, cfg.act)
        return (x, sk, sv), None

    (x, sk, sv), _ = jax.lax.scan(
        body, (x, cache["self_k"], cache["self_v"]), jnp.arange(cfg.n_layers))
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0] @ params["head"]
    return {"pos": pos + 1, "self_k": sk, "self_v": sv,
            "cross_k": cache["cross_k"], "cross_v": cache["cross_v"]}, logits
