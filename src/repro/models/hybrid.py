"""Zamba2-style hybrid: Mamba-2 backbone + one parameter-SHARED attention
block applied every ``cfg.attn_every`` SSM layers.

The backbone is a single scanned stack; the shared block is applied inside
the scan under ``lax.cond`` (real branching — not vmapped — so the compiled
step only pays for it on the layers that use it).  Each application point
has its own KV cache (n_app stacked) even though the weights are shared.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import mamba2, nn
from repro.models.transformer import (ModelOpts, attn_apply, attn_decode,
                                      attn_init, _ring_write)
from repro.parallel.axes import shard


def n_shared_apps(cfg: ModelConfig) -> int:
    return cfg.n_layers // cfg.attn_every


def hybrid_init(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or nn.dtype_of(cfg.dtype)
    ks = jax.random.split(key, 6)
    L = cfg.n_layers
    p = {
        "emb": nn.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
        "head": nn.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dtype),
        "ssm_layers": {
            "ln": jnp.zeros((L, cfg.d_model), dtype),
            "mixer": mamba2.mamba2_init(ks[2], cfg, L, dtype),
        },
        "shared": {
            "ln1": jnp.zeros((1, cfg.d_model), dtype),
            "attn": attn_init(ks[3], cfg, 1, dtype),
            "ln2": jnp.zeros((1, cfg.d_model), dtype),
            "mlp": nn.ffn_init(ks[4], cfg.d_model, cfg.d_ff, cfg.act, dtype,
                               n_stack=1),
        },
    }
    return p


def _shared_block(shared, x, cfg, positions):
    sp = jax.tree.map(lambda a: a[0], shared)
    h = nn.rmsnorm(x, sp["ln1"], cfg.norm_eps)
    x = x + attn_apply(sp["attn"], h, cfg, positions)
    h = nn.rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + nn.ffn_apply(sp["mlp"], h, cfg.act)


def hybrid_forward(params, batch, cfg: ModelConfig, opts: ModelOpts):
    tokens = batch["tokens"]
    x = nn.embed_lookup(params["emb"], tokens)
    x = shard(x, "batch", "seq", "embed")
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]

    def body(x, inp):
        lp, i = inp
        h = nn.rmsnorm(x, lp["ln"], cfg.norm_eps)
        x = x + mamba2.mamba2_apply(lp["mixer"], h, cfg)
        x = jax.lax.cond(
            (i % cfg.attn_every) == cfg.attn_every - 1,
            lambda x: _shared_block(params["shared"], x, cfg, positions),
            lambda x: x,
            x)
        return x, None

    body = (jax.checkpoint(body) if opts.remat == "full" else body)
    x, _ = jax.lax.scan(body, x, (params["ssm_layers"],
                                  jnp.arange(cfg.n_layers)))
    return nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)


def hybrid_loss(params, batch, cfg: ModelConfig, opts: ModelOpts):
    tokens = batch["tokens"]
    h = hybrid_forward(params, batch, cfg, opts)
    labels = jnp.roll(tokens, -1, axis=1)
    mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
    loss = nn.cross_entropy_loss(lambda hh: hh @ params["head"], h, labels,
                                 mask, chunk=opts.loss_chunk)
    return loss, {"ce": loss}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def hybrid_init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None):
    dtype = dtype or nn.dtype_of(cfg.dtype)
    napp = n_shared_apps(cfg)
    hd = cfg.resolved_head_dim
    return {
        "pos": jnp.zeros((), jnp.int32),
        "ssm": {
            "conv": jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1,
                               mamba2.d_inner(cfg) + 2 * cfg.ssm_state), dtype),
            "ssm": jnp.zeros((cfg.n_layers, batch, mamba2.n_ssm_heads(cfg),
                              cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        },
        "attn": {
            "k": jnp.zeros((napp, batch, max_len, cfg.n_kv_heads, hd), dtype),
            "v": jnp.zeros((napp, batch, max_len, cfg.n_kv_heads, hd), dtype),
        },
    }


def _shared_block_decode(shared, x, cfg, cache, app_i, pos):
    sp = jax.tree.map(lambda a: a[0], shared)
    k_l = jax.lax.dynamic_index_in_dim(cache["k"], app_i, 0, keepdims=False)
    v_l = jax.lax.dynamic_index_in_dim(cache["v"], app_i, 0, keepdims=False)
    h = nn.rmsnorm(x, sp["ln1"], cfg.norm_eps)
    a, k_l, v_l = attn_decode(sp["attn"], h, cfg, k_l, v_l, pos)
    cache = {
        "k": jax.lax.dynamic_update_index_in_dim(cache["k"], k_l, app_i, 0),
        "v": jax.lax.dynamic_update_index_in_dim(cache["v"], v_l, app_i, 0),
    }
    x = x + a
    h = nn.rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + nn.ffn_apply(sp["mlp"], h, cfg.act), cache


def hybrid_decode_step(params, cache, tokens, cfg: ModelConfig,
                       opts: ModelOpts):
    pos = cache["pos"]
    x = nn.embed_lookup(params["emb"], tokens[:, None])

    def body(carry, i):
        x, ssm_c, attn_c = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), params["ssm_layers"])
        st = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), ssm_c)
        h = nn.rmsnorm(x, lp["ln"], cfg.norm_eps)
        out, st = mamba2.mamba2_decode_step(lp["mixer"], h, st, cfg)
        x = x + out
        ssm_c = jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s, i, 0),
            ssm_c, st)

        def with_attn(args):
            x, attn_c = args
            return _shared_block_decode(params["shared"], x, cfg, attn_c,
                                        i // cfg.attn_every, pos)

        x, attn_c = jax.lax.cond(
            (i % cfg.attn_every) == cfg.attn_every - 1,
            with_attn, lambda args: args, (x, attn_c))
        return (x, ssm_c, attn_c), None

    (x, ssm_c, attn_c), _ = jax.lax.scan(
        body, (x, cache["ssm"], cache["attn"]), jnp.arange(cfg.n_layers))
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, 0] @ params["head"]
    return {"pos": pos + 1, "ssm": ssm_c, "attn": attn_c}, logits


def hybrid_prefill(params, cache, batch, cfg: ModelConfig, opts: ModelOpts):
    tokens = batch["tokens"]
    x = nn.embed_lookup(params["emb"], tokens)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]

    def body(carry, i):
        x, ssm_c, attn_c = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), params["ssm_layers"])
        h = nn.rmsnorm(x, lp["ln"], cfg.norm_eps)
        out, st = mamba2.mamba2_apply(lp["mixer"], h, cfg, return_state=True)
        x = x + out
        ssm_c = jax.tree.map(
            lambda a, s: jax.lax.dynamic_update_index_in_dim(a, s.astype(a.dtype), i, 0),
            ssm_c, st)

        def with_attn(args):
            x, attn_c = args
            sp = jax.tree.map(lambda a: a[0], params["shared"])
            h = nn.rmsnorm(x, sp["ln1"], cfg.norm_eps)
            from repro.models.transformer import _qkv
            from repro.models.attention import attention
            q, k, v = _qkv(sp["attn"], h, cfg, positions)
            o = attention(q, k, v, causal=True, chunk_q=cfg.attn_chunk_q,
                          chunk_k=cfg.attn_chunk_k)
            B = x.shape[0]
            x = x + o.reshape(B, S, -1) @ sp["attn"]["wo"]
            app_i = i // cfg.attn_every
            k_l = jax.lax.dynamic_index_in_dim(attn_c["k"], app_i, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(attn_c["v"], app_i, 0, keepdims=False)
            attn_c = {
                "k": jax.lax.dynamic_update_index_in_dim(
                    attn_c["k"], _ring_write(k_l, k, 0), app_i, 0),
                "v": jax.lax.dynamic_update_index_in_dim(
                    attn_c["v"], _ring_write(v_l, v, 0), app_i, 0),
            }
            h = nn.rmsnorm(x, sp["ln2"], cfg.norm_eps)
            return x + nn.ffn_apply(sp["mlp"], h, cfg.act), attn_c

        x, attn_c = jax.lax.cond(
            (i % cfg.attn_every) == cfg.attn_every - 1,
            with_attn, lambda args: args, (x, attn_c))
        return (x, ssm_c, attn_c), None

    (x, ssm_c, attn_c), _ = jax.lax.scan(
        body, (x, cache["ssm"], cache["attn"]), jnp.arange(cfg.n_layers))
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = x[:, -1] @ params["head"]
    return {"pos": jnp.asarray(S, jnp.int32), "ssm": ssm_c, "attn": attn_c}, logits
