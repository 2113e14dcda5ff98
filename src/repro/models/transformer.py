"""Decoder-only transformer LM assembly (dense / MoE / MLA / VLM families).

Layer stacks are *scanned*: per-layer params are stacked on a leading axis
and iterated with ``jax.lax.scan`` (or indexed with dynamic slices for the
decode path), so compiled HLO size is independent of depth — essential for
compiling 61–81-layer models on the 512-device dry-run mesh.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels import splash
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import nn
from repro.models.attention import attention, decode_attention
from repro.parallel.axes import shard


@dataclass(frozen=True)
class ModelOpts:
    """Runtime/compilation knobs (NOT architecture — see ModelConfig)."""
    remat: str = "none"              # none | full | dots
    attn_schedule: str = "dense"     # dense | triangle
    loss_chunk: int = 2048
    moe_token_chunk: int = 65536
    mtp: bool = True
    aux_loss_weight: float = 0.01
    mtp_loss_weight: float = 0.3


# The training path's ``jax.named_scope`` names: ``embed`` (token embedding),
# ``layers`` (the stacked-layer scan), inside each block ``norm``, ``attn``,
# ``mlp`` or ``moe``, the final ``norm``, ``loss`` (head matmul and
# cross-entropy) and ``optimizer`` (``train.optimizer.opt_update``).  They
# change the compiled HLO's ``op_name`` metadata (and a few names the
# compiler derives from it), never its instructions.  The benchmark reads
# them (``perfbench/lib/scopes.py``) to split the device's time in a
# profiler trace by layer.
SCOPES = ("embed", "layers", "norm", "attn", "mlp", "moe", "loss", "optimizer")
EMBED, LAYERS, NORM, ATTN, MLP, MOE, LOSS, OPTIMIZER = SCOPES


def _maybe_remat(fn, opts: ModelOpts):
    """GC (``remat="full"``) recomputes the layer from its input, except the
    splash kernel's residuals (``kernels.splash.RESIDUALS``: q, k, v, its
    output and log-sum-exp), which it saves: the backward then runs no
    forward kernel and no q/k/v projection again.  Off the kernel path
    nothing carries that name, and nothing but the layer input is saved."""
    if opts.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies
                              .save_only_these_names(splash.RESIDUALS))
    if opts.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return fn


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, n_stack: int, dtype) -> dict:
    ks = jax.random.split(key, 4)
    D, hd = cfg.d_model, cfg.resolved_head_dim
    Hq, Hkv = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": nn.stacked_dense_init(ks[0], n_stack, D, Hq * hd, dtype),
        "wk": nn.stacked_dense_init(ks[1], n_stack, D, Hkv * hd, dtype),
        "wv": nn.stacked_dense_init(ks[2], n_stack, D, Hkv * hd, dtype),
        "wo": nn.stacked_dense_init(ks[3], n_stack, Hq * hd, D, dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((n_stack, Hq * hd), dtype)
        p["bk"] = jnp.zeros((n_stack, Hkv * hd), dtype)
        p["bv"] = jnp.zeros((n_stack, Hkv * hd), dtype)
    return p


def _qkv(p, x, cfg: ModelConfig, positions):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ p["wq"] + (p.get("bq", 0))
    k = x @ p["wk"] + (p.get("bk", 0))
    v = x @ p["wv"] + (p.get("bv", 0))
    q = q.reshape(B, S, cfg.n_heads, hd)
    k = k.reshape(B, S, cfg.n_kv_heads, hd)
    v = v.reshape(B, S, cfg.n_kv_heads, hd)
    q = nn.apply_rope(q, positions, cfg.rope_theta)
    k = nn.apply_rope(k, positions, cfg.rope_theta)
    # NOTE: seq dim deliberately unsharded here — under sequence-parallel
    # rules the model axis belongs to heads inside attention.
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    return q, k, v


def attn_apply(p, x, cfg: ModelConfig, positions, opts: ModelOpts,
               causal: bool = True, kv_override=None):
    """Full-sequence attention.  kv_override: (k, v) for cross-attention."""
    B, S, _ = x.shape
    q, k, v = _qkv(p, x, cfg, positions)
    if kv_override is not None:
        k, v = kv_override
    o = attention(q, k, v, causal=causal, chunk_q=cfg.attn_chunk_q,
                  chunk_k=cfg.attn_chunk_k, window=cfg.sliding_window,
                  schedule=opts.attn_schedule)
    o = shard(o, "batch", "seq", "heads", None)
    return o.reshape(B, S, -1) @ p["wo"]


def attn_decode(p, x, cfg: ModelConfig, k_cache, v_cache, length):
    """One-token step.  x: (B,1,D); caches (B,Smax,Hkv,hd).  Sliding-window
    models use a ring buffer of size ≤ window."""
    B = x.shape[0]
    Smax = k_cache.shape[1]
    positions = jnp.full((B, 1), length, jnp.int32)
    q, k, v = _qkv(p, x, cfg, positions)
    slot = length % Smax if cfg.sliding_window else length
    k_cache = jax.lax.dynamic_update_slice(k_cache, k.astype(k_cache.dtype),
                                           (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v.astype(v_cache.dtype),
                                           (0, slot, 0, 0))
    o = decode_attention(q[:, 0], k_cache, v_cache,
                         jnp.minimum(length + 1, Smax))
    return (o.reshape(B, 1, -1) @ p["wo"]), k_cache, v_cache


# ---------------------------------------------------------------------------
# Layer (block) init / apply
# ---------------------------------------------------------------------------

def block_init(key, cfg: ModelConfig, n_stack: int, kind: str, dtype) -> dict:
    """kind ∈ {dense, moe}.  MLA is selected by cfg.mla."""
    ka, kf = jax.random.split(key)
    p = {
        "ln1": jnp.zeros((n_stack, cfg.d_model), dtype),
        "ln2": jnp.zeros((n_stack, cfg.d_model), dtype),
        "attn": (mla_mod.mla_init(ka, cfg, n_stack, dtype) if cfg.mla
                 else attn_init(ka, cfg, n_stack, dtype)),
    }
    if kind == "moe":
        p["moe"] = moe_mod.moe_init(kf, cfg, n_stack, dtype)
    else:
        p["mlp"] = nn.ffn_init(kf, cfg.d_model, cfg.d_ff, cfg.act, dtype,
                               n_stack=n_stack)
    return p


def block_apply(lp, x, cfg: ModelConfig, positions, opts: ModelOpts):
    """Pre-norm residual block.  Returns (x, aux_loss)."""
    with jax.named_scope(NORM):
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
    with jax.named_scope(ATTN):
        if cfg.mla:
            a = mla_mod.mla_attention(lp["attn"], h, cfg, positions,
                                      schedule=opts.attn_schedule)
        else:
            a = attn_apply(lp["attn"], h, cfg, positions, opts)
    x = shard(x + a, "batch", "seq", "embed")
    with jax.named_scope(NORM):
        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
    if "moe" in lp:
        with jax.named_scope(MOE):
            f, aux = moe_mod.moe_apply(lp["moe"], h, cfg, opts.moe_token_chunk)
    else:
        with jax.named_scope(MLP):
            f, aux = nn.ffn_apply(lp["mlp"], h, cfg.act), 0.0
    x = shard(x + f, "batch", "seq", "embed")
    return x, aux


# ---------------------------------------------------------------------------
# Full decoder
# ---------------------------------------------------------------------------

def decoder_init(key, cfg: ModelConfig, dtype=None) -> dict:
    dtype = dtype or nn.dtype_of(cfg.dtype)
    ks = jax.random.split(key, 6)
    p: dict = {
        "emb": nn.embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype),
        "ln_f": jnp.zeros((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = nn.dense_init(ks[1], cfg.d_model, cfg.vocab_size, dtype)
    if cfg.n_experts:
        if cfg.n_dense_layers:
            p["dense_layers"] = block_init(ks[2], cfg, cfg.n_dense_layers,
                                           "dense", dtype)
        p["moe_layers"] = block_init(ks[3], cfg, cfg.n_moe_layers, "moe", dtype)
    else:
        p["layers"] = block_init(ks[2], cfg, cfg.n_layers, "dense", dtype)
    if cfg.frontend == "vision":
        p["patch_proj"] = nn.dense_init(ks[4], cfg.d_model, cfg.d_model, dtype)
    if cfg.mtp_depth:
        p["mtp"] = {
            "proj": nn.dense_init(ks[5], 2 * cfg.d_model, cfg.d_model, dtype),
            "ln_h": jnp.zeros((cfg.d_model,), dtype),
            "ln_e": jnp.zeros((cfg.d_model,), dtype),
            "layer": block_init(ks[5], cfg, 1, "dense", dtype),
        }
    return p


def _scan_stack(stack_params, x, cfg, positions, opts):
    """Scan a stacked block over x.  Returns (x, aux_sum)."""
    body = _maybe_remat(
        lambda carry, lp: _body(carry, lp, cfg, positions, opts), opts)
    (x, aux), _ = jax.lax.scan(body, (x, 0.0), stack_params)
    return x, aux


def _body(carry, lp, cfg, positions, opts):
    x, aux = carry
    x, a = block_apply(lp, x, cfg, positions, opts)
    return (x, aux + a), None


def embed_inputs(params, batch: dict, cfg: ModelConfig):
    """Token (+ modality stub) embedding.  Returns (x, text_offset)."""
    tokens = batch["tokens"]
    x = nn.embed_lookup(params["emb"], tokens)
    off = 0
    if cfg.frontend == "vision" and "patches" in batch:
        pe = batch["patches"].astype(x.dtype) @ params["patch_proj"]
        x = jnp.concatenate([pe, x], axis=1)
        off = pe.shape[1]
    return shard(x, "batch", "seq", "embed"), off


def decoder_forward(params, batch: dict, cfg: ModelConfig, opts: ModelOpts):
    """Returns (hidden (B,S_total,D), aux_loss, text_offset)."""
    with jax.named_scope(EMBED):
        x, off = embed_inputs(params, batch, cfg)
    aux = 0.0
    with jax.named_scope(LAYERS):
        positions = jnp.arange(x.shape[1])[None, :]
        if cfg.n_experts:
            if cfg.n_dense_layers:
                x, a = _scan_stack(params["dense_layers"], x, cfg, positions,
                                   opts)
                aux += a
            x, a = _scan_stack(params["moe_layers"], x, cfg, positions, opts)
            aux += a
        else:
            x, a = _scan_stack(params["layers"], x, cfg, positions, opts)
            aux += a
    with jax.named_scope(NORM):
        x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return x, aux, off


def logits_fn(params, cfg: ModelConfig):
    w = params["emb"].T if cfg.tie_embeddings else params["head"]
    return lambda h: h @ w


def decoder_loss(params, batch: dict, cfg: ModelConfig, opts: ModelOpts):
    """Next-token CE (+ MoE aux + MTP)."""
    tokens = batch["tokens"]
    h, aux, off = decoder_forward(params, batch, cfg, opts)
    if off:
        h = h[:, off:]
    with jax.named_scope(LOSS):
        labels = jnp.roll(tokens, -1, axis=1)
        mask = jnp.ones_like(tokens, jnp.float32).at[:, -1].set(0.0)
        loss = nn.cross_entropy_loss(logits_fn(params, cfg), h, labels, mask,
                                     chunk=opts.loss_chunk)
    metrics = {"ce": loss}
    if cfg.n_experts:
        loss = loss + opts.aux_loss_weight * aux
        metrics["aux"] = aux
    if cfg.mtp_depth and opts.mtp:
        mtp = params["mtp"]
        e_next = nn.embed_lookup(params["emb"], jnp.roll(tokens, -1, axis=1))
        hin = jnp.concatenate(
            [nn.rmsnorm(h[:, :, :], mtp["ln_h"], cfg.norm_eps),
             nn.rmsnorm(e_next, mtp["ln_e"], cfg.norm_eps)], axis=-1)
        hm = hin @ mtp["proj"]
        lp = jax.tree.map(lambda a: a[0], mtp["layer"])
        hm, _ = block_apply(lp, hm, cfg, jnp.arange(hm.shape[1])[None, :], opts)
        labels2 = jnp.roll(tokens, -2, axis=1)
        mask2 = jnp.ones_like(tokens, jnp.float32).at[:, -2:].set(0.0)
        mtp_loss = nn.cross_entropy_loss(logits_fn(params, cfg), hm, labels2,
                                         mask2, chunk=opts.loss_chunk)
        loss = loss + opts.mtp_loss_weight * mtp_loss
        metrics["mtp"] = mtp_loss
    return loss, metrics


# ---------------------------------------------------------------------------
# Decode (serving) path
# ---------------------------------------------------------------------------

def _cache_len(cfg: ModelConfig, max_len: int) -> int:
    return min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len


def decoder_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                       dtype=None) -> dict:
    dtype = dtype or nn.dtype_of(cfg.dtype)
    hd = cfg.resolved_head_dim
    S = _cache_len(cfg, max_len)
    cache: dict = {"pos": jnp.zeros((), jnp.int32)}

    def kv(n_stack):
        if cfg.mla:
            return {"c": jnp.zeros((n_stack, batch, S, cfg.kv_lora_rank), dtype),
                    "pe": jnp.zeros((n_stack, batch, S, cfg.qk_rope_dim), dtype)}
        return {"k": jnp.zeros((n_stack, batch, S, cfg.n_kv_heads, hd), dtype),
                "v": jnp.zeros((n_stack, batch, S, cfg.n_kv_heads, hd), dtype)}

    if cfg.n_experts:
        if cfg.n_dense_layers:
            cache["dense_layers"] = kv(cfg.n_dense_layers)
        cache["moe_layers"] = kv(cfg.n_moe_layers)
    else:
        cache["layers"] = kv(cfg.n_layers)
    return cache


def _decode_stack(stack_params, stack_cache, x, cfg, opts, pos):
    """One-token pass through a stacked block group, updating its cache."""
    n = jax.tree.leaves(stack_params)[0].shape[0]

    def body(carry, i):
        x, cache = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), stack_params)
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.mla:
            c_l = jax.lax.dynamic_index_in_dim(cache["c"], i, 0, keepdims=False)
            pe_l = jax.lax.dynamic_index_in_dim(cache["pe"], i, 0, keepdims=False)
            a, c_l, pe_l = mla_mod.mla_decode(lp["attn"], h, cfg, c_l, pe_l, pos)
            cache = {
                "c": jax.lax.dynamic_update_index_in_dim(cache["c"], c_l, i, 0),
                "pe": jax.lax.dynamic_update_index_in_dim(cache["pe"], pe_l, i, 0),
            }
        else:
            k_l = jax.lax.dynamic_index_in_dim(cache["k"], i, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(cache["v"], i, 0, keepdims=False)
            a, k_l, v_l = attn_decode(lp["attn"], h, cfg, k_l, v_l, pos)
            cache = {
                "k": jax.lax.dynamic_update_index_in_dim(cache["k"], k_l, i, 0),
                "v": jax.lax.dynamic_update_index_in_dim(cache["v"], v_l, i, 0),
            }
        x = x + a
        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp:
            f, _ = moe_mod.moe_apply(lp["moe"], h, cfg, opts.moe_token_chunk)
        else:
            f = nn.ffn_apply(lp["mlp"], h, cfg.act)
        return (x + f, cache), None

    (x, stack_cache), _ = jax.lax.scan(body, (x, stack_cache), jnp.arange(n))
    return x, stack_cache


def _ring_write(cache_arr, kv, window: int):
    """Write full-sequence kv (B,S,...) into a ring cache (B,W,...)."""
    S = kv.shape[1]
    W = cache_arr.shape[1]
    if not window or S <= W:
        return jax.lax.dynamic_update_slice(
            cache_arr, kv.astype(cache_arr.dtype),
            (0, 0) + (0,) * (cache_arr.ndim - 2))
    idx = jnp.arange(S - W, S) % W
    return cache_arr.at[:, idx].set(kv[:, S - W:].astype(cache_arr.dtype))


def _prefill_stack(stack_params, stack_cache, x, cfg, opts, positions):
    """Full-sequence pass that also populates the KV cache."""
    n = jax.tree.leaves(stack_params)[0].shape[0]

    def body(carry, i):
        x, cache = carry
        lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, i, 0, keepdims=False), stack_params)
        h = nn.rmsnorm(x, lp["ln1"], cfg.norm_eps)
        if cfg.mla:
            a = mla_mod.mla_attention(lp["attn"], h, cfg, positions,
                                      schedule=opts.attn_schedule)
            c_kv, k_pe = mla_mod._compress_kv(lp["attn"], h, cfg, positions)
            c_l = jax.lax.dynamic_index_in_dim(cache["c"], i, 0, keepdims=False)
            pe_l = jax.lax.dynamic_index_in_dim(cache["pe"], i, 0, keepdims=False)
            cache = {
                "c": jax.lax.dynamic_update_index_in_dim(
                    cache["c"], _ring_write(c_l, c_kv, 0), i, 0),
                "pe": jax.lax.dynamic_update_index_in_dim(
                    cache["pe"], _ring_write(pe_l, k_pe, 0), i, 0),
            }
        else:
            B, S, _ = h.shape
            q, k, v = _qkv(lp["attn"], h, cfg, positions)
            o = attention(q, k, v, causal=True, chunk_q=cfg.attn_chunk_q,
                          chunk_k=cfg.attn_chunk_k, window=cfg.sliding_window,
                          schedule=opts.attn_schedule)
            a = o.reshape(B, S, -1) @ lp["attn"]["wo"]
            k_l = jax.lax.dynamic_index_in_dim(cache["k"], i, 0, keepdims=False)
            v_l = jax.lax.dynamic_index_in_dim(cache["v"], i, 0, keepdims=False)
            cache = {
                "k": jax.lax.dynamic_update_index_in_dim(
                    cache["k"], _ring_write(k_l, k, cfg.sliding_window), i, 0),
                "v": jax.lax.dynamic_update_index_in_dim(
                    cache["v"], _ring_write(v_l, v, cfg.sliding_window), i, 0),
            }
        x = x + a
        h = nn.rmsnorm(x, lp["ln2"], cfg.norm_eps)
        if "moe" in lp:
            f, _ = moe_mod.moe_apply(lp["moe"], h, cfg, opts.moe_token_chunk)
        else:
            f = nn.ffn_apply(lp["mlp"], h, cfg.act)
        return (x + f, cache), None

    (x, stack_cache), _ = jax.lax.scan(body, (x, stack_cache), jnp.arange(n))
    return x, stack_cache


def decoder_prefill(params, cache: dict, batch: dict, cfg: ModelConfig,
                    opts: ModelOpts):
    """Prefill the cache from a full prompt.  Returns (cache, last logits)."""
    x, _ = embed_inputs(params, batch, cfg)
    S = x.shape[1]
    positions = jnp.arange(S)[None, :]
    new_cache = {"pos": jnp.asarray(S, jnp.int32)}
    for grp in ("dense_layers", "moe_layers", "layers"):
        if grp in params and grp in cache:
            x, c = _prefill_stack(params[grp], cache[grp], x, cfg, opts,
                                  positions)
            new_cache[grp] = c
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(params, cfg)(x[:, -1])
    return new_cache, logits


def decoder_decode_step(params, cache: dict, tokens, cfg: ModelConfig,
                        opts: ModelOpts):
    """tokens: (B,) current token ids.  Returns (new_cache, logits (B,V))."""
    pos = cache["pos"]
    x = nn.embed_lookup(params["emb"], tokens[:, None])
    new_cache = {"pos": pos + 1}
    for grp in ("dense_layers", "moe_layers", "layers"):
        if grp in params and grp in cache:
            x, c = _decode_stack(params[grp], cache[grp], x, cfg, opts, pos)
            new_cache[grp] = c
    x = nn.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    logits = logits_fn(params, cfg)(x[:, 0])
    return new_cache, logits
