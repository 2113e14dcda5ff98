"""Training and prefill attention, by one of three routes that the step
itself decides; no option chooses among them.

  * **Splash kernels** (``repro.kernels.splash``: forward, dQ and dKV in
    VMEM, masked-out blocks skipped) for causal self-attention on a TPU;
    ``_kernel_applies`` gives the rule.
  * **Tile scans** everywhere else: online softmax over (chunk_q × chunk_k)
    tiles through two ``lax.scan``s, so the full (Sq, Sk) score matrix is
    never built; masked tiles are computed and masked, not skipped.
  * **Plain masked attention** for lengths the chunks do not divide (the
    tiny CPU configurations).

GQA/MQA are expressed by grouping query heads over kv heads.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax._src import mesh as mesh_lib
from jax.ad_checkpoint import checkpoint_name

from repro.kernels import splash
from repro.parallel.axes import logical_to_spec

NEG_INF = -1e30


def _platform() -> str:
    """Platform of the devices this trace compiles for: those of the mesh
    that ``jax.set_mesh`` installed (``train.step`` does), else the default
    backend's.  ``jax.sharding.get_mesh`` refuses to answer inside a trace,
    so the installed mesh is read directly."""
    mesh = mesh_lib.get_concrete_mesh()
    return jax.default_backend() if mesh.empty else mesh.devices.flat[0].platform


def _kernel_applies(q, k, v, causal: bool) -> bool:
    """Causal self-attention on a TPU, with equal head sizes, a sequence
    the kernel's blocks divide, and the sequence not sharded.  Everything
    else keeps the scans below."""
    _, Sq, _, d = q.shape
    return (causal and k.shape[1] == Sq
            and k.shape[-1] == v.shape[-1] == d
            and splash.block_for(Sq) is not None
            and all(p is None for p in logical_to_spec(("seq",), (Sq,)))
            and _platform() == "tpu")


def _kernel_operand(x):
    """(B, S, H, d) -> the kernel's (B, H, S, d).  Named ``splash.RESIDUALS``
    for GC to save as (B, S, H·d) before the transpose, which the recompute
    then redoes: that layout carries no lane padding when d is 64 (gpt2),
    and holds the same bytes as the kernel's when 128 divides d."""
    B, S, H, d = x.shape
    x = checkpoint_name(x.reshape(B, S, H * d), splash.RESIDUALS)
    return x.reshape(B, S, H, d).transpose(0, 2, 1, 3)


def _splash(q, k, v, *, window: int, scale: float):
    """(B, S, H, d) in and out.  The kernel takes no scale, so q is scaled
    in float32 and rounded once to its dtype.  Under a mesh each device runs
    its own batch rows and heads (``jax.shard_map``: GSPMD cannot split the
    kernel's custom call); heads stay whole when q's and kv's head
    shardings differ."""
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    q, k, v = map(_kernel_operand, (q, k, v))
    run = lambda q, k, v: splash.causal_attention(q, k, v, window=window)
    if not jax.sharding.get_abstract_mesh().empty:
        names = ("batch", "heads", None, None)
        qs, kvs = logical_to_spec(names, q.shape), logical_to_spec(names, k.shape)
        if tuple(qs)[1:2] != tuple(kvs)[1:2]:
            names = ("batch", None, None, None)
            qs = kvs = logical_to_spec(names, q.shape)
        # check_vma off: the kernel's pallas_call declares no per-axis
        # variance for its outputs.
        run = jax.shard_map(run, in_specs=(qs, kvs, kvs), out_specs=qs,
                            check_vma=False)
    return run(q, k, v).transpose(0, 2, 1, 3)


def _group(q: jax.Array, n_kv: int) -> jax.Array:
    """(B, S, Hq, d) -> (B, S, Hkv, G, d)"""
    B, S, Hq, d = q.shape
    return q.reshape(B, S, n_kv, Hq // n_kv, d)


def _mask(q0, k0, nq: int, nk: int, causal: bool, window: int):
    """(1, 1, 1, nq, nk): which of the keys at k0 + [0, nk) the queries at
    q0 + [0, nq) see; None when every query sees every key."""
    if not causal and not window:
        return None
    qpos = q0 + jnp.arange(nq)
    kpos = k0 + jnp.arange(nk)
    m = jnp.ones((nq, nk), bool)
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window:
        m &= qpos[:, None] - kpos[None, :] < window
    return m[None, None, None]


def _tile_attend(qc, kc, vc, mask, m, l, acc, scale):
    """One (cq × ck) tile of online-softmax.  qc: (B,cq,K,G,d); kc/vc: (B,ck,K,d)."""
    s = jnp.einsum("bqkgd,bskd->bkgqs", qc, kc, preferred_element_type=jnp.float32)
    s = s * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))            # (B,K,G,cq)
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vc.dtype), vc,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    chunk_q: int = 512,
    chunk_k: int = 1024,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    """q: (B,Sq,Hq,d); k,v: (B,Sk,Hkv,d) -> (B,Sq,Hq,dv), by the route the
    module docstring describes.  Queries sit at the last Sq of the Sk key
    positions, so Sq != Sk is allowed."""
    B, Sq, Hq, d = q.shape
    _, Sk, Hkv, _ = k.shape
    dv = v.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    if _kernel_applies(q, k, v, causal):
        return _splash(q, k, v, window=window, scale=scale)
    qg = _group(q, Hkv)

    cq = min(chunk_q, Sq)
    ck = min(chunk_k, Sk)
    if Sq % cq or Sk % ck:
        return _plain_attention(qg, k, v, causal=causal, window=window, scale=scale)

    nq, nk = Sq // cq, Sk // ck
    G = Hq // Hkv

    q_tiles = qg.reshape(B, nq, cq, Hkv, G, d).transpose(1, 0, 2, 3, 4, 5)
    k_tiles = k.reshape(B, nk, ck, Hkv, d).transpose(1, 0, 2, 3, 4)
    v_tiles = v.reshape(B, nk, ck, Hkv, dv).transpose(1, 0, 2, 3, 4)
    # q block i covers [off + i*cq, off + (i+1)*cq) in k coordinates.
    off = Sk - Sq

    def scan_q(_, qx):
        qc, qi = qx

        def kv_step(carry, kv):
            kc, vc, kj = kv
            mask = _mask(off + qi * cq, kj * ck, cq, ck, causal, window)
            return _tile_attend(qc, kc, vc, mask, *carry, scale), None

        m0 = jnp.full((B, Hkv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, cq, dv), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            kv_step, (m0, l0, a0), (k_tiles, v_tiles, jnp.arange(nk)))
        return None, acc / jnp.maximum(l, 1e-30)[..., None]

    _, o = jax.lax.scan(scan_q, None, (q_tiles, jnp.arange(nq)))

    # (nq, B, Hkv, G, cq, dv) -> (B, Sq, Hq, dv)
    o = o.transpose(1, 0, 4, 2, 3, 5).reshape(B, Sq, Hq, dv)
    return o.astype(q.dtype)


def _plain_attention(qg, k, v, *, causal, window, scale):
    B, Sq, Hkv, G, d = qg.shape
    Sk = k.shape[1]
    dv = v.shape[-1]
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32) * scale
    mask = _mask(Sk - Sq, 0, Sq, Sk, causal, window)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return o.reshape(B, Sq, Hkv * G, dv)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    length: jax.Array,
    *,
    window: int = 0,
    scale: float | None = None,
) -> jax.Array:
    """Single-token attention against a KV cache.

    q: (B, Hq, d); caches: (B, S, Hkv, d); length: scalar count of valid
    entries.  With ``window`` the cache is a ring buffer of size ≤ window and
    all filled slots are valid.  Returns (B, Hq, d).
    """
    B, Hq, d = q.shape
    _, S, Hkv, _ = k_cache.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = q.reshape(B, Hkv, Hq // Hkv, d)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache, preferred_element_type=jnp.float32)
    s = s * scale
    valid = jnp.arange(S) < length                         # (S,)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache)
    return o.reshape(B, Hq, d).astype(q.dtype)
