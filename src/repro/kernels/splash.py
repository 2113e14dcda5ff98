"""Causal self-attention through JAX's Pallas TPU splash attention.

Splash attention (``jax.experimental.pallas.ops.tpu.splash_attention``) is a
flash attention with its own forward, dQ and dKV kernels: scores and
probabilities live in VMEM one block at a time, and blocks the mask rules
out entirely (above the causal diagonal, beyond the window) are skipped,
not computed under a mask.  GQA is native: q head ``h`` reads kv head
``h // (Hq // Hkv)``, with no repeated kv.

This wrapper builds the kernel from the shapes it is given:

- mask: ``CausalMask``, or ``LocalMask`` with ``window_size=(window - 1, 0)``
  when ``0 < window < S`` (key ``j`` is seen from query ``i`` when
  ``i - window < j <= i``), one per q head;
- blocks: the largest of 512, 256 and 128 that divides ``S``, for every
  forward and backward block;
- layout ``(B, H, S, d)``, the kernel mapped over the batch.

The kernel takes no softmax scale: the caller scales q.  Its products take
q and k in their dtype with float32 accumulation; its forward's PV product
takes float32 probabilities and v, its backward bf16 ones.

Its backward needs q, k, v, the output and the log-sum-exp.  The kernel
names its output and log-sum-exp ``RESIDUALS`` (``checkpoint_name``), and
callers name q, k and v the same, so that a ``jax.checkpoint`` policy that
saves ``RESIDUALS`` keeps the forward kernel and whatever made its operands
out of the recompute.
"""

from __future__ import annotations

import functools

import jax
from jax.experimental.pallas.ops.tpu import splash_attention as sa

BLOCKS = (512, 256, 128)
RESIDUALS = "splash_residuals"


def block_for(seq: int) -> int | None:
    """The kernel's block (queries and keys) for sequence length ``seq``, or
    None when no block divides it."""
    return next((b for b in BLOCKS if seq % b == 0), None)


@functools.lru_cache(maxsize=None)
def _mask(heads: int, seq: int, window: int) -> sa.MultiHeadMask:
    if 0 < window < seq:
        mask = sa.LocalMask((seq, seq), window_size=(window - 1, 0), offset=0)
    else:
        mask = sa.CausalMask((seq, seq))
    return sa.MultiHeadMask([mask] * heads)


def causal_attention(q, k, v, *, window: int = 0, interpret: bool = False):
    """q: (B, Hq, S, d), already scaled; k, v: (B, Hkv, S, d) with Hkv
    dividing Hq -> (B, Hq, S, d) in q's dtype."""
    _, heads, seq, _ = q.shape
    b = block_for(seq)
    blocks = sa.BlockSizes(block_q=b, block_kv=b, block_kv_compute=b,
                           block_q_dkv=b, block_kv_dkv=b,
                           block_kv_dkv_compute=b, block_q_dq=b, block_kv_dq=b)
    # The kernel holds its mask's block tables as arrays of the trace that
    # builds it, so it is built in each trace; the tables themselves are
    # computed on the host once per mask and block (splash caches them).
    kernel = sa.make_splash_mha_single_device(_mask(heads, seq, window),
                                              block_sizes=blocks,
                                              residual_checkpoint_name=RESIDUALS,
                                              interpret=interpret)
    return jax.vmap(kernel)(q, k, v)
