"""``models/attention.attention`` off the TPU: the tile scans and plain
masked attention against ``kernels/ref.attention_ref``, forward and
gradients.  Every CPU run of a model takes these routes, and so does a
chip step whose sequence the rules shard."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ref
from repro.models import attention as attn_mod
from repro.models.attention import attention


def _rand(rng, shape, dtype):
    x = rng.normal(0, 1, shape).astype(np.float32)
    return jnp.asarray(x, dtype)


def _tol(dtype):
    return dict(atol=3e-2, rtol=3e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,Hq,Hkv,d,cq,ck", [
    (128, 4, 4, 64, 64, 64),      # MHA
    (128, 4, 1, 32, 32, 64),      # MQA, uneven chunks
    (256, 8, 2, 64, 128, 128),    # GQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_scans_match_reference(S, Hq, Hkv, d, cq, ck, causal, dtype):
    rng = np.random.default_rng(0)
    q = _rand(rng, (2, S, Hq, d), dtype)
    k = _rand(rng, (2, S, Hkv, d), dtype)
    v = _rand(rng, (2, S, Hkv, d), dtype)
    out = jax.jit(partial(attention, causal=causal, chunk_q=cq,
                          chunk_k=ck))(q, k, v)
    want = ref.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("window", [32, 96])
def test_scans_window(window):
    rng = np.random.default_rng(1)
    q = _rand(rng, (1, 256, 4, 32), jnp.float32)
    k = _rand(rng, (1, 256, 2, 32), jnp.float32)
    v = _rand(rng, (1, 256, 2, 32), jnp.float32)
    out = jax.jit(partial(attention, window=window, chunk_q=64,
                          chunk_k=64))(q, k, v)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-4, rtol=2e-4)


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000), s=st.sampled_from([32, 64]),
       h=st.sampled_from([1, 2]))
def test_scans_batch_permutation(seed, s, h):
    """Attention is batch-equivariant: permuting batch permutes outputs."""
    rng = np.random.default_rng(seed)
    q = _rand(rng, (3, s, 2 * h, 16), jnp.float32)
    k = _rand(rng, (3, s, h, 16), jnp.float32)
    v = _rand(rng, (3, s, h, 16), jnp.float32)
    f = jax.jit(partial(attention, chunk_q=16, chunk_k=16))
    perm = np.array([2, 0, 1])
    np.testing.assert_allclose(np.asarray(f(q, k, v))[perm],
                               np.asarray(f(q[perm], k[perm], v[perm])),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("S,plain", [(128, False), (120, True)],
                         ids=["tiled", "plain"])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 96)])
def test_gradients_match_reference(monkeypatch, S, plain, causal, window):
    """Autodiff through the scans (chunks 32 × 64, so several of each), or
    through plain attention where 32 does not divide the length, against
    autodiff of the reference."""
    calls, real = [], attn_mod._plain_attention
    monkeypatch.setattr(attn_mod, "_plain_attention",
                        lambda *a, **kw: calls.append(1) or real(*a, **kw))
    rng = np.random.default_rng(0)
    q = _rand(rng, (2, S, 4, 32), jnp.float32)
    k = _rand(rng, (2, S, 2, 32), jnp.float32)
    v = _rand(rng, (2, S, 2, 32), jnp.float32)

    def f(q, k, v):
        return jnp.sum(jnp.sin(attention(q, k, v, causal=causal, chunk_q=32,
                                         chunk_k=64, window=window)))

    def g(q, k, v):
        return jnp.sum(jnp.sin(ref.attention_ref(q, k, v, causal=causal,
                                                 window=window)))

    np.testing.assert_allclose(float(f(q, k, v)), float(g(q, k, v)),
                               rtol=1e-4)
    assert bool(calls) == plain
    g1 = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)
