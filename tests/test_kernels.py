"""Pallas kernel validation: shape/dtype sweeps vs the pure-jnp oracles in
``repro.kernels.ref`` (interpret=True executes kernel bodies on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.kernels import ops, ref


def _rand(rng, shape, dtype):
    x = rng.normal(0, 1, shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("S,H,P,N,chunk", [
    (64, 2, 16, 8, 16),
    (128, 3, 32, 16, 32),
    (128, 1, 64, 64, 64),
])
def test_ssd_scan_sweep(S, H, P, N, chunk, dtype):
    rng = np.random.default_rng(2)
    x = _rand(rng, (2, S, H, P), dtype)
    dt = jnp.asarray(rng.uniform(0.05, 1.0, (2, S, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.3, 2.0, (H,)), jnp.float32)
    B_ = _rand(rng, (2, S, N), dtype)
    C = _rand(rng, (2, S, N), dtype)
    y = ops.ssd_scan(x, dt, A, B_, C, chunk=chunk, interpret=True)
    want = ref.ssd_ref(x.astype(jnp.float32), dt, A,
                       B_.astype(jnp.float32), C.astype(jnp.float32))
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - want))) / scale
    assert err < (0.03 if dtype == jnp.bfloat16 else 2e-5), err


@pytest.mark.parametrize("S,H,hd,chunk", [(32, 2, 16, 8), (64, 1, 32, 32),
                                          (128, 4, 64, 32)])
def test_wkv6_sweep(S, H, hd, chunk):
    rng = np.random.default_rng(3)
    r = _rand(rng, (2, S, H, hd), jnp.float32)
    k = _rand(rng, (2, S, H, hd), jnp.float32)
    v = _rand(rng, (2, S, H, hd), jnp.float32)
    logw = -jnp.asarray(rng.uniform(0.02, 3.0, (2, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.normal(0, 0.5, (H, hd)), jnp.float32)
    y = ops.wkv6(r, k, v, logw, u, chunk=chunk, interpret=True)
    want = ref.wkv6_ref(r, k, v, logw, u)
    scale = float(jnp.max(jnp.abs(want))) + 1e-6
    assert float(jnp.max(jnp.abs(y - want))) / scale < 2e-5


# ---------------------------------------------------------------------------
# Property-based: oracle invariants the kernels must inherit
# ---------------------------------------------------------------------------

@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_wkv6_prefix_property(seed):
    """Causality: output at t depends only on inputs ≤ t."""
    rng = np.random.default_rng(seed)
    S, cut = 32, 16
    args = [_rand(rng, (1, S, 2, 8), jnp.float32) for _ in range(3)]
    logw = -jnp.asarray(rng.uniform(0.05, 2.0, (1, S, 2, 8)), jnp.float32)
    u = jnp.asarray(rng.normal(0, 0.5, (2, 8)), jnp.float32)
    full = ops.wkv6(*args, logw, u, chunk=8, interpret=True)
    half = ops.wkv6(*[a[:, :cut] for a in args], logw[:, :cut], u,
                    chunk=8, interpret=True)
    np.testing.assert_allclose(np.asarray(full[:, :cut]), np.asarray(half),
                               atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_ssd_prefix_property(seed):
    rng = np.random.default_rng(seed)
    S, cut = 64, 32
    x = _rand(rng, (1, S, 2, 8), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 1.0, (1, S, 2)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.3, 2.0, (2,)), jnp.float32)
    B_ = _rand(rng, (1, S, 8), jnp.float32)
    C = _rand(rng, (1, S, 8), jnp.float32)
    full = ops.ssd_scan(x, dt, A, B_, C, chunk=16, interpret=True)
    half = ops.ssd_scan(x[:, :cut], dt[:, :cut], A, B_[:, :cut], C[:, :cut],
                        chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(full[:, :cut]), np.asarray(half),
                               atol=1e-4, rtol=1e-4)
