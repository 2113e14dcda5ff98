"""Splash attention on the training path, on the CPU.

``kernels/splash.py`` in interpret mode against ``kernels/ref.attention_ref``
(forward, and the gradients of q, k and v through the kernel's own
backward), and the dispatch in ``models/attention.attention``: the kernel
only for causal self-attention on a TPU under the default schedule, the
scans, unchanged, for everything else.  A test that needs the TPU branch
says so by replacing ``attention._platform``.
"""

import contextlib
import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, splash
from repro.launch.mesh import make_mesh
from repro.models import attention as attn_mod
from repro.parallel.axes import logical_axis_rules
from repro.parallel.plan import ExecutionPlan
from repro.parallel.sharding import activation_rules


def _qkv(S, Hq, Hkv, d, *, Sk=None, dv=None, B=2):
    rng = np.random.default_rng(0)
    Sk, dv = Sk or S, dv or d
    return (jnp.asarray(rng.normal(0, 1, (B, S, Hq, d)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (B, Sk, Hkv, d)), jnp.float32),
            jnp.asarray(rng.normal(0, 1, (B, Sk, Hkv, dv)), jnp.float32))


def _bhsd(x):
    return x.transpose(0, 2, 1, 3)


@pytest.mark.parametrize("Hq,Hkv,d,S,window,remat", [
    (4, 4, 64, 256, 0, False),
    (4, 2, 64, 384, 100, False),
    (4, 2, 128, 512, 0, False),
    (4, 4, 128, 256, 200, False),
    (4, 2, 64, 256, 0, True),
])
def test_kernel_matches_reference(monkeypatch, Hq, Hkv, d, S, window, remat):
    """In 128-blocks (the rule's smallest), so that each case has several
    q and kv blocks: some skipped, some partly masked, some whole."""
    monkeypatch.setattr(splash, "BLOCKS", (128,))
    q, k, v = _qkv(S, Hq, Hkv, d)

    def kernel(q, k, v):
        o = splash.causal_attention(_bhsd(q / math.sqrt(d)), _bhsd(k), _bhsd(v),
                                    window=window, interpret=True)
        return _bhsd(o)

    f = jax.checkpoint(kernel) if remat else kernel
    g = partial(ref.attention_ref, causal=True, window=window)
    np.testing.assert_allclose(jax.jit(f)(q, k, v), g(q, k, v),
                               atol=1e-5, rtol=1e-5)

    def grads(fn):
        loss = lambda *a: jnp.sum(jnp.sin(fn(*a)))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    for a, b in zip(grads(f), grads(g)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)


def test_block_rule():
    assert [splash.block_for(s) for s in (128, 256, 384, 512, 640, 4096, 200)] \
        == [128, 256, 128, 512, 128, 512, None]


@pytest.mark.parametrize("case", [
    "cpu", "seq_not_128", "cross", "not_causal", "unequal_heads",
    "schedule", "seq_sharded",
])
def test_dispatch_keeps_the_scans(monkeypatch, case):
    """Outside its rule the dispatch leaves attention() bit for bit as the
    scans compute it."""
    q, k, v = _qkv(200 if case == "seq_not_128" else 256, 4, 2, 32,
                   Sk=384 if case == "cross" else None,
                   dv=16 if case == "unequal_heads" else None)
    kw = dict(causal=case != "not_causal", chunk_q=64, chunk_k=128,
              schedule="triangle" if case == "schedule" else "dense")
    if case != "cpu":
        monkeypatch.setattr(attn_mod, "_platform", lambda: "tpu")
    rules = (logical_axis_rules({"seq": "model"}, {"model": 1})
             if case == "seq_sharded" else contextlib.nullcontext())
    with rules:
        assert not attn_mod._kernel_applies(q, k, v, kw["causal"],
                                            kw["schedule"])
        out = jax.jit(partial(attn_mod.attention, **kw))(q, k, v)
        monkeypatch.setattr(attn_mod, "_kernel_applies", lambda *a: False)
        scans = jax.jit(partial(attn_mod.attention, **kw))(q, k, v)
    np.testing.assert_array_equal(out, scans)


@pytest.mark.parametrize("under_mesh", [False, True])
def test_dispatch_takes_the_kernel_on_tpu(monkeypatch, under_mesh):
    """With the TPU branch taken (the kernel interpreted), attention() goes
    through the kernel, inside ``shard_map`` under a mesh, and agrees with
    the reference."""
    monkeypatch.setattr(attn_mod, "_platform", lambda: "tpu")
    shapes, real = [], splash.causal_attention

    def interpreted(q, k, v, **kw):
        shapes.append(q.shape)
        return real(q, k, v, interpret=True, **kw)

    monkeypatch.setattr(splash, "causal_attention", interpreted)
    q, k, v = _qkv(256, 4, 2, 64)
    f = jax.jit(partial(attn_mod.attention, window=100))
    if under_mesh:
        mesh = make_mesh(1, 1, devices=jax.devices()[:1])
        with jax.set_mesh(mesh), logical_axis_rules(
                activation_rules(mesh, ExecutionPlan()), dict(mesh.shape)):
            out = f(q, k, v)
    else:
        out = f(q, k, v)
    assert shapes == [(2, 4, 256, 64)]
    np.testing.assert_allclose(out, ref.attention_ref(q, k, v, window=100),
                               atol=1e-5, rtol=1e-5)
