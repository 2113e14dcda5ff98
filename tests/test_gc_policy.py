"""GC's checkpoint policy (``models.transformer._maybe_remat``), on the CPU.

Under ``remat="full"`` a layer saves its input and the values named
``kernels.splash.RESIDUALS``, and recomputes everything else.  Only the
splash kernel path names values (q, k and v in ``models.attention``, the
output and log-sum-exp in the kernel), so off it the step is the plain full
remat it always was, and on it the backward runs the kernel's forward once.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.ad_checkpoint import checkpoint_name

from repro import configs
from repro.configs.base import ShapeConfig
from repro.kernels import splash
from repro.models import ModelOpts, build
from repro.models import attention as attn_mod
from repro.models import transformer


def test_full_remat_saves_exactly_the_named_values():
    """A layer scan whose body names two values of shapes of their own:
    ``remat="full"`` saves those, stacked over the layers, besides what any
    remat of a scan keeps (the weights and each layer's input); a value
    under another name is recomputed; ``remat="none"`` saves more."""
    def layer(x, w):
        h = jnp.sin(x @ w)
        a = checkpoint_name(jnp.cos(h)[:, :5], splash.RESIDUALS)
        b = checkpoint_name(jnp.tanh(h)[:, :6], "other")
        c = checkpoint_name(a.sum(-1) * b.sum(-1), splash.RESIDUALS)
        return x + c[:, None] * h, None

    def saved(remat):
        body = transformer._maybe_remat(layer, ModelOpts(remat=remat))
        f = lambda x, ws: jax.lax.scan(body, x, ws)[0]
        x, ws = jnp.ones((4, 8)), jnp.ones((3, 8, 8)) / 8
        return sorted(a.shape for a, _ in saved_residuals(f, x, ws))

    assert saved("full") == [(3, 4), (3, 4, 5), (3, 4, 8), (3, 8, 8)]
    assert len(saved("none")) > 4


def _decoder(remat):
    cfg = configs.get("starcoder2-3b").with_(
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=128, sliding_window=24)
    model = build(cfg, ModelOpts(remat=remat, loss_chunk=0))
    params = model.init(jax.random.PRNGKey(0))
    batch = model.dummy_batch(ShapeConfig("train", 32, 2, "train"))
    loss = lambda p: model.loss(p, batch)[0]
    return jax.jit(jax.value_and_grad(loss)), params


def test_cpu_path_is_plain_full_remat(monkeypatch):
    """Off the kernel path nothing is named: loss and gradients of a small
    decoder under ``remat="full"`` are bit for bit those of
    ``jax.checkpoint`` with no policy, as full remat was before."""
    step, params = _decoder("full")
    loss, grads = step(params)
    monkeypatch.setattr(transformer, "_maybe_remat",
                        lambda fn, opts: jax.checkpoint(fn))
    plain, params = _decoder("full")
    want_loss, want_grads = plain(params)
    np.testing.assert_array_equal(loss, want_loss)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_array_equal(a, b)


def _pallas_calls(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _pallas_calls(sub)
    return n


@pytest.mark.parametrize("d", [64, 128])
def test_kernel_under_gc_matches_no_remat(monkeypatch, d):
    """The attention layer on the kernel path (the kernel interpreted), in a
    two-layer scan: under ``remat="full"`` the gradients equal those without
    remat within ``test_splash``'s tolerances, and the backward holds three
    kernel calls (forward, dQ, dKV) where a checkpoint with no policy holds
    four.  A layer saves q, k, v, the output and the log-sum-exp besides its
    arguments and the mask's tables: q, k and v as (B, S, H·d), before
    their transpose into the kernel's (B, H, S, d), at either head size."""
    monkeypatch.setattr(attn_mod, "_platform", lambda: "tpu")
    monkeypatch.setattr(splash, "BLOCKS", (128,))
    monkeypatch.setattr(splash, "causal_attention",
                        partial(splash.causal_attention, interpret=True))
    B, S, H, D = 2, 256, 2, 64
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(keys[0], (B, S, D))
    ws = {"wq": jax.random.normal(keys[1], (2, D, H * d)) / 8,
          "wo": jax.random.normal(keys[2], (2, H * d, D)) / 8}

    def layer(x, w):
        q = (x @ w["wq"]).reshape(B, S, H, d)
        o = attn_mod.attention(q, jnp.sin(q), jnp.cos(q), window=100)
        return x + o.reshape(B, S, H * d) @ w["wo"], None

    def grads(body):
        loss = lambda x, ws: jnp.sum(jnp.sin(jax.lax.scan(body, x, ws)[0]))
        f = jax.grad(loss, argnums=(0, 1))
        return jax.jit(f)(x, ws), _pallas_calls(jax.make_jaxpr(f)(x, ws).jaxpr)

    body = transformer._maybe_remat(layer, ModelOpts(remat="full"))
    w0 = jax.tree.map(lambda w: w[0], ws)
    saved = sorted(a.shape for a, src in
                   saved_residuals(lambda x, w: body(x, w)[0], x, w0)
                   if not src.startswith(("from a constant", "from the arg")))
    assert saved == sorted([(B, S, H * d)] * 3 + [(B, H, S, d), (B, H, S)])

    gc, n_gc = grads(body)
    want, n_none = grads(layer)
    _, n_plain = grads(jax.checkpoint(layer))
    assert (n_gc, n_none, n_plain) == (3, 3, 4)
    for a, b in zip(jax.tree.leaves(gc), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4)
