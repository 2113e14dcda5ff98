"""Optimizers in pure JAX with plan-aware state placement.

AdamW with configurable moment dtype (f32 / bf16 for memory-tight plans).
Optimizer states mirror the param tree so ZeRO-1 sharding rules apply leaf
by leaf; under ``plan.offload`` the states live in ``pinned_host`` memory —
the TPU-native analogue of ZeRO-Offload (paper Sec 2.1): HBM keeps only
params+grads, the update streams moments over PCIe/DMA.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.models.transformer import OPTIMIZER


@dataclass(frozen=True)
class OptConfig:
    name: str = "adamw"                # adamw | lion
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip: float = 1.0
    moment_dtype: str = "float32"      # float32 | bfloat16


def _mdt(cfg: OptConfig):
    return jnp.bfloat16 if cfg.moment_dtype == "bfloat16" else jnp.float32


def opt_init(params, cfg: OptConfig):
    """Lion keeps only the momentum (2 B/param at bf16) — the plan dimension
    that lets 671B-class models train on a single 256-chip pod without the
    host-offload path (see DESIGN.md §Hardware-adaptation)."""
    dt = _mdt(cfg)
    zeros = lambda p: jnp.zeros(p.shape, dt)
    state = {
        "count": jnp.zeros((), jnp.int32),
        "m": jax.tree.map(zeros, params),
    }
    if cfg.name != "lion":
        state["v"] = jax.tree.map(zeros, params)
    return state


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32)))
              for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def opt_update(grads, state, params, cfg: OptConfig):
    """Returns (new_params, new_state, metrics), all under the ``optimizer``
    named scope (``models.transformer.SCOPES``)."""
    with jax.named_scope(OPTIMIZER):
        return _update(grads, state, params, cfg)


def _update(grads, state, params, cfg: OptConfig):
    count = state["count"] + 1
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-9)) \
        if cfg.grad_clip > 0 else 1.0

    b1, b2 = cfg.b1, cfg.b2
    bc1 = 1.0 - b1 ** count.astype(jnp.float32)
    bc2 = 1.0 - b2 ** count.astype(jnp.float32)
    dt = _mdt(cfg)

    if cfg.name == "lion":
        def upd_lion(p, g, m):
            g = g.astype(jnp.float32) * scale
            m32 = m.astype(jnp.float32)
            u = jnp.sign(b1 * m32 + (1 - b1) * g)
            if cfg.weight_decay:
                u = u + cfg.weight_decay * p.astype(jnp.float32)
            newp = p.astype(jnp.float32) - cfg.lr * u
            newm = b2 * m32 + (1 - b2) * g
            return newp.astype(p.dtype), newm.astype(dt)

        out = jax.tree.map(upd_lion, params, grads, state["m"])
        newp = jax.tree.map(lambda t: t[0], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        newm = jax.tree.map(lambda t: t[1], out,
                            is_leaf=lambda x: isinstance(x, tuple))
        return newp, {"count": count, "m": newm}, {"grad_norm": gnorm}

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32 = m.astype(jnp.float32) * b1 + (1 - b1) * g
        v32 = v.astype(jnp.float32) * b2 + (1 - b2) * g * g
        step = (m32 / bc1) / (jnp.sqrt(v32 / bc2) + cfg.eps)
        if cfg.weight_decay:
            step = step + cfg.weight_decay * p.astype(jnp.float32)
        newp = p.astype(jnp.float32) - cfg.lr * step
        return newp.astype(p.dtype), m32.astype(dt), v32.astype(dt)

    out = jax.tree.map(upd, params, grads, state["m"], state["v"])
    # unzip the 3-tuples
    newp = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    newm = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    newv = jax.tree.map(lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple))
    new_state = {"count": count, "m": newm, "v": newv}
    return newp, new_state, {"grad_norm": gnorm}
