"""Train-step builder: (model × ExecutionPlan × mesh) → compiled pjit step.

The plan controls:
  * gradient accumulation — ``lax.scan`` over microbatches, f32 accumulator
    sharded like the params (so ZeRO-3 keeps it sharded too);
  * remat (GC) — threaded into the model's ModelOpts;
  * shardings — params (TP/EP ± FSDP), optimizer states (ZeRO-1 ± host
    offload), batch (data axes);
  * activation logical-axis rules installed while tracing.
"""

from __future__ import annotations

from functools import partial
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.models.api import Model
from repro.parallel import sharding as sh
from repro.parallel.axes import logical_axis_rules
from repro.parallel.plan import ExecutionPlan
from repro.train.optimizer import OptConfig, opt_init, opt_update


def make_train_step(model: Model, plan: ExecutionPlan, optcfg: OptConfig):
    """Pure train-step function (no pjit)."""

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch)
        return loss, metrics

    def train_step(params, opt_state, batch):
        if plan.ga_steps > 1:
            ga = plan.ga_steps

            def mb_slice(x):
                b = x.shape[0]
                return x.reshape((ga, b // ga) + x.shape[1:])

            micro = jax.tree.map(mb_slice, batch)

            def body(acc, mb):
                (loss, metrics), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, mb)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return acc, loss

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            grads, losses = jax.lax.scan(body, zeros, micro)
            grads = jax.tree.map(lambda g: g / ga, grads)
            loss = jnp.mean(losses)
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)

        new_params, new_opt, opt_metrics = opt_update(
            grads, opt_state, params, optcfg)
        out = {"loss": loss, **metrics, **opt_metrics}
        return new_params, new_opt, out

    return train_step


def compile_train_step(model: Model, plan: ExecutionPlan, mesh,
                       optcfg: OptConfig, batch_specs_tree: Any,
                       donate: bool = True):
    """Lower+compile the train step on ``mesh``.

    ``batch_specs_tree``: ShapeDtypeStructs of the batch.
    Returns (lowered, param_shardings, opt_shardings, batch_shardings).
    """
    rng = jax.random.PRNGKey(0)
    param_shapes = jax.eval_shape(model.init, rng)
    opt_shapes = jax.eval_shape(partial(opt_init, cfg=optcfg), param_shapes)

    pspecs = sh.param_specs(param_shapes, mesh, plan)
    ospecs_inner = sh.opt_state_specs(param_shapes, mesh, plan)
    p_shard = sh.named(pspecs, mesh)
    o_shard = {"count": NamedSharding(mesh, P())}
    for key in opt_shapes:
        if key == "count":
            continue
        o_shard[key] = jax.tree.map(
            lambda s: sh.opt_sharding(s, mesh, plan),
            ospecs_inner, is_leaf=lambda x: isinstance(x, P))
    b_specs = sh.batch_specs(batch_specs_tree, mesh, plan)
    b_shard = sh.named(b_specs, mesh)

    step = make_train_step(model, plan, optcfg)
    metric_shard = NamedSharding(mesh, P())

    with jax.set_mesh(mesh), logical_axis_rules(sh.activation_rules(mesh, plan),
                                                dict(mesh.shape)):
        jitted = jax.jit(
            step,
            in_shardings=(p_shard, o_shard, b_shard),
            out_shardings=(p_shard, o_shard, None),
            donate_argnums=(0, 1) if donate else (),
        )
        lowered = jitted.lower(param_shapes, opt_shapes, batch_specs_tree)
    return lowered, p_shard, o_shard, b_shard
