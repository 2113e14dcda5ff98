"""Serving runtime: compiled prefill + decode steps with a sharded,
donated KV cache, plus a simple batched greedy engine.

``compile_serve_steps`` is also the dry-run entry point for the
``prefill_*`` / ``decode_*`` / ``long_*`` cells: it lowers ``serve_step``
(one new token against a seq_len cache) rather than ``train_step``.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig
from repro.models.api import Model
from repro.parallel import sharding as sh
from repro.parallel.axes import logical_axis_rules
from repro.parallel.plan import ExecutionPlan


def compile_decode_step(model: Model, plan: ExecutionPlan, mesh,
                        shape: ShapeConfig, donate: bool = True):
    """Lower the one-token decode step with a full-length cache."""
    cache_shapes = model.cache_specs(shape)
    cspecs = sh.cache_specs(cache_shapes, mesh, plan)
    c_shard = sh.named(cspecs, mesh)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = sh.named(sh.param_specs(param_shapes, mesh, plan), mesh)
    daxes = sh.data_axes(mesh)
    tok_spec = P(daxes if len(daxes) > 1 else (daxes[0] if daxes else None)) \
        if shape.global_batch % sh.axis_size(mesh, daxes) == 0 else P(None)
    tok_shard = NamedSharding(mesh, tok_spec)

    with jax.set_mesh(mesh), logical_axis_rules(sh.activation_rules(mesh, plan),
                                                dict(mesh.shape)):
        jitted = jax.jit(
            model.decode_step,
            in_shardings=(p_shard, c_shard, tok_shard),
            out_shardings=(c_shard, None),
            donate_argnums=(1,) if donate else (),
        )
        tok = jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32)
        lowered = jitted.lower(param_shapes, cache_shapes, tok)
    return lowered, p_shard, c_shard


def compile_prefill(model: Model, plan: ExecutionPlan, mesh,
                    shape: ShapeConfig):
    """Lower the full-prompt prefill step (populates the cache)."""
    cache_shapes = model.cache_specs(shape)
    cspecs = sh.cache_specs(cache_shapes, mesh, plan)
    c_shard = sh.named(cspecs, mesh)
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = sh.named(sh.param_specs(param_shapes, mesh, plan), mesh)
    batch = model.input_specs(shape)
    b_shard = sh.named(sh.batch_specs(batch, mesh, plan), mesh)

    with jax.set_mesh(mesh), logical_axis_rules(sh.activation_rules(mesh, plan),
                                                dict(mesh.shape)):
        jitted = jax.jit(
            model.prefill,
            in_shardings=(p_shard, c_shard, b_shard),
            out_shardings=(c_shard, None),
            donate_argnums=(1,),
        )
        lowered = jitted.lower(param_shapes, cache_shapes, batch)
    return lowered, p_shard, c_shard


class ServeEngine:
    """Minimal batched greedy-decoding engine (single-process runtime)."""

    def __init__(self, model: Model, params, max_len: int = 256,
                 batch_size: int = 4):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.batch_size = batch_size
        self._prefill = jax.jit(model.prefill, donate_argnums=(1,))
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

    def generate(self, batch: dict, steps: int) -> jnp.ndarray:
        """batch: prompt inputs (tokens (B,S) ± modality stubs)."""
        B = batch["tokens"].shape[0]
        cache = self.model.init_cache(B, self.max_len)
        cache, logits = self._prefill(self.params, cache, batch)
        out = []
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        for _ in range(steps):
            out.append(tok)
            cache, logits = self._decode(self.params, cache, tok)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
        return jnp.stack(out, axis=1)                       # (B, steps+1)
