"""Pipeline parallelism (GPipe schedule) via shard_map + ppermute.

The Rubick perf model treats PP analytically (V_pp, (m+p−1) bubble); this
module provides the runtime mechanism: layers are stacked and sharded over
a "pipe" mesh axis (each stage owns L/P consecutive layers), microbatches
stream through `n_micro + P − 1` ticks, and activations hop stages with
``jax.lax.ppermute``.  TPU adaptation: the stage hop is a neighbor
collective-permute over ICI — the natural TPU fit for 1F1B/GPipe.

The assigned production mesh has no pipe axis (plans map PP demand onto
TP/FSDP there); this module is exercised on auxiliary meshes and is the
building block for >2-pod deployments where cross-pod PP beats cross-pod
FSDP on DCN bandwidth.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_forward(layer_fn: Callable, stacked_params, x_micro,
                     mesh: Mesh, axis: str = "pipe"):
    """Run ``layer_fn`` stacks over microbatches with a GPipe schedule.

    layer_fn(layer_params, x) -> x;  stacked_params leaves: (L, ...);
    x_micro: (n_micro, mb, ...).  L must divide by the pipe-axis size.
    Returns (n_micro, mb, ...) outputs (replicated across the pipe axis).
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)

    def stage_body(params_local, xs):
        p = jax.lax.axis_index(axis)
        T = n_micro + n_stages - 1
        state = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def apply_local(x):
            def one(x, lp):
                return layer_fn(lp, x), None
            x, _ = jax.lax.scan(one, x, params_local)
            return x

        def tick(carry, t):
            state, outs = carry
            mb_idx = jnp.clip(t - p, 0, n_micro - 1)
            first_in = jax.lax.dynamic_index_in_dim(xs, jnp.clip(t, 0, n_micro - 1),
                                                    0, keepdims=False)
            inp = jnp.where(p == 0, first_in, state)
            out = apply_local(inp)
            valid = jnp.logical_and(t - p >= 0, t - p < n_micro)
            is_last = p == n_stages - 1
            write = jnp.where(jnp.logical_and(valid, is_last),
                              out, jax.lax.dynamic_index_in_dim(
                                  outs, mb_idx, 0, keepdims=False))
            outs = jax.lax.dynamic_update_index_in_dim(outs, write, mb_idx, 0)
            nxt = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (nxt, outs), None

        (_, outs), _ = jax.lax.scan(tick, (state, outs), jnp.arange(T))
        # only the last stage holds real outputs — broadcast them
        outs = jax.lax.psum(
            jnp.where(p == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), stacked_params)
    fn = jax.shard_map(stage_body, mesh=mesh, in_specs=(pspec, P()),
                       out_specs=P(), check_vma=False)
    return fn(stacked_params, x_micro)
