"""Weights from the seed, made by the benchmark and not by the program.

The program and the reference both take their starting parameters from
``make``: one jitted call that draws every leaf on the devices, in the
dtype it is trained in and in the sharding it is given.  Each leaf's key is
folded from its path, so a leaf's values do not depend on which other
leaves exist, and (with ``jax_threefry_partitionable``) not on how the leaf
is sharded either.
"""

from __future__ import annotations

import math
import zlib
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

SAMPLE = 1 << 16        # elements drawn from each leaf by ``sample``


def flat(tree) -> dict:
    """{"layers/attn/wq": leaf, ...} of a nested dict of arrays or shapes."""
    out = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        out["/".join(str(k.key) for k in path)] = leaf
    return out


def unflat(flat_tree: dict) -> dict:
    out: dict = {}
    for path, leaf in flat_tree.items():
        node = out
        *heads, last = path.split("/")
        for h in heads:
            node = node.setdefault(h, {})
        node[last] = leaf
    return out


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size up to 2**64."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def std_of(path: str, shape: tuple) -> float:
    """Embedding 0.02; matrices 1/sqrt(fan_in); norm scales and biases 0.02."""
    if path == "emb":
        return 0.02
    if len(shape) >= 2 and not (len(shape) == 2 and path.startswith("layers/")):
        return 1.0 / math.sqrt(shape[-2])
    return 0.02


def leaf(key, path: str, shape: tuple, dtype) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()))
    x = jax.random.normal(k, shape, jnp.float32) * std_of(path, shape)
    return x.astype(dtype)


def make(shapes: dict, seed_key_: jax.Array, shardings: dict | None = None):
    """Draw every leaf of ``shapes`` ({path: ShapeDtypeStruct}).

    Returns {path: array} in each leaf's dtype, placed by ``shardings``
    ({path: Sharding}) when given."""
    def draw(key):
        return {p: leaf(key, p, s.shape, s.dtype) for p, s in shapes.items()}
    return jax.jit(draw, out_shardings=shardings)(seed_key_)


@jax.jit
def _norms(t):
    return {p: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for p, x in t.items()}


@jax.jit
def _change_norms(params, key):
    return {p: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32) - leaf(key, p, x.shape, x.dtype).astype(jnp.float32))))
        for p, x in params.items()}


def norms(flat_tree: dict) -> dict:
    """{path: float32 norm of the leaf}."""
    return {p: float(v) for p, v in _norms(flat_tree).items()}


def change_norms(flat_params: dict, seed_key_: jax.Array) -> dict:
    """{path: norm of (leaf - its starting value)}, the start drawn anew."""
    return {p: float(v) for p, v in _change_norms(flat_params, seed_key_).items()}


@partial(jax.jit, static_argnums=2)
def _sample(t, key, k):
    out = {}
    for p, x in t.items():
        if x.size <= k:
            out[p] = x.reshape(-1).astype(jnp.float32)
            continue
        kp = jax.random.fold_in(key, zlib.crc32(("sample/" + p).encode()))
        idx = tuple(jax.random.randint(jax.random.fold_in(kp, ax), (k,), 0, n)
                    for ax, n in enumerate(x.shape))
        out[p] = x[idx].astype(jnp.float32)
    return out


def sample(flat_tree: dict, seed_key_: jax.Array, k: int = SAMPLE) -> dict:
    """{path: float32 numpy array}: every element of a leaf of at most ``k``,
    else ``k`` elements at places drawn from the seed and the path, so two
    trees of the same shapes are sampled at the same places.  A sharded leaf
    is gathered where it lies; only the sample leaves the devices."""
    return {p: np.asarray(v) for p, v in _sample(flat_tree, seed_key_, k).items()}
