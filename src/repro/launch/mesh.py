"""Mesh construction for single-pod and multi-pod deployments.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.

Every device mesh in the repo is built here.  Axes are ``Auto``: the
runtime places arrays with explicit ``NamedSharding``s and constrains
activations with ``with_sharding_constraint``, which only accepts Auto
axes (``jax.make_mesh`` defaults to Explicit axes).
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def mesh_of(shape: Sequence[int], axes: Sequence[str],
            devices: Sequence | None = None) -> Mesh:
    """An Auto-axis mesh of ``shape`` over the first prod(shape) devices."""
    n = 1
    for s in shape:
        n *= s
    pool = list(devices) if devices is not None else jax.devices()
    if len(pool) < n:
        raise ValueError(f"need {n} devices, have {len(pool)}")
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=pool[:n])


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """The assignment's production mesh: 16×16 (256 chips / pod) or
    2×16×16 (2 pods = 512 chips)."""
    if multi_pod:
        return mesh_of((2, 16, 16), ("pod", "data", "model"))
    return mesh_of((16, 16), ("data", "model"))


def make_mesh(dp: int, tp: int, pods: int = 1,
              devices: Sequence | None = None) -> Mesh:
    """Mesh for an arbitrary (dp × tp) job (Rubick jobs run at 1–64 GPUs)."""
    if pods > 1:
        return mesh_of((pods, dp, tp), ("pod", "data", "model"), devices)
    return mesh_of((dp, tp), ("data", "model"), devices)
