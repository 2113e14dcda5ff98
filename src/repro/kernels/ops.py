"""Jitted public wrappers for the Pallas kernels.

The kernels compile to Mosaic for the TPU.  ``interpret=True`` runs the
same kernel bodies through the Pallas interpreter instead (the CPU tests
against ``repro.kernels.ref``); it is never chosen implicitly.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ssd_scan as _ssd
from repro.kernels import wkv6 as _wkv


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B_, C, *, chunk: int = 128,
             interpret: bool = False):
    return _ssd.ssd_scan(x, dt, A, B_, C, chunk=chunk, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, logw, u, *, chunk: int = 32,
         interpret: bool = False):
    return _wkv.wkv6(r, k, v, logw, u, chunk=chunk, interpret=interpret)
