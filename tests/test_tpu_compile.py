"""Real-width compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
chip that is described, not attached, and the compiled text must hold the
Mosaic kernel (``tpu_custom_call``).  This catches what interpret mode
cannot: block shapes that break the (8, 128) tiling, primitives Mosaic does
not lower, and kernels that overflow VMEM.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile written to the persistent cache cannot be read back
    # without a chip, so keep these compiles out of it.
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_mosaic(fn, *specs):
    text = jax.jit(fn).lower(*specs).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_gemma_2b(one_chip):
    cfg = configs.get("gemma-2b")
    B, S, d = 1, 2048, cfg.resolved_head_dim
    q = _spec((B, S, cfg.n_heads, d), jnp.bfloat16, one_chip)
    kv = _spec((B, S, cfg.n_kv_heads, d), jnp.bfloat16, one_chip)
    _assert_mosaic(lambda q, k, v: ops.flash_attention(q, k, v), q, kv, kv)


def test_wkv6_compiles_rwkv6_1_6b(one_chip):
    cfg = configs.get("rwkv6-1.6b")
    B, S, H, hd = 1, 2048, cfg.d_model // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    x = _spec((B, S, H, hd), jnp.bfloat16, one_chip)
    logw = _spec((B, S, H, hd), jnp.float32, one_chip)
    u = _spec((H, hd), jnp.float32, one_chip)
    _assert_mosaic(lambda r, k, v, w, u: ops.wkv6(r, k, v, w, u, chunk=32),
                   x, x, x, logw, u)


def test_ssd_scan_compiles_zamba2_7b(one_chip):
    cfg = configs.get("zamba2-7b")
    H = cfg.ssm_expand * cfg.d_model // cfg.ssm_head_dim
    B, S, P, N = 1, 2048, cfg.ssm_head_dim, cfg.ssm_state
    x = _spec((B, S, H, P), jnp.bfloat16, one_chip)
    dt = _spec((B, S, H), jnp.float32, one_chip)
    A = _spec((H,), jnp.float32, one_chip)
    bc = _spec((B, S, N), jnp.bfloat16, one_chip)
    _assert_mosaic(
        lambda x, dt, A, b, c: ops.ssd_scan(x, dt, A, b, c,
                                            chunk=cfg.ssm_chunk),
        x, dt, A, bc, bc)
