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

import re
import sys
from collections import Counter
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.configs.base import ShapeConfig
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.models import ModelOpts, build
from repro.parallel.plan import ExecutionPlan
from repro.train.optimizer import OptConfig
from repro.train.step import compile_train_step

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def topo():
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
    yield topo
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


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


def _instructions(text: str) -> str:
    """The HLO text with one line per instruction: a Pallas kernel's
    ``kernel_metadata`` attribute holds JSON written over several lines,
    which puts the call's ``op_name`` on a line of its own."""
    return re.sub(r"kernel_metadata=\{\n(.*?)\n\}", r"kernel_metadata={\1}",
                  text, flags=re.S)


# GC saves the kernel's residuals (``models.transformer._maybe_remat``), so
# its recompute holds no splash call: with or without GC, the step runs one
# forward, one dQ and one dKV call under ``attn``.
SPLASH_CALLS = {("splash_mha_fwd_residuals", "forward"): 1,
                ("splash_mha_dq_no_residuals", "backward"): 1,
                ("splash_mha_dkv_no_residuals", "backward"): 1}


def _assert_attention_in_splash(topo, plan_kw, window, seq, batch):
    """Compile a 2-layer decoder's train step at small widths for the
    described chips, with full remat where the plan takes GC, and check
    what runs under scope ``attn``: splash's three calls, no ``while``
    loop, and no ``dynamic-update-slice`` whose outputs are not all stacks
    of the attention weights' gradients or of the kernel residuals that GC
    saves."""
    sys.path.insert(0, str(REPO))
    try:
        from perfbench.lib import scopes
    finally:
        sys.path.remove(str(REPO))

    cfg = configs.get("starcoder2-3b").with_(
        n_layers=2, d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
        d_ff=512, vocab_size=512, sliding_window=window, attn_chunk_q=128,
        attn_chunk_k=256)
    plan = ExecutionPlan(**plan_kw)
    model = build(cfg, ModelOpts(remat="full" if plan.gc else "none",
                                 loss_chunk=0))
    mesh = make_mesh(plan.dp, plan.tp, devices=topo.devices[:plan.n_gpus])
    specs = model.input_specs(ShapeConfig("train", seq, batch, "train"))
    lowered, *_ = compile_train_step(model, plan, mesh, OptConfig(), specs)
    text = _instructions(lowered.compile().as_text())
    smap = scopes.scope_map(text)

    kernels, stacks, loops = Counter(), set(), 0
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (.*?) [\w\-]+\(", line)
        if not m or smap.get(m.group(1), (None,))[0] != "attn":
            continue
        if 'custom_call_target="tpu_custom_call"' in line:
            kernels[m.group(1).rsplit(".", 1)[0], smap[m.group(1)][1]] += 1
        loops += " while(" in line
        if "dynamic-update-slice" in m.group(1):
            stacks.update(re.findall(r"\w+\[([\d,]*)\]", m.group(2)))
    assert kernels == SPLASH_CALLS
    assert loops == 0
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    weights = {",".join(map(str, w.shape))
               for w in jax.tree.leaves(params["layers"]["attn"])}
    # The kernel's residuals, B a chip's rows: q, k, v as GC names them,
    # (B, S, H·d), and as the kernel takes them, (B, H, S, d), which a step
    # without remat saves; the output (B, H, S, d) and the log-sum-exp
    # (B, H, S); stacked over the L layers.  A fusion that writes a layer's
    # slot may also hand on the layer's own value.
    L, B, d = cfg.n_layers, batch // plan.dp, cfg.resolved_head_dim
    layer = {f"{B},{seq},{h * d}" for h in (cfg.n_heads, cfg.n_kv_heads)}
    layer |= {f"{B},{h},{seq},{d}" for h in (cfg.n_heads, cfg.n_kv_heads)}
    layer.add(f"{B},{cfg.n_heads},{seq}")
    saved = layer | {f"{L},{s}" for s in layer}
    assert stacks <= weights | saved


@pytest.mark.parametrize("plan_kw,window", [
    ({"gc": True}, 0),
    ({"dp": 4, "zero_stage": 3, "gc": True}, 256),
    ({}, 0),
])
def test_train_step_attention_runs_in_splash_kernels(topo, plan_kw, window):
    """The train step of a small decoder holds splash attention's kernels
    under scope ``attn``: forward, dQ and dKV, and under GC no recompute of
    the forward, also where the kernel runs inside ``shard_map`` on four
    chips.  The dense schedule's scans (``while`` loops) and their stacked
    score tiles are gone from ``attn``: what ``dynamic-update-slice``s keep
    that scope write a layer's slot of a stacked attention weight's
    gradient, fused into its matmul.  The chunks are small, so that the
    scans would have several q blocks and kv steps."""
    _assert_attention_in_splash(topo, plan_kw, window, 512, 4)


def test_banded_train_step_at_8192_runs_in_splash_kernels(topo):
    """StarCoder2's window (4096) under a sequence twice as long, in blocks
    of 512, as the one-stage cell runs it: the step holds the same three
    splash calls under ``attn`` and no scan, and splash's own processing of
    the band mask keeps 108 of the 256 (q, kv) block pairs (each q block
    sees its own and at most 8 earlier kv blocks), where the causal mask
    keeps 136."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask_info as mask_info)

    from repro.kernels import splash

    seq, window = 8192, 4096
    assert splash.block_for(seq) == 512
    _assert_attention_in_splash(topo, {"gc": True}, window, seq, 1)

    def kept(window):
        info, _ = mask_info.process_mask(splash._mask(2, seq, window),
                                         (512, 512), shrink_grid=False)
        return int((info.block_mask[0] != 0).sum())
    assert (kept(window), kept(0)) == (108, 136)
