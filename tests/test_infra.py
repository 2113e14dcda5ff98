"""Checkpoint, data pipeline, memory estimator, plans, HLO analyzer,
serving engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs
from repro.core import hlo_cost, memory, paper_models
from repro.core.perfmodel import Alloc
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.parallel.plan import ExecutionPlan, enumerate_plans
from repro.train.checkpoint import CheckpointManager
from repro.train.optimizer import OptConfig, opt_init, opt_update


# --- checkpoint ---------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    params = {"layers": {"wq": jnp.arange(12, dtype=jnp.bfloat16).reshape(3, 4)},
              "emb": jnp.ones((5, 2), jnp.float32)}
    opt = opt_init(params, OptConfig())
    mgr = CheckpointManager(tmp_path, keep_last=2)
    mgr.save(10, params, opt, meta={"plan": "DP"}, block=True)
    p2, o2, meta = mgr.restore(params, opt)
    assert meta["step"] == 10 and meta["plan"] == "DP"
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p2)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_checkpoint_gc_keeps_last(tmp_path):
    params = {"w": jnp.zeros((2,))}
    mgr = CheckpointManager(tmp_path, keep_last=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, params, block=True)
    assert mgr.list_steps() == [3, 4]


def test_checkpoint_restore_missing_raises(tmp_path):
    mgr = CheckpointManager(tmp_path)
    with pytest.raises(FileNotFoundError):
        mgr.restore({"w": jnp.zeros((2,))})


def test_checkpoint_async_error_reraised_on_wait(tmp_path):
    import shutil
    mgr = CheckpointManager(tmp_path / "ck")
    shutil.rmtree(tmp_path / "ck")          # the writer thread cannot write
    mgr.save(1, {"w": jnp.zeros((2,))})
    with pytest.raises(FileNotFoundError):
        mgr.wait()
    mgr.wait()                              # raised once, then cleared


# --- data pipeline --------------------------------------------------------------

def test_data_deterministic():
    cfg = DataConfig(vocab_size=100, seq_len=16, global_batch=8, seed=3)
    a = SyntheticTokens(cfg).batch(5)
    b = SyntheticTokens(cfg).batch(5)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, SyntheticTokens(cfg).batch(6))


def test_data_shards_partition_batch():
    cfg = DataConfig(vocab_size=50, seq_len=8, global_batch=8, seed=0)
    src = SyntheticTokens(cfg)
    full = src.batch(3)
    parts = [src.shard(3, i, 4) for i in range(4)]
    np.testing.assert_array_equal(np.concatenate(parts), full)


# --- optimizer --------------------------------------------------------------------

def test_adamw_reduces_quadratic():
    params = {"w": jnp.array([3.0, -2.0])}
    state = opt_init(params, OptConfig(lr=0.1))
    for _ in range(100):
        grads = jax.tree.map(lambda w: 2 * w, params)
        params, state, _ = opt_update(grads, state, params, OptConfig(lr=0.1))
    assert float(jnp.abs(params["w"]).max()) < 0.3


def test_lion_state_is_momentum_only():
    params = {"w": jnp.zeros((4,), jnp.bfloat16)}
    st_ = opt_init(params, OptConfig(name="lion", moment_dtype="bfloat16"))
    assert "v" not in st_
    assert st_["m"]["w"].dtype == jnp.bfloat16


# --- memory estimator ------------------------------------------------------------

PROF = paper_models.profile("llama2-7b")


def test_memory_zero_ordering():
    alloc = Alloc(8, 96)
    m0 = memory.estimate(PROF, ExecutionPlan(dp=8), alloc).gpu_bytes
    m1 = memory.estimate(PROF, ExecutionPlan(dp=8, zero_stage=1), alloc).gpu_bytes
    m3 = memory.estimate(PROF, ExecutionPlan(dp=8, zero_stage=3), alloc).gpu_bytes
    assert m0 > m1 > m3


def test_memory_gc_reduces_activations():
    alloc = Alloc(8, 96)
    a = memory.estimate(PROF, ExecutionPlan(dp=8, zero_stage=1), alloc).gpu_bytes
    b = memory.estimate(PROF, ExecutionPlan(dp=8, zero_stage=1, gc=True),
                        alloc).gpu_bytes
    assert b < a


def test_memory_offload_moves_to_host():
    alloc = Alloc(2, 24)
    e = memory.estimate(PROF, ExecutionPlan(dp=2, zero_stage=1, offload=True),
                        alloc)
    d = memory.estimate(PROF, ExecutionPlan(dp=2, zero_stage=1), alloc)
    assert e.gpu_bytes < d.gpu_bytes
    assert e.host_bytes > d.host_bytes


def test_7b_oom_on_one_gpu_without_offload():
    """Paper Fig 3b: ZeRO-Offload is the only feasible 1-GPU plan for large
    models; plain DP OOMs."""
    alloc = Alloc(1, 12)
    assert not memory.feasible(PROF, ExecutionPlan(dp=1), alloc)
    assert memory.feasible(
        PROF, ExecutionPlan(dp=1, zero_stage=1, offload=True, gc=True,
                            ga_steps=4), alloc)


# --- plans ------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(g=st.sampled_from([1, 2, 4, 8, 16, 32, 64]),
       b=st.sampled_from([16, 32, 256]))
def test_enumerate_plans_valid(g, b):
    plans = list(enumerate_plans(g, b))
    assert plans
    for p in plans:
        assert p.n_gpus == g
        assert b % (p.dp * max(p.ga_steps, 1)) == 0
        p.validate()


# --- HLO cost analyzer --------------------------------------------------------------

def test_hlo_cost_counts_matmul():
    n = 128
    f = jax.jit(lambda a, b: a @ b)
    c = f.lower(jnp.zeros((n, n)), jnp.zeros((n, n))).compile()
    cost = hlo_cost.analyze_text(c.as_text())
    assert cost.flops == pytest.approx(2 * n**3, rel=0.01)


def test_hlo_cost_multiplies_scan_trips():
    n, L = 64, 10
    def f(x, w):
        return jax.lax.scan(lambda c, wi: (c @ wi, None), x, w)[0]
    c = jax.jit(f).lower(jnp.zeros((n, n)), jnp.zeros((L, n, n))).compile()
    cost = hlo_cost.analyze_text(c.as_text())
    assert cost.flops == pytest.approx(L * 2 * n**3, rel=0.05)


def test_hlo_shape_parsing():
    assert hlo_cost.shape_bytes("f32[8,4]{1,0}") == 128
    assert hlo_cost.shape_bytes("(bf16[2,2], s32[3])") == 8 + 12
    assert hlo_cost.shape_elems("pred[7]") == 7


# --- serving -------------------------------------------------------------------------

def test_serve_engine_greedy():
    from repro.serve.engine import ServeEngine
    cfg = configs.get_reduced("gemma-2b")
    from repro.models import build
    m = build(cfg)
    params = m.init(jax.random.PRNGKey(0))
    eng = ServeEngine(m, params, max_len=32)
    batch = m.dummy_batch(configs.SHAPES["train_4k"].__class__(
        "p", 8, 2, "train"))
    out = eng.generate(batch, steps=4)
    assert out.shape == (2, 5)
    assert jnp.all((out >= 0) & (out < cfg.vocab_size))


# --- roofline report -----------------------------------------------------------------

def test_roofline_bottleneck_math():
    from repro.core.roofline import RooflineReport
    r = RooflineReport(arch="x", shape="train_4k", mesh="16x16", chips=256,
                       hlo_flops=1e18, hlo_bytes=1e15, coll_bytes=1e12,
                       device_kind="TPU v5 lite", model_flops=5e17)
    assert r.t_compute == pytest.approx(1e18 / (256 * 197e12))
    assert r.bottleneck in ("compute", "memory", "collective")
    assert 0 < r.useful_ratio <= 1
    assert 0 < r.roofline_fraction <= 1


def test_roofline_unknown_device_kind_raises():
    from repro.core.roofline import RooflineReport, peaks
    assert peaks("TPU v5 lite") == (197e12, 819e9, 200e9)
    r = RooflineReport(arch="x", shape="s", mesh="1", chips=1, hlo_flops=1.0,
                       hlo_bytes=1.0, coll_bytes=0.0, device_kind="cpu")
    with pytest.raises(KeyError, match="cpu"):
        r.t_compute
