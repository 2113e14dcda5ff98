"""Distribution-correctness tests (subprocess with fake host devices so the
main process keeps seeing 1 device)."""


def test_dp_tp_matches_single_device(multidevice):
    """A DP2×TP2 sharded train step must produce the same loss trajectory
    as the unsharded single-device step."""
    out = multidevice("""
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.base import ShapeConfig
from repro.models import ModelOpts, build
from repro.parallel.plan import ExecutionPlan
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import compile_train_step, make_train_step

cfg = configs.get_reduced("llama2-7b")
model = build(cfg)
shape = ShapeConfig("t", 32, 4, "train")
optcfg = OptConfig(lr=1e-3)
params = model.init(jax.random.PRNGKey(0))
batch = model.dummy_batch(shape)

# single device reference
ref_step = jax.jit(make_train_step(model, ExecutionPlan(), optcfg))
p, o = params, opt_init(params, optcfg)
for _ in range(3):
    p, o, m = ref_step(p, o, batch)
ref_loss = float(m["loss"])

# sharded
from repro.launch.mesh import make_mesh
mesh = make_mesh(dp=2, tp=2)
plan = ExecutionPlan(dp=2, tp=2, zero_stage=1)
lowered, p_sh, o_sh, b_sh = compile_train_step(
    model, plan, mesh, optcfg, model.input_specs(shape), donate=False)
step = lowered.compile()
import jax.tree as jt
p2 = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
o2 = jax.tree.map(lambda a, s: jax.device_put(a, s),
                  opt_init(params, optcfg), o_sh)
b2 = jax.tree.map(lambda a, s: jax.device_put(a, s), batch, b_sh)
for _ in range(3):
    p2, o2, m2 = step(p2, o2, b2)
print("REF", ref_loss, "SHARDED", float(m2["loss"]))
assert abs(ref_loss - float(m2["loss"])) / ref_loss < 2e-2, (ref_loss, float(m2["loss"]))
print("OK")
""", n_devices=4)
    assert "OK" in out


def test_fsdp_zero3_matches(multidevice):
    out = multidevice("""
import jax
from repro import configs
from repro.configs.base import ShapeConfig
from repro.models import ModelOpts, build
from repro.parallel.plan import ExecutionPlan
from repro.train.optimizer import OptConfig, opt_init
from repro.train.step import compile_train_step, make_train_step

cfg = configs.get_reduced("qwen2-72b")
model = build(cfg)
shape = ShapeConfig("t", 32, 4, "train")
optcfg = OptConfig(lr=1e-3)
params = model.init(jax.random.PRNGKey(0))
batch = model.dummy_batch(shape)
ref_step = jax.jit(make_train_step(model, ExecutionPlan(ga_steps=2), optcfg))
p, o, m = ref_step(params, opt_init(params, optcfg), batch)
ref = float(m["loss"])

from repro.launch.mesh import make_mesh
mesh = make_mesh(dp=4, tp=1)
plan = ExecutionPlan(dp=4, tp=1, zero_stage=3, ga_steps=2, gc=True)
lowered, p_sh, o_sh, b_sh = compile_train_step(
    model, plan, mesh, optcfg, model.input_specs(shape), donate=False)
step = lowered.compile()
p2 = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
o2 = jax.tree.map(lambda a, s: jax.device_put(a, s),
                  opt_init(params, optcfg), o_sh)
b2 = jax.tree.map(lambda a, s: jax.device_put(a, s), batch, b_sh)
p2, o2, m2 = step(p2, o2, b2)
sh = float(m2["loss"])
print("REF", ref, "FSDP", sh)
assert abs(ref - sh) / ref < 2e-2
print("OK")
""", n_devices=4)
    assert "OK" in out


def test_moe_ep_sharded_decode(multidevice):
    """MoE decode with experts sharded over the model axis stays coherent
    with the single-device decode."""
    out = multidevice("""
import jax, jax.numpy as jnp
from repro import configs
from repro.configs.base import ShapeConfig
from repro.models import build
from repro.parallel.plan import ExecutionPlan
from repro.serve.engine import compile_decode_step

cfg = configs.get_reduced("moonshot-v1-16b-a3b")
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
cache = model.init_cache(4, 16)
tok = jnp.array([1,2,3,4], jnp.int32)
c1, ref_logits = jax.jit(model.decode_step)(params, cache, tok)

from repro.launch.mesh import make_mesh
mesh = make_mesh(dp=1, tp=4)
shape = ShapeConfig("d", 16, 4, "decode")
lowered, p_sh, c_sh = compile_decode_step(model, ExecutionPlan(dp=1, tp=4),
                                          mesh, shape, donate=False)
step = lowered.compile()
p2 = jax.tree.map(lambda a, s: jax.device_put(a, s), params, p_sh)
c2 = jax.tree.map(lambda a, s: jax.device_put(a, s),
                  model.init_cache(4, 16), c_sh)
c2, logits = step(p2, c2, tok)
import numpy as np
a = np.asarray(ref_logits, np.float32); b = np.asarray(logits, np.float32)
rel = np.max(np.abs(a - b)) / (np.max(np.abs(a)) + 1e-6)
print("rel", rel)
assert rel < 0.05, rel
print("OK")
""", n_devices=4)
    assert "OK" in out


def test_dryrun_entry_tiny(multidevice):
    """The dry-run entry point itself (mesh build + lower + compile +
    roofline) on a small mesh/arch — guards the deliverable's plumbing."""
    out = multidevice("""
import jax
from repro.launch import dryrun
from repro.launch.mesh import make_mesh
mesh = make_mesh(dp=2, tp=2)
row = dryrun.run_cell("gemma-2b", "train_4k", mesh, verbose=False,
                      plan_overrides={"dp": 4, "tp": 1, "ga_steps": 16})
assert row["status"] == "ok", row
assert row["hlo_flops"] > 0 and row["coll_bytes"] >= 0
print("OK", row["bottleneck"])
""", n_devices=4, timeout=900)
    assert "OK" in out
