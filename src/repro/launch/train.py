"""Training launcher: checkpoint/restart fault tolerance + plan
reconfiguration at the job level (the mechanism Rubick's scheduler drives).

    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b \
        --steps 50 --batch 8 --seq 128 --plan '{"zero_stage":1}'
    PYTHONPATH=src python -m repro.launch.train --full --arch gpt2-1.5b \
        --batch 2 --seq 1024 --lr 2e-4 --moment-dtype bfloat16 \
        --plan '{"gc":true}'

The plan is what runs: the mesh is built from ``plan.dp × plan.tp``, the
step is compiled with the plan's shardings (``compile_train_step``), and
params and optimizer state are created on the devices in those shardings.

Features exercised here (and by tests/test_train_loop.py):
  * resume from the latest checkpoint after a crash (fault tolerance);
  * restart with a DIFFERENT ExecutionPlan (Rubick reconfiguration) —
    checkpoints are plan/mesh-agnostic;
  * deterministic data sharding across restarts.
"""

from __future__ import annotations

import argparse
import json
from time import perf_counter
from typing import Sequence

import jax
import numpy as np


def build_runtime(arch: str, reduced: bool, plan_kw: dict, remat: bool):
    from repro import configs
    from repro.models import ModelOpts, build
    from repro.parallel.plan import ExecutionPlan

    cfg = configs.get_reduced(arch) if reduced else configs.get(arch)
    plan = ExecutionPlan(**plan_kw)
    plan.validate()
    opts = ModelOpts(remat="full" if (plan.gc or remat) else "none",
                     loss_chunk=0)
    model = build(cfg, opts)
    return cfg, model, plan


def _init_fn(model, optcfg):
    from repro.train.optimizer import opt_init

    def init(key):
        params = model.init(key)
        return params, opt_init(params, optcfg)
    return init


def train(arch: str = "gemma-2b", reduced: bool = True, steps: int = 50,
          batch: int = 8, seq: int = 128, lr: float = 1e-3,
          plan_kw: dict | None = None, ckpt_dir: str | None = None,
          ckpt_every: int = 20, log_every: int = 10, seed: int = 0,
          remat: bool = False, moment_dtype: str = "float32",
          devices: Sequence | None = None,
          resume_step: int | None = None) -> dict:
    """Train under ``plan_kw`` on the first ``dp × tp`` of ``devices``.

    Resumes from the latest checkpoint in ``ckpt_dir`` (or from
    ``resume_step``) whatever plan wrote it.  Returns the losses, the final
    params, the compile time, the time to create or restore the state on the
    devices, and the per-step wall times (each step ends in
    ``block_until_ready``)."""
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataConfig, make_source
    from repro.launch.mesh import make_mesh
    from repro.train.checkpoint import CheckpointManager
    from repro.train.optimizer import OptConfig
    from repro.train.step import compile_train_step

    cfg, model, plan = build_runtime(arch, reduced, plan_kw or {}, remat)
    optcfg = OptConfig(lr=lr, moment_dtype=moment_dtype)
    mesh = make_mesh(plan.dp, plan.tp, devices=devices)
    specs = model.input_specs(ShapeConfig("train", seq, batch, "train"))

    t0 = perf_counter()
    lowered, p_shard, o_shard, b_shard = compile_train_step(
        model, plan, mesh, optcfg, specs)
    step_fn = lowered.compile()
    compile_s = perf_counter() - t0

    init = _init_fn(model, optcfg)
    key = jax.random.PRNGKey(seed)
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    t0 = perf_counter()
    if mgr is not None and mgr.latest_step() is not None:
        p_shapes, o_shapes = jax.eval_shape(init, key)
        params, opt_state, meta = mgr.restore(
            p_shapes, o_shapes, step=resume_step, shardings=p_shard,
            opt_shardings=o_shard)
        start = meta["step"]
        print(f"[train] resumed from step {start} "
              f"(saved under {meta.get('plan')}, now {plan.strategy})")
    else:
        params, opt_state = jax.jit(init, out_shardings=(p_shard, o_shard))(
            key)
    jax.block_until_ready((params, opt_state))
    state_s = perf_counter() - t0

    data = make_source(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                                  global_batch=batch, seed=seed))
    losses, step_s = [], []
    for step in range(start, steps):
        host = {"tokens": data.batch(step)}
        if cfg.frontend == "vision":
            rng = np.random.default_rng(step)
            host = {
                "tokens": host["tokens"][:, :seq - cfg.n_patches],
                "patches": rng.normal(0, 0.02, (batch, cfg.n_patches,
                                                cfg.d_model)).astype(
                                                    np.float32),
            }
        elif cfg.frontend == "audio":
            rng = np.random.default_rng(step)
            host["frames"] = rng.normal(
                0, 0.02, (batch, cfg.n_frames, cfg.d_model)).astype(
                    np.float32)
        t = perf_counter()
        params, opt_state, metrics = step_fn(
            params, opt_state, jax.device_put(host, b_shard))
        jax.block_until_ready((params, opt_state, metrics))
        step_s.append(perf_counter() - t)
        loss = float(metrics["loss"])
        losses.append(loss)
        if step % log_every == 0:
            tokps = batch * seq / step_s[-1]
            print(f"[train] step {step:5d} loss {loss:.4f} "
                  f"({tokps:,.0f} tok/s)", flush=True)
        if mgr is not None and (step + 1) % ckpt_every == 0:
            mgr.save(step + 1, params, opt_state,
                     meta={"arch": arch, "plan": plan.strategy})
    if mgr is not None:
        mgr.save(steps, params, opt_state,
                 meta={"arch": arch, "plan": plan.strategy}, block=True)
    return {"losses": losses, "final_loss": losses[-1] if losses else None,
            "params": params, "compile_s": compile_s, "state_s": state_s,
            "step_s": step_s}


def main() -> None:
    from repro.launch.cache import init_compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--full", action="store_true",
                    help="full config (default: reduced smoke config)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--plan", default="{}",
                    help='ExecutionPlan kwargs as JSON, e.g. {"ga_steps":2}')
    ap.add_argument("--moment-dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(f"[train] compile cache: {init_compile_cache()}")
    out = train(arch=args.arch, reduced=not args.full, steps=args.steps,
                batch=args.batch, seq=args.seq, lr=args.lr,
                plan_kw=json.loads(args.plan), ckpt_dir=args.ckpt_dir,
                seed=args.seed, moment_dtype=args.moment_dtype)
    print(f"[train] done; compile {out['compile_s']:.1f}s, "
          f"final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
