"""Smoke run of the device path on a TPU: plan → sharding → train step.

    python chip_smoke.py             # one chip: (a) train, (b) reconfigure
    python chip_smoke.py --chips 4   # four chips: DP/TP/ZeRO plans vs dp=1

Drives gpt2-1.5b (paper Table 2) at its published widths and depth through
``repro.launch.train.train``, the launcher's own entry point, with random
weights from a seed and synthetic tokens from ``data.pipeline``.

(a) Train a few steps under GC with bf16 AdamW moments (the plan that fits
    16 GiB of HBM), checkpointing before the last step.  Every loss is
    finite and the last is below the first.
(b) Restart from that checkpoint under a second plan and take two steps —
    Rubick's reconfiguration mechanism.  The first loss after the restore
    is within 2 % of the loss plan (a) took at that step.
--chips 4 runs only the multi-chip phase: the same job under dp=4 ZeRO-1,
    dp=2×tp=2 and dp=4 ZeRO-3, each from the same params and batches, each
    against the dp=1 run on device 0 (first loss within 1e-2 relative, third
    within 2e-2).

Each phase prints its compile time, the time to create or restore the
state on the devices, warm step time (each step ends in
``block_until_ready``), losses, the device's peak bytes in use and the
compile-cache directory.  The last
line is one JSON object naming the device.  The script exits non-zero when
JAX finds no TPU, when fewer chips than asked for are present, or when any
check fails; it catches nothing and falls back to nothing.
"""

from __future__ import annotations

import argparse
import json
import math
import shutil
import sys
from dataclasses import dataclass, field
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import jax  # noqa: E402

from repro.launch.cache import init_compile_cache  # noqa: E402
from repro.launch.train import train  # noqa: E402

CKPT_DIR = REPO / ".smoke_ckpt"


@dataclass(frozen=True)
class Setup:
    """One smoke configuration: model, batch shapes and the plans run."""
    arch: str = "gpt2-1.5b"
    reduced: bool = False
    seq: int = 1024
    # Largest batch whose one-chip compile for a v5e leaves >= 10 % of HBM
    # free under plan_a (compiler peak 13.9 GB of 15.75 GB usable).
    batch: int = 2
    # dp=4 needs a batch divisible by 4; the dp=1 reference still fits.
    plans_batch: int = 4
    moment_dtype: str = "bfloat16"
    # GPT-3's rate for its 1.3B model (Brown et al. 2020, Table 2.1); at
    # 1e-3 without warmup the first steps do not reliably lower the loss.
    lr: float = 2e-4
    steps: int = 4                  # steps of phase (a) before the checkpoint
    plan_a: dict = field(default_factory=lambda: {"gc": True})
    # No GA plan fits one chip at full depth (its f32 gradient accumulator
    # adds 6.2 GB), so the restart is into ZeRO-1: on one device it compiles
    # to plan_a's program, but goes through checkpoint, restore into the
    # new plan's shardings, and a new compile.
    plan_b: dict = field(default_factory=lambda: {"zero_stage": 1,
                                                  "gc": True})
    plans: tuple = ({"dp": 4, "zero_stage": 1, "gc": True},
                    {"dp": 2, "tp": 2, "zero_stage": 1, "gc": True},
                    {"dp": 4, "zero_stage": 3, "gc": True})


FULL = Setup()


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {msg}")


def _run(setup: Setup, devices, plan_kw: dict, steps: int, batch: int,
         **kw) -> dict:
    return train(arch=setup.arch, reduced=setup.reduced, steps=steps,
                 batch=batch, seq=setup.seq, lr=setup.lr, plan_kw=plan_kw,
                 moment_dtype=setup.moment_dtype, devices=devices,
                 log_every=10**9, **kw)


def _summary(name: str, out: dict) -> dict:
    warm = out["step_s"][1:] or out["step_s"]
    res = {"phase": name, "compile_s": out["compile_s"],
           "state_s": out["state_s"], "warm_step_s": min(warm),
           "step_s": out["step_s"], "losses": out["losses"]}
    print(f"[{name}] compile {out['compile_s']:.2f}s  init/restore "
          f"{out['state_s']:.2f}s  warm step "
          f"{res['warm_step_s']:.4f}s  steps {out['step_s']}  "
          f"losses {out['losses']}", flush=True)
    return res


def phase_train(setup: Setup, devices, ckpt_dir: Path) -> dict:
    """(a) steps+1 steps under plan_a, checkpointed after ``steps``."""
    out = _run(setup, devices[:1], setup.plan_a, setup.steps + 1,
               setup.batch, ckpt_dir=str(ckpt_dir), ckpt_every=setup.steps)
    del out["params"]
    losses = out["losses"]
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    return _summary("train " + json.dumps(setup.plan_a), out)


def phase_reconfigure(setup: Setup, devices, ckpt_dir: Path,
                      ref_losses: list[float]) -> dict:
    """(b) restart from phase (a)'s checkpoint under plan_b, two steps."""
    out = _run(setup, devices[:1], setup.plan_b, setup.steps + 2,
               setup.batch, ckpt_dir=str(ckpt_dir), ckpt_every=10**9,
               resume_step=setup.steps)
    del out["params"]
    losses = out["losses"]
    _check(len(losses) == 2, f"expected 2 steps after restore, got {losses}")
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss {losses}")
    ref = ref_losses[setup.steps]
    rel = abs(losses[0] - ref) / abs(ref)
    print(f"[reconfigure] first loss after restore {losses[0]!r} vs plan_a "
          f"{ref!r} (rel {rel:.3e})", flush=True)
    _check(rel < 2e-2, f"restored loss {losses[0]} vs {ref}: rel {rel}")
    return _summary("reconfigure " + json.dumps(setup.plan_b), out)


def _placement(params) -> tuple[list[str], jax.Array]:
    """Names of fully replicated params, and the largest param."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    replicated = [jax.tree_util.keystr(p) for p, x in flat
                  if all(a is None for a in x.sharding.spec)]
    largest = max((x for _, x in flat), key=lambda x: x.size)
    return replicated, largest


def phase_plans(setup: Setup, devices) -> list[dict]:
    """Each multi-chip plan vs the dp=1 run on device 0, 3 steps each."""
    ref = _run(setup, devices[:1], {"gc": True}, 3, setup.plans_batch)
    del ref["params"]
    results = [_summary("plans dp=1 device 0", ref)]
    for plan_kw in setup.plans:
        out = _run(setup, devices, plan_kw, 3, setup.plans_batch)
        replicated, big = _placement(out.pop("params"))
        shards = big.addressable_shards
        n = plan_kw.get("dp", 1) * plan_kw.get("tp", 1)
        print(f"[plans {json.dumps(plan_kw)}] largest param {big.shape} "
              f"spec {big.sharding.spec}: shards "
              f"{[(s.device.id, s.data.shape) for s in shards]}", flush=True)
        if plan_kw.get("tp", 1) > 1:
            print(f"[plans {json.dumps(plan_kw)}] replicated under TP: "
                  f"{replicated}", flush=True)
        _check(len({s.device for s in shards}) == n,
               f"{plan_kw}: largest param on {len(shards)} devices, not {n}")
        if plan_kw.get("tp", 1) > 1 or plan_kw.get("zero_stage") == 3:
            _check(all(s.data.shape != big.shape for s in shards),
                   f"{plan_kw}: largest param {big.shape} is not sharded")
        for i, tol in ((0, 1e-2), (2, 2e-2)):
            rel = abs(out["losses"][i] - ref["losses"][i]) / abs(
                ref["losses"][i])
            _check(rel < tol, f"{plan_kw}: loss {i} {out['losses'][i]} vs "
                   f"dp=1 {ref['losses'][i]} (rel {rel:.3e} >= {tol})")
        results.append(_summary("plans " + json.dumps(plan_kw), out))
    return results


def _peak(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found: JAX's first device is "
                 f"{devices[0].platform!r}; this script runs on the chip only")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: asked for {args.chips} chips, JAX sees "
                 f"{len(devices)}")
    cache = init_compile_cache()
    print(f"[chip_smoke] devices: {len(devices)} x {devices[0].device_kind}",
          flush=True)
    setup = FULL
    print(f"[chip_smoke] {setup.arch} full config (no depth cut), "
          f"seq {setup.seq}", flush=True)

    def report(name: str) -> None:
        print(f"[{name}] peak bytes in use (device 0, since start): "
              f"{_peak(devices[0])}  compile cache: {cache}", flush=True)

    if args.chips == 4:
        phase_plans(setup, devices[:4])
        report("plans")
    else:
        shutil.rmtree(CKPT_DIR, ignore_errors=True)
        a = phase_train(setup, devices, CKPT_DIR)
        report("train")
        phase_reconfigure(setup, devices, CKPT_DIR, a["losses"])
        report("reconfigure")
        shutil.rmtree(CKPT_DIR)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
