"""Multi-pod dry-run: lower + compile every (architecture × input-shape)
cell on the production mesh and derive roofline terms for a TPU v5e.

``main()`` asks XLA for 512 placeholder host devices (the 16×16 single-pod
and 2×16×16 multi-pod meshes) before JAX first initializes its backends;
importing this module changes nothing.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]

Outputs one JSON row per cell under benchmarks/results/.
"""

import argparse
import json
import math
import os
import time
import traceback
from pathlib import Path

import jax

from repro import configs
from repro.configs.base import SHAPES, ModelConfig, ShapeConfig, shape_applicable
from repro.core import costs, roofline
from repro.launch.cache import init_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.models import ModelOpts, build
from repro.parallel.plan import ExecutionPlan
from repro.serve.engine import compile_decode_step, compile_prefill
from repro.train.optimizer import OptConfig
from repro.train.step import compile_train_step

RESULTS_DIR = Path(__file__).resolve().parents[3] / "benchmarks" / "results"

# Activation-carry budget per device used to derive the GA factor (bytes).
ACT_BUDGET = 4e9

# The chip whose peaks price the roofline terms (``roofline.PEAKS``).
DEVICE_KIND = "TPU v5 lite"


def default_plan(cfg: ModelConfig, shape: ShapeConfig, mesh,
                 overrides: dict | None = None):
    """Paper-faithful baseline plan for a dry-run cell + optimizer config.

    This is a static instance of the paper's own observation (Fig 3): the
    best plan depends on model size × resources.  Small models use
    ZeRO-DP across the whole machine (TP activation all-reduces would
    dominate); big models use Megatron-style TP over the model axis + FSDP
    over the data axes; DeepSeek-V3 additionally offloads optimizer states
    (ZeRO-Offload analogue, host memory).
    """
    n_params = cfg.param_count()
    big = n_params > 8e9
    tp = mesh.shape.get("model", 1) if big else 1
    daxes = [a for a in ("pod", "data") if a in mesh.axis_names]
    dp_phys = int(math.prod(mesh.shape[a] for a in daxes))
    dp = dp_phys if big else dp_phys * mesh.shape.get("model", 1)
    ga = 1
    if shape.kind == "train":
        b_loc = max(1, shape.global_batch // min(dp, shape.global_batch))
        act = b_loc * shape.seq_len * cfg.d_model * 2 * max(cfg.n_layers, 1)
        while act / ga > ACT_BUDGET and ga < b_loc:
            ga *= 2
    plan = ExecutionPlan(dp=dp, tp=tp,
                         zero_stage=3 if big else 1, ga_steps=ga,
                         gc=(shape.kind == "train"))
    # 671B-class: Lion (bf16 momentum only, 2 B/param of opt state) — the
    # memory-fitting plan dimension; ZeRO-Offload via memory_kind hits an
    # XLA:CPU SPMD limitation on this backend (DESIGN.md §Offload).
    if n_params > 1e11:
        opt = OptConfig(name="lion", moment_dtype="bfloat16", b1=0.95,
                        b2=0.98, lr=1e-4)
    else:
        opt = OptConfig()
    if overrides:
        od = dict(overrides)
        opt_over = {k[4:]: od.pop(k) for k in list(od) if k.startswith("opt_")}
        plan = plan.with_(**od)
        if opt_over:
            from dataclasses import replace
            opt = replace(opt, **opt_over)
    plan.validate()
    return plan, opt


def run_cell(arch: str, shape_name: str, mesh, *,
             plan_overrides: dict | None = None, verbose: bool = True):
    """Lower + compile one cell.  Returns a result-row dict."""
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    okay, why = shape_applicable(cfg, shape)
    mesh_name = "x".join(str(v) for v in mesh.shape.values())
    if not okay:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                "status": "skipped", "reason": why}

    plan, optcfg = default_plan(cfg, shape, mesh, plan_overrides)
    opts = ModelOpts(
        remat="full" if plan.gc else "none",
        loss_chunk=min(2048, shape.seq_len),
    )
    model = build(cfg, opts)

    t0 = time.time()
    if shape.kind == "train":
        lowered, *_ = compile_train_step(
            model, plan, mesh, optcfg, model.input_specs(shape))
    elif shape.kind == "prefill":
        lowered, *_ = compile_prefill(model, plan, mesh, shape)
    else:
        lowered, *_ = compile_decode_step(model, plan, mesh, shape)
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    rep = roofline.analyze(
        compiled, arch=arch, shape=shape, mesh=mesh, device_kind=DEVICE_KIND,
        model_flops=costs.model_flops(cfg, shape),
        attn_flops=costs.attention_flops(cfg, shape))
    ma = compiled.memory_analysis()
    row = rep.row()
    row.update({
        "status": "ok", "plan": plan.strategy,
        "plan_tuple": {"dp": plan.dp, "tp": plan.tp, "ga": plan.ga_steps,
                       "zero": plan.zero_stage, "gc": plan.gc,
                       "offload": plan.offload, "sp": plan.sp},
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
        "arg_bytes": getattr(ma, "argument_size_in_bytes", 0),
        "out_bytes": getattr(ma, "output_size_in_bytes", 0),
        "alias_bytes": getattr(ma, "alias_size_in_bytes", 0),
        "host_temp_bytes": getattr(ma, "host_temp_size_in_bytes", 0),
    })
    if verbose:
        print(f"[{mesh_name}] {arch} × {shape_name}: plan={plan.strategy} "
              f"compile={t_compile:.0f}s "
              f"Tc={rep.t_compute*1e3:.1f}ms Tm={rep.t_memory*1e3:.1f}ms "
              f"Tcoll={rep.t_collective*1e3:.1f}ms -> {rep.bottleneck} "
              f"useful={rep.useful_ratio:.2f} "
              f"roofline_frac={rep.roofline_fraction:.2f}", flush=True)
    return row


def main() -> None:
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    init_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--plan-override", default=None,
                    help='JSON, e.g. {"sp": true, "ga_steps": 4}')
    args = ap.parse_args()

    meshes = []
    if args.both_meshes:
        meshes = [make_production_mesh(), make_production_mesh(multi_pod=True)]
    else:
        meshes = [make_production_mesh(multi_pod=args.multi_pod)]

    overrides = json.loads(args.plan_override) if args.plan_override else None
    cells = []
    arch_list = configs.ARCHS[:10] if (args.all or not args.arch) \
        else [args.arch]
    shape_list = list(SHAPES) if (args.all or not args.shape) \
        else [args.shape]

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    rows = []
    for mesh in meshes:
        mesh_name = "x".join(str(v) for v in mesh.shape.values())
        for arch in arch_list:
            for shape_name in shape_list:
                try:
                    row = run_cell(arch, shape_name, mesh,
                                   plan_overrides=overrides)
                except Exception as e:  # a cell failure is a bug — surface it
                    traceback.print_exc()
                    row = {"arch": arch, "shape": shape_name,
                           "mesh": mesh_name, "status": "error",
                           "error": f"{type(e).__name__}: {e}"}
                rows.append(row)
                out = RESULTS_DIR / f"dryrun_{args.tag}.json"
                out.write_text(json.dumps(rows, indent=1, default=str))
                jax.clear_caches()

    n_ok = sum(r.get("status") == "ok" for r in rows)
    n_skip = sum(r.get("status") == "skipped" for r in rows)
    n_err = len(rows) - n_ok - n_skip
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped "
          f"(documented), {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
