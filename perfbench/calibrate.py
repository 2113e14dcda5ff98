"""Readings that the limits of ``correct`` are set from (not part of a run).

    python3 perfbench/calibrate.py --workload <name> --seeds 12 --first-seed <n> \
        [--control 3] [--control-modes int8,fp8] [--faults 3] [--ref-steps 3] \
        [--out <readings.json>]

In one process, so that the step compiles once: the program's first three
steps on each seed (sound runs: the lower readings), then the plain
reference on each seed, then, on the first ``--control`` seeds, the
reference in each lower precision of ``--control-modes`` put in the
program's place (the control: the upper readings), and on the first
``--faults`` seeds the reference with half of the batch left out (the mean
taken over the rest) and, on several chips, with only one chip's rows (the
exchange between chips left out).  Each reading is judged by the cell's
committed limits and printed with ``correct``.  A step that leaves the
state unchanged, or moves a leaf double, reads 1 on ``leaf_change_gap`` by
its definition and needs no run.  ``--ref-steps 1`` trains the reference
one step, enough for ``grad_diff`` alone, at a third of the time.

Prints one line per reading and writes every reading to ``--out``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path
from time import perf_counter

ROOT = Path(__file__).resolve().parents[1]


def calibrate(cell, devices, seeds, n_control, n_faults, reduced=False,
              control_modes=("int8",), ref_steps=3) -> dict:
    from perfbench.lib import compare, spec, weights
    from perfbench.lib.program import Program
    from perfbench.lib.traffic import MarkovTokens

    m, opt = cell.config["model"], cell.config["optimizer"]
    refmod = spec.reference(cell.config["reference"])
    t = perf_counter()
    prog = Program(cell, devices, reduced=reduced)
    out = {"workload": cell.name, "seeds": seeds, "compile_s": perf_counter() - t,
           "ref_steps": ref_steps, "limits": cell.limits,
           "program": {}, "reference": {}, "readings": {},
           "gaps": {"program": {}}, "seconds": {}}

    def tokens(seed):
        return MarkovTokens(m["vocab_size"], cell.traffic, seed).batch

    for s in seeds:
        t = perf_counter()
        key = weights.seed_key(s)
        params, opt_state = prog.init(key)
        params, opt_state, read = prog.first_steps(params, opt_state, tokens(s), key)
        del params, opt_state
        out["program"][s] = read
        out["seconds"].setdefault("program", []).append(perf_counter() - t)
        print(f"program seed {s}: losses {read['losses']}", flush=True)
    del prog
    gc.collect()

    def ref_read(mode, s, rows=None):
        t = perf_counter()
        batches = [tokens(s)(k) for k in range(ref_steps)]
        if rows is not None:
            batches = [b[:rows] for b in batches]
        r = refs[mode].train(weights.seed_key(s), batches, ref_steps)
        out["seconds"].setdefault(f"reference_{mode}_{rows}", []).append(
            perf_counter() - t)
        return r

    def compared(kind, s, read):
        """Gaps of one reading from the reference's, judged by the
        committed limits (only ``grad_diff`` after one reference step)."""
        ref = out["reference"][s]
        if ref_steps == 3:
            gaps, worst = compare.numbers(read, ref)
        else:
            g, w = compare.grad_diff(read, ref)
            gaps, worst = {"grad_diff": g}, {"grad_diff": w}
        limits = {k: v for k, v in cell.limits.items() if k in gaps}
        ok = compare.judge({k: gaps[k] for k in limits}, limits)[0] if limits else None
        out["gaps"].setdefault(kind, {})[s] = {**gaps, "worst": worst, "correct": ok}
        out["readings"].setdefault(kind, {})[s] = compare.readings(read)
        print(f"{kind} vs reference seed {s}: {gaps} worst {worst} correct: {ok}",
              flush=True)

    refs = {mode: refmod.Reference(m, opt, devices, mode)
            for mode in ("f32", *control_modes)}
    for s in seeds:
        out["reference"][s] = ref_read("f32", s)
        compared("program", s, out["program"][s])
    for mode in control_modes:
        for s in seeds[:n_control]:
            compared(f"control_{mode}", s, ref_read(mode, s))
    B = cell.traffic["global_batch"]
    fault_rows = {"half_batch": B // 2}
    if len(devices) > 1:
        fault_rows["no_exchange"] = B // len(devices)
    for name, rows in fault_rows.items():
        for s in seeds[:n_faults]:
            compared(name, s, ref_read("f32", s, rows))
    for kind, per_seed in out["gaps"].items():
        for num in next(iter(per_seed.values()), {}):
            if num not in ("worst", "correct"):
                vals = [g[num] for g in per_seed.values()]
                print(f"{kind:14s} {num:16s} min {min(vals):.6g} max {max(vals):.6g}")
    for side in ("program", "reference"):
        out[side] = {s: compare.readings(r) for s, r in out[side].items()}
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--control-modes", default="int8",
                    help="comma-separated reference modes read as controls")
    ap.add_argument("--ref-steps", type=int, choices=(1, 3), default=3,
                    help="steps of the reference, its controls and faults; "
                         "after 1, only grad_diff is compared")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".perfbench" / "tpu_logs"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from perfbench.lib import spec
    from perfbench.run import accelerator

    cell = spec.load(args.workload)
    devices = accelerator(cell.chips)
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    out = calibrate(cell, devices, seeds, args.control, args.faults,
                    control_modes=tuple(args.control_modes.split(",")),
                    ref_steps=args.ref_steps)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
