"""Per-layer device times of one cell's train step, from its named scopes.

    python3 perfbench/scope_trace.py --workload <name> --seed <n> [--out <dir>]

Builds the cell's program as ``run.py`` does, runs two warm-up steps, the
cell's ``trace_steps`` timed steps, then as many under the profiler, and
reduces the trace by the program's named scopes (``lib/scopes.py``, read
from the compiled step's ``as_text()``).  Prints
one JSON object: the scope x phase table in ms a step, the per-layer
numbers (``attn_ms``, ``mlp_ms``, ``loss_ms``, ``optimizer_ms``,
``remat_share``), what the scopes, ``unscoped`` and the collectives account
for against the busy time, the longest unscoped operations, and what
tracing costs: traced against untraced step times, and the seconds that
``as_text()``, the scope map, the trace's parse and both reductions take.
Writes every operation's self time and the HLO text (gzipped) to
``<out>/<workload>/`` for reducing again without the chip.

Exits non-zero when JAX finds no accelerator or fewer chips than the cell
asks for.
"""

from __future__ import annotations

import argparse
import gzip
import json
import shutil
import statistics
import sys
from pathlib import Path
from time import perf_counter

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".perfbench" / "scope_trace"
WARM_STEPS = 2


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", default=str(ROOT / ".perfbench" / "scopes"))
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from perfbench.lib import scopes, spec, trace, weights
    from perfbench.lib.program import Program
    from perfbench.lib.traffic import MarkovTokens
    from perfbench.run import accelerator

    cell = spec.load(args.workload)
    devices = accelerator(cell.chips)
    prog = Program(cell, devices)
    gen = MarkovTokens(cell.config["model"]["vocab_size"], cell.traffic,
                       args.seed)
    params, opt_state = prog.init(weights.seed_key(args.seed))
    step = 0

    def run(n):
        nonlocal params, opt_state, step
        times = []
        for _ in range(n):
            params, opt_state, _, ph = prog.train_step(params, opt_state,
                                                       gen.batch, step)
            times.append(sum(ph))
            step += 1
        return times

    run(WARM_STEPS)
    untraced = run(cell.traffic["trace_steps"])
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=trace.options())
    traced = run(cell.traffic["trace_steps"])
    jax.profiler.stop_trace()

    cost = {}
    t = perf_counter()
    text = prog.step.as_text()
    cost["as_text_s"] = perf_counter() - t
    t = perf_counter()
    smap = scopes.scope_map(text)
    cost["scope_map_s"] = perf_counter() - t
    t = perf_counter()
    from jax.profiler import ProfileData
    data = ProfileData.from_file(trace.find_xplane(str(TRACE_DIR)))
    cost["parse_s"] = perf_counter() - t
    t = perf_counter()
    summary = trace.reduce(data)
    cost["trace_reduce_s"] = perf_counter() - t
    t = perf_counter()
    s = scopes.reduce(data, smap)
    cost["scopes_reduce_s"] = perf_counter() - t
    shutil.rmtree(TRACE_DIR, ignore_errors=True)

    out = Path(args.out) / args.workload
    out.mkdir(parents=True, exist_ok=True)
    with gzip.open(out / "step.hlo.txt.gz", "wt") as f:
        f.write(text)
    (out / "op_s.json").write_text(json.dumps(
        {"steps": s.steps, "busy_s": s.busy_s, "op_s": s.op_s}))

    busy = s.busy_s
    unscoped = s.scope_s.get(scopes.UNSCOPED, 0.0)
    median = statistics.median(untraced)
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind, "chips": len(devices),
        "metrics": {n: scopes.metric(s, smap, n) for n in scopes.METRICS},
        "table_ms": scopes.table_ms(s),
        "busy_ms": 1e3 * busy / s.steps,
        "window_ms": 1e3 * summary.window_s / summary.steps,
        "accounted_over_busy": s.accounted_s / busy,
        "unscoped_share": unscoped / busy,
        "collective_share": s.collective_s / busy,
        "unscoped_ops": [[n, 1e3 * t / s.steps, smap.get(n)]
                         for n, t in s.unscoped_ops(smap)],
        "device_ops": summary.device_ops,
        "untraced_step_s": untraced, "traced_step_s": traced,
        "traced_over_untraced": statistics.median(traced) / median - 1.0,
        "cost_s": cost,
    }
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
