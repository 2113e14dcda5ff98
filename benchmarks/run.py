"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived is compact JSON).
Perf-tracking benches also write machine-readable ``BENCH_*.json``
artifacts (see benchmarks/_artifacts.py).

    PYTHONPATH=src python -m benchmarks.run              # all, sequential
    PYTHONPATH=src python -m benchmarks.run table4       # substring filter
    PYTHONPATH=src python -m benchmarks.run --jobs 4     # parallel workers

``--jobs N`` runs independent benchmark modules in N forked worker
processes.  The Table-2 model fits are pre-warmed in the parent first, so
every worker inherits them copy-on-write instead of refitting (~the
single most expensive shared setup across modules).
"""

from __future__ import annotations

import argparse
import json
import sys
import traceback

BENCHES = [
    "bench_table2_perfmodel",
    "bench_fig3_fig7_adaptation",
    "bench_fig6_sensitivity",
    "bench_fig8_two_jobs",
    "bench_table4_cluster",
    "bench_fig10_fig11_simulation",
    "bench_fig9_accuracy",
    "bench_sched_overhead",
    "bench_sim_scale",
    "bench_sched_scale",
    "bench_calibration",
    "bench_failures",
    "bench_grayfail",
]


def _run_module(mod_name: str) -> tuple[str, list[dict], str | None]:
    try:
        mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
        return mod_name, list(mod.run()), None
    except Exception:
        return mod_name, [], traceback.format_exc()


def _print_rows(rows: list[dict]) -> None:
    for row in rows:
        derived = json.dumps(row.get("derived", {}),
                             separators=(",", ":"), default=str)
        print(f"{row['name']},{row.get('us_per_call', 0):.0f},"
              f"\"{derived}\"", flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("filter", nargs="?", default="",
                        help="substring filter on module names")
    parser.add_argument("--jobs", type=int, default=1,
                        help="worker processes (1 = sequential)")
    args = parser.parse_args()
    mods = [m for m in BENCHES if args.filter in m]
    print("name,us_per_call,derived")
    failures = 0
    if args.jobs > 1 and len(mods) > 1:
        import multiprocessing as mp

        from benchmarks import _artifacts
        _artifacts.prewarmed_fit_cache()   # warm BEFORE fork: workers
        ctx = mp.get_context("fork")       # inherit the fits read-only
        with ctx.Pool(min(args.jobs, len(mods))) as pool:
            for mod_name, rows, err in pool.imap_unordered(_run_module,
                                                           mods):
                if err is not None:
                    failures += 1
                    print(err, file=sys.stderr)
                    print(f"{mod_name},0,\"ERROR\"", flush=True)
                else:
                    _print_rows(rows)
    else:
        for mod_name in mods:
            mod_name, rows, err = _run_module(mod_name)
            if err is not None:
                failures += 1
                print(err, file=sys.stderr)
                print(f"{mod_name},0,\"ERROR\"", flush=True)
            else:
                _print_rows(rows)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
