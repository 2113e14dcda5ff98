"""One run of one benchmark cell on the chips of this machine.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up (timed from process start): JAX, the mesh, the program's train step
(compiled, or read from the compile cache in ``<checkout>/.jax_cache``),
weights and AdamW state made on the devices from the seed, and the three
steps that ``correct`` compares, which also warm every program the window
calls.  The window then runs steps for ``--seconds``: feed the batch, run
the step, wait for it.  A compilation inside the window fails the run.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window untraced, then a few steps under the profiler, and reports
the per-layer metrics, the device's busy time and a breakdown.  After the
window the program's state is freed and the plain reference trains the
same three steps; the gaps between the two, each with its limit, decide
``correct``.  The last line of standard output is one JSON object.

Exits non-zero, printing no result, when JAX finds no accelerator or fewer
chips than the cell asks for.
"""

from time import perf_counter

T0 = perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".perfbench" / "trace"
CHECK_STEPS = 3


@dataclass(frozen=True)
class Ctx:
    """What a per-layer metric reader may read."""
    tokens_per_s: float
    flops_per_token: float
    chips: int
    device_kind: str
    hbm_peak_bytes: int
    trace: object            # lib.trace.Summary, or None


class CompileCounter:
    """Counts tracing, lowering and compilation events while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, _secs, **_kw):
        if self.armed and name.startswith("/jax/core/compile/"):
            self.count += 1

    def _event(self, name, **_kw):
        if self.armed and name == "/jax/compilation_cache/compile_requests_use_cache":
            self.count += 1


class GcPauses:
    """Seconds of each garbage collection of the interpreter while open."""

    def __init__(self):
        self.seconds, self._t = [], None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, _info):
        if phase == "start":
            self._t = perf_counter()
        elif self._t is not None:
            self.seconds.append(perf_counter() - self._t)

    def close(self):
        gc.callbacks.remove(self._cb)


def accelerator(chips: int):
    """The first ``chips`` accelerator devices; exits when there are none."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        sys.exit("no accelerator: JAX found only the CPU")
    if len(devs) < chips:
        sys.exit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def _pct(xs, q):
    import numpy as np
    return float(np.percentile(np.asarray(xs), q))


def run(cell, seed: int, seconds: float, traced: bool, devices,
        reduced: bool = False, t0: float = T0) -> dict:
    """One run; returns the result object.  ``reduced`` builds the
    program's reduced preset (the tests' CPU runs)."""
    import jax

    from perfbench.lib import compare, flops, spec, trace, weights
    from perfbench.lib.program import Program
    from perfbench.lib.traffic import MarkovTokens

    conf, m = cell.config, cell.config["model"]
    counter = CompileCounter()
    marks = {"start": perf_counter() - t0}
    prog = Program(cell, devices, reduced=reduced)
    marks["step_built"] = perf_counter() - t0
    refmod = spec.reference(conf["reference"])
    if {p: (s.shape, s.dtype) for p, s in prog.shapes.items()} != \
            {p: (s.shape, s.dtype) for p, s in refmod.param_shapes(m).items()}:
        raise SystemExit("the program's parameters differ from the reference's")
    gen = MarkovTokens(m["vocab_size"], cell.traffic, seed)
    key = weights.seed_key(seed)
    params, opt_state = jax.block_until_ready(prog.init(key))
    marks["state_made"] = perf_counter() - t0
    params, opt_state, prog_read = prog.first_steps(params, opt_state,
                                                    gen.batch, key, CHECK_STEPS)
    setup_s = perf_counter() - t0

    counter.armed = True
    pauses = GcPauses()
    step, phases, losses = CHECK_STEPS, [], []
    w0 = perf_counter()
    while perf_counter() - w0 < seconds:
        params, opt_state, met, ph = prog.train_step(params, opt_state,
                                                     gen.batch, step)
        phases.append(ph)
        losses.append(met["loss"])
        step += 1
    window_s = perf_counter() - w0
    pauses.close()
    times = [sum(p) for p in phases]
    summary = None
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=trace.options())
        for _ in range(cell.traffic["trace_steps"]):
            params, opt_state, met, _ = prog.train_step(params, opt_state,
                                                        gen.batch, step)
            losses.append(met["loss"])
            step += 1
        jax.profiler.stop_trace()
    counter.armed = False
    if counter.count:
        raise RuntimeError(f"{counter.count} compilations inside the window")
    if traced:
        summary = trace.reduce_file(trace.find_xplane(str(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    nonfinite = sum(not math.isfinite(float(x)) for x in losses)
    stats = [d.memory_stats() or {} for d in devices]
    mem_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    tokens = len(times) * prog.batch * prog.seq
    e2e = {"tokens_per_s": tokens / window_s,
           "step_s_p95": _pct(times, 95),
           "setup_s": setup_s}
    ctx = Ctx(tokens_per_s=e2e["tokens_per_s"],
              flops_per_token=flops.per_token(m, prog.seq), chips=len(devices),
              device_kind=devices[0].device_kind,
              hbm_peak_bytes=prog.hbm_peak_bytes, trace=summary)
    del params, opt_state, met, prog
    gc.collect()

    ref = refmod.Reference(m, conf["optimizer"], devices).train(
        key, [gen.batch(k) for k in range(CHECK_STEPS)], CHECK_STEPS)
    values, worst = compare.numbers(prog_read, ref)
    values["nonfinite_steps"] = nonfinite
    ok, checks = compare.judge(values, cell.limits)

    if traced:
        metrics = {}
        for mt in cell.per_layer:
            v = spec.reader(mt["name"])(ctx)
            if v is not None:
                metrics[mt["name"]] = {"value": v, "unit": mt["unit"]}
    else:
        metrics = {mt["name"]: {"value": e2e[mt["name"]], "unit": mt["unit"]}
                   for mt in cell.end_to_end}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": jax.device_count(), "memory_peak_bytes": mem_peak}
    result = {"correct": ok, "attempted": step - CHECK_STEPS,
              "failed": nonfinite, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = sum(summary.busy_s.values()) / summary.devices
        device["window_s"] = summary.window_s
        result["breakdown"] = {"device_ops": summary.device_ops,
                               "idle_gaps": summary.idle_gaps}
    result["detail"] = {"program": compare.readings(prog_read),
                        "reference": compare.readings(ref), "worst_leaf": worst,
                        "steps": len(times), "window_s": window_s,
                        "setup_marks_s": marks,
                        "step_s": times, "gc_pauses_s": pauses.seconds,
                        "slowest_steps": sorted(phases, key=sum)[-3:]}
    result["checks"] = checks
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / ".perfbench" / "tpu_logs"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from perfbench.lib import spec
    cell = spec.load(args.workload)
    devices = accelerator(cell.chips)
    result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
