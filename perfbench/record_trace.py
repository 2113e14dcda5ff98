"""Record a small profiler trace of the harness's step spans, for the tests.

    python3 perfbench/record_trace.py <out_dir>

Runs a few steps of a small jitted program (a matmul chain, and on several
chips an all-gather and a reduction across them) under the same host spans
the harness opens (``step`` around ``feed``, ``dispatch`` and ``sync``),
copies the ``.xplane.pb`` to ``<out_dir>/trace_<n>chip.xplane.pb``, and
prints the planes, lines and first events of the trace.
"""

from __future__ import annotations

import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main() -> None:
    sys.path[:0] = [str(ROOT)]
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from perfbench.lib import trace

    out = Path(sys.argv[1])
    devs = jax.devices()
    n = len(devs)
    mesh = jax.make_mesh((n,), ("d",), devices=devs)
    row = NamedSharding(mesh, P("d"))

    @jax.jit
    def step(x, w):
        for _ in range(4):
            x = jnp.tanh(x @ w)
        full = jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))
        return x + jnp.mean(full), jnp.sum(x)

    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    x = jax.device_put(jnp.ones((256 * n, 1024), jnp.bfloat16), row)
    jax.block_until_ready(step(x, w))
    tdir = ROOT / ".perfbench" / "record_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir), profiler_options=trace.options())
    for _ in range(3):
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("feed"):
                x = jax.device_put(x, row)
            with jax.profiler.TraceAnnotation("dispatch"):
                x, s = step(x, w)
            with jax.profiler.TraceAnnotation("sync"):
                jax.block_until_ready((x, s))
    jax.profiler.stop_trace()
    src = trace.find_xplane(str(tdir))
    out.mkdir(parents=True, exist_ok=True)
    dst = out / f"trace_{n}chip.xplane.pb"
    shutil.copy(src, dst)
    shutil.rmtree(tdir, ignore_errors=True)

    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(dst))
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                print("    ", repr(ev.name), ev.start_ns, ev.duration_ns,
                      [k for k, _ in ev.stats][:8])
    print(trace.reduce(data))


if __name__ == "__main__":
    main()
