"""Record a small profiler trace of a step under two named scopes, for the
tests of ``lib/scopes.py``.

    python3 perfbench/record_scoped_trace.py <out_dir>

Runs a few steps of a small jitted train step (a forward under the scopes
``attn`` and ``mlp``, its gradient, an update under none) under the host
spans the harness opens (``step`` around ``feed``, ``dispatch`` and
``sync``), and writes the pair the tests reduce:
``<out_dir>/trace_scoped_<n>chip.xplane.pb`` and the step's optimized HLO,
``<out_dir>/trace_scoped_<n>chip.hlo.txt``, both without the checkout's
path.  Prints the reduction by scope.
"""

from __future__ import annotations

import re
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_SOURCE = re.compile(r'\s(?:source_file="[^"]*"|stack_frame_id=\d+|'
                     r'source_(?:end_)?(?:line|column)=\d+)')


def portable(text: str) -> str:
    """HLO text without the source locations its metadata points to (the
    file names of the machine that compiled it); ``op_name`` stays."""
    out, skip = [], False
    for line in text.splitlines():
        if line in _TABLES:
            skip = True
        elif skip and not line:
            skip = False
        elif not skip:
            out.append(_SOURCE.sub("", line))
    return "\n".join(out) + "\n"


def scrub(blob: bytes, root: Path) -> bytes:
    """The trace with the checkout's path (in its source locations) put
    out of sight by a mark of the same length, so every length-prefixed
    string of the protobuf keeps its length."""
    path = str(root).encode()
    mark = (b"<checkout" + b"-" * len(path))[:len(path) - 1] + b">"
    return blob.replace(path, mark)


def main() -> None:
    sys.path[:0] = [str(ROOT)]
    import jax
    import jax.numpy as jnp

    from perfbench.lib import scopes, trace

    out = Path(sys.argv[1])
    n = len(jax.devices())

    def loss(w, x):
        with jax.named_scope("attn"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("mlp"):
            h = jnp.tanh(h @ w)
        return jnp.sum(h.astype(jnp.float32) ** 2)

    def step(w, x):
        g = jax.grad(loss)(w, x)
        return w - 1e-3 * g

    w = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    x = jnp.ones((2048, 1024), jnp.bfloat16)
    compiled = jax.jit(step).lower(w, x).compile()
    jax.block_until_ready(compiled(w, x))
    tdir = ROOT / ".perfbench" / "record_scoped_trace"
    shutil.rmtree(tdir, ignore_errors=True)
    jax.profiler.start_trace(str(tdir), profiler_options=trace.options())
    for _ in range(3):
        with jax.profiler.TraceAnnotation("step"):
            with jax.profiler.TraceAnnotation("feed"):
                x = jax.device_put(x)
            with jax.profiler.TraceAnnotation("dispatch"):
                w = compiled(w, x)
            with jax.profiler.TraceAnnotation("sync"):
                jax.block_until_ready(w)
    jax.profiler.stop_trace()
    out.mkdir(parents=True, exist_ok=True)
    stem = out / f"trace_scoped_{n}chip"
    Path(f"{stem}.xplane.pb").write_bytes(
        scrub(Path(trace.find_xplane(str(tdir))).read_bytes(), ROOT))
    shutil.rmtree(tdir, ignore_errors=True)
    text = portable(compiled.as_text())
    Path(f"{stem}.hlo.txt").write_text(text)

    s = scopes.reduce_file(f"{stem}.xplane.pb", text)
    print(scopes.table_ms(s))
    print({k: round(v * 1e6, 1) for k, v in s.op_s.items()}, "us",
          "busy", s.busy_s * 1e6, "accounted", s.accounted_s * 1e6)


if __name__ == "__main__":
    main()
