"""Reduce a profiler trace (``.xplane.pb``) to the device numbers reported.

The traced window runs from the start of the first ``step`` span the
harness opened on the host to the end of the last.  Per device (a plane
named ``/device:TPU:<n>``, its line ``XLA Ops``):

- busy: the union of the intervals in which an operation ran, inside the
  window;
- exposed collective time: the time covered by collective operations
  (all-gather, all-reduce, reduce-scatter, all-to-all, collective-permute,
  and their -start/-done halves) and by no other operation;
- each operation's self time: its duration less that of the operations
  nested in it on the line (a ``while`` holds the ops of its loop body);
- idle gaps: the window minus busy, each named by the host span (``feed``,
  ``dispatch``, ``sync``) that overlaps it most, or ``other``.

Only innermost operations count as "other" against a collective, so a loop
that holds a collective does not hide it.

Host and device events share one clock in the trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"allgather|allreduce|reducescatter|alltoall|collectivepermute", re.I)
STEP_SPAN = "step"
HOST_SPANS = ("feed", "dispatch", "sync")
TOP = 10


def options():
    """Profiler options for a traced window: host spans, no Python tracer."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def union(intervals) -> list:
    """Merged, sorted list of (start, end) covering ``intervals``."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(merged) -> float:
    return sum(e - s for s, e in merged)


def subtract(a, b) -> list:
    """Parts of merged ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def overlap(a: tuple, merged) -> float:
    return sum(max(0.0, min(a[1], e) - max(a[0], s)) for s, e in merged)


@dataclass(frozen=True)
class Summary:
    window_s: float
    steps: int
    busy_s: dict              # device id -> seconds busy in the window
    collective_exposed_s: dict  # device id -> seconds; empty: no collectives
    device_ops: list          # [[op name, self seconds]], mean over devices
    idle_gaps: list           # [[host span, seconds]], the longest gaps

    @property
    def devices(self) -> int:
        return len(self.busy_s)


def _events(line):
    for ev in line.events:
        yield ev.name.split(" = ")[0].lstrip("%"), ev.start_ns, \
            ev.start_ns + ev.duration_ns


def nesting(evs) -> tuple[list, list]:
    """(self time, innermost?) of each of ``evs`` [(name, start, end)]."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2]))
    self_t = [e - s for _, s, e in evs]
    leaf = [True] * len(evs)
    stack: list = []
    for i in order:
        _, s, e = evs[i]
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            self_t[stack[-1]] -= e - s
            leaf[stack[-1]] = False
        stack.append(i)
    return self_t, leaf


def reduce(data) -> Summary:
    """``data``: a ``jax.profiler.ProfileData``."""
    steps, spans = [], defaultdict(list)
    devices = {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name == STEP_SPAN:
                        steps.append((s, e))
                    elif name in HOST_SPANS:
                        spans[name].append((s, e))
            continue
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = list(_events(line))
    if not steps:
        raise RuntimeError("trace holds no host 'step' span")
    if not devices:
        raise RuntimeError("trace holds no device plane with an 'XLA Ops' line")
    w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    spans = {k: union(v) for k, v in spans.items()}

    busy, exposed, op_time, gaps = {}, {}, defaultdict(float), []
    for dev, evs in devices.items():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        self_t, leaf = nesting(inside)
        for (n, _, _), t in zip(inside, self_t):
            op_time[n] += t / len(devices)
        busy_u = union((s, e) for _, s, e in inside)
        busy[dev] = length(busy_u) * 1e-9
        leaves = [ev for ev, is_leaf in zip(inside, leaf) if is_leaf]
        coll = union((s, e) for n, s, e in leaves if COLLECTIVE.search(n))
        if coll:
            other = union((s, e) for n, s, e in leaves if not COLLECTIVE.search(n))
            exposed[dev] = length(subtract(coll, other)) * 1e-9
        for g in subtract([(w0, w1)], busy_u):
            best = max(spans, key=lambda k: overlap(g, spans[k]), default=None)
            name = best if best and overlap(g, spans[best]) > 0 else "other"
            gaps.append([name, (g[1] - g[0]) * 1e-9])
    ops = sorted(([n, t * 1e-9] for n, t in op_time.items()),
                 key=lambda x: -x[1])[:TOP]
    gaps = sorted(gaps, key=lambda x: -x[1])[:TOP]
    return Summary(window_s=(w1 - w0) * 1e-9, steps=len(steps), busy_s=busy,
                   collective_exposed_s=exposed, device_ops=ops, idle_gaps=gaps)


def reduce_file(path: str) -> Summary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path))
