"""Split a traced step's device time by the program's named scopes.

The program opens a ``jax.named_scope`` at each layer of its training path
(``models.transformer.SCOPES``).  A scope changes only the ``op_name``
metadata of the compiled HLO; the trace names each device operation by its
HLO instruction.  So the compiled step's optimized HLO text
(``compiled.as_text()``) maps each operation of the trace to a layer:

- scope: the innermost component of ``op_name`` that is one of ``SCOPES``,
  once the ``jvp(``, ``transpose(`` and ``)`` that autodiff wraps around a
  scope are stripped.  An instruction the compiler made has no such path
  (no ``op_name``, or one like ``convert.14``): a fusion of them takes the
  innermost scope that all named instructions of its fused computation
  share; any other (a copy the compiler inserted) the innermost scope that
  all named instructions of its own computation share (``layers`` in the
  layer scan's body), if any;
- phase: ``recompute`` in a ``rematted_computation`` (remat's second
  forward), ``backward`` under ``transpose(jvp(``, ``forward`` under
  ``jvp(``, ``update`` otherwise (the optimizer);
- collectives (``trace.COLLECTIVE``) never take a scope: they are counted
  apart, as the collectives layer.

``reduce`` then sums the operations' self times (``trace.nesting``: each
operation less the operations nested in it, so a ``while`` counts only its
own time) over the traced window per scope and per (scope, phase),
averaged over the devices.  Time no scope claims is ``unscoped``.  The
scopes, ``unscoped`` and the collectives add up to the devices' busy time
when no two operations overlap without nesting.

A program without the scopes (an older commit) gives a map with no scope:
``metric`` then returns None for every per-scope number.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

from perfbench.lib import trace

# The program's scope names, as ``repro.models.transformer.SCOPES`` holds
# them; a test holds the two equal.
SCOPES = ("embed", "layers", "norm", "attn", "mlp", "moe", "loss", "optimizer")
PHASES = ("forward", "recompute", "backward", "update")
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) ")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
_WRAPPERS = re.compile(r"jvp\(|transpose\(|\)")


def classify(op_name: str) -> tuple:
    """(the scopes it is in, outermost first; phase) of one ``op_name``."""
    if "rematted_computation" in op_name:
        phase = "recompute"
    elif "transpose(jvp(" in op_name:
        phase = "backward"
    elif "jvp(" in op_name:
        phase = "forward"
    else:
        phase = "update"
    parts = _WRAPPERS.sub("", op_name).split("/")
    return tuple(p for p in parts if p in SCOPES), phase


def _common(paths) -> tuple:
    first = min(paths, key=len)
    n = next((i for i, p in enumerate(first)
              if any(q[i] != p for q in paths)), len(first))
    return first[:n]


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> (scope or None, phase or None), from an
    optimized HLO module's text."""
    named, unnamed, comp = defaultdict(dict), defaultdict(list), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if op and "/" in op.group(1):
            named[comp][m.group(1)] = classify(op.group(1))
        else:
            calls = _CALLS.search(line)
            unnamed[comp].append((m.group(1), calls and calls.group(1)))

    def shared(c) -> tuple:
        pp = named[c].values()
        phases = {ph for _, ph in pp}
        return (_common([p for p, _ in pp]) if pp else (),
                phases.pop() if len(phases) == 1 else None)

    out = {n: pp for c in named.values() for n, pp in c.items()}
    for c, names in unnamed.items():
        out.update((n, shared(called if named.get(called) else c))
                   for n, called in names)
    return {n: (None if trace.COLLECTIVE.search(n) or not path else path[-1],
                phase) for n, (path, phase) in out.items()}


@dataclass(frozen=True)
class Scoped:
    steps: int
    busy_s: float        # busy time in the window, mean over devices
    op_s: dict           # op name -> self seconds, mean over devices
    scope_s: dict        # scope or "unscoped" -> self seconds
    phase_s: dict        # (scope or "unscoped", phase or None) -> self seconds
    collective_s: float  # self seconds of the collective operations

    @property
    def accounted_s(self) -> float:
        """Scopes, ``unscoped`` and collectives: the busy time, less any
        overlap of operations that do not nest."""
        return sum(self.scope_s.values()) + self.collective_s

    def unscoped_ops(self, smap: dict) -> list:
        """[[op name, self seconds]] of the longest operations no scope
        claims."""
        loose = [[n, t] for n, t in self.op_s.items()
                 if not trace.COLLECTIVE.search(n) and
                 smap.get(n, (None, None))[0] is None]
        return sorted(loose, key=lambda x: -x[1])[:trace.TOP]


def self_times(data) -> tuple:
    """(steps, busy seconds, {op name: self seconds}) of the traced window,
    mean over devices, as ``trace.reduce`` takes window and self times."""
    steps, devices = [], {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            steps += [(s, e) for line in plane.lines
                      for n, s, e in trace._events(line) if n == trace.STEP_SPAN]
            continue
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    devices[int(m.group(1))] = list(trace._events(line))
    if not steps or not devices:
        raise RuntimeError("trace holds no host 'step' span or no device ops")
    w0, w1 = min(s for s, _ in steps), max(e for _, e in steps)
    k = 1e-9 / len(devices)
    busy, op_s = 0.0, defaultdict(float)
    for evs in devices.values():
        inside = [(n, max(s, w0), min(e, w1)) for n, s, e in evs
                  if e > w0 and s < w1]
        busy += trace.length(trace.union((s, e) for _, s, e in inside)) * k
        self_t, _ = trace.nesting(inside)
        for (n, _, _), t in zip(inside, self_t):
            op_s[n] += t * k
    return len(steps), busy, dict(op_s)


def split(steps: int, busy_s: float, op_s: dict, smap: dict) -> Scoped:
    """Sum the operations' self times by scope and by (scope, phase); also
    reduces a saved ``self_times`` again (``scope_trace.py``'s output)."""
    scope_s, phase_s, coll = defaultdict(float), defaultdict(float), 0.0
    for n, t in op_s.items():
        if trace.COLLECTIVE.search(n):
            coll += t
            continue
        scope, phase = smap.get(n, (None, None))
        scope_s[scope or UNSCOPED] += t
        phase_s[(scope or UNSCOPED, phase)] += t
    return Scoped(steps=steps, busy_s=busy_s, op_s=op_s, scope_s=dict(scope_s),
                  phase_s=dict(phase_s), collective_s=coll)


def reduce(data, smap: dict) -> Scoped:
    """``data``: a ``jax.profiler.ProfileData`` of the harness's traced
    steps; ``smap``: ``scope_map`` of the step that ran."""
    return split(*self_times(data), smap)


def reduce_file(path: str, hlo_text: str) -> Scoped:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(path), scope_map(hlo_text))


# metric name -> the scope whose self time it reads, in ms a step
SCOPE_METRICS = {"attn_ms": "attn", "mlp_ms": "mlp", "loss_ms": "loss",
                 "optimizer_ms": "optimizer"}
METRICS = tuple(SCOPE_METRICS) + ("remat_share",)


def metric(s: Scoped | None, smap: dict | None, name: str):
    """A per-layer number of the traced steps, or None without a trace or a
    map, or when the program has no such scope or phase:

    - ``attn_ms``, ``mlp_ms``, ``loss_ms``, ``optimizer_ms``: self time
      under that scope, all phases, in ms a step;
    - ``remat_share`` (%): recompute-phase self time over busy time.
    """
    if s is None or smap is None:
        return None
    if name == "remat_share":
        if not any(p == "recompute" for _, p in smap.values()):
            return None
        rec = sum(t for (_, p), t in s.phase_s.items() if p == "recompute")
        return 100.0 * rec / s.busy_s
    scope = SCOPE_METRICS[name]
    if not any(sc == scope for sc, _ in smap.values()):
        return None
    return 1e3 * s.scope_s.get(scope, 0.0) / s.steps


def table_ms(s: Scoped) -> dict:
    """{scope: {phase: ms a step}} for every scope and ``unscoped``, each
    with every phase (``none``: an instruction whose phase no named
    instruction gave), plus ``collectives``."""
    out = {sc: dict.fromkeys(PHASES, 0.0) for sc in SCOPES + (UNSCOPED,)}
    for (sc, ph), t in s.phase_s.items():
        row = out[sc]
        row[ph or "none"] = row.get(ph or "none", 0.0) + 1e3 * t / s.steps
    out["collectives"] = 1e3 * s.collective_s / s.steps
    return out
