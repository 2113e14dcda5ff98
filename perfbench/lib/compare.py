"""The numbers that decide ``correct``, and their limits.

The program's first three steps are compared with the reference's:

- ``loss_gap``: the largest relative gap of a step's loss;
- ``grad_gap``: the worst leaf's gap between the norms of the first
  gradient (before the clip), over the reference's norm of that leaf or of
  the median leaf, whichever is larger;
- ``grad_diff``: the worst counted leaf's norm of the difference between
  the program's and the reference's first gradient, over the reference's
  norm, both taken on the same sample of the leaf's elements
  (``weights.sample``).  A gap of norms is second order in zero-mean
  rounding noise; this is first order, so it sees products taken in a
  precision below the configuration's;
- ``change_gap``: the gap of each leaf's change norm after the three steps,
  over the reference's norm of that leaf or of the median leaf, whichever
  is larger;
- ``leaf_change_gap``: the same over the leaf's own reference norm alone,
  so that a small leaf left unmoved or moved double reads 1;
- ``nonfinite_steps``: steps of the window whose loss is not finite.

Leaves counted in ``grad_diff`` and both change gaps are those whose first
gradient in the reference is at least a thousandth of the median leaf's
(the others move under Adam by round-off alone).
"""

from __future__ import annotations

import statistics

import numpy as np

COUNTED = 1e-3


def _worst(gaps: dict) -> tuple[float, str]:
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


def _norm_gaps(prog: dict, ref: dict, paths, own: bool = False) -> dict:
    med = statistics.median(ref[p] for p in paths)
    return {p: abs(prog[p] - ref[p]) / (ref[p] if own else max(ref[p], med))
            for p in paths}


def _diff(a: np.ndarray, b: np.ndarray) -> float:
    ref = float(np.linalg.norm(b))
    d = float(np.linalg.norm(a - b))
    return d / ref if ref > 0 else (0.0 if d == 0 else float("inf"))


def counted(ref: dict) -> list:
    g = ref["grad_norms"]
    med = statistics.median(g.values())
    return [p for p in g if g[p] >= COUNTED * med]


def grad_diff(prog: dict, ref: dict) -> tuple[float, str]:
    return _worst({p: _diff(prog["grad_sample"][p], ref["grad_sample"][p])
                   for p in counted(ref)})


def numbers(prog: dict, ref: dict) -> tuple[dict, dict]:
    """(numbers, worst leaf of each leaf number) of program vs reference."""
    if set(prog["grad_norms"]) != set(ref["grad_norms"]):
        raise ValueError("program and reference leaves differ: "
                         f"{sorted(prog['grad_norms'])} vs {sorted(ref['grad_norms'])}")
    values, worst = {}, {}
    values["loss_gap"] = max(abs(a - b) / abs(b)
                             for a, b in zip(prog["losses"], ref["losses"]))
    g_ref = ref["grad_norms"]
    values["grad_gap"], worst["grad_gap"] = _worst(
        _norm_gaps(prog["grad_norms"], g_ref, list(g_ref)))
    values["grad_diff"], worst["grad_diff"] = grad_diff(prog, ref)
    c_prog, c_ref, paths = prog["change_norms"], ref["change_norms"], counted(ref)
    values["change_gap"], worst["change_gap"] = _worst(
        _norm_gaps(c_prog, c_ref, paths))
    values["leaf_change_gap"], worst["leaf_change_gap"] = _worst(
        _norm_gaps(c_prog, c_ref, paths, own=True))
    return values, worst


def readings(read: dict) -> dict:
    """A side's readings without its gradient sample, for printing."""
    return {k: v for k, v in read.items() if k != "grad_sample"}


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    if set(values) - set(limits):
        raise ValueError(f"no limit for {sorted(set(values) - set(limits))}")
    checks = {k: {"value": values[k], "limit": limits[k]} for k in limits}
    return all(c["value"] <= c["limit"] for c in checks.values()), checks
