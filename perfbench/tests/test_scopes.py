"""The split of device time by the program's named scopes: the map from
optimized HLO, the reduction of a trace by it, and the per-layer numbers."""

from pathlib import Path

import jax
import pytest

from cells import TINY, tiny_cell
from perfbench.lib import scopes, trace
from perfbench.lib.program import Program
from test_trace import Data, Ev, Line, Plane

DATA = Path(__file__).resolve().parent / "data"
LAYER_SCOPES = {"embed", "layers", "norm", "attn", "mlp", "loss", "optimizer"}


@pytest.mark.parametrize("workload", list(TINY))
def test_scope_map_of_tiny_cells(workload):
    cell = tiny_cell(workload)
    prog = Program(cell, jax.devices()[:cell.chips], reduced=True)
    smap = scopes.scope_map(prog.step.as_text())
    assert {s for s, _ in smap.values()} >= LAYER_SCOPES
    assert {p for _, p in smap.values()} >= set(scopes.PHASES)
    coll = [n for n in smap if trace.COLLECTIVE.search(n)]
    assert all(smap[n][0] is None for n in coll)
    assert bool(coll) == (cell.chips > 1)


HLO = """\
HloModule jit_train_step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %tanh.1 = f32[8]{0} tanh(%param_0), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/attn/tanh"}
}

%body.2 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %dot.3 = f32[8]{0} dot(%p), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/rematted_computation/mlp/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%dot.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="convert.14"}
  %all-gather.5 = f32[8]{0} all-gather(%fusion.4), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/closed_call/checkpoint/attn/all_gather"}
  %copy.6 = f32[8]{0} copy(%all-gather.5)
  ROOT %tuple.7 = (s32[], f32[8]{0}) tuple(%p, %copy.6), metadata={op_name="jit(train_step)/transpose(jvp(layers))/while/body/add"}
}

ENTRY %main.8 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %while.9 = (s32[], f32[8]{0}) while(%a), body=%body.2, metadata={op_name="jit(train_step)/transpose(jvp(layers))/while"}
  %gather.10 = f32[8]{0} gather(%a), metadata={op_name="jit(train_step)/jvp(embed)/jit(_take)/gather"}
  %mul.11 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(train_step)/optimizer/mul"}
  ROOT %copy.12 = f32[8]{0} copy(%mul.11)
}
"""


def test_scope_map_rules():
    smap = scopes.scope_map(HLO)
    assert smap["tanh.1"] == ("attn", "backward")
    assert smap["dot.3"] == ("mlp", "recompute")
    # compiler-named fusion: the scope of its fused computation
    assert smap["fusion.4"] == ("attn", "backward")
    # a collective never takes a scope
    assert smap["all-gather.5"] == (None, "backward")
    # an unnamed copy: the innermost scope its computation's named
    # instructions share; the body mixes phases
    assert smap["copy.6"] == ("layers", None)
    assert smap["while.9"] == ("layers", "backward")
    assert smap["gather.10"] == ("embed", "forward")
    assert smap["mul.11"] == ("optimizer", "update")
    # the entry computation's named instructions share no scope
    assert smap["copy.12"] == (None, None)


def scoped_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("step", 0, 100), Ev("sync", 5, 100),
        Ev("step", 100, 200), Ev("sync", 105, 200)])])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("while.9", 10, 90), Ev("tanh.1", 20, 50), Ev("all-gather.5", 50, 60),
        Ev("dot.3", 60, 80),
        Ev("mul.11", 110, 150), Ev("copy.12", 150, 160)])])
    return Data([host, dev])


def test_reduce_by_scope_synthetic():
    smap = scopes.scope_map(HLO)
    s = scopes.reduce(scoped_trace(), smap)
    assert s.steps == 2
    assert s.busy_s == pytest.approx(130e-9)
    # the all-gather, though under attn, counts as a collective only
    assert s.scope_s["attn"] == pytest.approx(30e-9)
    assert s.collective_s == pytest.approx(10e-9)
    # the while counts only its own time: 80 - 30 - 10 - 20
    assert s.scope_s["layers"] == pytest.approx(20e-9)
    assert s.scope_s["mlp"] == pytest.approx(20e-9)
    assert s.phase_s[("mlp", "recompute")] == pytest.approx(20e-9)
    assert s.scope_s["optimizer"] == pytest.approx(40e-9)
    assert s.scope_s[scopes.UNSCOPED] == pytest.approx(10e-9)
    assert s.unscoped_ops(smap) == [["copy.12", pytest.approx(10e-9)]]
    # nothing overlaps without nesting: scopes + unscoped + collectives = busy
    assert s.accounted_s == pytest.approx(s.busy_s)
    assert scopes.metric(s, smap, "attn_ms") == pytest.approx(1e3 * 15e-9)
    assert scopes.metric(s, smap, "remat_share") == pytest.approx(100 * 20 / 130)
    table = scopes.table_ms(s)
    assert set(table) == set(scopes.SCOPES) | {scopes.UNSCOPED, "collectives"}
    assert all(set(table[sc]) >= set(scopes.PHASES) for sc in scopes.SCOPES)


@pytest.mark.parametrize("name", scopes.METRICS)
def test_metric_is_none_without_trace_or_map(name):
    smap = scopes.scope_map(HLO)
    s = scopes.reduce(scoped_trace(), smap)
    assert scopes.metric(None, smap, name) is None
    assert scopes.metric(s, None, name) is None
    # a program without the scopes and without remat (an older commit)
    bare = {n: (None, "forward") for n in smap}
    assert scopes.metric(scopes.reduce(scoped_trace(), bare), bare, name) is None


def test_recorded_scoped_tpu_trace():
    """A trace recorded on one TPU v5e chip by ``record_scoped_trace.py``:
    three steps of a small train step whose forward runs under ``attn``
    and ``mlp``, with the step's optimized HLO beside it."""
    hlo = (DATA / "trace_scoped_1chip.hlo.txt").read_text()
    smap = scopes.scope_map(hlo)
    s = scopes.reduce_file(str(DATA / "trace_scoped_1chip.xplane.pb"), hlo)
    assert s.steps == 3 and s.collective_s == 0
    for scope in ("attn", "mlp"):
        assert s.scope_s[scope] > 0
        assert s.phase_s[(scope, "forward")] > 0
        assert s.phase_s[(scope, "backward")] > 0
        assert scopes.metric(s, smap, f"{scope}_ms") > 0
    assert s.scope_s.get(scopes.UNSCOPED, 0) < 0.01 * s.busy_s
    assert s.accounted_s == pytest.approx(s.busy_s, rel=0.01)
    # the trace's own reduction sees the same busy time
    full = trace.reduce_file(str(DATA / "trace_scoped_1chip.xplane.pb"))
    assert full.busy_s[0] == pytest.approx(s.busy_s)
