"""The trace reduction, on interval arithmetic and on a recorded trace."""

from pathlib import Path

import pytest

from perfbench.lib import trace

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_subtract():
    u = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert u == [(0, 3), (5, 8)]
    assert trace.length(u) == 6
    assert trace.subtract([(0, 10)], u) == [(3, 5), (8, 10)]
    assert trace.subtract([(0, 2), (4, 6)], [(1, 5)]) == [(0, 1), (5, 6)]
    assert trace.subtract(u, []) == u
    assert trace.overlap((2, 6), u) == 2


class Ev:
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.duration_ns = name, start, end - start


class Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class Data:
    def __init__(self, planes):
        self.planes = planes


def synthetic():
    host = Plane("/host:CPU", [Line("python", [
        Ev("step", 0, 100), Ev("feed", 0, 10), Ev("dispatch", 10, 20),
        Ev("sync", 20, 100),
        Ev("step", 100, 200), Ev("feed", 100, 130), Ev("dispatch", 130, 135),
        Ev("sync", 135, 200)])])
    dev0 = Plane("/device:TPU:0", [Line("XLA Ops", [
        Ev("fusion.1", 15, 60), Ev("all-gather-start.2", 60, 70),
        Ev("fusion.1", 65, 95),          # hides half of the collective
        Ev("fusion.1", 140, 190)])])
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [
        Ev("fusion.1", 15, 95), Ev("all-reduce.3", 150, 170)])])
    return Data([host, dev0, dev1])


def test_reduce_synthetic():
    s = trace.reduce(synthetic())
    assert s.window_s == pytest.approx(200e-9)
    assert s.steps == 2
    assert s.busy_s == {0: pytest.approx(130e-9), 1: pytest.approx(100e-9)}
    # device 0: all-gather 60-70, fusion from 65: 5 ns exposed; device 1: 20
    assert s.collective_exposed_s == {0: pytest.approx(5e-9), 1: pytest.approx(20e-9)}
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx((45 + 30 + 50 + 80) / 2 * 1e-9)
    # the longest gap: device 1, 95-150, mostly while the host fed step 2
    assert s.idle_gaps[0] == ["feed", pytest.approx(55e-9)]
    assert s.idle_gaps[1] == ["feed", pytest.approx(45e-9)]  # device 0, 95-140


def test_reduce_without_collectives_reports_none():
    data = synthetic()
    data.planes[1].lines[0].events = [Ev("fusion.1", 10, 50)]
    data.planes = data.planes[:2]
    assert trace.reduce(data).collective_exposed_s == {}


def test_a_loop_does_not_hide_its_collective():
    data = synthetic()
    data.planes[2].lines[0].events = [Ev("while.1", 10, 190), Ev("fusion.2", 20, 140),
                                      Ev("all-reduce.3", 150, 170)]
    s = trace.reduce(data)
    assert s.collective_exposed_s[1] == pytest.approx(20e-9)
    ops = dict(s.device_ops)
    # while.1 self time on device 1: 180 - 120 - 20 = 40; mean over 2 devices
    assert ops["while.1"] == pytest.approx(20e-9)


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e chip by ``record_trace.py``: three
    steps of a small program, each a few ops between host spans."""
    s = trace.reduce_file(str(DATA / "trace_1chip.xplane.pb"))
    assert s.steps == 3 and s.devices == 1
    assert 0 < s.busy_s[0] < s.window_s
    assert s.collective_exposed_s == {}
    names = [n for n, _ in s.device_ops]
    assert "convolution_tanh_fusion.2" in names
    assert all(" " not in n and not n.startswith("%") for n in names)
    assert {g[0] for g in s.idle_gaps} <= {"feed", "dispatch", "sync", "other"}


def test_recorded_four_chip_trace():
    """The same program recorded on a four-chip v5e host, where a reduction
    across the chips adds an all-reduce that nothing else overlaps."""
    s = trace.reduce_file(str(DATA / "trace_4chip.xplane.pb"))
    assert s.steps == 3 and s.devices == 4
    assert set(s.collective_exposed_s) == {0, 1, 2, 3}
    assert all(0 < v < s.busy_s[d] for d, v in s.collective_exposed_s.items())
    assert "all-reduce.2" in [n for n, _ in s.device_ops]
