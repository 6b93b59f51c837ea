"""``trace_reduce`` against a synthetic trace with a known answer, and
against a small trace recorded on a v5e (``record_tiny_trace.py``)."""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from trace_reduce import reduce_planes, union  # noqa: E402

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_v5e.xplane.pb")


class Ev(object):
    def __init__(self, name, start, end):
        self.name, self.start_ns, self.end_ns = name, start, end
        self.duration_ns = end - start


class Line(object):
    def __init__(self, name, events):
        self.name, self.events = name, events


class Plane(object):
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_union_merges_overlaps_and_touching_intervals():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (8, 9)]) == [
        [0, 4], [5, 7], [8, 9]]


def test_synthetic_trace():
    host = Plane("/host:CPU", [Line("python", [
        Ev("perfbench.window", 100, 1100),
        Ev("job.run", 100, 600), Ev("job.read", 600, 700),
        Ev("job.run", 700, 1100), Ev("PjitFunction(x)", 150, 160)])])
    ops = [Ev("%fusion.1 = s32[8] fusion(%x)", 50, 150),  # clipped
           Ev("%sort.2 = s32[8] sort(%y)", 140, 300),     # overlaps it
           Ev("%fusion.1 = s32[8] fusion(%x)", 800, 900),
           Ev("%reduce = s32[] reduce(%z)", 1050, 1200)]  # clipped
    modules = [Ev("jit_program(123)", 40, 950), Ev("jit_kernel(9)", 1000,
                                                     1300)]
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                  Line("XLA Modules", modules)])
    got = reduce_planes([host, dev, Plane("/host:metadata", [])])
    # busy: 100..300, 800..900, 1050..1100 -> 350 ns of a 1000 ns window
    assert got["busy_s"] == pytest.approx(350e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    # gaps 300..800 and 900..1050, cut at the host spans' edges
    assert got["breakdown"]["idle_gaps"] == [
        ["job.run", pytest.approx(300e-9)],   # 300..600
        ["job.run", pytest.approx(150e-9)],   # 900..1050
        ["job.read", pytest.approx(100e-9)],  # 600..700
        ["job.run", pytest.approx(100e-9)]]   # 700..800
    ops_ = dict((k, v) for k, v in got["breakdown"]["device_ops"])
    assert ops_["jit_program/fusion.1"] == pytest.approx(150e-9)
    assert ops_["jit_program/sort.2"] == pytest.approx(160e-9)
    assert ops_["jit_kernel/reduce"] == pytest.approx(50e-9)


def test_trace_without_a_tpu_plane_is_refused():
    host = Plane("/host:CPU", [Line("python", [
        Ev("perfbench.window", 0, 10)])])
    with pytest.raises(ValueError):
        reduce_planes([host])


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded v5e trace")
def test_recorded_v5e_trace():
    from jax.profiler import ProfileData

    data = ProfileData.from_file(RECORDED)
    got = reduce_planes(data.planes)
    assert 0 < got["busy_s"] < got["window_s"]
    gaps = got["breakdown"]["idle_gaps"]
    # The recorder sleeps 20 ms in each of three job.release spans; the
    # device clock leads the host's by ~1.7 ms, so a program can fall
    # into a release span and cut its gap.
    assert [g[0] for g in gaps[:3]] == ["job.release"] * 3
    assert all(g[1] >= 0.015 for g in gaps[:3])
    idle = got["window_s"] - got["busy_s"]
    assert sum(g[1] for g in gaps) <= idle + 1e-9
    assert got["breakdown"]["device_ops"]
