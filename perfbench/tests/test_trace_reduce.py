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


def test_engine_span_inside_a_job_names_its_gap():
    """A ``dampr.`` span inside ``job.run``, on another host thread,
    names the part of the gap it covers; the innermost span wins where
    two engine spans nest; the device side is as before."""
    host = Plane("/host:CPU", [
        Line("python", [Ev("perfbench.window", 0, 1000),
                        Ev("job.run", 0, 1000)]),
        Line("worker", [Ev("dampr.map.scan", 200, 700),
                        Ev("dampr.scan.pad", 300, 400),
                        Ev("PjitFunction(x)", 250, 260)])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [Ev("%fusion.1 = s32[8] fusion(%x)", 0, 100),
                         Ev("%fusion.1 = s32[8] fusion(%x)", 900, 1000)]),
        Line("XLA Modules", [Ev("jit_program(1)", 0, 1000)])])
    got = reduce_planes([host, dev])
    assert got["busy_s"] == pytest.approx(200e-9)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["breakdown"]["device_ops"] == [
        ["jit_program/fusion.1", pytest.approx(200e-9)]]
    assert got["breakdown"]["idle_gaps"] == [
        ["dampr.map.scan", pytest.approx(300e-9)],  # 400..700
        ["job.run", pytest.approx(200e-9)],         # 700..900
        ["job.run", pytest.approx(100e-9)],         # 100..200
        ["dampr.map.scan", pytest.approx(100e-9)],  # 200..300
        ["dampr.scan.pad", pytest.approx(100e-9)]]  # 300..400


def test_many_spans_name_every_piece_as_a_rescan_would():
    """The sweep over span edges against the plain rule: each piece of a
    gap named by the shortest span covering its midpoint, the first
    listed among equals."""
    import random

    rng = random.Random(7)
    spans = [Ev("perfbench.window", 0, 100000)]
    for i in range(400):
        a = rng.randrange(0, 100000)
        spans.append(Ev("dampr.s{}".format(i % 37), a,
                        a + rng.choice([0, 5, 50, 500, 5000])))
    ops = []
    t = 0
    while t < 100000:
        t += rng.randrange(1, 3000)
        ops.append(Ev("%op = f32[] add(%a)", t, t + rng.randrange(1, 900)))
    host = Plane("/host:CPU", [Line("t", spans)])
    dev = Plane("/device:TPU:0", [Line("XLA Ops", ops),
                                  Line("XLA Modules", [])])
    got = reduce_planes([host, dev], top=10 ** 6)["breakdown"]["idle_gaps"]
    busy = union((a, b) for a, b in ((max(e.start_ns, 0), min(e.end_ns, 100000))
                                     for e in ops) if b > a)
    gaps, prev = [], 0
    for a, b in busy + [[100000, 100000]]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    inner = [(e.start_ns, e.end_ns, e.name) for e in spans[1:]]
    edges = sorted({t for s in inner for t in s[:2]})
    want = []
    for a, b in gaps:
        cuts = [a] + [t for t in edges if a < t < b] + [b]
        for x, y in zip(cuts, cuts[1:]):
            mid = (x + y) / 2.0
            cover = [s for s in inner if s[0] <= mid <= s[1]]
            name = (min(cover, key=lambda s: s[1] - s[0])[2] if cover
                    else "perfbench.between")
            if want and want[-1][2] == x and want[-1][0] == name:
                want[-1][1] += (y - x) / 1e9
                want[-1][2] = y
            else:
                want.append([name, (y - x) / 1e9, y])
    want = sorted(([n, s] for n, s, _y in want), key=lambda g: -g[1])
    assert len(got) == len(want) > 100
    assert [g[0] for g in got] == [g[0] for g in want]
    assert [g[1] for g in got] == pytest.approx([g[1] for g in want])


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
    # As the reduction read it before it took the engine's spans: the
    # trace holds none, so nothing moves.
    assert got["busy_s"] == 0.002243162
    assert got["window_s"] == 0.067945407
    assert got["breakdown"]["device_ops"] == [
        ["jit__lambda/sort.6", 0.002225185],
        ["jit__lambda/multiply_add_fusion", 1.2988e-05],
        ["jit__lambda/reduce_sum.7", 3.618e-06],
        ["jit__lambda/iota", 1.371e-06]]
    assert gaps == [
        ["job.release", 0.020983539], ["job.release", 0.019365565],
        ["job.release", 0.018531147], ["job.run", 0.00203727],
        ["job.run", 0.00195848], ["job.run", 0.00188554],
        ["job.release", 0.000474774], ["job.release", 0.000442813],
        ["perfbench.between", 4.18e-06], ["perfbench.between", 3.609e-06]]
