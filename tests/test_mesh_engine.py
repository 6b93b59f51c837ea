"""Engine <-> mesh integration: device-foldable associative reduces route
through the collective shuffle on multi-device meshes, with exact fallbacks."""

import numpy as np
import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.runner import MTRunner


@pytest.fixture(autouse=True)
def small_partitions():
    old = (settings.partitions, settings.mesh_fold)
    settings.partitions = 8
    settings.mesh_fold = "auto"
    yield
    settings.partitions, settings.mesh_fold = old


def _run_counting(pipe):
    pipe = pipe if not pipe.agg else pipe.checkpoint()
    runner = MTRunner("mesh-engine-test", pipe.pmer.graph)
    out = runner.run([pipe.source])
    return out[0], runner


class TestMeshFoldEngagement:
    def test_count_routes_through_mesh(self):
        pipe = Dampr.memory(list(range(5000)), partitions=8).count(
            lambda x: x % 7)
        ds, runner = _run_counting(pipe)
        assert runner.mesh_folds >= 1
        got = dict(v for _k, v in ds.read())
        want = {i: len(range(i, 5000, 7)) for i in range(7)}
        assert got == want

    def test_sum_matches_host_path(self):
        data = list(range(3000))
        mesh_out = (Dampr.memory(data, partitions=8)
                    .a_group_by(lambda x: x % 5).sum().read())
        settings.mesh_fold = "off"
        host_out = (Dampr.memory(data, partitions=8)
                    .a_group_by(lambda x: x % 5).sum().read())
        assert mesh_out == host_out

    def test_min_max_via_mesh(self):
        data = [(i % 4, i) for i in range(2000)]
        mn = dict(Dampr.memory(data, partitions=8)
                  .a_group_by(lambda x: x[0], lambda x: x[1])
                  .reduce(min).read())
        assert mn == {0: 0, 1: 1, 2: 2, 3: 3}
        mx = dict(Dampr.memory(data, partitions=8)
                  .a_group_by(lambda x: x[0], lambda x: x[1])
                  .reduce(max).read())
        assert mx == {0: 1996, 1: 1997, 2: 1998, 3: 1999}

    def test_opaque_binop_stays_on_host(self):
        pipe = (Dampr.memory(list(range(100)), partitions=4)
                .a_group_by(lambda x: x % 3)
                .reduce(lambda a, b: a + b))
        ds, runner = _run_counting(pipe)
        assert runner.mesh_folds == 0  # opaque Python binop: host path
        got = dict(v for _k, v in ds.read())
        assert got == {i: sum(range(i, 100, 3)) for i in range(3)}

    def test_object_values_stay_on_host(self):
        pipe = (Dampr.memory(["a", "bb", "a"], partitions=2)
                .a_group_by(lambda s: s).sum())  # str concat: object lane
        ds, runner = _run_counting(pipe)
        assert runner.mesh_folds == 0
        got = dict(v for _k, v in ds.read())
        assert got == {"a": "aa", "bb": "bb"}

    def test_large_values_fall_back_exactly(self):
        # int64 beyond 32-bit lanes: host path keeps exactness
        data = [("k", 2 ** 40)] * 50
        out = dict(Dampr.memory(data, partitions=4)
                   .a_group_by(lambda x: x[0], lambda x: x[1]).sum().read())
        assert out == {"k": 50 * 2 ** 40}

    def test_string_keys_via_mesh(self):
        words = ["alpha", "beta", "gamma"] * 500
        pipe = Dampr.memory(words, partitions=8).count()
        ds, runner = _run_counting(pipe)
        assert runner.mesh_folds >= 1
        got = dict(v for _k, v in ds.read())
        assert got == {"alpha": 500, "beta": 500, "gamma": 500}


class TestMeshFoldOverBudget:
    """The mesh path must survive over-budget (spilled)
    inputs by streaming windows through the collective, instead of bailing
    to the host exactly when distribution would pay."""

    def test_spilled_count_stays_on_mesh(self):
        # 5000 distinct keys keep map-side combining from shrinking the
        # exchange below the 64KB budget: the reduce input spills, and the
        # mesh fold must stream the spilled runs in windows, not refuse.
        data = [i % 5000 for i in range(60000)]
        pipe = Dampr.memory(data, partitions=8).count()
        pipe = pipe if not pipe.agg else pipe.checkpoint()
        runner = MTRunner("mesh-overbudget", pipe.pmer.graph,
                          memory_budget=1 << 16)
        out = runner.run([pipe.source])[0]
        assert runner.mesh_folds >= 1, "over-budget input left the mesh path"
        assert runner.store.spill_count > 0, "input never spilled"
        got = dict(v for _k, v in out.read())
        want = {k: 12 for k in range(5000)}
        assert got == want

    def test_spilled_string_fold_windows_exact(self):
        words = ["w%d" % (i % 499) for i in range(40000)]
        pipe = Dampr.memory(words, partitions=8).count()
        pipe = pipe if not pipe.agg else pipe.checkpoint()
        runner = MTRunner("mesh-overbudget-str", pipe.pmer.graph,
                          memory_budget=1 << 16)
        out = runner.run([pipe.source])[0]
        assert runner.mesh_folds >= 1
        got = dict(v for _k, v in out.read())
        assert got == {"w%d" % k: len(range(k, 40000, 499)) and
                       len([i for i in range(40000) if i % 499 == k])
                       for k in range(499)}

    def test_cross_window_overflow_falls_back_exact(self):
        # Each window's values fit the 32-bit lanes but the cross-window
        # total does not: the running host-side bound must push the fold to
        # the exact host path instead of wrapping device partials.  7000
        # distinct keys defeat map-side combining, so the reduce input is
        # ~2.7MB >> the 1MB window floor — genuinely multi-window (a
        # single-window regression cannot hide here) — while each window's
        # own abs-sum (~43k records x 3e4 ≈ 1.3e9) stays under 2^31.
        n, k, val = 120000, 7000, 30000  # total 3.6e9 > 2^31
        data = [(i % k, val) for i in range(n)]
        pipe = (Dampr.memory(data, partitions=8)
                .a_group_by(lambda x: x[0], lambda x: x[1]).sum()
                .checkpoint())
        runner = MTRunner("mesh-xwindow", pipe.pmer.graph,
                          memory_budget=1 << 16)
        out = dict(v for _k, v in runner.run([pipe.source])[0].read())
        want = {i: (n // k + (1 if i < n % k else 0)) * val
                for i in range(k)}
        assert out == want

    def test_min_over_budget_matches_host(self):
        data = [(i % 97, (i * 7919) % 100003) for i in range(30000)]

        def build():
            return (Dampr.memory(data, partitions=8)
                    .a_group_by(lambda x: x[0], lambda x: x[1]).reduce(min)
                    .checkpoint())

        p1 = build()
        r1 = MTRunner("mesh-ob-min", p1.pmer.graph, memory_budget=1 << 16)
        mesh_got = sorted(v for _k, v in r1.run([p1.source])[0].read())
        assert r1.mesh_folds >= 1
        settings.mesh_fold = "off"
        p2 = build()
        r2 = MTRunner("host-ob-min", p2.pmer.graph, memory_budget=1 << 16)
        host_got = sorted(v for _k, v in r2.run([p2.source])[0].read())
        assert mesh_got == host_got
