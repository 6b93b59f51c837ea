"""The engine's spans on the profiler's clock, the lowered scan's phase
buckets, and the names of the device programs.

- Under ``jax.profiler.trace`` a lowered word count leaves ``dampr.*``
  annotations (stages, the scan's phases) on the trace's host plane, read
  back with ``jax.profiler.ProfileData`` as ``perfbench/trace_reduce.py``
  reads a trace.
- With no session and no tracer a span is the shared no-op.
- ``devtime``'s scan buckets are wall-clock unions over threads.
- No compiled program is called ``program`` or ``kernel``.
- The benchmark's readers of the scan buckets.
"""

import glob
import importlib.util
import operator
import os
import re
import threading
import time

import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.obs import trace
from dampr_tpu.ops import devtime

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_compiled = []


def _on_compile(event, secs, fun_name="?", **_kw):
    if event == "/jax/core/compile/backend_compile_duration":
        _compiled.append(fun_name)


def _corpus(path, lines=6000):
    words = ["alpha", "beta", "gamma", "delta", "tok7", "zz", "mu", "xi"]
    with open(path, "w") as f:
        for i in range(lines):
            f.write(" ".join(words[(i * 7 + j) % len(words)]
                             for j in range(9)) + "\n")
    return str(path)


@pytest.fixture(scope="module", params=["off", "on"])
def profiled_run(request, tmp_path_factory):
    """One lowered word count under a profiler session, with the handoff
    tier off (the classic program) or on (the table probe), and every
    program compiled or loaded inside it.  Returns the host-plane event
    names, the compiled programs' names and the counts."""
    import jax
    from jax.profiler import ProfileData

    from dampr_tpu.ops.text import TokenCounts

    tmp = tmp_path_factory.mktemp("spans-" + request.param)
    corpus = _corpus(tmp / "corpus.txt")
    old = (settings.lower, settings.handoff, settings.scan_window_bytes,
           settings.scratch_root)
    settings.lower = "on"
    settings.handoff = request.param
    # Several windows per job: the handoff leg seeds its vocabulary from
    # the first window, the later ones run the table probe.
    settings.scan_window_bytes = 16 * 1024
    settings.scratch_root = str(tmp / "scratch")
    jax.monitoring.register_event_duration_secs_listener(_on_compile)
    jax.clear_caches()
    del _compiled[:]
    try:
        with jax.profiler.trace(str(tmp / "trace")):
            em = (Dampr.text(corpus, 1 << 17)
                  .custom_mapper(TokenCounts(mode="word", lower=True,
                                             pair_values=False))
                  .fold_values(operator.add)
                  .run("spans-" + request.param))
            counts = dict(em.read())
            stats = em.stats()
            em.delete()
    finally:
        (settings.lower, settings.handoff, settings.scan_window_bytes,
         settings.scratch_root) = old
    compiled = list(_compiled)
    xplane = glob.glob(str(tmp / "trace" / "plugins" / "profile" / "*"
                           / "*.xplane.pb"))
    assert len(xplane) == 1, xplane
    data = ProfileData.from_file(xplane[0])
    names = set()
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(trace.PREFIX):
                        names.add(ev.name)
    return {"handoff": request.param, "names": names,
            "compiled": compiled, "counts": counts, "stats": stats}


class TestProfilerClock:
    def test_stage_and_scan_spans_on_host_plane(self, profiled_run):
        names = profiled_run["names"]
        assert any(n.startswith("dampr.stage.s") for n in names), names
        assert any(n.startswith("dampr.scan.") for n in names), names
        assert "dampr.plan.apply" in names
        assert "dampr.run.finalize" in names
        if profiled_run["handoff"] == "on":
            assert "dampr.handoff.table-probe" in names, names
        else:
            assert {"dampr.device.dispatch", "dampr.device.wait",
                    "dampr.device.fetch"} <= names, names
        # Per-call values stay out of the names.
        assert not any(re.search(r"\d{3,}", n) for n in names), names
        # Results are those of the untraced program.
        assert profiled_run["counts"]["alpha"] > 1000

    def test_scan_buckets_in_run_stats(self, profiled_run):
        dev = profiled_run["stats"]["devtime"]
        assert dev["scan_feed"] > 0 and dev["scan_drain"] > 0
        assert dev["scan_wait"] >= 0
        wall = profiled_run["stats"]["wall_seconds"]
        for key in devtime.UNIONS:
            assert dev[key] <= wall + 1e-3, (key, dev, wall)

    def test_compiled_programs_are_named(self, profiled_run):
        compiled = profiled_run["compiled"]
        assert compiled, "the run compiled or loaded nothing"
        bad = [n for n in compiled
               if re.search(r"\b(program|kernel)\b", n)]
        assert not bad, compiled
        want = ("table_probe" if profiled_run["handoff"] == "on"
                else "token_fold")
        assert any(want in n for n in compiled), compiled


class TestOffPath:
    def test_span_is_shared_noop_without_session_or_tracer(self):
        assert not trace._collecting()
        assert not trace.enabled()
        assert trace.span("scan", "pad") is trace.span("stage", "s1:map")
        assert trace.annotate("scan", "pad") is None
        with trace.span("x", "y") as sp:
            assert sp.rename("z") is None

    def test_phase_with_no_session_feeds_buckets_only(self):
        before = devtime.snapshot()
        with devtime.phase("scan_feed", "scan", "pad", prof="build") as ph:
            ph.nbytes = 7
        d = devtime.delta(before)
        assert d["scan_feed"] > 0
        assert d["device"] == 0 and d["codec"] == 0


class TestUnionBuckets:
    @pytest.mark.parametrize("union", devtime.UNIONS)
    def test_overlapping_threads_count_wall_once(self, union):
        """Two threads inside one phase at once add the union of their
        intervals, not the sum of their durations."""
        spans = []
        second = threading.Event()

        def worker(lead):
            if not lead:
                second.wait()
            with devtime.phase(union, "scan", "overlap"):
                t0 = time.perf_counter()
                if lead:
                    time.sleep(0.05)
                    second.set()
                time.sleep(0.15)
                spans.append((t0, time.perf_counter()))

        before = devtime.snapshot()
        threads = [threading.Thread(target=worker, args=(lead,))
                   for lead in (True, False)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        got = devtime.delta(before)[union]
        union_s = devtime.union_seconds(spans)
        total = sum(b - a for a, b in spans)
        assert total - union_s > 0.04  # the intervals did overlap
        assert union_s <= got < union_s + 0.02, (got, union_s, total)

    def test_many_threads_leave_no_interval_open(self):
        """More threads than cores entering and leaving the phases with a
        tiny switch interval: every count returns to zero, no interval is
        left open, and each union stays within the elapsed wall."""
        import sys

        def worker():
            for i in range(300):
                with devtime.phase(devtime.UNIONS[i % 3], "scan", "stress",
                                   track="codec" if i % 2 else None):
                    pass

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            before = devtime.snapshot()
            t0 = time.perf_counter()
            threads = [threading.Thread(target=worker)
                       for _ in range(4 * (os.cpu_count() or 1))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            wall = time.perf_counter() - t0
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert devtime._inside == dict.fromkeys(devtime.UNIONS, 0)
        assert devtime._since == dict.fromkeys(devtime.UNIONS)
        assert not any(k[1] == "codec" for k in devtime._active)
        d = devtime.delta(before)
        for key in devtime.UNIONS:
            assert d[key] <= wall, (key, d[key], wall)

    def test_open_interval_is_read_mid_phase(self):
        before = devtime.snapshot()
        with devtime.phase("scan_wait", "device", "wait"):
            time.sleep(0.02)
            mid = devtime.delta(before)["scan_wait"]
        assert mid >= 0.02


def _reader(name):
    path = os.path.join(ROOT, "perfbench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class _Job(object):
    def __init__(self, wall, devtime_stats):
        self.wall = wall
        self.stats = {"devtime": devtime_stats}


class _Run(object):
    def __init__(self, jobs):
        self.jobs = jobs


class TestMetricReaders:
    @pytest.mark.parametrize("bucket", ["feed", "wait", "drain"])
    def test_share_of_summed_wall(self, bucket):
        key = "scan_" + bucket
        read = _reader("scan_{}_share".format(bucket)).read
        run = _Run([_Job(2.0, {key: 0.5, "codec_wait": 0.1}),
                    _Job(3.0, {key: 1.0, "codec_wait": 0.2})])
        assert read(run) == pytest.approx(30.0)

    @pytest.mark.parametrize("bucket", ["feed", "wait", "drain"])
    def test_none_without_the_bucket(self, bucket):
        read = _reader("scan_{}_share".format(bucket)).read
        assert read(_Run([])) is None
        assert read(_Run([_Job(2.0, {"codec_wait": 0.1})])) is None
