"""Per-operator profiler (dampr_tpu.obs.profile): disabled-path pin
(no thread, no profile section, inert module surface), per-op
attribution on batched-UDF chains and scanner stages, fusion provenance,
device sub-phase decomposition, and coverage on the fused headline
stage."""

import operator
import os
import threading

import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.obs import profile


@pytest.fixture
def profiled(tmp_path):
    """Profiler + tracing on for one test, artifacts and scratch under
    tmp_path (scratch isolation keeps the history corpus per-test)."""
    old = (settings.trace, settings.trace_dir, settings.profile,
           settings.scratch_root)
    settings.trace = True
    settings.trace_dir = str(tmp_path / "traces")
    settings.profile = True
    settings.scratch_root = str(tmp_path / "scratch")
    yield tmp_path
    (settings.trace, settings.trace_dir, settings.profile,
     settings.scratch_root) = old


def _corpus(tmp_path, lines=6000):
    path = tmp_path / "corpus.txt"
    words = ["alpha", "beta", "gamma", "delta", "tok7", "zz", "mu", "xi"]
    with open(path, "w") as f:
        for i in range(lines):
            f.write(" ".join(words[(i + j) % len(words)]
                             for j in range(9)) + "\n")
    return str(path)


class TestDisabledPath:
    @pytest.mark.skipif(settings.profile,
                        reason="DAMPR_TPU_PROFILE=1 forced (the CI "
                               "profile-on leg): the off-path pin only "
                               "applies at defaults")
    def test_off_by_default_no_thread_no_section(self):
        """The default-off pin (same discipline as test_metrics): module
        surface is inert, no profiler instance, no new threads, and the
        run summary carries no profile section."""
        assert settings.profile is False
        assert profile.active() is None
        assert not profile.enabled()
        # inert module-level calls (would raise if they touched state)
        profile.device_add("build", 0.1, 123)
        before = {t.name for t in threading.enumerate()}
        em = Dampr.memory(list(range(3000))).map(lambda x: (x, 1)).run()
        assert "profile" not in em.stats()
        assert {t.name for t in threading.enumerate()} <= before
        em.delete()

    def test_off_path_no_alloc_in_hot_sites(self):
        """The hot-site contract: with no active profiler the module
        global is None and the (hoisted) site check is one load — pinned
        by asserting active() returns the same object (None) with no
        per-call allocation of noop wrappers (unlike span(), there is no
        wrapper object at all)."""
        assert profile.active() is None
        assert profile.active() is None  # stable, allocation-free


class TestAttribution:
    def test_batch_chain_per_op_and_provenance(self, profiled, tmp_path):
        """A fused map chain attributes per-op seconds/records under
        index-prefixed labels, carries fusion provenance, and covers the
        bulk of the stage's job time."""
        em = (Dampr.memory(list(range(20000)))
              .map(lambda x: (x % 64, x))
              .filter(lambda kv: kv[1] % 2 == 0)
              .fold_by(lambda kv: kv[0], binop=operator.add,
                       value=lambda kv: kv[1])
              .run("prof-chain"))
        prof = em.stats()["profile"]
        assert prof["enabled"] is True
        fused = [s for s in prof["stages"]
                 if any(o["op"].startswith("0:") for o in s["ops"])]
        assert fused, prof["stages"]
        st = fused[0]
        labels = [o["op"] for o in st["ops"]]
        # the chain's ops appear individually, plus the hoisted combiner
        assert any("Filter" in l for l in labels), labels
        assert "combine" in labels, labels
        # records flow through the ops (filter halves them)
        by = {o["op"]: o for o in st["ops"]}
        filt = next(v for k, v in by.items() if "Filter" in k)
        assert filt["records"] > 0
        assert st["provenance"], st
        assert any("Filter" in p for p in st["provenance"])
        assert st["jobs"] >= 1 and st["job_seconds"] > 0
        em.delete()

    def test_scanner_stage_covers_job_time(self, profiled, tmp_path):
        """The fused scanner (map_blocks) stage — the TF-IDF headline
        shape — attributes its codec windows to the scanner op, and on
        a corpus big enough for the codec to dominate per-job fixed
        costs the coverage clears a conservative floor (the acceptance
        bar is 0.9 on the real bench, measured at full size; tiny CI
        corpora leave more registration/clone overhead per second).

        Coverage is attributed-op thread-seconds over job thread-
        seconds: on a loaded 2-core box the scheduler can preempt a job
        thread BETWEEN ops (the full-suite run shares the machine), so
        the denominator inflates with stolen wall the profiled ops
        never saw and a single sample can land under the floor.  The
        assertion is best-of-three: genuinely broken attribution fails
        every attempt, scheduler noise does not repeat three times."""
        from dampr_tpu.ops.text import DocFreq

        corpus = _corpus(tmp_path, lines=40000)
        best = None
        for attempt in range(3):
            docs = Dampr.text(corpus, 1 << 19)
            em = (docs.custom_mapper(DocFreq(mode="word", lower=True))
                  .fold_by(lambda kv: kv[0], operator.add,
                           lambda kv: kv[1])
                  .run("prof-scan-{}".format(attempt)))
            prof = em.stats()["profile"]
            scan = [s for s in prof["stages"]
                    if any("DocFreq" in o["op"]
                           or o["op"].startswith("scan:")
                           for o in s["ops"])]
            assert scan, prof["stages"]
            st = max(scan, key=lambda s: s["job_seconds"])
            em.delete()
            assert st["coverage"] is not None, st
            if best is None or st["coverage"] > best["coverage"]:
                best = st
            if best["coverage"] >= 0.7:
                break
        assert best["coverage"] >= 0.7, best

    def test_stats_profile_reaches_persisted_summary(self, profiled,
                                                     tmp_path):
        """The profile section lands in the persisted stats.json too."""
        import json

        em = (Dampr.memory(list(range(4096)))
              .map(lambda x: (x % 7, 1))
              .fold_by(lambda kv: kv[0], binop=operator.add,
                       value=lambda kv: kv[1])
              .run("prof-persist"))
        path = em.stats()["stats_file"]
        assert path and os.path.isfile(path)
        with open(path) as f:
            on_disk = json.load(f)
        assert on_disk.get("profile", {}).get("enabled") is True
        em.delete()


class TestDeviceSubPhases:
    @pytest.mark.parametrize("handoff", ["off", "on"])
    def test_lowered_stage_decomposes(self, profiled, tmp_path, handoff):
        """A device-lowered scanner stage records build/h2d/compute/d2h
        sub-phases with byte counts (the double-buffered dispatch loop's
        brackets), on the classic program and on the handoff tier's
        table probe alike."""
        from dampr_tpu.ops.text import TokenCounts

        old = (settings.lower, settings.handoff, settings.scan_window_bytes)
        settings.lower = "1"
        settings.handoff = handoff
        # Several windows per job: on this backend the handoff leg seeds
        # its vocabulary from the first window on the host, and the later
        # windows run the table probe.
        settings.scan_window_bytes = 16 * 1024
        try:
            # pair_values=False + fold_values is the device-eligible
            # map->fold shape (the bench's): no Rekey between scanner
            # and fold, so the lowering pass claims the map stage.
            em = (Dampr.text(_corpus(tmp_path), 1 << 17)
                  .custom_mapper(TokenCounts(mode="word", lower=True,
                                             pair_values=False))
                  .fold_values(operator.add)
                  .run("prof-device-" + handoff))
            prof = em.stats()["profile"]
            dev = [s for s in prof["stages"] if s["device"]]
            assert dev, prof["stages"]
            phases = dev[0]["device"]
            for phase in ("build", "h2d", "compute", "d2h"):
                assert phase in phases, phases
                assert phases[phase]["seconds"] >= 0
                assert phases[phase]["calls"] >= 1
            assert phases["h2d"]["bytes"] > 0
            assert phases["d2h"]["bytes"] > 0
            # results are unperturbed by profiling (byte-identity is the
            # lowering contract)
            counts = dict(em.read())
            assert counts and all(v > 0 for v in counts.values())
            assert counts["alpha"] > 1000
            em.delete()
        finally:
            (settings.lower, settings.handoff,
             settings.scan_window_bytes) = old


class TestProfilerUnit:
    def test_op_labels_and_accumulate(self):
        p = profile.Profiler("t")
        p.begin_stage(3, "map", provenance=["map[A]", "map[B]"])
        p.op_add("0:A", 0.5, records=10)
        p.op_add("0:A", 0.25, records=5)
        p.op_add("1:B", 0.1, records=15)
        p.device_add("h2d", 0.05, 1024, sid=3)
        p.job_add(1.0)
        s = p.summary({3: 2.0})
        st = s["stages"][0]
        assert st["stage"] == 3
        assert st["ops"][0] == {"op": "0:A", "seconds": 0.75,
                                "records": 15, "calls": 2}
        assert st["device"]["h2d"]["bytes"] == 1024
        assert st["jobs"] == 1
        assert abs(st["attributed_seconds"] - 0.9) < 1e-9
        assert st["coverage"] == round(min(1.0, 0.9 / 1.0), 4)
        assert st["seconds"] == 2.0
        assert st["provenance"] == ["map[A]", "map[B]"]

    def test_coverage_caps_at_one(self):
        p = profile.Profiler("t")
        p.begin_stage(0, "map")
        p.op_add("x", 5.0)
        p.job_add(1.0)
        assert p.summary()["stages"][0]["coverage"] == 1.0

    def test_timed_iter_attributes_each_next(self):
        p = profile.Profiler("t")
        p.begin_stage(1, "map")
        out = list(p.timed_iter(iter([[1, 2], [3]]), "scan"))
        assert out == [[1, 2], [3]]
        ops = p.summary()["stages"][0]["ops"]
        assert ops[0]["op"] == "scan"
        assert ops[0]["calls"] == 2
        assert ops[0]["records"] == 3

    def test_start_stop_nesting(self):
        a, b = profile.Profiler("a"), profile.Profiler("b")
        profile.start(a)
        profile.start(b)
        assert profile.active() is b
        profile.stop(b)
        assert profile.active() is a
        profile.stop(a)
        assert profile.active() is None
