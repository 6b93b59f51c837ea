"""Learned per-operator cost model (dampr_tpu.plan.model) + the closed
tuning loop (dampr_tpu.obs.autotune): feature extraction over clean /
legacy / corrupt / rank-tagged corpus lines, per-class fit recovery,
knob-search bounds properties, the DAMPR_TPU_COST_MODEL=0 kill-switch
equivalence pin, thin-corpus degradation reasons, the in-process
autotune session (winner selection, byte-exactness disqualification,
settings restore, tuned.json write-back), and the check_bench autotune
baseline / model-residual satellites."""

import importlib.util
import json
import operator
import os
import random
import types

import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.obs import autotune, history
from dampr_tpu.plan import cost, ir, model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


validate_doctor = _load_tool("validate_doctor")
check_bench = _load_tool("check_bench")

with open(os.path.join(ROOT, "docs", "doctor_schema.json")) as _f:
    DOCTOR_SCHEMA = json.load(_f)


@pytest.fixture
def isolated(tmp_path):
    """Per-test scratch root (the corpus lives under it) + model knobs
    restored.  mesh_exchange pinned off: the 8-device test rig would
    otherwise flip the tiny reduce's shuffle routing between runs
    (mesh on the history-less first run, host once recorded bytes land
    under exchange_min_bytes), splitting its measurements across the
    exchange/fold classes and thinning both fits — these tests pin the
    deterministic host-path behavior."""
    old = (settings.scratch_root, settings.cost_model,
           settings.autotune, settings.autotune_trials,
           settings.mesh_exchange, settings.optimize)
    settings.scratch_root = str(tmp_path / "scratch")
    settings.mesh_exchange = "off"
    # These tests pin model-layer behavior on EVERY CI leg: force the
    # model (and the optimizer the cost layer rides) on here; the
    # kill-switch tests set cost_model="0" themselves.
    settings.cost_model = "auto"
    settings.optimize = True
    yield tmp_path
    (settings.scratch_root, settings.cost_model,
     settings.autotune, settings.autotune_trials,
     settings.mesh_exchange, settings.optimize) = old


def _record(run="r", stages=None, mbps=10.0, knobs=None, rank=None,
            wall=1.0, schema=history.SCHEMA, fingerprint="fp0",
            shapes=None):
    rec = {
        "schema": schema,
        "run": run,
        "wall_seconds": wall,
        "n_partitions": 64,
        "stage_shapes": shapes if shapes is not None else [
            {"sid": 1, "shape": "map:DocFreq+c"},
            {"sid": 2, "shape": "reduce:AssocFoldReducer"},
        ],
        "stages": stages if stages is not None else [
            {"stage": 1, "kind": "map", "target": "host", "jobs": 4,
             "records_in": 1000, "records_out": 900,
             "bytes_in": 8_000_000, "bytes_out": 6_000_000,
             "spill_bytes": 0, "seconds": 0.8},
            {"stage": 2, "kind": "reduce", "target": "host",
             "shuffle_target": "host", "jobs": 64, "records_in": 900,
             "records_out": 50, "bytes_in": 6_000_000,
             "bytes_out": 4_000, "spill_bytes": 0, "seconds": 0.2},
        ],
        "throughput": {"records_out": 50, "bytes_out": 4_000,
                       "mbps": mbps},
        "settings": dict({"overlap_windows": 2, "spill_write_threads": 2,
                          "spill_read_prefetch": 2, "merge_fanin": 512,
                          "spill_codec": "auto",
                          "exchange_hbm_budget": 64 * 1024 ** 2},
                         **(knobs or {})),
        "fingerprint": fingerprint,
    }
    if rank is not None:
        rec["rank"] = rank
    return rec


class TestFeatureExtraction:
    def test_clean_record_rows(self):
        rows = model.stage_features(_record())
        assert len(rows) == 2
        scan, fold = rows
        assert scan["op_class"] == "scanner"  # DocFreq provenance
        assert fold["op_class"] == "fold"
        assert scan["mb"] == pytest.approx(8.0)
        assert fold["jobs"] == 64
        assert scan["record_bytes"] == pytest.approx(6_000_000 / 900)

    def test_op_class_matrix(self):
        assert model.op_class({"kind": "map"}, "map:DocFreq+c") \
            == "scanner"
        assert model.op_class({"kind": "map"}, "map:Rekey") == "merge"
        # A combinered re-key chain is fold_by's keyed map, not a sort.
        assert model.op_class({"kind": "map"}, "map:GMap.Rekey+c") \
            == "map"
        assert model.op_class({"kind": "reduce",
                               "shuffle_target": "mesh"}, "reduce:X") \
            == "exchange"
        assert model.op_class({"kind": "reduce"}, "reduce:X") == "fold"
        assert model.op_class({"kind": "sink"}, "sink:TSV") == "sink"
        assert model.op_class({"kind": "map", "target": "device"},
                              "map:DocFreq+c") == "device"

    def test_rank_tagged_records_excluded(self):
        recs = [_record(), _record(rank=1), _record(rank=2)]
        rows = model.features(recs)
        assert len(rows) == 2  # only the run-level record's stages

    def test_corrupt_and_partial_records_degrade(self):
        # Feature extraction over garbage must yield rows for what is
        # readable and never raise.
        assert model.stage_features(None) == []
        assert model.stage_features({"stages": "not-a-list"}) == []
        rows = model.stage_features({
            "stages": [
                {"stage": 0, "kind": "map", "seconds": "NaN-ish"},
                {"stage": 1, "kind": "map", "seconds": 0.5},
                "garbage",
            ]})
        assert len(rows) == 1 and rows[0]["seconds"] == 0.5

    def test_legacy_v1_lines_upgrade_on_load(self, isolated):
        """A v1 corpus (pre-PR-12: no shuffle_target, no v field) loads,
        upgrades in memory, and feeds the model — the tolerant upgrade
        path that lets feature extraction evolve."""
        path = history.corpus_path("legacy")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        v1 = _record(schema="dampr-tpu-history/1")
        for st in v1["stages"]:
            st.pop("shuffle_target", None)
        with open(path, "w") as f:
            f.write(json.dumps(v1) + "\n")
            f.write("not json at all\n")
            f.write(json.dumps({"schema": "other/9", "stages": []}) + "\n")
            f.write(json.dumps({"schema": "dampr-tpu-history/99",
                                "stages": []}) + "\n")
            f.write(json.dumps(_record(rank=1)) + "\n")
        recs = history.load("legacy")
        # v1 record + rank-tagged v2 record survive; corrupt/foreign/
        # future-versioned lines are skipped.
        assert len(recs) == 2
        up = recs[0]
        assert up["v"] == 1
        assert all(st.get("shuffle_target") is None
                   for st in up["stages"])
        rows = model.features(recs)
        assert len(rows) == 2  # the rank-tagged record is excluded
        assert {r["op_class"] for r in rows} == {"scanner", "fold"}

    def test_schema_version(self):
        assert history.schema_version({"schema": history.SCHEMA}) \
            == history.SCHEMA_VERSION
        assert history.schema_version(
            {"schema": "dampr-tpu-history/1"}) == 1
        assert history.schema_version({"schema": "dampr-tpu-history/99"}) \
            is None
        assert history.schema_version({"schema": "bogus"}) is None
        assert history.schema_version({}) is None


class TestFit:
    def test_recovers_slope_and_job_cost(self):
        recs = []
        rng = random.Random(7)
        for i in range(8):
            mb = 2.0 + 4.0 * rng.random()
            jobs = rng.choice([4, 16, 64])
            secs = 0.1 * mb + 0.002 * jobs
            recs.append(_record(stages=[
                {"stage": 2, "kind": "reduce", "target": "host",
                 "jobs": jobs, "bytes_in": int(mb * 1e6), "bytes_out": 10,
                 "records_in": 100, "records_out": 10,
                 "spill_bytes": 0, "seconds": secs}]))
        m = model.build(recs)
        f = m.fit_for("fold")
        assert f is not None
        assert f.secs_per_mb == pytest.approx(0.1, rel=0.05)
        assert f.secs_per_job == pytest.approx(0.002, rel=0.05)
        assert f.r2 > 0.95
        assert f.predict(10, 64) == pytest.approx(1.0 + 0.128, rel=0.1)

    def test_outlier_robustness(self):
        pts = [(mb, 1, 0.5 * mb) for mb in (1, 2, 3, 4, 5)]
        pts.append((3.0, 1, 50.0))  # cold-run spike
        recs = [_record(stages=[
            {"stage": 2, "kind": "reduce", "jobs": j,
             "bytes_in": int(mb * 1e6), "bytes_out": 1, "records_in": 1,
             "records_out": 1, "spill_bytes": 0, "seconds": s}])
            for mb, j, s in pts]
        m = model.build(recs)
        f = m.fit_for("fold")
        assert f.secs_per_mb == pytest.approx(0.5, rel=0.1)

    def test_below_min_points_no_fit(self):
        recs = [_record() for _ in range(2)]
        m = model.build(recs)
        assert m.fit_for("scanner") is None
        ok, why = m.confident_for(["scanner"])
        assert not ok and "scanner" in why or "thin-corpus" in why

    def test_confident_reports_missing_classes(self):
        recs = [_record() for _ in range(4)]
        m = model.build(recs)
        ok, why = m.confident_for(["scanner", "fold", "exchange"])
        assert not ok and "exchange" in why
        ok, why = m.confident_for(["scanner", "fold"])
        assert ok and why is None


class TestSearchBounds:
    """Property pins: no search path ever proposes a value outside the
    documented KNOB_BOUNDS, whatever the corpus says."""

    def _random_records(self, rng, n):
        recs = []
        for i in range(n):
            stages = []
            for sid, kind in ((1, "map"), (2, "reduce")):
                stages.append({
                    "stage": sid, "kind": kind,
                    "target": rng.choice(["host", "host", "device"]),
                    "shuffle_target": rng.choice([None, "host", "mesh"]),
                    "jobs": rng.choice([1, 4, 64, 256]),
                    "bytes_in": rng.randrange(0, 1 << 31),
                    "bytes_out": rng.randrange(0, 1 << 31),
                    "records_in": rng.randrange(0, 1 << 20),
                    "records_out": rng.randrange(0, 1 << 20),
                    "spill_bytes": 0,
                    "seconds": rng.random() * 100,
                })
            recs.append(_record(
                stages=stages, mbps=rng.random() * 500,
                knobs={
                    "overlap_windows": rng.choice([0, 2, 4, 8]),
                    "spill_write_threads": rng.choice([0, 2, 8]),
                    "merge_fanin": rng.choice([4, 64, 512, 4096]),
                    "spill_codec": rng.choice(["auto", "zstd", "zlib"]),
                    "exchange_hbm_budget": rng.choice(
                        [1 << 20, 1 << 26, 1 << 30]),
                }))
        return recs

    def test_partition_search_stays_in_bounds(self):
        rng = random.Random(1234)
        for trial in range(40):
            recs = self._random_records(rng, rng.randrange(3, 9))
            m = model.build(recs)
            rows = cost._hist_stage_rows(
                {"stages": recs[-1]["stages"]},
                types.SimpleNamespace(stages=[]))
            # op_class comes from the record fields when shapes are
            # unavailable (the graph is empty here).
            for r in rows:
                r["op_class"] = model.op_class(r, None)
            ch = model.search_partitions(m, rows,
                                         rng.choice([4, 64, 256]))
            if ch is not None:
                lo, hi = model.KNOB_BOUNDS["n_partitions"]
                assert lo <= ch["chosen"] <= hi, ch
                assert ch["chosen"] != ch["static"]

    def test_variance_search_stays_in_bounds(self):
        rng = random.Random(99)
        for trial in range(40):
            recs = self._random_records(rng, rng.randrange(2, 10))
            m = model.build(recs, fingerprint="fp0")
            current = {k: getattr(settings, k, None)
                       for k in model.VARIANCE_KNOBS}
            for ch in model.search_variance_knobs(m, current):
                if ch["chosen"] == ch["static"]:
                    continue
                assert model.in_bounds(ch["knob"], ch["chosen"]), ch

    def test_candidate_vectors_stay_in_bounds(self, isolated):
        rng = random.Random(5)
        path = history.corpus_path("bounds-run")
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            for rec in self._random_records(rng, 6):
                rec["critpath"] = {"run": rng.choice(
                    ["codec", "merge", "spill-queue", "io-read"])}
                f.write(json.dumps(rec, default=str) + "\n")
        for cand in autotune.candidate_vectors("bounds-run", 8):
            for knob, val in cand["knobs"].items():
                assert model.in_bounds(knob, val), (knob, val)

    def test_clamp_and_in_bounds(self):
        assert model.clamp("merge_fanin", 1 << 30) == 4096
        assert model.clamp("overlap_windows", -3) == 0
        assert model.in_bounds("spill_codec", "zstd")
        assert not model.in_bounds("spill_codec", "brotli")
        assert not model.in_bounds("n_partitions", True)
        assert not model.in_bounds("nonexistent_knob", 1)


def _fold_pipeline():
    return (Dampr.memory([(i % 50, 1) for i in range(30000)])
            .fold_by(lambda kv: kv[0], operator.add, lambda kv: kv[1]))


def _run(name):
    em = _fold_pipeline().run(name)
    s = em.stats()
    em.delete()
    return s


def _unpicked_partitions(name):
    """A partition count the next run of ``name`` picks only from a
    tuned.json: neither its static count (which follows the host's core
    count) nor the cost model's argmin over measured timings.  A probe
    run shows both; its history record is then taken back, so the next
    run plans from the same history and picks the same."""
    corpus = history.corpus_path(name)
    with open(corpus, "rb") as f:
        before = f.read()
    probe = _run(name)
    with open(corpus, "wb") as f:
        f.write(before)
    taken = {probe["n_partitions"]}
    taken.update(ch["static"] for ch in probe["plan"]["cost"]["choices"]
                 if ch["knob"] == "n_partitions")
    return next(p for p in (8, 16, 32, 12) if p not in taken)


class TestKillSwitchAndDegradation:
    def test_kill_switch_reproduces_median_path(self, isolated):
        """DAMPR_TPU_COST_MODEL=0: the adaptive decisions must be
        exactly the median path's — n_partitions from
        _clamped_partitions over the synthesized history, tiny-reduce
        collapse — with the kill switch recorded in the cost section
        and NOTHING model-applied."""
        settings.cost_model = "0"
        for i in range(4):
            s = _run("kill-switch")
        plan = s["plan"]
        assert plan["cost"]["enabled"] is False
        assert "disabled" in plan["cost"]["reason"]
        assert plan["cost"]["choices"] == []
        # The median path's exact decisions, recomputed from the corpus
        # the run accumulated (the pre-model behavior pin).
        recs = history.load("kill-switch")
        matched = history.matching(
            recs, recs[-1]["stage_shapes"])
        hist = history.synthesize(
            matched[-max(1, settings.history_window):])
        reduce_bytes = max(st.get("bytes_in") or 0
                           for st in hist["stages"]
                           if st["kind"] == "reduce")
        want = cost._clamped_partitions(reduce_bytes)
        ad = plan["adaptive"]
        changes = {c["what"]: c for c in ad["changes"]}
        assert changes["n_partitions"]["to"] == want
        assert s["n_partitions"] == want

    def test_kill_switch_apply_model_touches_nothing(self, isolated):
        settings.cost_model = "off"
        runner = types.SimpleNamespace(
            graph=None, name="whatever", n_partitions=64,
            _explicit_partitions=False, resume=False)
        report = {}
        cost.apply_model(runner, types.SimpleNamespace(stages=[]),
                         report)
        assert report["cost"]["enabled"] is False
        assert runner.n_partitions == 64

    def test_empty_corpus_degrades_to_static_with_reason(self, isolated):
        s = _run("cold-start")
        c = s["plan"]["cost"]
        assert c["enabled"] is False
        assert c["source"] == "static"
        assert "no-history" in c["reason"]

    def test_thin_corpus_degrades_to_median_with_reason(self, isolated):
        _run("thin")
        s = _run("thin")  # corpus holds 1 record at adapt time
        c = s["plan"]["cost"]
        assert c["enabled"] is False
        assert c["source"] == "median-fallback"
        assert "thin-corpus" in c["reason"] or "unfit" in c["reason"]
        # The median path still adapted (the pre-model behavior).
        assert s["plan"]["adaptive"]["applied"] is True

    def test_confident_corpus_engages_model(self, isolated):
        for i in range(4):
            s = _run("warm")
        c = s["plan"]["cost"]
        assert c["enabled"] is True
        assert c["source"] == "model"
        assert c["model"]["classes"]
        assert isinstance(c["choices"], list)
        # Every no-variance knob records the honest measure-me reason.
        untouched = [ch for ch in c["choices"]
                     if ch["chosen"] == ch["static"]]
        assert any("no-variance" in (ch.get("reason") or "")
                   for ch in untouched)


class TestAutotuneSession:
    def _measure_factory(self, walls_by_overlap):
        calls = []

        def measure():
            w = walls_by_overlap.get(settings.overlap_windows, 1.0)
            calls.append(settings.overlap_windows)
            return w, "result-token"

        return measure, calls

    def test_winner_and_restore(self, isolated):
        settings.autotune_trials = 3
        old_overlap = settings.overlap_windows
        # The exploration schedule tries the opposite regime first
        # (overlap 0 from the default 2): make that the fast config so
        # a non-baseline trial wins.
        measure, calls = self._measure_factory({old_overlap: 1.0,
                                                0: 0.4, 4: 0.4, 8: 0.4})
        best, report = autotune.tune_settings_session(
            measure, "tune-unit", digest_of=lambda r: "d0",
            out=lambda m: None)
        a = report["autotune"]
        assert settings.overlap_windows == old_overlap  # restored
        assert a["byte_identical"] is True
        assert a["winner"]["trial"] != 0
        assert a["improvement"] >= 2.0
        assert best == "result-token"
        # Winner persisted for the next fit.
        tuned = cost.load_tuned("tune-unit")
        assert tuned and tuned["knobs"]
        errors = validate_doctor.validate(report, DOCTOR_SCHEMA,
                                          check_settings=False)
        assert errors == [], errors

    def test_divergent_output_disqualifies(self, isolated):
        settings.autotune_trials = 3
        digests = iter(["base", "DIFFERENT", "base2"])

        def measure():
            return 0.1 if settings.overlap_windows != 2 else 1.0, None

        _best, report = autotune.tune_settings_session(
            measure, "tune-div", digest_of=lambda r: next(digests),
            out=lambda m: None)
        a = report["autotune"]
        assert a["byte_identical"] is False
        disq = [t for t in a["trials"]
                if t.get("byte_identical") is False]
        assert disq
        assert all(a["winner"]["trial"] != t["trial"] for t in disq)
        assert cost.load_tuned("tune-div") is None or \
            a["winner"]["trial"] != 0  # never persisted FROM a disq trial

    def test_tuned_winner_applies_next_run(self, isolated):
        """The closed loop: a tuned.json winner's n_partitions is
        applied by the next run's cost layer with the autotune
        provenance in the decision trace."""
        for i in range(4):
            _run("loop")
        want = _unpicked_partitions("loop")
        os.makedirs(os.path.join(settings.scratch_root, "loop"),
                    exist_ok=True)
        with open(os.path.join(settings.scratch_root, "loop",
                               "tuned.json"), "w") as f:
            json.dump({"schema": "dampr-tpu-tuned/1",
                       "session": "s1", "run": "loop",
                       "knobs": {"n_partitions": want},
                       "wall_seconds": 0.01}, f)
        s = _run("loop")
        c = s["plan"]["cost"]
        applied = {ch["knob"]: ch for ch in c["choices"]
                   if ch.get("applied")}
        assert "n_partitions" in applied, c["choices"]
        assert applied["n_partitions"]["chosen"] == want
        assert "autotuned winner" in applied["n_partitions"]["reason"]
        assert s["n_partitions"] == want

    def test_stale_fingerprint_tuned_never_applies(self, isolated):
        """A tuned.json winner measured on a DIFFERENT plan shape under
        the same run name is ignored (recorded as tuned_stale), never
        force-applied."""
        for i in range(4):
            _run("stale")
        want = _unpicked_partitions("stale")
        os.makedirs(os.path.join(settings.scratch_root, "stale"),
                    exist_ok=True)
        with open(os.path.join(settings.scratch_root, "stale",
                               "tuned.json"), "w") as f:
            json.dump({"schema": "dampr-tpu-tuned/1", "session": "sX",
                       "run": "stale", "fingerprint": "deadbeef" * 2,
                       "knobs": {"n_partitions": want}}, f)
        s = _run("stale")
        c = s["plan"]["cost"]
        assert c.get("tuned_stale", {}).get("session") == "sX", c
        for ch in c["choices"]:
            assert not (ch["knob"] == "n_partitions"
                        and ch.get("chosen") == want
                        and "autotuned" in (ch.get("reason") or "")), ch
        assert s["n_partitions"] != want

    def test_as_env_maps_only_env_knobs(self):
        env = autotune.as_env({"overlap_windows": 4, "n_partitions": 8,
                               "spill_codec": "zstd"})
        assert env == {"DAMPR_TPU_OVERLAP_WINDOWS": "4",
                       "DAMPR_TPU_SPILL_CODEC": "zstd"}

    def test_dir_digest_orders_and_content(self, tmp_path):
        d = tmp_path / "out"
        d.mkdir()
        (d / "a.txt").write_text("alpha\nbeta\n")
        one = autotune.dir_digest(str(d))
        tree_one = autotune.dir_digest(str(d), mode="tree")
        (d / "a.txt").write_text("alpha\nbeta!\n")
        assert autotune.dir_digest(str(d)) != one
        assert autotune.dir_digest(str(tmp_path / "missing")) is None
        # Layout invariance (default mode): the same line multiset split
        # across a different number of part files — a partition-count
        # choice — digests identically; tree mode distinguishes it.
        (d / "a.txt").write_text("beta\n")
        (d / "b.txt").write_text("alpha\n")
        assert autotune.dir_digest(str(d)) == one
        assert autotune.dir_digest(str(d), mode="tree") != tree_one


class TestCheckBenchSatellites:
    def _tune_report(self, tmp_path, mbps=120.0):
        report = {
            "schema": "dampr-tpu-doctor/1", "run": "bench-tfidf",
            "wall_seconds": 1.0, "stages": [], "findings": [],
            "metric": "tfidf_docfreq_throughput",
            "autotune": {
                "session": "s", "trials": [
                    {"trial": 0, "knobs": {}, "wall_seconds": 1.4},
                    {"trial": 1, "knobs": {"overlap_windows": 4},
                     "wall_seconds": 1.0, "mbps": mbps,
                     "byte_identical": True},
                ],
                "winner": {"trial": 1,
                           "knobs": {"overlap_windows": 4},
                           "wall_seconds": 1.0, "mbps": mbps},
                "baseline_wall_seconds": 1.4, "improvement": 1.4,
                "byte_identical": True,
            },
        }
        path = tmp_path / "TUNE_test.json"
        path.write_text(json.dumps(report))
        return str(path)

    def test_autotune_report_as_baseline(self, tmp_path, capsys):
        tune = self._tune_report(tmp_path, mbps=120.0)
        rec = check_bench.load_record(tune)
        assert rec["value"] == 120.0
        assert rec["metric"] == "tfidf_docfreq_throughput"
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({
            "metric": "tfidf_docfreq_throughput", "value": 60.0}))
        rc = check_bench.main([str(fresh), "--baseline", tune,
                               "--tolerance", "0.25"])
        out = capsys.readouterr().out
        assert rc == 0  # warn-only default
        assert "WARN" in out and "120" in out

    def test_autotune_report_without_toplevel_value(self, tmp_path):
        tune = self._tune_report(tmp_path, mbps=80.0)
        doc = json.loads(open(tune).read())
        doc.pop("metric", None)
        with open(tune, "w") as f:
            json.dump(doc, f)
        rec = check_bench.load_record(tune)
        assert rec["value"] == 80.0

    def test_model_residual_warns_under_trend(self, tmp_path, capsys):
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({
            "metric": "m", "value": 50.0,
            "model_predicted_value": 100.0}))
        rc = check_bench.main([str(fresh), "--trend",
                               "--tolerance", "0.25"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "MODEL WARN" in out

    def test_model_residual_quiet_within_tolerance(self, tmp_path,
                                                   capsys):
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({
            "metric": "m", "value": 95.0,
            "model_predicted_value": 100.0}))
        rc = check_bench.main([str(fresh), "--trend",
                               "--tolerance", "0.25"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "MODEL WARN" not in out
        assert "model residual" in out

    def test_no_prediction_no_model_line(self, tmp_path, capsys):
        fresh = tmp_path / "fresh.json"
        fresh.write_text(json.dumps({"metric": "m", "value": 95.0}))
        rc = check_bench.main([str(fresh), "--trend"])
        out = capsys.readouterr().out
        assert rc == 0 and "MODEL" not in out


class TestTrajectoryFeedstock:
    def test_load_trajectory_mixed(self, tmp_path):
        (tmp_path / "BENCH_r01.json").write_text(json.dumps({
            "parsed": {"metric": "tfidf", "value": 100.0,
                       "overlap_windows": 2}}))
        (tmp_path / "TUNE_r01.json").write_text(json.dumps({
            "metric": "tfidf",
            "autotune": {"winner": {"mbps": 140.0,
                                    "knobs": {"overlap_windows": 4}}}}))
        (tmp_path / "broken.json").write_text("{nope")
        recs = model.load_trajectory([
            str(tmp_path / "BENCH_r01.json"),
            str(tmp_path / "TUNE_r01.json"),
            str(tmp_path / "broken.json"),
            str(tmp_path / "missing.json")])
        assert len(recs) == 2
        assert recs[0]["mbps"] == 100.0
        assert recs[0]["knobs"] == {"overlap_windows": 2}
        assert recs[1]["mbps"] == 140.0
        assert recs[1]["knobs"] == {"overlap_windows": 4}
