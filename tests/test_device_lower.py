"""Device-lowering equivalence suite (ISSUE 6 tentpole + satellites).

Pins the lowering contract at three levels:

- **program parity** (property-style): the jitted tokenize+hash+fold
  programs produce key/count/hash-lane output identical to the host
  scanners on randomized corpora, across batch cuts, long tokens,
  multibyte UTF-8, and the explicit fallback edges (invalid UTF-8
  windows, lines wider than a batch, forced hash collisions);
- **pipeline byte-identity**: TF-IDF-shaped pipelines read back
  identical results with lowering on vs off, under BOTH
  ``DAMPR_TPU_OPTIMIZE`` legs, and ineligible (opaque-UDF) stages pin to
  the host fallback with a recorded reason;
- **observability**: device-targeted stages emit ``device`` spans, the
  run summary carries the ``device`` section (fraction, h2d/d2h,
  device_stages), ``explain()`` renders per-stage targets, and the plan
  report gains ``device_stages``.
"""

import math
import operator
import os

import numpy as np
import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.ops import hashing
from dampr_tpu.ops import lower as ops_lower
from dampr_tpu.ops.text import DocFreq, TokenCounts
from dampr_tpu.plan import lower as plan_lower


@pytest.fixture(autouse=True)
def lowering_on():
    """Force the lowering pass on (explicit, so no backend probe) and
    restore every knob after."""
    old = (settings.lower, settings.lower_batch, settings.optimize)
    settings.lower = "1"
    yield
    (settings.lower, settings.lower_batch, settings.optimize) = old


def _dict_of(blocks, pair_values):
    d = {}
    for b in blocks:
        for k, v in zip(b.keys, b.values):
            d[k] = d.get(k, 0) + (v[1] if pair_values else int(v))
    return d


def _host_dict(mapper, data):
    sink = mapper.window_sink()
    blks = list(sink.add(data)) + list(sink.finish())
    return _dict_of(blks, mapper.pair_values)


def _device_dict(mapper, data):
    sink = ops_lower.device_window_sink(mapper)
    assert sink is not None
    blks = list(sink.add(data)) + list(sink.finish())
    return _dict_of(blks, mapper.pair_values), sink


def _corpus(seed, n_lines=300, exotic=False):
    rng = np.random.RandomState(seed)
    words = ["w%d" % i for i in range(120)] + ["Tok_1", "UPPER", "a"]
    if exotic:
        words += ["x" * 300, "émoji", "naïve", "日本語", "mixedÉcase"]
    lines = [" ".join(rng.choice(words, size=rng.randint(1, 10)))
             for _ in range(n_lines)]
    return ("\n".join(lines) + "\n").encode()


SCANNERS = [
    TokenCounts(mode="whitespace", lower=False, pair_values=False),
    TokenCounts(mode="word", lower=True, pair_values=True),
    DocFreq(mode="word", lower=True, pair_values=False),
    DocFreq(mode="whitespace", lower=False, pair_values=True),
]


class TestProgramParity:
    @pytest.mark.parametrize("case", range(4))
    def test_counts_match_host_scanner(self, case):
        mapper = SCANNERS[case]
        for seed in (1, 2):
            data = _corpus(10 * case + seed, exotic=(seed == 2))
            dev, sink = _device_dict(mapper, data)
            assert dev == _host_dict(mapper, data)
            assert sink.batches >= 1

    def test_small_batches_cut_at_line_boundaries(self):
        data = _corpus(7, n_lines=400)
        for mapper in SCANNERS[1:3]:
            settings.lower_batch = 64  # floor of 1024 applies
            dev, sink = _device_dict(mapper, data)
            settings.lower_batch = 1 << 18
            assert sink.batches > 1
            assert dev == _host_dict(mapper, data)

    def test_hash_lanes_match_engine_hash(self):
        data = _corpus(3)
        mapper = DocFreq(mode="word", lower=True, pair_values=False)
        sink = ops_lower.device_window_sink(mapper)
        for b in sink.add(data):
            h1, h2 = hashing.hash_keys(b.keys)
            assert np.array_equal(h1, b.h1)
            assert np.array_equal(h2, b.h2)

    def test_invalid_utf8_window_falls_back_whole(self):
        data = b"alpha \xff\xfe beta\nbeta \xff gamma\n"
        mapper = DocFreq(mode="word", lower=True, pair_values=False)
        dev, sink = _device_dict(mapper, data)
        assert sink.fallbacks >= 1
        assert dev == _host_dict(mapper, data)

    def test_line_wider_than_batch_falls_back(self):
        settings.lower_batch = 0  # floor 1024
        wide = (" ".join("t%d" % (i % 5) for i in range(4000)) + "\n").encode()
        mapper = DocFreq(mode="word", lower=True, pair_values=False)
        dev, sink = _device_dict(mapper, wide)
        settings.lower_batch = 1 << 18
        assert sink.fallbacks >= 1
        assert dev == _host_dict(mapper, wide)

    def test_wide_line_with_long_token_counts_once(self):
        """The whole-window fallback recounts long tokens — staged
        long-token partials must be discarded, not double-counted."""
        settings.lower_batch = 0  # floor 1024
        big = "y" * 300
        wide = ((big + " " + " ".join("t%d" % (i % 5) for i in range(3000))
                 + " " + big) + "\n").encode()
        for mapper in (DocFreq(mode="word", lower=True, pair_values=False),
                       TokenCounts(mode="word", lower=True,
                                   pair_values=False)):
            dev, sink = _device_dict(mapper, wide)
            if mapper.__class__ is DocFreq:
                # only per-line dedup needs the whole-window fallback;
                # TokenCounts cuts the line into batches freely
                assert sink.fallbacks >= 1
            assert dev == _host_dict(mapper, wide)
        settings.lower_batch = 1 << 18

    def test_empty_and_blank_windows(self):
        mapper = TokenCounts(mode="word", lower=True, pair_values=False)
        for data in (b"", b"  \t \n \n", b"\n\n"):
            dev, _sink = _device_dict(mapper, data)
            assert dev == _host_dict(mapper, data)

    def test_forced_collision_regroups_exactly(self, monkeypatch):
        """A reported 64-bit collision re-groups the batch on host by
        exact token bytes — results cannot change."""
        real = ops_lower._token_fold_jit

        def lying(n, L, dedup):
            fn = real(n, L, dedup)

            def wrapped(mat, lens, lines):
                out = list(fn(mat, lens, lines))
                out[-1] = np.int32(1)  # claim a collision happened
                return tuple(out)

            return wrapped

        monkeypatch.setattr(ops_lower, "_token_fold_jit", lying)
        data = _corpus(11)
        for mapper in (TokenCounts(mode="word", lower=True,
                                   pair_values=False),
                       DocFreq(mode="word", lower=True, pair_values=False)):
            dev, sink = _device_dict(mapper, data)
            assert sink.fallbacks >= 1
            assert dev == _host_dict(mapper, data)

    def test_claims_rejects_subclasses_and_unknown(self):
        class Odd(TokenCounts):
            pass

        assert ops_lower.claims(Odd()) is None
        assert ops_lower.claims(object()) is None
        assert ops_lower.claims(TokenCounts()) is not None


def _tfidf(corpus, name):
    docs = Dampr.text(corpus, os.path.getsize(corpus) // 3 + 1)
    doc_freq = (docs.custom_mapper(
        DocFreq(mode="word", lower=True, pair_values=False))
        .fold_values(operator.add))
    idf = doc_freq.cross_right(
        docs.len(),
        lambda df, total: (df[0], df[1],
                           math.log(1 + (float(total) / df[1]))),
        memory=True)
    em = idf.run(name=name)
    got = em.read()
    stats = em.stats()
    em.delete()
    return got, stats


@pytest.fixture
def corpus(tmp_path):
    path = str(tmp_path / "corpus.txt")
    with open(path, "wb") as f:
        f.write(_corpus(21, n_lines=600))
    return path


class TestPipelineEquivalence:
    @pytest.mark.parametrize("optimize", [True, False])
    def test_tfidf_byte_identical_both_legs(self, corpus, optimize):
        settings.optimize = optimize
        settings.lower = "1"
        dev, s_dev = _tfidf(corpus, "lowertest-dev-%d" % optimize)
        settings.lower = "0"
        host, s_host = _tfidf(corpus, "lowertest-host-%d" % optimize)
        assert dev == host
        assert s_dev["device"]["device_stages"] >= 1
        assert s_dev["device"]["device_fraction"] > 0
        assert s_host["device"]["device_stages"] == 0
        targets = {st["stage"]: st["target"] for st in s_dev["stages"]}
        assert "device" in targets.values()
        assert all(st["target"] == "host" for st in s_host["stages"])

    def test_word_count_shape(self, corpus):
        def run():
            em = (Dampr.text(corpus, os.path.getsize(corpus) // 2 + 1)
                  .custom_mapper(TokenCounts(mode="whitespace",
                                             pair_values=False))
                  .fold_values(operator.add)
                  .run(name="lowertest-wc"))
            got = em.read()
            em.delete()
            return got

        settings.lower = "1"
        dev = run()
        settings.lower = "0"
        assert dev == run()

    def test_ineligible_udf_falls_back_with_reason(self, corpus):
        """An opaque per-record UDF after the scanner keeps the whole
        fused stage on host — and the decision records why.  The UDF
        branches on its value, so the widened jax-traceability
        vocabulary (dampr_tpu.analyze.jaxtrace) rejects it too — the
        abstract eval hits the data-dependent ``if``."""
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False))
            .map(lambda c: c * 2 if c > 0 else -c)
            .fold_values(operator.add))
        em = pipe.run(name="lowertest-udf")
        got_dev = em.read()
        stats = em.stats()
        em.delete()
        # the fused scanner+UDF map stage must NOT have lowered
        map_targets = [st["target"] for st in stats["stages"]
                       if st["kind"] == "map"]
        assert "device" not in map_targets
        decisions = stats["plan"]["lowering"]["targets"]
        reasons = [d["reason"] for d in decisions
                   if d["kind"] == "map" and d["target"] == "host"]
        assert any("vocabulary" in r or "opaque" in r for r in reasons)
        settings.lower = "0"
        em = pipe.run(name="lowertest-udf-host")
        assert got_dev == em.read()
        em.delete()

    def test_memory_input_marked_device_still_exact(self):
        """A device-marked scanner over a non-byte input takes the
        per-record fallback inside the job — results unchanged."""
        lines = ["a b c", "b c", "c c a"]
        pipe = (Dampr.memory(lines)
                .custom_mapper(DocFreq(mode="word", lower=True,
                                       pair_values=False))
                .fold_values(operator.add))
        em = pipe.run(name="lowertest-mem")
        dev = em.read()
        em.delete()
        settings.lower = "0"
        em = pipe.run(name="lowertest-mem-host")
        assert dev == em.read()
        em.delete()

    def test_per_stage_kill_switch(self, corpus):
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False),
            lower=False)
            .fold_values(operator.add))
        em = pipe.run(name="lowertest-kill")
        stats = em.stats()
        em.delete()
        decisions = stats["plan"]["lowering"]["targets"]
        killed = [d for d in decisions if "lower=False" in d["reason"]]
        assert killed, decisions


class TestGranularityGuards:
    """Device batching regroups partial counts (batch vs window
    granularity) — only summing consumers are invariant to it, so
    anything else must pin the scanner to host."""

    def test_min_fold_stays_host_and_matches(self, corpus):
        def build():
            return (Dampr.text(corpus, os.path.getsize(corpus))
                    .custom_mapper(DocFreq(mode="word", lower=True,
                                           pair_values=False))
                    .fold_values(min))

        decisions = plan_lower.analyze(build().pmer.graph)
        map_targets = [d for d in decisions if d["kind"] == "map"]
        assert all(d["target"] == "host" for d in map_targets), decisions
        settings.lower = "1"
        em = build().run(name="lowertest-min")
        dev = em.read()
        assert all(st["target"] == "host" for st in em.stats()["stages"]
                   if st["kind"] == "map")
        em.delete()
        settings.lower = "0"
        em = build().run(name="lowertest-min-host")
        assert dev == em.read()
        em.delete()

    def test_branched_consumer_pins_host(self, corpus):
        """A second, non-fold consumer of the scanner output would
        observe the partial grouping — the scanner must not lower."""
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        x = docs.custom_mapper(DocFreq(mode="word", lower=True,
                                       pair_values=False))
        folded = x.fold_values(operator.add)
        branch = x.filter(lambda c: c > 1)
        graph = folded.pmer.graph.union(branch.pmer.graph)
        decisions = plan_lower.analyze(graph)
        scanner = [d for d in decisions if d["kind"] == "map"][0]
        assert scanner["target"] == "host"
        assert "granularity" in scanner["reason"]

    def test_requested_output_pins_host(self, corpus):
        """Reading the scanner output directly exposes the partials."""
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        x = docs.custom_mapper(DocFreq(mode="word", lower=True,
                                       pair_values=False))
        decisions = plan_lower.analyze(x.pmer.graph, outputs=[x.source])
        scanner = [d for d in decisions if d["kind"] == "map"][0]
        assert scanner["target"] == "host"

    def test_sum_combiner_still_lowers(self, corpus):
        """With a hoisted sum combiner the job output is fold-compacted
        identically on both legs — eligibility is unaffected."""
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(DocFreq(mode="word", lower=True,
                                           pair_values=False))
                .fold_values(operator.add))
        from dampr_tpu.plan import passes

        optimized, _report = passes.optimize(pipe.pmer.graph, [pipe.source])
        decisions = plan_lower.analyze(optimized, outputs=[pipe.source])
        assert any(d["target"] == "device" and d["kind"] == "map"
                   for d in decisions), decisions


class TestPlanAnalysis:
    def test_history_pins_tiny_stage_to_host(self, corpus):
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False))
            .fold_values(operator.add))
        graph = pipe.pmer.graph
        base_decisions = plan_lower.analyze(graph)
        dev_sids = [d["sid"] for d in base_decisions
                    if d["target"] == "device" and d["kind"] == "map"]
        assert dev_sids
        history = {"stages": [{"stage": dev_sids[0], "records_out": 3}]}
        pinned = plan_lower.analyze(graph, history)
        got = {d["sid"]: d for d in pinned}[dev_sids[0]]
        assert got["target"] == "host"
        assert "lower_min_records" in got["reason"]

    def test_forced_lowering_ignores_history_floor(self, corpus,
                                                   tmp_path):
        """An explicit DAMPR_TPU_LOWER=1 wins over accumulated run
        history: the stats floor (lower_min_records) is an AUTO-mode
        behavior, so a tiny prior run recorded in the history corpus
        must not silently pin a forced run's eligible stage back to
        host (regression: the corpus — which untraced runs now feed —
        would otherwise flip device_stages to 0 on every rerun of a
        small named pipeline)."""
        from dampr_tpu.obs import history as obs_history

        old_scratch = settings.scratch_root
        settings.scratch_root = str(tmp_path / "scratch")
        settings.lower = "1"
        try:
            name = "lowertest-forced-history"

            def pipe():
                docs = Dampr.text(corpus, os.path.getsize(corpus))
                return (docs.custom_mapper(
                    DocFreq(mode="word", lower=True, pair_values=False))
                    .fold_values(operator.add))

            em1 = pipe().run(name)
            s1 = em1.stats()
            em1.delete()
            assert s1["device"]["device_stages"] >= 1, s1["device"]
            # the finalized run recorded tiny history under this name...
            assert obs_history.load(name)
            # ...and a rerun STILL lowers (forced mode skips the floor)
            em2 = pipe().run(name)
            s2 = em2.stats()
            em2.delete()
            assert s2["device"]["device_stages"] >= 1, s2["device"]
        finally:
            settings.lower = "auto"
            settings._resolved_lower = None
            settings.scratch_root = old_scratch

    def test_explain_renders_targets(self, corpus):
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False))
            .fold_values(operator.add))
        text = pipe.explain()
        assert "targets:" in text
        assert "-> device" in text
        assert "jitted" in text
        settings.lower = "0"
        text = pipe.explain()
        assert "device lowering off" in text

    def test_optimize_off_leg_still_analyzed(self, corpus):
        settings.optimize = False
        docs = Dampr.text(corpus, os.path.getsize(corpus))
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False))
            .fold_values(operator.add))
        text = pipe.explain()
        assert "optimizer OFF" in text
        assert "-> device" in text


class TestObservability:
    def test_device_span_and_stats_section(self, corpus, tmp_path):
        old_trace, old_dir = settings.trace, settings.trace_dir
        old_handoff = settings.handoff
        settings.trace = True
        settings.trace_dir = str(tmp_path / "traces")
        # This test pins the CLASSIC device-lowered surface (device
        # spans, boundary bytes); the handoff tier replaces exactly
        # those on its edge and has its own pins (test_handoff).
        settings.handoff = "off"
        try:
            _got, stats = _tfidf(corpus, "lowertest-traced")
        finally:
            settings.trace = old_trace
            settings.trace_dir = old_dir
            settings.handoff = old_handoff
        assert stats["device"]["device_stages"] >= 1
        assert stats["device"]["h2d_bytes"] > 0
        assert stats["device"]["d2h_bytes"] > 0
        spans = stats.get("spans") or {}
        assert "device" in spans, spans
        # the emitted trace validates against the checked-in schema
        import subprocess
        import sys
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        res = subprocess.run(
            [sys.executable, os.path.join(root, "tools",
                                          "validate_trace.py"),
             stats["trace_file"],
             "--schema", os.path.join(root, "docs", "trace_schema.json"),
             "--require-cats", "device,stage,fold"],
            capture_output=True, text=True)
        assert res.returncode == 0, res.stdout + res.stderr
