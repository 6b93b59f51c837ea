"""Cross-stage device-resident handoff (docs/plan.md "Cross-stage device
fusion"): the plan's ``handoff="device"`` edge keeps a lowered map's
program outputs HBM-resident into the consuming device fold.

Exactness contract under test: handoff on / off / forced-fallback are
byte-identical; every degrade (HBM budget, vocabulary overflow, lane
guard) flushes to the classic spill path; a killed job leaves no leaked
device residents; boundary accounting (h2d) is idempotent per block.
"""

import operator
import os
from collections import Counter

import numpy as np
import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.blocks import Block
from dampr_tpu.obs import doctor
from dampr_tpu.ops import handoff as handoff_mod
from dampr_tpu.ops.text import DocFreq
from dampr_tpu.plan import model as plan_model
from dampr_tpu.storage import BlockRef, RunStore


@pytest.fixture(autouse=True)
def handoff_knobs():
    """Force lowering on (so device edges exist on CPU JAX) and restore
    every knob this suite touches.  The optimizer and the analyzer are
    forced ON: the handoff edge only exists on the FUSED map->fold
    shape (the optimizer-off plan interposes an identity stage — a
    structural decline, pinned by its own test below), and certified
    lane chains need the analyze pass."""
    old = (settings.lower, settings.handoff, settings.hbm_budget,
           settings.optimize, settings.analyze, settings.mesh_fold,
           settings.faults)
    settings.lower = "1"
    settings.handoff = "auto"
    settings.optimize = True
    settings.analyze = True
    yield
    (settings.lower, settings.handoff, settings.hbm_budget,
     settings.optimize, settings.analyze, settings.mesh_fold,
     settings.faults) = old


def _corpus(tmp_path, seed=3, n_lines=900, vocab=140):
    rng = np.random.RandomState(seed)
    words = ["w%d" % i for i in range(vocab)] + ["Tok_1", "UPPER", "a"]
    lines = [" ".join(rng.choice(words, size=rng.randint(1, 10)))
             for _ in range(n_lines)]
    path = str(tmp_path / "corpus.txt")
    with open(path, "wb") as f:
        f.write(("\n".join(lines) + "\n").encode())
    return path


def _docfreq(corpus, name):
    docs = Dampr.text(corpus, os.path.getsize(corpus) // 3 + 1)
    pipe = (docs.custom_mapper(
        DocFreq(mode="word", lower=True, pair_values=False))
        .fold_values(operator.add))
    em = pipe.run(name=name)
    got = sorted(em.read())
    stats = em.stats()
    em.delete()
    return got, stats


def _oracle(corpus):
    import re

    rx = re.compile(r"[^\w]+")
    c = Counter()
    with open(corpus, encoding="utf-8") as f:
        for line in f:
            c.update(set(t for t in rx.split(line.lower()) if t))
    return sorted(c.items())


class TestEdgeDecision:
    def test_scanner_edge_marked_device(self, tmp_path):
        corpus = _corpus(tmp_path)
        docs = Dampr.text(corpus, os.path.getsize(corpus) + 1)
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=False))
            .fold_values(operator.add))
        text = pipe.explain()
        assert "handoff:" in text
        assert "stay HBM-resident" in text

    def test_handoff_off_declines_with_reason(self, tmp_path):
        settings.handoff = "off"
        corpus = _corpus(tmp_path)
        got, stats = _docfreq(corpus, "handoff-off-edge")
        assert stats["device"]["handoff_edges"] == 0
        assert stats["device"]["handoff_bytes"] == 0
        edges = stats["plan"]["lowering"]["handoff"]
        assert edges and all(e["handoff"] == "spill" for e in edges)
        assert any("handoff off" in e["reason"] for e in edges)

    def test_optimizer_off_declines_structurally(self, tmp_path):
        """Without the optimizer's map->fold fusion an identity stage
        sits between producer and fold: the edge declines (the runner
        only threads refs across a DIRECT device->device edge) and the
        whole run rides the spill path, byte-identically."""
        settings.optimize = False
        corpus = _corpus(tmp_path)
        got, stats = _docfreq(corpus, "handoff-noopt")
        assert got == _oracle(corpus)
        assert stats["device"]["handoff_edges"] == 0
        assert stats["device"]["handoff_bytes"] == 0
        edges = stats["plan"]["lowering"]["handoff"]
        assert all(e["handoff"] == "spill" for e in edges)

    def test_pair_values_scanner_declines(self, tmp_path):
        corpus = _corpus(tmp_path)
        docs = Dampr.text(corpus, os.path.getsize(corpus) + 1)
        pipe = (docs.custom_mapper(
            DocFreq(mode="word", lower=True, pair_values=True))
            .fold_by(lambda kv: kv[0], operator.add,
                     lambda kv: kv[1]))
        em = pipe.run(name="handoff-pairvalues")
        stats = em.stats()
        em.delete()
        assert stats["device"]["handoff_bytes"] == 0

    def test_price_handoff_ignores_host_runs(self):
        """Only LOWERED runs vote: a fast host-codec run of the same
        plan says nothing about handoff-vs-spill."""
        mk = lambda wall, frac, edges: {
            "fingerprint": "fp1", "wall_seconds": wall,
            "device_fraction": frac,
            "stages": [{"bytes_in": 64 << 20}],
            "handoff": {"edges": edges, "degrades": 0},
        }
        # host runs (device_fraction 0) are much faster — must not vote
        recs = ([mk(1.0, 0, 0)] * 6
                + [mk(10.0, 0.5, 0)] * 3 + [mk(7.0, 0.5, 1)] * 3)
        decision, why = plan_model.price_handoff(recs, "fp1")
        assert decision == "device", why

    def test_price_handoff_normalizes_by_volume(self):
        """A small spill run and a large resident run compare on s/MB,
        not wall seconds."""
        mk = lambda wall, mb, edges: {
            "fingerprint": "fp1", "wall_seconds": wall,
            "device_fraction": 0.5,
            "stages": [{"bytes_in": mb << 20}],
            "handoff": {"edges": edges, "degrades": 0},
        }
        # spill: 1s for 4MB (0.25 s/MB); resident: 8s for 64MB (0.125)
        recs = [mk(1.0, 4, 0), mk(8.0, 64, 1)]
        decision, why = plan_model.price_handoff(recs, "fp1")
        assert decision == "device", why

    def test_price_handoff_declines_on_slower_evidence(self):
        mk = lambda wall, edges: {
            "fingerprint": "fp1", "wall_seconds": wall,
            "device_fraction": 0.5,
            "stages": [{"bytes_in": 16 << 20}],
            "handoff": {"edges": edges, "degrades": 0},
        }
        recs = [mk(2.0, 0), mk(9.0, 1)]
        decision, why = plan_model.price_handoff(recs, "fp1")
        assert decision == "spill"
        assert "s/MB" in why

    def test_price_handoff_no_variance_reason(self):
        decision, why = plan_model.price_handoff([], "fp1")
        assert decision is None
        assert "variance" in why

    def test_degraded_runs_vote_neither_side(self):
        mk = lambda wall, edges, deg: {
            "fingerprint": "fp1", "wall_seconds": wall,
            "device_fraction": 0.5,
            "stages": [{"bytes_in": 16 << 20}],
            "handoff": {"edges": edges, "degrades": deg},
        }
        recs = [mk(2.0, 1, 3), mk(9.0, 0, 0)]
        decision, _why = plan_model.price_handoff(recs, "fp1")
        assert decision is None  # the degraded run's wall mixes paths


class TestExactness:
    def test_docfreq_byte_identical_on_off_fallback(self, tmp_path):
        corpus = _corpus(tmp_path)
        want = _oracle(corpus)

        settings.handoff = "on"
        on, s_on = _docfreq(corpus, "handoff-on")
        assert s_on["device"]["handoff_edges"] >= 1
        assert s_on["device"]["handoff_bytes"] > 0

        settings.handoff = "off"
        off, s_off = _docfreq(corpus, "handoff-off")
        assert s_off["device"]["handoff_bytes"] == 0

        # forced-fallback: handoff armed, but a starved budget degrades
        # every edge mid-stage back to the spill path
        settings.handoff = "on"
        settings.hbm_budget = 4096
        fb, s_fb = _docfreq(corpus, "handoff-fallback")
        settings.hbm_budget = "auto"
        assert s_fb["device"]["handoff_degrades"] >= 1

        assert on == off == fb == want

    def test_tfidf_shape_byte_identical(self, tmp_path):
        """The bench pipeline shape (DocFreq -> fold -> idf cross) is
        identical with the handoff on and off."""
        import math

        corpus = _corpus(tmp_path, seed=11)

        def tfidf(name):
            docs = Dampr.text(corpus, os.path.getsize(corpus) // 2 + 1)
            df = (docs.custom_mapper(
                DocFreq(mode="word", lower=True, pair_values=False))
                .fold_values(operator.add))
            idf = df.cross_right(
                docs.len(),
                lambda d, total: (d[0], d[1],
                                  math.log(1 + (float(total) / d[1]))),
                memory=True)
            em = idf.run(name=name)
            got = sorted(em.read())
            stats = em.stats()
            em.delete()
            return got, stats

        settings.handoff = "on"
        on, s_on = tfidf("handoff-tfidf-on")
        settings.handoff = "off"
        off, _ = tfidf("handoff-tfidf-off")
        assert on == off
        assert s_on["device"]["handoff_edges"] >= 1

    def test_certified_numeric_chain_byte_identical(self):
        """The first numeric non-text handoff edge: a certified
        ValueMap/Filter/Rekey lane chain feeding a keyed device sum
        fold, byte-identical with the edge resident and spilled."""
        old = settings.device_min_batch
        settings.device_min_batch = 1024
        try:
            N = 60000

            def build():
                return (Dampr.memory(list(range(N)), partitions=2)
                        .map(lambda x: x * 3 + 1)
                        .filter(lambda x: x % 2 == 0)
                        .count(lambda x: x % 97))

            text = build().explain()
            assert "Rekey" in text
            assert "handoff:" in text

            settings.handoff = "on"
            em = build().run(name="lane-handoff-on")
            on = sorted(em.read())
            s_on = em.stats()
            em.delete()

            settings.handoff = "off"
            em = build().run(name="lane-handoff-off")
            off = sorted(em.read())
            em.delete()

            settings.lower = "0"
            em = build().run(name="lane-handoff-host")
            host = sorted(em.read())
            em.delete()

            want = sorted(Counter(
                v % 97 for v in (x * 3 + 1 for x in range(N))
                if v % 2 == 0).items())
            assert on == off == host == want
            assert s_on["device"]["handoff_edges"] >= 1
            # handoff_bytes stays 0 here: lane-program outputs are
            # HOST-authoritative (64-bit host eval), so they enter the
            # HBM tier through a round trip — the chain edge's win is
            # the tier floor (no spill/pickle before the fold), and
            # only scanner vocabularies register without a round trip.
            assert s_on["device"]["handoff_bytes"] == 0
        finally:
            settings.device_min_batch = old

    def test_distinct_rekey_chains_get_distinct_programs(self):
        """Two bare ``count()`` chains have identical (empty) lane ops
        but different key functions: the program cache must key on the
        re-key too, or the second stage runs the first one's compiled
        program.  The key fns AGREE on the smallest values (both bucket
        to 0), so a first-batch differential check alone cannot catch
        the swap — only distinct cache entries can."""
        from dampr_tpu.analyze.jaxtrace import ChainSpec, _chain_key

        ka, kb = (lambda v: v // 10), (lambda v: v // 100)
        assert (_chain_key(ChainSpec([], [], rekey=(ka, None)))
                != _chain_key(ChainSpec([], [], rekey=(kb, None))))

        old = settings.device_min_batch
        settings.device_min_batch = 1024
        try:
            N = 30000
            for div in (10, 100):
                em = (Dampr.memory(list(range(N)), partitions=2)
                      .count(lambda x, d=div: x // d)
                      .run(name="rekey-prog-%d" % div))
                got = sorted(em.read())
                em.delete()
                want = sorted(Counter(x // div
                                      for x in range(N)).items())
                assert got == want, "div=%d" % div
        finally:
            settings.device_min_batch = old

    def test_vocabulary_shift_reverts_and_stays_exact(self, tmp_path):
        """A corpus whose vocabulary turns over mid-stream forces table
        misses past the revert bar; the job re-bootstraps and results
        stay exact."""
        rng = np.random.RandomState(5)
        lines = []
        for phase in range(4):
            words = ["p%d_%d" % (phase, i) for i in range(150)]
            lines += [" ".join(rng.choice(words,
                                          size=rng.randint(1, 10)))
                      for _ in range(400)]
        path = str(tmp_path / "shift.txt")
        with open(path, "wb") as f:
            f.write(("\n".join(lines) + "\n").encode())
        settings.handoff = "on"
        got, stats = _docfreq(path, "handoff-shift")
        assert got == _oracle(path)
        assert stats["device"]["handoff_bytes"] > 0


#: Two lowercase words with the same 32-bit FNV-1a ``h1`` (458778).
_SAME_H1 = ("gckxr", "ydtrd")

#: The three table-program variants: plain counts, and per-line dedup
#: with lines short enough for the windowed compares (``dedup_k`` 16)
#: or too long for them (the ``(slot, line)`` sort, ``dedup_k`` 0).
_VARIANTS = {"count": (False, 8, 0), "docfreq-window": (True, 8, 16),
             "docfreq-sort": (True, 24, 0)}


def _windows(rng, tokens_per_line, phases, every=()):
    """One window of whole lines per ``(words, n_lines)`` phase; the
    second window also holds each of ``every`` once."""
    wins = []
    for i, (words, n_lines) in enumerate(phases):
        lines = [" ".join(rng.choice(words, size=tokens_per_line))
                 for _ in range(n_lines)]
        if i == 1:
            lines += [" ".join(every[j:j + tokens_per_line])
                      for j in range(0, len(every), tokens_per_line)]
        wins.append(("\n".join(lines) + "\n").encode())
    return wins


class _IndexSpy(object):
    """Drives a handoff sink over whole windows and records, per table
    batch, the vocabulary bytes its published index left out, the batch's
    tokens and which of them missed, and the ``dedup_k`` it ran."""

    def __init__(self, monkeypatch):
        self.batches = []     # (unplaced bytes, token bytes, missed set)
        self.dedup_ks = set()
        self.caps = []
        spy = self
        orig_dispatch = handoff_mod.HandoffVocab._dispatch
        orig_drain = handoff_mod.HandoffVocab.drain
        orig_program = handoff_mod._table_program

        def dispatch(hv, *a):
            batch = orig_dispatch(hv, *a)
            # After the sync: the host index is the one the program reads.
            sv = hv.shared
            placed = {int(s) for s in sv._index[:, 1] if s < sv.cap}
            spy._unplaced = {sv.slot_bytes[s] for s in range(sv.nslots)
                             if s not in placed}
            spy.caps.append(sv.cap)
            return batch

        def drain(hv, buf, batch):
            out = orig_drain(hv, buf, batch)
            toks = [buf[s:s + ln].tobytes()
                    for s, ln in zip(batch.starts, batch.lens)]
            missed = set(batch.miss_idx.tolist()
                         if batch.miss_idx is not None else ())
            spy.batches.append((spy._unplaced, toks, missed, int(batch.n),
                                int(batch.n_miss)))
            return out

        def program(n, L, cap, Lcap, dedup, acc, dedup_k=0):
            spy.dedup_ks.add(dedup_k)
            return orig_program(n, L, cap, Lcap, dedup, acc, dedup_k)

        monkeypatch.setattr(handoff_mod.HandoffVocab, "_dispatch", dispatch)
        monkeypatch.setattr(handoff_mod.HandoffVocab, "drain", drain)
        monkeypatch.setattr(handoff_mod, "_table_program", program)

    def unplaced_tokens(self):
        """Tokens of unplaced slots in table batches; asserts each one
        took the miss path."""
        n = 0
        for unplaced, toks, missed, _n, _m in self.batches:
            for i, t in enumerate(toks):
                if t in unplaced:
                    assert i in missed, t
                    n += 1
        return n


def _run_handoff_sink(mapper, windows, name):
    """Every window through a handoff sink, then its job-end finalize:
    the counts and the store."""
    from dampr_tpu.ops import lower as ops_lower

    store = RunStore(name, budget=1 << 26)
    store.handoff_active = True
    got = Counter()
    try:
        sink = ops_lower.device_window_sink(mapper, store=store,
                                            handoff=True)
        blocks = []
        for win in windows:
            blocks += list(sink.add(win) or ())
        assert not sink._hv.degraded
        fblocks, hmap = sink.finalize_handoff(store, 4)
        blocks += list(fblocks)
        blocks += [ref.get() for refs in hmap.values() for ref in refs]
        for blk in blocks:
            for k, v in zip(blk.keys, blk.values):
                got[k] += int(v)
    finally:
        store.cleanup()
    return got, store


def _host_counts(windows, dedup):
    """The same windows through the host codec's exact grouping."""
    from dampr_tpu.ops.text import chunk_doc_freq, chunk_token_counts

    fn = chunk_doc_freq if dedup else chunk_token_counts
    got = Counter()
    for win in windows:
        blk = fn(win, "word", True, False)
        for k, v in zip(blk.keys, blk.values):
            got[k] += int(v)
    return got


class TestBucketIndex:
    """The table program's hashed bucket index: a slot the index leaves
    out (both buckets taken, or another slot holds its ``h1``) and a
    regrow's rebuilt index all keep the counts exact, on every dedup
    variant."""

    @pytest.mark.parametrize("variant", sorted(_VARIANTS))
    @pytest.mark.parametrize("case", ["tiny-index", "same-h1", "regrow"])
    def test_index_exact(self, monkeypatch, case, variant):
        from dampr_tpu.ops.text import TokenCounts

        dedup, per_line, dedup_k = _VARIANTS[variant]
        rng = np.random.RandomState(13)
        base = ["b%d" % i for i in range(56)]
        if case == "tiny-index":
            # cap 64, 128 buckets, no relocation: about 40% of the
            # vocabulary lands where both buckets are already taken.
            monkeypatch.setattr(handoff_mod, "_MIN_CAP", 64)
            monkeypatch.setattr(handoff_mod, "_MAX_KICKS", 0)
            fresh = ["n%d" % i for i in range(6)]
            phases = [(base, 60), (base + fresh, 60), (base + fresh, 60)]
        elif case == "same-h1":
            base += list(_SAME_H1)
            phases = [(base, 60), (base, 60)]
        else:
            # The second window's misses grow the vocabulary past cap 64
            # inside table mode: the index is rebuilt for cap 128, and
            # the third window finds every word there.
            monkeypatch.setattr(handoff_mod, "_MIN_CAP", 64)
            fresh = ["r%d" % i for i in range(20)]
            phases = [(base, 60), (base * 3 + fresh, 60),
                      (base * 3 + fresh, 60)]
        windows = _windows(rng, per_line, phases,
                           fresh if case == "regrow" else ())
        spy = _IndexSpy(monkeypatch)
        mapper = (DocFreq if dedup else TokenCounts)(
            mode="word", lower=True, pair_values=False)
        got, store = _run_handoff_sink(mapper, windows,
                                       "index-%s-%s" % (case, variant))

        assert got == _host_counts(windows, dedup)
        assert spy.batches, "no table batch ran"
        assert spy.dedup_ks == {dedup_k} if dedup else {0}
        if case == "regrow":
            assert spy.caps == [64, 128], spy.caps
            # The last batch found the regrown vocabulary in the index.
            _u, _t, _m, n, n_miss = spy.batches[-1]
            assert n_miss == 0 and store.table_index_unplaced == 0
        else:
            assert spy.unplaced_tokens() > 0
            assert store.table_index_unplaced > 0
        if case == "same-h1":
            assert store.table_index_unplaced == 1

    def test_probe_counters_add_up(self, monkeypatch):
        """``table_probe_tokens`` / ``table_probe_hits`` are each table
        batch's tokens and its tokens less its misses."""
        rng = np.random.RandomState(4)
        base = ["c%d" % i for i in range(90)]
        fresh = ["f%d" % i for i in range(30)]
        windows = _windows(rng, 8, [(base, 80), (base + fresh, 80),
                                    (base + fresh, 80)])
        spy = _IndexSpy(monkeypatch)
        _got, store = _run_handoff_sink(
            DocFreq(mode="word", lower=True, pair_values=False), windows,
            "index-counters")
        assert len(spy.batches) == 2
        assert store.table_probe_tokens == sum(
            b[3] for b in spy.batches) == 1280
        assert store.table_probe_hits == sum(
            b[3] - b[4] for b in spy.batches)
        assert 0 < store.table_probe_hits < store.table_probe_tokens


class TestDegradeAndKill:
    def test_budget_exceeded_mid_stage_degrades_exactly(self, tmp_path):
        corpus = _corpus(tmp_path, vocab=4000, n_lines=2500)
        settings.handoff = "on"
        settings.hbm_budget = 1 << 14  # 16 KB: vocabulary can't fit
        got, stats = _docfreq(corpus, "handoff-degrade")
        assert got == _oracle(corpus)
        assert stats["device"]["handoff_degrades"] >= 1

    def test_drain_failure_loses_no_miss_tokens(self, monkeypatch):
        """A table-mode drain whose miss absorb is REFUSED
        (vocabulary/lane budget) must re-emit the missed tokens through
        the exact host path — the degrade flush only holds the batch's
        hits.  Window 1 bootstraps the vocabulary; window 2 carries NEW
        tokens (guaranteed table misses) and every absorb is forced to
        fail, so its drain takes the degrade path; exactness over the
        emitted blocks proves no token was dropped."""
        from dampr_tpu.ops import lower as ops_lower
        from dampr_tpu.ops.text import DocFreq

        rng = np.random.RandomState(7)
        base = ["w%d" % i for i in range(120)]
        fresh = ["new%d" % i for i in range(80)]
        w1 = ("\n".join(" ".join(rng.choice(base, size=6))
                        for _ in range(300)) + "\n").encode()
        w2 = ("\n".join(" ".join(rng.choice(base + fresh, size=6))
                        for _ in range(300)) + "\n").encode()

        monkeypatch.setattr(
            handoff_mod.HandoffVocab, "_absorb_miss_tokens",
            lambda self, *a, **kw: False)
        settings.handoff = "on"
        store = RunStore("handoff-missdrop", budget=1 << 26)
        store.handoff_active = True
        try:
            sink = ops_lower.device_window_sink(
                DocFreq(mode="word", lower=True, pair_values=False),
                store=store, handoff=True)
            blocks = list(sink.add(w1) or ())
            assert sink._hv.table_mode  # window 1 really bootstrapped
            blocks += list(sink.add(w2) or ())
            assert sink._hv.degraded  # the refused absorb degraded
            fblocks, hmap = sink.finalize_handoff(store, 4)
            assert not hmap  # a degraded job registers no device refs
            blocks += list(fblocks)

            got = Counter()
            for blk in blocks:
                for k, v in zip(blk.keys, blk.values):
                    got[k] += int(v)
            want = Counter()
            for data in (w1, w2):
                for line in data.decode().splitlines():
                    want.update(set(t for t in __import__("re").split(
                        r"[^\w]+", line.lower()) if t))
            assert got == want
        finally:
            store.cleanup()

    def test_kill_mid_handoff_leaks_no_device_residents(self, tmp_path):
        """A fatal fault mid-map (after handoff batches dispatched) must
        not leave device bytes charged against the store budget."""
        from dampr_tpu import runner as runner_mod

        corpus = _corpus(tmp_path, n_lines=1500)
        settings.handoff = "on"
        # nth=1: the first job's window bootstraps (or dispatches) and
        # allocates device residents; the second dispatch-site hit dies
        # fatally with those residents live.
        settings.faults = "device_dispatch:nth=1,kind=fatal"
        stores = []
        orig = RunStore.__init__

        def spy(self, *a, **kw):
            orig(self, *a, **kw)
            stores.append(self)

        RunStore.__init__ = spy
        try:
            with pytest.raises(Exception):
                _docfreq(corpus, "handoff-kill")
        finally:
            RunStore.__init__ = orig
            settings.faults = None
        assert stores
        for store in stores:
            live = [r for r in store._dev_resident if not r._dead]
            assert not live, "leaked device residents"
            assert store._dev_bytes == 0, "device budget not returned"

    def test_long_token_does_not_widen_rows_or_degrade(self):
        """A multi-KB token absorbed into the vocabulary (the
        _long_tokens host path) must not widen every slot's device row —
        probe batches only carry tokens <= _SHORT_TOKEN, so a longer
        row can never verify anyway.  Its bytes truncate; its counts
        stay exact."""
        from dampr_tpu.ops.text import _SHORT_TOKEN

        store = RunStore("handoff-long", budget=1 << 26)
        store.handoff_active = True
        try:
            hv = handoff_mod.HandoffVocab(store, dedup=False)
            long_key = "x" * 5000
            keys = ["a", "b", long_key]
            from dampr_tpu.ops import hashing

            ks = np.empty(3, dtype=object)
            ks[:] = keys
            h1, h2 = hashing.hash_keys(ks)
            ok, _frac = hv.absorb_drain(
                keys, np.array([2, 3, 7], dtype=np.int64), h1, h2, 12)
            assert ok, "long token forced a degrade"
            assert not hv.degraded
            assert hv.shared.Lcap <= 2 * (_SHORT_TOKEN + 1), hv.shared.Lcap
            blk = hv.degrade("test flush")
            got = dict(zip(blk.keys, blk.values))
            assert got == {"a": 2, "b": 3, long_key: 7}
        finally:
            store.cleanup()

    def test_flush_block_returns_budget(self):
        """HandoffVocab.degrade flushes every count into one hash-sorted
        block and drops the job's accumulator — no device array of the
        job survives; the stage vocabulary keeps its slots."""
        store = RunStore("handoff-flush", budget=1 << 24)
        store.handoff_active = True
        hv = handoff_mod.HandoffVocab(store, dedup=False)
        keys = ["k%d" % i for i in range(100)]
        from dampr_tpu.ops import hashing

        ks = np.empty(100, dtype=object)
        ks[:] = keys
        h1, h2 = hashing.hash_keys(ks)
        ok, _frac = hv.absorb_drain(keys, np.ones(100, dtype=np.int64),
                                    h1, h2, 100)
        assert ok
        blk = hv.degrade("test degrade")
        assert blk is not None and len(blk) == 100
        assert sorted(blk.keys) == sorted(keys)
        assert hv.acc is None and hv.shared.nslots == 100
        assert store.handoff_degrades == 1
        store.cleanup()


def _phased_corpus(tmp_path, phases=4, n_lines=300, seed=11):
    """Lines in ``phases`` byte-contiguous parts, each with words of its
    own plus a common set: a job over one part never counts the other
    parts' words, which its chip's vocabulary still holds."""
    rng = np.random.RandomState(seed)
    common = ["c%d" % i for i in range(60)]
    lines = []
    for ph in range(phases):
        words = common + ["p%d_%d" % (ph, i) for i in range(80)]
        lines += [" ".join(rng.choice(words, size=rng.randint(1, 10)))
                  for _ in range(n_lines)]
    path = str(tmp_path / "phased.txt")
    with open(path, "wb") as f:
        f.write(("\n".join(lines) + "\n").encode())
    return path


def _stage_run(corpus, name, dedup, jobs):
    """``jobs`` map jobs of one lowered stage (DocFreq or TokenCounts
    into ``fold_values``): the result and the run's stats."""
    from dampr_tpu.ops.text import TokenCounts

    mapper = (DocFreq if dedup else TokenCounts)(
        mode="word", lower=True, pair_values=False)
    em = (Dampr.text(corpus, os.path.getsize(corpus) // jobs + 1)
          .custom_mapper(mapper).fold_values(operator.add).run(name=name))
    got = sorted(em.read())
    stats = em.stats()
    em.delete()
    return got, stats


def _word_counts(corpus):
    import re

    rx = re.compile(r"[^\w]+")
    c = Counter()
    with open(corpus, encoding="utf-8") as f:
        for line in f:
            c.update(t for t in rx.split(line.lower()) if t)
    return sorted(c.items())


_DEDUP = pytest.mark.parametrize("dedup", [False, True],
                                 ids=["word-count", "doc-freq"])


class TestStageVocabulary:
    """The map jobs of one lowered stage on one chip share its handoff
    vocabulary (slot map and device table), each with an accumulator of
    its own: exact results, each word inserted once, one seeding job,
    and nothing carried into the next ``run()``."""

    JOBS = 4

    @pytest.fixture(autouse=True)
    def one_chip(self, monkeypatch):
        """The data mesh cut to one device: every map job on chip 0;
        the stage vocabularies made recorded in ``self.made``."""
        from dampr_tpu.parallel import mesh as mesh_mod

        full = mesh_mod.data_mesh
        monkeypatch.setattr(mesh_mod, "data_mesh",
                            lambda devices=None, n=None: full(devices, 1))
        self.made = []
        init = handoff_mod.StageVocab.__init__
        made = self.made

        def record(sv, *a, **kw):
            init(sv, *a, **kw)
            made.append(sv)

        monkeypatch.setattr(handoff_mod.StageVocab, "__init__", record)
        settings.handoff = "on"

    def _want(self, corpus, dedup):
        return _oracle(corpus) if dedup else _word_counts(corpus)

    @_DEDUP
    def test_jobs_share_one_vocabulary(self, tmp_path, dedup):
        corpus = _phased_corpus(tmp_path)
        got, stats = _stage_run(corpus, "stage-vocab-share", dedup,
                                self.JOBS)
        want = self._want(corpus, dedup)
        assert got == want
        dev = stats["device"]
        assert dev["handoff_degrades"] == 0 and dev["handoff_bytes"] > 0
        # One vocabulary for the stage's chip, each word inserted once,
        # and every job but its seeding one started on it.
        (sv,) = self.made
        assert dev["handoff_slot_inserts"] == sv.nslots == len(want)
        assert sorted(sv.keys) == sorted(k for k, _c in want)
        assert dev["handoff_shared_joins"] == self.JOBS - 1
        assert dev["table_probe_tokens"] > 0

    @_DEDUP
    def test_seed_drains_before_the_others_join(self, tmp_path,
                                                monkeypatch, dedup):
        """The chip's bootstrap, on every backend: the seeding job runs a
        classic token-fold batch, its first drain ends the seed, and only
        then do the chip's other jobs join, in table mode."""
        from dampr_tpu.ops import lower as ops_lower

        events = []
        sink_cls = ops_lower.DeviceTokenFoldSink
        hv_cls = handoff_mod.HandoffVocab

        def spy(cls, name, note):
            """Record ``name``'s calls with ``note`` before and after;
            a seed's end is recorded before it wakes the waiting jobs."""
            real = getattr(cls, name)

            def wrapped(obj, *a, **kw):
                before = note(obj)
                if name == "end_seed":
                    events.append((name, before, None))
                    return real(obj, *a, **kw)
                out = real(obj, *a, **kw)
                events.append((name, before, note(obj)))
                return out

            monkeypatch.setattr(cls, name, wrapped)

        def seeding(sink):
            return sink._hv is not None and sink._hv.seeding

        spy(sink_cls, "_dispatch", seeding)
        spy(sink_cls, "_drain", seeding)
        spy(hv_cls, "end_seed", lambda hv: hv.seeding)
        spy(hv_cls, "join", lambda hv: (hv.seeding, hv.table_mode))

        corpus = _phased_corpus(tmp_path)
        got, stats = _stage_run(corpus, "stage-vocab-seed", dedup,
                                self.JOBS)
        assert got == self._want(corpus, dedup)
        dev = stats["device"]

        def at(name, before=None, after=None):
            return [i for i, (n, b, a) in enumerate(events)
                    if n == name and before in (None, b)
                    and after in (None, a)]

        (seed,) = at("join", after=(True, False))
        (ended,) = at("end_seed", before=True)
        assert [i for i in at("_dispatch", before=True) if seed < i < ended]
        assert [i for i in at("_drain", before=True) if seed < i < ended]
        joins = at("join", after=(False, True))
        assert len(joins) == dev["handoff_shared_joins"] >= 1
        assert min(joins) > ended
        assert dev["table_probe_tokens"] > 0

    @_DEDUP
    def test_faulted_job_retries_exactly(self, tmp_path, monkeypatch,
                                         dedup):
        """A transient fault at a job's table dispatch fails that
        attempt after it joined; its retry joins the same vocabulary,
        and the count stays exact."""
        monkeypatch.setattr(settings, "job_retries", 2)
        settings.faults = "device_dispatch:nth=3"
        corpus = _phased_corpus(tmp_path)
        got, stats = _stage_run(corpus, "stage-vocab-fault", dedup,
                                self.JOBS)
        want = self._want(corpus, dedup)
        assert got == want
        dev = stats["device"]
        assert stats["retries"] >= 1
        assert dev["handoff_degrades"] == 0
        (sv,) = self.made
        assert dev["handoff_slot_inserts"] == sv.nslots == len(want)
        # The failed attempt joined too.
        assert dev["handoff_shared_joins"] >= self.JOBS - 1

    @_DEDUP
    def test_one_job_degrades_while_others_go_on(self, tmp_path,
                                                 monkeypatch, dedup):
        """One job's table dispatch is refused: that job flushes its own
        counts to the spill path, the shared table stays, and the other
        jobs still register device partials.  The refused job is the
        first that joined the seeded vocabulary, at its first dispatch:
        it inserted nothing, so the table holds exactly the words of the
        other jobs' input."""
        import re
        import threading

        from dampr_tpu.ops import lower as ops_lower

        lock = threading.Lock()
        joiners, dispatched, refused = set(), set(), []
        windows = {}
        join = handoff_mod.HandoffVocab.join
        dispatch = handoff_mod.HandoffVocab._dispatch
        add = ops_lower.DeviceTokenFoldSink.add

        def record_join(hv):
            join(hv)
            if hv.table_mode:
                with lock:
                    joiners.add(hv)

        def refuse_first_joiner(hv, *a):
            with lock:
                refuse = hv in joiners and hv not in dispatched \
                    and not refused
                dispatched.add(hv)
                if refuse:
                    refused.append(hv)
            if refuse:
                return None
            return dispatch(hv, *a)

        def record_window(sink, win):
            with lock:
                windows.setdefault(sink._hv, []).append(bytes(win))
            return add(sink, win)

        monkeypatch.setattr(handoff_mod.HandoffVocab, "join", record_join)
        monkeypatch.setattr(handoff_mod.HandoffVocab, "_dispatch",
                            refuse_first_joiner)
        monkeypatch.setattr(ops_lower.DeviceTokenFoldSink, "add",
                            record_window)
        corpus = _phased_corpus(tmp_path)
        got, stats = _stage_run(corpus, "stage-vocab-degrade", dedup,
                                self.JOBS)
        assert got == self._want(corpus, dedup)
        dev = stats["device"]
        assert dev["handoff_degrades"] == 1
        assert dev["handoff_bytes"] > 0
        (victim,) = refused
        assert len(windows) == self.JOBS
        rx = re.compile(r"[^\w]+")
        others = set()
        for hv, wins in windows.items():
            if hv is not victim:
                for w in wins:
                    others.update(t for t in rx.split(w.decode().lower())
                                  if t)
        # The degrade did not reset the shared table, and none of the
        # refused job's words entered it after.
        (sv,) = self.made
        assert sv.tab_index is not None
        assert len(set(sv.slot_bytes)) == sv.nslots == len(others)
        assert sorted(sv.keys) == sorted(others)
        assert dev["handoff_slot_inserts"] == sv.nslots

    @_DEDUP
    def test_no_zero_count_key_in_a_partial(self, tmp_path, monkeypatch,
                                            dedup):
        """Each job's partials hold only the words its own accumulator
        counted, every one above zero, though its chip's vocabulary
        holds the other jobs' words too."""
        finalize = handoff_mod.HandoffVocab.finalize
        partials = []

        def spy(hv, store, n_partitions):
            blocks, mapping = finalize(hv, store, n_partitions)
            partials.append([(ref.get(), ref) for refs in mapping.values()
                             for ref in refs])
            return blocks, mapping

        monkeypatch.setattr(handoff_mod.HandoffVocab, "finalize", spy)
        corpus = _phased_corpus(tmp_path)
        got, stats = _stage_run(corpus, "stage-vocab-zero", dedup,
                                self.JOBS)
        assert got == self._want(corpus, dedup)
        (sv,) = self.made
        assert len(partials) == self.JOBS
        for job in partials:
            keys = [k for blk, _r in job for k in blk.keys]
            vals = [int(v) for blk, _r in job for v in blk.values]
            assert vals and min(vals) > 0
            assert len(keys) == len(set(keys))
            # A phase's own words are in one job's partials only.
            assert len(keys) < sv.nslots

    def test_concurrent_inserts_and_joins_lose_nothing(self):
        """More threads than cores insert overlapping words into one
        stage vocabulary and join it, the interpreter switching threads
        every microsecond: each word gets one slot that every thread
        agrees on, the insert count is the distinct words, and exactly
        one thread seeds while the others wait for it."""
        import sys
        import threading

        store = RunStore("stage-vocab-stress", budget=1 << 26)
        sv = handoff_mod.StageVocab(store)
        sv.attach(1 << 24)
        words = [("w%d" % i).encode() for i in range(3000)]
        n = 2 * (os.cpu_count() or 4)
        seen, seeded = [None] * n, []
        start = threading.Barrier(n)

        def job(t):
            start.wait()
            seeds = not sv.join(t)
            if seeds:
                seeded.append(t)
                sv.lookup_or_insert(words[:500])
                sv.end_seed(t)
            mine = [words[i] for i in
                    np.random.RandomState(t).permutation(len(words))]
            seen[t] = {}
            for a in range(0, len(mine), 7):
                slots, _n = sv.lookup_or_insert(mine[a:a + 7])
                seen[t].update(zip(mine[a:a + 7], slots.tolist()))

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=job, args=(t,))
                       for t in range(n)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(timeout=120)
            assert not any(th.is_alive() for th in threads)
        finally:
            sys.setswitchinterval(old)
            store.cleanup()
        assert len(seeded) == 1 and sv.seeded
        assert sv.nslots == len(set(sv.slot_bytes)) == len(words)
        assert store.handoff_slot_inserts == len(words)
        assert sorted(sv.bytes2slot.values()) == list(range(len(words)))
        for got in seen:
            assert all(sv.bytes2slot[w] == s for w, s in got.items())

    def test_back_to_back_runs_start_fresh(self, tmp_path):
        """Each ``run()`` builds its stage vocabulary anew: the second
        inserts every word again and seeds once again."""
        corpus = _phased_corpus(tmp_path)
        want = self._want(corpus, False)
        for i in range(2):
            got, stats = _stage_run(corpus, "stage-vocab-again", False,
                                    self.JOBS)
            assert got == want
            dev = stats["device"]
            assert len(self.made) == i + 1
            assert dev["handoff_slot_inserts"] == len(want)
            assert dev["handoff_shared_joins"] == self.JOBS - 1
        assert self.made[0] is not self.made[1]


class TestAccounting:
    def _blk(self, n=8192):
        ks = np.arange(n, dtype=np.int64) % 31
        vs = np.arange(n, dtype=np.int64) % 7
        return Block(ks, vs)

    def test_h2d_idempotent_on_reregistration(self):
        """The satellite fix: a device ref re-entered after a fallback
        must not double-count its h2d bytes — the charge is per actual
        transfer, armed where device_put happened."""
        old = settings.hbm_budget, settings.hbm_min_records
        settings.hbm_budget = 64 << 20
        settings.hbm_min_records = 1
        try:
            store = RunStore("handoff-h2d")
            ref = store.register(self._blk(), device=True)
            assert ref.is_device
            once = store.h2d_bytes
            assert once == ref.dev_bytes
            # fallback path re-enters the same (already-resident) ref
            store._enter_ref(ref)
            assert store.h2d_bytes == once, "h2d double-counted"
            store.cleanup()
        finally:
            settings.hbm_budget, settings.hbm_min_records = old

    def test_register_device_charges_hash_lanes_only(self):
        """from_device_lanes: the value lane never crossed the boundary
        (it was born on device), so only the uploaded hash lanes count
        as h2d, and the bytes land in handoff_bytes."""
        import jax

        old = settings.hbm_budget
        settings.hbm_budget = 64 << 20
        try:
            store = RunStore("handoff-dev-reg")
            store.handoff_active = True
            n = 1024
            keys = np.empty(n, dtype=object)
            keys[:] = ["k%d" % i for i in range(n)]
            h1 = np.arange(n, dtype=np.uint32)
            h2 = np.arange(n, dtype=np.uint32)[::-1].copy()
            dev_v = jax.device_put(np.ones(n, dtype=np.int64))
            dev_h1 = jax.device_put(h1)
            dev_h2 = jax.device_put(h2)
            ref = BlockRef.from_device_lanes(
                keys, h1, h2, dev_v, dev_h1, dev_h2, store=store,
                value_dtype=np.int64, lane_abs=n, lane_min=1,
                h2d_bytes=h1.nbytes + h2.nbytes)
            store.register_device(ref)
            assert store.h2d_bytes == h1.nbytes + h2.nbytes
            assert store.handoff_bytes == ref.dev_bytes
            # re-entry after a fallback: still no double count
            store._enter_ref(ref)
            assert store.h2d_bytes == h1.nbytes + h2.nbytes
            got = ref.get()
            assert list(got.keys) == list(keys)
            assert got.values.dtype == np.int64
            store.cleanup()
        finally:
            settings.hbm_budget = old


class TestCompaction:
    def test_compact_partial_preserves_live_rows(self):
        """The mesh-fold refold compaction: live (h1, h2, v) rows survive
        a compaction byte-for-byte; dead pad is dropped to a pow2
        bound."""
        import jax

        from dampr_tpu.parallel.shuffle import compact_partial

        rng = np.random.RandomState(9)
        n = 4096
        h1 = rng.randint(0, 2 ** 32, size=n, dtype=np.uint64).astype(
            np.uint32)
        h2 = rng.randint(0, 2 ** 32, size=n, dtype=np.uint64).astype(
            np.uint32)
        v = rng.randint(0, 100, size=n).astype(np.int32)
        ok = np.zeros(n, dtype=np.uint32)
        live_idx = rng.choice(n, size=300, replace=False)
        ok[live_idx] = 1
        part = tuple(jax.device_put(x) for x in (h1, h2, v, ok))
        ch1, ch2, cv, cok = compact_partial(part)
        assert int(ch1.shape[0]) == 512  # pow2 bound over 300 live
        m = np.asarray(cok) == 1
        assert m.sum() == 300
        got = set(zip(np.asarray(ch1)[m].tolist(),
                      np.asarray(ch2)[m].tolist(),
                      np.asarray(cv)[m].tolist()))
        want = set(zip(h1[live_idx].tolist(), h2[live_idx].tolist(),
                       v[live_idx].tolist()))
        assert got == want

    def test_compact_partial_noop_when_dense(self):
        import jax

        from dampr_tpu.parallel.shuffle import compact_partial

        n = 64
        part = tuple(jax.device_put(x) for x in (
            np.arange(n, dtype=np.uint32),
            np.arange(n, dtype=np.uint32),
            np.ones(n, dtype=np.int32),
            np.ones(n, dtype=np.uint32)))
        out = compact_partial(part)
        assert out is part  # all live: nothing to shrink


class TestDoctor:
    def _summary(self, declined=True, verdict="transfer",
                 kind="settings"):
        edge = {"src": 1, "dst": 2, "handoff": "spill", "kind": kind,
                "reason": "handoff off (settings.handoff='off'; hbm "
                          "budget 0 on this backend)"}
        return {
            "run": "handoff-doc", "wall_seconds": 10.0,
            "stages": [{"stage": 1, "kind": "map", "target": "device",
                        "seconds": 8.0}],
            "plan": {"lowering": {"enabled": True,
                                  "handoff": [edge] if declined else []}},
            "device": {"handoff_edges": 0, "handoff_degrades": 0},
            "critpath": {
                "source": "spans",
                "run": {"verdict": verdict,
                        "fractions": {verdict: 0.6}},
                "stages": [{"stage": 1, "kind": "map",
                            "seconds": 8.0, "verdict": verdict,
                            "fractions": {verdict: 0.7}}],
            },
        }

    def test_declined_edge_maps_to_budget_knobs(self, tmp_path,
                                                monkeypatch):
        import json

        monkeypatch.setattr(settings, "scratch_root", str(tmp_path))
        rundir = tmp_path / "handoff-doc" / "trace"
        rundir.mkdir(parents=True)
        with open(str(rundir / "stats.json"), "w") as f:
            json.dump(self._summary(), f)
        report = doctor.diagnose(str(tmp_path / "handoff-doc"))
        hand = [x for x in report["findings"]
                if x["bottleneck"] == "handoff"]
        assert hand, report["findings"]
        knobs = {s["setting"] for s in hand[0]["suggestions"]}
        assert "handoff" in knobs
        assert "hbm_budget" in knobs
        assert "lower_min_records" in knobs
        assert "declined" in hand[0]["evidence"]

    def test_unactionable_declines_emit_no_finding(self, tmp_path,
                                                   monkeypatch):
        """An object-lane edge has no device tier to buy and a priced
        decline is the cost model already choosing the faster path —
        neither should page the operator at the budget knobs."""
        import json

        monkeypatch.setattr(settings, "scratch_root", str(tmp_path))
        for kind in ("object-lane", "priced"):
            name = "handoff-doc-%s" % kind
            rundir = tmp_path / name / "trace"
            rundir.mkdir(parents=True)
            s = self._summary(kind=kind)
            s["run"] = name
            with open(str(rundir / "stats.json"), "w") as f:
                json.dump(s, f)
            report = doctor.diagnose(str(tmp_path / name))
            assert not [x for x in report["findings"]
                        if x["bottleneck"] == "handoff"], kind

    def test_no_finding_without_transfer_verdict(self, tmp_path,
                                                 monkeypatch):
        import json

        monkeypatch.setattr(settings, "scratch_root", str(tmp_path))
        rundir = tmp_path / "handoff-doc2" / "trace"
        rundir.mkdir(parents=True)
        with open(str(rundir / "stats.json"), "w") as f:
            json.dump(self._summary(verdict="codec"), f)
        report = doctor.diagnose(str(tmp_path / "handoff-doc2"))
        assert not [x for x in report["findings"]
                    if x["bottleneck"] == "handoff"]

    def test_playbook_knobs_exist(self):
        for knob, _env, _prop, why in doctor._PLAYBOOK["handoff"]:
            assert hasattr(settings, knob)
            assert why


class TestObservability:
    def test_stats_trace_and_explain_surfaces(self, tmp_path):
        corpus = _corpus(tmp_path)
        settings.handoff = "on"
        old_trace, old_dir = settings.trace, settings.trace_dir
        settings.trace = True
        settings.trace_dir = str(tmp_path / "traces")
        try:
            got, stats = _docfreq(corpus, "handoff-traced")
        finally:
            settings.trace, settings.trace_dir = old_trace, old_dir
        dev = stats["device"]
        assert dev["handoff_edges"] >= 1
        assert dev["handoff_bytes"] > 0
        assert dev["d2h_avoided_bytes"] > 0
        assert 0 < dev["table_probe_hits"] <= dev["table_probe_tokens"]
        assert dev["table_index_unplaced"] >= 0
        spans = stats.get("spans") or {}
        assert "handoff" in spans, spans
        # schema-valid trace including the handoff spans
        import subprocess
        import sys

        root = os.path.dirname(os.path.dirname(os.path.abspath(
            __file__)))
        res = subprocess.run(
            [sys.executable,
             os.path.join(root, "tools", "validate_trace.py"),
             stats["trace_file"],
             "--schema", os.path.join(root, "docs",
                                      "trace_schema.json"),
             "--require-cats", "handoff,stage"],
            capture_output=True, text=True)
        assert res.returncode == 0, res.stdout + res.stderr

    def test_history_records_handoff_evidence(self, tmp_path,
                                              monkeypatch):
        from dampr_tpu.obs import history

        monkeypatch.setattr(settings, "scratch_root", str(tmp_path))
        corpus = _corpus(tmp_path)
        settings.handoff = "on"
        got, stats = _docfreq(corpus, "handoff-hist")
        recs = history.load("handoff-hist")
        assert recs
        h = recs[-1].get("handoff") or {}
        assert h.get("edges", 0) >= 1
        assert h.get("bytes", 0) > 0
        assert "handoff" in (recs[-1].get("settings") or {})
