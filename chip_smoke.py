#!/usr/bin/env python3
"""Chip smoke: the engine's main path, once, on a real TPU.

Runs through the public DSL (``Dampr.text(...) ... .run()`` -> plan
lowering -> the jitted device programs -> fold -> sink), each pipeline
twice in one process (cold, then warm), over a generated Zipf corpus
(``bench_tfidf.corpus_text``, the Shakespeare shape) of ``--mb`` MiB:

  (a) TF-IDF doc-freq, exactly ``bench_tfidf.run_dampr_tpu``;
  (b) word count, ``TokenCounts`` feeding a keyed fold (``fold_values``);
  (c) a certified numeric chain over ``Dampr.memory`` that the HBM tier
      carries to its fold.

Every result is compared exactly with a plain-Python oracle, computed in
a process pool started before this process touches JAX (only this
process may hold the chip).  The run fails unless each pipeline ran
lowered on the device and nothing quietly fell back to the host.

``--chips 4`` runs only the mesh path instead: word count and a
group_by + reduce-side join with the mesh fold/exchange in force, the
same pipelines with both off, and the oracle.

Finding no TPU is an error.  The last line of standard output is one
JSON object, ``{"ok": true, "device": {...}}``, printed only on success.
"""

import argparse
import json
import multiprocessing
import operator
import os
import pickle
import re
import shutil
import sys
import tempfile
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
SMOKE_DIR = os.path.join(ROOT, ".smoke")
RX = re.compile(r"[^\w]+")
SHARDS = 32
#: The numeric chain (c): map, then count per key.
NUM_KEYS = 4099
JOIN_KEYS = 1000


def num_map(x):
    return x * 3 + 1


def num_key(v):
    return v % NUM_KEYS


class SmokeFailure(Exception):
    pass


def log(msg):
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# Corpus and oracles: pool workers, no JAX
# ---------------------------------------------------------------------------


def _shard(task):
    """Write one corpus shard and return its oracles: per-line document
    frequency and word counts of ``[^\\w]+``-split lowercased tokens (the
    reference baseline's rules, ``bench_tfidf.run_baseline``) and the
    shard's line count."""
    import numpy as np

    from dampr_tpu.bench_tfidf import corpus_text

    seed, idx, nbytes, path = task
    text = corpus_text(np.random.RandomState([seed, idx]), nbytes)
    with open(path, "w") as f:
        f.write(text)
    low = text.lower()
    words = Counter(RX.split(low))
    words.pop("", None)
    df = Counter()
    lines = low.split("\n")[:-1]  # every shard ends with a newline
    for line in lines:
        df.update(t for t in set(RX.split(line)) if t)
    return df, words, len(lines)


def _num_oracle(n):
    return Counter(num_key(num_map(x)) for x in range(n))


def _join_data(n):
    left = [(i % JOIN_KEYS, i) for i in range(n)]
    right = [(i % (JOIN_KEYS + 7), -i) for i in range(n // 4)]
    return left, right


def _join_oracle(n):
    left, right = _join_data(n)
    lg, rg = {}, {}
    for k, v in left:
        lg.setdefault(k, []).append(v)
    for k, v in right:
        rg.setdefault(k, []).append(v)
    joined = {k: (len(lg[k]), sum(rg[k])) for k in lg if k in rg}
    grouped = {k: max(vs) for k, vs in lg.items()}
    return joined, grouped


class Corpus(object):
    """The corpus file and its oracles, cached beside it keyed by seed and
    size.  ``start`` launches the generation in a spawn pool; ``wait``
    collects it.  Both run before anything in this process touches JAX
    (``start``) or touch none of it (``wait``)."""

    def __init__(self, seed, mb, records, join_records):
        self.dir = os.path.join(SMOKE_DIR, "corpus-s{}-{}mb".format(seed, mb))
        self.path = os.path.join(self.dir, "corpus.txt")
        self.key = (seed, mb, SHARDS, records, join_records)
        self.seed, self.mb = seed, mb
        self.records, self.join_records = records, join_records
        self.oracle = None
        self._pool = None
        self._pending = None

    def start(self):
        cache = os.path.join(self.dir, "oracle.pkl")
        try:
            with open(cache, "rb") as f:
                key, oracle = pickle.load(f)
            if key == self.key and os.path.exists(self.path):
                self.oracle = oracle
                log("corpus: cached {} ({} MiB, seed {})".format(
                    self.path, self.mb, self.seed))
                return
        except (OSError, EOFError, pickle.UnpicklingError, ValueError):
            pass
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        per = self.mb * 1024 ** 2 // SHARDS
        tasks = [(self.seed, i, per, os.path.join(self.dir, "s%03d" % i))
                 for i in range(SHARDS)]
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(min(SHARDS, os.cpu_count() or 1))
        self._pending = (
            self._pool.map_async(_shard, tasks),
            self._pool.apply_async(_num_oracle, (self.records,)),
            self._pool.apply_async(_join_oracle, (self.join_records,)),
            tasks)

    def abort(self):
        if self._pool is not None:
            self._pool.terminate()
            self._pool.join()
            self._pool = None

    def wait(self):
        if self.oracle is not None:
            return self.oracle
        t0 = time.time()
        shards_r, num_r, join_r, tasks = self._pending
        try:
            shards = shards_r.get()
            num, (joined, grouped) = num_r.get(), join_r.get()
        finally:
            self._pool.close()
            self._pool.join()
            self._pool = None
        df, words, lines = Counter(), Counter(), 0
        with open(self.path, "wb") as out:
            for (sdf, swords, slines), task in zip(shards, tasks):
                df.update(sdf)
                words.update(swords)
                lines += slines
                with open(task[3], "rb") as f:
                    shutil.copyfileobj(f, out, 16 << 20)
                os.remove(task[3])
        self.oracle = {"df": df, "words": words, "lines": lines,
                       "num": num, "joined": joined, "grouped": grouped}
        with open(os.path.join(self.dir, "oracle.pkl"), "wb") as f:
            pickle.dump((self.key, self.oracle), f,
                        protocol=pickle.HIGHEST_PROTOCOL)
        log("corpus: {} ({} bytes, {} lines, {} distinct words), corpus "
            "and oracles in {:.1f}s".format(
                self.path, os.path.getsize(self.path), lines, len(words),
                time.time() - t0))
        return self.oracle


# ---------------------------------------------------------------------------
# Compile accounting (jax.monitoring)
# ---------------------------------------------------------------------------


class CompileMeter(object):
    """Counts backend compiles (persistent-cache retrievals included),
    their seconds, and persistent-cache hits, from JAX's own events."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.hits = 0
        self.by_name = {}  # program name -> [compiles, seconds]
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def _duration(self, event, secs, fun_name="?", **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += secs
            entry = self.by_name.setdefault(fun_name, [0, 0.0])
            entry[0] += 1
            entry[1] += secs

    def top(self, k=8):
        ranked = sorted(self.by_name.items(), key=lambda kv: -kv[1][1])
        return ", ".join("{} x{} {:.3f}s".format(name, n, secs)
                         for name, (n, secs) in ranked[:k])

    def snapshot(self):
        return (self.compiles, self.seconds, self.hits)


# ---------------------------------------------------------------------------
# Pipelines
# ---------------------------------------------------------------------------


def _chunk_size(corpus):
    return os.path.getsize(corpus) // multiprocessing.cpu_count() + 1


def run_tfidf(corpus, oracle, workdir):
    from dampr_tpu import bench_tfidf

    outdir = os.path.join(workdir, "tfidf-out")
    _secs, stats = bench_tfidf.run_dampr_tpu(corpus, outdir)
    n = bench_tfidf.check_result(outdir, oracle["df"], oracle["lines"])
    return stats, "{} idf entries".format(n)


def wordcount(corpus):
    from dampr_tpu import Dampr
    from dampr_tpu.ops.text import TokenCounts

    # fold_values is fold_by over the scanner's own key (the token): the
    # form the plan lowers.  A re-keying fold_by(lambda kv: kv[0], ...)
    # keeps the scan on the host.
    return (Dampr.text(corpus, _chunk_size(corpus))
            .custom_mapper(TokenCounts(mode="word", lower=True,
                                       pair_values=False))
            .fold_values(operator.add))


def _read(pipe, name):
    em = pipe.run(name=name)
    try:
        return list(em.read()), em.stats()
    finally:
        em.delete()


def _expect(cond, what):
    if not cond:
        raise SmokeFailure(what)


def run_wordcount(corpus, oracle):
    got, stats = _read(wordcount(corpus), "smoke-wc")
    _expect(dict(got) == oracle["words"] and len(got) == len(oracle["words"]),
            "word counts differ from the oracle")
    return stats, "{} words".format(len(got))


def run_numeric(n, oracle):
    from dampr_tpu import Dampr

    pipe = (Dampr.memory(list(range(n)), partitions=64)
            .map(num_map).count(num_key))
    got, stats = _read(pipe, "smoke-num")
    _expect(dict(got) == oracle["num"] and len(got) == len(oracle["num"]),
            "numeric counts differ from the oracle")
    return stats, "{} keys over {} records".format(len(got), n)


def _check_device(name, stats, handoff=False):
    dev = stats["device"]
    log("  {} device: lowered={} device_stages={} handoff_edges={} "
        "h2d_bytes={} d2h_bytes={} handoff_degrades={} "
        "lower_fallbacks={}".format(
            name, dev["lowered"], dev["device_stages"],
            dev["handoff_edges"], dev["h2d_bytes"], dev["d2h_bytes"],
            dev["handoff_degrades"], dev["lower_fallbacks"]))
    _expect(dev["lowered"], name + ": lowering not in force")
    _expect(dev["device_stages"] >= 1, name + ": no stage ran on device")
    _expect(not handoff or dev["handoff_edges"] >= 1,
            name + ": no device-resident handoff edge")
    _expect(dev["h2d_bytes"] > 0, name + ": nothing was fed to the device")
    _expect(dev["handoff_degrades"] == 0,
            name + ": a handoff edge degraded to the spill path")
    _expect(dev["lower_fallbacks"] == 0,
            name + ": a lowered batch fell back to the host grouping")


def _timed(meter, fn):
    c0, s0, h0 = meter.snapshot()
    t0 = time.time()
    result = fn()
    wall = time.time() - t0
    c1, s1, h1 = meter.snapshot()
    return result, wall, (c1 - c0, s1 - s0, h1 - h0)


def one_chip(corpus, oracle, args, meter, workdir):
    phases = [
        ("tfidf", lambda: run_tfidf(corpus, oracle, workdir),
         {"handoff": True}),
        ("wordcount", lambda: run_wordcount(corpus, oracle), {}),
        ("numeric", lambda: run_numeric(args.records, oracle), {}),
    ]
    failed = []
    for name, fn, req in phases:
        for leg in ("cold", "warm"):
            try:
                (stats, what), wall, (nc, cs, hits) = _timed(meter, fn)
                log("{} {}: {:.3f}s wall, exact vs oracle ({}); {} compiles "
                    "in {:.3f}s, {} persistent-cache hits".format(
                        name, leg, wall, what, nc, cs, hits))
                _check_device("{} {}".format(name, leg), stats, **req)
            except Exception as e:  # noqa: BLE001 - report every phase
                log("{} {}: FAILED: {!r}".format(name, leg, e))
                failed.append("{} {}".format(name, leg))
    return failed


def four_chips(corpus, oracle, args, meter, workdir):
    """The mesh path and its host-shuffle twin, nothing else."""
    import jax

    from dampr_tpu import Dampr, settings
    from dampr_tpu.parallel import exchange as px

    n_dev = len(jax.devices())

    left, right = _join_data(args.join_records)

    def joined():
        lp = Dampr.memory(left, partitions=16).group_by(lambda x: x[0])
        rp = Dampr.memory(right, partitions=16).group_by(lambda x: x[0])
        return lp.join(rp).reduce(
            lambda l, r: (len(list(l)), sum(v for _k, v in r)))

    def grouped():
        return (Dampr.memory(left, partitions=16).group_by(lambda x: x[0])
                .reduce(lambda k, vs: max(v for _k, v in vs)))

    sent0 = dict(px.sent_bytes_per_device)
    recv0 = dict(px.received_bytes_per_device)
    results, failed = {}, []
    for mode in ("mesh", "host"):
        if mode == "host":
            settings.mesh_fold = settings.mesh_exchange = "off"
        folds = exchanges = 0
        for name, build, want in (
                ("wordcount", lambda: wordcount(corpus), oracle["words"]),
                ("join", joined, oracle["joined"]),
                ("group_by", grouped, oracle["grouped"])):
            try:
                (got, stats), wall, (nc, cs, _h) = _timed(
                    meter, lambda: _read(build(), "smoke4-" + name))
                got = dict(got)
                _expect(got == want, name + " differs from the oracle")
                results[(mode, name)] = got
                folds += stats["mesh"]["folds"]
                exchanges += stats["mesh"]["exchanges"]
                log("{} {}: {:.3f}s wall, exact vs oracle ({} keys); mesh "
                    "folds {} exchanges {} ({} bytes); {} compiles in "
                    "{:.3f}s".format(mode, name, wall, len(got),
                                     stats["mesh"]["folds"],
                                     stats["mesh"]["exchanges"],
                                     stats["mesh"]["exchange_bytes"], nc, cs))
            except Exception as e:  # noqa: BLE001 - report every phase
                log("{} {}: FAILED: {!r}".format(mode, name, e))
                failed.append("{} {}".format(mode, name))
        if mode == "mesh":
            sent = [px.sent_bytes_per_device.get(d, 0) - sent0.get(d, 0)
                    for d in range(n_dev)]
            recv = [px.received_bytes_per_device.get(d, 0) - recv0.get(d, 0)
                    for d in range(n_dev)]
            log("mesh: folds {} exchanges {}; bytes sent per device {}, "
                "received per device {}".format(folds, exchanges, sent,
                                                recv))
            if folds < 1 or exchanges < 1:
                failed.append("mesh path did not engage")
            if not all(s > 0 for s in sent) or not all(r > 0 for r in recv):
                failed.append("a device sent or received no bytes")
    for name in ("wordcount", "join", "group_by"):
        if (("mesh", name) in results and ("host", name) in results
                and results[("mesh", name)] != results[("host", name)]):
            failed.append(name + ": mesh and host shuffles differ")
    return failed


# ---------------------------------------------------------------------------
# Main
# ---------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mb", type=int, default=1024,
                    help="corpus size in MiB (default 1024)")
    ap.add_argument("--records", type=int, default=1 << 22,
                    help="records in the numeric chain (c)")
    ap.add_argument("--join-records", type=int, default=1 << 20,
                    help="left-side records of the --chips 4 join")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path on a four-chip host")
    return ap.parse_args(argv)


def smoke(args, corpus, dev0):
    """Everything after the device check; returns the failed phases."""
    import jax

    from dampr_tpu import native, settings

    log("compile cache: {}".format(jax.config.jax_compilation_cache_dir))
    oracle = corpus.wait()
    os.makedirs(SMOKE_DIR, exist_ok=True)
    workdir = tempfile.mkdtemp(prefix="run-", dir=SMOKE_DIR)
    # A fresh scratch root per invocation: run history and fault records
    # from an earlier run must not steer stages back to the host.
    settings.scratch_root = os.environ["DAMPR_TPU_SCRATCH"] = os.path.join(
        workdir, "scratch")
    codec = native.get_lib() is not None
    log("native codec loaded: {}".format(codec))
    meter = CompileMeter()
    t0 = time.time()
    try:
        run = four_chips if args.chips == 4 else one_chip
        failed = run(corpus.path, oracle, args, meter, workdir)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    if not codec:
        failed.append("the native codec did not load")
    log("total: {:.3f}s, {} compiles in {:.3f}s, {} persistent-cache "
        "hits".format(time.time() - t0, *meter.snapshot()))
    log("compile seconds by program: " + meter.top())
    try:
        peak = dev0.memory_stats()["peak_bytes_in_use"]
    except (TypeError, KeyError):
        peak = "not reported"
    log("peak_bytes_in_use (device 0): {}".format(peak))
    if failed:
        log("FAILED: " + "; ".join(failed))
    return failed


def main(argv=None):
    args = parse_args(argv)
    corpus = Corpus(args.seed, args.mb, args.records, args.join_records)
    corpus.start()  # the pool starts before JAX is touched

    import dampr_tpu  # noqa: F401 - places the compile cache first
    import jax

    devs = jax.devices()
    dev0 = devs[0]
    if dev0.platform != "tpu" or len(devs) < args.chips:
        corpus.abort()
        print("chip_smoke: needs {} TPU device(s), JAX found {} {} "
              "device(s)".format(args.chips, len(devs), dev0.platform),
              file=sys.stderr)
        return 1
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devs)}
    log("device: platform={platform} kind={kind} count={count}".format(
        **device))
    if smoke(args, corpus, dev0):
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
