"""Benchmark: the reference's headline TF-IDF workload (benchmarks/run.sh +
tf-idf-dampr.py) on dampr_tpu, vs the reference's own single-core CPU
baseline shape (benchmarks/baseline.py).

Workload (identical to reference tf-idf-dampr.py:9-21): per line, document
frequency of lowercased ``[^\\w]+``-split tokens; then idf = log(1 + total/df)
via a broadcast cross with the corpus line count; sunk as TSV.

Baseline (identical to reference benchmarks/baseline.py:12-24): single-core
Python ``Counter`` over per-line token sets, writing the same TSV.  (Both
sides drop the empty-string pseudo-token re.split emits at line edges.)

Corpus: deterministic synthetic Zipf text (the reference uses duplicated
Shakespeare; this container has no corpus and zero egress).  Size via
DAMPR_BENCH_MB (default 64).

Runs from the repo root (``python bench.py``, the driver hook) or the
installed console script ``dampr-tpu-bench``.  Prints ONE JSON line:
  {"metric": "tfidf_docfreq_throughput", "value": <MB/s>, "unit": "MB/s",
   "vs_baseline": <ours / single-core-baseline>}
"""

import json
import math
import multiprocessing
import operator
import os
import re
import shutil
import sys
import time
from collections import Counter

BENCH_DIR = os.environ.get("DAMPR_BENCH_DIR", "/tmp/dampr_tpu_bench")
BENCH_MB = int(os.environ.get("DAMPR_BENCH_MB", "128"))

RX = re.compile(r"[^\w]+")


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def corpus_text(rng, nbytes):
    """At least ``nbytes`` of deterministic Zipf-ish text drawn from
    ``rng`` (a ``numpy.random.RandomState``): a ~24k-word vocabulary,
    8-12 tokens per line (the Shakespeare corpus shape: 5.3MB, 23,903
    unique words)."""
    import numpy as np

    vocab_n = 24000
    vocab = np.array(["w%04x" % i if i > 200 else "t%d" % i
                      for i in range(vocab_n)], dtype=object)
    # Zipf ranks: common words dominate like natural text
    probs = 1.0 / np.arange(1, vocab_n + 1) ** 1.1
    probs /= probs.sum()
    parts = []
    written = 0
    while written < nbytes:
        ids = rng.choice(vocab_n, size=(20000,), p=probs)
        lens = rng.randint(8, 13, size=2000)
        pos = 0
        out = []
        for L in lens:
            out.append(" ".join(vocab[ids[pos:pos + L]]))
            pos += L
            if pos + 13 > len(ids):
                break
        chunk = "\n".join(out) + "\n"
        parts.append(chunk)
        written += len(chunk)
    return "".join(parts)


def make_corpus(path, mb, seed=1234):
    """Write ``mb`` MiB of :func:`corpus_text` drawn from ``seed`` to
    ``path`` (kept when a file of that size is already there)."""
    import numpy as np

    if os.path.exists(path) and os.path.getsize(path) >= mb * 1024 ** 2:
        return
    os.makedirs(os.path.dirname(path), exist_ok=True)
    text = corpus_text(np.random.RandomState(seed), mb * 1024 ** 2)
    with open(path, "w") as f:
        f.write(text)
    log("corpus: {} ({:.1f} MB)".format(path, len(text) / 1e6))


def run_baseline(corpus, outdir):
    """Reference benchmarks/baseline.py, verbatim shape: single core.

    The measured time and result are cached next to the corpus keyed on its
    (size, mtime): the baseline is deterministic and costs ~30 min at the
    10 GB tier, so re-measuring OUR side must not re-pay it.  Set
    DAMPR_BENCH_FRESH_BASELINE=1 to force a fresh baseline run."""
    import pickle

    st = os.stat(corpus)
    cache = corpus + ".baseline.pkl"
    if not os.environ.get("DAMPR_BENCH_FRESH_BASELINE"):
        try:
            with open(cache, "rb") as f:
                key, secs, counter, total = pickle.load(f)
            if key == (st.st_size, st.st_mtime_ns):
                log("baseline: cached measurement ({:.2f}s)".format(secs))
                return secs, counter, total
        except (OSError, ValueError, EOFError, pickle.UnpicklingError):
            pass
    if os.path.isdir(outdir):
        shutil.rmtree(outdir)
    os.makedirs(outdir)
    t0 = time.time()
    with open(corpus) as f:
        counter = Counter()
        num_rows = 0
        for num_rows, line in enumerate(f):
            counter.update(t for t in set(RX.split(line.lower())) if t)
        total = num_rows + 1
    with open(os.path.join(outdir, "out"), "w") as out:
        for word, count in counter.items():
            print("\t".join((word, str(count),
                             str(math.log(1 + float(total) / count)))),
                  file=out)
    secs = time.time() - t0
    try:
        with open(cache, "wb") as f:
            pickle.dump(((st.st_size, st.st_mtime_ns), secs, counter, total),
                        f, protocol=pickle.HIGHEST_PROTOCOL)
    except OSError:
        pass
    return secs, counter, total


def run_dampr_tpu(corpus, outdir):
    """Reference tf-idf-dampr.py shape on the new engine: vectorized DocFreq
    map (native tokenize+count), device-capable fold, broadcast idf join,
    TSV sink."""
    from dampr_tpu import Dampr
    from dampr_tpu.ops.text import DocFreq

    if os.path.isdir(outdir):
        shutil.rmtree(outdir)

    chunk_size = os.path.getsize(corpus) // multiprocessing.cpu_count() + 1
    t0 = time.time()
    docs = Dampr.text(corpus, chunk_size)
    # pair_values=False + fold_values: blocks keep their token keys, cached
    # hash lanes, and a numeric count column end-to-end — zero per-record
    # Python between the native tokenizer and the (device-eligible) fold
    doc_freq = (docs.custom_mapper(
        DocFreq(mode="word", lower=True, pair_values=False))
        .fold_values(operator.add))
    idf = doc_freq.cross_right(
        docs.len(),
        lambda df, total: (df[0], df[1],
                           math.log(1 + (float(total) / df[1]))),
        memory=True)
    em = idf.sink_tsv(outdir).run(name="bench-tfidf")
    secs = time.time() - t0
    return secs, em.stats()


def lint_pipelines():
    """dampr-tpu-lint discovery hook: the benchmark's pipeline shape
    (constructed over this source file; nothing runs)."""
    from dampr_tpu import Dampr
    from dampr_tpu.ops.text import DocFreq

    docs = Dampr.text(__file__, 1024 ** 2)
    doc_freq = (docs.custom_mapper(
        DocFreq(mode="word", lower=True, pair_values=False))
        .fold_values(operator.add))
    idf = doc_freq.cross_right(
        docs.len(),
        lambda df, total: (df[0], df[1],
                           math.log(1 + (float(total) / df[1]))),
        memory=True)
    return [("bench_tfidf", idf.sink_tsv("/tmp/dampr_tpu_lint_idfs"))]


def check_result(outdir, counter, total):
    got = {}
    for part in sorted(os.listdir(outdir)):
        with open(os.path.join(outdir, part)) as f:
            for line in f:
                w, c, idf = line.rstrip("\n").split("\t")
                got[w] = (int(c), float(idf))
    want = {w: (c, math.log(1 + float(total) / c))
            for w, c in counter.items()}
    assert set(got) == set(want), (
        "token sets differ: {} extra, {} missing".format(
            len(set(got) - set(want)), len(set(want) - set(got))))
    for w, (c, i) in want.items():
        gc, gi = got[w]
        assert gc == c, (w, gc, c)
        assert abs(gi - i) < 1e-9, (w, gi, i)
    return len(got)


def main():
    corpus = os.path.join(BENCH_DIR, "corpus_{}mb.txt".format(BENCH_MB))
    make_corpus(corpus, BENCH_MB)
    size_mb = os.path.getsize(corpus) / 1e6

    base_secs, counter, total = run_baseline(
        corpus, os.path.join(BENCH_DIR, "baseline-idf"))
    log("baseline (1 core): {:.2f}s = {:.1f} MB/s".format(
        base_secs, size_mb / base_secs))

    from dampr_tpu import settings as _trace_settings

    # Every bench run under one name would overwrite one trace dir, so the
    # reported artifact paths could belong to a different trial than the
    # reported (winning) numbers; give each run its own directory instead.
    old_trace_dir = _trace_settings.trace_dir
    # try/finally: a failed trial must not leave the process-global
    # trace_dir pointed at the bench scratch (main() runs in-process via
    # the bench.py driver hook; later traced runs would litter it).
    try:
        if _trace_settings.trace:
            _trace_settings.trace_dir = os.path.join(
                BENCH_DIR, "traces", "cold")
        ours_dir = os.path.join(BENCH_DIR, "dampr-idf")
        cold, _cold_summary = run_dampr_tpu(corpus, ours_dir)
        log("dampr_tpu cold: {:.2f}s".format(cold))
        # warm steady-state: best of two runs (this box time-shares one
        # core with unrelated tenants; a single sample is noise-prone),
        # with the wall-time split (device kernels / transfers / native
        # codec) taken from the winning run.  Epoch/delta snapshots (not
        # reset()) keep the accounting run-scoped: another in-flight
        # run's counters are never clobbered by this bench.
        from dampr_tpu.ops import devtime

        tune_section = None
        if _trace_settings.autotune_enabled():
            # Closed-loop bench tuning (settings.autotune, docs/tuning.md):
            # the warm trials become an in-process autotune session — each
            # trial re-measures under one model/playbook-suggested knob
            # vector, the winner must be byte-identical (output-dir
            # digest), and its vector persists to tuned.json so the next
            # fit sees a measured value for every explored knob.
            from dampr_tpu.obs import autotune as _autotune

            def _measure():
                epoch = devtime.epoch()
                t, summary = run_dampr_tpu(corpus, ours_dir)
                return t, (t, devtime.delta(epoch), summary)

            best, tune_report = _autotune.tune_settings_session(
                _measure, "bench-tfidf",
                digest_of=lambda _res: _autotune.dir_digest(ours_dir),
                out=log)
            tune_section = tune_report["autotune"]
            log("autotune: {:.2f}x over the baseline config (winner "
                "trial {} {}, byte_identical={})".format(
                    tune_section["improvement"],
                    tune_section["winner"]["trial"],
                    tune_section["winner"]["knobs"] or "baseline",
                    tune_section["byte_identical"]))
        else:
            best = None
        for trial in (() if best is not None else range(2)):
            if _trace_settings.trace:
                _trace_settings.trace_dir = os.path.join(
                    BENCH_DIR, "traces", "trial-{}".format(trial))
            epoch = devtime.epoch()
            t, summary = run_dampr_tpu(corpus, ours_dir)
            split = devtime.delta(epoch)
            tio = summary.get("io", {})
            trial_line = ("trial {}: {:.2f}s  spill {:.1f} MB  "
                          "merge-gens {}  io w {:.0f}/r {:.0f} MB/s  "
                          "io_wait {:.1%}".format(
                              trial, t,
                              summary.get("store", {}).get("spilled_bytes",
                                                           0) / 1e6,
                              summary.get("store", {}).get("merge_gens", 0),
                              tio.get("spill_write_mbps", 0.0),
                              tio.get("spill_read_mbps", 0.0),
                              tio.get("io_wait_fraction", 0.0)))
            sampler = summary.get("metrics", {}).get("sampler", {})
            if sampler.get("samples"):
                trial_line += "  sampler {}x @{}ms ovh {:.2%}".format(
                    sampler["samples"], sampler.get("interval_ms", 0),
                    sampler.get("overhead", 0.0))
            if summary.get("trace_file"):
                trial_line += "  trace {}".format(summary["trace_file"])
            log(trial_line)
            if best is None or t < best[0]:
                best = (t, split, summary)
    finally:
        _trace_settings.trace_dir = old_trace_dir
    secs, split, summary = best
    log("dampr_tpu warm: {:.2f}s = {:.1f} MB/s".format(secs, size_mb / secs))
    # Non-overlapped codec seconds: the codec time still on the critical
    # path.  With the overlap executor off every codec second blocks the
    # job thread that could otherwise fold (serial interleave), so it is
    # the whole codec bucket; with overlap on it shrinks to the measured
    # wall-clock union of intervals where EVERY live map slot was blocked
    # on its codec — codec time no fold anywhere could cover (devtime
    # "codec_wait").
    from dampr_tpu import settings as _settings

    overlapped = _settings.overlap_windows > 0
    codec_nonov = split["codec_wait"] if overlapped else split["codec"]
    log("wall split: device {:.2f}s, transfer {:.2f}s, codec {:.2f}s "
        "({} -> {:.2f}s non-overlapped)".format(
            split["device"], split["transfer"], split["codec"],
            "overlapped" if overlapped else "serial", codec_nonov))

    n = check_result(ours_dir, counter, total)
    log("verified {} idf entries match baseline exactly".format(n))

    value = size_mb / secs
    # Learned-cost-model decision trace (plan/model.py): what the model
    # predicted for this plan and where its choices came from — the
    # perf gate (tools/check_bench.py --trend) warns when the measured
    # number falls far below the model's own prediction.
    cost_sec = (summary.get("plan") or {}).get("cost") or {}
    predicted = cost_sec.get("predicted") or {}
    record = {
        "metric": "tfidf_docfreq_throughput",
        "value": round(value, 2),
        "unit": "MB/s",
        "vs_baseline": round(value / (size_mb / base_secs), 2),
        # Thread-seconds per wall second for the winning warm run (see
        # ops/devtime.py): device kernel dispatch+sync, host<->device
        # transfers, the native C codec.  Utilization-style — concurrent
        # pool workers sum, so a value can exceed 1.0 on multi-core
        # hosts (2.0 = two cores' worth).  The single-chip claim made
        # explicit: everything else is generic host Python/numpy.
        "device_fraction": round(split["device"] / secs, 4),
        "transfer_fraction": round(split["transfer"] / secs, 4),
        "codec_fraction": round(split["codec"] / secs, 4),
        # Device lowering (dampr_tpu.plan.lower, winning warm run): how
        # many plan stages compiled to jitted device programs and the
        # feed/drain bytes the host moved for them — the evidence behind
        # device_fraction (0 stages + fraction ~0 = the host-codec leg).
        "lower": _settings.lower_enabled(),
        "device_stages": summary.get("device", {}).get("device_stages"),
        "h2d_bytes": summary.get("device", {}).get("h2d_bytes"),
        "d2h_bytes": summary.get("device", {}).get("d2h_bytes"),
        # Cross-stage device handoff (docs/plan.md "Cross-stage device
        # fusion", winning warm run): edges the plan kept HBM-resident,
        # device bytes registered without a host round-trip, and the
        # drain bytes the table-mode programs never fetched (the CI
        # trace-smoke gate reads these).
        "handoff_edges": summary.get("device", {}).get("handoff_edges"),
        "handoff_bytes": summary.get("device", {}).get("handoff_bytes"),
        "d2h_avoided_bytes": summary.get("device", {}).get(
            "d2h_avoided_bytes"),
        "handoff_degrades": summary.get("device", {}).get(
            "handoff_degrades"),
        # Codec-attributable NON-overlapped fraction of the wall: codec
        # seconds the fold actually waited on (the full codec bucket when
        # the overlap executor is off).  This is the number the overlap
        # work moves; codec_fraction above stays the total thread-seconds
        # the codec burned, overlapped or not.
        "codec_nonoverlapped_fraction": round(codec_nonov / secs, 4),
        "overlap_windows": _settings.overlap_windows,
        # Run-scoped observability (winning warm run): spill/merge volume
        # from the per-run summary, plus artifact locations when tracing
        # was on (DAMPR_TPU_TRACE=1) — stats.json carries per-stage
        # records/bytes/spill and the trace loads in Perfetto.
        "spilled_mb": round(summary.get("store", {}).get(
            "spilled_bytes", 0) / 1e6, 1),
        "merge_generations": summary.get("store", {}).get("merge_gens", 0),
        # Async spill I/O (dampr_tpu.io, winning warm run): post-codec
        # disk bandwidth each way and the fold-side stall fraction —
        # what the background writer pool / prefetching reader move.
        "spill_write_mbps": summary.get("io", {}).get("spill_write_mbps"),
        "spill_read_mbps": summary.get("io", {}).get("spill_read_mbps"),
        "io_wait_fraction": summary.get("io", {}).get("io_wait_fraction"),
        # Live metrics plane: sampler self-overhead for the winning run
        # (None when the plane was off — the default untraced path).
        "sampler_overhead": summary.get("metrics", {}).get(
            "sampler", {}).get("overhead"),
        # Logical plan optimizer (dampr_tpu.plan, winning warm run):
        # constructed vs executed stage counts and the rules that fired —
        # the fused-vs-unfused shape baselines capture (identical counts
        # under DAMPR_TPU_OPTIMIZE=0).
        "optimize": _settings.optimize,
        "plan_stages_before": summary.get("plan", {}).get("stages_before"),
        "plan_stages_after": summary.get("plan", {}).get("stages_after"),
        "trace_file": summary.get("trace_file"),
        "stats_file": summary.get("stats_file"),
        "cost_source": cost_sec.get("source"),
        "cost_choices_applied": sum(
            1 for c in cost_sec.get("choices") or () if c.get("applied")),
        "model_predicted_value": predicted.get("mbps"),
        "n_partitions": summary.get("n_partitions"),
    }
    if tune_section is not None:
        record["autotune"] = tune_section
    print(json.dumps(record))


if __name__ == "__main__":
    main()
