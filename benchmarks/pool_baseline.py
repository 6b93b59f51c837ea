"""Second-system TF-IDF baseline: multiprocessing.Pool Counter-merge.

The reference compares against Dask bag (benchmarks/tf-idf-dask.py) and
derives a finding from it (Dask OOMs at the 500x tier).  This offline
image has no dask wheel, so the second system
is the stdlib's honest multi-core yardstick: split the corpus into
line-aligned byte ranges, run the reference baseline's exact per-line
Counter loop (benchmarks/baseline.py:12-24 shape) in a worker pool, and
merge the per-chunk Counters in the parent.

This is the fairest non-engine comparison on a multi-core host: same
tokenization regex, same per-line set() dedup, C-speed Counter update,
zero spill machinery — its only costs over the 1-core baseline are chunk
scheduling and the Counter merge (vocabulary-sized, 24k keys).  What it
cannot do is bound memory (every worker holds a full vocabulary Counter
and the merge holds all of them) or generalize past this one workload —
which is the point of the comparison.

    python benchmarks/pool_baseline.py --mb 2048

Prints ONE JSON line: {"metric": "tfidf_pool_baseline_throughput", ...}.
Verifies the merged result exactly against the single-core baseline's
cached Counter when one exists for the same corpus (bench_tfidf caches
it next to the corpus file).
"""

import _pathfix  # noqa: F401  (repo root onto sys.path)

import argparse
import json
import math
import multiprocessing
import os
import re
import sys
import time
from collections import Counter

from dampr_tpu.bench_tfidf import make_corpus

RX = re.compile(r"[^\w]+")


def _count_range(args):
    """Reference baseline.py's per-line loop over one byte range of the
    corpus.  Ranges are split on arbitrary byte offsets; a line is owned
    by the range containing its FIRST byte, so the worker seeks to the
    first line start at or after ``begin`` (consuming the partial line
    the previous range owns) and reads through the line straddling
    ``end``.  The loop bound is strict: a line starting exactly at
    ``end`` belongs to the next range, which lands on it via its own
    seek(begin-1)+readline."""
    path, begin, end = args
    counter = Counter()
    lines = 0
    with open(path, "rb") as f:
        if begin:
            f.seek(begin - 1)
            f.readline()  # consume the partial line the previous range owns
        while f.tell() < end:
            line = f.readline()
            if not line:
                break
            lines += 1
            counter.update(
                t for t in set(RX.split(line.decode().lower())) if t)
    return counter, lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=int, default=2048)
    ap.add_argument("--dir", default=os.environ.get(
        "DAMPR_BENCH_DIR", "/tmp/dampr_tpu_bench"))
    ap.add_argument("--procs", type=int, default=multiprocessing.cpu_count())
    args = ap.parse_args()

    corpus = os.path.join(args.dir, "corpus_{}mb.txt".format(args.mb))
    make_corpus(corpus, args.mb)
    size = os.path.getsize(corpus)
    size_mb = size / 1e6

    # ~4 ranges per worker bounds straggler skew without per-chunk cost
    n_chunks = max(args.procs * 4, 1)
    step = size // n_chunks + 1
    ranges = [(corpus, at, min(at + step, size))
              for at in range(0, size, step)]

    t0 = time.time()
    counter = Counter()
    total = 0
    with multiprocessing.Pool(args.procs) as pool:
        for c, n in pool.imap_unordered(_count_range, ranges):
            counter.update(c)
            total += n
    outdir = os.path.join(args.dir, "pool-idf")
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, "out"), "w") as out:
        for word, count in counter.items():
            print("\t".join((word, str(count),
                             str(math.log(1 + float(total) / count)))),
                  file=out)
    secs = time.time() - t0
    print("pool baseline ({} procs): {:.2f}s = {:.1f} MB/s".format(
        args.procs, secs, size_mb / secs), file=sys.stderr)

    verified = False
    cache = corpus + ".baseline.pkl"
    if os.path.exists(cache):
        import pickle

        with open(cache, "rb") as f:
            _key, _secs, want_counter, want_total = pickle.load(f)
        assert total == want_total, (total, want_total)
        assert counter == want_counter, "pool merge diverged from 1-core"
        verified = True
        print("verified: merged Counter identical to 1-core baseline",
              file=sys.stderr)

    print(json.dumps({
        "metric": "tfidf_pool_baseline_throughput",
        "value": round(size_mb / secs, 2),
        "unit": "MB/s",
        "procs": args.procs,
        "corpus_mb": round(size_mb, 1),
        "verified_vs_1core": verified,
    }))


if __name__ == "__main__":
    main()
