"""TF-IDF document frequency: the reference's headline job.

``run`` is ``dampr_tpu/bench_tfidf.py:run_dampr_tpu``'s pipeline, which is
Refefer/Dampr ``benchmarks/tf-idf-dampr.py`` in the form the plan lowers:
per line, the set of lowercased ``[^\\w]+`` tokens; their document
frequency; idf = log(1 + lines / df) through a broadcast cross with the
line count; a TSV sink.  The reference is plain Python over the same bytes.
"""

import math
import multiprocessing
import os
import re
from collections import Counter

RX = re.compile(r"[^\w]+")


def run(corpus, outdir, name):
    """One job through the public DSL; returns its emitter."""
    import operator

    from dampr_tpu import Dampr
    from dampr_tpu.ops.text import DocFreq

    chunk_size = os.path.getsize(corpus) // multiprocessing.cpu_count() + 1
    docs = Dampr.text(corpus, chunk_size)
    doc_freq = (docs.custom_mapper(
        DocFreq(mode="word", lower=True, pair_values=False))
        .fold_values(operator.add))
    idf = doc_freq.cross_right(
        docs.len(),
        lambda df, total: (df[0], df[1],
                           math.log(1 + (float(total) / df[1]))),
        memory=True)
    return idf.sink_tsv(outdir).run(name=name)


def result(em, outdir):
    """The sink's directory: complete when ``run`` returns."""
    return outdir


def release(em):
    """Nothing: deleting a sink's emitter deletes a part of the result."""


def render(want, outdir):
    """A reference as the program's result, for the control."""
    os.makedirs(outdir)
    with open(os.path.join(outdir, "part-00000"), "w") as f:
        for w, (c, idf) in want.items():
            f.write("{}\t{}\t{}\n".format(w, c, idf))
    return outdir


def reference_shard(text):
    """(document frequency, lines) of one line-aligned shard."""
    df = Counter()
    lines = text.lower().split("\n")[:-1]
    for line in lines:
        df.update(t for t in set(RX.split(line)) if t)
    return df, len(lines)


def reference(parts, control=False):
    """token -> (df, idf).  The control computes idf in float32, the
    precision below the configuration's float64."""
    df, total = Counter(), 0
    for part_df, lines in parts:
        df.update(part_df)
        total += lines
    if not control:
        return {w: (c, math.log(1 + float(total) / c))
                for w, c in df.items()}
    import numpy as np

    one, n = np.float32(1), np.float32(total)
    return {w: (c, float(np.log(one + n / np.float32(c))))
            for w, c in df.items()}


def compare(result, want):
    """Reads the sunk TSV back.  ``wrong_keys``: tokens missing, extra,
    repeated or with another df.  ``idf_rel_gap``: the widest relative gap
    of an idf from the reference's."""
    got, rows = {}, 0
    for part in sorted(os.listdir(result)):
        with open(os.path.join(result, part)) as f:
            for line in f:
                w, c, idf = line.rstrip("\n").split("\t")
                got[w] = (int(c), float(idf))
                rows += 1
    wrong = rows - len(got) + len(got.keys() ^ want.keys())
    gap = 0.0
    for w, (c, idf) in want.items():
        if w not in got:
            continue
        gc, gi = got[w]
        wrong += gc != c
        gap = max(gap, abs(gi - idf) / idf)
    return {"wrong_keys": wrong, "idf_rel_gap": gap}
