"""A scratch external sort over gensort's records (``data/gensort.py``),
judged by ``valsort``: ``sort_by`` on each record's 10-byte key through
the public DSL, the merged result streamed back into a file of records.

No cell of ``BENCHMARK.json`` runs it: ``scratch_sort.py`` lays it into a
scratch checkout as ``pipelines/sort.py``, for the tests and for sizing a
sort cell.  The reference's side keeps each input shard's text only for
the control, which it sorts on its first ``CONTROL_KEY`` key bytes
alone.
"""

import multiprocessing
import os

import valsort

#: Key bytes the control sorts on: one int32 lane's worth.
CONTROL_KEY = 4


def run(corpus, outdir, name):
    from dampr_tpu import Dampr

    chunk_size = os.path.getsize(corpus) // multiprocessing.cpu_count() + 1
    return (Dampr.text(corpus, chunk_size)
            .sort_by(lambda rec: rec[:valsort.KEY])
            .run(name=name))


def _write(records, outdir):
    os.makedirs(outdir)
    path = os.path.join(outdir, "part-00000")
    with open(path, "w", encoding="ascii", newline="") as f:
        f.writelines(rec + "\n" for rec in records)
    return [path]


def result(em, outdir):
    """The output's paths, once the sorted records are on disk (a text
    line keeps its ``\\r``)."""
    return _write(em.stream(), outdir)


def release(em):
    em.delete()


def render(records, outdir):
    """A reference as the program's result, for the control."""
    return _write((r[:-1] for r in records), outdir)


def reference_shard(text):
    return valsort.reference_shard(text) + (text,)


def reference(parts, control=False):
    """(records, checksum); the control, the records sorted on their first
    ``CONTROL_KEY`` key bytes, stably."""
    if control:
        recs = [t[i:i + valsort.RECORD] for _n, _c, t in parts
                for i in range(0, len(t), valsort.RECORD)]
        return sorted(recs, key=lambda r: r[:CONTROL_KEY])
    return valsort.reference([(n, c) for n, c, _t in parts])


def compare(result, want):
    return valsort.check(result, want)
