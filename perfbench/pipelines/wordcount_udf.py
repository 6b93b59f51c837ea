"""Refefer/Dampr ``examples/wc.py`` exactly as written (and as this repo's
``examples/wc.py`` writes it): a per-line Python ``flat_map(line.split)``
and a per-record ``fold_by`` with lambdas over 16 MiB chunks, the whole
result read back.  The plan cannot lower it: the scan stays on the host.
Its reference splits on whitespace and keeps case; reading, merging and
comparing are word count's (``wordcount.py``)."""

from collections import Counter

from corpus import load

_wc = load("pipelines", "wordcount")
result, release, render = _wc.result, _wc.release, _wc.render
reference, compare = _wc.reference, _wc.compare


def run(corpus, outdir, name):
    from dampr_tpu import Dampr

    return (Dampr.text(corpus, chunk_size=16 * 1024 ** 2)
            .flat_map(lambda line: line.split())
            .fold_by(lambda w: w, binop=lambda x, y: x + y,
                     value=lambda w: 1)
            .run(name))


def reference_shard(text):
    return Counter(text.split())
