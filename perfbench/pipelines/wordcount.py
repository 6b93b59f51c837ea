"""Word count in the form the plan lowers: ``TokenCounts`` (lowercased
``[^\\w]+`` tokens, plain int counts) feeding ``fold_values(add)``, the
whole result read back.  Refefer/Dampr ``examples/wc.py``'s job, written
the way ``chip_smoke.py`` drives it."""

import multiprocessing
import os
import re
from collections import Counter

RX = re.compile(r"[^\w]+")


def run(corpus, outdir, name):
    import operator

    from dampr_tpu import Dampr
    from dampr_tpu.ops.text import TokenCounts

    chunk_size = os.path.getsize(corpus) // multiprocessing.cpu_count() + 1
    return (Dampr.text(corpus, chunk_size)
            .custom_mapper(TokenCounts(mode="word", lower=True,
                                       pair_values=False))
            .fold_values(operator.add)
            .run(name=name))


def result(em, outdir):
    return em.read()


def release(em):
    em.delete()


def render(want, outdir):
    """A reference as the program's result, for the control."""
    return list(want.items())


def reference_shard(text):
    counts = Counter(RX.split(text.lower()))
    counts.pop("", None)
    return counts


def wrap16(n):
    """``n`` as an int16 accumulator holds it (the control)."""
    return (n + (1 << 15)) % (1 << 16) - (1 << 15)


def reference(parts, control=False):
    """word -> count.  The control counts in int16, the width below the
    device's int32 count lanes, and wraps."""
    total = Counter()
    for part in parts:
        total.update(part)
    if control:
        return {w: wrap16(c) for w, c in total.items()}
    return dict(total)


def compare(result, want):
    """``wrong_keys``: words missing, extra, repeated or with another
    count."""
    got = dict(result)
    wrong = len(result) - len(got) + len(got.keys() ^ want.keys())
    wrong += sum(got[w] != c for w, c in want.items() if w in got)
    return {"wrong_keys": wrong}
