"""Faults planted in the timed path, for ``test_correct.py``.

    python3 -c "...; faults.main(<fault>, <run.py arguments>)"

patches ``dampr_tpu`` in this process, then drives ``run.main`` with the
harness's look for a chip skipped (``--rehearse-cpu``).  Each fault is one
that a cell can have; the exchange between chips is not among them, since
every cell runs on one chip.
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
#: The most common token of the corpus, in every job's result.
TOKEN = "t1"


def state_unchanged():
    """Every fold step returns its state unchanged."""
    from dampr_tpu.dampr import PMap

    fold_values, fold_by = PMap.fold_values, PMap.fold_by
    PMap.fold_values = lambda self, binop, **kw: fold_values(
        self, lambda x, y: x, **kw)
    PMap.fold_by = lambda self, key, binop, value=lambda x: x, **kw: fold_by(
        self, key, lambda x, y: x, value, **kw)


def half_batch():
    """Half of each job's input is left out: the text source reads the
    first half of the corpus's lines."""
    from dampr_tpu.dampr import Dampr

    text = Dampr.text.__func__

    def first_half(cls, fname, *args, **kw):
        half = fname + ".half"
        if not os.path.exists(half):
            with open(fname, "rb") as f:
                lines = f.readlines()
            with open(half, "wb") as f:
                f.writelines(lines[:len(lines) // 2])
        return text(cls, half, *args, **kw)

    Dampr.text = classmethod(first_half)


def answer_altered():
    """One answer is altered where it is produced: the count (or document
    frequency) of the most common token is one more."""
    from dampr_tpu.dampr import PMap, ValueEmitter

    read = ValueEmitter.read
    ValueEmitter.read = lambda self, k=None: [
        (w, c + 1) if w == TOKEN else (w, c) for w, c in read(self, k)]
    sink_tsv = PMap.sink_tsv
    PMap.sink_tsv = lambda self, path: sink_tsv(self.map(
        lambda r: (r[0], r[1] + 1, r[2]) if r[0] == TOKEN else r), path)


def host_path():
    """The plan lowers nothing: a lowered cell's jobs run the host codec
    path, whose answers are as right and whose rate is another cell's."""
    from dampr_tpu import settings

    settings.lower_enabled = lambda: False


FAULTS = {f.__name__: f for f in (state_unchanged, half_batch,
                                  answer_altered, host_path)}


def main(fault, argv):
    sys.path[:0] = [BENCH, ROOT]
    FAULTS[fault]()
    import run

    return run.main(argv)
