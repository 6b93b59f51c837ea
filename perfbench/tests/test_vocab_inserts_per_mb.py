"""The ``vocab_inserts_per_mb`` reader on fake runs: slot inserts over
the window's input MB, and nothing from a program without the counter.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

NAME = "vocab_inserts_per_mb"
CELLS = ("tfidf.ref20x", "wordcount.ref20x", "wordcount-mesh.ref20x")


class _Job(object):
    def __init__(self, device, input_bytes):
        self.stats = {"device": device}
        self.input_bytes = input_bytes


class _Run(object):
    def __init__(self, jobs):
        self.jobs = jobs


def read(jobs):
    from corpus import load

    return load("metrics", NAME).read(_Run(jobs))


def test_inserts_over_the_windows_input_mb():
    jobs = [_Job({"handoff_slot_inserts": 24000}, 106000000),
            _Job({"handoff_slot_inserts": 26000}, 94000000)]
    assert read(jobs) == pytest.approx(250.0)


def test_nothing_from_a_program_without_the_counter():
    assert read([_Job({"h2d_bytes": 5}, 1000)]) is None
    assert read([_Job({"handoff_slot_inserts": 3}, 1000),
                 _Job({"h2d_bytes": 5}, 1000)]) is None
    assert read([]) is None


@pytest.mark.parametrize("cell", CELLS)
def test_reported_in_the_lowered_cells(cell):
    import run

    assert NAME in {m["name"] for m in run.Cell(cell).per_layer}
    assert NAME not in {m["name"]
                        for m in run.Cell("wordcount-udf.ref1x").per_layer}
