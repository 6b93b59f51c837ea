"""The ``native_feed_share`` reader on fake runs: natively built padded
batches over every padded batch, and nothing from a program without the
counters.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

NAME = "native_feed_share"
CELLS = ("tfidf.ref20x", "wordcount.ref20x", "wordcount-mesh.ref20x")


class _Job(object):
    def __init__(self, device):
        self.stats = {"device": device}


class _Run(object):
    def __init__(self, jobs):
        self.jobs = jobs


def read(jobs):
    from corpus import load

    return load("metrics", NAME).read(_Run(jobs))


def _dev(pad, native):
    return {"scan_pad_batches": pad, "scan_native_batches": native}


def test_native_batches_over_every_padded_batch():
    assert read([_Job(_dev(94, 94)), _Job(_dev(96, 96))]) == 100.0
    assert read([_Job(_dev(30, 30)), _Job(_dev(10, 0))]) == pytest.approx(
        75.0)


def test_nothing_from_a_program_without_the_counters():
    assert read([_Job({"h2d_bytes": 5})]) is None
    assert read([_Job(_dev(4, 4)), _Job({"h2d_bytes": 5})]) is None
    assert read([_Job(_dev(0, 0))]) is None
    assert read([]) is None


@pytest.mark.parametrize("cell", CELLS)
def test_reported_in_the_lowered_cells(cell):
    import run

    assert NAME in {m["name"] for m in run.Cell(cell).per_layer}
    assert NAME not in {m["name"]
                        for m in run.Cell("wordcount-udf.ref1x").per_layer}
