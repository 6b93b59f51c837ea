"""The comparison that decides ``correct`` for the four-chip cell
``wordcount-mesh.ref20x``, at a size a test run holds, on four virtual
CPU devices: a sound rehearsal run passes with every chip scanning and
the fold over all four, each planted fault of the timed path fails, the
control fails.  Each run in a process of its own.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

CELL = "wordcount-mesh.ref20x"
CHIPS = 4
#: The 1x tier cut for a test; the cell runs 20 times this.
TIER_BYTES = 60000
SEED = 2 ** 31 + 25
#: The cell's per-layer metrics, and the keys of ``stats()`` they read.
METRICS = {"mesh_fold_share": ("devtime", "mesh_fold"),
           "fold_a2a_bytes_per_input_byte": ("device", "fold_a2a_bytes"),
           "scan_chip_balance": ("device", "h2d_bytes_by_chip")}


def harness(fault=None):
    argv = ["--workload", CELL, "--seed", str(SEED), "--seconds", "1",
            "--trace", "0", "--rehearse-cpu", str(TIER_BYTES)]
    if fault is None:
        cmd = [sys.executable, os.path.join(BENCH, "run.py")] + argv
    else:
        cmd = [sys.executable, "-c",
               "import sys; sys.path.insert(0, {!r}); import faults; "
               "sys.exit(faults.main({!r}, {!r}))".format(
                   os.path.join(BENCH, "tests"), fault, argv)]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count={}".format(
                   CHIPS))
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_sound_run_is_correct_on_four_chips():
    out = result(harness())
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert out["device"] == dict(out["device"], platform="cpu", count=CHIPS)
    checks = out["checks"]
    assert checks["scan_chips"] == {"value": CHIPS, "min": CHIPS}
    assert checks["fold_chips"] == {"value": CHIPS, "min": CHIPS}
    assert checks["wrong_keys"]["value"] == 0
    assert checks["handoff_degrades"]["value"] == 0


@pytest.mark.parametrize("fault", ["half_batch", "answer_altered"])
def test_planted_fault_is_not_correct(fault):
    out = result(harness(fault))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1


def test_control_is_not_correct():
    import control
    import run

    c = run.Cell(CELL)
    got = control.readings(c, SEED, TIER_BYTES * 40)
    assert got["wrong_keys"] > 0, got


class _Job(object):
    def __init__(self, stats, wall=2.0, input_bytes=1000):
        self.stats, self.wall, self.input_bytes = stats, wall, input_bytes


class _Run(object):
    def __init__(self, jobs):
        self.jobs = jobs


def _stats(**device):
    return {"devtime": {"mesh_fold": 0.5},
            "device": dict({"fold_a2a_bytes": 250,
                            "h2d_bytes_by_chip": [90, 100, 100, 95]},
                           **device)}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_nothing_from_a_program_without_it(name):
    """The parent program has none of the cell's counters: each reader
    returns None, and does not raise."""
    import run
    from corpus import load

    section, key = METRICS[name]
    stats = _stats()
    del stats[section][key]
    reader = load("metrics", name)
    assert reader.read(_Run([_Job(stats), _Job(_stats())])) is None
    assert name in {m["name"] for m in run.Cell(CELL).per_layer}


def test_metrics_read_the_cells_counters():
    from corpus import load

    jobs = [_Job(_stats()),
            _Job(_stats(h2d_bytes_by_chip=[50, 100, 100, 100]))]

    def read(name):
        return load("metrics", name).read(_Run(jobs))

    assert read("mesh_fold_share") == pytest.approx(25.0)
    assert read("fold_a2a_bytes_per_input_byte") == pytest.approx(0.25)
    assert read("scan_chip_balance") == pytest.approx(70.0)
    one_chip = [_Job(_stats(h2d_bytes_by_chip=[100]))]
    assert load("metrics", "scan_chip_balance").read(_Run(one_chip)) is None
