"""The comparison that decides ``correct``, at a size a test run holds:
sound rehearsal runs pass, the control fails, and each planted fault of
the timed path fails.  Runs on the CPU (``--rehearse-cpu``), each run in
a process of its own.

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

CELLS = ("tfidf.ref20x", "wordcount.ref20x", "wordcount-udf.ref1x")
#: The 1x tier cut for a test; the 20x cells run 20 times this.
TIER_BYTES = 60000
SEED = 2 ** 31 + 11


def harness(cell, fault=None, extra=()):
    argv = ["--workload", cell, "--seed", str(SEED), "--seconds", "1",
            "--trace", "0"] + list(extra)
    if fault is None:
        cmd = [sys.executable, os.path.join(BENCH, "run.py")] + argv
    else:
        cmd = [sys.executable, "-c",
               "import sys; sys.path.insert(0, {!r}); import faults; "
               "sys.exit(faults.main({!r}, {!r}))".format(
                   os.path.join(BENCH, "tests"), fault, argv)]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=600)


def result(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = result(harness(cell, extra=["--rehearse-cpu", str(TIER_BYTES)]))
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"input_mbps", "job_s_p95", "setup_s"}
    assert out["device"]["platform"] == "cpu"


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_planted_fault_is_not_correct(cell, fault):
    out = result(harness(cell, fault, ["--rehearse-cpu", str(TIER_BYTES)]))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1


@pytest.mark.parametrize("cell", ("tfidf.ref20x", "wordcount.ref20x"))
def test_lowered_cell_on_the_host_path_is_not_correct(cell):
    out = result(harness(cell, "host_path", ["--rehearse-cpu",
                                             str(TIER_BYTES)]))
    assert not out["correct"]
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["device_stages"]["value"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    import control
    import run

    c = run.Cell(cell)
    got = control.readings(c, SEED, TIER_BYTES * 40)
    assert any(got[k] > v for k, v in c.spec["limits"].items()), got


def test_no_tpu_exits_nonzero_without_a_result():
    proc = harness("wordcount-udf.ref1x")
    assert proc.returncode != 0
    assert not proc.stdout.strip()
