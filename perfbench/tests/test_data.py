"""The data generators under ``perfbench/data/``, found by the name a
configuration gives: the Zipf text is byte for byte what the benchmark
made before generators had names, and gensort's records are well formed,
numbered across shards and seeded; a configuration naming ``gensort``
runs through the pool, ``run.py`` and ``control.py`` with new files
alone.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import hashlib
import json
import os
import re
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests")]

from corpus import DEFAULT_DATA, Pool, load  # noqa: E402

SEED = 3000000421
SHARD = 1325000
ZIPF = {"vocabulary": 24000, "zipf_exponent": 1.1, "tokens_per_line": [8, 12]}
#: SHA-256 of ``corpus.shard_text(SEED, idx, SHARD, ZIPF)`` at the commit
#: before the generators moved under ``data/``.
ZIPF_SHA256 = {
    0: "c53097e5e12f01b21502173fc226145a958cedda25d7fef4357b0ecad9f63bd7",
    1: "fee0265ed2f95ba6d7a3acf3fe0fced583c6581b191daebc48d382f27604f7a2"}
RECORD = re.compile(rb"[\x20-\x7e]{10}  ([0-9A-F]{32})  "
                    rb"(?:([0-9A-F])\2{3}){13}\r\n")


def gensort(seed, idx, offset, nbytes):
    return load("data", "gensort").shard(seed, idx, offset, nbytes, {})


def rows(data):
    """Each record's row number; every record well formed."""
    assert len(data) % 100 == 0
    out = []
    for i in range(0, len(data), 100):
        m = RECORD.fullmatch(data[i:i + 100])
        assert m, data[i:i + 100]
        out.append(int(m.group(1), 16))
    return out


@pytest.mark.parametrize("idx", sorted(ZIPF_SHA256))
def test_zipf_text_is_the_parents_byte_for_byte(idx):
    data = load("data", "zipf_text").shard(SEED, idx, idx * SHARD, SHARD,
                                           ZIPF)
    assert hashlib.sha256(data).hexdigest() == ZIPF_SHA256[idx]


def test_existing_configurations_name_no_generator():
    """So every configuration there is runs the Zipf text, with the
    parameters the digests were taken with."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        configs = json.load(f)["configs"]
    for entry in configs:
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config.get("data", DEFAULT_DATA) == "zipf_text"
        assert config["corpus"] == ZIPF
    assert DEFAULT_DATA == "zipf_text"


def test_gensort_records_are_well_formed_and_printable():
    data = gensort(SEED, 0, 0, 100 * 2000)
    assert rows(data) == list(range(2000))
    keys = {data[i:i + 10] for i in range(0, len(data), 100)}
    assert len(keys) == 2000
    assert min(min(k) for k in keys) >= 0x20
    assert max(max(k) for k in keys) <= 0x7e


def test_gensort_rows_run_on_across_shards():
    offset = 7 * 100 * 1000
    assert rows(gensort(SEED, 7, offset, 100 * 1000)) == list(
        range(7000, 8000))
    # 2**40 rows in: the high hex digits count too
    big = (1 << 40) * 100
    assert rows(gensort(SEED, 1, big, 300)) == [1 << 40, (1 << 40) + 1,
                                                (1 << 40) + 2]


def test_gensort_is_seeded():
    data = gensort(SEED, 3, 0, 100 * 500)
    code = ("import sys, hashlib; sys.path.insert(0, {!r}); "
            "from corpus import load; print(hashlib.sha256(load('data', "
            "'gensort').shard({}, 3, 0, 50000, {{}})).hexdigest())").format(
                BENCH, SEED)
    other = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=120, check=True)
    assert other.stdout.strip() == hashlib.sha256(data).hexdigest()
    assert gensort(SEED + 1, 3, 0, 100 * 500)[:10 * 100] != data[:10 * 100]
    assert gensort(SEED, 4, 0, 100 * 500) != data


@pytest.mark.parametrize("offset, nbytes", [(0, 150), (50, 100)])
def test_gensort_refuses_part_records(offset, nbytes):
    with pytest.raises(ValueError, match="whole 100-byte records"):
        gensort(SEED, 0, offset, nbytes)


def test_gensort_through_the_pool(tmp_path):
    """The pool writes a configuration's named generator: five shards,
    the last one short, numbered as one input."""
    pool = Pool(2)
    try:
        pool.start_corpus(SEED, "gensort", {}, 4500 * 100, 1000 * 100,
                          str(tmp_path / "corpus.txt"))
        assert pool.wait_corpus() == 4500 * 100
    finally:
        pool.close()
    with open(str(tmp_path / "corpus.txt"), "rb") as f:
        assert rows(f.read()) == list(range(4500))
    assert pool.bounds[-1] == (4000 * 100, 4500 * 100)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    """A scratch checkout with one more configuration (``"data":
    "gensort"``) and cell, as a later PR would add them."""
    import scratch_sort

    dest = str(tmp_path_factory.mktemp("checkout"))
    scratch_sort.build(dest, 2 * 10 ** 6, 5 * 10 ** 5)
    return dest


def in_checkout(dest, script, argv):
    return subprocess.run(
        [sys.executable, os.path.join("perfbench", script)] + argv, cwd=dest,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=600)


def test_gensort_cell_runs_and_is_correct(checkout):
    proc = in_checkout(checkout, "run.py", [
        "--workload", "sort.scratch", "--seed", str(2 ** 31 + 29),
        "--seconds", "1", "--trace", "0", "--rehearse-cpu", "2000000"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] and out["attempted"] >= 1
    assert out["checks"] == {"unordered": {"value": 0, "limit": 0},
                             "wrong_records": {"value": 0, "limit": 0}}


def test_gensort_cell_control_is_not_correct(checkout):
    """``control.py`` unchanged on gensort data: records sorted on 4 key
    bytes read unordered at 200,000 records."""
    proc = in_checkout(checkout, "control.py", [
        "--workload", "sort.scratch", "--seeds", str(SEED),
        "--tier-bytes", str(2 * 10 ** 7)])
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["control_fails"]
    assert got["checks"]["unordered"]["value"] > 0
    assert got["checks"]["wrong_records"]["value"] == 0
