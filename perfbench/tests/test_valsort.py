"""``valsort`` against sorted gensort output and against each fault a
sort can plant: a record dropped, two adjacent records swapped, a payload
byte changed, a record duplicated over another, an order on fewer key
bytes than ten; malformed records; and a stream larger than its buffer.

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q
"""

import os
import sys
import tracemalloc

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import valsort  # noqa: E402
from corpus import load  # noqa: E402

SEED = 2 ** 31 + 29
N = 3000


def records(n=N, seed=SEED):
    data = load("data", "gensort").shard(seed, 0, 0, n * 100, {})
    return np.frombuffer(data, dtype=np.uint8).reshape(n, 100).copy()


def by_key(recs, width=10):
    """``recs`` sorted on their first ``width`` key bytes, stably."""
    order = np.lexsort(recs[:, :width].T[::-1])
    return recs[order]


def want(recs):
    return valsort.reference([valsort.reference_shard(
        recs.tobytes().decode("ascii"))])


def write(tmp_path, recs, files=1):
    paths = []
    for i, part in enumerate(np.array_split(recs, files)):
        path = str(tmp_path / "part-{:05d}".format(i))
        with open(path, "wb") as f:
            f.write(part.tobytes())
        paths.append(path)
    return paths


@pytest.mark.parametrize("files", [1, 4])
def test_sorted_output_reads_zero(tmp_path, files):
    recs = records()
    got = valsort.check(write(tmp_path, by_key(recs), files), want(recs))
    assert got == {"unordered": 0, "wrong_records": 0}


def test_reference_counts_records_and_sums_their_crcs():
    import zlib

    recs = records(10)
    n, crc = want(recs)
    assert n == 10
    assert crc == sum(zlib.crc32(r.tobytes()) for r in recs)


def dropped(out):
    return np.delete(out, N // 2, axis=0)


def swapped(out):
    out[[10, 11]] = out[[11, 10]]
    return out


def payload_byte(out):
    out[N // 3, 60] ^= 1
    return out


def duplicated(out):
    out[N // 4 + 1] = out[N // 4]
    return out


#: Each fault with what the check reads: a dropped record is one short
#: and changes the checksum.
FAULTS = {dropped: (0, 2), swapped: (1, 0), payload_byte: (0, 1),
          duplicated: (0, 1)}


@pytest.mark.parametrize("fault", sorted(FAULTS, key=lambda f: f.__name__),
                         ids=lambda f: f.__name__)
def test_planted_fault_reads_nonzero(tmp_path, fault):
    recs = records()
    got = valsort.check(write(tmp_path, fault(by_key(recs)), 3), want(recs))
    unordered, wrong = FAULTS[fault]
    assert got == {"unordered": unordered, "wrong_records": wrong}


def test_order_on_eight_key_bytes_reads_unordered(tmp_path):
    """Uniform 10-byte keys almost never share 8 bytes, so the input gets
    groups that do: a sort on 8 bytes then keeps each group in input
    order, which the last two bytes put out of order."""
    recs = records()
    recs[:, :8] = recs[np.arange(N) % 50, :8]
    got = valsort.check(write(tmp_path, by_key(recs, 8)), want(recs))
    assert got["wrong_records"] == 0
    assert got["unordered"] > 0
    assert valsort.check(write(tmp_path, by_key(recs)), want(recs)) == {
        "unordered": 0, "wrong_records": 0}


def test_order_is_checked_across_files(tmp_path):
    recs = by_key(records())
    out = np.concatenate((recs[N // 2:], recs[:N // 2]))
    got = valsort.check(write(tmp_path, out, 2), want(recs))
    assert got == {"unordered": 1, "wrong_records": 0}


@pytest.mark.parametrize("cut", [
    b"\r\n",                  # a record one byte short
    b"  \r\n",                # one byte long
    b"xx\n",                  # 100 bytes with no \r
    b"",                      # a last line with no newline
])
def test_malformed_record_is_wrong(tmp_path, cut):
    recs = records(20)
    out = by_key(recs).tobytes()
    bad = out[:500] + out[500:597] + cut + out[600:]
    path = str(tmp_path / "part-00000")
    with open(path, "wb") as f:
        f.write(bad if cut else out[:-1])
    got = valsort.check([path], want(recs))
    assert got["wrong_records"] >= 1, got


def test_line_longer_than_any_buffer_is_one_malformed_record(tmp_path):
    recs = by_key(records(40))
    path = str(tmp_path / "part-00000")
    with open(path, "wb") as f:
        f.write(recs[:20].tobytes() + b"x" * 5000 + b"\r\n"
                + recs[20:].tobytes())
    got = valsort.check([path], want(recs), bufsize=256)
    assert got == {"unordered": 0, "wrong_records": 1}


def test_stream_larger_than_the_buffer_holds_one_buffer(tmp_path):
    """A 2 MB output read through a 16 KiB buffer reads as it does in one
    read, and the check's allocations stay within a few buffers."""
    recs = records(20000)
    paths = write(tmp_path, by_key(recs), 2)
    whole = valsort.check(paths, want(recs), bufsize=1 << 24)
    bufsize = 16 * 1024 + 37  # cuts records at every offset
    ref = want(recs)
    tracemalloc.start()
    try:
        got = valsort.check(paths, ref, bufsize=bufsize)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert got == whole == {"unordered": 0, "wrong_records": 0}
    assert peak < 16 * bufsize < recs.nbytes / 4, peak
