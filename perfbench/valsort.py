"""The plain check of a sort's output, with the Sort Benchmark's
``valsort`` semantics (sortbenchmark.org), for gensort's 100-byte ASCII
records (``data/gensort.py``).

The input's side: ``reference_shard`` gives each input shard's record
count and the sum of its records' CRC-32s; ``reference`` adds the shards'
up.  The output's side: ``check`` streams a job's output files in order,
holding one read buffer at a time, and reads

- ``unordered``: adjacent records whose 10-byte keys are out of order,
  compared bytewise, across file boundaries too;
- ``wrong_records``: the records that are malformed (a line that is not
  100 bytes ending in ``\\r\\n``), plus the gap between the output's count
  of well-formed records and the input's, plus 1 where the checksums of
  the two differ (a record dropped, duplicated over another or altered).

Both are whole numbers, 0 on a correct sort.  Nothing here imports JAX or
``dampr_tpu``.
"""

import zlib

import numpy as np

RECORD = 100
KEY = 10
#: Bytes read from an output file at a time.
BUFSIZE = 1 << 22


def _crc_sum(mv, starts):
    crc = zlib.crc32
    return sum(crc(mv[s:s + RECORD]) for s in starts.tolist())


def reference_shard(text):
    """(records, sum of their CRC-32s) of one input shard of whole
    records."""
    data = text.encode("ascii")
    if len(data) % RECORD:
        raise ValueError("an input shard of {} bytes is not whole {}-byte "
                         "records".format(len(data), RECORD))
    n = len(data) // RECORD
    return n, _crc_sum(memoryview(data), np.arange(0, len(data), RECORD))


def reference(parts):
    """The whole input's (records, checksum) from its shards'."""
    return (sum(n for n, _ in parts), sum(c for _, c in parts))


class _Reader(object):
    """The running state of ``check`` over one stream of records."""

    def __init__(self):
        self.records = self.checksum = self.malformed = self.unordered = 0
        self.last = None  # the last well-formed record's key, as a row

    def lines(self, buf):
        """Counts the whole lines of ``buf`` (which ends with one)."""
        arr = np.frombuffer(buf, dtype=np.uint8)
        ends = np.flatnonzero(arr == 10) + 1
        starts = np.concatenate(([0], ends[:-1]))
        good = (ends - starts == RECORD) & (arr[ends - 2] == 13)
        self.malformed += int(len(ends) - np.count_nonzero(good))
        starts = starts[good]
        if not len(starts):
            return
        self.records += len(starts)
        self.checksum += _crc_sum(memoryview(buf), starts)
        keys = arr[starts[:, None] + np.arange(KEY)]
        if self.last is not None:
            keys = np.concatenate((self.last[None], keys))
        self.last = keys[-1].copy()
        self.unordered += _descents(keys)


def _descents(keys):
    """How many rows of the ``[n, 10]`` key matrix are bytewise greater
    than the row after them."""
    hi = keys[:, :8].copy().view(">u8")[:, 0]
    lo = keys[:, 8:].copy().view(">u2")[:, 0]
    a_hi, b_hi, a_lo, b_lo = hi[:-1], hi[1:], lo[:-1], lo[1:]
    return int(np.count_nonzero((a_hi > b_hi) | ((a_hi == b_hi)
                                                 & (a_lo > b_lo))))


def check(paths, want, bufsize=BUFSIZE):
    """``{"unordered": ..., "wrong_records": ...}`` of the output files
    ``paths``, read in that order, against ``want`` from ``reference``."""
    r = _Reader()
    for path in paths:
        carry = b""
        skipping = False  # inside a line already longer than a record
        with open(path, "rb") as f:
            while True:
                chunk = f.read(bufsize)
                if not chunk:
                    break
                if skipping:
                    nl = chunk.find(b"\n")
                    if nl < 0:
                        continue
                    chunk, skipping = chunk[nl + 1:], False
                buf = carry + chunk
                cut = buf.rfind(b"\n") + 1
                carry = buf[cut:]
                if cut:
                    r.lines(memoryview(buf)[:cut])
                if len(carry) >= RECORD:
                    r.malformed += 1
                    carry, skipping = b"", True
        r.malformed += bool(carry)  # a last line with no newline
    n, checksum = want
    return {"unordered": r.unordered,
            "wrong_records": r.malformed + abs(n - r.records)
            + (r.checksum != checksum)}
