"""gensort's ASCII records (``gensort -a``, the Sort Benchmark's input
generator, sortbenchmark.org; Hadoop's TeraGen writes the same layout).

Each record is 100 bytes:

- bytes 0-9: the key, ten printable characters (0x20-0x7E), each uniform
  and independent, as in the GraySort "Indy" category;
- bytes 10-11: two spaces;
- bytes 12-43: the record's row number in the whole input, 32 uppercase
  hexadecimal digits; numbers run on across shards (``offset // 100``);
- bytes 44-45: two spaces;
- bytes 46-97: filler, 13 runs of four copies of one uppercase hexadecimal
  digit, drawn from the seed;
- bytes 98-99: ``\\r\\n``.

``assumed`` (what could not be checked against gensort's source here, for
a configuration that uses this generator to copy):

- the filler: gensort derives its 13 digits from the low 48 bits of the
  record's 128-bit random number; here they are drawn uniform from the
  seed.  Same layout, other digits;
- the key: gensort takes the ten characters from its own 128-bit
  generator; here each is uniform from ``(seed, idx)``, so the keys of a
  seed are not those of ``gensort -a`` at the same start row;
- the uppercase hexadecimal of the row number and the filler.

The configuration's ``corpus`` block is passed as ``params`` and takes no
key: the layout has no free parameter.
"""

RECORD = 100
KEY = 10
MASK32 = 0xFFFFFFFF
_HEX = b"0123456789ABCDEF"


def shard(seed, idx, offset, nbytes, params):
    """``nbytes // 100`` records drawn from ``(seed, idx)``, the first
    numbered ``offset // 100``; both sizes whole records."""
    import numpy as np

    if nbytes % RECORD or offset % RECORD:
        raise ValueError(
            "gensort shards are whole {}-byte records: offset {} and size {} "
            "are not".format(RECORD, offset, nbytes))
    n = nbytes // RECORD
    # RandomState takes 32-bit words; the driver's seeds pass 2**31.
    rng = np.random.RandomState([seed & MASK32, (seed >> 32) & MASK32, idx])
    hexd = np.frombuffer(_HEX, dtype=np.uint8)
    out = np.empty((n, RECORD), dtype=np.uint8)
    out[:, :KEY] = rng.randint(0, 95, size=(n, KEY), dtype=np.uint8) + 0x20
    out[:, 10:12] = ord(" ")
    rows = np.arange(offset // RECORD, offset // RECORD + n, dtype=np.uint64)
    out[:, 12:28] = ord("0")  # the high 64 bits of a row number below 2**64
    shifts = np.arange(60, -4, -4, dtype=np.uint64)
    out[:, 28:44] = hexd[(rows[:, None] >> shifts) & np.uint64(0xF)]
    out[:, 44:46] = ord(" ")
    fill = rng.randint(0, 16, size=(n, 13), dtype=np.uint8)
    out[:, 46:98] = hexd[np.repeat(fill, 4, axis=1)]
    out[:, 98] = ord("\r")
    out[:, 99] = ord("\n")
    return out.tobytes()
