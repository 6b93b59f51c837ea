"""The lowered scan's native feed (``native.token_bounds`` /
``native.pad_rows``) against the numpy forms it replaces: token bounds,
per-line ids and the padded batch matrix equal byte for byte, and a run
with the native library unavailable gives the same results through the
numpy fallback, with no batch counted as natively built."""

import operator
import os

import numpy as np
import pytest

from dampr_tpu import Dampr, native, settings
from dampr_tpu.ops import lower as ops_lower
from dampr_tpu.ops.text import (_LOWER, _SHORT_TOKEN, DocFreq, TokenCounts,
                                _token_bounds)


def _words(seed, n_lines, vocab, trailing=True):
    rng = np.random.RandomState(seed)
    lines = [" ".join(rng.choice(vocab, size=rng.randint(1, 9)))
             for _ in range(n_lines)]
    text = "\n".join(lines) + ("\n" if trailing else "")
    return text.encode("utf-8")


_ASCII = ["w%d" % i for i in range(80)] + ["Tok_1", "UPPER", "a", "x,y",
                                           "semi;colon", "tab\there"]
_UTF8 = _ASCII + ["émoji", "naïve", "日本語", "mixedÉcase", "Ünïcödé_42"]
# One token at each length-bucket edge and one past it (the pad's row
# width is the bucket of the batch's longest token), up to the longest
# token the padded path takes.
_EDGES = ["e" * n for n in (1, 7, 8, 9, 15, 16, 17, 31, 32, 33, 63, 64,
                            65, 127, 128, 129, 254, _SHORT_TOKEN)]

# (window, mode, lower, dedup)
CASES = {
    "word-lower": (_words(1, 400, _ASCII), "word", True, False),
    "word-raw": (_words(2, 400, _ASCII), "word", False, False),
    "whitespace-lower": (_words(3, 400, _ASCII), "whitespace", True, False),
    "whitespace-raw": (_words(4, 400, _ASCII), "whitespace", False, False),
    "word-dedup": (_words(5, 400, _ASCII), "word", True, True),
    "whitespace-dedup": (_words(6, 400, _ASCII), "whitespace", False, True),
    "multibyte-utf8": (_words(7, 300, _UTF8), "word", True, True),
    "no-trailing-newline": (_words(8, 200, _ASCII, trailing=False),
                            "whitespace", True, True),
    "bucket-edges": (("\n".join(_EDGES) + "\n" + " ".join(_EDGES[::-1])
                      ).encode(), "word", False, True),
    "longer-than-short": (_words(9, 100, _ASCII + ["L" * 300, "m" * 1000]),
                          "word", True, True),
    "empty": (b"", "word", True, True),
    "no-tokens": (b" ,.;!\n\n\t-- \r\n", "word", False, True),
    "no-tokens-whitespace": (b"  \n\t\r\n\x0b\x0c ", "whitespace", True,
                             False),
}


def _pads(sink, buf, starts, lens, lines):
    """Every padded batch the sink would build from these tokens: the
    padded-path tokens whole, then each length apart (its own bucket)."""
    short = lens <= _SHORT_TOKEN
    starts, lens = starts[short], lens[short]
    lines = lines[short] if lines is not None else None
    groups = [np.arange(len(starts))] + [np.flatnonzero(lens == n)
                                        for n in np.unique(lens)]
    return [sink._pad_batch(buf, starts[g], lens[g],
                            lines[g] if lines is not None else None)
            for g in groups if len(g)]


@pytest.mark.parametrize("case", sorted(CASES))
def test_native_feed_matches_numpy(case, monkeypatch):
    if native.get_lib() is None:
        pytest.skip("native library unavailable")
    data, mode, lower, dedup = CASES[case]
    sink = ops_lower.DeviceTokenFoldSink(
        {"mode": mode, "lower": lower, "dedup": dedup, "pair_values": False})
    buf = np.frombuffer(data, dtype=np.uint8)
    if lower:
        buf = _LOWER[buf]

    got = native.token_bounds(buf, 1 if mode == "word" else 0, dedup)
    want = _token_bounds(buf, mode)
    if dedup:
        want = want + (sink._line_ids(buf, want[0]),)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)

    lines = want[2] if dedup else None
    pads = _pads(sink, buf, want[0], want[1], lines)
    monkeypatch.setattr(native, "get_lib", lambda: None)
    assert native.token_bounds(buf, 0, dedup) is None
    ref = _pads(sink, buf, want[0], want[1], lines)
    assert len(pads) == len(ref)
    for got_p, want_p in zip(pads, ref):
        for g, w in zip(got_p, want_p):
            assert g.dtype == w.dtype and g.shape == w.shape
            np.testing.assert_array_equal(g, w)


@pytest.fixture
def lowered():
    old = (settings.lower, settings.lower_batch)
    settings.lower = "1"
    settings.lower_batch = 1024  # several batches per window
    yield
    (settings.lower, settings.lower_batch) = old


@pytest.fixture
def corpus(tmp_path):
    path = str(tmp_path / "corpus.txt")
    with open(path, "wb") as f:
        f.write(_words(11, 3000, _UTF8 + ["Z" * 300]))
    return path


def _run(corpus, mapper, name):
    em = (Dampr.text(corpus, os.path.getsize(corpus) // 3 + 1)
          .custom_mapper(mapper)
          .fold_values(operator.add)
          .run(name=name))
    got = sorted(em.read())
    device = em.stats()["device"]
    em.delete()
    return got, device


@pytest.mark.parametrize("mapper", [
    TokenCounts(mode="whitespace", lower=False, pair_values=False),
    DocFreq(mode="word", lower=True, pair_values=False),
], ids=["word-count", "doc-freq"])
def test_numpy_fallback_gives_the_native_results(corpus, mapper, lowered,
                                                 monkeypatch):
    if native.get_lib() is None:
        pytest.skip("native library unavailable")
    tag = type(mapper).__name__.lower()
    fast, dev = _run(corpus, mapper, "nativefeed-" + tag)
    assert dev["device_stages"] >= 1
    assert dev["scan_pad_batches"] > 1
    assert dev["scan_native_batches"] == dev["scan_pad_batches"]

    monkeypatch.setattr(native, "get_lib", lambda: None)
    slow, dev = _run(corpus, mapper, "nativefeed-numpy-" + tag)
    assert slow == fast
    assert dev["scan_pad_batches"] > 1
    assert dev["scan_native_batches"] == 0


@pytest.mark.parametrize("bad", ["matrix-dtype", "too-few-rows",
                                 "not-contiguous", "buffer-dtype"])
def test_pad_rows_refuses_what_it_cannot_fill(bad):
    if native.get_lib() is None:
        pytest.skip("native library unavailable")
    buf = np.frombuffer(b"ab cd ef", dtype=np.uint8)
    starts = np.array([0, 3, 6], dtype=np.int64)
    lens = np.array([2, 2, 2], dtype=np.int32)
    mat = np.zeros((8, 8), dtype=np.uint8)
    if bad == "matrix-dtype":
        mat = mat.astype(np.int32)
    elif bad == "too-few-rows":
        mat = mat[:2]
    elif bad == "not-contiguous":
        mat = np.zeros((8, 16), dtype=np.uint8)[:, ::2]
    else:
        buf = buf.astype(np.int16)
    with pytest.raises(ValueError):
        native.pad_rows(buf, starts, lens, mat)
