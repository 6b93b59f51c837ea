"""Native host codec: builds and binds the C++ tokenizer via ctypes.

The shared object compiles on first use (g++ -O3, ~1s) and is cached next to
the source under a name keyed by the source's content and the flags; set
DAMPR_TPU_NATIVE=0 to force the pure-numpy fallback.  The binding is ctypes
on purpose — no pybind11 in the image, and the interface is
four flat arrays, exactly what ctypes does well.
"""

import ctypes
import hashlib
import logging
import os
import subprocess
import threading

import numpy as np

log = logging.getLogger("dampr_tpu.native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "tokenizer.cpp")
_FLAGS = ("-O3", "-shared", "-fPIC")

_lock = threading.Lock()
_lib = None
_tried = False


def _so_path():
    """The built library's path, named by a digest of the source and the
    build flags: a copy of the tree can only load a library built from
    the same source with the same flags.  No ``-march=native`` — a tree
    copied to another host must never load code built for a CPU it lacks."""
    h = hashlib.sha256(" ".join(_FLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_HERE, "_native-{}.so".format(h.hexdigest()[:16]))


def _build(so):
    tmp = "{}.{}.tmp".format(so, os.getpid())
    subprocess.run(["g++", *_FLAGS, _SRC, "-o", tmp], check=True,
                   capture_output=True)
    os.replace(tmp, so)  # atomic: a concurrent loader never sees half a file


def _codec_timed(fn):
    """Charge this native-codec entry point's wall time to the 'codec'
    devtime bucket (the bench's host/device split)."""
    import functools

    @functools.wraps(fn)
    def wrapped(*a, **kw):
        from ..ops import devtime

        with devtime.track("codec"):
            return fn(*a, **kw)

    return wrapped


def get_lib():
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DAMPR_TPU_NATIVE", "1") in ("0", "false"):
            return None
        try:
            so = _so_path()
            if not os.path.exists(so):
                _build(so)
            lib = ctypes.CDLL(so)
            fc = lib.dampr_token_counts
            fc.restype = ctypes.c_long
            fc.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_int, ctypes.c_int,
                ctypes.c_int, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            fp = lib.dampr_parse_i64
            fp.restype = ctypes.c_long
            fp.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p,
                ctypes.c_void_p,
            ]
            fb = lib.dampr_hash_bytes_batch
            fb.restype = None
            fb.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_long,
                ctypes.c_void_p, ctypes.c_void_p,
            ]
            ft = lib.dampr_token_bounds
            ft.restype = ctypes.c_long
            ft.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_int,
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
            ]
            fr = lib.dampr_pad_rows
            fr.restype = None
            fr.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_long, ctypes.c_long,
                ctypes.c_void_p,
            ]
            _lib = lib
        except Exception as exc:  # noqa: BLE001 - any failure -> numpy path
            log.warning("native tokenizer unavailable (%s); using numpy", exc)
            _lib = None
    return _lib


def _u8(buf):
    buf = np.ascontiguousarray(buf)
    if buf.dtype != np.uint8 or buf.ndim != 1:
        raise ValueError("expected a 1-D uint8 buffer, got {} {}".format(
            buf.dtype, buf.shape))
    return buf


def token_bounds(buf, mode, want_line_ids=False):
    """One native pass: (starts int64, lens int32[, line_ids int32]) of
    the token runs of a contiguous uint8 buffer, equal to
    ``ops.text._token_bounds`` (and the line ids to a newline search).
    Returns None when the native library is unavailable.  Untimed: the
    lowered scan charges it to its own feed phase."""
    lib = get_lib()
    if lib is None:
        return None
    buf = _u8(buf)
    n = len(buf)
    cap = n // 2 + 1
    starts = np.empty(cap, dtype=np.int64)
    lens = np.empty(cap, dtype=np.int32)
    line_ids = np.empty(cap, dtype=np.int32) if want_line_ids else None
    count = lib.dampr_token_bounds(
        buf.ctypes.data, n, int(mode), starts.ctypes.data, lens.ctypes.data,
        line_ids.ctypes.data if want_line_ids else None)
    if want_line_ids:
        return starts[:count], lens[:count], line_ids[:count]
    return starts[:count], lens[:count]


def pad_rows(buf, starts, lens, mat):
    """Fill rows ``[0, len(starts))`` of the zeroed C-contiguous uint8
    matrix ``mat`` with each token's bytes from ``buf``, zero past its
    length, in one native pass.  Returns False when the native library
    is unavailable (``mat`` untouched).  Untimed, like
    :func:`token_bounds`."""
    lib = get_lib()
    if lib is None:
        return False
    buf = _u8(buf)
    starts = np.ascontiguousarray(starts, dtype=np.int64)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    if (mat.dtype != np.uint8 or mat.ndim != 2 or not mat.flags.c_contiguous
            or mat.shape[0] < len(starts) or len(lens) != len(starts)):
        raise ValueError("pad_rows: {} tokens, {} lengths, matrix {} {}"
                         .format(len(starts), len(lens), mat.dtype,
                                 mat.shape))
    lib.dampr_pad_rows(buf.ctypes.data, len(buf), starts.ctypes.data,
                       lens.ctypes.data, len(starts), mat.shape[1],
                       mat.ctypes.data)
    return True


@_codec_timed
def parse_i64(buf):
    """Whitespace-separated int64 parse of a uint8 buffer in one C pass.
    Returns an int64 array, None when the native library is unavailable,
    or raises ValueError on the first unparsable/out-of-range token
    (numpy-parse error semantics)."""
    lib = get_lib()
    if lib is None:
        return None
    buf = np.ascontiguousarray(buf)
    n = len(buf)
    out = np.empty(n // 2 + 1, dtype=np.int64)
    bad = ctypes.c_long(-1)
    count = lib.dampr_parse_i64(buf.ctypes.data, n, out.ctypes.data,
                                ctypes.byref(bad))
    if bad.value >= 0:
        raise ValueError(
            "unparsable numeric token at index {}".format(bad.value))
    return out[:count].copy()


@_codec_timed
def hash_bytes_batch(bs):
    """Dual-lane FNV over a list of bytes keys in one C pass.  Returns
    (h1, h2) uint32 arrays, or None when the native library is
    unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(bs)
    offs = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.fromiter((len(b) for b in bs), dtype=np.int64, count=n),
              out=offs[1:])
    buf = np.frombuffer(b"".join(bs), dtype=np.uint8)
    h1 = np.empty(n, dtype=np.uint32)
    h2 = np.empty(n, dtype=np.uint32)
    lib.dampr_hash_bytes_batch(
        np.ascontiguousarray(buf).ctypes.data, offs.ctypes.data, n,
        h1.ctypes.data, h2.ctypes.data)
    return h1, h2


@_codec_timed
def token_counts(buf, mode, lower, dedup_per_line):
    """Fused native tokenize+hash+count: one pass, no sort.  Returns
    (h1, h2, counts, rep_starts, rep_lens) over distinct tokens, or None when
    the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None
    n = len(buf)
    cap = n // 2 + 1
    h1 = np.empty(cap, dtype=np.uint32)
    h2 = np.empty(cap, dtype=np.uint32)
    counts = np.empty(cap, dtype=np.int64)
    starts = np.empty(cap, dtype=np.int64)
    lens = np.empty(cap, dtype=np.int32)
    buf = np.ascontiguousarray(buf)
    k = lib.dampr_token_counts(
        buf.ctypes.data, n, int(mode), int(lower), int(dedup_per_line),
        h1.ctypes.data, h2.ctypes.data, counts.ctypes.data,
        starts.ctypes.data, lens.ctypes.data)
    if k < 0:
        return None
    return h1[:k], h2[:k], counts[:k], starts[:k], lens[:k]
