"""Vectorized 64-bit record hashing (dual uint32 lanes).

Replaces the reference's per-record ``hash(key) % n_partitions`` partitioner
(reference dampr/base.py:6-8 ``Splitter``) with a batched kernel: string keys become a
padded uint8 matrix hashed by a dual-lane FNV-1a scan on device; integer keys go
through a murmur-style finalizer.  Two independent 32-bit lanes (h1, h2) stand in for
a 64-bit hash without requiring global ``jax_enable_x64``:

- partition routing uses ``h1 % P`` (cheap, single lane);
- grouping sorts lexicographically on ``(h1, h2)`` via ``lax.sort(num_keys=2)``;
- host bookkeeping combines lanes into one uint64 (``combine64``).

Collisions on the full 64 bits are detected during sort-based grouping
(ops/segment.py ``sort_and_group`` compares real keys of same-hash neighbors and
repairs boundaries), so hashing here only needs to be uniform, not perfect.

Python-equality nuance: ``1 == 1.0 == True`` group together under the reference's
sort+groupby semantics, so integral floats and bools are canonicalized to int64
before hashing.
"""

import functools

import numpy as np

from .. import settings

_FNV_OFFSET1 = np.uint32(2166136261)
_FNV_OFFSET2 = np.uint32(0x9747B28C)
_FNV_PRIME1 = np.uint32(16777619)
_FNV_PRIME2 = np.uint32(0x85EBCA6B)

# Length padding buckets bound jit recompilations for variable-width string blocks.
_LEN_BUCKETS = (8, 16, 32, 64, 128, 256, 512, 1024)


def _len_bucket(max_len):
    for b in _LEN_BUCKETS:
        if max_len <= b:
            return b
    # Very long keys: round up to a multiple of 1024.
    return ((max_len + 1023) // 1024) * 1024


def _pow2_rows(n):
    p = 1 << max(0, (n - 1).bit_length())
    return max(p, 8)


def encode_str_keys(keys):
    """Encode a sequence of str/bytes keys as (padded uint8 [N, L], lengths int32 [N]).

    UTF-8 encodes str; bytes pass through.  L is bucketed to bound compilations.
    """
    bs = [k.encode("utf-8") if isinstance(k, str) else bytes(k) for k in keys]
    n = len(bs)
    max_len = max((len(b) for b in bs), default=1)
    L = _len_bucket(max(max_len, 1))
    mat = np.zeros((n, L), dtype=np.uint8)
    lens = np.empty(n, dtype=np.int32)
    for i, b in enumerate(bs):
        lens[i] = len(b)
        if b:
            mat[i, : len(b)] = np.frombuffer(b, dtype=np.uint8)
    return mat, lens


# ---------------------------------------------------------------------------
# numpy host path
# ---------------------------------------------------------------------------

def _fnv_numpy(mat, lens):
    n, L = mat.shape
    h1 = np.full(n, _FNV_OFFSET1, dtype=np.uint32)
    h2 = np.full(n, _FNV_OFFSET2, dtype=np.uint32)
    cols = np.arange(L, dtype=np.int32)
    with np.errstate(over="ignore"):
        for c in range(L):
            active = cols[c] < lens
            b = mat[:, c].astype(np.uint32)
            nh1 = (h1 ^ b) * _FNV_PRIME1
            nh2 = (h2 ^ b) * _FNV_PRIME2
            h1 = np.where(active, nh1, h1)
            h2 = np.where(active, nh2, h2)
    return h1, h2


def _mix_int_numpy(vals_i64):
    v = vals_i64.astype(np.uint64)
    lo = (v & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (v >> np.uint64(32)).astype(np.uint32)
    with np.errstate(over="ignore"):
        h1 = _murmur_fmix_np(lo ^ np.uint32(0x9E3779B9), hi)
        h2 = _murmur_fmix_np(lo ^ np.uint32(0x85EBCA6B), hi ^ np.uint32(0xC2B2AE35))
    return h1, h2


def _murmur_fmix_np(x, y):
    h = x
    h ^= y
    h ^= h >> np.uint32(16)
    h = (h * np.uint32(0x85EBCA6B)).astype(np.uint32)
    h ^= h >> np.uint32(13)
    h = (h * np.uint32(0xC2B2AE35)).astype(np.uint32)
    h ^= h >> np.uint32(16)
    return h


# ---------------------------------------------------------------------------
# JAX device path
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _fnv_jit():
    import jax
    import jax.numpy as jnp
    from jax import lax

    def hash_tokens(mat, lens):
        n, L = mat.shape
        h1 = jnp.full((n,), _FNV_OFFSET1, dtype=jnp.uint32)
        h2 = jnp.full((n,), _FNV_OFFSET2, dtype=jnp.uint32)

        def body(c, hs):
            h1, h2 = hs
            active = c < lens
            b = mat[:, c].astype(jnp.uint32)
            nh1 = (h1 ^ b) * _FNV_PRIME1
            nh2 = (h2 ^ b) * _FNV_PRIME2
            return (jnp.where(active, nh1, h1), jnp.where(active, nh2, h2))

        h1, h2 = lax.fori_loop(0, L, body, (h1, h2))
        return h1, h2

    return jax.jit(hash_tokens)


@functools.lru_cache(maxsize=None)
def _mix_int_jit():
    import jax
    import jax.numpy as jnp

    def fmix(x, y):
        h = x ^ y
        h = h ^ (h >> 16)
        h = h * jnp.uint32(0x85EBCA6B)
        h = h ^ (h >> 13)
        h = h * jnp.uint32(0xC2B2AE35)
        h = h ^ (h >> 16)
        return h

    def hash_mix(lo, hi):
        h1 = fmix(lo ^ jnp.uint32(0x9E3779B9), hi)
        h2 = fmix(lo ^ jnp.uint32(0x85EBCA6B), hi ^ jnp.uint32(0xC2B2AE35))
        return h1, h2

    return jax.jit(hash_mix)


def _use_device(n):
    return settings.use_device_for(n)


def _fnv(mat, lens):
    # _fnv_jit is the one device FNV: a hand-written kernel ran slower.
    n = mat.shape[0]
    if not _use_device(n):
        return _fnv_numpy(mat, lens)
    np_rows = _pow2_rows(n)
    if np_rows != n:
        mat = np.pad(mat, ((0, np_rows - n), (0, 0)))
        lens = np.pad(lens, (0, np_rows - n))
    h1, h2 = _fnv_jit()(mat, lens)
    return np.asarray(h1)[:n], np.asarray(h2)[:n]


def _mix_int(vals_i64):
    n = vals_i64.shape[0]
    if not _use_device(n):
        return _mix_int_numpy(vals_i64)
    np_rows = _pow2_rows(n)
    v = vals_i64
    if np_rows != n:
        v = np.pad(v, (0, np_rows - n))
    u = v.astype(np.uint64)
    lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    hi = (u >> np.uint64(32)).astype(np.uint32)
    h1, h2 = _mix_int_jit()(lo, hi)
    return np.asarray(h1)[:n], np.asarray(h2)[:n]


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

def _canonical_int(k):
    """Map bools / integral floats to int to mirror Python equality grouping."""
    if isinstance(k, bool):
        return int(k)
    if isinstance(k, float) and k.is_integer():
        return int(k)
    return k


# Per-item key kinds.  Each kind maps to exactly one typed hash kernel, so a key
# hashes identically whether it appears in a homogeneous block or a mixed one
# (dispatching on the whole batch's type-set would route 'x' differently in a
# str-only block vs a str/int block — a shuffle-correctness bug).
_K_INT = 0     # bool / int in int64 range / integral float in range -> _mix_int
_K_STR = 1     # str / bytes -> dual-lane FNV over utf-8 bytes
_K_FBITS = 2   # non-integral or huge float -> _mix_int over float64 bit pattern
_K_OBJ = 3     # everything else -> deterministic canonical-bytes FNV

_I64_LO = -(2 ** 63)
_I64_HI = 2 ** 63 - 1


def _kind_of(k):
    if isinstance(k, np.generic):
        # numpy scalars (np.int64, np.bool_, np.float32, ...) classify by their
        # Python value — np.int64(5) must group with 5.
        k = k.item()
    if isinstance(k, bool):
        return _K_INT
    if isinstance(k, int):
        if _I64_LO <= k <= _I64_HI:
            return _K_INT
        # Out-of-range int: if exactly float-representable, hash as float bits
        # (Python equality: 10**300 == 1e300); else canonical-bytes lane.
        try:
            f = float(k)
        except OverflowError:
            return _K_OBJ
        return _K_FBITS if int(f) == k else _K_OBJ
    if isinstance(k, float):
        # Strict upper bound: 2.0**63 is float-representable but overflows
        # int64; anything strictly below converts exactly.
        if k.is_integer() and -(2.0 ** 63) <= k < 2.0 ** 63:
            return _K_INT
        return _K_FBITS
    if isinstance(k, (str, bytes)):
        return _K_STR
    return _K_OBJ


def encode_canonical(k):
    """Deterministic, type-tagged byte encoding of an arbitrary (hashable) key.

    Used for the object-lane hash: equal keys encode equally across processes
    and hosts (unlike Python's PYTHONHASHSEED-salted ``hash()``), so partition
    routing of tuple/frozenset keys is stable across spill-reload and multi-host
    boundaries.  Numeric leaves canonicalize exactly like the typed lanes
    (1 == 1.0 == True encode identically)."""
    if isinstance(k, np.generic):
        k = k.item()
    kind = _kind_of(k)
    if kind == _K_INT:
        return b"i" + str(int(_canonical_int(k))).encode("ascii")
    if kind == _K_FBITS:
        return b"f" + np.float64(k).tobytes()
    if kind == _K_STR:
        return (b"s" + k.encode("utf-8")) if isinstance(k, str) else (b"s" + bytes(k))
    if isinstance(k, int):
        # huge non-float-representable int
        return b"I" + str(k).encode("ascii")
    if k is None:
        return b"N"
    if isinstance(k, tuple):
        return b"(" + _join_lenprefixed(encode_canonical(x) for x in k)
    if isinstance(k, frozenset):
        return b"{" + _join_lenprefixed(sorted(encode_canonical(x) for x in k))
    # Last resort: repr (deterministic for well-behaved types).
    return b"r" + repr(k).encode("utf-8", "backslashreplace")


def _join_lenprefixed(encs):
    """Length-prefix each element encoding so composites are injective —
    ('a','b') and ('a\\x00sb',) must not encode identically."""
    out = bytearray()
    for e in encs:
        out += len(e).to_bytes(4, "little")
        out += e
    return bytes(out)


def _hash_bytes_list(bs):
    """(h1, h2) for a list of bytes keys: one native C pass when available
    (below the device-dispatch threshold), else the padded-matrix kernel.
    Both produce identical lanes by construction."""
    if not settings.use_device_for(len(bs)):
        from .. import native

        res = native.hash_bytes_batch(bs)
        if res is not None:
            return res
    mat, lens = encode_str_keys(bs)
    return _fnv(mat, lens)


def _hash_object_items(items):
    """Canonical-bytes FNV for a list of arbitrary keys -> (h1, h2)."""
    encs = [encode_canonical(_freeze(k)) for k in items]
    h1, h2 = _hash_bytes_list(encs)
    # Tag the object lane so b"i5" (a str key) and int 5's encoding can't be
    # confused with a real str key's hash by construction alone; collisions are
    # still resolved exactly downstream, this just keeps them rare.
    return h1 ^ np.uint32(0xA5A5A5A5), h2 ^ np.uint32(0x3C3C3C3C)


def _hash_kind(kind, items):
    """Run the single typed kernel for one homogeneous kind of keys.  Both the
    homogeneous fast path and the mixed-kind scatter path go through here, so a
    key's hash can never depend on which batch it arrived in."""
    n = len(items)
    if kind == _K_INT:
        return _mix_int(np.fromiter(
            (int(_canonical_int(k)) for k in items), dtype=np.int64, count=n))
    if kind == _K_STR:
        return _hash_bytes_list(
            [k.encode("utf-8") if isinstance(k, str) else bytes(k)
             for k in items])
    if kind == _K_FBITS:
        return _mix_int(np.fromiter(
            (float(k) for k in items), dtype=np.float64, count=n).view(np.int64))
    return _hash_object_items(items)


def hash_keys(keys):
    """Hash a batch of keys -> (h1, h2) uint32 arrays.

    `keys` is a numpy array (numeric dtype or object) or a list.  Dispatch is
    per item kind, so mixed-type blocks hash each key with the same typed
    kernel a homogeneous block would use (replaces the reference's per-record
    ``hash(key)`` — dampr/base.py:6-8 — with batched kernels).
    """
    if isinstance(keys, np.ndarray) and keys.dtype != object:
        if np.issubdtype(keys.dtype, np.integer) or keys.dtype == np.bool_:
            if keys.dtype == np.uint64 and len(keys) and keys.max() > np.uint64(_I64_HI):
                # astype(int64) would wrap; route through the per-item path so
                # uint64 2**63+1 hashes like the equal Python int.
                keys = keys.astype(object)
            else:
                return _mix_int(keys.astype(np.int64))
        elif np.issubdtype(keys.dtype, np.floating):
            return _hash_float_array(keys)
        else:
            # other dtypes (complex, datetime, ...): go through object path
            keys = keys.astype(object)

    keys = list(keys) if not isinstance(keys, np.ndarray) else keys
    n = len(keys)
    if n == 0:
        return (np.empty(0, dtype=np.uint32), np.empty(0, dtype=np.uint32))

    # Homogeneity probe: one C-level pass over the exact types.  A block of
    # all-str / in-range-int / plain-float keys — the overwhelmingly common
    # case — skips the per-item _kind_of loop entirely.  Every branch routes
    # into the same typed kernels (_hash_kind / _hash_float_array) the
    # per-item path would pick, so hashes are identical by construction.
    ts = set(map(type, keys))
    if ts == {str} or ts == {bytes}:
        return _hash_kind(_K_STR, keys)
    if ts == {bool}:
        return _mix_int(np.fromiter(keys, dtype=np.int64, count=n))
    if ts == {int}:
        try:
            return _mix_int(np.fromiter(keys, dtype=np.int64, count=n))
        except OverflowError:
            pass  # out-of-int64 ints present: per-item classification
    elif ts == {float}:
        return _hash_float_array(np.fromiter(keys, dtype=np.float64, count=n))

    kinds = np.empty(n, dtype=np.int8)
    for i, k in enumerate(keys):
        kinds[i] = _kind_of(k)

    uniq = set(kinds.tolist())
    if len(uniq) == 1:
        return _hash_kind(uniq.pop(), keys)

    # Mixed kinds: hash each homogeneous sub-batch with its typed kernel and
    # scatter results back into place.
    h1 = np.empty(n, dtype=np.uint32)
    h2 = np.empty(n, dtype=np.uint32)
    for kind in uniq:
        idx = np.flatnonzero(kinds == kind)
        a, b = _hash_kind(kind, [keys[i] for i in idx])
        h1[idx] = a
        h2[idx] = b
    return h1, h2


def _hash_float_array(arr):
    """Float keys: integral in-int64-range values canonicalize to ints (Python
    equality: 1.0 groups with 1); the rest hash on their float64 bit pattern.
    Bounds match ``_kind_of`` exactly so container type never changes a hash."""
    arr64 = arr.astype(np.float64)
    integral = ((arr64 == np.floor(arr64)) & np.isfinite(arr64)
                & (arr64 >= -(2.0 ** 63)) & (arr64 < 2.0 ** 63))
    as_int = np.where(integral, arr64, 0).astype(np.int64)
    bits = arr64.view(np.int64)
    mixed_src = np.where(integral, as_int, bits)
    return _mix_int(mixed_src)


def _freeze(k):
    if isinstance(k, list):
        return tuple(_freeze(x) for x in k)
    if isinstance(k, dict):
        return tuple(sorted((kk, _freeze(vv)) for kk, vv in k.items()))
    if isinstance(k, set):
        return frozenset(k)
    return k


def combine64(h1, h2):
    """Combine the two uint32 lanes into one uint64 per record (host only)."""
    return (h1.astype(np.uint64) << np.uint64(32)) | h2.astype(np.uint64)
