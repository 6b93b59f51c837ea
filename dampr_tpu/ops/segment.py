"""Sort-based grouping and segment reduction.

This is the TPU-native replacement for the reference's external-sort grouping
machinery: sorted spill runs + k-way heap merge + itertools.groupby (reference
dampr/dataset.py:161-188, 567-588; base.py:184-195 ``yield_groups``).  Instead of
comparison-sorting Python objects, we:

1. lexsort the dual hash lanes ``(h1, h2)`` — :func:`sort_perm` on device,
   ``np.lexsort`` on host for small blocks;
2. find segment boundaries by adjacent-hash inequality;
3. fold numeric values with ``jax.ops.segment_sum``-family kernels, or yield
   per-group Python lists for opaque reducers.

Exactness: after sorting we verify that adjacent records with equal hashes have
equal *real* keys (vectorized compare).  On the (astronomically rare) 64-bit
collision the affected block falls back to exact host grouping by real key.
"""

import functools

import numpy as np

from .. import settings

# ---------------------------------------------------------------------------
# Associative fold descriptors (DSL-recognized ops that fold on device)
# ---------------------------------------------------------------------------


class AssocOp(object):
    """Descriptor for an associative binop.  ``kind`` is a device-foldable tag
    ('sum'|'min'|'max') or None for opaque Python binops (host dict combine).
    ``fn`` is the Python binop used for host fallback and object values.
    ``elementwise`` marks ops whose fn IS elementwise over tuple/composite
    values, so 2D lanes may fold vectorized — a plain ``min`` over tuples
    is lexicographic, NOT elementwise, and must stay on the fn path."""

    __slots__ = ("kind", "fn", "elementwise")

    def __init__(self, kind, fn, elementwise=False):
        self.kind = kind
        self.fn = fn
        self.elementwise = elementwise

    def __call__(self, a, b):
        return self.fn(a, b)


SUM = AssocOp("sum", lambda a, b: a + b)
MIN = AssocOp("min", lambda a, b: a if a <= b else b)
MAX = AssocOp("max", lambda a, b: a if a >= b else b)
FIRST = AssocOp("first", lambda a, _b: a)
#: Elementwise pair sum: composite (sum, count)-style accumulators.  The
#: "sum" kind rides the vectorized 2D-lane segment kernels; the fn gives
#: object-lane tuples an exact pairwise fold (plain SUM.fn would
#: CONCATENATE tuples).
PAIR_SUM = AssocOp("sum", lambda a, b: (a[0] + b[0], a[1] + b[1]),
                   elementwise=True)


def _builtin_ops():
    import operator
    return {operator.add: SUM, operator.iadd: SUM,
            min: MIN, max: MAX}


_BUILTIN_OPS = None


def as_assoc_op(binop):
    """Wrap a Python binop; recognized builtins (operator.add, min, max) get a
    device-foldable kind so ``count()``/``a_group_by(...).reduce(operator.add)``
    hit segment kernels, not per-record Python."""
    global _BUILTIN_OPS
    if isinstance(binop, AssocOp):
        return binop
    if _BUILTIN_OPS is None:
        _BUILTIN_OPS = _builtin_ops()
    hit = _BUILTIN_OPS.get(binop)
    if hit is not None:
        return hit
    return AssocOp(None, binop)


# ---------------------------------------------------------------------------
# Hash lexsort
# ---------------------------------------------------------------------------


def _pow2(n):
    return max(8, 1 << max(0, (n - 1).bit_length()))


def sort_perm(*keys):
    """Traced: the stable permutation ordering rows by ``keys`` (most
    significant first), built as one ``(key, index)`` sort per key from
    the least significant up.  The TPU compiler's time for ONE sort
    grows with the rows and with every lane it carries (a four-lane
    three-key sort of 2^16 rows compiles for a minute and a half on v5e,
    longer past that); a two-lane sort compiles in under half a minute
    up to 2^20 rows, and the passes share one compiled sort."""
    import jax.numpy as jnp
    from jax import lax

    perm = jnp.arange(keys[0].shape[0], dtype=jnp.int32)
    for key in reversed(keys):
        _, perm = lax.sort((jnp.take(key, perm), perm), num_keys=1,
                           is_stable=True)
    return perm


@functools.lru_cache(maxsize=None)
def _lexsort_jit():
    import jax
    import jax.numpy as jnp

    def hash_lexsort(valid, h1, h2):
        perm = sort_perm(valid, h1, h2)
        return jnp.take(h1, perm), jnp.take(h2, perm), perm

    return jax.jit(hash_lexsort)


def hash_sort_perm(h1, h2):
    """Return the stable permutation sorting records by (h1, h2)."""
    n = len(h1)
    if settings.use_device_for(n):
        from . import devtime

        npad = _pow2(n)
        valid = np.zeros(npad, dtype=np.uint8)
        if npad != n:
            valid[n:] = 1
            h1 = np.pad(h1, (0, npad - n))
            h2 = np.pad(h2, (0, npad - n))
        with devtime.track("device"):
            _, _, perm = _lexsort_jit()(valid, h1, h2)
            return np.asarray(perm)[:n]
    return np.lexsort((h2, h1)).astype(np.int32)


# ---------------------------------------------------------------------------
# Grouping
# ---------------------------------------------------------------------------


def _adjacent_new_segment(h1s, h2s):
    """Boolean[n]: True where a new (h1,h2) segment starts (position 0 inclusive)."""
    n = len(h1s)
    starts = np.empty(n, dtype=bool)
    if n == 0:
        return starts
    starts[0] = True
    np.not_equal(h1s[1:], h1s[:-1], out=starts[1:])
    starts[1:] |= h2s[1:] != h2s[:-1]
    return starts


def _keys_adjacent_equal(keys_sorted):
    """Boolean[n-1]: keys_sorted[i] == keys_sorted[i+1], vectorized where possible."""
    if keys_sorted.dtype != object:
        return keys_sorted[1:] == keys_sorted[:-1]
    eq = np.empty(len(keys_sorted) - 1, dtype=bool)
    a = keys_sorted[:-1]
    b = keys_sorted[1:]
    for i in range(len(eq)):
        eq[i] = a[i] == b[i]
    return eq


class SortedGroups(object):
    """A block sorted by hash with verified exact segment boundaries.

    ``starts`` indexes the first record of each group; ``block`` is the sorted
    block; groups are contiguous slices.  Construction detects hash collisions and
    repairs boundaries so every segment holds exactly one distinct key.
    """

    __slots__ = ("block", "starts")

    def __init__(self, block, starts):
        self.block = block
        self.starts = starts

    @property
    def n_groups(self):
        return len(self.starts)

    def group_keys(self):
        return self.block.keys.take(self.starts)

    def bounds(self):
        ends = np.empty_like(self.starts)
        ends[:-1] = self.starts[1:]
        if len(ends):
            ends[-1] = len(self.block)
        return self.starts, ends

    def iter_groups(self):
        """Yield (key, [values]) per group — values materialized as a list,
        mirroring the reference's grouped_read (dataset.py:429-433)."""
        from ..blocks import pylist

        starts, ends = self.bounds()
        keys = self.block.keys
        vals = self.block.values
        for i in range(len(starts)):
            k = keys[starts[i]]
            yield (
                k.item() if isinstance(k, np.generic) else k,
                pylist(vals[starts[i]: ends[i]]),
            )


def sort_and_group(block):
    """Sort a Block by hash and return exact SortedGroups."""
    from ..blocks import Block

    n = len(block)
    if n == 0:
        return SortedGroups(block, np.empty(0, dtype=np.int64))
    h1, h2 = block.hashes()
    perm = hash_sort_perm(h1, h2)
    sb = block.take(perm)
    starts_mask = _adjacent_new_segment(sb.h1, sb.h2)

    # Collision / exactness check: same-hash neighbors must hold equal keys.
    same_hash = ~starts_mask[1:]
    if same_hash.any():
        keq = _keys_adjacent_equal(sb.keys)
        bad = same_hash & ~keq
        if bad.any():
            # Rare path: refine boundaries by real key within colliding runs.
            starts_mask[1:] |= bad
            # Note: records of the colliding keys may interleave within the
            # hash-run; enforce exact grouping by stable-subsorting the run.
            starts_mask = _repair_collisions(sb, starts_mask)
    return SortedGroups(sb, np.flatnonzero(starts_mask))


def _repair_collisions(sb, starts_mask):
    """Exact regroup of hash-runs that contain >1 distinct key.  Reorders records
    inside each colliding run so equal keys are contiguous, and rebuilds the
    starts mask.  O(run length) Python — runs are tiny and collisions rare."""
    h1, h2 = sb.h1, sb.h2
    run_starts = np.flatnonzero(_adjacent_new_segment(h1, h2))
    run_ends = np.append(run_starts[1:], len(sb))
    perm = np.arange(len(sb))
    new_mask = starts_mask.copy()
    for s, e in zip(run_starts, run_ends):
        if e - s <= 1:
            continue
        seg = sb.keys[s:e]
        distinct = {}
        multi = False
        for i in range(len(seg)):
            kk = seg[i]
            found = None
            for did, (dk, idxs) in distinct.items():
                if dk == kk:
                    found = did
                    break
            if found is None:
                distinct[len(distinct)] = (kk, [i])
            else:
                distinct[found][1].append(i)
        if len(distinct) > 1:
            multi = True
        if multi:
            order = []
            starts_local = []
            for _, (dk, idxs) in distinct.items():
                starts_local.append(len(order))
                order.extend(idxs)
            perm[s:e] = s + np.asarray(order)
            new_mask[s:e] = False
            for sl in starts_local:
                new_mask[s + sl] = True
    # apply permutation in place
    sb.keys = sb.keys.take(perm)
    sb.values = sb.values[perm]
    sb.h1 = sb.h1.take(perm)
    sb.h2 = sb.h2.take(perm)
    return new_mask


# ---------------------------------------------------------------------------
# Segment folds
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _segment_fold_jit(kind, num_segments):
    import jax
    import jax.numpy as jnp

    def segment_fold(vals, seg_ids):
        if kind == "sum":
            return jax.ops.segment_sum(vals, seg_ids, num_segments=num_segments)
        if kind == "min":
            return jax.ops.segment_min(vals, seg_ids, num_segments=num_segments)
        if kind == "max":
            return jax.ops.segment_max(vals, seg_ids, num_segments=num_segments)
        raise ValueError(kind)

    return jax.jit(segment_fold)


_NP_FOLD = {
    "sum": np.add,
    "min": np.minimum,
    "max": np.maximum,
}

_I32_MAX = 2 ** 31 - 1
_I64_MAX = 2 ** 63 - 1


def _device_fold_exact(vals, kind):
    """True when folding ``vals`` in the device's 32-bit lanes is exact
    (jax_enable_x64 is off, so int64/float64 inputs would silently truncate
    to int32/float32 on device — the host numpy path stays exact instead).

    - int64: every *result* must fit int32; for 'sum' bound by sum(|v|)
      (conservative: any per-group sum is within it), for min/max by max(|v|).
    - float64: device would drop to float32 precision; keep on host unless
      values already are float32.
    """
    import jax

    if vals.dtype == object:
        return False  # promoted-to-object exact host fold (huge uint64 sums)
    if jax.config.jax_enable_x64:
        return True
    if vals.dtype == np.uint64:
        return False  # 32-bit lanes truncate; host uint64 min/max is exact
    if vals.dtype == np.int64:
        if not len(vals):
            return True
        lo, hi = int(vals.min()), int(vals.max())
        if lo < -_I32_MAX - 1 or hi > _I32_MAX:
            return False  # (min/max never overflow; np.abs would wrap at int64 min)
        if kind == "sum":
            # |v| <= 2**31 each, so the int64 abs-sum is exact for any
            # realistic block length; it bounds every per-group sum.
            return int(np.abs(vals).sum()) <= _I32_MAX
        return True
    if vals.dtype == np.float64:
        return False
    return True


def fold_sorted(groups, op):
    """Fold each group's values with ``op`` -> compacted Block (one record per
    group, hashes preserved).  Device segment kernels when ``op.kind`` is
    recognized and values are numeric; host otherwise."""
    from ..blocks import Block, _column_from_list

    sb = groups.block
    starts, ends = groups.bounds()
    n = len(sb)
    ng = groups.n_groups
    if ng == 0:
        return Block.empty()

    kh1 = sb.h1.take(starts)
    kh2 = sb.h2.take(starts)
    keys = sb.keys.take(starts)

    if op.kind == "first":
        # Stable sort preserves arrival order within groups, so the group's
        # first record is at its start offset — a pure gather, any dtype.
        return Block(keys, sb.values[starts], kh1, kh2)

    if (op.kind in _NP_FOLD and sb.numeric_values
            and (sb.values.ndim == 1 or op.elementwise)):
        # 2D composite lanes only fold vectorized for ops declaring
        # elementwise tuple semantics (PAIR_SUM); a generic min/max/add
        # over tuples means lexicographic-compare / concatenation and
        # takes the fn path below.
        vals = sb.values
        if vals.dtype == np.bool_:
            # Python semantics: True + True == 2; promote before folding
            # (min/max could stay bool, but a uniform int64 lane is simpler and
            # round-trips bools as 0/1 exactly like the reference's binop).
            vals = vals.astype(np.int64)
        elif vals.dtype == np.uint64 and op.kind == "sum":
            # uint64 sums wrap silently in numpy's host reduceat; when even
            # the conservative whole-array bound (n * max) fits int64 the
            # checked int64 path is exact, otherwise fold as Python ints.
            # min/max stay native uint64 — reduceat compares exactly there,
            # and _device_fold_exact keeps uint64 off the 32-bit lanes.
            if not len(vals) or len(vals) * int(vals.max()) <= _I64_MAX:
                vals = vals.astype(np.int64)
            else:
                ov = np.empty(len(vals), dtype=object)
                ov[:] = [int(x) for x in vals]
                vals = ov
        elif (op.kind == "sum" and vals.dtype.kind in "iu"
                and vals.dtype.itemsize < 8):
            # Narrow int sums wrap silently in both reduceat and the 32-bit
            # device lanes; the reference folds in arbitrary-precision Python
            # ints, so promote to int64 (then the int64 exactness check below
            # governs device eligibility as usual).
            vals = vals.astype(np.int64)
        if (settings.use_device_for(n)
                and _device_fold_exact(vals, op.kind)):
            # Segment ids must come from the collision-repaired group bounds,
            # not raw (h1,h2) adjacency — after a 64-bit collision the repaired
            # starts split a hash-run into multiple real-key groups.
            import jax as _jax
            if not _jax.config.jax_enable_x64:
                # Explicit lossless cast into the 32-bit device lanes
                # (_device_fold_exact guaranteed representability).
                if vals.dtype == np.int64:
                    vals = vals.astype(np.int32)
            from . import devtime
            seg_ids = np.repeat(np.arange(ng, dtype=np.int64), ends - starts)
            npad = _pow2(n)
            ng_pad = _pow2(ng)
            if npad != n:
                pad_val = {"sum": 0, "min": vals.dtype.type(np.inf) if vals.dtype.kind == "f" else np.iinfo(vals.dtype).max,
                           "max": vals.dtype.type(-np.inf) if vals.dtype.kind == "f" else np.iinfo(vals.dtype).min}[op.kind]
                pad_spec = ((0, npad - n), (0, 0)) if vals.ndim == 2 else (0, npad - n)
                vals = np.pad(vals, pad_spec, constant_values=pad_val)
                seg_ids = np.pad(seg_ids, (0, npad - n), constant_values=ng_pad - 1)
            with devtime.track("device"):
                folded = np.asarray(
                    _segment_fold_jit(op.kind, ng_pad)(vals, seg_ids.astype(np.int32)))[:ng]
            # padding contributed only to the last (possibly real) segment when
            # ng == ng_pad and op == sum with pad 0 / min with inf — safe by
            # construction of pad values going to segment ng_pad-1 only if
            # ng < ng_pad; otherwise pad rows land in the real last segment with
            # identity pad values, which is still correct.
        else:
            ufunc = _NP_FOLD[op.kind]
            folded = ufunc.reduceat(vals, starts)
        return Block(keys, folded, kh1, kh2)

    # Host generic fold.  C-level lane conversion (pylist unboxes numpy
    # scalars, so a user binop never sees an np.int64 that would wrap
    # silently) happens in bounded windows — whole-lane boxing would
    # multiply the footprint of near-budget partitions, the same
    # discipline Block.iter_pairs applies.
    from ..blocks import pylist

    W = 65536
    out_vals = [None] * ng
    fn = op.fn
    varr = sb.values
    gi = 0
    while gi < ng:
        s0 = int(starts[gi])
        e0 = int(ends[gi])
        if e0 - s0 > W:
            # One oversized group: fold it across bounded boxed windows,
            # carrying the accumulator.
            acc = None
            first = True
            for w0 in range(s0, e0, W):
                it = iter(pylist(varr[w0:min(e0, w0 + W)]))
                if first:
                    acc = next(it)
                    first = False
                for v in it:
                    acc = fn(acc, v)
            out_vals[gi] = acc
            gi += 1
            continue
        # A run of whole groups fitting one window: one conversion, tight
        # per-group loops over local offsets.
        ge = gi + 1
        while ge < ng and int(ends[ge]) - s0 <= W:
            ge += 1
        win = pylist(varr[s0:int(ends[ge - 1])])
        ls = (starts[gi:ge] - s0).tolist()
        le = (ends[gi:ge] - s0).tolist()
        for i in range(ge - gi):
            acc = win[ls[i]]
            for j in range(ls[i] + 1, le[i]):
                acc = fn(acc, win[j])
            out_vals[gi + i] = acc
        gi = ge
    return Block(keys, _column_from_list(out_vals), kh1, kh2)


def fold_block(block, op):
    """sort_and_group + fold_sorted in one call (map-side combine compaction)."""
    return fold_sorted(sort_and_group(block), op)
