"""The mesh keyed shuffle: local fold → all_to_all → final fold.

This is the TPU-native ``DefaultShuffler`` (reference base.py:416-433): where
the reference hash-routes every record to partition files on a shared
filesystem, this routes compacted (hash, value) pairs across the device mesh
with a single fixed-shape ``lax.all_to_all`` over the ICI, inside one
``shard_map`` program:

1. **Local combine** (communication avoidance — the reference's
   ``PartialReduceCombiner``/``ReducedWriter`` map-side pass, SURVEY §3.3):
   sort the device-local records by their 64-bit hash pair and segment-fold,
   so at most one record per distinct key crosses the wire.
2. **Route**: destination device = ``h1 % n_devices``.  Each device packs a
   ``[D, C]`` capacity buffer per destination (MoE-style fixed capacity,
   ``settings.shuffle_capacity_factor``); overflow is *detected* (psum'd
   count) and the host wrapper retries with doubled capacity, so results are
   never silently dropped.
3. **Exchange**: ``lax.all_to_all`` — row j of the receive buffer is what
   device j sent us.
4. **Final fold**: flatten, sort, segment-fold the received pairs.

Exactness: grouping is on the full (h1, h2) 64-bit pair.  Distinct real keys
colliding in all 64 bits are astronomically rare and are repaired at the host
boundary when real keys materialize (same contract as the single-chip path,
ops/segment.py).

Everything is shape-static and data-independent-control-flow, so XLA compiles
one program per (N_local, D, C, dtype) bucket.
"""

import functools

import numpy as np

from .. import settings
from ..ops.segment import sort_perm
from .mesh import mesh_size

_INVALID_SLOT_PAD = 1  # extra scatter slot that swallows dropped writes


def _segments(inv, h1, h2):
    """Boolean starts for runs of equal (inv, h1, h2) over sorted arrays."""
    import jax.numpy as jnp

    n = h1.shape[0]
    iota = jnp.arange(n, dtype=jnp.int32)
    prev_ne = ((h1 != jnp.roll(h1, 1)) | (h2 != jnp.roll(h2, 1))
               | (inv != jnp.roll(inv, 1)))
    return jnp.where(iota == 0, True, prev_ne)


def _local_fold(inv, h1, h2, v, kind, nonneg_sum=False):
    """Sort by (validity, h1, h2) and fold values per segment.  Returns
    (inv, h1, h2, v) arrays of the same length: one live entry per segment,
    dead entries marked invalid.

    Two lowerings, selected statically:

    - ``nonneg_sum`` (the count/len/doc-freq hot path): pure scan fold —
      sort, then segment totals land at segment *end* positions via
      ``cumsum`` + a ``cummax``-carried start offset.  No scatter at all:
      XLA's TPU scatter serializes random updates while sort and scan are
      bandwidth-bound.  Exact because the host wrapper only sets the
      flag for signed integer values whose *global* sum fits the lane dtype,
      so the running cumsum cannot wrap and is order-exact.
    - otherwise: segment_sum/min/max scatters into segment-id slots (handles
      negative sums and min/max, where a monotone carried scan doesn't
      apply).
    """
    import jax
    import jax.numpy as jnp

    n = h1.shape[0]
    perm = sort_perm(inv, h1, h2)
    inv, h1, h2, v = (jnp.take(a, perm) for a in (inv, h1, h2, v))
    starts = _segments(inv, h1, h2)

    if nonneg_sum and kind == "sum":
        return _scan_fold_sorted(inv, h1, h2, v, starts)

    seg_id = jnp.cumsum(starts.astype(jnp.int32)) - 1
    if kind == "sum":
        folded = jax.ops.segment_sum(v, seg_id, num_segments=n)
    elif kind == "min":
        folded = jax.ops.segment_min(v, seg_id, num_segments=n)
    elif kind == "max":
        folded = jax.ops.segment_max(v, seg_id, num_segments=n)
    else:
        raise ValueError(kind)

    ns = n  # segments indexed [0, n)
    seg_h1 = jax.ops.segment_max(h1, seg_id, num_segments=ns)
    seg_h2 = jax.ops.segment_max(h2, seg_id, num_segments=ns)
    # A segment is live iff it contains at least one valid record; invalid
    # records sort last so any segment containing them is all-invalid.
    live = jax.ops.segment_max(
        jnp.where(inv == 0, jnp.int32(1), jnp.int32(0)), seg_id,
        num_segments=ns)
    n_segs = jnp.sum(starts.astype(jnp.int32))
    in_range = jnp.arange(ns, dtype=jnp.int32) < n_segs
    live = (live == 1) & in_range
    return (jnp.where(live, jnp.uint32(0), jnp.uint32(1)),
            seg_h1, seg_h2, folded)


def _scan_fold_sorted(inv, h1, h2, v, starts=None):
    """The post-sort scan chain of the nonneg-sum lowering (see
    _local_fold): segment totals land at segment-end positions via cumsum +
    a cummax-carried start offset, no scatters.  Exposed separately so
    benchmarks/pallas_bench.py can compare it against the fused Pallas
    kernel on identical pre-sorted inputs."""
    import jax.numpy as jnp
    from jax import lax

    if starts is None:
        starts = _segments(inv, h1, h2)
    ends = jnp.concatenate(
        [starts[1:], jnp.ones((1,), dtype=starts.dtype)])
    csum = jnp.cumsum(v)
    ex = csum - v  # exclusive prefix, nonneg + monotone by assumption
    start_ex = lax.cummax(jnp.where(starts, ex, -1))
    tot = jnp.where(ends, csum - start_ex, 0).astype(v.dtype)
    # The end entry of a segment carries the segment's own (h1, h2);
    # invalid records sort last and form all-invalid segments.
    live = ends & (inv == 0)
    return (jnp.where(live, jnp.uint32(0), jnp.uint32(1)), h1, h2, tot)


def _pack_by_dest(inv, h1, h2, v, n_dev, capacity):
    """Scatter live entries into fixed [D, C] per-destination buffers.
    Returns (send_valid, send_h1, send_h2, send_v, n_dropped)."""
    import jax.numpy as jnp
    from jax import lax

    n = h1.shape[0]
    dest = (h1 % jnp.uint32(n_dev)).astype(jnp.uint32)
    # Order by (validity, dest) so each destination's entries are
    # contiguous: invalid entries take the key n_dev, past every dest.
    perm = sort_perm(jnp.where(inv == 0, dest, jnp.uint32(n_dev)))
    inv, dest, h1, h2, v = (jnp.take(a, perm)
                            for a in (inv, dest, h1, h2, v))
    iota = jnp.arange(n, dtype=jnp.int32)
    new_group = jnp.where(
        iota == 0, True,
        (dest != jnp.roll(dest, 1)) | (inv != jnp.roll(inv, 1)))
    start_iota = lax.cummax(jnp.where(new_group, iota, 0))
    rank = iota - start_iota

    valid = inv == 0
    keep = valid & (rank < capacity)
    dropped = jnp.sum(valid & (rank >= capacity)).astype(jnp.int32)

    flat = n_dev * capacity
    slot = jnp.where(keep, dest.astype(jnp.int32) * capacity + rank, flat)
    buf_h1 = jnp.zeros(flat + _INVALID_SLOT_PAD, dtype=h1.dtype).at[slot].set(h1)
    buf_h2 = jnp.zeros(flat + _INVALID_SLOT_PAD, dtype=h2.dtype).at[slot].set(h2)
    buf_v = jnp.zeros(flat + _INVALID_SLOT_PAD, dtype=v.dtype).at[slot].set(v)
    buf_ok = jnp.zeros(flat + _INVALID_SLOT_PAD, dtype=jnp.uint32).at[slot].set(
        jnp.where(keep, jnp.uint32(1), jnp.uint32(0)))

    shape = (n_dev, capacity)
    return (buf_ok[:flat].reshape(shape), buf_h1[:flat].reshape(shape),
            buf_h2[:flat].reshape(shape), buf_v[:flat].reshape(shape), dropped)


@functools.lru_cache(maxsize=None)
def _build_fold_program(mesh, n_dev, n_local, capacity, kind, v_dtype_name,
                        axis, nonneg_sum=False, gather=False):
    """Compile the full shard_map keyed-fold program for one shape bucket.
    ``mesh`` participates in the cache key so re-meshing recompiles."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    v_dtype = jnp.dtype(v_dtype_name)

    def per_device(h1, h2, v, valid):
        # shapes: [n_local] each (the device-local shard)
        inv = jnp.where(valid == 1, jnp.uint32(0), jnp.uint32(1))

        # 1. local combine
        inv, h1, h2, v = _local_fold(inv, h1, h2, v, kind, nonneg_sum)

        # 2. pack per destination
        ok, sh1, sh2, sv, dropped = _pack_by_dest(inv, h1, h2, v, n_dev,
                                                  capacity)

        # 3. exchange over the mesh axis
        rok = lax.all_to_all(ok, axis, split_axis=0, concat_axis=0)
        rh1 = lax.all_to_all(sh1, axis, split_axis=0, concat_axis=0)
        rh2 = lax.all_to_all(sh2, axis, split_axis=0, concat_axis=0)
        rv = lax.all_to_all(sv, axis, split_axis=0, concat_axis=0)

        # 4. final fold over everything received (partial sums of nonneg
        # values stay nonneg, so the scan lowering remains applicable)
        flat = n_dev * capacity
        inv2 = jnp.where(rok.reshape(flat) == 1, jnp.uint32(0), jnp.uint32(1))
        inv2, fh1, fh2, fv = _local_fold(
            inv2, rh1.reshape(flat), rh2.reshape(flat), rv.reshape(flat),
            kind, nonneg_sum)

        total_dropped = lax.psum(dropped, axis)
        out_valid = jnp.where(inv2 == 0, jnp.uint32(1), jnp.uint32(0))
        if gather:
            # Multi-process runs cannot fetch axis-sharded outputs at the
            # host boundary (shards live on other hosts' devices), so
            # replicate results with one all_gather ring over ICI/DCN.
            fh1 = lax.all_gather(fh1, axis, tiled=True)
            fh2 = lax.all_gather(fh2, axis, tiled=True)
            fv = lax.all_gather(fv, axis, tiled=True)
            out_valid = lax.all_gather(out_valid, axis, tiled=True)
        return fh1, fh2, fv, out_valid, total_dropped

    def mesh_fold(h1, h2, v, valid):
        out_spec = P() if gather else P(axis)
        kwargs = {}
        if gather:
            # all_gather output IS replicated; the varying-axes inference
            # can't prove it, so disable the check for this variant.
            kwargs["check_vma"] = False
        return jax.shard_map(
            per_device,
            mesh=mesh,
            in_specs=(P(axis), P(axis), P(axis), P(axis)),
            out_specs=(out_spec, out_spec, out_spec, out_spec, P()),
            **kwargs)(h1, h2, v, valid)

    return jax.jit(mesh_fold)


def _pad_pow2(n, floor=8):
    return max(floor, 1 << max(0, (n - 1).bit_length()))


_I32_MAX = 2 ** 31 - 1
_I64_MAX = 2 ** 63 - 1


def _lane_safe_values(v, kind):
    """Make values exact in the device lanes, or refuse loudly.

    With jax_enable_x64 off the mesh program runs 32-bit lanes; silent
    truncation would corrupt folds, so every dtype is whitelisted: floats
    ride as float32 (float64 refuses — precision), every integer dtype
    (signed, unsigned, any width) exact-casts into the checked int32 lane or
    refuses (same contract as the single-chip path, which falls back to
    exact host folds — ops/segment.py _device_fold_exact)."""
    import jax

    if v.dtype == object:
        raise ValueError("object values cannot ride the mesh fold lanes")
    if jax.config.jax_enable_x64:
        return v
    if v.dtype == np.float32:
        return v
    if v.dtype == np.float16:
        return v.astype(np.float32)  # exact widening
    if v.dtype == np.float64:
        raise ValueError(
            "float64 values would silently fold at float32 precision on "
            "device; pass float32 explicitly or enable jax_enable_x64")
    if v.dtype == np.bool_ or v.dtype.kind in "iu":
        if v.dtype == np.uint64 and len(v) and int(v.max()) > _I64_MAX:
            raise ValueError(
                "uint64 values exceed the device fold lanes; "
                "enable jax_enable_x64 or pre-scale")
        v64 = v.astype(np.int64)
        if not len(v64):
            return v64.astype(np.int32)
        lo, hi = int(v64.min()), int(v64.max())
        in_range = lo >= -_I32_MAX - 1 and hi <= _I32_MAX
        if in_range and (kind != "sum"
                         or int(np.abs(v64).sum()) <= _I32_MAX):
            return v64.astype(np.int32)
        raise ValueError(
            "integer values exceed the 32-bit device fold lanes "
            "(min={}, max={}); enable jax_enable_x64 or pre-scale".format(
                lo, hi))
    raise ValueError(
        "unsupported value dtype {} for mesh folds".format(v.dtype))


def mesh_keyed_fold(mesh, h1, h2, v, kind="sum", capacity_factor=None,
                    raw=False):
    """Distributed keyed fold over a device mesh.

    ``h1``/``h2``: uint32 hash lanes, ``v``: numeric values (int32/int64/
    float32 — int64 values fold in int32 lanes unless x64 is enabled).
    Returns ``(h1, h2, v)`` numpy arrays with one entry per distinct (h1, h2)
    pair, in unspecified order.  Retries with doubled capacity on overflow, so
    the result is complete regardless of key skew.

    ``raw=True`` keeps the result DEVICE-RESIDENT: returns the padded
    ``(h1, h2, v, ok)`` jax arrays (ok == 1 marks live entries) without the
    host fetch/compact, so callers accumulating partials across windows
    (runner._mesh_reduce) never round-trip intermediates through the host —
    they re-fold partials with :func:`mesh_keyed_refold` and fetch once.
    """
    import jax

    n_dev = mesh_size(mesh)
    total = len(h1)
    if total == 0:
        if raw:
            import jax.numpy as jnp

            z = jnp.zeros(0, jnp.uint32)
            # lane-cast even when empty so the dtype matches non-empty
            # partials a caller may mix this with in mesh_keyed_refold
            ev = _lane_safe_values(np.asarray(v)[:0], kind)
            return z, z, jnp.asarray(ev), z
        return (np.empty(0, np.uint32), np.empty(0, np.uint32),
                np.asarray(v)[:0])

    n_local = _pad_pow2(-(-total // n_dev))
    padded = n_local * n_dev
    ph1 = np.zeros(padded, dtype=np.uint32)
    ph2 = np.zeros(padded, dtype=np.uint32)
    v = _lane_safe_values(np.asarray(v), kind)
    pv = np.zeros(padded, dtype=v.dtype)
    pvalid = np.zeros(padded, dtype=np.uint32)
    ph1[:total] = h1
    ph2[:total] = h2
    pv[:total] = v
    pvalid[:total] = 1

    factor = capacity_factor or settings.shuffle_capacity_factor
    # Integer nonneg sums (count/len/doc-freq — the hot aggregations) take
    # the scan fold lowering (padding rows are zero, so they cannot break
    # the nonneg invariant).  The lowering needs (a) a signed dtype — its -1
    # start sentinel wraps on unsigned lanes — and (b) a global-cumsum bound
    # in the lane dtype, not just per-key bounds: with x64 off the
    # _lane_safe_values cast above already proved abs-sum <= int32 max; with
    # x64 on the values passed through unchecked, so bound them here.
    nonneg = False
    if (kind == "sum" and v.dtype.kind == "i"
            and (not len(v) or int(v.min()) >= 0)):
        if not len(v):
            nonneg = True
        elif v.dtype == np.int32:
            if jax.config.jax_enable_x64:
                nonneg = int(v.sum(dtype=np.int64)) <= _I32_MAX
            else:
                nonneg = True  # abs-sum check ran in _lane_safe_values
        elif v.dtype == np.int64:
            nonneg = len(v) * int(v.max()) <= _I64_MAX
    fh1, fh2, fv, ok = _run_fold_padded(
        mesh, ph1, ph2, pv, pvalid, n_dev, n_local, kind, nonneg, factor)
    if raw:
        return fh1, fh2, fv, ok
    mask = np.asarray(ok) == 1
    return (np.asarray(fh1)[mask], np.asarray(fh2)[mask],
            np.asarray(fv)[mask])


def _run_fold_padded(mesh, h1, h2, v, valid, n_dev, n_local, kind, nonneg,
                     factor):
    """Shared capacity-retry loop over already-padded (host or device)
    arrays: compile the program for the current capacity bucket, run,
    double on overflow."""
    import jax

    from ..obs import trace as _trace
    from ..ops import devtime

    capacity = max(8, int(-(-n_local // n_dev) * factor))
    axis = settings.mesh_axis
    gather = jax.process_count() > 1
    while True:
        prog = _build_fold_program(mesh, n_dev, n_local, capacity, kind,
                                   np.dtype(v.dtype).name, axis, nonneg,
                                   gather)
        with devtime.track("device"), _trace.span(
                "collective", "keyed-fold:{}".format(kind),
                records=int(n_local * n_dev), capacity=int(capacity)):
            fh1, fh2, fv, ok, dropped = prog(h1, h2, v, valid)
            dropped = int(dropped)
        if dropped == 0:
            return fh1, fh2, fv, ok
        capacity *= 2


def mesh_keyed_refold(mesh, parts, kind, nonneg=False, capacity_factor=None):
    """Re-fold device-resident partials from ``mesh_keyed_fold(raw=True)``.

    ``parts``: list of (h1, h2, v, ok) jax arrays.  Everything — concat,
    padding, the collective fold — stays on device; only the overflow
    scalar is fetched per retry.  Lane safety is the CALLER's contract: it
    must bound the elementwise abs-sum across every window it folded (the
    engine tracks the running bound host-side before uploading windows),
    because partial magnitudes are bounded by element magnitudes.  All
    parts must share one value dtype (the engine guards this)."""
    n_dev = mesh_size(mesh)
    total = sum(int(p[0].shape[0]) for p in parts)
    n_local = _pad_pow2(-(-total // n_dev))
    h1, h2, v, valid = _concat_pad(n_local * n_dev)(
        [tuple(p) for p in parts])

    factor = capacity_factor or settings.shuffle_capacity_factor
    return _run_fold_padded(mesh, h1, h2, v, valid, n_dev, n_local, kind,
                            nonneg, factor)


@functools.lru_cache(maxsize=None)
def _concat_pad(padded):
    """One program: concatenate each lane of ``parts`` and zero-pad it to
    ``padded`` rows (one compile per list of part shapes, not eight)."""
    import jax
    import jax.numpy as jnp

    def concat_pad(parts):
        out = []
        for lane in range(4):
            cat = jnp.concatenate([p[lane] for p in parts])
            out.append(jnp.pad(cat, (0, padded - cat.shape[0])))
        return tuple(out)

    return jax.jit(concat_pad)


@functools.lru_cache(maxsize=None)
def _live_prefix_sort(n):
    """Stable sort moving live (ok == 1) entries to a prefix — the
    device-side half of :func:`compact_partial`."""
    import jax
    import jax.numpy as jnp

    def live_prefix_sort(h1, h2, v, ok):
        perm = sort_perm(jnp.where(ok == 1, jnp.uint32(0), jnp.uint32(1)))
        return tuple(jnp.take(a, perm) for a in (h1, h2, v))

    return jax.jit(live_prefix_sort)


def compact_partial(part):
    """Shrink a device-resident ``(h1, h2, v, ok)`` partial to (a pow2
    pad of) its LIVE entries.

    Fold programs return capacity-padded lanes — ~1.5x their input,
    dead rows included — so accumulating partials through repeated
    ``mesh_keyed_refold`` rounds grows the padded garbage geometrically
    even when the distinct-key count is tiny (each round re-feeds the
    previous round's dead pad).  One validity sort + a prefix slice per
    compaction round bounds every partial at the distinct-key count
    instead.  Costs one scalar fetch (the live count); shapes stay pow2,
    so compile buckets stay bounded."""
    import jax.numpy as jnp

    h1, h2, v, ok = part
    n = int(h1.shape[0])
    if n == 0:
        return part
    nlive = int(jnp.sum(jnp.where(ok == 1, 1, 0)))
    m = _pad_pow2(max(1, nlive))
    if m >= n:
        return part
    sh1, sh2, sv = _live_prefix_sort(n)(h1, h2, v, ok)
    okc = (jnp.arange(m, dtype=jnp.int32)
           < jnp.int32(nlive)).astype(jnp.uint32)
    return sh1[:m], sh2[:m], sv[:m], okc


def mesh_global_sum(mesh, v):
    """Global aggregate over the mesh: local sum + psum (the degenerate-key
    case — the reference's ``len``/global ``sum`` pipelines)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    n_dev = mesh_size(mesh)
    v = _lane_safe_values(np.asarray(v), "sum")
    total = len(v)
    n_local = max(1, -(-total // n_dev))
    padded = n_local * n_dev
    pv = np.zeros(padded, dtype=v.dtype)
    pv[:total] = v

    axis = settings.mesh_axis

    def global_sum(x):
        return jax.lax.psum(jnp.sum(x), axis)

    from ..obs import trace as _trace

    with _trace.span("collective", "global-sum", records=int(total)):
        out = jax.jit(jax.shard_map(
            global_sum, mesh=mesh,
            in_specs=(P(axis),), out_specs=P()))(pv)
    return np.asarray(out).item()
