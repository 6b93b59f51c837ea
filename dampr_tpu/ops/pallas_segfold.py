"""Pallas TPU kernel: fused segmented fold over hash-sorted records.

After the engine sorts records by (validity, h1, h2), the scan lowering in
:func:`dampr_tpu.parallel.shuffle._local_fold` computes per-segment totals
at segment-end positions with ~6 separate XLA passes (boundary compare,
shift, cumsum, cummax, two selects) — each a full HBM round-trip.  This
kernel fuses the whole post-sort chain into ONE pass: each grid step pulls
one tile of (h1, h2, v, inv) into VMEM, computes the flattened prefix sum
and the carried segment-start offset in-register, and writes the totals and
liveness mask; scalar carry state (previous element's keys/validity, the
running global prefix, the last segment-start's exclusive prefix) rides
SMEM across the sequential grid.

Lookahead: an element is a segment *end* iff the next element starts a new
segment, so the kernel reads a second view of the key arrays offset one
tile ahead (same buffers, shifted index_map) to see the first element of
the next tile; the final tile treats "next" as different (last element of
the array is always an end).

Exactness contract: identical to the scan lowering — nonnegative integer
values whose global sum fits the lane dtype (callers guarantee it: see
`mesh_keyed_fold`'s `nonneg` predicate), so the running prefix cannot wrap
and subtraction of exclusive prefixes is exact.

Like ops/pallas_fnv.py this is TPU-Mosaic code; CPU tests run it with
``interpret=True``.  The real-chip benchmark lives in
benchmarks/pallas_bench.py; whether it beats the XLA scan chain is not
measured yet (no unverified perf claims here).

Reference anchor: this is the hot half of the reference's combine path
(dampr/base.py:393-402 PartialReduceCombiner + dataset.py:84-117
ReducedWriter) — per-key accumulation — executed as one device pass.
"""

import functools

import numpy as np

_LANES = 128
_ROWS = 64  # 64 x 128 = 8192 records per tile (4 uint32 tiles = 128KB VMEM)


def _tile_elems():
    return _ROWS * _LANES


def _build_kernel():
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import pallas as pl

    def corner(x, r, l):
        """Scalar at static position (r, l) of a tile.  jnp integer
        indexing (``x[-1, -1]``) lowers through ``dynamic_slice`` even for
        constant indices, which this Mosaic version does not implement;
        a static ``lax.slice`` + single-element reduce does."""
        r = r % x.shape[0]
        l = l % x.shape[1]
        assert jnp.issubdtype(x.dtype, jnp.signedinteger), (
            "corner() requires signed tiles: Mosaic lacks unsigned "
            "reductions (callers convert hash/validity lanes to int32)")
        return jnp.sum(lax.slice(x, (r, l), (r + 1, l + 1)))

    def _iotas(shape):
        ri = lax.broadcasted_iota(jnp.int32, shape, 0)
        li = lax.broadcasted_iota(jnp.int32, shape, 1)
        return ri, li

    def shift_one(x, first):
        """Flattened-order shift-by-one of an (R, L) tile: element (r, l)
        receives (r, l-1), row starts receive the previous row's last lane,
        and (0, 0) receives ``first`` (the carried previous element).

        Built from ``pltpu.roll`` + iota masks: Mosaic rejects the natural
        width-1 column concatenates ("offset mismatch on non-concat
        dimension"), but full-tile rotates lower cleanly."""
        from jax.experimental.pallas import tpu as pltpu

        lane = pltpu.roll(x, 1, axis=1)       # (r, l) <- (r, (l-1) % L)
        wrap = pltpu.roll(lane, 1, axis=0)    # at l==0: (r, 0) <- (r-1, L-1)
        ri, li = _iotas(x.shape)
        s = jnp.where(li == 0, wrap, lane)
        return jnp.where((li == 0) & (ri == 0), first, s)

    def _scan(x, op, pad, axis):
        """Inclusive Hillis-Steele scan along one axis of a 2-D tile.
        Mosaic's TC lowering (this jax version) has no cumsum/cummax
        primitive, so the scan is log-depth shifted-operand steps built
        from concatenate/slice — which do lower."""
        n = x.shape[axis]
        d = 1
        while d < n:
            if axis == 1:
                pads = jnp.full((x.shape[0], d), pad, x.dtype)
                shifted = jnp.concatenate([pads, x[:, :-d]], axis=1)
            else:
                pads = jnp.full((d, x.shape[1]), pad, x.dtype)
                shifted = jnp.concatenate([pads, x[:-d, :]], axis=0)
            x = op(x, shifted)
            d *= 2
        return x

    def flat_cumsum(x):
        """Inclusive prefix sum of an (R, L) int32 tile in flattened
        row-major order: lane scan + carried row offsets."""
        row = _scan(x, jnp.add, 0, axis=1)
        # per-row totals broadcast across lanes, then scanned over rows so
        # the sublane scan runs at full lane width (a (R, 1) operand would
        # fight the (8, 128) tiling)
        row_tot = jnp.broadcast_to(row[:, -1:], x.shape)
        row_off_incl = _scan(row_tot, jnp.add, 0, axis=0)
        return row + (row_off_incl - row_tot)

    def flat_cummax(x):
        """Inclusive prefix max, flattened row-major order."""
        neg = jnp.iinfo(x.dtype).min
        row = _scan(x, jnp.maximum, neg, axis=1)
        row_max = jnp.broadcast_to(row[:, -1:], x.shape)
        row_carry = _scan(row_max, jnp.maximum, neg, axis=0)
        prev_rows = jnp.concatenate(
            [jnp.full((1, x.shape[1]), neg, x.dtype), row_carry[:-1]],
            axis=0)
        return jnp.maximum(row, prev_rows)

    def kernel(h1_ref, h2_ref, v_ref, inv_ref, nh1_ref, nh2_ref, ninv_ref,
               tot_ref, live_ref, carry_ref):
        # carry_ref (SMEM int64-free: 5 x int32-compatible slots):
        # [0] prev_h1 (as int32 bits), [1] prev_h2, [2] prev_inv,
        # [3] running exclusive prefix, [4] exclusive prefix at the
        #     current segment's start
        i = pl.program_id(0)
        n_i = pl.num_programs(0)

        @pl.when(i == 0)
        def _():
            carry_ref[0] = jnp.int32(0)
            carry_ref[1] = jnp.int32(0)
            carry_ref[2] = jnp.int32(2)  # impossible validity: forces start
            carry_ref[3] = jnp.int32(0)
            carry_ref[4] = jnp.int32(0)

        # All key/validity logic runs in int32 bitspace (same-width integer
        # conversion is modular, so equality is preserved): Mosaic lacks
        # unsigned reductions and some unsigned selects.
        h1 = h1_ref[:].astype(jnp.int32)
        h2 = h2_ref[:].astype(jnp.int32)
        v = v_ref[:]
        inv = inv_ref[:].astype(jnp.int32)

        ph1 = shift_one(h1, carry_ref[0].astype(h1.dtype))
        ph2 = shift_one(h2, carry_ref[1].astype(h2.dtype))
        pinv = shift_one(inv, carry_ref[2].astype(inv.dtype))
        starts = (h1 != ph1) | (h2 != ph2) | (inv != pinv)

        run = carry_ref[3]
        prefix = flat_cumsum(v) + run          # inclusive global prefix
        ex = prefix - v                        # exclusive global prefix

        # Exclusive prefix at each element's segment start: carried value
        # until the first start in this tile, then a running max of start
        # positions' ex (monotone because v >= 0).
        neg = jnp.iinfo(jnp.int32).min
        marked = jnp.where(starts, ex, neg)
        run_start_ex = jnp.maximum(flat_cummax(marked), carry_ref[4])

        # Ends: the next element (flattened order, with one-tile lookahead)
        # begins a new segment.  next_* of the last element comes from the
        # lookahead view; on the final tile it is forced different.
        last = n_i - 1
        # the forced "next" must differ from the LAST element so the
        # array's final record is always an end; +1 wraps and so always
        # differs in the h1 lane
        nxt_h1 = jnp.where(i == last, corner(h1, -1, -1) + 1,
                           corner(nh1_ref[:].astype(jnp.int32), 0, 0))
        nxt_h2 = jnp.where(i == last, corner(h2, -1, -1),
                           corner(nh2_ref[:].astype(jnp.int32), 0, 0))
        nxt_inv = jnp.where(i == last, jnp.int32(3),
                            corner(ninv_ref[:].astype(jnp.int32), 0, 0))
        nh1s = shift_back(h1, nxt_h1)
        nh2s = shift_back(h2, nxt_h2)
        ninvs = shift_back(inv, nxt_inv)
        ends = (h1 != nh1s) | (h2 != nh2s) | (inv != ninvs)

        tot_ref[:] = jnp.where(ends, prefix - run_start_ex, 0).astype(
            tot_ref.dtype)
        live_ref[:] = jnp.where(
            ends & (inv == 0), 1, 0).astype(live_ref.dtype)

        # Update carries for the next tile.
        carry_ref[0] = corner(h1, -1, -1).astype(jnp.int32)
        carry_ref[1] = corner(h2, -1, -1).astype(jnp.int32)
        carry_ref[2] = corner(inv, -1, -1).astype(jnp.int32)
        carry_ref[3] = corner(prefix, -1, -1)
        carry_ref[4] = corner(run_start_ex, -1, -1)

    def shift_back(x, nxt):
        """Flattened-order shift-backward-by-one: element (r, l) receives
        (r, l+1); row ends receive the next row's first lane; the tile's
        last element receives ``nxt``.  Same roll+mask construction as
        :func:`shift_one` (rolls take non-negative shifts: size-1 = -1)."""
        from jax.experimental.pallas import tpu as pltpu

        R, L = x.shape
        lane = pltpu.roll(x, L - 1, axis=1)   # (r, l) <- (r, (l+1) % L)
        wrap = pltpu.roll(lane, R - 1, axis=0)  # at l==L-1: <- (r+1, 0)
        ri, li = _iotas(x.shape)
        s = jnp.where(li == L - 1, wrap, lane)
        return jnp.where((li == L - 1) & (ri == R - 1), nxt, s)

    return kernel


@functools.lru_cache(maxsize=None)
def _segfold_call(n_tiles, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    kernel = _build_kernel()
    R, L = _ROWS, _LANES

    def tile_map(i):
        return (i, 0)

    def next_tile_map(i):
        # lookahead view: the first sublane-aligned row block of the next
        # tile (only its [0, 0] element is read), clamped on the final tile
        # (its values are ignored there — the kernel forces a difference).
        # Index units are (8, L) blocks: one tile spans R // 8 of them.
        per_tile = R // 8
        return (jnp.minimum((i + 1) * per_tile, n_tiles * per_tile - 1), 0)

    def call(h1, h2, v, inv):
        return pl.pallas_call(
            kernel,
            grid=(n_tiles,),
            in_specs=[
                pl.BlockSpec((R, L), tile_map),
                pl.BlockSpec((R, L), tile_map),
                pl.BlockSpec((R, L), tile_map),
                pl.BlockSpec((R, L), tile_map),
                pl.BlockSpec((8, L), next_tile_map),
                pl.BlockSpec((8, L), next_tile_map),
                pl.BlockSpec((8, L), next_tile_map),
            ],
            out_specs=[
                pl.BlockSpec((R, L), tile_map),
                pl.BlockSpec((R, L), tile_map),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((n_tiles * R, L), jnp.int32),
                jax.ShapeDtypeStruct((n_tiles * R, L), jnp.uint32),
            ],
            scratch_shapes=[pltpu.SMEM((5,), jnp.int32)],
            interpret=interpret,
        )(h1, h2, v, inv, h1, h2, inv)

    return jax.jit(call)


def segfold_sorted(h1, h2, v, inv, interpret=False):
    """Per-segment totals of hash-sorted records, one fused device pass.

    Inputs are 1-D device or host arrays sorted by (inv, h1, h2): uint32
    hash lanes, int32 nonneg values, uint32 validity (0 = valid).  Returns
    (tot, live) 1-D arrays: ``tot[j]`` is the segment total where ``j`` is
    the segment's last position and ``live[j] == 1``; 0/0 elsewhere.  The
    caller pads to a multiple of the tile size with invalid rows.
    """
    import jax.numpy as jnp

    n = len(h1)
    te = _tile_elems()
    assert n % te == 0, "caller pads to a multiple of %d" % te
    n_tiles = n // te
    R, L = _ROWS, _LANES
    shape = (n_tiles * R, L)
    call = _segfold_call(n_tiles, interpret)
    tot, live = call(
        jnp.asarray(h1).reshape(shape), jnp.asarray(h2).reshape(shape),
        jnp.asarray(v).reshape(shape), jnp.asarray(inv).reshape(shape))
    return jnp.asarray(tot).reshape(n), jnp.asarray(live).reshape(n)


def segfold_reference(h1, h2, v, inv):
    """Host oracle for tests: exact per-segment totals at end positions."""
    n = len(h1)
    tot = np.zeros(n, dtype=np.int64)
    live = np.zeros(n, dtype=np.uint32)
    at = 0
    while at < n:
        end = at
        while (end + 1 < n and h1[end + 1] == h1[at]
               and h2[end + 1] == h2[at] and inv[end + 1] == inv[at]):
            end += 1
        tot[end] = int(v[at:end + 1].sum())
        live[end] = 1 if inv[at] == 0 else 0
        at = end + 1
    return tot, live
