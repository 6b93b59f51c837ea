"""Cross-stage device-resident handoff: the stage vocabulary tier.

This is the execution half of the plan's ``handoff="device"`` edge
decision (:mod:`dampr_tpu.plan.lower`): when a lowered scanner map feeds
a device-lowered associative sum fold, the map's program outputs never
round-trip through the host spill path (d2h fetch -> host combine ->
pickle -> frame encode -> spill -> re-read -> h2d).  Instead the stage
keeps a device-resident vocabulary on each chip, in two parts.

The shared part, :class:`StageVocab`, is one per lowered handoff stage
and chip (:class:`StageVocabs`, keyed by ``parallel.placement.current()``)
for one stage of one ``run()``; every map job placed on that chip uses
it, and it goes with the stage:

- the slot map — ``bytes2slot``, ``keys``, ``slot_bytes`` and the hash
  lanes ``h1``/``h2`` — append-only, so a slot id stays valid in every
  job's accumulator;
- ``tab_index`` — a hashed bucket index of ``2 * cap`` ``(h1, slot)``
  rows: each token reads its two candidate buckets, derived from its
  FNV ``h1``, with two row gathers (no search loop); the host places
  every new slot there (two-choice cuckoo, :meth:`StageVocab._place`)
  and publishes the changed rows with the next batch;
- ``tab_mat``/``tab_lens`` — the vocabulary's raw byte rows, so every
  probe HIT is verified byte-for-byte inside the program (a 64-bit — or
  32-bit — hash collision can never merge distinct tokens: mismatching
  bytes route to the exact host miss path instead).

The per-job part, :class:`HandoffVocab`, is the job's own ``acc``
(per-slot count accumulator, int32 lanes, int64 under x64, updated in
place by every batch with buffer donation where the backend supports
it), its lane guard, its table/classic mode and its degrade state.

Concurrency: slot inserts and table publication take the shared lock.
A table dispatch takes one consistent snapshot ``(cap, Lcap, tab_index,
tab_mat, tab_lens)`` under it and runs outside it.  A new table version
is a new set of arrays — the shared arrays are never donated, because
another job's in-flight probe may still read the version it took.  A
probe against an older snapshot stays exact: its hits are byte-verified
against that snapshot's rows, and anything newer misses to the host
path, which finds the slot.  When the shared ``cap`` grew, the job grows
its accumulator to the snapshot's ``cap + 1`` (the last row is the
program's sink) before it dispatches.

Batches whose tokens are mostly in the table run the **table program**:
single-lane FNV + bucket probe + byte verify + (for per-line dedup) a
two-lane ``(slot, line)`` sort + scatter-add — roughly a third of the
classic program's cost, because the five-lane ``lax.sort`` over the full
token stream disappears.  One job per (stage, chip) bootstraps the
vocabulary through the classic hash->sort->segment program
(:mod:`.lower`), whose drained survivors seed the table; the seed ends
at that job's first drain, and the chip's other jobs wait for it at their
first dispatch and start in table mode.  This is the one bootstrap, on
every backend.  A job whose table batches miss past the revert bar
(vocabulary shift) goes back to classic batches on its own.

At job end the job's accumulator becomes per-partition HBM-resident
:class:`~dampr_tpu.storage.BlockRef` s (``BlockRef.from_device_lanes``)
that the consuming fold (``runner._mesh_reduce``) consumes in place —
only the slots this job counted: other jobs' words, and the slots a
failed or speculative attempt inserted, read zero there and never enter
a partial.

Exactness contract: every count lands in a slot either (a) verified
byte-identical to the slot's bytes inside a program, or (b) through the
host miss/fallback path keyed by canonical UTF-8 bytes.  Degrades — HBM
budget exceeded, int32 overflow risk, vocabulary overflow — flush the
job's accumulator into one hash-sorted host block and hand the rest of
the job to the classic spill path, byte-identically; the shared table
stays for the chip's other jobs.
"""

import functools
import logging
import threading

import numpy as np

from .. import settings
from ..obs import trace as _trace
from . import devtime

log = logging.getLogger("dampr_tpu.ops.handoff")

#: Classic-drain lane bytes per padded slot the table program never
#: fetches: sh1 (4) + sh2 (4) + tot (4) + live (1) + rep_orig (4).
CLASSIC_DRAIN_BYTES_PER_SLOT = 17

#: Bootstrap heuristic: a classic batch whose NEW-vocabulary-slots-per-
#: batch-token fraction falls under the enter bar switches the job to
#: the table program; a table batch whose miss fraction exceeds the
#: revert bar switches back (vocabulary shift).  Both signals estimate
#: the same quantity — the next batch's miss rate, whose host cost is
#: roughly the classic per-token cost — so the bars sit where the
#: table's drain saving (17 bytes/slot never fetched) beats the miss
#: path; enter is slightly stricter than revert for hysteresis.  On
#: Zipf text one 256k-token classic batch seeds ~93% token coverage
#: (new_frac ~0.07), so jobs engage after their FIRST drain.  Pure
#: performance knobs — results are identical either way.
_TABLE_ENTER_NEW_FRAC = 0.20
_TABLE_REVERT_MISS_FRAC = 0.25

_I32_GUARD = 1 << 30
_I64_GUARD = 1 << 62

#: Smallest vocabulary capacity (slots); the table grows in powers of two.
_MIN_CAP = 4096

#: The bucket index holds ``2 * cap`` rows, so it is at most half full.
#: A token's two buckets are the low bits of its FNV ``h1`` and the high
#: bits of ``h1 * _GOLDEN`` (Knuth's multiplicative hash).  An insert
#: whose buckets are both taken relocates occupants to their other
#: bucket at most ``_MAX_KICKS`` times; the entry then left homeless
#: stays out of the index, and its tokens miss to the exact host path.
_GOLDEN = 0x9E3779B1
_MAX_KICKS = 32

#: Rows a host-to-device update of the table or an accumulator is cut
#: into: a small update pads to the first count, a larger one goes in
#: pieces of the second.  The jobs sharing a vocabulary insert in an
#: order the host's scheduling decides, so the update sizes vary from
#: run to run; these two are the only shapes they compile.
_UPDATE_ROWS = (256, 4096)


def _pow2(n, floor=4096):
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))


def _pieces(n):
    """``(start, stop, rows)`` pieces of an ``n``-row update, each padded
    to ``rows`` (:data:`_UPDATE_ROWS`)."""
    small, big = _UPDATE_ROWS
    if n <= small:
        return [(0, n, small)]
    return [(a, min(n, a + big), big) for a in range(0, n, big)]


def _pad(x, rows, value=0):
    """``x`` padded along its first axis to ``rows`` with ``value``."""
    return np.pad(x, [(0, rows - len(x))] + [(0, 0)] * (x.ndim - 1),
                  constant_values=value)


def _buckets(h1, rows):
    """The two buckets of ``h1`` in a ``rows``-row index (``rows`` a
    power of two): a Python int, or a ``uint64`` array of hashes.  The
    table program computes the same pair on the device in ``uint32``."""
    shift = 32 - (rows.bit_length() - 1)
    return h1 & (rows - 1), ((h1 * _GOLDEN) & 0xFFFFFFFF) >> shift


@functools.lru_cache(maxsize=None)
def _donate_ok():
    """Buffer donation is a no-op (with a warning) on CPU backends;
    donate only where shapes and platform permit."""
    return settings.backend() != "cpu"


@functools.lru_cache(maxsize=None)
def _acc_dtype():
    import jax

    return np.dtype(np.int64 if jax.config.jax_enable_x64 else np.int32)


#: Windowed-dedup span (tokens): batches whose longest line fits run
#: the shifted-compare dedup (K passes over the batch) instead of the
#: ~4x-costlier (slot, line) sort; the host picks the variant per batch
#: from the actual max tokens-per-line (``dedup_k=0`` = sort).
_DEDUP_WINDOW = 16


@functools.lru_cache(maxsize=None)
def _table_program(n, L, cap, Lcap, dedup, acc_dtype_name, dedup_k=0):
    """One compiled probe-and-count program per shape bucket.

    hash (single FNV lane) -> two bucket-row gathers from the index ->
    byte-verified hit mask -> dedup'd (slot, line) scatter-add into the
    donated accumulator.  Returns (acc, miss mask, miss count); misses
    (new vocabulary, slots not in the index, byte mismatches) are
    handled exactly on the host.

    ``dedup_k > 0``: every line in the batch spans at most ``dedup_k``
    tokens (host-verified per batch), so a duplicate (slot, line) pair
    sits within ``dedup_k`` positions of its first occurrence — K
    shifted compares replace the full (slot, line) sort."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .hashing import _FNV_OFFSET1, _FNV_PRIME1

    W = min(L, Lcap)
    rows = 2 * cap
    shift = 32 - (rows.bit_length() - 1)

    def table_probe(mat, lens, lines, tab_index, tab_mat, tab_lens, acc):
        h1 = jnp.full((n,), _FNV_OFFSET1, dtype=jnp.uint32)

        def body(c, a):
            active = c < lens
            byte = mat[:, c].astype(jnp.uint32)
            return jnp.where(active, (a ^ byte) * _FNV_PRIME1, a)

        h1 = lax.fori_loop(0, L, body, h1)

        # Two independent row gathers: a bucket holds the token's slot
        # when its h1 matches and it is not empty (slot ``cap``); the
        # first bucket wins.  The byte verify below decides the hit.
        r1 = jnp.take(tab_index, (h1 & (rows - 1)).astype(jnp.int32),
                      axis=0, mode="clip")
        r2 = jnp.take(tab_index,
                      ((h1 * jnp.uint32(_GOLDEN)) >> shift).astype(jnp.int32),
                      axis=0, mode="clip")
        in1 = (r1[:, 0] == h1) & (r1[:, 1] < cap)
        in2 = (r2[:, 0] == h1) & (r2[:, 1] < cap)
        cand = jnp.where(in1, r1[:, 1],
                         jnp.where(in2, r2[:, 1], 0)).astype(jnp.int32)
        valid = lens > 0
        same = valid & (in1 | in2) \
            & (jnp.take(tab_lens, cand, mode="clip") == lens)
        rep = jnp.take(tab_mat, cand, axis=0, mode="clip")
        if Lcap > W:
            rep = lax.slice(rep, (0, 0), (n, W))
        mw = mat if L == W else lax.slice(mat, (0, 0), (n, W))
        # Byte columns past a token's length are zero in BOTH the batch
        # matrix and the table rows, and lengths already matched, so a
        # W-column compare is a full-token compare.
        same = same & jnp.all(rep == mw, axis=1)
        miss = valid & ~same
        sink = jnp.int32(cap)
        if dedup and dedup_k:
            # Per-line first occurrence, windowed: line ids are
            # non-decreasing (tokens arrive in document order) and no
            # line spans more than dedup_k tokens, so a duplicate
            # (slot, line) pair lies within dedup_k positions of its
            # first occurrence — K shifted compares beat the sort ~4x.
            slot_key = jnp.where(same, cand, sink)
            li = lines.astype(jnp.int32)
            dup = jnp.zeros((n,), dtype=bool)
            for k in range(1, dedup_k + 1):
                dup = dup.at[k:].set(
                    dup[k:] | ((slot_key[k:] == slot_key[:-k])
                               & (li[k:] == li[:-k])
                               & (slot_key[k:] < sink)))
            contrib = jnp.where(~dup & (slot_key < sink), 1, 0)
            acc = acc.at[slot_key].add(contrib.astype(acc.dtype))
        elif dedup:
            # Per-line first occurrence (DocFreq): sort hits by
            # (slot, line) — two int32 lanes instead of the classic
            # five-lane token sort — and count segment starts per slot.
            slot_key = jnp.where(same, cand, sink)
            s_slot, s_line = lax.sort(
                (slot_key, lines.astype(jnp.int32)), num_keys=2,
                is_stable=False)
            first = jnp.ones((n,), dtype=bool).at[1:].set(
                (s_slot[1:] != s_slot[:-1]) | (s_line[1:] != s_line[:-1]))
            contrib = jnp.where(first & (s_slot < sink), 1, 0)
            acc = acc.at[s_slot].add(contrib.astype(acc.dtype))
        else:
            acc = acc.at[jnp.where(same, cand, sink)].add(
                jnp.where(same, 1, 0).astype(acc.dtype))
        return acc, miss, jnp.sum(miss.astype(jnp.int32))

    kwargs = {"donate_argnums": (6,)} if _donate_ok() else {}
    return jax.jit(table_probe, **kwargs)


@functools.lru_cache(maxsize=None)
def _scatter_program():
    """Host-side contributions (bootstrap drains, misses, long tokens,
    fallback windows) fold into the accumulator with one scatter-add."""
    import jax

    def table_scatter(acc, slots, vals):
        return acc.at[slots].add(vals)

    kwargs = {"donate_argnums": (0,)} if _donate_ok() else {}
    return jax.jit(table_scatter, **kwargs)


@functools.lru_cache(maxsize=None)
def _set_rows_program():
    """Stage vocabulary rows and changed index rows into a new version of
    the device table; slots and positions past the table (the padding)
    drop.  Nothing is donated: the old version's arrays may still feed
    another job's in-flight probe."""
    import jax

    def table_set_rows(tab_mat, tab_lens, tab_index, slots, rows, lens,
                       pos, index_rows):
        return (tab_mat.at[slots].set(rows, mode="drop"),
                tab_lens.at[slots].set(lens, mode="drop"),
                tab_index.at[pos].set(index_rows, mode="drop"))

    return jax.jit(table_set_rows)


@functools.lru_cache(maxsize=None)
def _finalize_program():
    """Gather the accumulator into partition order (``idx``; pad rows
    read the sink slot)."""
    import jax
    import jax.numpy as jnp

    def table_finalize(acc, idx):
        return jnp.take(acc, idx)

    return jax.jit(table_finalize)


def _slice(x, start, width):
    """``x[start:start + width]`` through one compiled slice per
    ``(x.shape, width)``: the start is a traced argument."""
    return _slice_program(width)(x, np.int32(start))


@functools.lru_cache(maxsize=None)
def _slice_program(width):
    import jax
    from jax import lax

    def lane_slice(x, start):
        return lax.dynamic_slice(x, (start,), (width,))

    return jax.jit(lane_slice)


def group_token_rows(buf, starts, lens, lines, dedup):
    """Exact host grouping of a token subset: length-prefixed byte rows
    through ``np.unique`` — colliding hashes can never merge distinct
    tokens — with per-line first-occurrence dedup when ``dedup``.
    Returns ``(uniq_rows, counts)``; ``uniq_rows[i, 0]`` is the token
    length, its bytes follow.  The ONE copy of this algorithm: both the
    classic collision fallback (``lower._host_batch``) and the handoff
    miss path absorb through it, so their byte-identity can never drift
    apart.  MIRROR of ``text._numpy_counts_block``'s short-token path
    parameterized on precomputed bounds — a semantic change to either
    grouping MUST land in both, or the equivalence suite's parity pins
    will catch it."""
    n = len(starts)
    L = int(lens.max())
    idx = starts[:, None] + np.arange(L, dtype=np.int64)[None, :]
    np.clip(idx, 0, len(buf) - 1, out=idx)
    mat = np.where(np.arange(L, dtype=np.int32)[None, :]
                   < lens[:, None], buf[idx], 0)
    rows = np.empty((n, L + 1), dtype=np.uint8)
    rows[:, 0] = lens
    rows[:, 1:] = mat
    uniq, inverse = np.unique(rows, axis=0, return_inverse=True)
    inverse = inverse.reshape(-1)
    if dedup:
        combined = lines.astype(np.int64) * len(uniq) + inverse
        uc = np.unique(combined)
        counts = np.bincount(uc % len(uniq), minlength=len(uniq))
    else:
        counts = np.bincount(inverse, minlength=len(uniq))
    return uniq, counts


class _TableBatch(object):
    """One in-flight table-program dispatch (the double-buffer handle).
    ``miss_idx`` caches the fetched miss positions so a drain that
    degrades (or resolves after a degrade) can hand the missed tokens to
    the exact host emit path without re-fetching."""

    __slots__ = ("miss", "n_miss", "starts", "lens", "lines", "n",
                 "npad", "miss_idx")

    def __init__(self, miss, n_miss, starts, lens, lines, n, npad):
        self.miss = miss
        self.n_miss = n_miss
        self.starts = starts
        self.lens = lens
        self.lines = lines
        self.n = n
        self.npad = npad
        self.miss_idx = None


def _acc_bytes(cap):
    """Device bytes of one job's accumulator for a ``cap``-slot table
    (+ the sink row)."""
    return int((cap + 1) * _acc_dtype().itemsize)


def _table_bytes(cap, Lcap):
    """Device bytes of a ``cap``-slot shared table: byte rows, lengths
    and the ``2 * cap`` 8-byte index rows."""
    return int(cap * (Lcap + 4 + 16))


class StageVocab(object):
    """The shared part of a handoff vocabulary: slot map, hash lanes,
    byte rows and bucket index, host and device, for the map jobs of one
    stage placed on one chip (see the module docstring).

    ``budget`` is the sum of the shares of the jobs attached to it
    (:meth:`attach`): the table is charged once against it, together
    with one accumulator per attached job.  Where the jobs meet the same
    words, as the jobs over one corpus do, that is less than each job's
    own table and accumulator took, so such a stage fits wherever it
    fitted with a vocabulary per job."""

    def __init__(self, store):
        self.store = store
        self.lock = threading.Condition()
        self.budget = 0
        self.jobs = 0
        self.nslots = 0
        self.cap = 0
        self.Lcap = 8
        self.bytes2slot = {}
        self.keys = []        # decoded str per slot
        self.slot_bytes = []  # canonical utf-8 bytes per slot
        self.h1 = []          # python ints (host lanes)
        self.h2 = []
        self._pending_rows = []   # (slot, bytes) not yet on device
        # Host copy of the bucket index (``uint32[2 * cap, 2]`` rows of
        # (h1, slot), slot ``cap`` = empty); None until the next sync
        # builds it for the current cap.  ``_dirty`` holds the rows
        # changed since the device copy was published.
        self._index = None
        self._dirty = set()
        self.tab_index = None
        self.tab_mat = None
        self.tab_lens = None
        # Bootstrap: ``seeded`` once a job's first drain put the
        # vocabulary in; ``_seeding`` is the job doing it meanwhile.
        self.seeded = False
        self._seeding = None

    # -- jobs ----------------------------------------------------------------
    def attach(self, share):
        with self.lock:
            self.budget += share
            self.jobs += 1

    def detach(self, share):
        with self.lock:
            self.budget -= share
            self.jobs -= 1

    def join(self, job):
        """A job's first dispatch: True when the vocabulary is seeded
        (the job starts in table mode); False when ``job`` now seeds it
        and must call :meth:`end_seed`.  A seed in flight is waited for."""
        with self.lock:
            while not self.seeded and self._seeding is not None:
                self.lock.wait()
            if self.seeded:
                return True
            self._seeding = job
            return False

    def end_seed(self, job):
        """``job``'s seeding ended, drained or failed: the jobs waiting
        in :meth:`join` go on (and one of them seeds, if nothing did)."""
        with self.lock:
            if self._seeding is job:
                self._seeding = None
                self.seeded = self.nslots > 0
                self.lock.notify_all()

    # -- capacity ----------------------------------------------------------
    def _ensure_capacity(self, need_slots, need_len):
        """Grow the device table (pow2 slots, pow2 byte width) as a new
        version.  Returns False when growth would exceed the budget —
        the caller degrades.  Row width never grows past the probe-able
        maximum: batches only carry tokens <= ``text._SHORT_TOKEN``
        bytes, and a hit requires ``tab_lens == lens``, so a LONGER
        token's row can never verify — such tokens get a slot, hash
        lanes, and an accumulator row, but their stored bytes truncate
        (dead for probing either way) instead of widening every slot's
        row."""
        import jax.numpy as jnp

        from .text import _SHORT_TOKEN

        new_cap = self.cap
        while need_slots > (new_cap or 0):
            new_cap = _pow2(max(need_slots, _MIN_CAP, (new_cap or 0) * 2),
                            _MIN_CAP)
        new_L = self.Lcap
        while need_len > new_L and new_L < _SHORT_TOKEN + 1:
            new_L *= 2
        if new_cap == self.cap and new_L == self.Lcap:
            return True
        if (_table_bytes(new_cap, new_L)
                + max(1, self.jobs) * _acc_bytes(new_cap)) > self.budget:
            return False
        self.tab_mat = jnp.zeros((new_cap, new_L), dtype=jnp.uint8)
        self.tab_lens = jnp.full((new_cap,), -1, dtype=jnp.int32)
        self.cap = new_cap
        self.Lcap = new_L
        # Re-stage every row: the widened/regrown matrices start empty.
        self._pending_rows = list(enumerate(self.slot_bytes))
        # The index was sized for the old cap (the program bucket keys
        # on cap): the next sync builds it anew.
        self._index = None
        return True

    # -- the bucket index ----------------------------------------------------
    def _place(self, slot, h):
        """Put ``slot`` (FNV hash ``h``) into the host index: its first
        bucket, else its second, else a bounded cuckoo walk that moves
        occupants to their other bucket.  A slot whose ``h1`` another
        slot already holds, or the entry a walk leaves homeless, stays
        out: its tokens miss to the exact host path."""
        ix, empty = self._index, self.cap
        b1, b2 = _buckets(h, len(ix))
        if ((ix[b1, 1] != empty and ix[b1, 0] == h)
                or (ix[b2, 1] != empty and ix[b2, 0] == h)):
            return
        b = b1 if ix[b1, 1] == empty else b2
        for _ in range(_MAX_KICKS):
            if ix[b, 1] == empty:
                break
            old_h, old_slot = int(ix[b, 0]), int(ix[b, 1])
            ix[b] = (h, slot)
            self._dirty.add(b)
            h, slot = old_h, old_slot
            o1, o2 = _buckets(h, len(ix))
            b = o2 if b == o1 else o1
        else:
            if ix[b, 1] != empty:
                return
        ix[b] = (h, slot)
        self._dirty.add(b)

    def _build_index(self):
        """The host index for the current cap, from every slot: each
        slot's first bucket where it is the first claimant, then its
        second bucket where that is still empty, then :meth:`_place` for
        the few left (vectorized: a rebuild places the whole vocabulary)."""
        ix = np.zeros((2 * self.cap, 2), dtype=np.uint32)
        ix[:, 1] = self.cap
        self._index = ix
        if not self.nslots:
            return
        h = np.asarray(self.h1, dtype=np.uint64)
        _u, slots = np.unique(h, return_index=True)  # first slot per h1
        slots.sort()
        h = h[slots]
        left = np.ones(len(slots), dtype=bool)
        for b in _buckets(h, len(ix)):
            i = np.flatnonzero(left)
            i = i[ix[b[i], 1] == self.cap]
            _u, first = np.unique(b[i], return_index=True)
            i = i[first]
            ix[b[i], 0] = h[i]
            ix[b[i], 1] = slots[i]
            left[i] = False
        for i in np.flatnonzero(left):
            self._place(int(slots[i]), int(h[i]))

    def unplaced(self, slots):
        """How many of ``slots`` are not in the index (0 before the first
        sync has built it)."""
        with self.lock:
            ix = self._index
            if ix is None:
                return 0
            placed = np.zeros(self.cap + 1, dtype=bool)
            placed[ix[:, 1]] = True
        return int(np.count_nonzero(~placed[slots]))

    def _sync_table(self):
        """Publish staged host rows and the changed index rows to device
        in one padded scatter-set; a regrow uploads the whole index it
        built (h2d charged for what actually moves).  Caller holds the
        lock."""
        import jax
        import jax.numpy as jnp

        moved = 0
        if self._index is None:
            self._build_index()
            self.tab_index = jax.device_put(self._index)
            moved += self._index.nbytes
            self._dirty = set()
        if self._pending_rows or self._dirty:
            slots = np.fromiter((s for s, _b in self._pending_rows),
                                dtype=np.int32,
                                count=len(self._pending_rows))
            rows = np.zeros((len(slots), self.Lcap), dtype=np.uint8)
            lens = np.empty(len(slots), dtype=np.int32)
            for i, (_s, b) in enumerate(self._pending_rows):
                # Rows wider than Lcap truncate: their true length in
                # tab_lens already fails every probe's length check
                # (batch tokens are <= _SHORT_TOKEN <= Lcap's bound).
                w = min(len(b), self.Lcap)
                rows[i, :w] = np.frombuffer(b[:w], dtype=np.uint8)
                lens[i] = len(b)
            pos = np.fromiter(self._dirty, dtype=np.int32,
                              count=len(self._dirty))
            index_rows = self._index[pos]
            # Both go in the same pieces (pad slots and positions point
            # past the table and drop).
            for a, b, m in _pieces(max(len(slots), len(pos))):
                piece = (_pad(slots[a:b], m, self.cap), _pad(rows[a:b], m),
                         _pad(lens[a:b], m),
                         _pad(pos[a:b], m, len(self._index)),
                         _pad(index_rows[a:b], m))
                self.tab_mat, self.tab_lens, self.tab_index = (
                    _set_rows_program()(
                        self.tab_mat, self.tab_lens, self.tab_index,
                        *(jnp.asarray(x) for x in piece)))
                moved += sum(x.nbytes for x in piece[1:3] + piece[4:])
            self._pending_rows = []
            self._dirty = set()
        if moved and self.store is not None:
            self.store.count_h2d(moved)

    def snapshot(self):
        """One consistent published table version ``(cap, Lcap,
        tab_index, tab_mat, tab_lens)``, every staged row in it; None
        when even an empty table exceeds the budget."""
        with self.lock:
            if not self._ensure_capacity(max(self.nslots, 1), self.Lcap):
                return None
            self._sync_table()
            return (self.cap, self.Lcap, self.tab_index, self.tab_mat,
                    self.tab_lens)

    # -- host-side insert/lookup -------------------------------------------
    def _insert(self, raw, key, h1, h2):
        """New slot for canonical bytes ``raw`` (caller checked absence
        and capacity, and holds the lock)."""
        slot = self.nslots
        self.nslots += 1
        self.bytes2slot[raw] = slot
        self.slot_bytes.append(raw)
        self.keys.append(key)
        self.h1.append(int(h1))
        self.h2.append(int(h2))
        self._pending_rows.append((slot, raw))
        if self._index is not None:
            self._place(slot, self.h1[-1])
        return slot

    def lookup_or_insert(self, raws, keys=None, h1=None, h2=None):
        """Slots for a list of canonical utf-8 byte strings; unseen ones
        insert (hash lanes computed here unless provided).  Returns
        ``(slots, inserted)`` — an int32 array, or None when the table
        refused to grow — with the number of slots this call added."""
        from . import hashing

        with self.lock:
            get = self.bytes2slot.get
            new_at = [i for i, b in enumerate(raws) if get(b) is None]
            if new_at:
                # One capacity step for the whole call: a duplicate among
                # the new raws only over-asks by its count.
                need_len = max(len(raws[i]) for i in new_at)
                if not self._ensure_capacity(self.nslots + len(new_at),
                                             need_len):
                    return None, 0
                if keys is None or h1 is None:
                    nk = np.empty(len(new_at), dtype=object)
                    for j, i in enumerate(new_at):
                        nk[j] = raws[i].decode("utf-8", "replace")
                    nh1, nh2 = hashing.hash_keys(nk)
                else:
                    nk = [keys[i] for i in new_at]
                    nh1 = [h1[i] for i in new_at]
                    nh2 = [h2[i] for i in new_at]
                before = self.nslots
                for j, i in enumerate(new_at):
                    if raws[i] not in self.bytes2slot:
                        self._insert(raws[i], nk[j], nh1[j], nh2[j])
                inserted = self.nslots - before
            else:
                inserted = 0
            slots = np.fromiter((get(b) for b in raws), dtype=np.int32,
                                count=len(raws))
        if inserted and self.store is not None:
            self.store.count_slot_inserts(inserted)
        return slots, inserted

    def lanes(self, n):
        """Keys and hash lanes of the first ``n`` slots."""
        with self.lock:
            keys = np.empty(n, dtype=object)
            keys[:] = self.keys[:n]
            return (keys, np.asarray(self.h1[:n], dtype=np.uint32),
                    np.asarray(self.h2[:n], dtype=np.uint32))


class StageVocabs(object):
    """One lowered handoff stage's :class:`StageVocab` s, one per chip,
    for one run of the stage: the runner makes one with the stage's job
    factory and drops it with the stage."""

    def __init__(self, store):
        self.store = store
        self._lock = threading.Lock()
        self._by_chip = {}

    def current(self):
        """The vocabulary of the chip this thread's work is placed on."""
        from ..parallel import placement

        chip = placement.current()
        with self._lock:
            sv = self._by_chip.get(chip)
            if sv is None:
                sv = self._by_chip[chip] = StageVocab(self.store)
            return sv


class HandoffVocab(object):
    """The per-job part of a handoff vocabulary: one lowered map job's
    accumulator over a :class:`StageVocab` (its own when ``shared`` is
    None), its lane guard, table/classic mode and degrade state.

    ``budget`` is THIS job's share of the run's handoff budget: the
    runner divides ``settings.effective_handoff_budget()`` by the
    stage's concurrent job count, so N parallel jobs can never hold
    N x budget of device memory between them (each job degrades
    gracefully at its share instead of the fleet hitting an allocator
    failure).  The job adds its share to the shared vocabulary's budget
    while it is attached."""

    def __init__(self, store, dedup, budget=None, shared=None):
        self.store = store
        self.dedup = dedup
        self.budget = (int(budget) if budget is not None
                       else settings.effective_handoff_budget())
        self.shared = shared if shared is not None else StageVocab(store)
        self.shared.attach(self.budget)
        self._attached = True
        self.acc = None
        self.acc_cap = 0
        self.total_added = 0
        self.joined = False
        self.seeding = False
        self.table_mode = False
        self.degraded = False
        self.degrade_reason = None

    def _guard(self):
        return _I64_GUARD if _acc_dtype() == np.int64 else _I32_GUARD

    # -- the stage vocabulary ------------------------------------------------
    def join(self):
        """At the job's first dispatch: start in table mode on a seeded
        vocabulary (another job seeded it), else seed it (``seeding``:
        the caller ends that with :meth:`end_seed`)."""
        self.joined = True
        if self.shared.join(self):
            self.table_mode = True
            if self.store is not None:
                self.store.count_shared_join()
        else:
            self.seeding = True

    def end_seed(self):
        """Release this job's seeding, if it holds it (a no-op else)."""
        if self.seeding:
            self.seeding = False
            self.shared.end_seed(self)

    def _detach(self):
        if self._attached:
            self._attached = False
            self.shared.detach(self.budget)

    def _grow_acc(self, cap):
        """The accumulator sized for a ``cap``-slot table: counts kept,
        the sink (row ``cap``) moved to the new end."""
        import jax.numpy as jnp

        if cap == self.acc_cap:
            return
        acc = jnp.zeros(cap + 1, dtype=_acc_dtype())
        if self.acc is not None:
            acc = acc.at[:self.acc_cap].set(self.acc[:self.acc_cap])
        self.acc, self.acc_cap = acc, cap

    # -- count flow --------------------------------------------------------
    def scatter_counts(self, slots, counts):
        """Fold host-side per-slot contributions into the accumulator."""
        import jax.numpy as jnp

        if not len(slots):
            return True
        total = int(np.asarray(counts, dtype=np.int64).sum())
        if self.total_added + total > self._guard():
            return False
        self.total_added += total
        self._grow_acc(self.shared.cap)
        slots = np.asarray(slots, dtype=np.int32)
        counts = np.asarray(counts).astype(_acc_dtype())
        for a, b, m in _pieces(len(slots)):
            # Pad entries add 0 to the sink slot.
            self.acc = _scatter_program()(
                self.acc, jnp.asarray(_pad(slots[a:b], m, self.acc_cap)),
                jnp.asarray(_pad(counts[a:b], m)))
            if self.store is not None:
                self.store.count_h2d(m * (4 + _acc_dtype().itemsize))
        return True

    def absorb_block(self, blk):
        """Fold a host-path block (long tokens, fallback windows,
        collision regroups) into the accumulator — keyed by the decoded
        key's canonical utf-8 bytes, same as the device rows.  Returns
        False when the job must degrade."""
        h1, h2 = blk.hashes()
        keys = blk.keys
        raws = [None] * len(keys)
        for i in range(len(keys)):
            raws[i] = keys[i].encode("utf-8")
        slots, _n = self.shared.lookup_or_insert(raws, keys, h1, h2)
        if slots is None:
            return False
        return self.scatter_counts(slots, blk.values)

    def absorb_drain(self, keys, counts, h1, h2, batch_tokens):
        """Seed the table from a classic-program drain's survivors and
        fold their counts (the bootstrap path).  Returns (ok,
        new_fraction) — NEW vocabulary slots per batch TOKEN, the
        table-mode switch signal: the miss path's cost scales with the
        tokens that would miss, and new vocabulary under a Zipf tail is
        rare per token even while it is common per distinct key."""
        raws = [None] * len(keys)
        for i in range(len(keys)):
            raws[i] = keys[i].encode("utf-8")
        slots, inserted = self.shared.lookup_or_insert(raws, keys, h1, h2)
        if slots is None:
            return False, 0.0
        new_frac = inserted / float(batch_tokens) if batch_tokens else 0.0
        return self.scatter_counts(slots, counts), new_frac

    # -- the table-mode batch ----------------------------------------------
    def dispatch(self, mat, lens_p, lines_p, starts, lens, lines, n):
        """Launch the probe-and-count program over one padded batch; the
        accumulator advances asynchronously (double-buffered like the
        classic dispatch).  Returns the drain handle, or None when the
        job must degrade (overflow guard).  The whole launch — guards,
        table sync, program call — is the feed phase ``table-probe``."""
        with devtime.phase("scan_feed", "handoff", "table-probe",
                           track="device", prof="h2d", tokens=int(n),
                           bytes=int(mat.nbytes)) as ph:
            ph.nbytes = mat.nbytes
            return self._dispatch(mat, lens_p, lines_p, starts, lens,
                                  lines, n)

    def _dispatch(self, mat, lens_p, lines_p, starts, lens, lines, n):
        import jax.numpy as jnp

        npad, L = mat.shape
        if self.total_added + n > self._guard():
            return None
        snap = self.shared.snapshot()
        if snap is None:
            return None
        cap, Lcap, tab_index, tab_mat, tab_lens = snap
        self._grow_acc(cap)
        self.total_added += n
        dedup_k = 0
        if self.dedup and lines_p is not None and n:
            # Longest line in this batch (line ids are non-decreasing):
            # when it fits the window, the cheap shifted-compare dedup
            # variant is exact; wider lines take the sort variant.
            la = np.asarray(lines_p[:n])
            bound = np.flatnonzero(np.diff(la)) + 1
            runs = np.diff(np.concatenate(([0], bound, [n])))
            if int(runs.max()) <= _DEDUP_WINDOW:
                dedup_k = _DEDUP_WINDOW
        fn = _table_program(npad, L, cap, Lcap, self.dedup,
                            _acc_dtype().name, dedup_k)
        nbytes = mat.nbytes + lens_p.nbytes + lines_p.nbytes
        if self.store is not None:
            self.store.count_h2d(nbytes)
        self.acc, miss, n_miss = fn(
            jnp.asarray(mat), jnp.asarray(lens_p), jnp.asarray(lines_p),
            tab_index, tab_mat, tab_lens, self.acc)
        return _TableBatch(miss, n_miss, starts, lens, lines, n, npad)

    def drain(self, buf, batch):
        """Resolve one table dispatch: fetch the (tiny) miss evidence,
        absorb misses exactly on the host, and credit the drain bytes the
        classic program would have fetched.  Returns (ok, miss_frac);
        ``ok=False`` means NO miss count landed (the absorb is
        transactional: slots inserted before the refusal carry zero
        counts in this job, and no job emits a zero count) — the caller
        must emit ``batch.miss_idx``'s tokens through the exact host path
        or they are lost."""
        import jax

        with devtime.phase("scan_wait", "device", "wait", track="device",
                           prof="compute", tokens=int(batch.n)):
            jax.block_until_ready(batch.n_miss)
        with devtime.phase("scan_drain", "device", "fetch", track="device",
                           prof="d2h") as ph:
            n_miss = int(batch.n_miss)
            fetched = 4
            if n_miss:
                miss = np.asarray(batch.miss)[:batch.n]
                fetched += batch.npad  # the bool lane
            ph.nbytes = fetched
        ok = True
        if n_miss:
            with devtime.phase("scan_drain", "scan", "absorb-misses",
                               tokens=n_miss):
                idx = np.flatnonzero(miss)
                batch.miss_idx = idx
                ok = self._absorb_miss_tokens(
                    buf, batch.starts[idx], batch.lens[idx],
                    batch.lines[idx] if batch.lines is not None else None)
        if self.store is not None:
            self.store.count_d2h(fetched)
            self.store.count_table_probe(batch.n, batch.n - n_miss)
            if ok:
                # Only a batch that stayed on the tier claims the
                # avoided drain (a degrading batch is leaving it).
                self.store.count_d2h_avoided(
                    max(0, CLASSIC_DRAIN_BYTES_PER_SLOT * batch.npad
                        - fetched))
        return ok, (n_miss / float(batch.n) if batch.n else 0.0)

    def _absorb_miss_tokens(self, buf, starts, lens, lines):
        """Exact host grouping of a batch's missed tokens
        (:func:`group_token_rows` — the same grouping the classic host
        fallback uses), then slot insert + scatter."""
        if not len(starts):
            return True
        uniq, counts = group_token_rows(
            buf, starts, lens, lines,
            self.dedup and lines is not None)
        raws = [None] * len(uniq)
        for i in range(len(uniq)):
            ln = int(uniq[i, 0])
            raws[i] = uniq[i, 1:1 + ln].tobytes()
        slots, _n = self.shared.lookup_or_insert(raws)
        if slots is None:
            return False
        return self.scatter_counts(slots, counts)

    # -- endgame -----------------------------------------------------------
    def _counted(self):
        """Fetch this job's counts: ``(slots, counts)`` of the slots it
        counted (every other slot reads zero here), or None when it
        holds no accumulator."""
        if self.acc is None:
            return None
        acc = np.asarray(self.acc)
        if self.store is not None:
            # Charge what actually crossed the boundary: the
            # accumulator's own lane width.
            self.store.count_d2h(acc.nbytes)
        n = min(self.shared.nslots, self.acc_cap)
        slots = np.flatnonzero(acc[:n] > 0)
        return slots, acc[slots].astype(np.int64)

    def flush_block(self):
        """Degrade: one d2h of the accumulator -> a hash-sorted host
        block of this job's counts, byte-identical to what the classic
        combine would have produced; the job continues on the spill
        path.  The shared table stays for the chip's other jobs."""
        counted = self._counted()
        self._reset()
        if counted is None or not len(counted[0]):
            return None
        from ..blocks import Block

        slots, counts = counted
        keys, h1, h2 = self.shared.lanes(int(slots[-1]) + 1)
        return Block(keys[slots], counts, h1[slots],
                     h2[slots]).sort_by_hash()

    def degrade(self, reason):
        self.degraded = True
        self.degrade_reason = reason
        self.end_seed()
        if self.store is not None:
            self.store.count_handoff_degrade()
        _trace.instant("handoff", "degrade", reason=reason)
        log.info("handoff degraded to the spill path: %s", reason)
        return self.flush_block()

    def _reset(self):
        """Drop this job's accumulator and leave the shared vocabulary."""
        self.acc = None
        self.acc_cap = 0
        self.table_mode = False
        self._detach()

    def finalize(self, store, n_partitions):
        """Job end: the slots this job counted become per-partition
        HBM-resident refs — hash-sorted within each partition, exactly
        the layout the classic combine would have registered —
        registered under the store's budget/attempt discipline.  Returns
        ``(blocks, {pid: [BlockRef]})``: at most one side is non-empty
        (``blocks`` is the degrade flush the caller must push through the
        classic path)."""
        import jax
        import jax.numpy as jnp

        from ..storage import BlockRef

        self.end_seed()
        if self.degraded or self.acc is None:
            self._reset()
            return (), {}
        with devtime.phase("scan_drain", "handoff", "finalize-fetch",
                           track="device", records=int(self.acc_cap)):
            slots, counts = self._counted()
        if not len(slots):
            self._reset()
            return (), {}
        if _acc_bytes(self.acc_cap) + len(slots) * 16 > self.budget:
            blk = self.degrade("hbm budget exceeded at finalize")
            return ((blk,) if blk is not None else ()), {}
        store.count_table_unplaced(self.shared.unplaced(slots))
        keys, h1, h2 = self.shared.lanes(int(slots[-1]) + 1)
        keys, h1, h2 = keys[slots], h1[slots], h2[slots]
        n = len(slots)
        order = np.lexsort((h2, h1))
        pid = (h1[order] % np.uint32(n_partitions)).astype(np.int32)
        porder = np.argsort(pid, kind="stable")
        perm = order[porder]
        sorted_pid = pid[porder]
        bounds = np.flatnonzero(np.diff(sorted_pid)) + 1
        starts = np.concatenate(([0], bounds))
        ends = np.concatenate((bounds, [n]))
        psum = np.add.reduceat(counts[perm], starts)
        lane_min = int(counts.min())
        # Every partition's lanes are padded to a power of two (the
        # consumer marks the valid prefix, ``len(ref)``), and all of them
        # gather from the accumulator in ONE program: device shapes stay
        # in a few pow2 buckets instead of one compile per partition size.
        # Pad rows read the sink slot.
        widths = [_pow2(e - s, 8) for s, e in zip(starts, ends)]
        offs = np.concatenate(([0], np.cumsum(widths)))
        total = _pow2(int(offs[-1]), 256)
        idx = np.full(total, self.acc_cap, dtype=np.int32)
        for s, e, o in zip(starts, ends, offs):
            idx[o:o + e - s] = slots[perm[s:e]]
        vals = _finalize_program()(self.acc, jnp.asarray(idx))
        if self.store is not None:
            self.store.count_h2d(idx.nbytes)
        mapping = {}
        total_dev = 0
        for j, (s, e, o, w) in enumerate(zip(starts, ends, offs, widths)):
            p = int(sorted_pid[s])
            seg = perm[s:e]
            dev_v = _slice(vals, int(o), w)
            h1_seg = np.zeros(w, dtype=np.uint32)
            h2_seg = np.zeros(w, dtype=np.uint32)
            h1_seg[:e - s] = h1[seg]
            h2_seg[:e - s] = h2[seg]
            dev_h1 = jax.device_put(h1_seg)
            dev_h2 = jax.device_put(h2_seg)
            # total_added is guarded under the lane bound, so the segment
            # sum is exact in the accumulator dtype.
            ref = BlockRef.from_device_lanes(
                keys.take(seg), h1[seg], h2[seg], dev_v, dev_h1, dev_h2,
                store=store, value_dtype=np.int64, lane_abs=int(psum[j]),
                lane_min=lane_min,
                h2d_bytes=h1_seg.nbytes + h2_seg.nbytes)
            store.register_device(ref)
            total_dev += ref.dev_bytes
            mapping.setdefault(p, []).append(ref)
        _trace.instant("handoff", "registered", bytes=int(total_dev),
                       partitions=len(mapping))
        self._reset()
        return (), mapping
