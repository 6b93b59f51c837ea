"""Cross-stage device-resident handoff: the per-job vocabulary tier.

This is the execution half of the plan's ``handoff="device"`` edge
decision (:mod:`dampr_tpu.plan.lower`): when a lowered scanner map feeds
a device-lowered associative sum fold, the map's program outputs never
round-trip through the host spill path (d2h fetch -> host combine ->
pickle -> frame encode -> spill -> re-read -> h2d).  Instead each job
keeps a device-resident vocabulary:

- ``acc``        — per-slot count accumulator (int32 lanes, int64 under
  x64), updated in place by every batch (buffer donation where the
  backend supports it);
- ``tab_h1``/``tab_slot`` — the sorted hash-lookup lanes batches probe
  with one vectorized ``searchsorted``;
- ``tab_mat``/``tab_lens`` — the vocabulary's raw byte rows, so every
  probe HIT is verified byte-for-byte inside the program (a 64-bit — or
  32-bit — hash collision can never merge distinct tokens: mismatching
  bytes route to the exact host miss path instead).

Batches whose tokens are mostly in the table run the **table program**:
single-lane FNV + searchsorted + byte verify + (for per-line dedup) a
two-lane ``(slot, line)`` sort + scatter-add — roughly a third of the
classic program's cost, because the five-lane ``lax.sort`` over the full
token stream disappears.  Early batches (and vocabulary-shift phases)
bootstrap through the classic hash->sort->segment program
(:mod:`.lower`), whose drained survivors seed the table; on the CPU
backend the job's first whole window seeds it through the native host
codec instead (:func:`_host_bootstrap` — cached hash lanes, no
re-hash, the window's tokenize/pad/dispatch skipped outright).

At job end the accumulator becomes per-partition HBM-resident
:class:`~dampr_tpu.storage.BlockRef` s (``BlockRef.from_device_lanes``)
that the consuming fold (``runner._mesh_reduce``) consumes in place.

Exactness contract: every count lands in a slot either (a) verified
byte-identical to the slot's bytes inside a program, or (b) through the
host miss/fallback path keyed by canonical UTF-8 bytes.  Degrades — HBM
budget exceeded, int32 overflow risk, vocabulary overflow — flush the
accumulator into one hash-sorted host block and hand the rest of the job
to the classic spill path, byte-identically.
"""

import functools
import logging

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


def _pow2(n, floor=4096):
    return max(floor, 1 << max(0, (int(n) - 1).bit_length()))


@functools.lru_cache(maxsize=None)
def _donate_ok():
    """Buffer donation is a no-op (with a warning) on CPU backends;
    donate only where shapes and platform permit."""
    return settings.backend() != "cpu"


@functools.lru_cache(maxsize=None)
def _acc_dtype():
    import jax

    return np.dtype(np.int64 if jax.config.jax_enable_x64 else np.int32)


@functools.lru_cache(maxsize=None)
def _host_bootstrap():
    """On the CPU backend the classic bootstrap program is pure
    overhead: its five-lane ``lax.sort`` runs on the very cores the
    native host codec would use at ~20x the throughput — so an
    empty-vocabulary job seeds the table from its FIRST WHOLE WINDOW
    through that codec (whose blocks carry cached hash lanes: no
    re-hash, no row sort), skipping the window's tokenize/pad/dispatch
    entirely.  A real accelerator keeps the classic bootstrap: the
    program runs on device while the host tokenizes the next window."""
    return settings.backend() == "cpu"


#: Windowed-dedup span (tokens): batches whose longest line fits run
#: the shifted-compare dedup (K passes over the batch) instead of the
#: ~4x-costlier (slot, line) sort; the host picks the variant per batch
#: from the actual max tokens-per-line (``dedup_k=0`` = sort).
_DEDUP_WINDOW = 16


@functools.lru_cache(maxsize=None)
def _table_program(n, L, cap, Lcap, dedup, acc_dtype_name, dedup_k=0):
    """One compiled probe-and-count program per shape bucket.

    hash (single FNV lane) -> searchsorted into the sorted table ->
    byte-verified hit mask -> dedup'd (slot, line) scatter-add into the
    donated accumulator.  Returns (acc, miss mask, miss count); misses
    (new vocabulary, hash duplicates, byte mismatches) are handled
    exactly on the host.

    ``dedup_k > 0``: every line in the batch spans at most ``dedup_k``
    tokens (host-verified per batch), so a duplicate (slot, line) pair
    sits within ``dedup_k`` positions of its first occurrence — K
    shifted compares replace the full (slot, line) sort."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .hashing import _FNV_OFFSET1, _FNV_PRIME1

    W = min(L, Lcap)

    def table_probe(mat, lens, lines, tab_h1, tab_slot, tab_mat, tab_lens,
                    acc):
        h1 = jnp.full((n,), _FNV_OFFSET1, dtype=jnp.uint32)

        def body(c, a):
            active = c < lens
            byte = mat[:, c].astype(jnp.uint32)
            return jnp.where(active, (a ^ byte) * _FNV_PRIME1, a)

        h1 = lax.fori_loop(0, L, body, h1)

        pos = jnp.clip(jnp.searchsorted(tab_h1, h1), 0, cap - 1)
        cand = jnp.take(tab_slot, pos)
        valid = lens > 0
        same = valid & (jnp.take(tab_h1, pos) == h1) \
            & (jnp.take(tab_lens, cand) == lens)
        rep = jnp.take(tab_mat, cand, axis=0)
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

    kwargs = {"donate_argnums": (7,)} if _donate_ok() else {}
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
    """Stage vocabulary rows into the device table; slots past the table
    (the padding) drop."""
    import jax

    def table_set_rows(tab_mat, tab_lens, slots, rows, lens):
        return (tab_mat.at[slots].set(rows, mode="drop"),
                tab_lens.at[slots].set(lens, mode="drop"))

    kwargs = {"donate_argnums": (0, 1)} if _donate_ok() else {}
    return jax.jit(table_set_rows, **kwargs)


@functools.lru_cache(maxsize=None)
def _finalize_program(n_partitions):
    """Gather the accumulator into partition order (``idx``) and sum each
    partition's counts (``seg_of``; pad rows carry ``n_partitions`` and
    drop).  Returns the gathered lanes, the per-partition sums and the
    smallest count."""
    import jax
    import jax.numpy as jnp

    def table_finalize(acc, idx, seg_of):
        vals = jnp.take(acc, idx)
        live = seg_of < n_partitions
        psum = jax.ops.segment_sum(vals, seg_of, num_segments=n_partitions)
        vmin = jnp.min(jnp.where(live, vals, jnp.iinfo(vals.dtype).max))
        return vals, psum, vmin

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


class HandoffVocab(object):
    """Per-job device-resident vocabulary + accumulator (one per lowered
    handoff-edge map job; never shared across jobs or threads).

    ``budget`` is THIS vocabulary's share of the run's handoff budget:
    the runner divides ``settings.effective_handoff_budget()`` by the
    stage's concurrent job count, so N parallel jobs can never hold
    N x budget of device memory between them (each job degrades
    gracefully at its share instead of the fleet hitting an allocator
    failure)."""

    def __init__(self, store, dedup, budget=None):
        self.store = store
        self.dedup = dedup
        self.budget = (int(budget) if budget is not None
                       else settings.effective_handoff_budget())
        self.nslots = 0
        self.cap = 0
        self.Lcap = 8
        self.bytes2slot = {}
        self.keys = []        # decoded str per slot
        self.slot_bytes = []  # canonical utf-8 bytes per slot
        self.h1 = []          # python ints (host lanes)
        self.h2 = []
        self._tab_dirty = True
        self._lanes_forced = False  # regrow reallocated the lanes
        self._lanes_deferred = 0    # slots inserted since last rebuild
        self._pending_rows = []   # (slot, bytes) not yet on device
        self.acc = None
        self.tab_h1 = None
        self.tab_slot = None
        self.tab_mat = None
        self.tab_lens = None
        self.total_added = 0
        self.table_mode = False
        self.degraded = False
        self.degrade_reason = None
        self.table_batches = 0
        self.classic_batches = 0

    # -- capacity ----------------------------------------------------------
    def _guard(self):
        return _I64_GUARD if _acc_dtype() == np.int64 else _I32_GUARD

    def device_bytes(self):
        if self.cap == 0:
            return 0
        return int(self.cap * (self.Lcap + 4 + 4 + 4)
                   + (self.cap + 1) * _acc_dtype().itemsize)

    def _ensure_capacity(self, need_slots, need_len):
        """Grow the device table (pow2 slots, pow2 byte width).  Returns
        False when growth would exceed the handoff budget — the caller
        degrades.  Row width never grows past the probe-able maximum:
        batches only carry tokens <= ``text._SHORT_TOKEN`` bytes, and a
        hit requires ``tab_lens == lens``, so a LONGER token's row can
        never verify — such tokens get a slot, hash lanes, and an
        accumulator row, but their stored bytes truncate (dead for
        probing either way) instead of widening every slot's row."""
        import jax.numpy as jnp

        from .text import _SHORT_TOKEN

        new_cap = self.cap
        while need_slots > (new_cap or 0):
            new_cap = _pow2(max(need_slots, 4096, (new_cap or 0) * 2))
        new_L = self.Lcap
        while need_len > new_L and new_L < _SHORT_TOKEN + 1:
            new_L *= 2
        if new_cap == self.cap and new_L == self.Lcap:
            return True
        projected = int(new_cap * (new_L + 12)
                        + (new_cap + 1) * _acc_dtype().itemsize)
        if projected > self.budget:
            return False
        old_acc, old_nslots = self.acc, self.nslots
        self.acc = jnp.zeros(new_cap + 1, dtype=_acc_dtype())
        if old_acc is not None and old_nslots:
            self.acc = self.acc.at[:old_nslots].set(old_acc[:old_nslots])
        self.tab_mat = jnp.zeros((new_cap, new_L), dtype=jnp.uint8)
        self.tab_lens = jnp.full((new_cap,), -1, dtype=jnp.int32)
        self.cap = new_cap
        self.Lcap = new_L
        # Re-stage every row: the widened/regrown matrices start empty.
        self._pending_rows = list(enumerate(self.slot_bytes))
        self._tab_dirty = True
        # The lookup lanes were sized for the old cap: the next sync
        # MUST rebuild them (the program bucket keys on cap).
        self._lanes_forced = True
        return True

    def _sync_table(self):
        """Publish staged host rows + the sorted lookup lanes to device
        (h2d charged for what actually moves)."""
        import jax
        import jax.numpy as jnp

        moved = 0
        if self._pending_rows:
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
            # Pad the staged rows to a power-of-two count (pad slots
            # point past the table and drop): one compiled update per
            # bucket, not one per distinct staging size.
            m = _pow2(len(slots), 256)
            slots = np.pad(slots, (0, m - len(slots)),
                           constant_values=self.cap)
            rows = np.pad(rows, ((0, m - len(rows)), (0, 0)))
            lens = np.pad(lens, (0, m - len(lens)))
            self.tab_mat, self.tab_lens = _set_rows_program()(
                self.tab_mat, self.tab_lens, jnp.asarray(slots),
                jnp.asarray(rows), jnp.asarray(lens))
            moved += rows.nbytes + lens.nbytes
            self._pending_rows = []
        if self._tab_dirty and (
                self.tab_h1 is None or self._lanes_forced
                or self._lanes_deferred >= max(1024, self.nslots >> 4)):
            # One argsort per REBUILD beats per-insert sorted-array
            # maintenance (np.insert is a full copy — O(vocab^2) across a
            # bootstrap) — and rebuilds themselves are deferred until
            # enough slots accumulated (~6% of the vocabulary), because
            # each one re-sorts and re-uploads the whole cap-sized lane
            # pair.  Deferral is exact: a slot absent from the lanes
            # simply keeps MISSING to the host absorb path, which finds
            # it in ``bytes2slot`` and scatters into the same
            # accumulator row.  A regrow always rebuilds (the lanes were
            # reallocated for the new cap).  Pad positions carry the max
            # hash; a bogus hit there fails the byte/length verify, so
            # no validity lane is needed.
            h1a = np.asarray(self.h1, dtype=np.uint32)
            order = np.argsort(h1a, kind="stable")
            th1 = np.full(self.cap, np.uint32(0xFFFFFFFF),
                          dtype=np.uint32)
            th1[:len(order)] = h1a[order]
            tsl = np.zeros(self.cap, dtype=np.int32)
            tsl[:len(order)] = order
            self.tab_h1 = jax.device_put(th1)
            self.tab_slot = jax.device_put(tsl)
            moved += th1.nbytes + tsl.nbytes
            self._tab_dirty = False
            self._lanes_forced = False
            self._lanes_deferred = 0
        if moved and self.store is not None:
            self.store.count_h2d(moved)

    # -- host-side insert/lookup -------------------------------------------
    def _insert(self, raw, key, h1, h2):
        """New slot for canonical bytes ``raw`` (caller checked absence).
        Returns the slot, or -1 when the table cannot grow (degrade)."""
        if not self._ensure_capacity(self.nslots + 1, len(raw)):
            return -1
        slot = self.nslots
        self.nslots += 1
        self.bytes2slot[raw] = slot
        self.slot_bytes.append(raw)
        self.keys.append(key)
        self.h1.append(int(h1))
        self.h2.append(int(h2))
        self._pending_rows.append((slot, raw))
        self._tab_dirty = True
        self._lanes_deferred += 1
        return slot

    def lookup_or_insert(self, raws, keys=None, h1=None, h2=None):
        """Slots for a list of canonical utf-8 byte strings; unseen ones
        insert (hash lanes computed here unless provided).  Returns an
        int32 array, or None when the table refused to grow."""
        from . import hashing

        slots = np.empty(len(raws), dtype=np.int32)
        new_at = [i for i, b in enumerate(raws)
                  if b not in self.bytes2slot]
        if new_at and (keys is None or h1 is None):
            nk = np.empty(len(new_at), dtype=object)
            for j, i in enumerate(new_at):
                nk[j] = raws[i].decode("utf-8", "replace")
            nh1, nh2 = hashing.hash_keys(nk)
            for j, i in enumerate(new_at):
                s = self._insert(raws[i], nk[j], nh1[j], nh2[j])
                if s < 0:
                    return None
        elif new_at:
            for i in new_at:
                s = self._insert(raws[i], keys[i], h1[i], h2[i])
                if s < 0:
                    return None
        get = self.bytes2slot.get
        for i, b in enumerate(raws):
            slots[i] = get(b)
        return slots

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
        self._sync_table()
        # Pad to a power-of-two count: pad entries add 0 to the sink slot.
        m = _pow2(len(slots), 256)
        slots = np.pad(np.asarray(slots, dtype=np.int32),
                       (0, m - len(slots)), constant_values=self.cap)
        counts = np.pad(np.asarray(counts).astype(_acc_dtype()),
                        (0, m - len(counts)))
        self.acc = _scatter_program()(self.acc, jnp.asarray(slots),
                                      jnp.asarray(counts))
        if self.store is not None:
            self.store.count_h2d(len(slots) * (4 + _acc_dtype().itemsize))
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
        slots = self.lookup_or_insert(raws, keys=keys, h1=h1, h2=h2)
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
        before = self.nslots
        slots = self.lookup_or_insert(raws, keys=keys, h1=h1, h2=h2)
        if slots is None:
            return False, 0.0
        new_frac = ((self.nslots - before) / float(batch_tokens)
                    if batch_tokens else 0.0)
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
        if not self._ensure_capacity(max(self.nslots, 1), self.Lcap):
            return None
        self._sync_table()
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
        fn = _table_program(npad, L, self.cap, self.Lcap, self.dedup,
                            _acc_dtype().name, dedup_k)
        nbytes = mat.nbytes + lens_p.nbytes + lines_p.nbytes
        if self.store is not None:
            self.store.count_h2d(nbytes)
        self.acc, miss, n_miss = fn(
            jnp.asarray(mat), jnp.asarray(lens_p), jnp.asarray(lines_p),
            self.tab_h1, self.tab_slot, self.tab_mat, self.tab_lens,
            self.acc)
        self.table_batches += 1
        return _TableBatch(miss, n_miss, starts, lens, lines, n, npad)

    def drain(self, buf, batch):
        """Resolve one table dispatch: fetch the (tiny) miss evidence,
        absorb misses exactly on the host, and credit the drain bytes the
        classic program would have fetched.  Returns (ok, miss_frac);
        ``ok=False`` means NO miss count landed (the absorb is
        transactional: slots inserted before the refusal carry zero
        counts, which the degrade flush drops) — the caller must emit
        ``batch.miss_idx``'s tokens through the exact host path or they
        are lost."""
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
        slots = self.lookup_or_insert(raws)
        if slots is None:
            return False
        return self.scatter_counts(slots, counts)

    # -- endgame -----------------------------------------------------------
    def flush_block(self):
        """Degrade: one d2h of the accumulator -> a hash-sorted host
        block, byte-identical to what the classic combine would have
        produced; the job continues on the spill path."""
        if self.nslots == 0:
            self._reset()
            return None
        counts = np.asarray(self.acc[:self.nslots]).astype(np.int64)
        if self.store is not None:
            # Charge what actually crossed the boundary: the
            # accumulator's own lane width, not the int64-widened copy.
            self.store.count_d2h(self.nslots * _acc_dtype().itemsize)
        from ..blocks import Block

        keys = np.empty(self.nslots, dtype=object)
        for i, k in enumerate(self.keys):
            keys[i] = k
        h1 = np.asarray(self.h1, dtype=np.uint32)
        h2 = np.asarray(self.h2, dtype=np.uint32)
        keep = counts > 0
        blk = Block(keys[keep], counts[keep], h1[keep], h2[keep])
        self._reset()
        if not len(blk):
            return None
        return blk.sort_by_hash()

    def degrade(self, reason):
        self.degraded = True
        self.degrade_reason = reason
        if self.store is not None:
            self.store.count_handoff_degrade()
        _trace.instant("handoff", "degrade", reason=reason)
        log.info("handoff degraded to the spill path: %s", reason)
        return self.flush_block()

    def _reset(self):
        self.acc = None
        self.tab_h1 = self.tab_slot = None
        self.tab_mat = self.tab_lens = None
        self.cap = 0
        self.nslots = 0
        self.bytes2slot = {}
        self.keys = []
        self.slot_bytes = []
        self.h1 = []
        self.h2 = []
        self._pending_rows = []
        self._tab_dirty = True
        self._lanes_forced = False
        self._lanes_deferred = 0
        self.table_mode = False

    def finalize(self, store, n_partitions):
        """Job end: the accumulator becomes per-partition HBM-resident
        refs — hash-sorted within each partition, exactly the layout the
        classic combine would have registered — registered under the
        store's budget/attempt discipline.  Returns ``(blocks, {pid:
        [BlockRef]})``: at most one side is non-empty (``blocks`` is the
        degrade flush the caller must push through the classic path)."""
        import jax
        import jax.numpy as jnp

        from ..storage import BlockRef

        if self.degraded or self.nslots == 0:
            self._reset()
            return (), {}
        if self.device_bytes() + self.nslots * 16 > self.budget:
            blk = self.degrade("hbm budget exceeded at finalize")
            return ((blk,) if blk is not None else ()), {}
        h1 = np.asarray(self.h1, dtype=np.uint32)
        h2 = np.asarray(self.h2, dtype=np.uint32)
        order = np.lexsort((h2, h1))
        pid = (h1[order] % np.uint32(n_partitions)).astype(np.int32)
        porder = np.argsort(pid, kind="stable")
        perm = order[porder]
        sorted_pid = pid[porder]
        keys = np.empty(self.nslots, dtype=object)
        for i, k in enumerate(self.keys):
            keys[i] = k
        bounds = np.flatnonzero(np.diff(sorted_pid)) + 1
        starts = np.concatenate(([0], bounds))
        ends = np.concatenate((bounds, [self.nslots]))
        # Every partition's lanes are padded to a power of two (the
        # consumer marks the valid prefix, ``len(ref)``), and all of them
        # gather from the accumulator in ONE program: device shapes stay
        # in a few pow2 buckets instead of one compile per partition size.
        # Pad rows read the sink slot and belong to no partition.
        widths = [_pow2(e - s, 8) for s, e in zip(starts, ends)]
        offs = np.concatenate(([0], np.cumsum(widths)))
        total = _pow2(int(offs[-1]), 256)
        idx = np.full(total, self.cap, dtype=np.int32)
        seg_of = np.full(total, n_partitions, dtype=np.int32)
        for s, e, o in zip(starts, ends, offs):
            idx[o:o + e - s] = perm[s:e]
            seg_of[o:o + e - s] = sorted_pid[s]
        with devtime.phase("scan_drain", "handoff", "finalize-fetch",
                           track="device", records=int(self.nslots)):
            vals, psum, vmin = _finalize_program(n_partitions)(
                self.acc, jnp.asarray(idx), jnp.asarray(seg_of))
            psum = np.asarray(psum)
            lane_min = int(vmin)
        if self.store is not None:
            self.store.count_h2d(idx.nbytes + seg_of.nbytes)
            self.store.count_d2h(psum.nbytes + 4)
        mapping = {}
        total_dev = 0
        for s, e, o, w in zip(starts, ends, offs, widths):
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
                store=store, value_dtype=np.int64, lane_abs=int(psum[p]),
                lane_min=lane_min,
                h2d_bytes=h1_seg.nbytes + h2_seg.nbytes)
            store.register_device(ref)
            total_dev += ref.dev_bytes
            mapping.setdefault(p, []).append(ref)
        _trace.instant("handoff", "registered", bytes=int(total_dev),
                       partitions=len(mapping))
        self._reset()
        return (), mapping
