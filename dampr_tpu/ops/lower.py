"""Jitted device programs for lowered map->fold stages.

This is the execution half of the device-lowering pass
(:mod:`dampr_tpu.plan.lower`): a fused per-record stage built from the
native scanner vocabulary (``ops.text.TokenCounts`` / ``DocFreq``) feeding
a keyed associative fold compiles into ONE jitted JAX program per shape
bucket — DrJAX's blueprint (PAPERS.md, arXiv 2403.07128): the MapReduce
primitive is *lowered through JAX*, not interpreted per record.

Division of labor per line-aligned scan window:

- **host (feed)**: byte classification + token bounds (the vectorized
  table lookups from :mod:`.text`), case fold, per-line ids, and the
  padded token byte matrix — the h2d payload, built for the NEXT batch
  while the previous batch's program runs (double buffering);
- **device (one program)**: dual-lane FNV hash of the matrix (byte-exact
  with :mod:`.hashing`), stable sorts ordering rows by ``(h1, h2)``,
  per-line first-occurrence dedup (DocFreq), segment counts via an
  in-program prefix scan (or the Pallas fused segfold kernel when
  ``settings.lower_pallas_segfold`` opts in), segment-representative
  indices, and a collision check;
- **host (drain)**: compact the vocabulary-sized survivors, decode their
  representative strings from the original buffer, and build the Block
  the normal fold/spill machinery consumes.

Exactness contract: grouping is by the engine's 64-bit dual hash lanes,
and the program *verifies* every record's token bytes equal its segment
representative's bytes — any mismatch (a 64-bit collision) falls that
batch back to the exact host grouping, so results are byte-identical to
the host path by construction.  Windows that are not round-trip-clean
UTF-8 (lossy-decode tokens would break the per-line set contract — see
``text.chunk_doc_freq``) and lines longer than a program batch fall back
whole, for the same reason.

Per-batch partial counts merge in the downstream combiner exactly like
the host scanners' per-window partials: the fold is associative, so
batch boundaries are unobservable in the results.
"""

import functools

import numpy as np

from .. import settings
from ..obs import trace as _trace
from . import devtime
from .segment import sort_perm
from .text import (_LOWER, _SHORT_TOKEN, _token_bounds, chunk_doc_freq,
                   chunk_token_counts)

# ---------------------------------------------------------------------------
# Stage claims: which mappers have a device lowering
# ---------------------------------------------------------------------------


def claims(mapper):
    """Lowering params for a mapper the device programs can execute, or
    None.  Exact types only — a subclass may have changed semantics the
    program would silently miss."""
    from .text import DocFreq, TokenCounts

    if type(mapper) is TokenCounts:
        if mapper.mode in ("word", "whitespace"):
            return {"mode": mapper.mode, "lower": bool(mapper.lower),
                    "dedup": False, "pair_values": bool(mapper.pair_values)}
        return None
    if type(mapper) is DocFreq:
        if mapper.mode in ("word", "whitespace"):
            return {"mode": mapper.mode, "lower": bool(mapper.lower),
                    "dedup": True, "pair_values": bool(mapper.pair_values)}
        return None
    return None


# ---------------------------------------------------------------------------
# The jitted program
# ---------------------------------------------------------------------------


def _pow2(n):
    return max(8, 1 << max(0, (n - 1).bit_length()))


def _len_bucket(max_len):
    from .hashing import _len_bucket as hb

    return hb(max(1, int(max_len)))


@functools.lru_cache(maxsize=None)
def _token_fold_jit(n, L, dedup, pallas, interpret):
    """One compiled program: hash -> sort -> dedup -> segment count ->
    collision check over a padded [n, L] token byte matrix.  Cached per
    shape bucket so recompilations stay bounded."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from .hashing import _FNV_OFFSET1, _FNV_OFFSET2, _FNV_PRIME1, _FNV_PRIME2

    def token_fold(mat, lens, lines):
        # -- dual-lane FNV over the byte columns (== hashing._fnv_jit) --
        h1 = jnp.full((n,), _FNV_OFFSET1, dtype=jnp.uint32)
        h2 = jnp.full((n,), _FNV_OFFSET2, dtype=jnp.uint32)

        def body(c, hs):
            a, b = hs
            active = c < lens
            byte = mat[:, c].astype(jnp.uint32)
            na = (a ^ byte) * _FNV_PRIME1
            nb = (b ^ byte) * _FNV_PRIME2
            return (jnp.where(active, na, a), jnp.where(active, nb, b))

        h1, h2 = lax.fori_loop(0, L, body, (h1, h2))

        # -- order by (h1, h2), pad rows last ---------------------------
        # Pad rows carry the all-ones key; stability keeps them behind a
        # real token with that key (they are the batch's tail), and the
        # validity lane splits the two.  Rows enter in document order, so
        # stability also leaves each (h1, h2) group ordered by line: the
        # per-line dedup below needs no line key.
        valid = lens > 0
        pad = jnp.uint32(0xFFFFFFFF)
        k1 = jnp.where(valid, h1, pad)
        k2 = jnp.where(valid, h2, pad)
        perm = sort_perm(k1, k2)
        sh1 = jnp.take(k1, perm)
        sh2 = jnp.take(k2, perm)
        sinv = jnp.where(jnp.take(valid, perm), 0, 1).astype(jnp.int32)
        sline = jnp.take(lines.astype(jnp.int32), perm) if dedup else None

        def adj_new(*lanes):
            """True where any lane differs from its predecessor (position
            0 inclusive)."""
            out = jnp.ones((n,), dtype=bool)
            neq = jnp.zeros((n - 1,), dtype=bool)
            for lane in lanes:
                neq = neq | (lane[1:] != lane[:-1])
            return out.at[1:].set(neq)

        starts = adj_new(sinv, sh1, sh2)          # token segments
        if dedup:
            # contribution: first occurrence of (token, line) counts 1
            v = jnp.where(adj_new(sinv, sh1, sh2, sline)
                          & (sinv == 0), 1, 0).astype(jnp.int32)
        else:
            v = jnp.where(sinv == 0, 1, 0).astype(jnp.int32)

        pos = jnp.arange(n, dtype=jnp.int32)
        start_pos = lax.cummax(jnp.where(starts, pos, -1), axis=0)

        use_pallas = pallas and n >= 8192 and n % 8192 == 0
        if use_pallas:
            from . import pallas_segfold as SF

            tot, live = SF._segfold_call(n // SF._tile_elems(), interpret)(
                sh1.astype(jnp.int32).reshape(-1, 128),
                sh2.astype(jnp.int32).reshape(-1, 128),
                v.reshape(-1, 128), sinv.reshape(-1, 128))
            tot = tot.reshape(n)
            live = live.reshape(n).astype(bool)
        else:
            csum = jnp.cumsum(v, dtype=jnp.int32)
            ex = csum - v
            # exclusive prefix at the segment start: ex is nondecreasing,
            # so a running max over start-marked values carries it
            start_ex = lax.cummax(jnp.where(starts, ex, -1), axis=0)
            ends = jnp.ones((n,), dtype=bool).at[:-1].set(starts[1:])
            tot = jnp.where(ends, csum - start_ex, 0)
            live = ends & (sinv == 0)

        # -- collision check: every token's bytes == its segment rep's --
        smat = jnp.take(mat, perm, axis=0)
        slens = jnp.take(lens, perm)
        rep_rows = jnp.take(smat, start_pos, axis=0)
        rep_lens = jnp.take(slens, start_pos)
        same = (slens == rep_lens) & jnp.all(smat == rep_rows, axis=1)
        collisions = jnp.sum(jnp.where((sinv == 0) & ~same, 1, 0))

        rep_orig = jnp.take(perm, start_pos)  # original index of each rep
        return sh1, sh2, tot, live, rep_orig, collisions

    return jax.jit(token_fold)


def _lower_interpret():
    """Pallas runs interpreted off-TPU only: on a TPU the kernel always
    compiles through Mosaic."""
    return settings.backend() != "tpu"


def _handoff_enter_frac():
    from .handoff import _TABLE_ENTER_NEW_FRAC

    return _TABLE_ENTER_NEW_FRAC


class _Batch(object):
    """One dispatched program invocation plus the host metadata needed to
    drain it: the window-local token starts/lens the reps decode from."""

    __slots__ = ("out", "starts", "lens", "n")

    def __init__(self, out, starts, lens, n):
        self.out = out
        self.starts = starts
        self.lens = lens
        self.n = n


def _batch_bounds(lines, n_tokens, limit):
    """Batch cut points (token indices) honoring line boundaries so the
    per-line dedup never straddles a batch.  Returns None when a single
    line exceeds the limit (caller falls back to the host path)."""
    if n_tokens <= limit:
        return [(0, n_tokens)]
    cuts = [0]
    at = 0
    while at < n_tokens:
        end = min(at + limit, n_tokens)
        if end < n_tokens and lines is not None:
            # retreat to the last token of the previous line
            line_at_end = lines[end]
            while end > at and lines[end - 1] == line_at_end:
                end -= 1
            if end == at:
                return None  # one line wider than a whole batch
        cuts.append(end)
        at = end
    return list(zip(cuts[:-1], cuts[1:]))


class DeviceTokenFoldSink(object):
    """Window-sink adapter running the lowered tokenize+hash+fold program
    (drop-in for the scanners' ``window_sink()``).  ``add(win)`` feeds the
    window through double-buffered program dispatches and yields resolved
    partial-count Blocks; per-batch collision fallbacks and whole-window
    host fallbacks keep results byte-identical to the host scanner.

    ``handoff=True`` (the plan's ``handoff="device"`` edge,
    :mod:`.handoff`): emitted partials stay DEVICE-RESIDENT in the job's
    accumulator over its chip's stage vocabulary (``vocabs``, shared with
    the stage's other jobs on that chip; the job's own when None)
    instead of draining to host blocks — one job's classic batches
    seed the vocabulary, every batch after them runs the cheap
    table-probe program, and ``finalize_handoff`` registers the
    accumulated counts as HBM-resident BlockRefs the consuming fold
    reads in place.  Any degrade flushes the accumulator into one
    hash-sorted block and reverts to the classic emit path,
    byte-identically."""

    def __init__(self, params, store=None, handoff=False, jobs=1,
                 vocabs=None):
        self.mode = params["mode"]
        self.lower = params["lower"]
        self.dedup = params["dedup"]
        self.pair_values = params["pair_values"]
        self.store = store
        self.batches = 0
        self.fallbacks = 0
        self._hv = None
        if handoff and store is not None and not self.pair_values:
            from . import handoff as _handoff

            # Each concurrent job gets an equal share of the run's
            # handoff budget: N parallel vocabularies can never hold
            # N x budget of device memory between them.
            share = (settings.effective_handoff_budget()
                     // max(1, int(jobs)))
            self._hv = _handoff.HandoffVocab(
                store, self.dedup, budget=share,
                shared=vocabs.current() if vocabs is not None else None)

    # -- host fallbacks ----------------------------------------------------
    def _note_fallback(self):
        self.fallbacks += 1
        if self.store is not None:
            self.store.count_lower_fallback()

    def _host_window(self, win):
        """Exact host path for one whole window (non-UTF-8 windows, lines
        wider than a batch)."""
        self._note_fallback()
        if self.dedup:
            blk = chunk_doc_freq(win, self.mode, self.lower,
                                 self.pair_values)
        else:
            blk = chunk_token_counts(win, self.mode, self.lower,
                                     self.pair_values)
        return (blk,) if blk is not None and len(blk) else ()

    def _host_batch(self, buf, starts, lens, lines):
        """Exact host grouping for one collided batch:
        ``handoff.group_token_rows`` (np.unique over length-prefixed
        token byte rows — colliding hashes can never merge distinct
        tokens; the ONE copy shared with the handoff miss path)."""
        from . import hashing
        from .handoff import group_token_rows

        self._note_fallback()
        uniq, counts = group_token_rows(buf, starts, lens, lines,
                                        self.dedup)
        keys = np.empty(len(uniq), dtype=object)
        for i in range(len(uniq)):
            ln = int(uniq[i, 0])
            keys[i] = uniq[i, 1:1 + ln].tobytes().decode("utf-8", "replace")
        h1, h2 = hashing.hash_keys(keys)
        return self._emit(keys, counts.astype(np.int64), h1, h2)

    def _emit(self, keys, counts, h1, h2):
        from ..blocks import Block

        n = len(keys)
        if self.pair_values:
            vals = np.empty(n, dtype=object)
            for i in range(n):
                vals[i] = (keys[i], int(counts[i]))
        else:
            vals = np.asarray(counts, dtype=np.int64)
        return Block(keys, vals, h1, h2)

    # -- long tokens (host dict, window-scoped like the numpy path) --------
    def _long_tokens(self, buf, starts, lens, line_id, long_idx):
        from . import hashing

        bb_get = buf.tobytes if len(long_idx) > 1024 else None
        bb = bb_get() if bb_get else None
        agg = {}
        seen = set()
        for i in long_idx:
            s = int(starts[i])
            ln = int(lens[i])
            raw = (bb[s:s + ln] if bb is not None
                   else buf[s:s + ln].tobytes())
            tok = raw.decode("utf-8", "replace")
            if self.dedup:
                key = (int(line_id[i]), tok)
                if key in seen:
                    continue
                seen.add(key)
            agg[tok] = agg.get(tok, 0) + 1
        keys = np.empty(len(agg), dtype=object)
        counts = np.empty(len(agg), dtype=np.int64)
        for i, (k, c) in enumerate(agg.items()):
            keys[i] = k
            counts[i] = c
        h1, h2 = hashing.hash_keys(keys)
        return self._emit(keys, counts, h1, h2)

    # -- the pipeline ------------------------------------------------------
    @property
    def _handoff_live(self):
        return self._hv is not None and not self._hv.degraded

    def _absorb_or_out(self, blocks, out):
        """Route host-path blocks: into the handoff accumulator while it
        is live (a refused absorb degrades — the flushed accumulator and
        the unabsorbed block both land in ``out``), else straight into
        the emitted stream."""
        for blk in blocks:
            if blk is None or not len(blk):
                continue
            if self._handoff_live:
                if self._hv.absorb_block(blk):
                    continue
                fb = self._hv.degrade("vocabulary or lane budget "
                                      "exceeded")
                if fb is not None and len(fb):
                    out.append(fb)
            out.append(blk)

    def _degrade_to(self, out, reason):
        fb = self._hv.degrade(reason)
        if fb is not None and len(fb):
            out.append(fb)

    def _pad_batch(self, buf, starts, lens, lines):
        """Shared padded-matrix construction for both program shapes."""
        n = len(starts)
        with devtime.phase("scan_feed", "scan", "pad", track="codec",
                           prof="build") as ph:
            L = _len_bucket(lens.max())
            npad = max(_pow2(n),
                       8192 if settings.lower_pallas_segfold else 8)
            idx = starts[:, None] + np.arange(L, dtype=np.int64)[None, :]
            np.clip(idx, 0, len(buf) - 1, out=idx)
            mat = np.zeros((npad, L), dtype=np.uint8)
            mat[:n] = np.where(np.arange(L, dtype=np.int32)[None, :]
                               < lens[:, None], buf[idx], 0)
            lens_p = np.zeros(npad, dtype=np.int32)
            lens_p[:n] = lens
            lines_p = np.zeros(npad, dtype=np.int32)
            if lines is not None:
                lines_p[:n] = lines
            ph.nbytes = mat.nbytes
        return mat, lens_p, lines_p

    def _dispatch(self, starts, lens, padded):
        """Launch the classic program over one padded batch; h2d payload
        bytes are charged to the store's HBM counters.  The feed's
        phases (``devtime.phase``) are ``pad`` (``build`` under
        ``settings.profile``) and ``dispatch`` (the program call and its
        argument feed, ``h2d``); ``wait`` (``compute``) and ``fetch``
        (``d2h``) follow at drain."""
        n = len(starts)
        mat, lens_p, lines_p = padded
        npad, L = mat.shape
        fn = _token_fold_jit(npad, L, self.dedup,
                             settings.lower_pallas_segfold,
                             _lower_interpret())
        nbytes = mat.nbytes + lens_p.nbytes + lines_p.nbytes
        if self.store is not None:
            self.store.count_h2d(nbytes)
        # Dispatch is async: this phase is the launch + feed cost; the
        # program's run time surfaces as ``wait`` at drain.
        with devtime.phase("scan_feed", "device", "dispatch", track="device",
                           prof="h2d", tokens=n, bytes=nbytes) as ph:
            ph.nbytes = nbytes
            out = fn(mat, lens_p, lines_p)
        self.batches += 1
        return _Batch(out, starts, lens, n)

    def _next_batch(self, buf, starts, lens, lines, out):
        """Pad one batch and dispatch it through whichever program the
        vocabulary state calls for (table probe once the vocabulary
        converged, classic otherwise).  The job's first dispatch joins
        its chip's stage vocabulary: a seeded one (waited for while
        another job seeds it) starts the job in table mode, else this
        job seeds it.  A refused table dispatch (overflow/budget guard)
        degrades the job and falls back to classic."""
        from .. import faults as _faults

        # Fault site: a classified failure here surfaces through the map
        # job and rides the job retry loop (the whole-chunk fallback
        # paths keep results byte-identical on re-execution).
        _faults.check("device_dispatch")
        padded = self._pad_batch(buf, starts, lens, lines)
        if self._handoff_live and not self._hv.joined:
            self._hv.join()
        if self._handoff_live and self._hv.table_mode:
            batch = self._hv.dispatch(*padded, starts, lens, lines,
                                      len(starts))
            if batch is not None:
                self.batches += 1
                return batch
            self._degrade_to(out, "count-lane overflow guard or hbm "
                                  "budget exceeded mid-stage")
        return self._dispatch(starts, lens, padded)

    def _resolve(self, buf, batch, out):
        """Drain one in-flight dispatch of either shape into ``out`` (or
        into the device accumulator when the handoff is live)."""
        from .handoff import _TABLE_REVERT_MISS_FRAC, _TableBatch

        if isinstance(batch, _TableBatch):
            if not self._handoff_live:
                # The vocabulary degraded while this dispatch was in
                # flight: its HIT counts left with the accumulator
                # flush (they scattered at dispatch time), but its
                # misses never landed anywhere — emit them through the
                # exact host grouping or they are lost.
                self._emit_table_misses(buf, batch, out, count_d2h=True)
                return
            ok, miss_frac = self._hv.drain(buf, batch)
            if not ok:
                # The absorb refused (vocabulary/lane budget): no miss
                # count landed, so the degrade flush holds only this
                # batch's hits — the misses emit exactly on host.
                self._degrade_to(out, "vocabulary or lane budget "
                                      "exceeded")
                self._emit_table_misses(buf, batch, out,
                                        count_d2h=False)
            elif miss_frac > _TABLE_REVERT_MISS_FRAC:
                # Vocabulary shift: bootstrap again through the classic
                # program until the table converges once more.
                self._hv.table_mode = False
            return
        blk = self._drain(buf, batch, out)
        if self._hv is not None:
            # A seeding job's first drain is in the vocabulary (or the
            # job degraded): the chip's waiting jobs go on.
            self._hv.end_seed()
        if blk is not None and len(blk):
            out.append(blk)

    def _emit_table_misses(self, buf, batch, out, count_d2h):
        """Missed tokens of a table dispatch that can no longer enter
        the (degraded) accumulator: group them exactly on host —
        ``_host_batch``, the same grouping the classic collision
        fallback uses — and emit the block.  ``count_d2h`` charges the
        miss-evidence fetch, and counts the batch's probe, when
        :meth:`HandoffVocab.drain` has not already done so."""
        n_miss = int(batch.n_miss)
        if count_d2h and self.store is not None:
            self.store.count_d2h((batch.npad if n_miss else 0) + 4)
            self.store.count_table_probe(batch.n, batch.n - n_miss)
        if not n_miss:
            return
        if batch.miss_idx is None:
            miss = np.asarray(batch.miss)[:batch.n]
            batch.miss_idx = np.flatnonzero(miss)
        idx = batch.miss_idx
        blk = self._host_batch(
            buf, batch.starts[idx], batch.lens[idx],
            batch.lines[idx] if batch.lines is not None else None)
        if blk is not None and len(blk):
            out.append(blk)

    def _drain(self, buf, batch, out=None):
        """Fetch one classic program's results and build the
        partial-count Block (vocabulary-sized).  Collisions re-group the
        batch on host.  With the handoff live, survivors seed the device
        vocabulary instead of emitting (returns None)."""
        import jax

        with devtime.phase("scan_wait", "device", "wait", track="device",
                           prof="compute", tokens=batch.n):
            jax.block_until_ready(batch.out)
        # The outputs are ready: the conversions are pure d2h movement.
        with devtime.phase("scan_drain", "device", "fetch", track="device",
                           prof="d2h") as ph:
            sh1, sh2, tot, live, rep_orig, collisions = (
                np.asarray(a) for a in batch.out)
            ph.nbytes = d2h_bytes = (sh1.nbytes + sh2.nbytes + tot.nbytes
                                     + live.nbytes + rep_orig.nbytes)
        if self.store is not None:
            self.store.count_d2h(d2h_bytes)
        with devtime.phase("scan_drain", "scan", "decode"):
            return self._decode(buf, batch, out, sh1, sh2, tot, live,
                                rep_orig, int(collisions))

    def _decode(self, buf, batch, out, sh1, sh2, tot, live, rep_orig,
                collisions):
        """The host half of a classic drain: decode the survivors' keys
        and emit them, or seed the handoff vocabulary with them."""
        if collisions:
            lines = None
            if self.dedup:
                # line ids were consumed by the program; rebuild them for
                # the host regroup from the batch's token starts
                lines = self._line_ids(buf, batch.starts)
            blk = self._host_batch(buf, batch.starts, batch.lens, lines)
            if self._handoff_live and out is not None:
                self._absorb_or_out((blk,), out)
                return None
            return blk
        idx = np.flatnonzero(live)
        if not len(idx):
            return None
        counts = tot[idx].astype(np.int64)
        h1g = sh1[idx]
        h2g = sh2[idx]
        reps = rep_orig[idx]
        keys = np.empty(len(idx), dtype=object)
        starts, lens = batch.starts, batch.lens
        for i, r in enumerate(reps):
            s = int(starts[r])
            keys[i] = buf[s:s + int(lens[r])].tobytes().decode(
                "utf-8", "replace")
        if self._handoff_live:
            ok, new_frac = self._hv.absorb_drain(keys, counts, h1g, h2g,
                                                 batch.n)
            if not ok:
                if out is not None:
                    self._degrade_to(out, "vocabulary or lane budget "
                                          "exceeded")
                    out.append(self._emit(keys, counts, h1g, h2g))
                    return None
                blk = self._emit(keys, counts, h1g, h2g)
                return blk
            if new_frac < _handoff_enter_frac():
                self._hv.table_mode = True
            return None
        return self._emit(keys, counts, h1g, h2g)

    def _line_ids(self, buf, starts):
        nl = np.flatnonzero(buf == 10)
        line_starts = np.concatenate(([0], nl + 1)).astype(np.int64)
        return (np.searchsorted(line_starts, starts, side="right")
                - 1).astype(np.int32)

    def add(self, win):
        try:
            return self._add(win)
        except BaseException:
            if self._hv is not None:
                # A failed seeding must not hold the chip's other jobs.
                self._hv.end_seed()
            raise

    def _add(self, win):
        data = bytes(win) if isinstance(win, memoryview) else win
        buf = np.frombuffer(data, dtype=np.uint8)
        if not len(buf):
            return ()
        out = []
        if (buf > 127).any():
            # Only valid-UTF-8 windows lower: token substrings of valid
            # UTF-8 decode losslessly (boundaries are ASCII), so no
            # U+FFFD substitution can desync keys from their raw-byte
            # hash lanes or the per-line byte-dedup contract.  A strict
            # decode attempt is the one-pass equivalent of the
            # replace-decode round-trip test.
            try:
                data.decode("utf-8")
            except UnicodeDecodeError:
                self._absorb_or_out(self._host_window(win), out)
                return out
        from .handoff import _host_bootstrap

        if self._handoff_live and not self._hv.joined and _host_bootstrap():
            self._hv.join()
            if self._hv.seeding:
                # CPU-backend bootstrap: the seeding job's first window
                # fills the vocabulary through the NATIVE host codec —
                # its blocks carry cached hash lanes, so the absorb never
                # re-hashes or re-sorts, and this window's
                # tokenize/pad/dispatch is skipped outright (~20x the
                # classic bootstrap program, which has no accelerator to
                # hide on here).  Counts are byte-identical: absorb_block
                # keys by canonical utf-8 bytes, the same contract as a
                # classic drain.  Table mode engages immediately — a
                # vocabulary that fails to cover the next window's
                # batches reverts through the standard miss-fraction
                # bar.
                from .. import faults as _faults
                from .handoff import CLASSIC_DRAIN_BYTES_PER_SLOT

                # The bootstrap replaces this window's program dispatches
                # — it keeps their fault site, so chaos schedules aimed
                # at the lowered map fire on every backend.
                _faults.check("device_dispatch")
                with _trace.span("handoff", "bootstrap-host",
                                 bytes=len(data)):
                    # The native grouping is codec work — bucketed and
                    # traced as such, so codec_fraction/critpath keep
                    # attributing the scan's host compute when the
                    # handoff swallows every emitted block.
                    with devtime.track("codec"), _trace.span(
                            "codec", "codec-window", bytes=len(data)):
                        if self.dedup:
                            blk = chunk_doc_freq(data, self.mode,
                                                 self.lower,
                                                 self.pair_values)
                        else:
                            blk = chunk_token_counts(data, self.mode,
                                                     self.lower,
                                                     self.pair_values)
                    self._absorb_or_out(
                        (blk,) if blk is not None else (), out)
                self._hv.end_seed()
                if self._handoff_live and self._hv.shared.seeded:
                    self._hv.table_mode = True
                    if self.store is not None and blk is not None:
                        # Drain bytes the classic path would have
                        # fetched for this window, one-batch lower
                        # bound (its real fetch scales with padded
                        # TOKENS, not distinct keys).
                        self.store.count_d2h_avoided(
                            CLASSIC_DRAIN_BYTES_PER_SLOT * len(blk))
                return out
        with devtime.phase("scan_feed", "scan", "bounds", track="codec",
                           bytes=len(buf)):
            if self.lower:
                buf = _LOWER[buf]
            starts, lens = _token_bounds(buf, self.mode)
        n = len(starts)
        if n == 0:
            return ()
        line_id = None
        if self.dedup:
            with devtime.phase("scan_feed", "scan", "line-ids"):
                line_id = self._line_ids(buf, starts)

        short = lens <= _SHORT_TOKEN
        long_idx = np.flatnonzero(~short)
        s_starts, s_lens, s_lines = starts, lens, line_id
        if len(long_idx):
            sidx = np.flatnonzero(short)
            s_starts, s_lens = starts[sidx], lens[sidx]
            s_lines = line_id[sidx] if line_id is not None else None
        ns = len(s_starts)

        bounds = (_batch_bounds(s_lines, ns,
                                max(1024, settings.lower_batch))
                  if ns else [])
        if bounds is None:
            # The whole-window host path recounts EVERY token, long ones
            # included — nothing else may land for this window (long
            # tokens commit only after this check passes, so they can
            # never count twice).
            self._absorb_or_out(self._host_window(win), out)
            return out

        if len(long_idx):
            blk = self._long_tokens(buf, starts, lens, line_id, long_idx)
            self._absorb_or_out((blk,), out)
        if ns == 0:
            return out

        # Double-buffered feed: build + dispatch batch i+1 while batch i's
        # program runs; drain resolves the previous dispatch only after
        # the next one is in flight (jax dispatch is async).
        pending = None
        for a, b in bounds:
            if pending is not None and self._hv is not None \
                    and self._hv.seeding:
                # Bootstrap sync: resolve the seeding job's FIRST classic
                # batch before the next dispatch — its drain seeds the
                # chip's vocabulary, so every remaining batch, and every
                # batch of the chip's other jobs, can run the cheap table
                # program.  One batch of lost overlap buys table-mode for
                # the rest of the stage on this chip.
                self._resolve(buf, pending, out)
                pending = None
            nxt = self._next_batch(
                buf, s_starts[a:b], s_lens[a:b],
                s_lines[a:b] if s_lines is not None else None, out)
            if pending is not None:
                self._resolve(buf, pending, out)
            pending = nxt
        if pending is not None:
            self._resolve(buf, pending, out)
        return out

    def finish(self):
        return ()

    def finalize_handoff(self, store, n_partitions):
        """Register the job's accumulated vocabulary as per-partition
        HBM-resident refs (the plan's ``handoff="device"`` edge).
        Returns ``(blocks, {pid: [BlockRef]})`` — ``blocks`` is the
        degrade flush the caller must push through the classic combine
        path; at most one side is non-empty."""
        if self._hv is None:
            return (), {}
        with _trace.span("handoff", "finalize"):
            return self._hv.finalize(store, n_partitions)


def device_window_sink(mapper, store=None, handoff=False, jobs=1,
                       vocabs=None):
    """The device window sink for a claimed mapper, or None.
    ``handoff=True`` arms the cross-stage device-resident tier (a
    pair-values scanner — an object lane with no device tier — silently
    stays on the classic emit path); ``jobs`` is the stage's concurrent
    job count, dividing the handoff budget per job; ``vocabs`` is the
    stage's :class:`~.handoff.StageVocabs` (None: a vocabulary of the
    job's own)."""
    params = claims(mapper)
    if params is None:
        return None
    return DeviceTokenFoldSink(params, store=store, handoff=handoff,
                               jobs=jobs, vocabs=vocabs)

