"""Out-of-core storage: block refs, spill tiers, scratch layout, accounting.

Replaces the reference's disk-spill machinery — RSS-watermark writers
(dampr/dataset.py:119-262, memory.py) and the /tmp/<job>/stage_N scratch tree
(base.py:435-469) — with deterministic byte accounting: block sizes are known
exactly, so no /proc sampling is needed.  The tier order is HBM → RAM → disk:
numeric value lanes of reduce-feeding stage outputs stay device-resident
under ``settings.hbm_budget`` (the reduce's collective fold consumes them
in place — no host round-trip at the map→reduce boundary); device→host
offload is the first spill step, gzip'd pickle files on disk the second.

Every stage output lives behind :class:`BlockRef`; the per-run
:class:`RunStore` decides which refs stay hot.  ``pin=True`` refs (``cached()``
stages) never spill.

Spill I/O rides :mod:`dampr_tpu.io`: blocks spill as chunked-frame files
(independently compressed length-prefixed frames + an index footer —
parallel decompress, streamable partial reads) through a background
writer pool whose in-flight bytes are charged against the stage budget,
and spilled runs read back through a prefetching frame reader.  Pre-frame
spills (whole-file gzip / plain pickle streams) remain readable via magic
sniffing.
"""

import contextlib
import gzip
import logging
import os
import pickle
import shutil
import threading
import time
import uuid

import numpy as np

from . import settings
from .io import codecs as _codecs
from .io import frames as _frames
from .io.writer import SpillWriterPool
from .obs import metrics as _metrics
from .obs import trace as _trace

log = logging.getLogger("dampr_tpu.storage")

_I32_MAX = 2 ** 31 - 1

_warned_spill_modes = set()  # one warning per unrecognized policy string


def _file_size(path):
    try:
        return os.path.getsize(path)
    except OSError:
        return 0


class BlockRef(object):
    """A handle to one materialized block: HBM-resident (numeric value lane
    on device — the tier the reference never had), RAM-resident,
    compressed-in-RAM (pinned ``cached()`` blocks — the reference's
    MemGZipDataset tier, dampr/dataset.py:528-547), or spilled to disk.

    Device residency model: the VALUE lane and both hash lanes live on
    device (what the reduce-side collective fold consumes); keys and the
    hash lanes ALSO stay host-side as ``_kmeta`` (partition routing and the
    exact-key table are host metadata by design), so a device-fold reduce
    touches the value lane without any host copy in either direction.
    ``lane_abs``/``lane_min`` carry the registration-time exactness
    metadata the cross-window overflow accounting needs (computed where the
    host array still existed — no device fetch ever required for it)."""

    __slots__ = ("_block", "_packed", "path", "nbytes", "nrecords",
                 "value_dtype", "key_dtype", "store", "pin",
                 "_dev", "_kmeta", "dev_bytes", "lane_abs", "lane_min",
                 "_dead", "_h2d_pending")

    def __init__(self, block, store=None, pin=False, device_prep=None):
        self._packed = None
        self._dead = False
        self._h2d_pending = 0
        self.nrecords = len(block)
        self.value_dtype = block.values.dtype  # metadata survives spilling
        self.key_dtype = block.keys.dtype
        self.store = store
        self.pin = pin
        self.path = None
        self._dev = None
        self._kmeta = None
        self.dev_bytes = 0
        self.lane_abs = None
        self.lane_min = None
        if pin:
            # cached() semantics: compressed RAM, charged at compressed size
            # (never spilled to disk, decompressed per read).
            self._block = None
            self._packed = pack_block(block)
            self.nbytes = len(self._packed)
        elif device_prep is not None:
            self._put_device(block, device_prep)
        else:
            self._block = block
            self.nbytes = block.nbytes()

    # -- HBM tier ----------------------------------------------------------
    @staticmethod
    def lane_prep(values, kind_hint="sum"):
        """One pass over a value lane deciding device eligibility AND
        producing everything _put_device needs: returns None (ineligible —
        mirrors parallel.shuffle._lane_safe_values' whitelist, so a
        device-tiered block can never hit the fold's refusal path at reduce
        time) or ``(lane_vals, lane_abs, lane_min)``."""
        import jax

        x64 = jax.config.jax_enable_x64
        dt = values.dtype
        if values.ndim != 1:
            return None  # composite lanes: mesh fold lanes are 1D-shaped
        if dt == object or dt == np.uint64 or (
                dt == np.float64 and not x64):
            return None
        if dt.kind == "f":
            if dt == np.float16:
                return values.astype(np.float32), None, None
            return values, None, None
        if dt == np.bool_ or dt.kind in "iu":
            v64 = values.astype(np.int64)
            if not len(v64):
                return (v64 if x64 else v64.astype(np.int32)), 0, 0
            lo, hi = int(v64.min()), int(v64.max())
            if x64:
                # Unbounded int64 lane: a float64 abs-sum over-estimate
                # (margin applied at use) — np.abs on raw int64 could wrap
                # at int64 min.
                lane_abs = float(np.abs(v64.astype(np.float64)).sum())
                return v64, lane_abs, lo
            if lo < -_I32_MAX - 1 or hi > _I32_MAX:
                return None
            lane_abs = int(np.abs(v64).sum())
            if kind_hint == "sum" and lane_abs > _I32_MAX:
                return None
            return v64.astype(np.int32), lane_abs, lo
        return None

    def _put_device(self, block, prep):
        """Move the value lane (cast to its exact device lane dtype by
        lane_prep) and hash lanes to device; keys + hashes stay host as
        routing metadata."""
        import jax

        from .ops import devtime

        h1, h2 = block.hashes()
        lane_vals, self.lane_abs, self.lane_min = prep
        with devtime.track("transfer"), _trace.span(
                "hbm", "h2d", bytes=int(lane_vals.nbytes + h1.nbytes
                                        + h2.nbytes)):
            self._dev = (jax.device_put(lane_vals), jax.device_put(h1),
                         jax.device_put(h2))
        self.dev_bytes = lane_vals.nbytes + h1.nbytes + h2.nbytes
        # Boundary accounting is per actual transfer, never per
        # registration: the store drains this pending charge exactly once
        # (a ref re-registered after a fallback adds nothing).
        self._h2d_pending = self.dev_bytes
        self._kmeta = (block.keys, h1, h2)
        self._block = None
        # Host budget is charged for what stays host-resident; object key
        # lanes charge the same 64 B/record heuristic Block.nbytes uses
        # (raw .nbytes would count 8-byte pointers, not the strings).
        kb = (block.keys.nbytes if block.keys.dtype != object
              else len(block.keys) * 64)
        self.nbytes = kb + h1.nbytes + h2.nbytes

    @property
    def is_device(self):
        return self._dev is not None

    def device_lanes(self):
        """(values, h1, h2) jax arrays — the reduce-side fold's input."""
        return self._dev

    def host_meta(self):
        """(keys, h1, h2) host arrays (routing / exact-key table)."""
        return self._kmeta

    def offload(self):
        """Device -> host: the HBM tier's first spill step.  Returns
        (freed_dev_bytes, host_bytes_delta); the caller re-enters this ref
        into host accounting."""
        if self._dev is None:  # raced with a concurrent drop
            return 0, 0
        blk = self.get()  # one counted device fetch of the value lane
        freed = self.dev_bytes
        old_host = self.nbytes
        # Publish order matters: reduce jobs read this ref concurrently
        # (eviction runs outside the store lock), so the host block must be
        # visible BEFORE the device lanes disappear — mirroring spill(),
        # which writes ``path`` before clearing ``_block``.  A reader that
        # still sees ``_dev`` uses its own snapshot (get() below).
        self._block = blk
        self.nbytes = blk.nbytes()
        self._dev = None
        self._kmeta = None
        self.dev_bytes = 0
        return freed, self.nbytes - old_host

    @classmethod
    def from_device_lanes(cls, keys, h1, h2, dev_vals, dev_h1, dev_h2,
                          store=None, value_dtype=None, lane_abs=None,
                          lane_min=None, h2d_bytes=0):
        """Build an HBM-resident ref straight from ALREADY-device-resident
        lanes (the cross-stage handoff tier): a lowered producer's program
        outputs become the consuming fold's input without ever leaving the
        device.  ``keys``/``h1``/``h2`` are the host routing metadata
        (the same ``_kmeta`` contract as ``_put_device``);
        ``value_dtype`` is the dtype ``get()`` materializes on the host
        fallback path (what the spill path would have registered);
        ``h2d_bytes`` charges only what was genuinely uploaded to
        assemble the ref (hash lanes), never the value lane — it was
        already resident."""
        ref = cls.__new__(cls)
        ref._packed = None
        ref._dead = False
        ref.path = None
        ref.nrecords = len(keys)
        ref.value_dtype = (np.dtype(value_dtype) if value_dtype is not None
                           else np.dtype(dev_vals.dtype))
        ref.key_dtype = keys.dtype
        ref.store = store
        ref.pin = False
        ref._dev = (dev_vals, dev_h1, dev_h2)
        ref._kmeta = (keys, h1, h2)
        ref._block = None
        ref.dev_bytes = int(dev_vals.nbytes + dev_h1.nbytes
                            + dev_h2.nbytes)
        ref._h2d_pending = int(h2d_bytes)
        ref.lane_abs = lane_abs
        ref.lane_min = lane_min
        kb = (keys.nbytes if keys.dtype != object else len(keys) * 64)
        ref.nbytes = kb + h1.nbytes + h2.nbytes
        return ref

    @classmethod
    def from_disk(cls, path, nrecords, nbytes, key_dtype, value_dtype):
        """Rebuild a disk-backed ref from checkpoint-manifest metadata
        (resume.py): no RAM residency, reads stream from ``path``."""
        import numpy as np

        ref = cls.__new__(cls)
        ref._block = None
        ref._packed = None
        ref.path = path
        ref.nrecords = nrecords
        ref.nbytes = nbytes
        ref.key_dtype = np.dtype(key_dtype)
        ref.value_dtype = np.dtype(value_dtype)
        ref.store = None
        ref.pin = False
        ref._dev = None
        ref._kmeta = None
        ref.dev_bytes = 0
        ref.lane_abs = None
        ref.lane_min = None
        ref._dead = False
        ref._h2d_pending = 0
        return ref

    def __len__(self):
        return self.nrecords

    @property
    def total_bytes(self):
        """Host + device bytes: what size-based gates must sum (nbytes
        alone hides an HBM-resident value lane)."""
        return self.nbytes + self.dev_bytes

    @property
    def resident(self):
        return self._block is not None

    def get(self):
        blk = self._block
        if blk is None:
            # Snapshot the device lanes + host metadata into locals: a
            # concurrent offload() publishes _block first, then clears
            # _dev/_kmeta, so a reader passing the _dev check must not
            # re-read those slots (it could otherwise unpack a None).
            dev, kmeta = self._dev, self._kmeta
            if dev is not None and kmeta is not None:
                # Host materialization of a device-resident block: one
                # value-lane fetch (counted — the HBM tier's whole point is
                # that device-fold reduces never take this path).
                from .ops import devtime

                with devtime.track("transfer"):
                    vals = np.asarray(dev[0]).astype(
                        self.value_dtype, copy=False)
                if self.store is not None:
                    self.store.count_d2h(vals.nbytes)
                keys, h1, h2 = kmeta
                from .blocks import Block

                # Handoff lanes may be pow2-padded past the records.
                return Block(keys, vals[:len(keys)], h1, h2)
            blk = self._block  # re-check: offload may have just published
            if blk is not None:
                return blk
            if self._packed is not None:
                return unpack_block(self._packed)
            blk = load_block(self.path, self.store)
            # Do not re-cache: reduce jobs stream partitions one at a time and
            # re-residency would defeat the memory bound.
        return blk

    def iter_windows(self):
        """Stream the block in bounded windows without materializing it
        whole (resident blocks yield array-view slices)."""
        blk = self._block
        if blk is None:
            if self._packed is not None:
                blk = unpack_block(self._packed)
            elif self._dev is not None or self.path is None:
                # Device-resident — or an offload racing us (path exists
                # only once spilled): get() resolves the live tier with a
                # consistent snapshot.
                blk = self.get()
            else:
                for w in iter_block_windows(self.path, self.store):
                    yield w
                return
        from .blocks import Block

        n = len(blk)
        for at in range(0, n, SPILL_WINDOW):
            end = min(at + SPILL_WINDOW, n)
            yield Block(
                blk.keys[at:end], blk.values[at:end],
                None if blk.h1 is None else blk.h1[at:end],
                None if blk.h2 is None else blk.h2[at:end])

    def spill(self, directory):
        if self._block is None or self.pin:
            return 0
        if self.path is None:
            os.makedirs(directory, exist_ok=True)
            path = os.path.join(directory, uuid.uuid4().hex + ".blk")
            t0 = time.perf_counter()

            def write_once():
                # Same transient-retry + fault-site contract as the
                # background writer pool ("wb" truncates, so a retried
                # partial write is idempotent).
                from . import faults as _faults

                _faults.check("spill_write")
                save_block(self._block, path)

            from . import faults as _faults

            _faults.retry_io(write_once, "spill_write")
            secs = time.perf_counter() - t0
            self.path = path
            # The synchronous path feeds the same io bandwidth counters
            # as the writer pool, so spill_write_mbps stays comparable
            # with DAMPR_TPU_SPILL_WRITERS=0 (the async-off baseline).
            if self.store is not None:
                self.store.count_spill_write(_file_size(path), secs)
        # else: already durable on disk (checkpoint/resume persisted it) —
        # dropping the RAM copy is the whole spill.
        freed = self.nbytes
        self._block = None
        return freed

    def delete(self):
        # Serialized against the background writer's publish (both take
        # the store lock): either the publish lands first and this delete
        # unlinks the published file, or the ``_dead`` flag lands first
        # and the publish unlinks its own write — a dropped ref can never
        # leak a freshly spilled file either way.
        store = self.store
        if store is not None:
            with store._lock:
                self._delete_inner()
        else:
            self._delete_inner()

    def _delete_inner(self):
        self._dead = True
        self._block = None
        self._packed = None
        self._dev = None
        self._kmeta = None
        self.dev_bytes = 0
        if self.path and os.path.exists(self.path):
            os.unlink(self.path)
            self.path = None


#: Records per spill window: the unit of streamed re-reads.  Bounded so a
#: k-way merge holds k windows, never k whole blocks.
SPILL_WINDOW = 16384


def _spill_codec(key_dtype, value_dtype):
    """Compression policy, shared by every spill writer: numeric columns
    (hashes, parsed numbers, counts) are mostly high-entropy, so a codec
    buys little and costs a core-bound pass each way — they spill as raw
    frames at disk bandwidth; object lanes compress with the configured
    codec (``settings.spill_codec``).  ``settings.spill_compress`` =
    "always"/"never" overrides the heuristic, and a codec name there
    ("zstd", "zlib:6", ...) means always-compress with that codec."""
    mode = str(settings.spill_compress).lower()
    if mode in ("never", "0", "false", "none", "raw"):
        return _codecs.resolve("raw")
    if mode in ("always", "1", "true"):
        return _codecs.resolve(settings.spill_codec,
                               settings.compress_level)
    if mode != "auto":
        try:
            return _codecs.resolve(mode, settings.compress_level)
        except ValueError:
            # Tolerate unrecognized policy strings the way the old
            # boolean heuristic did ("on", "yes", ... behaved as auto):
            # a config typo must not fail the run at its first spill.
            if mode not in _warned_spill_modes:
                _warned_spill_modes.add(mode)
                log.warning("unrecognized settings.spill_compress %r; "
                            "using the 'auto' policy", mode)
    if key_dtype != object and value_dtype != object:
        return _codecs.resolve("raw")
    return _codecs.resolve(settings.spill_codec,
                           settings.compress_level)


def save_block(block, path):
    """Spill wire format (dampr_tpu.io.frames): pickled columnar
    SPILL_WINDOW slices, each an independently compressed length-prefixed
    frame, with an index footer — frames decompress in parallel and merge
    readers stream partial ranges instead of inflating whole blocks.
    Readers sniff the magic, so these coexist with pre-frame gzip/plain
    spills (``iter_block_windows`` reads all three)."""
    codec = _spill_codec(block.keys.dtype, block.values.dtype)
    with open(path, "wb") as f:
        _frames.write_block_frames(block, f, codec, SPILL_WINDOW,
                                   at_least_one=True)


def _iter_legacy_windows(path, magic):
    """Pre-frame spill formats: a pickle-window stream, whole-file gzip'd
    for object-lane blocks (sniffed).  Kept verbatim so spill dirs and
    checkpoint manifests written before the frame format still load."""
    from .blocks import Block

    with open(path, "rb") as raw:
        f = gzip.GzipFile(fileobj=raw) if magic[:2] == b"\x1f\x8b" else raw
        while True:
            try:
                keys, values, h1, h2 = pickle.load(f)
            except EOFError:
                return
            yield Block(keys, values, h1, h2)


def iter_block_windows(path, store=None):
    """Stream a spilled block back window by window (bounded memory).
    Sniffs the leading magic: frame files get the prefetching frame
    reader (``settings.spill_read_prefetch`` frames in flight on the
    shared read executor); legacy gzip / plain pickle streams read
    serially.  ``store`` (when given) accrues read-bandwidth and
    ``io_wait`` accounting."""
    from .blocks import Block

    # One open serves both the magic sniff and the frame reader (the fd
    # is adopted); only the legacy formats re-open through the buffered
    # stream readers.
    fd = os.open(path, os.O_RDONLY)
    magic = os.pread(fd, 4, 0)
    if not _frames.is_frame_file(magic):
        os.close(fd)
        for w in _iter_legacy_windows(path, magic):
            yield w
        return

    on_read = on_wait = None
    if store is not None:
        on_read = store.count_spill_read

        def on_wait(secs):
            store.count_io_wait(secs, read=True)
            if _trace.enabled():
                _trace.complete("io_wait", "read-wait",
                                time.perf_counter() - secs)

    reader = _frames.FrameReader(path, fd=fd)
    payloads = reader.iter_payloads(
        settings.spill_read_prefetch, on_read, on_wait)
    try:
        for payload in payloads:
            keys, values, h1, h2 = _frames.load_window_payload(payload)
            yield Block(keys, values, h1, h2)
    finally:
        # Close the payload generator FIRST: its own finally waits out
        # in-flight prefetch reads before the fd goes away (closing the
        # fd under a live pread could hit EBADF — or a recycled fd
        # number).  The direct close is the sequential-branch backstop.
        payloads.close()
        reader.close()


def load_block(path, store=None):
    from .blocks import Block

    return Block.concat(list(iter_block_windows(path, store)))


def pack_block(block):
    """Compress a block into RAM bytes (the ``cached()`` tier)."""
    import io

    buf = io.BytesIO()
    with gzip.GzipFile(fileobj=buf, mode="wb",
                       compresslevel=settings.compress_level) as f:
        pickle.dump((block.keys, block.values, block.h1, block.h2), f,
                    protocol=pickle.HIGHEST_PROTOCOL)
    return buf.getvalue()


def unpack_block(data):
    import io

    from .blocks import Block

    with gzip.GzipFile(fileobj=io.BytesIO(data), mode="rb") as f:
        keys, values, h1, h2 = pickle.load(f)
    return Block(keys, values, h1, h2)


class RunStore(object):
    """Per-run block registry with a byte budget (the memory-governor analog).

    Tracks every RAM-resident ref; when residency exceeds
    ``settings.max_memory_per_stage`` the oldest unpinned refs spill to the
    run's scratch directory.  Thread-safe — map jobs register refs
    concurrently.
    """

    def __init__(self, name, budget=None):
        safe = name.replace("/", "_")
        self.root = os.path.join(settings.scratch_root, safe)
        self.budget = settings.max_memory_per_stage if budget is None else budget
        self._lock = threading.Lock()
        self._resident = []          # FIFO of RAM refs
        self._resident_bytes = 0
        self._dev_resident = []      # FIFO of HBM refs
        self._dev_bytes = 0
        self._stage = "stage_0"
        self._attempts = threading.local()
        self.spill_count = 0
        self.spilled_bytes = 0
        # HBM tier stats: the boundary evidence (h2d at registration,
        # offloads + d2h fetches after — a device-fold reduce adds zero to
        # d2h_bytes for the lanes it consumed).
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.hbm_offloads = 0
        self.hbm_peak_bytes = 0
        # Cross-stage handoff tier (docs/plan.md "Cross-stage device
        # fusion"): device bytes registered WITHOUT a host round-trip,
        # the drain bytes the table-mode programs never fetched, and how
        # many times an edge degraded back to the spill path.
        self.handoff_active = False   # set by the runner per plan
        self.handoff_bytes = 0
        self.d2h_avoided_bytes = 0
        self.handoff_degrades = 0
        # The table programs' bucket index: tokens probed, tokens that
        # hit a verified slot, and vocabulary slots left out of the index
        # at job end (their tokens took the exact host miss path).
        self.table_probe_tokens = 0
        self.table_probe_hits = 0
        self.table_index_unplaced = 0
        # The handoff stage vocabularies (``ops.handoff.StageVocab``):
        # slots inserted into them on the host, and map jobs that joined
        # one another job had already seeded.
        self.handoff_slot_inserts = 0
        self.handoff_shared_joins = 0
        # Lowered map batches or windows handed back to the exact host
        # grouping (hash collision, non-UTF-8 window, over-wide line).
        self.lower_fallbacks = 0
        # Chips the lowered map jobs were placed over
        # (``parallel.placement``), the lowered feed's h2d bytes per chip
        # (mesh position -> bytes), the mesh size of the collective fold
        # programs run (0: none) and the bytes their all_to_all moved
        # between chips.
        self.mesh_chips = 1
        self.h2d_by_chip = {}
        self.fold_chips = 0
        self.fold_a2a_bytes = 0
        # Overlap executor accounting: bytes of in-flight scan windows /
        # codec output the pipelined map driver holds ahead of the fold.
        # Charged against the same budget as resident blocks (reserving
        # overlap bytes pushes resident refs out to disk), so overlapping
        # never raises the stage's memory ceiling.
        self._overlap_bytes = 0
        self.overlap_peak_bytes = 0
        # Spill-lean merge generations: bytes written by streamed run
        # compactions (register_stream) — the only re-spill generation the
        # merge planner ever pays, and only past the merge_fanin cap.
        self.merge_gen_bytes = 0
        self.merge_gens = 0
        # Spill I/O shape (dampr_tpu.io): post-codec bytes/seconds moved
        # by spill writes and frame reads, plus the fold-side seconds
        # spent blocked on the writer pool's backpressure or a
        # not-yet-prefetched frame — the ``io`` section of the run stats.
        self.spill_disk_bytes = 0
        self.spill_write_seconds = 0.0
        self.spill_read_bytes = 0
        self.spill_read_seconds = 0.0
        self.io_wait_seconds = 0.0        # total: write + read side
        self.io_wait_write_seconds = 0.0  # fold-side writer backpressure
        self._writer = None          # lazy SpillWriterPool

    def count_d2h(self, n):
        with self._lock:
            self.d2h_bytes += n

    def count_d2h_avoided(self, n):
        """Drain bytes a handoff-mode program batch kept device-resident
        that the classic path would have fetched (the lowered edge's
        evidence counter)."""
        with self._lock:
            self.d2h_avoided_bytes += n

    def count_table_probe(self, tokens, hits):
        """One drained table-program batch: its tokens and its hits."""
        with self._lock:
            self.table_probe_tokens += tokens
            self.table_probe_hits += hits

    def count_table_unplaced(self, n):
        """A finalized job's counted slots outside its chip's bucket
        index."""
        with self._lock:
            self.table_index_unplaced += n

    def count_slot_inserts(self, n):
        with self._lock:
            self.handoff_slot_inserts += n

    def count_shared_join(self):
        with self._lock:
            self.handoff_shared_joins += 1

    def count_handoff_degrade(self):
        with self._lock:
            self.handoff_degrades += 1

    def count_lower_fallback(self):
        with self._lock:
            self.lower_fallbacks += 1

    def count_h2d(self, n):
        """Feed bytes shipped to device outside the HBM-tier register path
        (the lowered map programs' padded token matrices), charged to
        the chip the calling job is placed on (the default device, 0,
        outside any placement)."""
        from .parallel import placement

        chip = placement.current() or 0
        with self._lock:
            self.h2d_bytes += n
            self.h2d_by_chip[chip] = self.h2d_by_chip.get(chip, 0) + n

    def count_fold(self, n_dev, a2a_bytes):
        """One collective fold program call over ``n_dev`` chips, and the
        bytes its all_to_all sent off-chip."""
        with self._lock:
            self.fold_chips = n_dev
            self.fold_a2a_bytes += a2a_bytes

    def h2d_chip_list(self):
        """The lowered feed's h2d bytes per chip, one entry per chip the
        map jobs were placed over."""
        with self._lock:
            n = max([self.mesh_chips] + [c + 1 for c in self.h2d_by_chip])
            return [self.h2d_by_chip.get(c, 0) for c in range(n)]

    def count_spill_read(self, nbytes, secs):
        with self._lock:
            self.spill_read_bytes += nbytes
            self.spill_read_seconds += secs

    def count_spill_write(self, disk_bytes, secs):
        """One accounting point for every spill writer — sync spills,
        streamed merge generations, and the background pool all feed the
        same bandwidth counters, so their MB/s stay comparable."""
        with self._lock:
            self.spill_disk_bytes += disk_bytes
            self.spill_write_seconds += secs

    def count_io_wait(self, secs, read=False):
        """``read=False`` is the fold-side stall (a register/fold thread
        blocked on writer-pool backpressure — the number the async
        subsystem exists to keep near zero); ``read=True`` is a merge or
        final-read consumer outrunning its frame prefetch."""
        with self._lock:
            self.io_wait_seconds += secs
            if not read:
                self.io_wait_write_seconds += secs

    # -- background writer pool ---------------------------------------------
    @property
    def spill_inflight_bytes(self):
        w = self._writer
        return 0 if w is None else w.inflight_bytes

    @property
    def spill_inflight_peak_bytes(self):
        w = self._writer
        return 0 if w is None else w.inflight_peak

    @property
    def spill_queue_peak(self):
        """Deepest the writer pool's backlog ever got (queued writes) —
        the ``io.writer_queue_peak`` stats field."""
        w = self._writer
        return 0 if w is None else w.queue_peak

    def writer_pool(self):
        """The store's background spill writer, or None when disabled
        (``settings.spill_write_threads = 0`` keeps the synchronous
        pre-frame behavior)."""
        if settings.spill_write_threads <= 0:
            return None
        if self._writer is None:
            with self._lock:
                if self._writer is None:
                    cap = settings.spill_inflight_bytes
                    if not cap or cap <= 0:
                        # None/0/negative all mean "default": a 0 from
                        # the env must not become a 1-byte cap that
                        # serializes every spill.
                        cap = max(self.budget // 2, 1 << 22)
                    self._writer = SpillWriterPool(
                        self, settings.spill_write_threads, cap,
                        SPILL_WINDOW)
        return self._writer

    def publish_spill(self, ref, path, freed_ram, disk_bytes, secs,
                      clear_block=True):
        """Background-write completion: the file is durable (fsync +
        rename done), so land ``path`` and — for true spills — free the
        RAM copy.  Publish order matches the synchronous ``spill()``:
        ``path`` becomes visible before ``_block`` clears, so a reader
        passing the residency check never loses both tiers."""
        unlink = False
        with self._lock:
            if ref._dead:
                unlink = True
            else:
                ref.path = path
                if clear_block:
                    ref._block = None
                    # Counted only for live refs: a raced delete already
                    # freed this RAM itself — charging it here too would
                    # over-report spill volume (the sync path never
                    # counted deleted refs either).
                    self.spill_count += 1
                    self.spilled_bytes += freed_ram
        self.count_spill_write(disk_bytes, secs)
        if unlink:
            try:
                os.unlink(path)
            except OSError:
                pass

    def drain_writes(self):
        """Barrier: every queued spill/persist write has published.  Ran
        at stage boundaries (per-stage spill attribution stays causal) and
        before checkpoint manifests reference spill files."""
        if self._writer is not None:
            self._writer.drain()

    def abort_writes(self):
        """Kill-path drain: queued-but-unstarted writes are discarded
        (refs keep their RAM blocks); in-flight writes finish and publish.
        Budget charges released, no temp files left.  The pool flushes
        the live flight recorder first, so the crash dump's last samples
        still show the queue state at death (this runs only on failing
        runs — normal teardown goes through cleanup/close)."""
        if self._writer is not None:
            self._writer.abort(flush_recorder=True)

    # -- overlap (pipelined map driver) accounting --------------------------
    @property
    def overlap_bytes(self):
        return self._overlap_bytes

    def reserve_overlap(self, n):
        """Charge ``n`` in-flight overlap bytes against the budget; resident
        refs spill to make room, so codec readahead trades RAM residency
        instead of adding to it."""
        with self._lock:
            self._overlap_bytes += n
            self.overlap_peak_bytes = max(self.overlap_peak_bytes,
                                          self._overlap_bytes)
            victims, evicted_dev = self._select_victims_locked()
        self._spill_victims(victims, evicted_dev)

    def release_overlap(self, n):
        with self._lock:
            self._overlap_bytes = max(0, self._overlap_bytes - n)

    def hbm_budget(self):
        """HBM residency budget for this run.  When the plan produced
        device-handoff edges (``handoff_active``), the handoff budget
        applies — on forced CPU-JAX legs the plain HBM budget resolves to
        0 and would instantly offload the very refs the handoff tier just
        kept resident.  Runs without handoff edges keep the classic
        budget byte-for-byte."""
        if self.handoff_active:
            return settings.effective_handoff_budget()
        return settings.effective_hbm_budget()

    @contextlib.contextmanager
    def attempt(self):
        """Track every ref this thread registers inside the block; on
        exception the refs are dropped, so a retried job's failed attempt
        cannot orphan blocks against the memory budget.

        Attempts NEST: a successfully committed inner attempt merges its
        refs into the enclosing frame, so an outer rollback still covers
        them — the contract speculative job execution relies on (the
        retry wrapper's per-attempt frame sits inside the speculation
        layer's first-result-wins frame; a losing duplicate must roll
        back everything its retries committed)."""
        stack = getattr(self._attempts, "stack", None)
        if stack is None:
            stack = self._attempts.stack = []
        refs = []
        stack.append(refs)
        try:
            yield refs
        except BaseException:
            stack.pop()
            for ref in refs:
                self.drop_ref(ref)
            raise
        else:
            stack.pop()
            if stack:
                stack[-1].extend(refs)

    def set_stage(self, stage_name):
        self._stage = "stage_{}".format(stage_name)

    def register(self, block, pin=False, device=False, handoff=False):
        prep = None
        # hbm_min_records is a perf heuristic (tiny lanes aren't worth
        # the tier bookkeeping); a plan-decided handoff edge overrides
        # it — the edge's whole point is that the consuming fold reads
        # these lanes in place.
        floor = 1 if handoff else settings.hbm_min_records
        if (device and not pin and settings.use_device
                and self.hbm_budget() > 0
                and len(block) >= floor):
            prep = BlockRef.lane_prep(block.values)
        ref = BlockRef(block, store=self, pin=pin, device_prep=prep)
        # handoff only overrides the tier FLOOR here: these blocks came
        # through a host round trip (degrade flushes, compaction
        # merges), so they never count toward handoff_bytes — that
        # counter means "registered WITHOUT a host round-trip" and only
        # register_device() feeds it.
        return self._enter_ref(ref, handoff=False)

    def register_device(self, ref):
        """Register an already-assembled HBM-resident ref
        (:meth:`BlockRef.from_device_lanes` — the cross-stage handoff
        tier).  Same budget/attempt/metrics discipline as
        :meth:`register`; the value lane never crossed the boundary, so
        only the ref's pending hash-lane upload charges h2d."""
        ref.store = self
        return self._enter_ref(ref, handoff=True)

    def _enter_ref(self, ref, handoff=False):
        if _metrics.enabled():
            # Stage-output throughput: every materialized block crosses
            # here, so records/s and MB/s difference off these counters
            # (the progress line and the sampled series both do).
            _metrics.counter_add("store.records", ref.nrecords)
            _metrics.counter_add("store.bytes", ref.nbytes + ref.dev_bytes)
            _metrics.counter_add("store.blocks", 1)
        stack = getattr(self._attempts, "stack", None)
        if stack:
            stack[-1].append(ref)
        dev_victims = []
        with self._lock:
            if ref.is_device:
                self._dev_resident.append(ref)
                self._dev_bytes += ref.dev_bytes
                # h2d is charged per actual transfer (the ref's pending
                # counter, armed where the device_put happened), so a
                # ref re-registered after a fallback — or assembled from
                # already-resident program outputs — never double-counts
                # the boundary.
                self.h2d_bytes += ref._h2d_pending
                ref._h2d_pending = 0
                if handoff:
                    self.handoff_bytes += ref.dev_bytes
                self.hbm_peak_bytes = max(self.hbm_peak_bytes,
                                          self._dev_bytes)
                dev_victims = self._select_dev_victims_locked()
            # Host accounting charges what stays host-side (full block, or
            # keys+hashes for a device-tiered ref).
            self._resident.append(ref)
            self._resident_bytes += ref.nbytes
            victims, evicted_dev = self._select_victims_locked()
        # Offload / spill I/O happens OUTSIDE the lock: victims are already
        # removed from their resident list (each ref is selected exactly
        # once), so concurrent workers keep registering while the device
        # fetch / gzip+write proceeds here.
        for v in dev_victims:
            self._offload_ref(v)
        self._spill_victims(victims, evicted_dev)
        return ref

    def register_stream(self, blocks):
        """Materialize an iterator of key-sorted window blocks straight into
        a disk-backed ref: the spill-lean merge generation.  Data streams
        file -> merge -> file in SPILL_WINDOW units and is never RAM- or
        budget-resident as a whole; the returned ref reads back through the
        normal spilled-block path (iter_windows is sequential IO).

        The compression heuristic matches save_block: decided from the
        first window's dtypes (a merged run is dtype-uniform by
        construction — its sources were windows of one logical column
        pair)."""
        directory = os.path.join(self.root, self._stage)
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, uuid.uuid4().hex + ".blk")
        raw = fw = None
        total_records = 0
        total_bytes = 0
        write_secs = 0.0
        key_dtype = value_dtype = np.dtype(object)
        t0 = _trace.now()
        try:
            for blk in blocks:
                if not len(blk):
                    continue
                if fw is None:
                    key_dtype = blk.keys.dtype
                    value_dtype = blk.values.dtype
                    raw = open(path, "wb")
                    fw = _frames.FrameWriter(
                        raw, _spill_codec(key_dtype, value_dtype))
                # Frame granularity = the spill window, regardless of the
                # (possibly multi-window) merged-round block size, so the
                # read side's one-window-per-run memory bound holds.
                w0 = time.perf_counter()
                fw.add_block(blk, SPILL_WINDOW)
                write_secs += time.perf_counter() - w0
                total_records += len(blk)
                total_bytes += blk.nbytes()
        except BaseException:
            # A failed generation (disk full, merge-source read error)
            # must not leak the fd or strand a partial .blk no ref owns.
            if raw is not None:
                try:
                    raw.close()
                except OSError:
                    pass
                try:
                    os.unlink(path)
                except OSError:
                    pass
            raise
        else:
            if fw is not None:
                # The footer/trailer write can fail too (disk full at the
                # very end): same no-leaked-fd / no-stranded-partial
                # contract as the loop body above.
                w0 = time.perf_counter()
                try:
                    fw.close()
                    raw.close()
                except BaseException:
                    try:
                        raw.close()
                    except OSError:
                        pass
                    try:
                        os.unlink(path)
                    except OSError:
                        pass
                    raise
                write_secs += time.perf_counter() - w0
        ref = BlockRef.from_disk(path if fw is not None else None,
                                 total_records, total_bytes,
                                 key_dtype, value_dtype)
        ref.store = self
        if fw is None:
            # empty stream: nothing on disk, an empty resident block
            from .blocks import Block

            ref.path = None
            ref._block = Block.empty()
        stack = getattr(self._attempts, "stack", None)
        if stack:
            stack[-1].append(ref)
        if fw is not None:
            self.count_spill_write(_file_size(path), write_secs)
        if _metrics.enabled():
            _metrics.counter_add("store.records", total_records)
            _metrics.counter_add("store.bytes", total_bytes)
            _metrics.counter_add("store.blocks", 1)
        with self._lock:
            self.merge_gens += 1
            self.merge_gen_bytes += total_bytes
        _trace.complete("merge", "merge-run", t0, bytes=total_bytes,
                        records=total_records)
        return ref

    def release_device(self):
        """Drop every HBM-resident ref and return the device budget to
        zero — the failing/killed-run path.  HBM is shared across runs
        on a real accelerator, and a dead run's lanes are never
        consumed, so refs die outright (no offload copy: there is
        nothing to preserve)."""
        with self._lock:
            victims = list(self._dev_resident)
            self._dev_resident = []
            self._dev_bytes = 0
            for ref in victims:
                if ref in self._resident:
                    self._resident.remove(ref)
                    self._resident_bytes -= ref.nbytes
        for ref in victims:
            ref.delete()

    def _select_dev_victims_locked(self):
        """Oldest device refs past the HBM budget offload to host (the HBM
        tier's spill step; host pressure then cascades to disk).  Selected
        refs leave BOTH resident lists here, so no later selection — host
        victims in the same register call included — can pick them twice;
        _offload_ref re-enters them as plain host refs."""
        budget = self.hbm_budget()
        if self._dev_bytes <= budget:
            return []
        victims = []
        keep = []
        for ref in self._dev_resident:
            if self._dev_bytes > budget and ref.is_device:
                victims.append(ref)
                self._dev_bytes -= ref.dev_bytes
                if ref in self._resident:
                    self._resident.remove(ref)
                    self._resident_bytes -= ref.nbytes
            else:
                keep.append(ref)
        self._dev_resident = keep
        return victims

    def _spill_victims(self, victims, evicted_dev):
        """Spill I/O for already-selected victims (outside the lock).
        ``evicted_dev`` refs were HBM-resident with unevictable host
        metadata: they offload (synchronously — the device fetch is the
        point) and then take the same write path — both their device
        bytes and host bytes were already deducted.

        With the writer pool on, victims that need a disk write enqueue
        and the evicting thread returns immediately; their RAM stays
        readable (and charged, via the pool's in-flight bytes) until the
        background write publishes.  Victims that already own a durable
        file — checkpoint-persisted refs — just drop their RAM copy, and
        pinned/raced refs fall through to the synchronous path."""
        if not victims and not evicted_dev:
            return
        directory = os.path.join(self.root, self._stage)
        for v in evicted_dev:
            with _trace.span("hbm", "offload", bytes=v.dev_bytes):
                v.offload()
        if evicted_dev:
            with self._lock:
                self.hbm_offloads += len(evicted_dev)
        pool = self.writer_pool()
        freed_sync = n_sync = 0
        queued = []
        for v in evicted_dev + victims:
            if (pool is not None and not v.pin and v.path is None
                    and v._block is not None):
                queued.append(v)
            else:
                with _trace.span("spill", "spill", bytes=v.nbytes,
                                 records=v.nrecords):
                    freed_sync += v.spill(directory)
                n_sync += 1
        if n_sync:
            with self._lock:
                self.spill_count += n_sync
                self.spilled_bytes += freed_sync
        if queued:
            os.makedirs(directory, exist_ok=True)
            for v in queued:
                blk = v._block
                if blk is None:  # raced with a concurrent drop
                    continue
                path = os.path.join(directory, uuid.uuid4().hex + ".blk")
                pool.submit(v, blk, path,
                            _spill_codec(v.key_dtype, v.value_dtype),
                            clear_block=True)

    def _offload_ref(self, ref):
        """Device -> host for one ref already removed from both resident
        lists (outside the lock), then re-enter it as a plain host ref,
        which may cascade to a disk spill."""
        with _trace.span("hbm", "offload", bytes=ref.dev_bytes):
            freed, _delta = ref.offload()
        if not freed:
            return  # raced with a concurrent drop
        with self._lock:
            self.hbm_offloads += 1
            self._resident.append(ref)
            self._resident_bytes += ref.nbytes
            victims, evicted_dev = self._select_victims_locked()
        self._spill_victims(victims, evicted_dev)

    def _select_victims_locked(self):
        """Pick oldest unpinned refs until projected residency meets the
        budget; deduct their bytes immediately so other threads see the
        budget as already relieved.  Returns (spill_victims, evicted_dev):
        HBM-resident refs' host metadata (keys+hashes) is not spillable in
        place, so under host pressure those refs are evicted whole —
        offload + disk — and leave both accountings here.

        In-flight overlap bytes AND queued-but-unwritten spill bytes (the
        writer pool's backlog — that RAM is still held) shrink the
        effective residency target: both are charged against the same
        budget, so readahead and write queueing displace resident blocks
        instead of stacking on top of them."""
        inflight = 0 if self._writer is None else self._writer.inflight_bytes
        target = max(0, self.budget - self._overlap_bytes - inflight)
        if self._resident_bytes <= target:
            return [], []
        victims = []
        evicted_dev = []
        keep = []
        for ref in self._resident:
            if self._resident_bytes <= target or ref.pin:
                keep.append(ref)
            elif ref.resident:
                victims.append(ref)
                self._resident_bytes -= ref.nbytes
            elif ref.is_device:
                evicted_dev.append(ref)
                self._resident_bytes -= ref.nbytes
                if ref in self._dev_resident:
                    self._dev_resident.remove(ref)
                    self._dev_bytes -= ref.dev_bytes
            else:
                keep.append(ref)
        self._resident = keep
        if self._resident_bytes > self.budget:
            # Everything unpinned has spilled; what remains is cached()
            # data, already gzip-compressed in RAM.  The reference would
            # keep allocating until the OS kills it; fail loudly instead.
            raise MemoryError(
                "cached() blocks exceed the memory budget even compressed "
                "({} > {} bytes); raise the budget or drop a cached()/"
                "memory=True stage".format(
                    self._resident_bytes, self.budget))
        return victims, evicted_dev

    def drop_ref(self, ref):
        with self._lock:
            if ref in self._resident:
                self._resident.remove(ref)
                self._resident_bytes -= ref.nbytes
            if ref in self._dev_resident:
                self._dev_resident.remove(ref)
                self._dev_bytes -= ref.dev_bytes
        ref.delete()

    def release_ref(self, ref):
        """Free a ref's RAM residency but KEEP its on-disk file (durable
        checkpoint): the budget no longer charges it, reads stream from
        disk.  Refs that never got a path keep their RAM block (nothing
        else holds the data)."""
        with self._lock:
            if ref in self._resident:
                self._resident.remove(ref)
                self._resident_bytes -= ref.nbytes
        if ref.path is not None:
            ref._block = None

    def cleanup(self):
        """Remove the run's scratch tree (outputs the caller wants to keep
        must have been read or re-registered elsewhere first).  Queued
        background writes are aborted first — their target files are about
        to be deleted anyway, and their refs keep their RAM blocks."""
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        if os.path.isdir(self.root):
            shutil.rmtree(self.root, ignore_errors=True)


class PartitionSet(object):
    """The stage-exchange format: {partition_id: [BlockRef]} — the engine
    analog of the reference's {partition: [Dataset]} dicts
    (base.py:416-433, runner.py:163-172).

    Provenance flags (how these refs were produced — what downstream fast
    paths may assume):

    - ``hash_routed``: every record lives in partition ``h1 % P`` (map
      outputs routed through ``split_by_partition``).  Reduce outputs are
      registered under the reduce *job's* pid without re-hashing whatever
      keys the reducer emitted, so they are NOT hash-routed.
    - ``hash_sorted``: every ref is a (h1, h2)-sorted run — the invariant
      the over-budget streaming merge (StreamingGroupedView) relies on.
    - ``key_sorted_runs``: every ref is a KEY-sorted run (ascending,
      numeric keys) registered without partition fan-out — the spill-lean
      merge plan for outputs no reduce ever consumes; the final read
      streams a k-way merge over the runs instead of re-sorting.

    The identity-checkpoint alias (runner) is gated on these: an alias may
    stand in for the elided copy stage only when the input already carries
    the invariants that stage would have established."""

    __slots__ = ("parts", "n_partitions", "hash_routed", "hash_sorted",
                 "key_sorted_runs", "shuffle_target", "pipeline_fold_delta")

    def __init__(self, n_partitions, hash_routed=False, hash_sorted=False,
                 key_sorted_runs=False):
        self.parts = {}
        self.n_partitions = n_partitions
        self.hash_routed = hash_routed
        self.hash_sorted = hash_sorted
        self.key_sorted_runs = key_sorted_runs
        # Host-vs-mesh routing the plan chose for the producing stage's
        # redistribution (None = undecided): lazily-read sorted outputs
        # consult it when they range-exchange at read time.
        self.shuffle_target = None
        # Streamed-edge provenance (runner pipelined executor): per-pid
        # byte shrinkage from early partial folds.  Size-gated consumers
        # add it back so their branch decisions match a staged run.
        self.pipeline_fold_delta = {}

    def add(self, pid, ref):
        self.parts.setdefault(pid, []).append(ref)

    def refs(self, pid):
        return self.parts.get(pid, [])

    def all_refs(self):
        for pid in sorted(self.parts):
            for ref in self.parts[pid]:
                yield ref

    def total_records(self):
        return sum(len(r) for r in self.all_refs())

    def delete(self, store=None):
        for refs in self.parts.values():
            for ref in refs:
                if store is not None:
                    store.drop_ref(ref)
                else:
                    ref.delete()
        self.parts = {}

    def release(self, store):
        """Free RAM residency, keep disk files (checkpoint retention)."""
        for refs in self.parts.values():
            for ref in refs:
                store.release_ref(ref)
