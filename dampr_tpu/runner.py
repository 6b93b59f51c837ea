"""Stage executor: walks the logical graph and runs each stage.

Replaces the reference's fork-join machinery (dampr/runner.py:137-374 +
stagerunner.py) with a thread-pool executor over columnar block jobs:

- **Map stages** stream records through the fused mapper chain into blocks;
  associative stages fold map-side (the ``PartialReduceCombiner`` +
  ``ReducedWriter`` path, reference stagerunner.py:79-129) via vectorized
  segment kernels; every map output is hash-partitioned into the run's
  ``n_partitions`` (the reference's ``DefaultShuffler``, base.py:416-433).
- **Reduce stages** build a key-sorted :class:`~dampr_tpu.base.GroupedView`
  per (partition, input) — vectorized hash-sort replacing sorted-spill +
  heapq merge — and stream the reducer's output back into blocks.
- **Sink stages** write durable part-files exempt from cleanup.

Threads (not forked processes) carry the jobs: the heavy keyed work happens in
numpy/XLA kernels that release the GIL, and a single process keeps one device
context (forking around a live TPU runtime is not safe).  Stage barriers are
preserved: stage N completes before N+1 starts, exactly like the reference's
per-stage join (runner.py:174-232).

Failure semantics: a job exception fails the run immediately with the original
traceback (the reference deadlocks on a dead worker — stagerunner.py:35-38 —
which SURVEY.md flags as a defect not to replicate).
"""

import copy
import itertools
import logging
import os
import queue as _queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from . import base, faults as _faults, settings, storage
from .parallel import mitigate as _mitigate
from .parallel import placement as _placement
from .blocks import Block, BlockBuilder
from .dataset import BlockDataset, Chunker, Dataset, SinkDataset
from .graph import GInput, GMap, GReduce, GSink
from .obs import log as _obslog
from .obs import metrics as _metrics
from .obs import profile as _profile
from .obs import trace as _trace
from .ops import segment

log = logging.getLogger("dampr_tpu.runner")

# Cap on accumulated map-side partial folds before re-compaction; bounds the
# map-side working set the way the reference's reduce_buffer flush does
# (dampr.py:661-673) but in block units.
_PARTIAL_FANIN = 8

#: Device-partial compaction trigger for the mesh fold (lanes, not
#: partial count): vocabulary-sized handoff partials accumulate until one
#: deterministic refold, while capacity-sized window partials still
#: compact before they stack past device memory.
_REFOLD_LANE_CAP = 1 << 20


def _clone_op(op):
    """Per-job operator instance.  The built-in stateless wrapper ops
    (Map/RecordOps/StreamMapper/Reduce/joins/…) define ``__deepcopy__`` as
    share-by-reference (base._shared_instance_deepcopy), so user callables —
    which may hold uncopyable resources — are never descended into.
    Everything else still deep-copies: BlockMapper/BlockReducer lifecycle
    ops (per-chunk state the reference isolated by process fork) and
    unknown user Mapper/Reducer subclasses installed via custom_mapper /
    custom_reducer, which may be stateful — such a subclass holding an
    uncopyable resource should define ``__deepcopy__`` itself.  deepcopy of
    a fused Composed chain reaches the stateful leaves while sharing the
    rest."""
    return copy.deepcopy(op)


class _SinkOutput(object):
    """Durable sink result: a list of part-file datasets."""

    def __init__(self, paths):
        self.paths = paths

    def datasets(self):
        return [SinkDataset(p) for p in self.paths]


def _exchange_mesh_gate(budget, target=None):
    """Shared engage/window policy for every mesh byte-exchange user.
    Returns (mesh, D, window_bytes) or None when the path is off or only
    one device is visible.  The window bound keeps the host-side pack
    working set a fraction of the run budget (the DEVICE-side bound is
    separate: the exchange itself runs a replan schedule under
    ``settings.exchange_hbm_budget``).

    ``target`` is the plan layer's per-stage shuffle choice
    (``"mesh"``/``"host"``, from the cost model over the run-history
    corpus): ``"host"`` declines the mesh path in auto mode, ``"mesh"``
    engages it even where the auto device-count heuristic would not.
    Explicit ``settings.mesh_exchange`` modes always win — the plan's
    choice was made under the same mode, so only auto runs ever diverge."""
    mode = str(settings.mesh_exchange).lower()
    if mode in ("off", "0", "false") or not settings.use_device:
        return None
    if mode not in ("on", "1", "true"):
        if target == "host":
            return None
        if target != "mesh" and settings.device_count_for_auto() < 2:
            return None
    from .parallel.mesh import data_mesh, mesh_size

    mesh = data_mesh()
    D = mesh_size(mesh)
    window = max(1 << 18, budget // (8 * D * D))
    return mesh, D, window


def _overlap_stream(items, store, size_of=None):
    """The stage-overlapped streaming executor's pipe: run ``items`` — the
    codec, a generator whose ``next()`` does the decompress/tokenize/parse
    work — on a dedicated producer thread that stays up to
    ``settings.overlap_windows`` produced blocks ahead of the consumer (the
    fold/register loop on the job thread).  This extends the readahead
    pattern ``inputs.Readahead`` applies to raw chunk bytes up through the
    codec: while the current block folds, the next window is already being
    tokenized.

    Memory discipline: every in-flight block is charged byte-for-byte
    against the run's budget (``store.reserve_overlap``) from the moment
    the codec emits it until the consumer has finished folding it, so
    readahead displaces resident refs (they spill) instead of stacking on
    top of the stage ceiling.  The charge is released in a ``finally`` on
    both sides — consumer abandonment (a failed fold mid-window, a retried
    job) stops the producer and drains every outstanding reservation, so a
    killed window can never leak budget.

    Critical-path accounting: while this consumer blocks on the queue
    with its producer inside the native codec, the slot is marked
    stalled (devtime.slot_stall); the ``codec_wait`` bucket accumulates
    the WALL-CLOCK union of intervals where every live slot is stalled
    at once — the codec seconds no fold anywhere could cover, i.e. the
    codec time still on the engine's critical path after overlapping.
    (With overlap off the job thread runs the codec itself, so the whole
    ``codec`` bucket is non-overlapped by construction.)

    Returns ``items`` unchanged when overlap is disabled or there is no
    store to account against."""
    depth = settings.overlap_windows
    if depth <= 0 or store is None:
        return items
    if size_of is None:
        size_of = lambda b: b.nbytes()  # noqa: E731
    from .ops import devtime

    # Each produced window records one codec span on the producer thread's
    # lane (a no-op pass-through when tracing is off).  The span covers the
    # generator's next() — decompress + tokenize/parse — not the queue wait.
    items = _trace.timed_iter(items, "codec", "codec-window")

    q = _queue.Queue(maxsize=max(1, depth))
    stop = threading.Event()
    state = {"err": None, "done": False}
    _END = object()

    def produce():
        try:
            for item in items:
                # Fault site: chaos tests widen the producer/consumer
                # shutdown race here (sleep action) to prove reservation
                # accounting survives a consumer that dies mid-run.
                _faults.check("overlap_produce")
                if stop.is_set():
                    return
                if item is None:
                    # The serial consumer drops None windows (a
                    # map_blocks mapper may emit them for empty input);
                    # keep that contract rather than crash size_of.
                    continue
                nb = size_of(item) or 0
                _metrics.counter_add("overlap.windows", 1)
                if nb:
                    store.reserve_overlap(nb)
                placed = False
                while not stop.is_set():
                    try:
                        q.put((item, nb), timeout=0.05)
                        placed = True
                        break
                    except _queue.Full:
                        continue
                if not placed:
                    if nb:
                        store.release_overlap(nb)
                    return
        except BaseException as e:  # delivered to the consumer
            state["err"] = e
        finally:
            state["done"] = True
            while not stop.is_set():
                try:
                    q.put((_END, 0), timeout=0.05)
                    break
                except _queue.Full:
                    continue

    # The producer dispatches the job's programs: on the job's chip.
    on_chip = _placement.carried()

    def produce_on_chip():
        with on_chip():
            produce()

    thread = threading.Thread(target=produce_on_chip, daemon=True,
                              name="dampr-tpu-codec")

    def wait_item():
        # Stall accounting per poll slice: the slot counts as
        # blocked-on-codec only while THIS job's producer thread is
        # executing the native codec (devtime.active_in) — wait caused by
        # producer-side IO/Python is real pipeline wait but is not
        # codec-attributable (the ``codec`` bucket doesn't count it
        # either), and a sibling job's codec is not what this fold is
        # blocked on.
        while True:
            stalled = devtime.active_in(thread.ident, "codec")
            if stalled:
                devtime.slot_stall()
            try:
                return q.get(timeout=0.05)
            except _queue.Empty:
                pass
            finally:
                if stalled:
                    devtime.slot_unstall()
            if state["done"] and q.empty():
                return _END, 0

    def gen():
        thread.start()
        devtime.slot_enter()
        try:
            while True:
                try:
                    item, nb = q.get_nowait()
                except _queue.Empty:
                    # Consumer-side pipeline wait (this slot's fold was
                    # blocked on its producer) — the per-slot view of what
                    # devtime's codec_wait aggregates across all slots.
                    with _trace.span("stall", "pipe-wait"):
                        item, nb = wait_item()
                    _metrics.counter_add("overlap.consumer_stalls", 1)
                if item is _END:
                    if state["err"] is not None:
                        raise state["err"]
                    return
                try:
                    yield item
                finally:
                    if nb:
                        store.release_overlap(nb)
        finally:
            devtime.slot_exit()
            stop.set()

            def drain():
                while True:
                    try:
                        _item, nb = q.get_nowait()
                    except _queue.Empty:
                        return
                    if nb:
                        store.release_overlap(nb)

            drain()
            thread.join(timeout=5.0)
            if thread.is_alive():
                # A producer stuck inside the native codec (or a wedged
                # disk under it) past the join deadline: name it loudly
                # instead of silently abandoning the join result, and
                # keep draining briefly — the producer releases its own
                # reservation when it observes ``stop``, but an item it
                # slips into the queue after our drain would otherwise
                # leak its budget charge until process exit.
                _obslog.warn(
                    "overlap-producer-stuck",
                    "overlap producer thread %s did not stop within "
                    "5.0s at shutdown; draining in-flight windows in "
                    "the background (daemon thread abandoned)",
                    thread.name, logger=log, thread=thread.name)
                deadline = time.perf_counter() + 5.0
                while thread.is_alive() and time.perf_counter() < deadline:
                    drain()
                    thread.join(timeout=0.05)
                if thread.is_alive():
                    _obslog.warn(
                        "overlap-producer-stuck",
                        "overlap producer thread %s still alive after "
                        "drain grace; any window it produces past this "
                        "point leaks its budget reservation until the "
                        "store is cleaned up", thread.name, logger=log,
                        thread=thread.name, after_drain=True)
            # The producer may have slipped one reserved block into the
            # slot the first drain freed before it observed ``stop`` —
            # with the thread joined (or the grace above spent), a final
            # drain is conclusive.
            drain()

    return gen()


def _stage_kind(stage):
    """``map``, ``reduce`` or ``sink``: what a stage is, by its type."""
    return ("map" if isinstance(stage, GMap) else
            "reduce" if isinstance(stage, GReduce) else "sink")


class _FoldDeclined(Exception):
    """Internal _StreamFolder control flow: this mapping keeps its
    original refs (ineligible dtype or mid-drain disable)."""


class _StreamFolder(object):
    """Consumer half of a streamed map->keyed-fold edge (docs/pipeline.md):
    completed map-job partition mappings publish into a bounded queue and
    a folder thread pre-folds each one under the consuming reduce's
    associative op while the map stage is still running, so the reduce
    inherits compacted partials and the fold work hides under map compute.

    Byte-identity contract: folding only regroups partials across jobs —
    both reduce paths fold the exact hash groups and emit in ascending
    real-key order, so for commutative ops the regrouping cannot change a
    single output byte.  Commutativity is gated per block at run time
    (the coded-exchange exactness rule): ``sum`` folds integer/bool value
    lanes only (reordered float addition is not byte-identical), min/max
    fold any numeric lane.  The first ineligible block disables folding
    for the stage — remaining mappings pass through untouched, which is
    always correct.

    Backpressure: ``publish`` runs on the dispatching thread AFTER the
    job's result committed (attempt rollback and speculation already
    resolved) and blocks while queued bytes exceed ``bound``.  Queued
    bytes are charged through ``store.reserve_overlap`` so spill
    admission sees the pressure; the charge releases as each mapping
    folds.  A folder error never fails the run — the affected mappings
    keep their original refs."""

    def __init__(self, store, op, bound, device=False, label="early-fold"):
        self.store = store
        self.op = op
        self.bound = max(1, int(bound))
        self.device = device
        self.label = label
        self.folded = {}    # job idx -> replacement mapping
        self.fold_delta = {}  # pid -> staged-bytes minus folded-bytes
        self.stats = {"published": 0, "early_folded_blocks": 0,
                      "bytes_in": 0, "bytes_out": 0, "fold_seconds": 0.0,
                      "overlap_seconds": 0.0, "stall_seconds": 0.0,
                      "queue_peak_bytes": 0, "queue_depth_series": []}
        self._q = _queue.Queue()
        self._cv = threading.Condition()
        self._pending = 0
        self._disabled = False
        self._t0 = time.perf_counter()
        self._pool_done_at = None
        self._thread = threading.Thread(
            target=self._run, name="dampr-tpu-pipefold", daemon=True)
        self._thread.start()

    def _sample_depth(self):
        # Bounded queue-depth series for stats()["pipeline"]: decimate by
        # dropping every other sample once the cap is hit, so the series
        # stays an even sketch of the whole stage.
        series = self.stats["queue_depth_series"]
        series.append([round(time.perf_counter() - self._t0, 4),
                       self._pending])
        if len(series) > 512:
            del series[::2]

    def _must_wait(self, nb):
        return (self._pending > 0 and self._pending + nb > self.bound
                and not self._disabled)

    def publish(self, idx, mapping):
        """Dispatch-thread side: charge, bound, enqueue.  ``mapping`` is
        the committed job result ({pid: [refs]}); the folder may replace
        it wholesale in ``self.folded[idx]``."""
        _faults.check("stream_publish")
        if self._disabled:
            return
        nb = sum(ref.total_bytes for refs in mapping.values()
                 for ref in refs)
        if nb <= 0:
            return
        with self._cv:
            if self._must_wait(nb):
                # "stream-wait" (not the overlap executor's "pipe-wait"):
                # critpath classifies this name as pipeline-stall, whose
                # doctor fix (raise pipeline_queue_bytes) differs from
                # the overlap knobs.
                with _trace.span("stall", "stream-wait"):
                    wait_t0 = time.perf_counter()
                    while self._must_wait(nb):
                        self._cv.wait(0.05)
                    self.stats["stall_seconds"] += (time.perf_counter()
                                                    - wait_t0)
            if self._disabled:
                return
            self._pending += nb
            self.stats["queue_peak_bytes"] = max(
                self.stats["queue_peak_bytes"], self._pending)
            self._sample_depth()
        self.store.reserve_overlap(nb)
        self.stats["published"] += 1
        self.stats["bytes_in"] += nb
        self._q.put((idx, mapping, nb))

    def _value_dtype_ok(self, block):
        dt = getattr(getattr(block, "values", None), "dtype", None)
        if dt is None:
            return False
        if self.op.kind == "sum":
            return dt.kind in "iub"
        return dt.kind in "iubf"

    def _fold_one(self, idx, mapping):
        """Fold one job mapping, atomically: every pid folds into a fresh
        ref BEFORE any original drops, so a mid-mapping failure (or a
        dtype disable) leaves the original, correct refs in place."""
        out = {}
        blocks_in = sum(len(refs) for refs in mapping.values())
        try:
            with _trace.span("pipeline", self.label, lane="pipeline",
                             blocks=blocks_in):
                for pid, refs in mapping.items():
                    if not refs:
                        continue
                    if self._disabled:
                        raise _FoldDeclined()
                    blocks = [r.get() for r in refs]
                    merged = (blocks[0] if len(blocks) == 1
                              else Block.concat(blocks))
                    del blocks
                    if not self._value_dtype_ok(merged):
                        # Ineligible value lane: disable for the whole
                        # stage (one dtype per stage output).
                        with self._cv:
                            self._disabled = True
                            self._cv.notify_all()
                        raise _FoldDeclined()
                    folded = segment.fold_block(merged, self.op)
                    out[pid] = [self.store.register(folded,
                                                    device=self.device)]
        except _FoldDeclined:
            for refs in out.values():
                for r in refs:
                    self.store.drop_ref(r)
            return None
        except Exception:
            for refs in out.values():
                for r in refs:
                    self.store.drop_ref(r)
            raise
        for pid, refs in mapping.items():
            if pid in out:
                self.stats["early_folded_blocks"] += len(refs)
                for r in refs:
                    self.store.drop_ref(r)
            else:
                out[pid] = list(refs)
        return out

    def _run(self):
        while True:
            item = self._q.get()
            if item is None:
                return
            idx, mapping, nb = item
            t0 = time.perf_counter()
            try:
                if not self._disabled:
                    replacement = self._fold_one(idx, mapping)
                    if replacement is not None:
                        self.folded[idx] = replacement
                        self.stats["bytes_out"] += sum(
                            ref.total_bytes for refs in replacement.values()
                            for ref in refs)
                        self._note_delta(mapping, replacement)
            except Exception:  # noqa: BLE001 - folding is an optimization;
                #               originals stay registered, the run is fine
                _obslog.warn("early-fold-error",
                             "early-fold worker failed; disabling folding "
                             "for this stage (originals kept)",
                             logger=log, exc_info=True)
                with self._cv:
                    self._disabled = True
                    self._cv.notify_all()
            finally:
                dt = time.perf_counter() - t0
                self.stats["fold_seconds"] += dt
                if self._pool_done_at is None:
                    self.stats["overlap_seconds"] += dt
                self.store.release_overlap(nb)
                with self._cv:
                    self._pending = max(0, self._pending - nb)
                    self._sample_depth()
                    self._cv.notify_all()

    def _note_delta(self, mapping, replacement):
        """Per-pid staged-vs-folded byte delta: the reduce's size gates
        (tiny fast path) must decide on STAGED bytes, or the pipelined
        run could take a different branch than the staged one."""
        for pid, refs in mapping.items():
            orig = sum(r.total_bytes for r in refs)
            now = sum(r.total_bytes for r in replacement.get(pid, ()))
            self.fold_delta[pid] = self.fold_delta.get(pid, 0) + max(
                0, orig - now)

    def mark_pool_done(self):
        """Called when the map stage's job pool returns: fold seconds
        after this point no longer overlap map compute."""
        self._pool_done_at = time.perf_counter()

    def finish(self):
        """Drain, join, and return {idx: replacement mapping}."""
        self._q.put(None)
        self._thread.join(timeout=60.0)
        if self._thread.is_alive():
            # Wedged folder at shutdown: stop consuming its results (the
            # originals are still registered and correct) and let the
            # daemon thread release its reservations as it drains.
            _obslog.warn("early-fold-stuck",
                         "early-fold worker did not drain within 60s; "
                         "using unfolded mappings", logger=log)
            with self._cv:
                self._disabled = True
                self._cv.notify_all()
            return {}, dict(self.stats)
        return dict(self.folded), dict(self.stats)


class _ChainedOutput(object):
    """Placeholder env entry for a streamed chain producer's output
    (docs/pipeline.md): the stage's blocks flowed straight into the
    consumer's jobs and were dropped as each one was consumed — nothing
    ever materialized.  Duck-types the probes stage bookkeeping applies
    to arbitrary env entries (cleanup ignores non-PartitionSets)."""

    __slots__ = ("records",)

    def __init__(self, records):
        self.records = records

    def total_records(self):
        return self.records


class _SharedScanChunk(object):
    """One-read view of a tap chunk shared by scan-fused map stages: the
    first read_bytes() materializes, later readers (including streaming
    iter_byte_blocks consumers) serve from the cache.  If nothing ever
    materializes, iter_byte_blocks delegates to the chunk's own bounded
    scan — fusion never raises the memory ceiling above what the widest
    member would have used alone."""

    def __init__(self, chunk):
        self._chunk = chunk
        self._bytes = None

    def read_bytes(self):
        if self._bytes is None:
            self._bytes = self._chunk.read_bytes()
        return self._bytes

    def __getattr__(self, name):
        if name == "iter_byte_blocks":
            if self._bytes is not None:
                cached = self._bytes
                # accept (and ignore) block_size etc. like the real method
                return lambda *a, **k: iter((cached,))
            return getattr(self._chunk, name)  # AttributeError if absent
        return getattr(self._chunk, name)


class _RawRef(object):
    """Minimal in-memory stand-in for BlockRef when an OutputDataset has no
    store (direct construction in tests/tools)."""

    __slots__ = ("_block",)

    def __init__(self, block):
        self._block = block

    def get(self):
        return self._block

    def delete(self):
        self._block = None


class OutputDataset(Dataset):
    """Final-output view over a PartitionSet: reads records in ascending key
    order (the reference heap-merges sorted partition runs —
    runner.py:352-374).  Each partition is sorted independently and the
    partitions stream through a lazy k-way heap merge, so ``read(k)`` never
    materializes one giant concatenated copy and peak memory is the sum of
    partition working sets, not 2x the output."""

    def __init__(self, pset, store=None):
        self.pset = pset
        self.store = store
        self._range_cache = None  # mesh range-sort bucket runs, reused
        #                           across reads, released in delete()

    def _partition_stream(self, pid):
        from .dataset import OrderKey

        try:
            blk = self._sorted_partition_block(pid)
        except TypeError:
            # Uncomparable mixed keys: stable Python sort under the
            # total-order wrapper (rare path, matches the merge order).
            blk = Block.concat([r.get() for r in self.pset.refs(pid)])
            keys = blk.keys
            order = np.asarray(
                sorted(range(len(blk)), key=lambda i: OrderKey(keys[i])),
                dtype=np.int64)
            blk = blk.take(order)
        if blk is None:
            return iter(())
        return blk.iter_pairs()

    def _sorted_concat(self):
        """Vectorized fast path: one concat + stable argsort of the whole
        output.  Returns None when it shouldn't run — the working copies
        (refs + concat + take) peak near 3x the output size, so it is gated
        at a third of the memory budget; uncomparable mixed keys also bail
        to the streamed merge."""
        total = sum(r.total_bytes for r in self.pset.all_refs())
        budget = (self.store.budget if self.store is not None
                  else settings.max_memory_per_stage)
        if total * 3 > budget:
            return None
        blk = Block.concat([r.get() for r in self.pset.all_refs()])
        if not len(blk):
            return blk
        try:
            order = np.argsort(blk.keys, kind="stable")
        except TypeError:
            return None
        return blk.take(order)

    def _merged_run_blocks(self):
        """Stream a key-sorted run set (the spill-lean sort layout) through
        the vectorized k-way merge: one in-flight window per run, every run
        file read sequentially front to back, no read-side re-sort.  The
        write-side merge planner already capped the fan-in, so the working
        set is bounded."""
        from .blocks import merge_sorted_streams

        refs = [r for r in self.pset.all_refs() if len(r)]
        if not refs:
            return iter(())
        return merge_sorted_streams([r.iter_windows() for r in refs])

    def _key_sorted_blocks(self):
        """Sorted-block iterator for a key-sorted run set.  Multi-device
        meshes keep the collective range exchange — global order
        parallelizes across devices; single-device (the gate declines)
        streams the k-way merge."""
        blocks = self._mesh_range_sorted(sorted(self.pset.parts))
        if blocks is None:
            blocks = self._merged_run_blocks()
        return blocks

    def read(self):
        import itertools

        pids = sorted(self.pset.parts)
        if not pids:
            return iter(())
        if getattr(self.pset, "key_sorted_runs", False):
            # sorted_blocks() handles the whole strategy ladder (small
            # concat, mesh range exchange, streamed k-way merge).
            return itertools.chain.from_iterable(
                b.iter_pairs() for b in self.sorted_blocks())
        if len(pids) == 1:
            return self._partition_stream(pids[0])
        blk = self._sorted_concat()
        if blk is not None:
            return blk.iter_pairs()
        blocks = self._mesh_range_sorted(pids)
        if blocks is None:
            blocks = self._vector_merge_blocks(pids)
        if blocks is not None:
            return itertools.chain.from_iterable(
                b.iter_pairs() for b in blocks)
        return self._merge_partitions(pids)

    def _merge_partitions(self, pids):
        from .dataset import StreamDataset, merged_read

        streams = [StreamDataset(self._partition_stream(pid)) for pid in pids]
        return merged_read(streams)

    def _sorted_partition_block(self, pid):
        blk = Block.concat([r.get() for r in self.pset.refs(pid)])
        if not len(blk):
            return None
        order = np.argsort(blk.keys, kind="stable")  # TypeError -> caller
        return blk.take(order)

    def _mesh_range_sorted(self, pids, chunk=1 << 16):
        """sort_by's redistribution on the mesh: numeric-keyed partitions
        re-partition by key *range* across the devices — sampled quantile
        bounds route every record through the collective byte exchange to
        device ``bucket`` (bucket b ≡ pid b, so ``pid % D`` lands it
        there) — and global order becomes bucket order, each bucket merged
        independently.  Returns a sorted-block generator, or None when the
        mesh path is off, single-device, or keys are non-numeric."""
        if self._range_cache is None:
            budget = (self.store.budget if self.store is not None
                      else settings.max_memory_per_stage)
            gate = _exchange_mesh_gate(
                budget, getattr(self.pset, "shuffle_target", None))
            if gate is None:
                return None
            mesh, D, window = gate
            refs = [r for pid in pids for r in self.pset.refs(pid)]
            if not refs:
                return iter(())
            if any(getattr(r, "key_dtype", np.dtype(object)) == object
                   for r in refs):
                return None
            from .parallel import exchange as px

            # Range bounds from a strided sample.  Hash-partitioned runs
            # are key-random, so ONE window per ref samples uniformly.
            # Key-sorted runs are ordered WITHIN each run, so early
            # windows hold only that run's smallest keys — but each run
            # is one whole input chunk, so for unordered input every run
            # spans the full key distribution and a FEW runs read end to
            # end sample it faithfully; for pre-sorted input the runs
            # cover disjoint ranges, which striding the run choice across
            # the ref list covers.  Cost: <= 8 runs re-read (keys only),
            # against a pass that re-reads everything anyway.
            sorted_runs = bool(getattr(self.pset, "key_sorted_runs", False))
            if sorted_runs:
                # linspace, not a stride: the chosen runs must span BOTH
                # ends of the ref list, or pre-sorted input (runs with
                # disjoint ascending ranges) leaves the top of the key
                # space unsampled and overloads the last bucket.
                idx = np.unique(np.linspace(
                    0, len(refs) - 1, min(8, len(refs))).astype(int))
                sample_refs = [refs[i] for i in idx]
                per = max(16, 65536 // max(1, sum(
                    max(1, len(r) >> 14) for r in sample_refs)))
            else:
                sample_refs = refs
                per = max(16, 65536 // len(refs))
            samples = []
            for r in sample_refs:
                for wi, w in enumerate(r.iter_windows()):
                    if len(w):
                        stride = max(1, len(w) // per)
                        samples.append(np.asarray(w.keys[::stride]))
                    if not sorted_runs and wi == 0:
                        break
            if not samples:
                return iter(())
            allk = np.concatenate(samples)
            bounds = np.quantile(allk, np.linspace(0, 1, D + 1)[1:-1])

            bucket_refs = [[] for _ in range(D)]
            state = {"batch": [], "bytes": 0, "seq": 0}

            def flush():
                if not state["batch"]:
                    return
                received, _moved = px.mesh_shuffle_blocks(
                    mesh, state["batch"])
                for b, blk in received:
                    # store each bucket piece key-sorted: a mergeable run
                    order = np.argsort(blk.keys, kind="stable")
                    bucket_refs[b].append(
                        self.store.register(blk.take(order))
                        if self.store is not None
                        else _RawRef(blk.take(order)))
                state["batch"], state["bytes"] = [], 0

            for r in refs:
                for w in r.iter_windows():
                    if not len(w):
                        continue
                    keys = np.asarray(w.keys)
                    bidx = np.searchsorted(bounds, keys)
                    order = np.argsort(bidx, kind="stable")
                    sb = bidx[order]
                    edges = np.flatnonzero(np.diff(sb)) + 1
                    at = 0
                    for end in list(edges) + [len(sb)]:
                        if end > at:
                            b = int(sb[at])
                            state["batch"].append(
                                (state["seq"], state["seq"] % D, b,
                                 w.take(order[at:end])))
                            state["seq"] += 1
                            state["bytes"] += w.nbytes() * (end - at) // max(
                                1, len(w))
                        at = end
                    if state["bytes"] >= window:
                        flush()
            flush()
            # The bucket runs ARE the sorted materialization: cache them so
            # repeated reads reuse one exchange, and release them (only) in
            # delete() — abandoned read iterators cannot leak refs.  This
            # build may run AFTER the stage walk (lazy post-run reads), so
            # settle any spills its registrations queued: no other barrier
            # will run for them.
            if self.store is not None:
                self.store.drain_writes()
            self._range_cache = bucket_refs

        def gen():
            for brefs in self._range_cache:
                parts = [ref.get() for ref in brefs]
                for blk in self._merge_sorted_parts(parts, chunk):
                    yield blk

        return gen()

    def _vector_merge_blocks(self, pids, chunk=1 << 16):
        """K-way merge of key-sorted numeric-keyed partitions, emitted as
        blocks in bounded vectorized chunks: each round advances to the
        smallest partition-chunk boundary key, gathers every record at or
        below it via searchsorted, and stable-sorts only that slice —
        replacing per-record Python heap merging.  Returns None (fall back to
        the record merge) when any partition's keys are non-numeric."""
        all_refs = [r for pid in pids for r in self.pset.refs(pid)]
        if any(getattr(r, "key_dtype", np.dtype(object)) == object
               for r in all_refs):
            return None
        # Per-partition sorts run on the pool: numpy's sort kernels release
        # the GIL, so multi-core hosts get near-linear speedup on the read
        # phase's dominant cost (this bench box has one core; the path is
        # exercised by the multi-core CI rig either way).
        workers = max(1, min(settings.max_processes, len(pids)))
        if workers > 1:
            with ThreadPoolExecutor(max_workers=workers) as pool:
                sorted_parts = list(pool.map(self._sorted_partition_block,
                                             pids))
        else:
            sorted_parts = [self._sorted_partition_block(p) for p in pids]
        parts = [blk for blk in sorted_parts if blk is not None]
        if not parts:
            return iter(())

        return self._merge_sorted_parts(parts, chunk)

    @staticmethod
    def _merge_sorted_parts(parts, chunk=1 << 16):
        """Vectorized k-way merge over key-sorted blocks (see
        _vector_merge_blocks for the chunking/tie rules)."""
        parts = [p for p in parts if len(p)]

        def slice_of(blk, a, b):
            return Block(
                blk.keys[a:b], blk.values[a:b],
                None if blk.h1 is None else blk.h1[a:b],
                None if blk.h2 is None else blk.h2[a:b])

        def gen():
            pos = [0] * len(parts)
            n_parts = len(parts)
            while True:
                bound = None
                active = False
                for i in range(n_parts):
                    blk = parts[i]
                    if pos[i] >= len(blk):
                        continue
                    active = True
                    edge = min(pos[i] + chunk, len(blk)) - 1
                    k = blk.keys[edge]
                    if bound is None or k < bound:
                        bound = k
                if not active:
                    return
                # Records strictly below the bound: at most `chunk` per
                # partition by construction, so this gather is bounded —
                # stable sort keeps partition-order ties like the heap merge.
                pieces = []
                for i in range(n_parts):
                    blk = parts[i]
                    if pos[i] >= len(blk):
                        continue
                    end = int(np.searchsorted(blk.keys, bound, side="left"))
                    if end > pos[i]:
                        pieces.append(slice_of(blk, pos[i], end))
                        pos[i] = end
                if pieces:
                    merged = Block.concat(pieces)
                    yield merged.take(
                        np.argsort(merged.keys, kind="stable"))
                # Records equal to the bound need no sorting: emit them as
                # raw partition-order slices in bounded pieces, so a hot key
                # with millions of duplicates streams instead of
                # materializing (the heap merge's tie order is partition
                # order, preserved here).
                for i in range(n_parts):
                    blk = parts[i]
                    if pos[i] >= len(blk):
                        continue
                    end = int(np.searchsorted(blk.keys, bound, side="right"))
                    at = pos[i]
                    while at < end:
                        sub = min(at + chunk, end)
                        yield slice_of(blk, at, sub)
                        at = sub
                    pos[i] = end

        return gen()

    def sorted_blocks(self):
        """Bulk access: the key-sorted output as columnar blocks.  Under a
        third of the memory budget: one concatenated sorted block.  Numeric
        keys over budget: the vectorized k-way merge (block sizes bounded by
        ~chunk x partitions, not settings.batch_size).  Otherwise: the
        per-record merge re-blocked at batch_size."""
        blk = self._sorted_concat()
        if blk is not None:
            if len(blk):
                yield blk
            return
        if getattr(self.pset, "key_sorted_runs", False):
            for b in self._key_sorted_blocks():
                yield b
            return
        pids = sorted(self.pset.parts)
        blocks = self._mesh_range_sorted(pids)
        if blocks is None:
            blocks = self._vector_merge_blocks(pids)
        if blocks is not None:
            for b in blocks:
                yield b
            return
        builder = BlockBuilder(settings.batch_size)
        for k, v in self._merge_partitions(pids):
            out = builder.add(k, v)
            if out is not None:
                yield out
        out = builder.flush()
        if out is not None:
            yield out

    def delete(self):
        if self._range_cache is not None:
            for brefs in self._range_cache:
                for ref in brefs:
                    if self.store is not None:
                        self.store.drop_ref(ref)
                    else:
                        ref.delete()
            self._range_cache = None
        self.pset.delete(self.store)


class StageStats(object):
    """Per-stage observability (the reference has log lines only — SURVEY §5
    commits to structured metrics).

    Beyond the original jobs/records/seconds triple this now carries the
    stage's IO shape (records/bytes in and out, best-effort: taps whose
    size is unknowable report 0 in) and the store-pressure deltas measured
    while the stage ran — spill volume, merge generations, retries.  Spill
    attribution is *causal*: a spill is charged to the stage whose
    registration pressure evicted the block, which may have been produced
    by an earlier stage."""

    __slots__ = ("stage_id", "kind", "n_jobs", "records_in", "records_out",
                 "bytes_in", "bytes_out", "spill_count", "spill_bytes",
                 "merge_gens", "merge_gen_bytes", "retries", "quarantined",
                 "seconds", "target", "shuffle_target")

    def __init__(self, stage_id, kind):
        self.stage_id = stage_id
        self.kind = kind
        # Execution target the plan's lowering pass assigned ("host" |
        # "device"); device map stages ran the jitted tokenize+hash+fold
        # programs, device reduces the segment kernels.
        self.target = "host"
        # Host-vs-mesh shuffle routing the plan's cost layer chose for
        # this stage's redistribution (None = not a redistribution stage,
        # or routing off).
        self.shuffle_target = None
        self.n_jobs = 0
        self.records_in = 0
        self.records_out = 0
        self.bytes_in = 0
        self.bytes_out = 0
        self.spill_count = 0
        self.spill_bytes = 0
        self.merge_gens = 0
        self.merge_gen_bytes = 0
        self.retries = 0
        # Poison records this stage skipped into the quarantine sink
        # (settings.max_quarantined; see dampr_tpu.faults.Quarantine).
        self.quarantined = 0
        self.seconds = 0.0

    def as_dict(self):
        return {"stage": self.stage_id, "kind": self.kind,
                "target": self.target,
                "jobs": self.n_jobs,
                "records_in": self.records_in,
                "records_out": self.records_out,
                "bytes_in": self.bytes_in, "bytes_out": self.bytes_out,
                "spill_count": self.spill_count,
                "spill_bytes": self.spill_bytes,
                "merge_gens": self.merge_gens,
                "merge_gen_bytes": self.merge_gen_bytes,
                "retries": self.retries,
                "quarantined": self.quarantined,
                "shuffle_target": self.shuffle_target,
                "seconds": round(self.seconds, 4)}


class MTRunner(object):
    """The scheduler: sequential stage walk, parallel jobs within a stage
    (reference MTRunner, runner.py:235-374)."""

    def __init__(self, name, graph, n_maps=None, n_reducers=None,
                 n_partitions=None, memory_budget=None, resume=False):
        self.name = name
        self.graph = graph
        self.resume = bool(resume)
        self.n_maps = n_maps or settings.max_processes
        self.n_reducers = n_reducers or settings.max_processes
        self.n_partitions = n_partitions or settings.partitions
        # Logical plan optimizer state (dampr_tpu.plan): the report lands
        # here when the plan is applied (by the DSL entry points or by
        # run() below — first caller wins) and feeds the run summary's
        # "plan" section.  An explicitly-passed partition count is pinned:
        # the cost layer's adaptive sizing only retunes the default.
        self.plan_report = None
        self._explicit_partitions = n_partitions is not None
        self.store = storage.RunStore(name, budget=memory_budget)
        self.stats = []
        self.mesh_folds = 0  # reduces executed via the mesh collective path
        self.mesh_exchanges = 0  # general shuffles routed over all_to_all
        self.mesh_exchange_bytes = 0  # payload bytes that crossed the mesh
        self.mesh_exchange_steps = 0  # chunked collective steps executed
        self.mesh_exchange_peak_inflight = 0  # modeled per-step high-water
        # Host-vs-mesh shuffle routing per stage id, ridden here by the
        # plan layer (plan.lower.apply_shuffle) — a dispatch hint, not
        # stage options, so fingerprints never depend on history.
        self._shuffle_targets = {}
        # Producer stage ids whose output edge the plan marked
        # handoff="device" (cross-stage device-resident handoff): their
        # jobs keep program outputs HBM-resident for the consuming fold.
        # A dispatch decision like _shuffle_targets — never stage
        # options, so resume/cache fingerprints stay history-independent.
        self._handoff_sids = set()
        # Streamed stage edges (plan/pipeline.py): producer sid -> edge
        # hint for the barrier-free executor.  Same dispatch-hint
        # discipline as _shuffle_targets/_handoff_sids — never stage
        # options, so fingerprints stay history-independent.
        self._pipeline_edges = {}
        # Per-run pipelined-execution accounting (stats()["pipeline"]).
        self._pipeline_stats = {
            "executed": 0, "degraded": 0, "published": 0,
            "early_folded_blocks": 0, "bytes_in": 0, "bytes_out": 0,
            "fold_seconds": 0.0, "overlap_seconds": 0.0,
            "stall_seconds": 0.0, "queue_peak_bytes": 0,
            "queue_depth_series": []}
        # Consumer-stage results a streamed chain computed ahead of the
        # stage walk (the consumer's loop turn consumes, not recomputes).
        self._chain_results = {}
        self.streamed_assoc_folds = 0  # over-budget vectorized accumulators
        self.retries_total = 0  # transient-failure job re-executions
        self._retry_lock = threading.Lock()
        self._backoff_seconds = 0.0  # classified-retry sleep total
        # Poison-record quarantine sink (settings.max_quarantined > 0):
        # deterministically-failing records on the batched-UDF path are
        # bisected out into <scratch>/<run>/quarantine.jsonl and the
        # stage completes; 0 keeps fail-fast.
        self._quarantine = (_faults.Quarantine(name,
                                               settings.max_quarantined)
                            if settings.max_quarantined > 0 else None)
        # Fault-injection counter epoch (process-cumulative counters;
        # finalize reports this run's deltas in stats()["faults"]).
        self._fault_snapshot = None
        # Run-scoped observability (dampr_tpu.obs): the tracer is live only
        # while settings.trace is on; run_summary (the stats.json dict) is
        # built for every run — it is how StageStats reaches users.
        self.tracer = None
        self.run_summary = None
        # Live metrics plane: registry + sampler while
        # settings.effective_metrics_interval_ms() > 0, flight recorder
        # whenever tracing or metrics is on, progress reporter under
        # settings.progress.  _status is the progress line's live stage
        # view (plain dict: single-writer per key, display-only reads).
        self.metrics = None
        self.flightrec = None
        self._sampler = None
        self._progress = None
        self._status = {}
        # Per-operator profiler (settings.profile): attributes fused-stage
        # time to individual user ops; summary ships as stats()["profile"].
        self.profiler = None
        # Live metrics endpoint (obs.serve, settings.metrics_port): one
        # stdlib HTTP thread per rank while the run is in flight.
        # _endpoint_info survives the server's teardown so finalize can
        # record the bound port (fallback included) in stats().
        self._metrics_server = None
        self._endpoint_info = None
        # Structured log stream (obs.log, settings.log_level): coded
        # JSONL events to <run>/trace/events.jsonl, WARN+ mirrored into
        # the flight recorder's crashdump tail.  None = every emit site
        # is one None-check.
        self.logstream = None
        # Per-run device-route accounting: snapshot of the exchange
        # module's cumulative per-device/per-route counters at run start,
        # differenced at finalize so stats() carries THIS run's matrix.
        self._exchange_snapshot = None
        # Straggler mitigation (parallel.mitigate, settings.mitigate):
        # work stealing + speculative re-execution on the host path,
        # live-skew degrade-in-place + sticky down-weighting on the
        # collective path.  Off = one None-check per site.
        self._mitigation = None
        # CAMR-style coded-exchange accounting (settings.exchange_coding):
        # window pre-folds traded for shuffle bytes, summed per run.
        self.coded_exchange = {"windows": 0, "raw_bytes": 0,
                               "coded_bytes": 0}
        # Failed runs must not feed the run-history corpus (their
        # measurements would poison the adaptation medians).
        self._run_failed = False
        # Cross-run materialization cache (plan/reuse.py,
        # settings.reuse): the live decision/byte counters that land as
        # stats()["reuse"].  None while the cache is off keeps untouched
        # runs free of the section (back-compat pin).
        self._reuse_summary = None

    # -- job fan-out --------------------------------------------------------
    def _speculation_ok(self, *stages):
        """May these stages' jobs be speculatively re-executed?  The
        static analyzer (settings.analyze) declines speculation for any
        stage holding an evidence-nondeterministic UDF — first-result-
        wins over a nondeterministic function commits whichever answer
        finished first, silently.  Only consulted when the mitigation
        controller is armed (the default path stays one None-check);
        ``assume_deterministic=True`` stage options suppress."""
        if not settings.analyze or _mitigate.active() is None:
            return True
        from .analyze import props

        for stage in stages:
            try:
                v = props.stage_verdict(stage)
            except Exception:  # noqa: BLE001 - analysis never fails a run
                continue
            if not v.deterministic:
                ctl = _mitigate.active()
                if ctl is not None:
                    ctl.note_speculation_declined(
                        v.name, v.nondet_evidence)
                return False
        return True

    def _pool_run(self, fn, jobs, n_workers, label=None, speculative=True,
                  on_result=None, place=None):
        """``on_result(idx, result)`` — the pipelined executor's publish
        hook — runs on the dispatching thread as each job's COMMITTED
        result is collected (attempt rollback, retries, and speculation
        all resolved), in job order.  It may block (backpressure); job
        workers keep running ahead, bounded by the store budget.
        ``place(job)`` — the context each attempt of a job runs in
        (``parallel.placement``: the job's chip); its span carries
        ``chip``."""
        retries = settings.job_retries
        if retries:
            inner = fn

            def fn(job):  # noqa: F811 - deliberate retry wrapper
                for attempt in range(retries + 1):
                    try:
                        # attempt() rolls back this attempt's block
                        # registrations on failure so retries never orphan
                        # refs against the memory budget.
                        with self.store.attempt():
                            return inner(job)
                    except Exception as e:
                        # Classified retry (dampr_tpu.faults): fatal
                        # failures never re-execute; transient ones back
                        # off exponentially with jitter so a retry storm
                        # against a sick disk decorrelates; deterministic
                        # failures retry immediately (a stateful UDF may
                        # recover — the historical contract).
                        kind = _faults.classify(e)
                        if kind == "fatal" or attempt == retries:
                            raise
                        delay = (_faults.backoff(attempt)
                                 if kind == "transient" else 0.0)
                        ctl = _mitigate.active()
                        if ctl is not None and kind == "transient":
                            # Local transient-fault rate: shared with
                            # the fleet on the next exchange window's
                            # piggyback — a rank drowning in retries
                            # earns the sticky down-weight even before
                            # its step entries turn late.
                            ctl.note_local_retry()
                        with self._retry_lock:
                            self.retries_total += 1
                            self._backoff_seconds += delay
                        _trace.instant("retry", label or "job",
                                       attempt=attempt + 1, kind=kind)
                        log.warning(
                            "job failed (%s, attempt %d/%d), retrying"
                            "%s", kind, attempt + 1, retries + 1,
                            " in %.0f ms" % (delay * 1000) if delay
                            else "", exc_info=True)
                        if delay:
                            time.sleep(delay)

        if label is not None and _trace.enabled():
            traced = fn

            def fn(job, _inner=traced):  # noqa: F811 - span per job, on the
                #                          worker thread = one lane per slot
                chip = _placement.current()
                args = {} if chip is None else {"chip": chip}
                with _trace.span("job", label, **args):
                    return _inner(job)

        # Speculative duplicate attempts re-run the job but must not
        # re-run the one-call-per-job accounting below: the profiler's
        # job thread-seconds (the coverage denominator) and the
        # jobs_started/done counters both assume one counted call per
        # job — a losing duplicate would inflate them (10/8 jobs done).
        # Retry + trace-span wrappers DO apply to duplicates (a real
        # attempt deserves a real span).
        fn_speculative = fn

        prof = _profile.active()
        if prof is not None:
            # Per-stage job thread-seconds: the denominator of the
            # profiler's coverage metric (how much of the stage's job
            # time the per-op attribution explains).
            profiled = fn

            def fn(job, _inner=profiled):  # noqa: F811
                t0 = time.perf_counter()
                try:
                    return _inner(job)
                finally:
                    prof.job_add(time.perf_counter() - t0)

        m = _metrics.active()
        if m is not None:
            # Active-jobs accounting + the progress line's per-stage job
            # tally.  Outermost wrapper: a retried job counts once per
            # attempt started/done, so the active gauge stays balanced.
            st = self._status
            st["jobs_total"] = len(jobs)
            st["jobs_done"] = 0
            metered = fn

            def fn(job, _inner=metered):  # noqa: F811
                m.counter_add("run.jobs_started", 1)
                try:
                    return _inner(job)
                finally:
                    m.counter_add("run.jobs_done", 1)
                    st["jobs_done"] = st.get("jobs_done", 0) + 1

        if place is not None:
            # Outermost: every attempt, and the job's span, on its chip.
            def on_chip(inner):
                def placed(job):
                    with place(job):
                        return inner(job)
                return placed

            fn, fn_speculative = on_chip(fn), on_chip(fn_speculative)

        def collect(results_iter):
            out = []
            for r in results_iter:
                if on_result is not None:
                    on_result(len(out), r)
                out.append(r)
            return out

        n_workers = max(1, min(n_workers, len(jobs), settings.max_processes))
        if n_workers == 1 or len(jobs) <= 1:
            return collect(fn(j) for j in jobs)
        ctl = _mitigate.active()
        if ctl is not None:
            # Mitigation-aware dispatch: rank-owned per-worker queues
            # with work stealing, plus speculative re-execution of
            # straggler jobs (first-result-wins under attempt-scoped
            # commits).  Sinks never speculate (duplicate part-file
            # writes would race on one path); quarantine-armed runs
            # don't either (a losing duplicate's quarantine commits
            # would double-count poison records against the budget).
            results = _mitigate.pool_dispatch(
                ctl, fn, jobs, n_workers, store=self.store,
                speculative=(speculative and self._quarantine is None),
                spec_fn=fn_speculative)
            if on_result is not None:
                # Mitigation dispatch returns only after every job
                # finished; publish post-hoc in order so the consumer
                # still sees each result exactly once (no overlap —
                # streamed stages degrade under an armed controller).
                for i, r in enumerate(results):
                    on_result(i, r)
            return results
        with ThreadPoolExecutor(max_workers=n_workers) as pool:
            return collect(pool.map(fn, jobs))

    # -- pipelined (barrier-free) dispatch ---------------------------------
    def _stage_folder(self, stage_id, feeds_dev, run_mode, pin):
        """Early-fold folder for a streamed map->keyed-fold edge, or None
        when the edge isn't streamed (the common case: one dict probe) or
        a runtime condition degrades it back to the staged barrier."""
        hint = self._pipeline_edges.get(stage_id)
        if hint is None or hint["mode"] != "early_fold":
            return None
        if not settings.pipeline_enabled():
            return None
        degrade = None
        if _mitigate.active() is not None:
            degrade = "mitigation controller armed"
        elif run_mode or pin:
            degrade = "producer run mode incompatible"
        if degrade is None:
            cons = self.graph.stages[hint["dst"]]
            op = getattr(getattr(cons, "reducer", None), "op", None)
            if op is None or op.kind not in ("sum", "min", "max"):
                degrade = "consumer op not early-foldable"
        if degrade is not None:
            self._pipeline_stats["degraded"] += 1
            log.info("streamed edge s%s degraded to staged barrier: %s",
                     stage_id, degrade)
            return None
        bound = settings.pipeline_queue_bytes or max(
            1, self.store.budget // 4)
        self._pipeline_stats["executed"] += 1
        _trace.instant("pipeline", "streamed-edge", src=stage_id,
                       dst=hint["dst"], mode="early_fold")
        # feeds_dev rides through so folded replacements register in the
        # same tier the originals did (the reduce's device fold reads
        # them without an extra host round-trip).
        return _StreamFolder(self.store, op, bound, device=feeds_dev)

    def _note_pipeline(self, stage_id, fstats):
        """Merge one streamed edge's folder stats into the run total."""
        ps = self._pipeline_stats
        for k in ("published", "early_folded_blocks", "bytes_in",
                  "bytes_out", "fold_seconds", "overlap_seconds",
                  "stall_seconds"):
            ps[k] += fstats[k]
        ps["queue_peak_bytes"] = max(ps["queue_peak_bytes"],
                                     fstats["queue_peak_bytes"])
        series = ps["queue_depth_series"]
        series.extend([stage_id, t, b]
                      for t, b in fstats["queue_depth_series"])
        if len(series) > 1024:
            del series[: len(series) - 1024]

    def _wrap_chain_job(self, fn):
        """Retry + trace wrapper for chain consumer jobs: the _pool_run
        stack minus speculation (chain never speculates — duplicate
        consumer jobs would double-emit) and minus the per-stage job
        tally (the consumer's job count isn't known up front)."""
        retries = settings.job_retries

        def run(job):
            for attempt in range(retries + 1):
                try:
                    with self.store.attempt():
                        if _trace.enabled():
                            with _trace.span("job", "chain"):
                                return fn(job)
                        return fn(job)
                except Exception as e:
                    kind = _faults.classify(e)
                    if kind == "fatal" or attempt == retries:
                        raise
                    delay = (_faults.backoff(attempt)
                             if kind == "transient" else 0.0)
                    with self._retry_lock:
                        self.retries_total += 1
                        self._backoff_seconds += delay
                    _trace.instant("retry", "chain", attempt=attempt + 1,
                                   kind=kind)
                    log.warning(
                        "chain job failed (%s, attempt %d/%d), retrying%s",
                        kind, attempt + 1, retries + 1,
                        " in %.0f ms" % (delay * 1000) if delay else "",
                        exc_info=True)
                    if delay:
                        time.sleep(delay)
        return run

    def _run_chain(self, sid_p, stage_p, sid_c, env):
        """Streamed map->map chain (docs/pipeline.md): the consumer's
        jobs run per completed producer partition block while the
        producer stage is still executing, and the producer's output
        never materializes as a stage-boundary PartitionSet.

        Byte-identity contract: consumer results collect in the staged
        job order — (producer pid, producer job idx) — which is exactly
        the order the staged executor's ``all_refs()`` walk would have
        fed them in, and per-pid record order survives compaction's
        order-preserving concat on both legs.  Block BOUNDARIES differ
        (the staged leg compacts producer refs first), which the plan
        pass already proved invisible: chain edges require a pure record
        stream with no boundary-sensitive consumer downstream.

        Returns (producer placeholder, records, n_jobs) for the
        producer's stage bookkeeping and stashes the consumer's result
        in ``self._chain_results[sid_c]``; returns None to degrade to
        the staged barrier."""
        stage_c = self.graph.stages[sid_c]
        if _mitigate.active() is not None:
            self._pipeline_stats["degraded"] += 1
            log.info("streamed edge s%s degraded to staged barrier: "
                     "mitigation controller armed", sid_p)
            return None
        entries = [env[s] for s in stage_p.inputs]
        chunks = self._as_chunks(entries[0])
        supplementary = [self._as_chunks(e) for e in entries[1:]]
        (job_p, comb_p, pin_p, fr_p, _sp, dev_p, run_p,
         _wp) = self._map_job_factory(stage_p, supplementary, sid=sid_p)
        (job_c, comb_c, pin_c, fr_c, _sc, dev_c, run_c,
         _wc) = self._map_job_factory(stage_c, [], sid=sid_c)
        if (comb_p is not None or pin_p or fr_p or dev_p or run_p
                or comb_c is not None or pin_c or fr_c or dev_c or run_c):
            # The factories disagree with the plan-time gates (a settings
            # override between plan and run, or a shape the pass missed):
            # the staged barrier is always correct.
            self._pipeline_stats["degraded"] += 1
            log.info("streamed edge s%s degraded to staged barrier: "
                     "factory mode incompatible", sid_p)
            return None

        self._pipeline_stats["executed"] += 1
        _trace.instant("pipeline", "streamed-edge", src=sid_p, dst=sid_c,
                       mode="chain")
        n_maps = stage_p.options.get("n_maps", self.n_maps)
        wrapped_c = self._wrap_chain_job(job_c)
        futures = {}   # (producer pid, producer job idx) -> (future, refs)
        spans = []     # (t0, t1) per consumer job, for overlap accounting
        spans_lock = threading.Lock()
        acct = {"bytes_in": 0, "records_in": 0}

        def timed_c(ds, _run=wrapped_c):
            t0 = time.perf_counter()
            try:
                return _run(ds)
            finally:
                with spans_lock:
                    spans.append((t0, time.perf_counter()))

        def publish(idx, mapping):
            _faults.check("stream_publish")
            self._pipeline_stats["published"] += 1
            for pid in sorted(k for k in mapping if k != "_sorted"):
                refs = list(mapping[pid])
                if not refs:
                    continue
                acct["bytes_in"] += sum(r.total_bytes for r in refs)
                acct["records_in"] += sum(len(r) for r in refs)
                futures[(pid, idx)] = (
                    pool_c.submit(timed_c, BlockDataset(refs)), refs)

        pool_c = ThreadPoolExecutor(
            max_workers=max(1, min(n_maps, settings.max_processes)),
            thread_name_prefix="dampr-tpu-chain")
        pool_done_at = None
        try:
            self._pool_run(job_p, chunks, n_maps, label="map",
                           speculative=False, on_result=publish)
            pool_done_at = time.perf_counter()
            mappings_c = []
            for key in sorted(futures):
                fut, refs = futures[key]
                mappings_c.append(fut.result())
                for r in refs:
                    self.store.drop_ref(r)
        finally:
            pool_c.shutdown(wait=True)
        fold_s = sum(t1 - t0 for t0, t1 in spans)
        overlap_s = sum(max(0.0, min(t1, pool_done_at) - t0)
                        for t0, t1 in spans) if pool_done_at else 0.0
        bytes_out = sum(r.total_bytes for m in mappings_c
                        for refs in m.values() for r in refs)
        self._note_pipeline(sid_p, {
            "published": 0, "early_folded_blocks": 0,
            "bytes_in": acct["bytes_in"], "bytes_out": bytes_out,
            "fold_seconds": fold_s, "overlap_seconds": overlap_s,
            "stall_seconds": 0.0, "queue_peak_bytes": 0,
            "queue_depth_series": []})
        pset = self._collect_partitions(
            mappings_c, comb_c, pin_c, fr_c, device=dev_c,
            sorted_runs=run_c, handoff=sid_c in self._handoff_sids)
        self._chain_results[sid_c] = (
            pset, pset.total_records(), len(futures))
        return (_ChainedOutput(acct["records_in"]), acct["records_in"],
                len(chunks))

    def _scan_placer(self, stages, chunks):
        """Where a lowered map stage's jobs run: each on one chip of the
        data mesh the collective fold uses (``parallel.placement``).
        None when the lowering claimed none of ``stages``."""
        if not any(s.options.get("exec_target") == "device"
                   for s in stages):
            return None
        from .ops import lower as ops_lower
        from .parallel.mesh import data_mesh

        if not any(ops_lower.claims(s.mapper) is not None for s in stages):
            return None
        mesh = data_mesh()
        self.store.mesh_chips = len(_placement.local_chips(mesh))
        return _placement.placer(chunks, mesh)

    # -- stage input views --------------------------------------------------
    def _as_chunks(self, entry):
        """Entry (tap Chunker or PartitionSet) -> list of map-job datasets
        (the DMChunker flattening, reference dataset.py:622-629)."""
        if isinstance(entry, storage.PartitionSet):
            ds = [BlockDataset([ref]) for ref in entry.all_refs()]
            return ds if ds else [BlockDataset([])]
        if isinstance(entry, _SinkOutput):
            return entry.datasets()
        assert isinstance(entry, Chunker), entry
        chunks = list(entry.chunks())
        return chunks if chunks else [BlockDataset([])]

    # -- map ---------------------------------------------------------------
    def run_map(self, stage_id, stage, env):
        entries = [env[s] for s in stage.inputs]
        chunks = self._as_chunks(entries[0])
        supplementary = [self._as_chunks(e) for e in entries[1:]]

        # Tiny-input collapse: a small materialized input to a plain record
        # mapper runs as ONE job over the concatenated refs instead of one
        # job per ref — per-job fixed costs dominate at this size.  Only
        # where chunking is mechanical, not semantic: pure record streams
        # (fused chains of plain Maps — is_pure_record_stream walks the
        # composition, since a fused chain can embed a StreamMapper whose
        # per-chunk invocation IS the semantics) and the broadcast joins
        # (which iterate the primary side record-wise).
        if (len(chunks) > 1
                and isinstance(entries[0], storage.PartitionSet)
                and (base.is_pure_record_stream(stage.mapper)
                     or type(stage.mapper) in (base.MapCrossJoin,
                                               base.MapAllJoin))):
            refs = list(entries[0].all_refs())
            if sum(getattr(r, 'total_bytes', r.nbytes)
                   for r in refs) <= settings.small_stage_bytes:
                chunks = [BlockDataset(refs)]

        (job, combine_op, pin, feeds_reduce, _new_sink,
         feeds_dev, run_mode, _wsink) = self._map_job_factory(
            stage, supplementary, sid=stage_id)

        n_maps = stage.options.get("n_maps", self.n_maps)
        folder = self._stage_folder(stage_id, feeds_dev=feeds_dev,
                                    run_mode=run_mode, pin=pin)
        replaced = {}
        try:
            # A broadcast join's jobs trace as "cross".
            results = self._pool_run(
                job, chunks, n_maps, label="cross" if supplementary else "map",
                speculative=self._speculation_ok(stage),
                on_result=folder.publish if folder is not None else None,
                place=(None if supplementary
                       else self._scan_placer((stage,), chunks)))
        finally:
            if folder is not None:
                # finish() drains even on a failed stage, so every queued
                # reservation releases — kill-mid-stream never orphans
                # queue entries against the budget.
                folder.mark_pool_done()
                replaced, fstats = folder.finish()
                self._note_pipeline(stage_id, fstats)
        for idx, mapping in replaced.items():
            if idx < len(results):
                results[idx] = mapping
        pset = self._collect_partitions(
            results, combine_op, pin, feeds_reduce, device=feeds_dev,
            sorted_runs=run_mode,
            handoff=stage_id in self._handoff_sids)
        if folder is not None and folder.fold_delta:
            # Staged-bytes pinning for the reduce's size gates: the tiny
            # fast path must branch on what the partition WOULD have
            # weighed unfolded, or pipeline on/off could take different
            # emit paths (hash-order vs key-order layouts).
            pset.pipeline_fold_delta = dict(folder.fold_delta)
        return pset, pset.total_records(), len(chunks)

    def _collect_partitions(self, mappings, combine_op, pin, feeds_reduce,
                            device=False, sorted_runs=False,
                            handoff=False):
        """Assemble per-chunk {pid: [refs]} job results into one compacted
        PartitionSet (shared by run_map and run_map_group).

        ``sorted_runs``: the jobs ran in spill-lean run mode — each mapping
        carries a ``_sorted`` marker recording whether every one of its
        blocks registered as a key-sorted run (numeric keys); the pset is
        flagged ``key_sorted_runs`` only when ALL jobs' blocks did, so the
        read-side streaming merge can trust every ref."""
        all_sorted = bool(sorted_runs)
        pset = storage.PartitionSet(
            self.n_partitions,
            hash_routed=not sorted_runs,
            hash_sorted=not sorted_runs and (combine_op is not None
                                             or feeds_reduce))
        for mapping in mappings:
            if sorted_runs and not mapping.pop("_sorted", False):
                all_sorted = False
            for pid, refs in mapping.items():
                for ref in refs:
                    pset.add(pid, ref)
        pset.key_sorted_runs = all_sorted
        if all_sorted and pset.parts:
            # Spill-lean path: no block-count compaction rewrite — merge
            # planning caps the read fan-in instead, and under the cap the
            # final read feeds straight from first-level runs.
            self._plan_sorted_merge(pset)
        else:
            self._compact_partitions(pset, combine_op, pin, feeds_reduce,
                                     device=device, handoff=handoff)
        return pset

    def _effective_merge_fanin(self, runs):
        """Fan-in cap for the sorted-run merge: the configured
        ``settings.merge_fanin``, clamped so the k-way merge's working set
        — one buffered spill window per run PLUS that run's bounded frame
        readahead (``settings.spill_read_prefetch`` windows in flight on
        the read executor), sized from the runs' observed bytes/record —
        stays inside the stage budget."""
        total = sum(max(1, r.total_bytes) for r in runs)
        nrec = sum(len(r) for r in runs)
        window = max(1, int(total / max(1, nrec)) * storage.SPILL_WINDOW)
        per_run = (2 + max(0, settings.spill_read_prefetch)) * window
        cap = max(4, int(self.store.budget // per_run))
        return max(2, min(settings.merge_fanin, cap))

    def _plan_sorted_merge(self, pset):
        """Merge planning for a key-sorted run set (the spill-lean external
        sort).  When the number of first-level runs fits the fan-in cap,
        nothing happens — the final read merges the runs directly, so the
        only bytes that ever hit disk are the map jobs' single spill
        generation.  Past the cap, a generation merges runs through
        streamed file->file passes (one in-flight window per source,
        output written as it merges — never RAM-resident whole) until the
        count fits, with two spill-lean refinements:

        - **minimal-touch planning**: only enough runs merge to bring the
          count under the cap — the smallest ones, so a run set just past
          the fan-in re-spills a fraction of its bytes, not all of them
          (merging m groups of <= g runs cuts the count by sum(g_i - 1),
          so m = ceil(excess / (g - 1)) merges suffice and everything
          else feeds the final read untouched);
        - **parallel generations**: the groups are independent, so they
          merge concurrently on a worker pool, each group's fan-in share
          capped at ``fanin // workers`` — the combined working set (one
          buffered window + prefetch per source across every concurrent
          merge) stays inside what the fan-in clamp budgeted.
        """
        from .blocks import merge_sorted_streams

        runs = [r for r in pset.all_refs() if len(r)]
        if not runs:
            return
        fanin = self._effective_merge_fanin(runs)
        gen = 0
        while len(runs) > fanin:
            # Worker count divides the fan-in budget: workers * group_cap
            # <= fanin, so the concurrent merges' combined working set
            # (one buffered window + prefetch per source) never exceeds
            # what _effective_merge_fanin budgeted — and group_cap >= 2
            # always (fanin >= 2), so every group genuinely reduces the
            # run count.
            workers = max(1, min(settings.max_processes, 8, fanin // 2))
            group_cap = max(2, fanin // workers)
            need = len(runs) - fanin
            m = max(1, -(-need // (group_cap - 1)))
            touched = need + m
            if touched > len(runs):
                # Far over the cap: every run merges this generation, in
                # groups of group_cap (the count still shrinks by a
                # group_cap factor per generation; the loop reruns).
                touched = len(runs)
                m = -(-touched // group_cap)
            # Smallest runs merge (fewest re-spilled bytes); the stride
            # split balances group sizes AND bytes across the workers.
            runs.sort(key=lambda r: r.total_bytes)
            to_merge = runs[:touched]
            keep = runs[touched:]
            groups = [g for g in (to_merge[i::m] for i in range(m)) if g]
            if _metrics.enabled():
                # Merge shape per generation: fan-in distribution and the
                # live run count the planner is working down.
                _metrics.counter_add("merge.generations", 1)
                _metrics.gauge_set("merge.runs", len(runs))
                for g in groups:
                    _metrics.observe("merge.fanin", len(g))
            log.info(
                "sorted-run merge generation: %d runs over fan-in %d — "
                "merging %d smallest into %d group(s) on %d worker(s)",
                len(runs), fanin, touched, len(groups),
                min(workers, len(groups)))

            def merge_group(group):
                if len(group) == 1:
                    return group[0]
                merged = self.store.register_stream(merge_sorted_streams(
                    [r.iter_windows() for r in group]))
                for r in group:
                    self.store.drop_ref(r)
                return merged

            # The generation gets its own trace lane, so Perfetto shows
            # merge generations stacked under the map slots they follow;
            # each group's streamed merge-run span lands on its worker
            # thread's lane.
            with _trace.span("merge", "generation {}".format(gen),
                             lane="merge gen {}".format(gen),
                             runs=len(runs), fanin=fanin,
                             groups=len(groups)):
                if len(groups) > 1 and workers > 1:
                    with ThreadPoolExecutor(
                            max_workers=min(workers, len(groups))) as pool:
                        merged = list(pool.map(merge_group, groups))
                else:
                    merged = [merge_group(g) for g in groups]
            runs = keep + merged
            gen += 1
        pset.parts = {0: runs}

    def _scan_share_group(self, sid, stage, env):
        """Later GMap stages reading the SAME tap source as `stage`: fusion
        candidates for one shared pass.  Only single-input stages over a
        Chunker tap (where IO is the dominant cost) qualify."""
        if not settings.scan_sharing or len(stage.inputs) != 1:
            return []
        if not isinstance(env.get(stage.inputs[0]), Chunker):
            return []
        group = []
        for sjd in range(sid + 1, len(self.graph.stages)):
            s2 = self.graph.stages[sjd]
            if (isinstance(s2, GMap) and len(s2.inputs) == 1
                    and s2.inputs[0] == stage.inputs[0]):
                group.append((sjd, s2))
        return group

    def run_map_group(self, sids, stages, env):
        """Scan sharing: execute several map stages over one pass of their
        common tap.

        Preferred path — every member exposes ``window_sink`` (the
        ops.text scanners): ONE line-aligned window pass per chunk fans
        each window out to every member's sink and pushes the resulting
        blocks straight into that member's fold/register pipeline, so the
        tap is read (and a .gz decompressed) exactly once with memory
        bounded by the window, never the chunk.

        Fallback — members that materialize bytes share one chunk read via
        the _SharedScanChunk cache (byte-materializing members run before
        streaming ones, Mapper.streams_bytes); per-record members read
        independently.  Returns one (pset, nrec, njobs) per stage, in the
        given order."""
        tap = env[stages[0].inputs[0]]
        chunks = self._as_chunks(tap)
        factories = [self._map_job_factory(s, [], sid=sjd)
                     for sjd, s in zip(sids, stages)]
        order = sorted(range(len(stages)),
                       key=lambda i: bool(
                           getattr(stages[i].mapper, "streams_bytes", False)))
        all_window = all(
            hasattr(s.mapper, "window_sink") for s in stages)

        def group_job(chunk):
            if all_window and hasattr(chunk, "iter_byte_blocks"):
                from .ops.text import _scan_windows

                members = []
                for i, s in enumerate(stages):
                    push, end = factories[i][4]()
                    # factories[i][7] is the target-aware window-sink
                    # factory: device-lowered members scan through the
                    # jitted programs, host members keep their own sink.
                    members.append((factories[i][7](), push, end))

                def codec():
                    # ONE sequential window pass drives every member's
                    # sink (sinks are stateful, so a single producer
                    # thread owns them); the emitted (member, block)
                    # pairs overlap with the fold/register consumer.
                    for win in _scan_windows(chunk):
                        for mi, (wsink, _push, _end) in enumerate(members):
                            for blk in wsink.add(win) or ():
                                yield mi, blk
                    for mi, (wsink, _push, _end) in enumerate(members):
                        for blk in wsink.finish() or ():
                            yield mi, blk

                gen = codec()
                prof = _profile.active()
                if prof is not None:
                    # The shared window pass serves EVERY member; its scan
                    # time is attributed once, under a label naming the
                    # fused scanners (per-member split is not observable —
                    # one producer thread drives all the sinks).
                    gen = prof.timed_iter(
                        gen, "scan:" + "+".join(
                            type(s.mapper).__name__ for s in stages),
                        records_of=lambda it: len(it[1]))
                for mi, blk in _overlap_stream(
                        gen, self.store,
                        size_of=lambda it: it[1].nbytes()):
                    members[mi][1](blk)
                outs_w = []
                for wsink, push_m, end_m in members:
                    hmap = None
                    if hasattr(wsink, "finalize_handoff"):
                        fblocks, hmap = wsink.finalize_handoff(
                            self.store, self.n_partitions)
                        for blk in fblocks:
                            push_m(blk)
                    o = end_m()
                    if hmap:
                        for pid, refs in hmap.items():
                            o.setdefault(pid, []).extend(refs)
                    outs_w.append(o)
                return outs_w
            shared = (_SharedScanChunk(chunk)
                      if hasattr(chunk, "read_bytes") else chunk)
            outs = [None] * len(stages)
            for i in order:
                outs[i] = factories[i][0](shared)
            return outs

        # Honor every member's explicit n_maps: the most restrictive wins,
        # so a stage that asked to serialize stays serialized when fused.
        n_maps = min(s.options.get("n_maps", self.n_maps) for s in stages)
        results = self._pool_run(group_job, chunks, n_maps,
                                 label="map-group",
                                 speculative=self._speculation_ok(*stages),
                                 place=self._scan_placer(stages, chunks))

        ret = []
        for i in range(len(stages)):
            (_job, combine_op, pin, feeds_reduce, _new_sink,
             feeds_dev, run_mode, _wsink) = factories[i]
            pset = self._collect_partitions(
                [outs[i] for outs in results], combine_op, pin, feeds_reduce,
                device=feeds_dev, sorted_runs=run_mode,
                handoff=sids[i] in self._handoff_sids)
            ret.append((pset, pset.total_records(), len(chunks)))
        log.info("scan sharing: %d stages fused over one pass of %d chunks",
                 len(stages), len(chunks))
        return ret

    def _map_job_factory(self, stage, supplementary, sid=None):
        """Build the per-chunk job closure for one map stage.  Shared by
        run_map and the scan-sharing group executor (run_map_group), which
        runs several stages' jobs over one chunk read.  ``sid`` keys the
        plan's per-edge dispatch decisions (the device-handoff set)."""
        combine_op = None
        if isinstance(stage.combiner, base.PartialReduceCombiner):
            combine_op = stage.combiner.op
        elif "binop" in stage.options:
            combine_op = segment.as_assoc_op(stage.options["binop"])

        pin = bool(stage.options.get("memory"))
        P = self.n_partitions
        # Hash-sorted runs are only needed when a reduce consumes this output
        # (it's what the over-budget streaming merge relies on); stages
        # feeding sinks or final reads skip the sort — their consumers
        # re-order by key anyway.  The view is TRANSITIVE through identity
        # checkpoints: a reduce behind ``checkpoint(force=True)`` still
        # needs hash routing here, or the checkpoint's declined alias
        # forces a full re-routing copy pass over the dataset.
        feeds_reduce = self._reduce_consumes(stage.output)
        # HBM residency: outputs consumed by a device-foldable reduce keep
        # their numeric value lanes on device (storage register gates on
        # the lane whitelist + budget), so the map->reduce boundary never
        # round-trips those lanes through host memory.
        feeds_device_fold = (
            feeds_reduce
            and settings.use_device
            and str(settings.mesh_fold).lower() not in ("off", "0", "false")
            and any(
                isinstance(s, GReduce) and stage.output in s.inputs
                and len(s.inputs) == 1
                and isinstance(getattr(s, "reducer", None),
                               base.AssocFoldReducer)
                and getattr(getattr(s.reducer, "op", None), "kind", None)
                in ("sum", "min", "max")
                for s in self.graph.stages))
        # Spill-lean sorted-run mode (external sorts): outputs no reduce
        # consumes don't need hash fan-out at all — their only readers
        # re-order by key (OutputDataset) or stream refs whole (sinks,
        # record maps).  Each job registers its chunk as ONE key-sorted run
        # instead of `partitions` hash-routed sub-blocks; the compaction
        # rewrite is replaced by fan-in-capped merge planning and the final
        # read streams a k-way merge.  Jobs fall back to hash fan-out per
        # chunk when keys aren't uniformly numeric (the ``_sorted`` marker
        # records which happened).
        sorted_run_mode = (settings.sort_runs_enabled()
                           and combine_op is None
                           and not feeds_reduce
                           and not pin
                           and not supplementary)

        def try_sorted_run(blocks):
            """Register one key-sorted run for this job, or None when the
            keys don't qualify (caller falls back to hash fan-out)."""
            blocks = [b for b in blocks if len(b)]
            if not blocks:
                return {"_sorted": True}
            kdts = {b.keys.dtype for b in blocks}
            if len(kdts) != 1 or next(iter(kdts)).kind not in "iuf":
                return None
            if (next(iter(kdts)).kind == "f"
                    and any(np.isnan(b.keys).any() for b in blocks)):
                # NaN has no total order: a NaN-tailed run would break
                # the k-way merge's non-decreasing emission contract
                # (NaN poisons the bound comparisons).  Hash fan-out
                # handles NaN keys the same way it always has.
                return None
            merged = blocks[0] if len(blocks) == 1 else Block.concat(blocks)
            merged = merged.take(np.argsort(merged.keys, kind="stable"))
            return {0: [self.store.register(merged)], "_sorted": True}

        def new_sink():
            """Push-mode accumulator for one chunk job: push(blk) folds/
            collects, end() registers and returns the partition mapping.
            The scan-sharing group executor pushes blocks from a SHARED
            window pass into several stages' sinks."""
            raw, partials = [], []

            def push(blk):
                if blk is None or not len(blk):
                    return
                if combine_op is not None:
                    _faults.check("fold")
                    prof = _profile.active()
                    t0p = time.perf_counter() if prof is not None else 0.0
                    with _trace.span("fold", "partial-fold",
                                     records=len(blk)):
                        partials.append(segment.fold_block(blk, combine_op))
                        if len(partials) >= _PARTIAL_FANIN:
                            merged = segment.fold_block(
                                Block.concat(partials), combine_op)
                            del partials[:]
                            partials.append(merged)
                    if prof is not None:
                        prof.op_add("combine", time.perf_counter() - t0p,
                                    records=len(blk))
                else:
                    raw.append(blk)

            def end():
                blocks = raw
                if combine_op is not None and partials:
                    prof = _profile.active()
                    t0p = time.perf_counter() if prof is not None else 0.0
                    with _trace.span("fold", "final-fold"):
                        blocks = [segment.fold_block(
                            Block.concat(partials), combine_op)]
                    if prof is not None:
                        prof.op_add("combine", time.perf_counter() - t0p)
                if sorted_run_mode:
                    out = try_sorted_run(blocks)
                    if out is not None:
                        return out
                # Register with the store *inside* the job so the memory
                # budget is enforced while the stage runs, not after all
                # jobs complete.  Every registered block is a hash-sorted
                # run (fold outputs already are; raw blocks sort here —
                # stable, so equal keys keep input order), which is what
                # lets over-budget reduces stream a k-way merge instead of
                # materializing the partition.
                out = {}
                for blk in blocks:
                    if combine_op is None and feeds_reduce:
                        blk = blk.sort_by_hash()
                    for pid, sub in blk.split_by_partition(P).items():
                        out.setdefault(pid, []).append(
                            self.store.register(sub, pin=pin,
                                                device=feeds_device_fold,
                                                handoff=stage_handoff))
                return out

            return push, end

        # Per-stage block sizing: the plan's cost layer may have set a
        # batch_size option from observed bytes/record history.
        stage_batch = stage.options.get("batch_size") or settings.batch_size

        # Device-lowered stage (plan.lower assigned exec_target): the
        # scanner's window pass runs through the jitted tokenize+hash+fold
        # programs instead of the host codec.  claims() re-checks the
        # mapper so a stale/foreign annotation can never dispatch an
        # unrecognized op — the host path below is the guaranteed fallback.
        # Cross-stage device handoff (plan.lower handoff_analyze): this
        # stage's output edge keeps program outputs HBM-resident for the
        # consuming device fold.  A runtime dispatch hint keyed by sid —
        # deliberately NOT stage options (fingerprints stay
        # history-independent).
        stage_handoff = sid is not None and sid in self._handoff_sids
        dev_lowered = False
        lane_program = None
        if stage.options.get("exec_target") == "device":
            from .ops import lower as ops_lower

            dev_lowered = ops_lower.claims(stage.mapper) is not None
            if not dev_lowered:
                # Certified numeric UDF chain (analyze.jaxtrace, the
                # widened ROADMAP-5a vocabulary): the batched-UDF path
                # below runs whole batches through one vectorized lane
                # program.  stage_program re-certifies the chain, so a
                # stale/foreign annotation can never dispatch an
                # unknown op; non-numeric batches fall back per batch.
                from .analyze import jaxtrace as _jaxtrace

                lane_program = _jaxtrace.stage_program(stage)
        # One handoff vocabulary per chip for this run of the stage: the
        # jobs placed on a chip share its slot map and device table, each
        # with its own accumulator (ops.handoff).  It goes with the stage.
        vocabs = None
        if dev_lowered and stage_handoff:
            from .ops import handoff as ops_handoff

            vocabs = ops_handoff.StageVocabs(self.store)

        def window_sink():
            """The stage's window sink honoring its execution target
            (shared with run_map_group's fused window pass)."""
            if dev_lowered:
                from .ops import lower as ops_lower

                return ops_lower.device_window_sink(
                    _clone_op(stage.mapper), self.store,
                    handoff=stage_handoff,
                    jobs=stage.options.get("n_maps", self.n_maps),
                    vocabs=vocabs)
            return _clone_op(stage.mapper).window_sink()

        def job(chunk):
            mapper = _clone_op(stage.mapper)
            builder = BlockBuilder(stage_batch)
            # Attempt-scoped quarantine recorder: records isolated by
            # this attempt's bisect land in the global sink only when
            # the attempt SUCCEEDS (commit below), so a retried job
            # never double-counts and genuine duplicates each count.
            quarantine = self._quarantine
            qrec = quarantine.attempt() if quarantine is not None else None
            # Vectorized block protocol: mappers exposing map_blocks consume
            # the chunk's raw bytes and emit whole Blocks, skipping the
            # per-record Python path entirely (the SURVEY §7 dual-path).
            use_blocks = (not supplementary
                          and hasattr(mapper, "map_blocks")
                          and hasattr(chunk, "read_bytes"))
            # Identity stages (bare checkpoint/sink heads) pass blocks
            # through whole — the records are already materialized; walking
            # them one by one through Python buys nothing.
            ident_blocks = (not supplementary and not use_blocks
                            and type(mapper) is base.Map
                            and mapper.mapper is base._identity
                            and hasattr(chunk, "iter_blocks"))
            # Batched-UDF path (SURVEY §7 hard part 1): a chain of pure
            # RecordOps runs batch-at-a-time — read B records, run each
            # op's apply_batch over the whole batch, build the block from
            # the surviving lists.  Replaces the reference's per-record
            # generator hot loop (ref stagerunner.py:73-74).
            chain = (base.record_op_chain(mapper)
                     if settings.batch_udf and not supplementary
                     and not use_blocks and not ident_blocks else None)
            # Per-operator profiler (obs.profile): one hoisted None-check
            # per job; labels are index-prefixed so duplicate op types in
            # one fused chain stay distinct.
            prof = _profile.active()
            prof_labels = (_profile.chain_labels(chain)
                           if prof is not None and chain is not None
                           else None)
            push, end = new_sink()
            dev_sink = None
            if (dev_lowered and not supplementary
                    and (hasattr(chunk, "read_bytes")
                         or hasattr(chunk, "iter_byte_blocks"))):
                # Device-lowered scan: windows feed double-buffered jitted
                # programs (ops.lower); the producer thread tokenizes and
                # dispatches while this thread folds/registers the
                # vocabulary-sized partials.  Under a handoff="device"
                # edge the sink accumulates device-resident instead of
                # emitting — finalize below registers the HBM refs.
                from .ops import lower as ops_lower
                from .ops.text import _drive_windows

                dev_sink = ops_lower.device_window_sink(
                    mapper, self.store, handoff=stage_handoff,
                    jobs=stage.options.get("n_maps", self.n_maps),
                    vocabs=vocabs)
                for blk in _overlap_stream(
                        _drive_windows(mapper, chunk, sink=dev_sink),
                        self.store):
                    push(blk)
            elif use_blocks:
                # Stage-overlapped streaming executor: the codec (window
                # scan + tokenize/parse inside map_blocks) runs ahead on
                # its own thread while this thread folds/registers, with
                # in-flight blocks charged against the run budget.
                blocks_iter = mapper.map_blocks(chunk)
                if prof is not None:
                    # Each produced window's decompress+tokenize time is
                    # the scanner op's attribution.
                    blocks_iter = prof.timed_iter(
                        blocks_iter, _profile.op_label(mapper, 0))
                for blk in _overlap_stream(blocks_iter, self.store):
                    push(blk)
            elif ident_blocks:
                for blk in chunk.iter_blocks():
                    push(blk)
            elif chain is not None:
                B = stage_batch
                reader = getattr(chunk, "read_lists", None)
                if reader is not None:
                    batches = reader(B)
                else:
                    def _islice_batches(it=iter(chunk.read())):
                        while True:
                            ks, vs = [], []
                            for k, v in itertools.islice(it, B):
                                ks.append(k)
                                vs.append(v)
                            if not ks:
                                return
                            yield ks, vs
                    batches = _islice_batches()
                # Surviving records accumulate across input batches so a
                # selective filter still emits ~B-record blocks (matching
                # BlockBuilder's coalescing on the generator path), while
                # FlatMap feeds in adaptive slices so B x fanout never
                # materializes at once — memory stays bounded either way.
                pk, pv = [], []

                def emit(ks, vs):
                    pk.extend(ks)
                    pv.extend(vs)
                    while len(pk) >= B:
                        push(Block.from_lists(pk[:B], pv[:B]))
                        del pk[:B]
                        del pv[:B]

                def run_chain(ks, vs, start, emit_fn):
                    for i in range(start, len(chain)):
                        op = chain[i]
                        if type(op) is base.FlatMap and len(ks) > 1024:
                            # Slice the expanding op's input, adapting to
                            # its observed fanout so each slice's output
                            # stays ~B; the rest of the chain runs per
                            # slice.  Slices preserve stream order, so
                            # batch/stream equivalence is unaffected.
                            n = len(ks)
                            at, step = 0, 1024
                            while at < n:
                                took = min(step, n - at)
                                t0p = (time.perf_counter()
                                       if prof is not None else 0.0)
                                sks, svs = op.apply_batch(
                                    ks[at:at + took], vs[at:at + took])
                                if prof is not None:
                                    prof.op_add(prof_labels[i],
                                                time.perf_counter() - t0p,
                                                records=len(sks))
                                at += took
                                if sks:
                                    fan = -(-len(sks) // took)
                                    step = max(64, min(B, B // fan))
                                    run_chain(sks, svs, i + 1, emit_fn)
                            return
                        if prof is None:
                            ks, vs = op.apply_batch(ks, vs)
                        else:
                            # One clock pair per op per BATCH — the
                            # sampled-timer discipline that keeps the
                            # profiled path inside the <=3% overhead gate.
                            t0p = time.perf_counter()
                            ks, vs = op.apply_batch(ks, vs)
                            prof.op_add(prof_labels[i],
                                        time.perf_counter() - t0p,
                                        records=len(ks))
                        if not ks:
                            return
                    emit_fn(ks, vs)

                fa = _faults.active()
                if quarantine is None and fa is None:
                    prog = lane_program
                    if prog is None:
                        # The hot default: straight through, zero added
                        # cost.
                        for ks, vs in batches:
                            run_chain(ks, vs, 0, emit)
                    else:
                        # Certified lane program: whole batches evaluate
                        # vectorized (64-bit host authority; device
                        # dispatch verified per batch inside run_batch).
                        # The FIRST vectorized batch of each job is
                        # additionally differential-tested against the
                        # per-record chain — a divergence (int64 wrap,
                        # a dtype-sensitive UDF) drops the job back to
                        # the authoritative per-record path for good.
                        diffed = False
                        for ks, vs in batches:
                            out = (prog.run_batch(ks, vs)
                                   if prog is not None else None)
                            if out is not None and not diffed:
                                diffed = True
                                staged = []
                                run_chain(ks, vs, 0,
                                          lambda a, b:
                                          staged.append((a, b)))
                                rks = [k for a, _ in staged for k in a]
                                rvs = [v for _, b in staged for v in b]
                                prog.count("diff_checked")
                                if rks != out[0] or rvs != out[1]:
                                    prog.count("diff_diverged")
                                    log.warning(
                                        "lane program diverged from the "
                                        "per-record chain on its first "
                                        "batch (%s); job falls back to "
                                        "the per-record path",
                                        prog.spec.describe())
                                    prog = None
                                    emit(rks, rvs)
                                    continue
                            if out is None:
                                run_chain(ks, vs, 0, emit)
                            else:
                                emit(*out)
                else:
                    # Poison-record quarantine (and/or fault injection):
                    # each input batch runs TRANSACTIONALLY — outputs
                    # stage into a local buffer and only merge into the
                    # block builder on success, so a deterministic
                    # failure mid-chain (or mid-FlatMap-slice) can be
                    # bisected and re-run without duplicating records.
                    # Order is preserved (left half before right half),
                    # so results are byte-identical to a run whose input
                    # simply lacked the quarantined records.
                    def guarded_run(ks, vs):
                        staged = []

                        def stage_emit(sks, svs):
                            staged.append((sks, svs))

                        try:
                            if fa is not None:
                                _faults.check_records("udf", ks, vs)
                            run_chain(ks, vs, 0,
                                      stage_emit if quarantine is not None
                                      else emit)
                        except Exception as e:
                            if (quarantine is None
                                    or _faults.classify(e)
                                    != "deterministic"):
                                raise
                            if len(ks) <= 1:
                                qrec.add(
                                    _faults.run_context.get("stage"),
                                    ks[0] if ks else None,
                                    vs[0] if vs else None, e)
                                return
                            with _trace.span("fault", "quarantine-bisect",
                                             records=len(ks)):
                                mid = len(ks) // 2
                                guarded_run(ks[:mid], vs[:mid])
                                guarded_run(ks[mid:], vs[mid:])
                            return
                        for sks, svs in staged:
                            emit(sks, svs)

                    for ks, vs in batches:
                        guarded_run(ks, vs)
                if pk:
                    push(Block.from_lists(pk, pv))
            else:
                kvs = (mapper.map(chunk, *supplementary) if supplementary
                       else mapper.map(chunk))
                if prof is not None and combine_op is None:
                    # Generator-path chains don't decompose per op (the
                    # fused generators interleave); attribute the whole
                    # stream to one chain-shaped label so coverage holds.
                    t0p = time.perf_counter()
                    nrec = 0
                    for k, v in kvs:
                        nrec += 1
                        push(builder.add(k, v))
                    push(builder.flush())
                    prof.op_add("stream:" + _profile.op_label(mapper),
                                time.perf_counter() - t0p, records=nrec)
                else:
                    for k, v in kvs:
                        push(builder.add(k, v))
                    push(builder.flush())
            hmap = None
            if dev_sink is not None:
                # Device-resident finalize: the accumulated vocabulary
                # becomes per-partition HBM refs; a budget-degrade flush
                # block rides the classic combine instead.
                fblocks, hmap = dev_sink.finalize_handoff(self.store, P)
                for blk in fblocks:
                    push(blk)
            out = end()
            if hmap:
                for pid, refs in hmap.items():
                    out.setdefault(pid, []).extend(refs)
            if qrec is not None:
                qrec.commit()
            return out

        return (job, combine_op, pin, feeds_reduce, new_sink,
                feeds_device_fold, sorted_run_mode, window_sink)

    def _compact_partitions(self, pset, combine_op, pin, feeds_reduce=True,
                            device=False, handoff=False):
        """Block-count governor (the reference's file-count combiner rounds,
        runner.py:293-320): partitions holding more than max_files_per_stage
        refs merge — re-folding under the stage's associative op when present
        — so ref counts and reduce-side fan-in stay bounded.

        Memory discipline: refs merge in rounds of at most ``limit`` at a
        time, and each round's source refs are dropped from the store before
        the merged block registers, so peak residency stays one round's worth
        over budget instead of the whole partition (and near-budget source
        refs never get pointlessly spilled just to be deleted)."""
        limit = max(2, settings.max_files_per_stage)
        for pid, refs in list(pset.parts.items()):
            if len(refs) > limit:
                _trace.instant("merge", "compact", partition=pid,
                               blocks=len(refs))
            while len(refs) > limit:
                merged_refs = []
                for at in range(0, len(refs), limit):
                    round_refs = refs[at:at + limit]
                    if len(round_refs) == 1:
                        merged_refs.append(round_refs[0])
                        continue
                    blocks = [r.get() for r in round_refs]
                    for r in round_refs:
                        self.store.drop_ref(r)
                    merged = Block.concat(blocks)
                    del blocks
                    if combine_op is not None:
                        merged = segment.fold_block(merged, combine_op)
                    elif feeds_reduce:
                        # keep the run invariant: merged blocks stay
                        # hash-sorted so streaming reduces can merge them
                        merged = merged.sort_by_hash()
                    # On a handoff edge the merged block re-enters the
                    # HBM tier at the edge's floor (the consuming fold
                    # reads it in place); the fetch above is the
                    # governor's one bounded host round trip per
                    # `limit` refs, honestly counted as d2h.
                    merged_refs.append(self.store.register(
                        merged, pin=pin, device=device or handoff,
                        handoff=handoff))
                refs = merged_refs
            pset.parts[pid] = refs

    # -- reduce ------------------------------------------------------------
    def _mesh_reduce(self, stage, entries):
        """Distributed fast path for device-foldable associative reduces:
        window-streamed mesh collective folds (local fold -> all_to_all by
        hash -> final fold per window, partials re-folded through the same
        program), so host memory is one window plus the distinct-key
        accumulator — never the partition set, which may be arbitrarily
        over-budget and spilled.  Returns None whenever the host path is
        required for exactness: object values, lane overflow (every
        mesh_keyed_fold call re-checks its inputs, and partial magnitudes
        are bounded by element magnitudes, so per-call checks compose),
        a 64-bit key collision, or accumulator cardinality past the budget."""
        mode = str(settings.mesh_fold).lower()
        if mode in ("off", "0", "false") or not settings.use_device:
            return None
        ctl = _mitigate.active()
        if ctl is not None and not ctl.collective_fold_ok():
            # Degrade-in-place: while the mitigation is engaged the
            # collective fold would re-serialize the fleet on the
            # straggler at every window — the host path is exact and
            # free-running.  Deterministic from shared controller state,
            # so every rank declines together (no one-sided collective).
            return None
        if len(entries) != 1 or not isinstance(stage.reducer,
                                               base.AssocFoldReducer):
            return None
        op = stage.reducer.op
        if op.kind not in ("sum", "min", "max"):
            return None
        refs = list(entries[0].all_refs())
        if (mode not in ("on", "1", "true")
                and settings.device_count_for_auto() < 2
                and not any(getattr(r, "is_device", False) for r in refs)):
            # Single device and nothing HBM-resident: the local fold path
            # is cheaper.  With device-resident inputs the mesh fold (D=1
            # degenerates to the plain collective program) IS the consumer
            # that keeps the value lanes from round-tripping through host.
            return None
        import jax

        if not refs:
            return storage.PartitionSet(self.n_partitions, hash_routed=True,
                                        hash_sorted=True), 0, 1
        # Cheap metadata check before touching any (possibly spilled) data.
        if any(getattr(r, "value_dtype", object) == object for r in refs):
            return None

        from .blocks import _concat_cols
        from .ops import devtime
        from .ops.hashing import combine64
        from .parallel import mesh_keyed_fold
        from .parallel.shuffle import (compact_by_chip, mesh_keyed_refold,
                                       part_chips)
        from .parallel.mesh import data_mesh, mesh_size

        mesh = data_mesh()
        x64 = jax.config.jax_enable_x64
        window_budget = max(1 << 20, self.store.budget // 4)
        acc_budget = max(1 << 20, self.store.budget // 4)

        class _HostPath(Exception):
            pass

        # Distinct-key table: u64-sorted hash lanes with the matching keys,
        # kept as GEOMETRIC SEGMENTS (the logarithmic method): each window's
        # new keys append as one sorted segment; equal-size neighbors merge
        # pairwise, so every key participates in O(log W) linear merges —
        # replacing a per-window np.insert whose O(table) rebuild degraded
        # quadratically on high-cardinality folds.  Grows with key
        # cardinality only; replaces the former all-records host concat +
        # sort + Python dict.
        kt = {"segs": [], "n": 0}  # [(u64 sorted, keys)], total entries

        # Folded (h1, h2, v, ok) partials, each on the chip that holds
        # it or spread over the mesh (``ok``: a live-row lane, or the
        # count of a valid prefix), and per chip the partials and lanes
        # it holds.
        partials = []
        held = {}

        def keys_equal(a, b):
            if a.dtype != object and b.dtype != object:
                return bool(np.all(a == b))
            return all(x == y for x, y in zip(a, b))

        def merge_segs(a, b):
            """Allocate-once merge of two disjoint sorted (u, k) segments."""
            ua, ka = a
            ub, kb = b
            n = len(ua) + len(ub)
            tgt = np.searchsorted(ua, ub) + np.arange(len(ub))
            ou = np.empty(n, dtype=np.uint64)
            mask = np.ones(n, dtype=bool)
            mask[tgt] = False
            ou[tgt] = ub
            ou[mask] = ua
            if ka.dtype != kb.dtype:
                allk = _concat_cols([ka, kb])
                ka, kb = allk[:len(ka)], allk[len(ka):]
            ok = np.empty(n, dtype=ka.dtype)
            ok[tgt] = kb
            ok[mask] = ka
            return ou, ok

        def merge_table(keys, h1, h2):
            """Fold the window's (hash -> key) pairs into the segment table,
            verifying equal 64-bit hashes always carry equal keys."""
            u = combine64(h1, h2)
            worder = np.argsort(u, kind="stable")
            su = u[worder]
            sk = np.asarray(keys).take(worder)
            # In-window dedup with the collision check on adjacent dups.
            first = np.empty(len(su), dtype=bool)
            first[0] = True
            np.not_equal(su[1:], su[:-1], out=first[1:])
            dup = np.flatnonzero(~first)
            if len(dup) and not keys_equal(sk.take(dup), sk.take(dup - 1)):
                raise _HostPath  # 64-bit collision
            keep = np.flatnonzero(first)
            su = su[keep]
            sk = sk.take(keep)
            # Cross-segment exists check (every segment is consulted; a key
            # lives in exactly one).
            new_mask = np.ones(len(su), dtype=bool)
            for eu, ek in kt["segs"]:
                pos_c = np.minimum(np.searchsorted(eu, su), len(eu) - 1)
                exists = eu[pos_c] == su
                hit = np.flatnonzero(exists & new_mask)
                if len(hit) and not keys_equal(
                        sk.take(hit), ek.take(pos_c[hit])):
                    raise _HostPath  # cross-window 64-bit collision
                new_mask &= ~exists
            idx = np.flatnonzero(new_mask)
            if len(idx):
                kt["segs"].append((su[idx], sk.take(idx)))
                kt["n"] += len(idx)
                while (len(kt["segs"]) > 1
                       and len(kt["segs"][-2][0])
                       <= 2 * len(kt["segs"][-1][0])):
                    b = kt["segs"].pop()
                    a = kt["segs"].pop()
                    kt["segs"].append(merge_segs(a, b))
            if kt["n"] * 80 > acc_budget:
                raise _HostPath  # extreme cardinality: stream on host

        def table_compact():
            """Merge all segments into the single sorted (u, k) table the
            final hash -> key join consumes."""
            while len(kt["segs"]) > 1:
                b = kt["segs"].pop()
                a = kt["segs"].pop()
                kt["segs"].append(merge_segs(a, b))
            if kt["segs"]:
                return kt["segs"][0]
            return np.empty(0, dtype=np.uint64), np.empty(0, dtype=object)

        # Device-resident accumulation state: partials are the raw padded
        # (h1, h2, v, ok) jax arrays from each window's collective fold —
        # they never round-trip through the host; re-folds concatenate and
        # re-run the program in HBM, and only the final result is fetched.
        # Lane safety across windows is tracked host-side (where the window
        # data still is): the running elementwise abs-sum bounds every
        # partial magnitude, and all windows must share one lane dtype.
        acc = {"abs": 0, "dtype": None, "nonneg": True,
               "lane_max": 2 ** 64}

        def add(part):
            partials.append(part)
            for chip, rows in part_chips(mesh, part):
                n, lanes = held.get(chip, (0, 0))
                held[chip] = (n + 1, lanes + rows)

        def compact():
            # Compaction bounds the padded lanes at the distinct-key
            # count, on each chip: refold outputs are capacity-padded
            # (~1.5x input, dead rows included), so re-feeding them
            # uncompacted grows the accumulated partial geometrically
            # across rounds.
            f = mesh_keyed_refold(mesh, partials, op.kind,
                                  nonneg=acc["nonneg"], store=self.store)
            del partials[:]
            held.clear()
            with devtime.phase("mesh_fold", "collective", "compact"):
                add(compact_by_chip(mesh, f))

        def maybe_compact():
            # Compact by accumulated LANE volume, not partial count, on
            # any one chip: handoff refs are vocabulary-sized (hundreds
            # of tiny partials are cheaper to hold than to re-fold),
            # while the window path's partials are capacity-sized and
            # must not stack past device memory.
            if len(partials) > 1 and any(
                    n >= 256 or lanes >= _REFOLD_LANE_CAP
                    for n, lanes in held.values()):
                compact()

        def flush(win_blocks):
            blk = Block.concat(win_blocks)
            if not len(blk):
                return
            vals = blk.values
            if vals.ndim != 1:
                raise _HostPath  # composite lanes fold on the segment path
            if vals.dtype == np.bool_:
                vals = vals.astype(np.int64)
            if vals.dtype == np.float64 and not x64:
                raise _HostPath
            if vals.dtype.kind in "iu":
                # The lane dtype this window will fold in: int32 with x64
                # off (_lane_safe_values casts), the input dtype otherwise.
                # The running bound must respect the NARROWEST lane used.
                lane_dt = np.dtype(np.int32) if not x64 else vals.dtype
                acc["lane_max"] = min(acc["lane_max"],
                                      int(np.iinfo(lane_dt).max))
                if op.kind == "sum":
                    # Only sums can exceed the element range across windows;
                    # min/max results stay inside the per-window-checked
                    # element range and need no cross-window guard.
                    if x64:
                        # values are unbounded here; a wrapped int64 np-sum
                        # could hide an overflow, so bound with a margined
                        # float64 over-estimate instead.
                        s = float(np.abs(vals.astype(np.float64)).sum())
                        acc["abs"] += s * (1 + 1e-6) + 1
                    else:
                        # per-window lane checks cap |v| at 2^31, so the
                        # int64 window sum (<= 2^58) cannot wrap, and the
                        # running total is an exact Python int
                        acc["abs"] += int(np.abs(
                            vals.astype(np.int64, copy=False)).sum())
                    if acc["abs"] > acc["lane_max"]:
                        raise _HostPath  # cross-window overflow: host exact
                # The scan lowering's -1 sentinel needs SIGNED lanes and
                # nonneg values (mesh_keyed_fold's own gate mirrors this).
                if acc["nonneg"] and (lane_dt.kind != "i" or (
                        len(vals) and int(vals.min()) < 0)):
                    acc["nonneg"] = False
            else:
                acc["nonneg"] = False
            h1, h2 = blk.hashes()
            merge_table(blk.keys, h1, h2)
            try:
                f = mesh_keyed_fold(mesh, h1, h2, vals, op.kind, raw=True,
                                    store=self.store)
            except ValueError:
                raise _HostPath
            if acc["dtype"] is None:
                acc["dtype"] = f[2].dtype
            elif f[2].dtype != acc["dtype"]:
                raise _HostPath  # mixed lane dtypes across windows
            add(f)
            maybe_compact()

        _I32 = 2 ** 31 - 1
        _I64 = 2 ** 63 - 1

        def flush_dev(ref):
            """Queue one HBM-resident block for the collective fold
            without any host lane copy: the device lanes ride straight
            into the refold as a raw partial (``ok`` marks the valid
            prefix — handoff refs may carry pow2-padded lanes); the
            exact-key table merges from the ref's HOST-side metadata
            (keys + hashes kept at registration); overflow/nonneg
            bookkeeping uses the registration-time lane_abs/lane_min
            numbers — the same math flush() runs on host values, sourced
            where the host array last existed.  One deterministic final
            refold replaces the former per-ref fold programs, so compile
            buckets stay bounded regardless of ref count or arrival
            order.  The ref stays on the chip its map job ran on."""
            dv, dh1, dh2 = ref.device_lanes()
            keys, h1, h2 = ref.host_meta()
            lane_dt = np.dtype(dv.dtype)
            if lane_dt.kind in "iu":
                acc["lane_max"] = min(acc["lane_max"],
                                      int(np.iinfo(lane_dt).max))
                if op.kind == "sum":
                    if x64:
                        acc["abs"] += float(ref.lane_abs) * (1 + 1e-6) + 1
                    else:
                        acc["abs"] += int(ref.lane_abs)
                    if acc["abs"] > acc["lane_max"]:
                        raise _HostPath  # cross-window overflow: host exact
                if acc["nonneg"] and (lane_dt.kind != "i"
                                      or ref.lane_min < 0):
                    acc["nonneg"] = False
            else:
                acc["nonneg"] = False
            merge_table(keys, h1, h2)
            if acc["dtype"] is None:
                acc["dtype"] = dv.dtype
            elif dv.dtype != acc["dtype"]:
                raise _HostPath  # mixed lane dtypes across windows
            add((dh1, dh2, dv, len(ref)))
            maybe_compact()

        try:
            win, wbytes = [], 0
            dev_folds = 0
            for ref in refs:
                if getattr(ref, "is_device", False) and len(ref):
                    # HBM-resident map output: fold it where it lives.
                    flush_dev(ref)
                    dev_folds += 1
                    continue
                for w in ref.iter_windows():
                    if not len(w):
                        continue
                    win.append(w)
                    wbytes += w.nbytes()
                    if wbytes >= window_budget:
                        flush(win)
                        win, wbytes = [], 0
            if win:
                flush(win)
            if dev_folds:
                log.info("mesh fold: %d HBM-resident blocks consumed "
                         "on-device", dev_folds)
            if not partials:
                return storage.PartitionSet(self.n_partitions,
                                            hash_routed=True,
                                            hash_sorted=True), 0, 1
            if len(partials) > 1:
                compact()
        except _HostPath:
            log.info("mesh fold: falling back to the host path")
            return None

        # One fetch for the whole reduce: mask the final partial's live
        # rows.  The async refold dispatches materialize here, so this IS
        # the stage's final fold work (the span the host combine path
        # emits at its own final fold).
        with devtime.phase("mesh_fold", "fold", "final-fold"):
            (rh1, rh2, rv, rok), = partials
            if hasattr(rok, "shape"):
                mask = np.asarray(rok) == 1
            else:  # a lone handoff ref: its valid prefix
                mask = np.arange(int(rh1.shape[0])) < rok
            fh1 = np.asarray(rh1)[mask]
            fh2 = np.asarray(rh2)[mask]
            fv = np.asarray(rv)[mask]
            # Vectorized hash -> key join against the compacted table
            # (every output hash entered the table with its window).
            tu, tk = table_compact()
            fu = combine64(fh1, fh2)
            idx = np.minimum(np.searchsorted(tu, fu), len(tu) - 1)
            assert bool(np.all(tu[idx] == fu)), "mesh fold lost a key"
            out_keys = tk.take(idx)

        pin = bool(stage.options.get("memory"))
        pset, nrec = self._emit_keyed_fold(out_keys, fv, fh1, fh2, pin)
        self.mesh_folds += 1
        log.info("mesh fold: %d keys folded across %d devices",
                 nrec, mesh_size(mesh))
        return pset, nrec, 1

    def _code_exchange_batch(self, batch, op):
        """CAMR-style coded aggregation (settings.exchange_coding): fold
        each destination partition's window blocks into ONE partial under
        the stage's associative op BEFORE they cross the mesh — replicated
        map-side fold work traded for strictly fewer shuffle bytes
        (duplicate keys collapse host-side; arXiv 1901.07418).  Exactness
        gate per partition: integer lanes for sums (float summation order
        would drift ulps), any real numeric lane for min/max; ineligible
        or fold-failing partitions ship raw.  Returns (coded batch,
        raw_bytes, coded_bytes)."""
        by_pid = {}
        raw_bytes = 0
        for s, pid, item in batch:
            blk = (item.get() if isinstance(item, storage.BlockRef)
                   else item)
            raw_bytes += blk.nbytes()
            by_pid.setdefault(pid, []).append((s, blk))
        out = []
        coded_bytes = 0
        for pid in sorted(by_pid):
            items = by_pid[pid]
            blocks = [b for _s, b in items]
            seq0 = min(s for s, _b in items)
            merged = blocks[0] if len(blocks) == 1 else Block.concat(
                blocks)
            vals = merged.values
            kinds = "iu" if op.kind == "sum" else "iuf"
            eligible = vals.ndim == 1 and vals.dtype.kind in kinds
            if eligible:
                try:
                    folded = segment.fold_block(merged, op)
                except Exception:  # exactness fallback: ship raw
                    eligible = False
            if eligible:
                coded_bytes += folded.nbytes()
                out.append((seq0, pid, folded))
            else:
                for s, blk in items:
                    coded_bytes += blk.nbytes()
                    out.append((s, pid, blk))
        return out, raw_bytes, coded_bytes

    def _mesh_exchange_entries(self, entries, target=None, reducer=None):
        """The general shuffle on the mesh (the reference's universal
        DefaultShuffler — base.py:416-433 — as a collective): every input
        partition's blocks cross a budget-scheduled ``all_to_all`` byte
        exchange, streamed in windows bounded by the run budget, with
        partition pid landing on device pid % D.  Joins stay co-partitioned
        because both inputs route identically.  ``target`` is the plan
        layer's shuffle choice for this stage (see ``_exchange_mesh_gate``);
        ``reducer`` (the consuming stage's reducer, when there is exactly
        one input) arms the coded-aggregation pre-fold for sum-combinable
        keyed folds under ``settings.exchange_coding``.
        Returns the exchanged PartitionSets (new refs registered against
        the store), or None when the mesh path is disabled or only one
        device is visible."""
        gate = _exchange_mesh_gate(self.store.budget, target)
        if gate is None:
            return None
        mesh, D, window = gate
        from .parallel import exchange as px

        coding_op = None
        if (settings.exchange_coding_enabled() and len(entries) == 1
                and isinstance(reducer, base.AssocFoldReducer)
                and getattr(reducer.op, "kind", None)
                in ("sum", "min", "max")):
            coding_op = reducer.op

        out_entries = []
        ran_exchange = False
        for pset in entries:
            out = storage.PartitionSet(pset.n_partitions)
            batch, batch_bytes = [], 0
            seq = 0

            def flush():
                nonlocal batch, batch_bytes, ran_exchange
                if not batch:
                    return
                coding_info = None
                if coding_op is not None:
                    coded, raw_b, coded_b = self._code_exchange_batch(
                        batch, coding_op)
                    batch = coded
                    coding_info = {"mode": "camr", "raw_bytes": raw_b,
                                   "coded_bytes": coded_b}
                routed = [
                    (s, s % D, pid,
                     item.get() if isinstance(item, storage.BlockRef)
                     else item)
                    for s, pid, item in batch]
                received, moved = px.mesh_shuffle_blocks(
                    mesh, routed, coding=coding_info)
                if coding_info is not None and not (
                        px.last_info or {}).get("skipped"):
                    # Counted only when the window actually crossed the
                    # mesh: a mitigation-skipped window shuffled zero
                    # bytes, so claiming coded "savings" there would
                    # double-count what windows_skipped already reports.
                    self.coded_exchange["windows"] += 1
                    self.coded_exchange["raw_bytes"] += (
                        coding_info["raw_bytes"])
                    self.coded_exchange["coded_bytes"] += (
                        coding_info["coded_bytes"])
                for pid, blk in received:
                    out.add(pid, self.store.register(blk))
                self.mesh_exchange_bytes += moved
                if px.last_info is not None:
                    self.mesh_exchange_steps += px.last_info["steps"]
                    self.mesh_exchange_peak_inflight = max(
                        self.mesh_exchange_peak_inflight,
                        px.last_info["peak_inflight_bytes"])
                ran_exchange = True
                batch, batch_bytes = [], 0

            def add(pid, item, nbytes):
                nonlocal batch_bytes, seq
                batch.append((seq, pid, item))
                seq += 1
                batch_bytes += nbytes
                if batch_bytes >= window:
                    flush()

            for pid in sorted(pset.parts):
                for ref in pset.parts[pid]:
                    if ref.nbytes <= window:
                        add(pid, ref, ref.nbytes)
                        continue
                    # An over-window block would amplify to a D*D-row buffer
                    # of its own pow2 size; stream it in bounded pieces
                    # instead (consecutive slices of a sorted run stay
                    # sorted runs, and seq order keeps arrival order).
                    piece, pbytes = [], 0
                    for w in ref.iter_windows():
                        piece.append(w)
                        pbytes += w.nbytes()
                        if pbytes >= window:
                            add(pid, Block.concat(piece), pbytes)
                            piece, pbytes = [], 0
                    if piece:
                        add(pid, Block.concat(piece), pbytes)
            flush()
            out_entries.append(out)
        if ran_exchange:
            self.mesh_exchanges += 1
        return out_entries

    def _tiny_assoc_reduce(self, stage, entries):
        """Small-stage fast path for associative folds: fold EVERY partition
        in one vectorized pass over the concatenated refs, then re-split by
        the same hash % P.  Partition identity of each key is unchanged
        (same hash, same P); only the per-partition numpy fixed costs —
        which dominate when partitions hold a few hundred records — are
        collapsed.  Output shape matches the per-partition reducer exactly:
        (k, (k, acc)) records, unordered within a partition (the same
        contract the mesh fold path already ships)."""
        if len(entries) != 1 or not isinstance(stage.reducer,
                                               base.AssocFoldReducer):
            return None
        refs = list(entries[0].all_refs())
        P = self.n_partitions
        pin = bool(stage.options.get("memory"))
        if not refs:
            return storage.PartitionSet(P, hash_routed=True,
                                        hash_sorted=True), 0, 1
        # The one-pass fold materializes every ref at once, so it must stay
        # inside the streaming memory discipline, not just the tiny-stage
        # cutoff.
        limit = settings.small_stage_bytes
        thr = settings.streaming_reduce_threshold
        if thr is None:
            thr = self.store.budget
        # Streamed-edge inputs gate on STAGED bytes: early folds shrink
        # the refs, but this fast path emits a different (hash-order)
        # layout than the per-partition jobs, so the branch decision must
        # match what the staged run would have taken byte-for-byte.
        staged_extra = sum(getattr(entries[0], "pipeline_fold_delta",
                                   {}).values())
        if staged_extra + sum(getattr(r, 'total_bytes', r.nbytes)
                              for r in refs) > min(limit, thr):
            return None
        merged = Block.concat([r.get() for r in refs])
        if not len(merged):
            return storage.PartitionSet(P, hash_routed=True,
                                        hash_sorted=True), 0, 1
        folded = segment.fold_sorted(
            segment.sort_and_group(merged), stage.reducer.op)
        h1, h2 = folded.hashes()
        pset, nrec = self._emit_keyed_fold(folded.keys, folded.values,
                                           h1, h2, pin)
        return pset, nrec, 1

    def _emit_keyed_fold(self, keys, vals, h1, h2, pin):
        """Register a keyed fold result as a stage-output PartitionSet in
        the reduce-output contract: (k, (k, acc)) records (KeyedReduce
        shape), np.generic values unwrapped to Python scalars, split by the
        engine hash % P.  Shared by the mesh fold and tiny-fold fast paths
        so the contract lives in exactly one place."""
        from .blocks import pylist

        P = self.n_partitions
        n = len(keys)
        kl = pylist(keys) if isinstance(keys, np.ndarray) else list(keys)
        vl = pylist(vals) if isinstance(vals, np.ndarray) else list(vals)
        vcol = np.empty(n, dtype=object)
        for i in range(n):
            vcol[i] = (kl[i], vl[i])
        out_blk = Block(keys, vcol, h1, h2)
        # Hash-routed by construction (split below); the sub-blocks keep the
        # fold's output order, which is NOT a (h1, h2)-sorted run — consumers
        # that need sorted runs (a following reduce) re-establish them in the
        # copy stage the alias gate forces.
        pset = storage.PartitionSet(P, hash_routed=True)
        nrec = 0
        for pid, sub in out_blk.split_by_partition(P).items():
            nrec += len(sub)
            pset.add(pid, self.store.register(sub, pin=pin))
        return pset, nrec

    def run_reduce(self, stage_id, stage, env):
        entries = [env[s] for s in stage.inputs]
        for e in entries:
            assert isinstance(e, storage.PartitionSet), (
                "reduce inputs must be materialized partitions; the DSL "
                "checkpoints before grouping")
        fast = self._mesh_reduce(stage, entries)
        if fast is not None:
            return fast
        fast = self._tiny_assoc_reduce(stage, entries)
        if fast is not None:
            return fast
        exchanged = self._mesh_exchange_entries(
            entries, target=self._shuffle_targets.get(stage_id),
            reducer=stage.reducer)
        if exchanged is not None:
            entries = exchanged
        P = self.n_partitions
        pin = bool(stage.options.get("memory"))

        threshold = settings.streaming_reduce_threshold
        if threshold is None:
            threshold = self.store.budget
        # The streaming merge yields groups in hash order, not key order —
        # safe for per-group reducers (Reduce/KeyedReduce/AssocFoldReducer,
        # where each group is independent), but Stream/BlockReducers observe
        # the group sequence directly, so they always get the key-ordered
        # materialized view.
        order_insensitive = isinstance(
            stage.reducer, (base.Reduce, base.AssocFoldReducer))

        joinable = isinstance(
            stage.reducer, (base.KeyedInnerJoin, base.KeyedLeftJoin,
                            base.KeyedOuterJoin))

        def _streaming_assoc_fold(refs, reducer):
            """Over-budget associative fold, vectorized: fold each spill
            window as it streams and re-compact partials — the working set is
            one accumulator of *distinct keys*, not the partition's records
            (the reduce-side mirror of the map-side _PARTIAL_FANIN combine).
            Returns None (caller falls back to the per-record stream) if the
            accumulator itself outgrows the threshold (extreme cardinality).
            """
            op = reducer.op
            partials = []

            def compact():
                merged = segment.fold_block(Block.concat(partials), op)
                del partials[:]
                partials.append(merged)
                return merged.nbytes()

            for ref in refs:
                for window in ref.iter_windows():
                    if not len(window):
                        continue
                    partials.append(segment.fold_block(window, op))
                    if len(partials) >= _PARTIAL_FANIN:
                        if compact() > threshold:
                            return None
            if not partials:
                return iter(())
            self.streamed_assoc_folds += 1
            final = segment.fold_sorted(
                segment.sort_and_group(Block.concat(partials)), op)
            gkeys = final.keys
            try:
                order = np.argsort(gkeys, kind="stable")
            except TypeError:
                order = np.arange(len(final))

            def emit():
                vals = final.values
                for gi in order:
                    k = gkeys[gi]
                    v = vals[gi]
                    k = k.item() if isinstance(k, np.generic) else k
                    v = v.item() if isinstance(v, np.generic) else v
                    yield k, (k, v)

            return emit()

        def job(pid):
            if joinable and len(entries) == 2:
                sizes = [sum(r.total_bytes for r in pset.refs(pid))
                         for pset in entries]
                if sum(sizes) > threshold:
                    # Over-budget join partition: hash-ordered streaming
                    # merge join — memory bound is the largest single
                    # join-key group, not the partition.
                    log.info(
                        "partition %d join (%.1f MB) exceeds the streaming "
                        "threshold: merging by hash order", pid,
                        sum(sizes) / 1e6)
                    lview = base.StreamingGroupedView(entries[0].refs(pid))
                    rview = base.StreamingGroupedView(entries[1].refs(pid))
                    reducer = _clone_op(stage.reducer)
                    builder = BlockBuilder(settings.batch_size)
                    refs_out = []
                    for k, v in base.streaming_merge_join(lview, rview,
                                                          reducer):
                        blk = builder.add(k, v)
                        if blk is not None:
                            refs_out.append(
                                self.store.register(blk, pin=pin))
                    blk = builder.flush()
                    if blk is not None:
                        refs_out.append(self.store.register(blk, pin=pin))
                    return pid, refs_out
            record_stream = None
            if len(entries) == 1:
                prefs = entries[0].refs(pid)
                part_bytes = sum(r.total_bytes for r in prefs)
                if (part_bytes > threshold
                        and isinstance(stage.reducer, base.AssocFoldReducer)
                        and stage.reducer.op.kind is not None):
                    record_stream = _streaming_assoc_fold(
                        prefs, stage.reducer)

            if record_stream is None:
                views = []
                for pset in entries:
                    refs = pset.refs(pid)
                    part_bytes = sum(r.total_bytes for r in refs)
                    if (len(entries) == 1 and order_insensitive
                            and part_bytes > threshold):
                        # Out-of-core partition: stream a k-way merge over
                        # the hash-sorted runs — one window per run resident
                        # — instead of materializing the whole partition.
                        # (Over-budget joins were handled above; assoc folds
                        # with recognized ops took the vectorized accumulator
                        # unless cardinality blew it; Stream/BlockReducers
                        # still materialize.)
                        log.info(
                            "partition %d (%.1f MB) exceeds the streaming "
                            "threshold: groups will stream in hash order",
                            pid, part_bytes / 1e6)
                        views.append(base.StreamingGroupedView(refs))
                    else:
                        views.append(base.GroupedView(
                            [ref.get() for ref in refs]))
                reducer = _clone_op(stage.reducer)
                record_stream = reducer.reduce(*views)

            builder = BlockBuilder(settings.batch_size)
            refs = []
            prof = _profile.active()
            if prof is None:
                # The profiler-off hot loop stays increment-free (the
                # one-None-check-per-job contract).
                for k, v in record_stream:
                    blk = builder.add(k, v)
                    if blk is not None:
                        refs.append(self.store.register(blk, pin=pin))
            else:
                # Whole-stream attribution (a reducer doesn't decompose
                # per op): grouping + the user's reduce + re-register.
                t0p = time.perf_counter()
                nrec_out = 0
                for k, v in record_stream:
                    nrec_out += 1
                    blk = builder.add(k, v)
                    if blk is not None:
                        refs.append(self.store.register(blk, pin=pin))
                prof.op_add(
                    "reduce:" + _profile.op_label(stage.reducer),
                    time.perf_counter() - t0p, records=nrec_out)
            blk = builder.flush()
            if blk is not None:
                refs.append(self.store.register(blk, pin=pin))
            return pid, refs

        n_reducers = stage.options.get("n_reducers", self.n_reducers)
        try:
            results = self._pool_run(job, list(range(P)), n_reducers,
                                     label="reduce",
                                     speculative=self._speculation_ok(stage))
        finally:
            if exchanged is not None:
                # The exchanged copies are intermediates private to this
                # reduce; the originals in env still own the stage output
                # lifecycle.  finally: a reducer exception must not leak a
                # duplicate of the stage input against the budget.
                for e in exchanged:
                    e.delete(self.store)

        pset = storage.PartitionSet(P)
        nrec = 0
        for pid, refs in results:
            for ref in refs:
                nrec += len(ref)
                pset.add(pid, ref)
        return pset, nrec, P

    # -- sink --------------------------------------------------------------
    def run_sink(self, stage_id, stage, env):
        entries = [env[s] for s in stage.inputs]
        chunks = self._as_chunks(entries[0])
        # Same tiny-input collapse as run_map: sink chunking (one part file
        # per chunk) is mechanical, and the sinker is always a fused record
        # stream (dampr.py sink()).
        if (len(chunks) > 1
                and isinstance(entries[0], storage.PartitionSet)
                and type(stage.sinker) in (base.Map, base.ComposedMapper)):
            refs = list(entries[0].all_refs())
            if sum(getattr(r, 'total_bytes', r.nbytes)
                   for r in refs) <= settings.small_stage_bytes:
                chunks = [BlockDataset(refs)]
        os.makedirs(stage.path, exist_ok=True)

        def job(args):
            i, chunk = args
            mapper = _clone_op(stage.sinker)
            part = os.path.join(stage.path, "part-{}".format(i))
            n = 0
            prof = _profile.active()
            t0p = time.perf_counter() if prof is not None else 0.0
            with open(part, "w", encoding="utf-8") as f:
                for _k, v in mapper.map(chunk):
                    f.write("{}\n".format(v))
                    n += 1
            if prof is not None:
                # Sink chains don't decompose per op (fused generators
                # interleave with the writes); one whole-stream label
                # keeps the stage's coverage honest.
                prof.op_add("sink:" + _profile.op_label(mapper),
                            time.perf_counter() - t0p, records=n)
            return part, n

        n_maps = stage.options.get("n_maps", self.n_maps)
        results = self._pool_run(job, list(enumerate(chunks)), n_maps,
                                 label="sink", speculative=False)
        paths = [p for p, _ in results]
        nrec = sum(n for _, n in results)
        return _SinkOutput(paths), nrec, len(chunks)

    def _reduce_consumes(self, output, _seen=None):
        """Does a GReduce consume ``output`` — directly, or through
        identity checkpoint stages (which alias or copy it forward
        unchanged)?  Run-mode planning (sorted runs vs hash fan-out) and
        the alias provenance gate share this transitive view so they
        cannot disagree about what a downstream reduce will need."""
        seen = _seen if _seen is not None else set()
        if output in seen:
            return False
        seen.add(output)
        for s in self.graph.stages:
            if output not in s.inputs:
                continue
            if isinstance(s, GReduce):
                return True
            if (isinstance(s, GMap)
                    and type(s.mapper) is base.Map
                    and s.mapper.mapper is base._identity
                    and s.combiner is None
                    and "binop" not in s.options
                    and self._reduce_consumes(s.output, seen)):
                return True
        return False

    def _alias_provenance_ok(self, stage, src):
        """May an identity checkpoint alias ``src`` instead of running the
        copy stage?  The copy stage it elides would hash-route every record
        (split_by_partition) and register hash-sorted runs — invariants a
        consuming GReduce depends on for partition-local grouping and the
        over-budget streaming merge.  So the alias stands only when no
        reduce consumes the output (directly or through further identity
        checkpoints), or the input already carries both invariants by
        construction (map-stage outputs).  Reduce outputs are registered
        under the reduce job's pid with whatever keys the reducer emitted
        — e.g. ``X.partition_reduce(f).partition_reduce(g)`` aliasing f's
        output would leave g grouping each key only within f's job
        partitions: silently wrong results (ADVICE round 5)."""
        if not self._reduce_consumes(stage.output):
            return True
        return src.hash_routed and src.hash_sorted

    # -- main walk ---------------------------------------------------------
    def _register_gauges(self):
        """Install the load-bearing pull gauges once per run: the hot
        paths whose state they expose pay nothing — the background
        sampler evaluates these callbacks on its cadence."""
        from .ops import devtime

        m = self.metrics
        sto = self.store
        m.register_gauge("store.resident_bytes",
                         lambda: sto._resident_bytes)
        m.register_gauge(
            "store.budget_occupancy",
            lambda: (sto._resident_bytes / sto.budget) if sto.budget
            else 0.0)
        m.register_gauge("store.overlap_bytes", lambda: sto._overlap_bytes)
        m.register_gauge("store.hbm_bytes", lambda: sto._dev_bytes)
        m.register_gauge("store.spilled_bytes", lambda: sto.spilled_bytes)

        def _writer(attr):
            w = sto._writer
            return 0 if w is None else getattr(w, attr)

        m.register_gauge("writer.queue_depth",
                         lambda: _writer("_outstanding"))
        m.register_gauge("writer.inflight_bytes",
                         lambda: _writer("inflight_bytes"))
        m.register_gauge("overlap.live_slots", devtime.live_slots)
        m.register_gauge("overlap.stalled_slots", devtime.stalled_slots)
        m.register_gauge(
            "run.active_jobs",
            lambda: m.counters.get("run.jobs_started", 0)
            - m.counters.get("run.jobs_done", 0))

    def _start_obs(self):
        """Run-scoped observability setup: tracer (settings.trace),
        flight recorder (tracing OR metrics on), metrics registry +
        sampler (effective_metrics_interval_ms > 0), progress reporter
        (settings.progress).  Returns the flight recorder (the failure
        path flushes it)."""
        from .obs import flightrec as _flightrec

        interval = settings.effective_metrics_interval_ms()
        rec = None
        if settings.trace or interval > 0:
            # A crashdump describes the LATEST run under this name: a
            # stale one from an earlier failure must not keep failing
            # dampr-tpu-stats after the rerun succeeds.
            _flightrec.clear_stale(self.name)
        if settings.flight_recorder_events > 0 and (settings.trace
                                                    or interval > 0):
            rec = _flightrec.FlightRecorder(
                self.name, settings.flight_recorder_events)
            self.flightrec = rec
            _flightrec.start(rec)
        lvl = settings.effective_log_level()
        if lvl or rec is not None:
            # Structured log stream: on-disk events.jsonl when a level is
            # in force (explicit DAMPR_TPU_LOG, or the traced-run "info"
            # default), recorder-only otherwise — an unstreamed metered
            # run still gets a WARN+ tail in its crashdump.  Starts
            # before the remaining obs pieces so THEIR warnings (port
            # fallback, bind failure) land as coded events too.
            from .parallel.mesh import rank_info

            path = None
            if lvl and settings.log_events_max > 0:
                from .obs import export as _export

                tdir = _export.run_trace_dir(self.name)
                os.makedirs(tdir, exist_ok=True)
                path = os.path.join(tdir, _obslog.FILE)
            self.logstream = _obslog.LogStream(
                self.name, rank=rank_info()[0], level=lvl or "warn",
                path=path, recorder=rec)
            _obslog.start(self.logstream)
            _obslog.info("run-start", "run %s started", self.name,
                         partitions=getattr(self, "n_partitions", None))
        if settings.trace:
            # Run-scoped engine timeline.  The tracer is process-global
            # while active (instrumentation sites are free functions);
            # concurrent traced runs in one process would interleave spans
            # into the innermost tracer — run-level metrics stay exact
            # regardless (they come from this runner's own counters).
            self.tracer = _trace.Tracer(self.name)
            self.tracer.recorder = rec
            _trace.start(self.tracer)
        if settings.profile:
            # Per-operator attribution (obs.profile): passive — no
            # thread; hot sites hoist the None-check to one per job.
            self.profiler = _profile.Profiler(self.name)
            _profile.start(self.profiler)
        if settings.mitigate_enabled():
            # Straggler mitigation controller: live skew -> action.
            # Every rank of a process group builds one and feeds it the
            # same shared observations, so collective decisions agree.
            self._mitigation = _mitigate.MitigationController(self.name)
            _mitigate.start(self._mitigation)
        if interval > 0:
            from .obs.metrics import Metrics
            from .obs.sampler import Sampler

            self.metrics = Metrics(self.name)
            if self.tracer is not None:
                # One clock: counter events and span events share the
                # tracer's epoch inside trace.json.
                self.metrics.epoch = self.tracer.epoch
            self._register_gauges()
            _metrics.start(self.metrics)
            self._sampler = Sampler(self.metrics, interval, recorder=rec)
            self._sampler.start()
            if settings.progress:
                from .obs.progress import ProgressReporter

                self._progress = ProgressReporter(
                    self.metrics, lambda: dict(self._status),
                    settings.progress_interval_ms)
                self._progress.start()
            if settings.metrics_port > 0:
                # Live metrics endpoint: per-rank /metrics + /healthz on
                # metrics_port + process_id (co-located ranks never
                # collide).  Best-effort — a busy port degrades the
                # endpoint, never the run.
                from .obs import serve as _serve

                self._metrics_server = _serve.start_server(
                    settings.metrics_port, run_name=self.name)
        # Route-matrix epoch: the exchange module's counters are
        # process-cumulative; remember where they stood so finalize can
        # attribute only this run's bytes.
        try:
            from .parallel import exchange as px

            self._exchange_snapshot = (
                dict(px.sent_bytes_per_device),
                dict(px.received_bytes_per_device),
                dict(px.pair_bytes_per_route),
                {"codec_raw": px.codec_raw_bytes,
                 "codec_wire": px.codec_wire_bytes,
                 "pack_seconds": px.pack_seconds_total,
                 "pack_hidden": px.pack_hidden_seconds_total})
        except Exception:
            self._exchange_snapshot = None
        return rec

    def _stop_obs(self):
        from .obs import flightrec as _flightrec

        if self._progress is not None:
            self._progress.stop()
        if self._sampler is not None:
            self._sampler.stop()
        if self.metrics is not None:
            _metrics.stop(self.metrics)
        if self.tracer is not None:
            _trace.stop(self.tracer)
        if self.profiler is not None:
            _profile.stop(self.profiler)
        if self.flightrec is not None:
            _flightrec.stop(self.flightrec)
        if self._mitigation is not None:
            _mitigate.stop(self._mitigation)
        if self._metrics_server is not None:
            srv = self._metrics_server
            if srv.port is not None:
                # Survives the teardown: finalize records the LIVE port
                # (fallback-shifted or not) in stats()["endpoint"].
                self._endpoint_info = {
                    "port": srv.port,
                    "requested": (srv.base_port + srv.rank
                                  if srv.base_port > 0 else srv.base_port),
                    "fallback": srv.fallback,
                }
            srv.stop()
            self._metrics_server = None

    def _install_sigterm(self):
        """Raise-on-SIGTERM while a run is in flight, so an external kill
        walks the same BaseException path as KeyboardInterrupt — flight
        recorder flush, spill-writer abort, nonzero exit — instead of
        dying with no crash artifact.  Only from the main thread (signal
        API constraint) and only when no application handler is already
        installed; returns a restore closure."""
        import signal

        if threading.current_thread() is not threading.main_thread():
            return None
        try:
            prev = signal.getsignal(signal.SIGTERM)
            if prev is not signal.SIG_DFL:
                # The application owns SIGTERM (a Python handler, SIG_IGN,
                # or — getsignal() returning None — a handler installed
                # by non-Python code): never clobber it.
                return None

            def _on_term(signum, frame):
                raise SystemExit(143)  # 128 + SIGTERM, shell convention

            signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, OSError):
            return None
        return lambda: signal.signal(signal.SIGTERM, prev)

    def run(self, outputs, cleanup=True):
        from . import plan as _plan
        from .ops import devtime

        # Optimize the stage list for the requested outputs (no-op when
        # the DSL already applied a plan, or settings.optimize is off —
        # the report records either way).  Before obs setup: stage counts
        # and resume fingerprints must see the final graph.
        _plan.apply_to_runner(self, outputs)
        # Pre-flight dispatch check (analyze.validate): on a multi-rank
        # deployment an unpicklable UDF capture WILL fail at a process
        # boundary (checkpoint manifests, quarantine audit lines, the
        # exchange's pickled lanes) — fail here with a diagnostic naming
        # the stage, the UDF, and the closure variable instead of a raw
        # PicklingError traceback from deep inside the dispatch.
        if settings.analyze:
            from .parallel.mesh import rank_info

            nproc = rank_info()[1]
            if nproc > 1:
                from .analyze import validate as _av

                _av.preflight_dispatch_check(self.graph, nproc)
        # Fault plan (settings.faults): a fresh per-run schedule so chaos
        # runs replay identically; the counter epoch scopes the
        # stats()["faults"] section to THIS run.
        _faults.configure_for_run()
        self._fault_snapshot = _faults.counters_snapshot()
        _faults.set_context(run=self.name)
        restore_sigterm = self._install_sigterm()
        wall_start = time.time()
        epoch = devtime.epoch()
        rec = self._start_obs()
        try:
            if settings.profile_dir:
                import jax

                with jax.profiler.trace(settings.profile_dir):
                    return self._run(outputs, cleanup)
            return self._run(outputs, cleanup)
        except BaseException as e:
            # The flight recorder's whole reason to exist: a dying run —
            # stage exception, KeyboardInterrupt, SIGTERM-raised exit —
            # leaves a bounded timeline tail with the last gauge samples
            # (writer-pool queue state included) instead of nothing.
            self._run_failed = True
            if self.logstream is not None:
                # Terminal structured record BEFORE the crashdump flush,
                # so the dump's log tail names the death.  Direct emit
                # (not module error()): the exception is re-raised — a
                # duplicate stdlib error line here would be noise.
                self.logstream.emit(
                    "error", "run-failed",
                    "run {} failed: {}: {}".format(
                        self.name, type(e).__name__, str(e)[:500]),
                    data={"exception": type(e).__name__})
            if rec is not None:
                if self._sampler is not None:
                    # One last snapshot so the dump's final samples show
                    # the state at death, not the previous cadence tick.
                    self._sampler.stop()
                rec.flush("run-failed", e)
            raise
        finally:
            if restore_sigterm is not None:
                try:
                    restore_sigterm()
                except (ValueError, OSError):
                    pass
            _faults.set_context(run=None, stage=None)
            self._stop_obs()
            try:
                # Built on failure too: a partial timeline + stage stats
                # is exactly what a crashed run's postmortem needs.
                with _trace.span("run", "finalize"):
                    self._finalize_obs(wall_start, time.time() - wall_start,
                                       devtime.delta(epoch))
            except Exception:
                log.warning("stats/trace finalize failed", exc_info=True)
            finally:
                # The structured stream outlives _stop_obs so finalize
                # can stamp run-finish; close it last, no matter what.
                if self.logstream is not None:
                    _obslog.stop(self.logstream)
                    self.logstream = None

    def _exchange_deltas(self):
        """THIS run's per-device sent/received bytes and (src, dst)
        device-route matrix: the exchange module's cumulative counters
        minus the snapshot taken at run start.  None when nothing moved
        (the section stays compact for host-only runs)."""
        if self._exchange_snapshot is None:
            return None
        try:
            from .parallel import exchange as px
        except Exception:
            return None
        sent0, recv0, pair0, sc0 = self._exchange_snapshot

        def delta(cur, base):
            out = {}
            for k, v in cur.items():
                d = v - base.get(k, 0)
                if d > 0:
                    out[k] = d
            return out

        sent = delta(px.sent_bytes_per_device, sent0)
        recv = delta(px.received_bytes_per_device, recv0)
        pair = delta(px.pair_bytes_per_route, pair0)
        if not (sent or recv or pair):
            return None
        section = {
            "sent_per_device": {str(k): v for k, v in sorted(sent.items())},
            "received_per_device": {str(k): v
                                    for k, v in sorted(recv.items())},
            # JSON-safe route triples [src_device, dst_device, bytes]
            "routes": [[s, d, n] for (s, d), n in sorted(pair.items())],
        }
        raw = px.codec_raw_bytes - sc0["codec_raw"]
        wire = px.codec_wire_bytes - sc0["codec_wire"]
        if raw > 0:
            # Per-route payload codec evidence (settings.exchange_codec):
            # pre-compression bytes vs wire bytes this run.
            section["codec"] = {
                "raw_bytes": raw, "wire_bytes": wire,
                "savings_fraction": round(1.0 - wire / float(raw), 4)}
        packed = px.pack_seconds_total - sc0["pack_seconds"]
        hidden = px.pack_hidden_seconds_total - sc0["pack_hidden"]
        if packed > 1e-9:
            # Double-buffered schedule evidence: how much of the host
            # pack time hid behind in-flight collectives this run.
            section["overlap"] = {
                "pack_seconds": round(packed, 4),
                "hidden_seconds": round(hidden, 4),
                "hidden_fraction": round(hidden / packed, 4)}
        return section

    def _faults_section(self):
        """The per-run ``stats()["faults"]`` payload: this run's share of
        the process-cumulative retry/injection counters, plus quarantine
        and backoff totals."""
        injected, io_retries, io_backoff = _faults.counters_delta(
            self._fault_snapshot)
        q = self._quarantine
        plan = _faults.active()
        section = {
            "enabled": plan is not None,
            "job_retries": self.retries_total,
            "io_retries": dict(io_retries),
            "retries": self.retries_total + sum(io_retries.values()),
            # Job-loop backoff plus the IO layer's in-place retry sleeps
            # — an IO-only retry storm must show its cost here.
            "backoff_seconds": round(self._backoff_seconds + io_backoff, 4),
            "quarantined": q.count if q is not None else 0,
            "max_quarantined": settings.max_quarantined,
        }
        if q is not None and q.count:
            section["quarantine_file"] = q.path
        if plan is not None:
            section["plan"] = plan.spec
            section["injected"] = dict(injected)
        return section

    def _pipeline_section(self):
        """The per-run ``stats()["pipeline"]`` payload: plan-time edge
        decisions (from the plan report) plus the runtime folder/chain
        counters.  overlap_fraction is the share of streamed-consumer
        seconds that ran WHILE the producing stage's pool was still
        busy — the wall-clock the pipelining actually hid."""
        ps = self._pipeline_stats
        rep = ((self.plan_report or {}).get("pipeline") or {})
        fold_s = ps["fold_seconds"]
        return {
            "enabled": settings.pipeline_enabled(),
            "edges_streamed": rep.get("streamed", 0),
            "edges_barrier": rep.get("barriers", 0),
            "executed": ps["executed"],
            "degraded": ps["degraded"],
            "published": ps["published"],
            "early_folded_blocks": ps["early_folded_blocks"],
            "bytes_in": ps["bytes_in"],
            "bytes_out": ps["bytes_out"],
            "fold_seconds": round(fold_s, 4),
            "overlap_seconds": round(ps["overlap_seconds"], 4),
            "overlap_fraction": (round(ps["overlap_seconds"] / fold_s, 4)
                                 if fold_s > 1e-9 else 0.0),
            "stall_seconds": round(ps["stall_seconds"], 4),
            "queue_peak_bytes": ps["queue_peak_bytes"],
            "queue_depth_series": [[sid, round(t, 4), b]
                                   for sid, t, b
                                   in ps["queue_depth_series"]],
        }

    def _finalize_obs(self, wall_start, wall, dev):
        """Build the per-run summary (the stats.json payload) and, when
        tracing, persist trace.json + stats.json under the run's trace
        directory.  The summary is always built — it is how ``StageStats``
        reaches users (ValueEmitter.stats()); the files are written only
        for traced runs so untraced test/tool runs leave no litter."""
        from .obs import export as _export

        sto = self.store
        stages = [s.as_dict() for s in self.stats]
        summary = {
            "schema": _export.STATS_SCHEMA,
            "run": self.name,
            # Rank identity on every artifact: which process of how many
            # produced this summary (plus the clock-handshake anchor when
            # the process group ran one) — the key obs.fleet merges on.
            "process": _export.process_section(),
            "started_at": round(wall_start, 3),
            "wall_seconds": round(wall, 4),
            "n_partitions": self.n_partitions,
            "stages": stages,
            "totals": {
                "records_out": sum(s["records_out"] for s in stages),
                "bytes_out": sum(s["bytes_out"] for s in stages),
                "spill_bytes": sum(s["spill_bytes"] for s in stages),
            },
            "devtime": {k: round(v, 4) for k, v in dev.items()},
            "overlap": {
                "windows": settings.overlap_windows,
                "stall_fraction": (round(dev.get("codec_wait", 0.0) / wall,
                                         4) if wall > 0 else 0.0),
                "peak_bytes": sto.overlap_peak_bytes,
            },
            # Spill I/O shape (dampr_tpu.io): post-codec disk bandwidth on
            # both sides plus the fold-side stall on writer backpressure /
            # not-yet-prefetched frames — the numbers the async spill
            # subsystem moves (seconds are thread-seconds on the writer/
            # reader pools; io_wait_fraction is against run wall time).
            "io": {
                "spill_write_bytes": sto.spill_disk_bytes,
                "spill_write_seconds": round(sto.spill_write_seconds, 4),
                "spill_write_mbps": (
                    round(sto.spill_disk_bytes / 1e6
                          / sto.spill_write_seconds, 2)
                    if sto.spill_write_seconds > 1e-9 else 0.0),
                "spill_read_bytes": sto.spill_read_bytes,
                "spill_read_seconds": round(sto.spill_read_seconds, 4),
                "spill_read_mbps": (
                    round(sto.spill_read_bytes / 1e6
                          / sto.spill_read_seconds, 2)
                    if sto.spill_read_seconds > 1e-9 else 0.0),
                "io_wait_seconds": round(sto.io_wait_seconds, 4),
                "io_wait_fraction": (round(sto.io_wait_seconds / wall, 4)
                                     if wall > 0 else 0.0),
                # fold-side only (writer backpressure): the stall the
                # background writer pool exists to eliminate; read-side
                # prefetch waits are the difference to the totals above.
                "io_wait_write_seconds": round(sto.io_wait_write_seconds, 4),
                "io_wait_write_fraction": (
                    round(sto.io_wait_write_seconds / wall, 4)
                    if wall > 0 else 0.0),
                "writer_threads": settings.spill_write_threads,
                "read_prefetch": settings.spill_read_prefetch,
                "inflight_peak_bytes": sto.spill_inflight_peak_bytes,
                "writer_queue_peak": sto.spill_queue_peak,
            },
            "store": {
                "budget": sto.budget,
                "spill_count": sto.spill_count,
                "spilled_bytes": sto.spilled_bytes,
                "merge_gens": sto.merge_gens,
                "merge_gen_bytes": sto.merge_gen_bytes,
                "h2d_bytes": sto.h2d_bytes,
                "d2h_bytes": sto.d2h_bytes,
                "hbm_offloads": sto.hbm_offloads,
                "hbm_peak_bytes": sto.hbm_peak_bytes,
                "overlap_peak_bytes": sto.overlap_peak_bytes,
            },
            "mesh": {
                "folds": self.mesh_folds,
                "exchanges": self.mesh_exchanges,
                "exchange_bytes": self.mesh_exchange_bytes,
                # The chunked-collective shape of this run's exchanges:
                # schedule steps executed, the modeled per-step in-flight
                # high-water mark (parallel.replan.step_inflight_bytes),
                # and the budget it was planned under.  mesh_stages is
                # how many redistribution stages the plan routed here.
                "exchange": {
                    "bytes": self.mesh_exchange_bytes,
                    "steps": self.mesh_exchange_steps,
                    "peak_inflight_bytes": self.mesh_exchange_peak_inflight,
                    "hbm_budget": settings.exchange_hbm_budget,
                    "mesh_stages": ((self.plan_report or {}).get("shuffle")
                                    or {}).get("mesh_stages", 0),
                },
            },
        }
        ex_delta = self._exchange_deltas()
        if ex_delta is not None:
            summary["mesh"]["exchange"].update(ex_delta)
        if self.coded_exchange["windows"]:
            # Coded-aggregation evidence: what the CAMR pre-fold traded
            # (replicated map-side fold work) for (shuffle bytes).
            ce = dict(self.coded_exchange)
            ce["mode"] = str(settings.exchange_coding)
            if ce["raw_bytes"]:
                ce["savings_fraction"] = round(
                    1.0 - ce["coded_bytes"] / float(ce["raw_bytes"]), 4)
            summary["mesh"]["exchange"]["coding"] = ce
        summary.update({
            # Device execution: run-wide device counters — device_fraction
            # is thread-seconds inside ANY jitted kernel (lowered programs,
            # segment folds, the hash lexsort, mesh collectives) over wall,
            # and h2d/d2h aggregate the lowered-program feed/drain WITH the
            # HBM tier's puts/fetches.  device_stages is the
            # lowering-specific signal: how many stages the plan placed on
            # device this run.
            "device": {
                "device_fraction": (round(dev.get("device", 0.0) / wall, 4)
                                    if wall > 0 else 0.0),
                "device_seconds": round(dev.get("device", 0.0), 4),
                "h2d_bytes": sto.h2d_bytes,
                "d2h_bytes": sto.d2h_bytes,
                "device_stages": (self.plan_report or {}).get(
                    "device_stages", 0),
                "lowered": bool(((self.plan_report or {}).get("lowering")
                                 or {}).get("enabled")),
                # Cross-stage handoff evidence: device bytes registered
                # without a host round-trip, drain bytes the table
                # programs never fetched, edges the plan marked
                # handoff="device", and runtime degrades back to spill.
                "handoff_bytes": sto.handoff_bytes,
                "d2h_avoided_bytes": sto.d2h_avoided_bytes,
                "handoff_edges": (self.plan_report or {}).get(
                    "handoff_edges", 0),
                "handoff_degrades": sto.handoff_degrades,
                "lower_fallbacks": sto.lower_fallbacks,
                # The table programs' bucket index: tokens probed and
                # hit, and slots its jobs ended with outside the index.
                "table_probe_tokens": sto.table_probe_tokens,
                "table_probe_hits": sto.table_probe_hits,
                "table_index_unplaced": sto.table_index_unplaced,
                # The stage vocabularies (one per stage and chip): host
                # slot inserts into them, and map jobs that started on
                # one another job had seeded.
                "handoff_slot_inserts": sto.handoff_slot_inserts,
                "handoff_shared_joins": sto.handoff_shared_joins,
                # The lowered scan's padded batches, and those the
                # native feed built rather than the numpy fallback.
                "scan_pad_batches": sto.scan_pad_batches,
                "scan_native_batches": sto.scan_native_batches,
                # Chips at work (parallel.placement): chips whose lowered
                # map jobs fed their programs, the lowered feed's h2d
                # bytes per chip, the mesh size of the collective fold
                # (0: none ran) and the bytes its all_to_all moved
                # between chips.
                "scan_chips": sum(1 for b in sto.h2d_by_chip.values() if b),
                "h2d_bytes_by_chip": sto.h2d_chip_list(),
                "fold_chips": sto.fold_chips,
                "fold_a2a_bytes": sto.fold_a2a_bytes,
            },
            "streamed_assoc_folds": self.streamed_assoc_folds,
            # Barrier-free pipelining evidence (docs/pipeline.md):
            # streamed-edge decisions, early-fold/chain runtime counters,
            # and the overlap the dissolved barriers actually bought.
            "pipeline": self._pipeline_section(),
            "retries": self.retries_total,
            # Failure-recovery summary (dampr_tpu.faults): classified
            # retries absorbed at every layer (job re-executions + the IO
            # layer's in-place transient retries), quarantine state, and
            # injection counts when a chaos plan was active.  "retries"
            # is the headline total the chaos gates assert on.
            "faults": self._faults_section(),
            # The logical plan that executed: stages before/after the
            # optimizer, rules fired, adaptive sizing decisions, and the
            # stage shapes the NEXT run's cost layer matches against.
            "plan": self.plan_report or {"enabled": False},
            "trace_file": None,
            "stats_file": None,
        })
        if self._reuse_summary is not None:
            # Cross-run cache evidence (plan/reuse.py): hits, bytes
            # mounted/published, incremental merges, recompute
            # fallbacks, and the per-stage decision list — what the
            # reuse-smoke CI leg and the doctor findings read.
            summary["reuse"] = self._reuse_summary
        if self._mitigation is not None:
            # What the skew signal made the engine DO: speculative wins,
            # stolen partitions, skipped collective windows, sticky
            # down-weights.  Mirrored into the plan report (the
            # mitigation is a runtime plan change) and — on merged
            # multi-process runs — into stats()["fleet"]["mitigation"].
            mit = self._mitigation.summary()
            summary["mitigation"] = mit
            plan_sec = summary.get("plan")
            if isinstance(plan_sec, dict):
                plan_sec["mitigation"] = {
                    "engagements": mit["engagements"],
                    "disengagements": mit["disengagements"],
                    "windows_skipped": mit["windows_skipped"],
                    "speculative_wins": mit["speculative_wins"],
                    "stolen_partitions": mit["stolen_partitions"],
                    "downweighted_ranks": mit["downweighted_ranks"],
                }
        if self.metrics is not None:
            # Counters, gauge peaks/lasts, histogram summaries, and the
            # sampler's self-accounting (samples, series drops, the
            # overhead self-metric) — the metrics plane measuring itself.
            summary["metrics"] = self.metrics.summary()
        if self.profiler is not None:
            # Per-operator attribution: which of the fused ops the stage
            # time went to, device sub-phases, per-stage coverage.
            summary["profile"] = self.profiler.summary(
                {s.stage_id: s.seconds for s in self.stats})
        if self.flightrec is not None and self.flightrec.path:
            summary["crashdump_file"] = self.flightrec.path
        if self.logstream is not None:
            if not self._run_failed:
                self.logstream.emit(
                    "info", "run-finish",
                    "run {} finished in {:.3f}s".format(self.name, wall),
                    data={"wall_seconds": round(wall, 3)})
            # Where the postmortem log lives + how much of it survived
            # the bound — stats.json's pointer into events.jsonl.
            summary["log"] = self.logstream.summary()
        if self._endpoint_info is not None:
            # The /metrics port this rank ACTUALLY served on (fallback-
            # shifted when the requested port was taken) — what the
            # dashboard and the serve concurrency tests read back.
            summary["endpoint"] = self._endpoint_info
        if self.tracer is not None:
            summary["spans"] = self.tracer.span_summary()
            # Critical-path verdicts: per-stage and whole-run dominant
            # bottleneck from the span timeline (wall-clock interval
            # unions, so concurrent lanes never double-count).
            try:
                from .obs import critpath as _critpath

                summary["critpath"] = _critpath.analyze(
                    summary, self.tracer.events)
            except Exception:
                log.warning("critical-path analysis failed", exc_info=True)
            tdir = _export.run_trace_dir(self.name)
            os.makedirs(tdir, exist_ok=True)
            summary["trace_file"] = _export.write_trace(
                self.tracer, os.path.join(tdir, _export.TRACE_FILE),
                metrics=self.metrics)
            spath = os.path.join(tdir, _export.STATS_FILE)
            summary["stats_file"] = spath
            _export.write_stats(summary, spath)
            log.info("trace: %s · stats: %s", summary["trace_file"], spath)
            # Fleet merge: rank 0 of a healthy multi-process traced run
            # waits (bounded) for its siblings' per-rank artifacts, then
            # builds the merged clock-aligned timeline + the
            # stats()["fleet"] section — persisted back into stats.json
            # AND visible on the in-memory summary.  A dead sibling
            # cannot wedge the survivor: past fleet_wait_ms the merge
            # proceeds with whatever landed and records the missing
            # ranks.  Single-process runs never enter (back-compat pin:
            # no fleet section, identical artifact layout).
            proc = summary.get("process") or {}
            if (proc.get("num_processes", 1) > 1
                    and not proc.get("process_id")
                    and not self._run_failed
                    and settings.fleet_wait_ms > 0):
                try:
                    from .obs import fleet as _fleet

                    fl = _fleet.merge_run(
                        self.name, wait_ms=settings.fleet_wait_ms,
                        summary=summary)
                    if fl is not None:
                        summary["fleet"] = fl
                except Exception:
                    log.warning("fleet trace merge failed", exc_info=True)
        self.run_summary = summary
        if not self._run_failed:
            # Run-history corpus: one compact record per FINALIZED run
            # (failed runs would poison the adaptation medians) — the
            # accumulated telemetry plan/cost.py and doctor consume.
            with _trace.span("run", "history"):
                self._append_history(summary)

    def _append_history(self, summary):
        from .obs import history as _history

        hpath = _history.append(summary)
        proc = summary.get("process") or {}
        if (hpath and settings.sentry_window > 0
                and not proc.get("process_id")):
            # Long-horizon telemetry: fold this run into the compact
            # per-fingerprint series (rank 0 only — sibling ranks'
            # records are rank-tagged trail, not run-level points),
            # then ask the sentry whether the newest point regressed
            # against its trailing baseline.  Warn-only here: a
            # finalized run must never fail on its own telemetry.
            try:
                from .obs import sentry as _sentry
                from .obs import timeseries as _timeseries

                _timeseries.append_from_summary(summary)
                findings = _sentry.check_run(self.name, summary=summary)
                if findings:
                    summary["sentry"] = findings
                    if self.logstream is not None:
                        for f in findings:
                            self.logstream.emit(
                                "warn", "sentry-regression",
                                "{metric} regressed: {value:g} vs "
                                "baseline median {median:g} "
                                "(z={z:.1f}, window={window})".format(
                                    **f),
                                data=f)
            except Exception:
                log.warning("telemetry sentry failed", exc_info=True)

    def _run(self, outputs, cleanup=True):
        from . import resume as _resume
        # EVERY run holds the scratch root's liveness lock — named roots
        # are shared across runs whether or not they resume, and a
        # concurrent run's in-flight spill blocks are not manifest-
        # referenced until its stage completes.  The GC sweep fires only
        # when the exclusive probe proves no other live run is mid-flight
        # under this name; we then downgrade to shared for our duration.
        guard = _resume.RunGuard(self.store.root)
        try:
            # Inside the try so a failure in the sweep or the shared
            # downgrade can never leak the flock fd (which would block
            # other runs' GC under this name until process exit).
            if guard.exclusive:
                with _trace.span("run", "gc"):
                    _resume.gc_unreferenced(self.store.root)
            guard.share()
            return self._run_stages(outputs, cleanup)
        except BaseException:
            # Drain-on-kill: a failing/killed run discards its queued
            # background spill writes (refs keep their RAM blocks; no
            # temp files survive) instead of racing them against teardown.
            try:
                self.store.abort_writes()
            except Exception:
                log.warning("spill writer abort failed", exc_info=True)
            # HBM residents die with the run: a killed run's device
            # lanes are never consumed, and holding them would leak the
            # shared device budget (the handoff tier keeps whole
            # vocabularies resident mid-stage).
            try:
                self.store.release_device()
            except Exception:
                log.warning("device release failed", exc_info=True)
            raise
        finally:
            guard.close()

    def _entry_io(self, entry):
        """Best-effort (records, bytes) of a stage input/output entry.
        Materialized PartitionSets and sink part files have exact sizes;
        raw taps (Chunkers) report (None, None) — their size is unknowable
        without reading them."""
        if isinstance(entry, storage.PartitionSet):
            recs = nbytes = 0
            for r in entry.all_refs():
                recs += len(r)
                nbytes += r.total_bytes
            return recs, nbytes
        if isinstance(entry, _SinkOutput):
            nbytes = 0
            for p in entry.paths:
                try:
                    nbytes += os.path.getsize(p)
                except OSError:
                    pass
            return None, nbytes
        return None, None

    def _pressure_snap(self):
        """Store/retry counters at a stage boundary; the per-stage deltas
        become that stage's StageStats pressure fields."""
        sto = self.store
        q = self._quarantine
        return (sto.spill_count, sto.spilled_bytes, sto.merge_gens,
                sto.merge_gen_bytes, self.retries_total,
                q.count if q is not None else 0)

    def _fill_stage_io(self, st, stage, env, result, snap):
        for s in getattr(stage, "inputs", ()):
            r, b = self._entry_io(env.get(s))
            if r:
                st.records_in += r
            if b:
                st.bytes_in += b
        _r, b = self._entry_io(result)
        if b:
            st.bytes_out += b
        sto = self.store
        st.spill_count = sto.spill_count - snap[0]
        st.spill_bytes = sto.spilled_bytes - snap[1]
        st.merge_gens = sto.merge_gens - snap[2]
        st.merge_gen_bytes = sto.merge_gen_bytes - snap[3]
        st.retries = self.retries_total - snap[4]
        q = self._quarantine
        st.quarantined = (q.count - snap[5]) if q is not None else 0

    def _run_stages(self, outputs, cleanup):
        rep = self.plan_report
        if rep is not None:
            # The plan decision record on the stage timeline: how many
            # construction-order stages collapsed into the schedule below.
            _trace.instant(
                "plan", "optimize", lane="stages",
                enabled=bool(rep.get("enabled")),
                stages_before=rep.get("stages_before"),
                stages_after=rep.get("stages_after"),
                rules={k: v for k, v in (rep.get("rules") or {}).items()
                       if v})
        env = {}
        to_delete = []
        fused = {}  # sid -> (pset, nrec, njobs) computed by an earlier pass
        plan, stage_fps = {}, {}
        volatile_sources = set()
        n_stages = len(self.graph.stages)
        required = None  # None = every stage (the non-resume fast path)
        from . import resume as _resume

        if self.resume:
            stage_fps = _resume.stage_fingerprints(
                self.graph, salt="p{}".format(self.n_partitions))
            plan = _resume.load_plan(self.store.root, stage_fps)
            if plan:
                log.info("resume: %d stage(s) restorable from %s",
                         len(plan), self.store.root)
        # Cross-run materialization cache (plan/reuse.py): decisions and
        # mounts happen HERE, before the need-set walk, so a corrupted
        # entry degrades to a normal recompute while its prefix is still
        # scheduled.  Best-effort by design: any failure disarms the
        # cache for this run and the run proceeds cold.
        reuse_ctl = None
        if settings.reuse_enabled():
            from .plan import reuse as _reuse

            try:
                reuse_ctl = _reuse.RunReuse(self, outputs)
                reuse_ctl.plan(outputs, satisfied=plan)
                self._reuse_summary = reuse_ctl.summary
            except Exception:
                log.warning("reuse cache disabled for this run",
                            exc_info=True)
                reuse_ctl = None
        if self.resume or (reuse_ctl is not None
                           and (reuse_ctl.mounted or reuse_ctl.incremental)):
            # Lazy need-set: a stage executes only if its output feeds a
            # stage that executes (or is itself requested / an effectful
            # sink) AND it was not restored or mounted.  Without this, a
            # rerun whose intermediates were cleaned up would recompute
            # the whole chain below its one surviving (final-output)
            # checkpoint — or below a cache hit.
            required = set()
            needed = set(outputs)
            for sid in range(n_stages - 1, -1, -1):
                stage = self.graph.stages[sid]
                if isinstance(stage, GInput):
                    continue
                if stage.output not in needed and not isinstance(
                        stage, GSink):
                    continue
                required.add(sid)
                if sid in plan:
                    continue  # restored from checkpoint: inputs not needed
                if reuse_ctl is not None and sid in reuse_ctl.mounted:
                    continue  # mounted from the shared cache
                if reuse_ctl is not None and sid in reuse_ctl.incremental:
                    continue  # delta re-run reads only its tap (GInput
                    #           sources always populate env below)
                needed.update(stage.inputs)
        for sid, stage in enumerate(self.graph.stages):
            t0 = time.time()
            snap = self._pressure_snap()
            self.store.set_stage(sid)
            # Fault attribution context: the stage the exchange watchdog
            # and quarantine sink tag their events with (sequential
            # walk: single writer).
            _faults.set_context(run=self.name, stage=sid)
            if isinstance(stage, GInput):
                env[stage.output] = stage.tap
                continue
            if self.profiler is not None:
                # Per-operator attribution context: the stage walk is
                # sequential, so the profiler's current-stage pointer is
                # exact; provenance (the original user stages a fused
                # node absorbed) rides the node from plan fusion.
                from .plan import ir as _plan_ir

                self.profiler.begin_stage(
                    sid, _plan_ir.stage_kind(stage),
                    provenance=_plan_ir.stage_provenance(stage))
            if _metrics.enabled():
                # The progress line's live stage view + a sampled stage
                # gauge, so the time series shows stage boundaries.
                self._status.update({
                    "sid": sid + 1, "n_stages": n_stages,
                    "kind": _stage_kind(stage),
                    "stage_t0": t0, "jobs_total": 0, "jobs_done": 0})
                _metrics.gauge_set("run.stage", sid)

            if required is not None and sid not in required:
                log.info("Stage %s/%s skipped: every consumer was restored "
                         "from checkpoint", sid + 1, n_stages)
                continue
            with _trace.span("stage", "s{}:{}".format(
                    sid, _stage_kind(stage)), lane="stages") as stage_span:
                log.info("Stage %s/%s: %r", sid + 1, n_stages, stage)
                if sid in plan:
                    result, nrec = _resume.restore_stage(
                        self.store.root, plan[sid])
                    env[stage.output] = result
                    if not isinstance(stage, GSink):
                        to_delete.append(stage.output)
                    st = StageStats(sid, "resumed-" + _stage_kind(stage))
                    st.n_jobs = 0
                    st.records_out = nrec
                    st.seconds = time.time() - t0
                    self._fill_stage_io(st, stage, env, result, snap)
                    self.stats.append(st)
                    stage_span.rename("s{}:{}".format(sid, st.kind),
                                      records=nrec)
                    log.info("Stage %s resumed: %s", sid + 1, st.as_dict())
                    continue
                if reuse_ctl is not None and reuse_ctl.handles(sid):
                    out = None
                    try:
                        out = reuse_ctl.apply(sid, stage, env)
                    except Exception:
                        # Exactness contract: a cache entry that fails mid-
                        # apply degrades to recompute, never to wrong
                        # results — fall through to normal execution (the
                        # need-set kept an incremental stage's tap input
                        # live; full mounts were validated at plan time).
                        log.warning("reuse: stage %s falls back to recompute",
                                    sid + 1, exc_info=True)
                        reuse_ctl.note_fallback(sid)
                    if out is not None:
                        result, nrec, rkind = out
                        env[stage.output] = result
                        if not isinstance(stage, GSink):
                            to_delete.append(stage.output)
                        # Mounted frames persist no resume manifest: their
                        # scratch hardlinks must be DELETED (not released)
                        # at cleanup, exactly like volatile stages' blocks.
                        volatile_sources.add(stage.output)
                        self.store.drain_writes()
                        st = StageStats(sid, rkind + "-" + _stage_kind(stage))
                        st.n_jobs = 0
                        st.records_out = nrec
                        st.seconds = time.time() - t0
                        self._fill_stage_io(st, stage, env, result, snap)
                        self.stats.append(st)
                        stage_span.rename("s{}:{}".format(sid, st.kind),
                                          records=nrec)
                        log.info("Stage %s %s from reuse cache: %s", sid + 1,
                                 rkind, st.as_dict())
                        continue
                if isinstance(stage, GMap):
                    if (sid not in fused
                            and len(stage.inputs) == 1
                            and type(stage.mapper) is base.Map
                            and stage.mapper.mapper is base._identity
                            and stage.combiner is None
                            and "binop" not in stage.options
                            and not stage.options.get("memory")
                            and not self.resume
                            and stage.inputs[0] not in outputs
                            and isinstance(env[stage.inputs[0]],
                                           storage.PartitionSet)
                            and env[stage.inputs[0]].n_partitions
                            == self.n_partitions
                            and self._alias_provenance_ok(
                                stage, env[stage.inputs[0]])):
                        # Identity checkpoint over an already-materialized
                        # partition set: alias it instead of re-registering
                        # (and re-spilling) every byte through a copy stage.
                        # The alias takes over deletion duty from the input.
                        result = env[stage.inputs[0]]
                        nrec, njobs = result.total_records(), 0
                        if stage.inputs[0] in to_delete:
                            to_delete.remove(stage.inputs[0])
                        env[stage.output] = result
                        to_delete.append(stage.output)
                        st = StageStats(sid, "map-alias")
                        st.records_out = nrec
                        st.seconds = time.time() - t0
                        self._fill_stage_io(st, stage, env, result, snap)
                        self.stats.append(st)
                        stage_span.rename("s{}:map-alias".format(sid),
                                          records=nrec)
                        log.info("Stage %s aliased (identity checkpoint): %s",
                                 sid + 1, st.as_dict())
                        continue
                    if sid in self._chain_results:
                        # Consumer half of a streamed chain: its jobs already
                        # ran, overlapped with the producer's, at the
                        # producer's turn (docs/pipeline.md).  Normal stage
                        # bookkeeping below still applies.
                        result, nrec, njobs = self._chain_results.pop(sid)
                    elif sid in fused:
                        result, nrec, njobs = fused.pop(sid)
                    else:
                        chained = None
                        hint = self._pipeline_edges.get(sid)
                        if (hint is not None and hint["mode"] == "chain"
                                and settings.pipeline_enabled()):
                            chained = self._run_chain(
                                sid, stage, hint["dst"], env)
                        if chained is not None:
                            result, nrec, njobs = chained
                            to_delete.append(stage.output)
                            env[stage.output] = result
                            self.store.drain_writes()
                            st = StageStats(sid, "map-chained")
                            st.n_jobs = njobs
                            st.records_out = nrec
                            st.seconds = time.time() - t0
                            self._fill_stage_io(st, stage, env, result, snap)
                            self.stats.append(st)
                            stage_span.rename(
                                "s{}:map-chained".format(sid),
                                records=nrec, jobs=njobs)
                            log.info("Stage %s chained into s%s: %s", sid + 1,
                                     hint["dst"] + 1, st.as_dict())
                            continue
                        group = [g for g in self._scan_share_group(
                            sid, stage, env)
                            if g[0] not in plan
                            and (required is None or g[0] in required)
                            and (reuse_ctl is None
                                 or not reuse_ctl.handles(g[0]))]
                        if group:
                            members = [(sid, stage)] + group
                            outs = self.run_map_group(
                                [s for s, _ in members],
                                [st for _, st in members], env)
                            for (msid, _), out in zip(members[1:], outs[1:]):
                                fused[msid] = out
                            result, nrec, njobs = outs[0]
                        else:
                            result, nrec, njobs = self.run_map(sid, stage, env)
                    kind = "map"
                    to_delete.append(stage.output)
                elif isinstance(stage, GReduce):
                    result, nrec, njobs = self.run_reduce(sid, stage, env)
                    kind = "reduce"
                    to_delete.append(stage.output)
                elif isinstance(stage, GSink):
                    result, nrec, njobs = self.run_sink(sid, stage, env)
                    kind = "sink"  # durable: never cleaned up
                else:
                    raise TypeError("Unknown stage type: {!r}".format(stage))

                env[stage.output] = result
                # Stage-boundary write barrier: every spill this stage's
                # registration pressure queued publishes now, so per-stage
                # spill attribution stays causal and checkpoint persistence
                # below sees settled refs (a ref mid-write has no path yet
                # and would be pointlessly re-written).
                self.store.drain_writes()
                if self.resume:
                    _resume.persist_stage(
                        self.store, sid, stage_fps[sid], result, nrec)
                    if _resume.is_volatile(stage_fps[sid]):
                        volatile_sources.add(stage.output)
                if reuse_ctl is not None:
                    # Cross-run publish rides the same settled-refs barrier
                    # as checkpoint persistence: on-disk blocks hardlink in
                    # for free, RAM blocks encode once.  Never fails the
                    # run; chaos/quarantined runs are gated off inside.
                    reuse_ctl.maybe_publish(sid, stage, result, nrec)
                # Ride the plan's shuffle choice on the stage's materialized
                # partitions: lazily-read sorted outputs (sort_by) decide
                # host-vs-mesh range redistribution at read time, after the
                # stage walk is gone.
                if isinstance(result, storage.PartitionSet):
                    result.shuffle_target = self._shuffle_targets.get(sid)
                st = StageStats(sid, kind)
                st.target = (stage.options or {}).get("exec_target", "host")
                st.shuffle_target = self._shuffle_targets.get(sid)
                st.n_jobs = njobs
                st.records_out = nrec
                st.seconds = time.time() - t0
                self._fill_stage_io(st, stage, env, result, snap)
                self.stats.append(st)
                stage_span.rename("s{}:{}".format(sid, kind),
                                  records=nrec, jobs=njobs)
                log.info("Stage %s done: %s", sid + 1, st.as_dict())

        # Final write barrier: OutputDataset readers and post-run tools see
        # every spill published (per-stage drains cover the loop; this
        # backstops runs whose last stage raised between drain points).
        self.store.drain_writes()

        sto = self.store
        if sto.h2d_bytes or sto.d2h_bytes or sto.hbm_offloads:
            log.info(
                "HBM tier: %d bytes up, %d bytes fetched back, %d offloads, "
                "peak residency %d bytes",
                sto.h2d_bytes, sto.d2h_bytes, sto.hbm_offloads,
                sto.hbm_peak_bytes)

        ret = []
        keep = set()
        for source in outputs:
            keep.add(source)
            entry = env[source]
            if isinstance(entry, storage.PartitionSet):
                ret.append(OutputDataset(entry, self.store))
            elif isinstance(entry, _SinkOutput):
                from .dataset import CatDataset
                ret.append(CatDataset(entry.datasets()))
            else:  # raw tap requested directly
                from .dataset import CatDataset
                ret.append(CatDataset(list(entry.chunks())))

        if cleanup:
            with _trace.span("run", "cleanup"):
                self._cleanup(to_delete, keep, env, volatile_sources)
        return ret

    def _cleanup(self, to_delete, keep, env, volatile_sources):
        """Delete (or, on durable runs, release) the run's intermediates,
        sparing the requested outputs and their aliases."""
        for source in to_delete:
            if source in keep:
                continue
            entry = env.get(source)
            if any(env.get(k) is entry for k in keep):
                # identity-checkpoint alias of a kept output: the
                # PartitionSet is shared, deletion would empty both
                continue
            if isinstance(entry, storage.PartitionSet):
                if self.resume and source not in volatile_sources:
                    # Durable runs keep intermediate checkpoints on disk
                    # (a modified rerun resumes from the longest valid
                    # prefix) but release RAM residency now.
                    entry.release(self.store)
                else:
                    # Volatile stages persist no manifest and can never
                    # be resumed — retaining their spilled blocks would
                    # grow the named scratch root without bound.
                    entry.delete(self.store)
