"""Wall-time attribution of device work (the bench's ``device_fraction``).

Buckets, accumulated process-wide behind one lock:

- ``device``:   jitted kernel dispatch+result sites (segment folds, the
                hash lexsort, mesh collective programs);
- ``transfer``: explicit host<->device lane movement (HBM tier puts,
                value-lane fetches, final fold-result fetches);
- ``codec``:    the native C text/hash/parse codec (host, but worth
                separating from generic Python time);
- ``codec_wait``: WALL-CLOCK union of intervals during which EVERY live
                map slot was blocked on its codec — each slot's fold
                consumer waiting for the next block while that slot's
                producer thread was inside the native codec (the overlap
                executor, runner._overlap_stream, via slot_stall/
                slot_unstall below).  This is the codec time still on
                the engine's critical path after overlapping: whenever
                at least one slot is folding, the codec seconds
                elsewhere are covered by useful work and do NOT count.
                Consumer wait caused by producer-side IO or Python
                (window reads, block building) is not codec-
                attributable and is excluded, matching what the
                ``codec`` bucket itself counts.  With the overlap
                executor off there are no slots and the bucket stays 0;
                the serial non-overlapped codec cost is then the whole
                ``codec`` bucket, since the job thread that runs the
                codec is by construction not folding meanwhile.
- ``scan_feed``, ``scan_wait``, ``scan_drain``: WALL-CLOCK unions of the
                lowered scan's phases (:class:`phase`, at the dispatch and
                drain sites of ``ops.lower`` and ``ops.handoff``).  Feed:
                the host builds and launches a batch — token bounds, line
                ids, the padded matrix, the program call (classic or
                table probe).  Wait: the host is blocked on a program's
                outputs (an explicit ``block_until_ready`` before every
                fetch).  Drain: the fetch and the host work after it — key
                decode, absorb into the accumulator, host regroup of
                collisions and misses, the handoff finalize's fetch.  Each
                bucket counts the wall time in which at least one thread
                was inside its phase (a count of threads in the phase; the
                interval opens at 0 -> 1 and closes at 1 -> 0), so
                concurrent map slots count it once, never their
                thread-seconds summed.
- ``mesh_fold``: WALL-CLOCK union of the collective fold's device work
                (``parallel.shuffle``, ``runner._mesh_reduce``): each
                chip's assembly of the fold's input, every keyed-fold
                program call with its ``dropped`` fetch, the per-chip
                compaction, and the final fold's fetch.

Times are dispatch-site THREAD-seconds (``codec_wait`` and the
:data:`UNIONS` excepted — each is a wall-clock interval union, never exceeding
elapsed wall): concurrent pool workers each add their own elapsed time,
so a bucket divided by wall time reads like CPU utilization (2.0 = two
cores' worth per wall second) and can exceed 1.0 on multi-core hosts —
same convention as `top`.  Thread-seconds inside ``track`` regions include any GIL waits
the region suffers, so under core contention the ``codec`` bucket is an
UPPER bound on codec CPU — one more reason the critical-path question
needs the interval-union bucket.  A jax call that returns an unrealized
array charges its sync cost to whichever site forces it (usually a
``transfer`` fetch).  Attribution-accurate at the boundaries users can
act on, not a profiler-grade kernel timeline (use settings.profile_dir
-> jax.profiler for that).
"""

import contextlib
import threading
import time

from ..obs import profile as _profile
from ..obs import trace as _trace

_lock = threading.Lock()
#: The wall-clock-union buckets (see :class:`phase`): the lowered scan's
#: three phases, and the collective fold's device work.
UNIONS = ("scan_feed", "scan_wait", "scan_drain", "mesh_fold")
_counters = {"device": 0.0, "transfer": 0.0, "codec": 0.0,
             "codec_wait": 0.0}
_counters.update(dict.fromkeys(UNIONS, 0.0))
_active = {}  # (thread ident, kind) -> nesting depth inside track(kind)
_inside = dict.fromkeys(UNIONS, 0)  # threads inside each union's phase
_since = dict.fromkeys(UNIONS)      # open union interval's start, or None

# codec_wait state: live overlap slots vs slots currently blocked on
# their own producer's codec.  The union interval is open exactly while
# every live slot is stalled (_all_since is its start timestamp).
_slots = 0
_stalled = 0
_all_since = None


def _roll_union_locked():
    """Close/open the all-slots-stalled interval after a state change."""
    global _all_since
    all_stalled = _slots > 0 and _stalled >= _slots
    if _all_since is None and all_stalled:
        _all_since = time.perf_counter()
    elif _all_since is not None and not all_stalled:
        _counters["codec_wait"] += time.perf_counter() - _all_since
        _all_since = None


def slot_enter():
    """A map slot's overlapped fold consumer came alive."""
    global _slots
    with _lock:
        _slots += 1
        _roll_union_locked()


def slot_exit():
    global _slots
    with _lock:
        _slots -= 1
        _roll_union_locked()


def slot_stall():
    """This slot's consumer is blocked waiting while its producer is in
    the native codec."""
    global _stalled
    with _lock:
        _stalled += 1
        _roll_union_locked()


def slot_unstall():
    global _stalled
    with _lock:
        _stalled -= 1
        _roll_union_locked()


def live_slots():
    """Overlap fold consumers currently alive (unlocked read: a sampled
    gauge tolerates a one-off torn value)."""
    return _slots


def stalled_slots():
    """Slots currently blocked on their producer's codec — the live
    consumer-stall state the metrics sampler snapshots."""
    return _stalled


def _leave_locked(key):
    depth = _active.get(key, 1) - 1
    if depth:
        _active[key] = depth
    else:
        _active.pop(key, None)


@contextlib.contextmanager
def track(kind):
    t0 = time.perf_counter()
    if kind != "codec":
        # Only codec regions feed active_in() (the overlap executor's
        # stall attribution); device/transfer sites skip the entry lock
        # and the _active bookkeeping — one lock take on exit, as before
        # the overlap work landed.
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            with _lock:
                _counters[kind] += dt
        return
    key = (threading.get_ident(), kind)
    with _lock:
        _active[key] = _active.get(key, 0) + 1
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        with _lock:
            _leave_locked(key)
            _counters[kind] += dt


class phase(object):
    """One timed region of the lowered scan, read by ONE clock pair that
    feeds every account of the region:

    - the wall-clock union bucket ``union`` (one of :data:`UNIONS`);
    - the span ``<cat>/<name>`` (:mod:`..obs.trace`: the run's tracer,
      and the profiler annotation ``dampr.<cat>.<name>`` when a session
      collects);
    - ``obs.profile``'s device sub-phase ``prof`` (``build``/``h2d``/
      ``compute``/``d2h``) with ``nbytes``, set by the site, when
      ``settings.profile`` is on;
    - the thread-seconds bucket ``track`` (``codec``, with its
      :func:`active_in` bookkeeping, or ``device``), where given.

    Per batch or window, never per record; ``args`` ride the tracer's
    span only."""

    __slots__ = ("union", "cat", "name", "track", "prof", "args",
                 "nbytes", "_key", "_ann", "_t0")

    def __init__(self, union, cat, name, track=None, prof=None, **args):
        self.union = union
        self.cat = cat
        self.name = name
        self.track = track
        self.prof = prof
        self.args = args
        self.nbytes = 0
        self._key = None
        self._ann = None
        self._t0 = 0.0

    def __enter__(self):
        self._ann = _trace.annotate(self.cat, self.name)
        if self.track == "codec":
            self._key = (threading.get_ident(), "codec")
        u = self.union
        with _lock:
            # The clock is read under the lock: the union's transitions
            # then happen in the order of their timestamps.
            t0 = self._t0 = time.perf_counter()
            n = _inside[u]
            if not n:
                _since[u] = t0
            _inside[u] = n + 1
            if self._key is not None:
                _active[self._key] = _active.get(self._key, 0) + 1
        return self

    def __exit__(self, exc_type, exc, tb):
        u = self.union
        with _lock:
            t1 = time.perf_counter()
            dt = t1 - self._t0
            n = _inside[u] - 1
            _inside[u] = n
            if not n and _since[u] is not None:
                _counters[u] += t1 - _since[u]
                _since[u] = None
            if self.track is not None:
                _counters[self.track] += dt
                if self._key is not None:
                    _leave_locked(self._key)
        _trace.record(self.cat, self.name, self._t0, dt, **self.args)
        if self.prof is not None:
            _profile.device_add(self.prof, dt, self.nbytes)
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        return False


def active_in(thread_ident, kind):
    """Is the given thread currently inside ``track(kind)``?  Lets a
    waiter attribute its blocked time to the SPECIFIC producer it waits
    on (a consumer blocked on its own job's codec, not a sibling job's)."""
    with _lock:
        return _active.get((thread_ident, kind), 0) > 0


def add(kind, seconds):
    with _lock:
        _counters[kind] += seconds


def union_seconds(intervals):
    """Total length of the union of ``(t0, t1)`` intervals — the same
    wall-clock-union discipline the live ``codec_wait`` bucket applies to
    slot stalls, as a pure function over recorded spans.  Concurrent
    lanes doing the same kind of work (two codec producers tokenizing at
    once) count the covered WALL time once, never their thread-seconds
    summed; this is what lets the critical-path analyzer
    (:mod:`dampr_tpu.obs.critpath`) compare resources against elapsed
    wall on an equal footing."""
    total = 0.0
    end = None
    for t0, t1 in sorted(i for i in intervals if i[1] > i[0]):
        if end is None or t0 > end:
            total += t1 - t0
            end = t1
        elif t1 > end:
            total += t1 - end
            end = t1
    return total


def snapshot():
    with _lock:
        out = dict(_counters)
        now = time.perf_counter()
        if _all_since is not None:  # fold in the open stall interval
            out["codec_wait"] += now - _all_since
        for u in UNIONS:  # ... and every open scan-phase interval
            if _since[u] is not None:
                out[u] += now - _since[u]
        return out


def epoch():
    """Run-scoped accounting without ``reset()``: capture the cumulative
    counters now and difference them later with :func:`delta`.  Unlike
    ``reset()`` this never disturbs other in-flight work's view — two
    sequential or concurrent ``run()`` calls each hold their own epoch and
    read their own deltas, while the process-wide counters stay monotone
    (``snapshot()`` folds any open interval in, so ``codec_wait`` and the
    ``scan_*`` unions are monotone across snapshots too)."""
    return snapshot()


def delta(since):
    """Per-bucket seconds accumulated since an :func:`epoch` snapshot.
    Clamped at zero so an interleaved ``reset()`` (legacy callers) can
    produce a short read, never a negative one."""
    now = snapshot()
    return {k: max(0.0, now[k] - since.get(k, 0.0)) for k in now}


def reset():
    global _all_since
    with _lock:
        for k in _counters:
            _counters[k] = 0.0
        now = time.perf_counter()
        if _all_since is not None:  # an open interval restarts at zero
            _all_since = now
        for u in UNIONS:
            if _since[u] is not None:
                _since[u] = now
