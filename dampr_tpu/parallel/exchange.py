"""General mesh exchange: the universal shuffle over ``lax.all_to_all``.

The reference routes *every* keyed exchange — non-associative group_by,
both join families, sort_by redistribution — through one shuffler writing
partition files to a shared filesystem (reference dampr/base.py:416-433,
runner.py:322-335).  :mod:`.shuffle` covers the associative-numeric case with
a fused fold+exchange; this module covers everything else: records whose
values are arbitrary Python objects cross the mesh as *byte payloads* inside
a fixed-shape ``all_to_all``.

Design:

- **Routing** is by partition id: partition ``pid`` lives on device
  ``pid % D``, so a partition's records (from both sides of a join) always
  land on the same device — co-partitioning is preserved by construction,
  exactly like the reference's shared ``Splitter``.
- **Payload** is host-marshalled: each (source shard, destination) pair's
  blocks serialize once per window (columnar pickle — numpy lanes serialize
  as raw buffers), not per record.  The collective moves the real bytes;
  the host only packs/unpacks at the boundary, which is where any system
  marshals opaque Python payloads.
- **Shape** is static per compile bucket: a ``[D*D, C]`` uint8 buffer
  (row ``s*D + d`` = source s's bytes for destination d) plus an int32
  length row, both sharded over the mesh axis.  ``C`` is the pow2 bucket of
  the largest blob in the window, so XLA compiles one program per (mesh, C).
- **Windows**: callers stream bounded windows through the exchange (the
  engine bounds them by the run-store budget), so working memory never
  depends on the total shuffled volume.

There is no overflow/retry here (unlike the capacity-factor scheme in
:func:`.shuffle.mesh_keyed_fold`): the host packs exact sizes, so the buffer
always fits by construction.

- **Budget**: one window is never one collective.  The planner
  (:mod:`.replan`) decomposes each window into a schedule of chunked
  all_to_all steps whose per-step in-flight bytes respect
  ``settings.exchange_hbm_budget`` — blob slices round-robin across
  steps and reassemble in order on the receive side, so peak device
  memory is bounded by configuration while results stay byte-identical.
"""

import functools
import logging
import os
import pickle
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .. import faults as _faults
from .. import settings
from ..io import codecs as _codecs
from . import mitigate as _mitigate
from . import replan
from .mesh import mesh_size

log = logging.getLogger("dampr_tpu.parallel.exchange")


def wire_codec():
    """The per-route payload codec (``settings.exchange_codec``), or None
    for uncompressed wire bytes.  ``auto`` resolves down a zstd -> lz4 ->
    OFF ladder — unlike the spill codec, the exchange never falls back to
    zlib: on an in-memory wire path a slow stdlib DEFLATE costs more
    step latency than the bytes it saves, while the spill path is
    amortized against disk.  Every blob carries a one-byte codec id, so
    a blob whose compressed form isn't smaller ships raw under the same
    framing."""
    name = str(settings.exchange_codec).lower()
    if name in ("off", "0", "false", "no", "none", "raw"):
        return None
    if name == "auto":
        for cand in ("zstd", "lz4"):
            if _codecs.available(cand):
                return _codecs.resolve(cand)
        return None
    try:
        codec = _codecs.resolve(name)
    except ValueError:
        log.warning("unknown exchange_codec %r; sending raw", name)
        return None
    return None if codec.cid == _codecs.RAW else codec


@functools.lru_cache(maxsize=None)
def _build_exchange(mesh, axis, capacity, gather=False):
    """One all_to_all program per (mesh, capacity) bucket: moves the byte
    buffer and the valid-length row across the mesh axis.  ``gather``
    (multi-process runs) replicates the delivered buffers with an
    all_gather so every host process can read the full result — the same
    scheme as mesh_keyed_fold (shuffle.py)."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def per_device(bb, ln):
        # bb: [D, C] uint8 — row j is this device's payload for device j.
        # After all_to_all, row i is what device i sent us.
        rb = lax.all_to_all(bb, axis, split_axis=0, concat_axis=0)
        rl = lax.all_to_all(ln, axis, split_axis=0, concat_axis=0)
        if gather:
            rb = lax.all_gather(rb, axis, tiled=True)
            rl = lax.all_gather(rl, axis, tiled=True)
        return rb, rl

    out_spec = P() if gather else P(axis)
    kwargs = {}
    if gather:
        # all_gather output IS replicated; the varying-axes inference
        # can't prove it, so disable the check for this variant (same as
        # mesh_keyed_fold's gather path).
        kwargs["check_vma"] = False

    def program(bb, ln):
        return jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=(out_spec, out_spec), **kwargs)(bb, ln)

    return jax.jit(program)


#: Shape of the LAST exchange this process ran (observability): steps,
#: payload bytes, peak in-flight bytes (per the replan cost model),
#: whether the budget clamped at the capacity floor, and per-device
#: sent/received payload byte counts.  The runner folds these into its
#: per-run ``stats()["mesh"]["exchange"]`` section; the multichip dryrun
#: prints them per device.
last_info = None

#: Process-cumulative exchange-timeout near-misses (steps that finished
#: but only after the watchdog was armed).  Purely observational.
watchdogs_armed = 0


def _step_watchdog(step_i, timeout_ms):
    """Bounded deadline for one collective step: a dead rank wedges a
    gloo collective FOREVER — no Python-level interrupt can break the
    native call — so the only clean abort for the surviving ranks is to
    flush their flight recorders (schema-valid crashdump per rank),
    record the timeout in the run's fault-event sidecar (the next run's
    shuffle routing degrades this stage to the host path), and exit the
    process nonzero.  Returns the event the step sets on completion."""
    done = threading.Event()
    ctx = dict(_faults.run_context)

    def expire():
        if done.wait(timeout_ms / 1000.0):
            return
        from ..obs import flightrec as _flightrec

        exc = TimeoutError(
            "collective exchange step {} exceeded "
            "exchange_timeout_ms={} — a peer rank is dead or wedged; "
            "aborting this rank rather than hanging the gloo "
            "collective".format(step_i, timeout_ms))
        log.error("%s (run=%r stage=%r)", exc, ctx.get("run"),
                  ctx.get("stage"))
        _flightrec.flush_active("exchange-timeout", exc)
        _faults.record_event(
            ctx.get("run"), "exchange_timeout", stage=ctx.get("stage"),
            step=step_i, timeout_ms=timeout_ms)
        os._exit(70)  # EX_SOFTWARE: bounded abort, never a hang

    t = threading.Thread(target=expire, daemon=True,
                         name="dampr-exchange-watchdog")
    t.start()
    return done


def mesh_blob_exchange(mesh, blobs, budget=None, coding=None):
    """Move arbitrary byte blobs across the mesh, under an HBM budget.

    ``blobs``: {(src_device, dst_device): bytes}.  Returns the delivered
    {(src_device, dst_device): bytes} — every blob crossed a collective
    (row ``s*D+d`` of a send buffer lives on device s; the matching row of
    the receive buffer lives on device d).

    The transfer runs as a :mod:`.replan` schedule of chunked all_to_all
    steps whose modeled in-flight bytes respect ``budget`` (default
    ``settings.exchange_hbm_budget``); blob slices reassemble in piece
    order, so the result is byte-identical to a single collective.  Each
    step emits ``exchange`` spans for its pack (h2d staging), collective,
    and unpack (d2h fetch) phases.

    Straggler mitigation (``dampr_tpu.parallel.mitigate``): when an
    engaged controller says to skip this window (degrade-in-place), the
    blobs are returned verbatim — the exchange is a placement transport
    whose delivered content equals its input byte-for-byte (the
    multi-process gather replicates everything to every host), so the
    skip is exact by construction and ``last_info["skipped"]`` records
    it.  On multi-process runs each executed window also piggybacks a
    tiny all_gather of per-rank step-entry times (on the
    ``mesh.clock_sync`` barrier-aligned clock), feeding the controller
    the LIVE form of the skew signal ``obs.fleet.step_skew`` computes
    post-hoc.
    """
    import jax

    from ..obs import trace as _trace

    global last_info
    D = mesh_size(mesh)
    ctl = _mitigate.active()
    if ctl is not None and not ctl.use_collective():
        # Degrade-in-place: the fleet stops serializing on the straggler
        # at every chunked step; content is identical by construction.
        _trace.instant("mitigation", "window-skipped",
                       bytes=sum(len(b) for b in blobs.values()))
        last_info = {
            "steps": 0, "bytes": 0, "peak_inflight_bytes": 0,
            "budget": (budget if budget is not None
                       else settings.exchange_hbm_budget),
            "clamped": False, "skipped": True,
            "sent_per_device": [0] * D, "received_per_device": [0] * D,
            "pair_bytes": {},
        }
        return dict(blobs)
    gather = jax.process_count() > 1
    # Per-route wire compression (settings.exchange_codec): blobs
    # compress BEFORE planning, so the schedule's cells slice WIRE bytes
    # and every downstream byte count (sent/received/pair/steps) is what
    # actually crossed the collective.  One-byte codec id per blob;
    # blobs that don't shrink ship raw under the same framing; empty
    # blobs stay empty (they deliver nothing, coded or not).
    codec = wire_codec()
    codec_info = None
    if codec is not None and blobs:
        raw_total = wire_total = 0
        wire = {}
        for sd, b in blobs.items():
            if not b:
                wire[sd] = b
                continue
            cb = codec.compress(b)
            if len(cb) + 1 < len(b):
                wire[sd] = bytes((codec.cid,)) + cb
            else:
                wire[sd] = bytes((_codecs.RAW,)) + b
            raw_total += len(b)
            wire_total += len(wire[sd])
        blobs = wire
        codec_info = {"name": codec.name, "raw_bytes": raw_total,
                      "wire_bytes": wire_total}
        global codec_raw_bytes, codec_wire_bytes
        codec_raw_bytes += raw_total
        codec_wire_bytes += wire_total
    sched = replan.plan_exchange(
        D, {sd: len(b) for sd, b in blobs.items()},
        budget=budget, gather=gather, coding=coding)
    sent = [0] * D
    received = [0] * D
    pair = {}  # (src_device, dst_device) -> payload bytes this exchange
    for s, d in blobs:
        n = len(blobs[(s, d)])
        if n:
            pair[(s, d)] = pair.get((s, d), 0) + n
    parts = {}
    entry_perf = None

    def pack_step(step):
        """Host-side staging of one step's send buffer.  Pure over
        (blobs, step) — safe to run one step ahead on the packer thread
        while the current step's collective is in flight."""
        t0 = time.perf_counter()
        buf = np.zeros((D * D, step.capacity), dtype=np.uint8)
        lens = np.zeros(D * D, dtype=np.int32)
        sent_inc = [0] * D
        for s, d, start, stop in step.cells:
            row = s * D + d
            n = stop - start
            lens[row] = n
            if n:
                buf[row, :n] = np.frombuffer(
                    blobs[(s, d)], dtype=np.uint8, count=n,
                    offset=start)
                sent_inc[s] += n
        pack_acct["seconds"] += time.perf_counter() - t0
        return buf, lens, sent_inc

    # Double-buffered schedule execution (settings.pipeline,
    # docs/pipeline.md): step k+1's h2d staging packs on a background
    # thread while step k's collective runs, so the host-side copy cost
    # hides behind device time.  The watchdog and fault sites stay
    # strictly per step on the dispatching thread — only the pure pack
    # moved off it.  DAMPR_TPU_PIPELINE=0 restores the serial loop.
    pack_acct = {"seconds": 0.0, "exposed": 0.0}
    packer = None
    if settings.pipeline_enabled() and len(sched.steps) > 1:
        packer = ThreadPoolExecutor(max_workers=1,
                                    thread_name_prefix="dampr-tpu-xpack")
    try:
        nxt = (packer.submit(pack_step, sched.steps[0])
               if packer is not None and sched.steps else None)
        for i, step in enumerate(sched.steps):
            with _trace.span("exchange", "h2d:{}".format(i),
                             step=i, capacity=int(step.capacity)):
                if packer is not None:
                    wait0 = time.perf_counter()
                    buf, lens, sent_inc = nxt.result()
                    pack_acct["exposed"] += time.perf_counter() - wait0
                    if i + 1 < len(sched.steps):
                        nxt = packer.submit(pack_step, sched.steps[i + 1])
                else:
                    s0 = pack_acct["seconds"]
                    buf, lens, sent_inc = pack_step(step)
                    pack_acct["exposed"] += pack_acct["seconds"] - s0
            for s in range(D):
                sent[s] += sent_inc[s]
            prog = _build_exchange(mesh, settings.mesh_axis, step.capacity,
                                   gather=gather)
            # Fault sites: ``rank_kill`` (exit action — the multi-process
            # chaos tests kill one rank mid-exchange here, precisely where
            # a real dead rank would leave its peers hanging) and
            # ``exchange_step`` (classified failures on the step itself).
            _faults.check("rank_kill")
            _faults.check("exchange_step")
            if i == 0:
                # First-step collective entry on this rank's monotonic
                # clock — AFTER the fault checks, so an injected slow
                # stretch (sleep_ms) shows up as entry lateness exactly
                # like real host-side straggling would.  Shared below.
                entry_perf = time.perf_counter()
            timeout_ms = settings.exchange_timeout_ms
            guard = None
            if timeout_ms > 0:
                global watchdogs_armed
                watchdogs_armed += 1
                guard = _step_watchdog(i, timeout_ms)
            try:
                with _trace.span("exchange", "step:{}".format(i), step=i,
                                 bytes=int(step.payload_bytes()),
                                 capacity=int(step.capacity),
                                 inflight_bytes=int(step.inflight_bytes)):
                    rb, rl = prog(buf, lens)
                    rb.block_until_ready()
            finally:
                if guard is not None:
                    guard.set()
            with _trace.span("exchange", "d2h:{}".format(i), step=i):
                rb = np.asarray(rb)
                rl = np.asarray(rl)
                for s, d, _start, _stop in step.cells:
                    row = d * D + s  # device d's local row s = sent by s
                    n = int(rl[row])
                    if n:
                        parts.setdefault((s, d), []).append(
                            rb[row, :n].tobytes())
                        received[d] += n
    finally:
        if packer is not None:
            packer.shutdown(wait=True)
    hidden = max(0.0, pack_acct["seconds"] - pack_acct["exposed"])
    global pack_seconds_total, pack_hidden_seconds_total
    pack_seconds_total += pack_acct["seconds"]
    pack_hidden_seconds_total += hidden
    out = {sd: b"".join(ps) for sd, ps in parts.items()}
    if codec is not None:
        out = {sd: _codecs.decompress(b[0], b[1:])
               for sd, b in out.items()}
    if ctl is not None and gather and entry_perf is not None:
        # Live skew observation: one tiny all_gather of (entry time,
        # transient-fault count) per rank — every rank receives the SAME
        # vector, so controller state transitions stay identical
        # fleet-wide (the invariant the skip/route decisions rely on).
        # The share is a collective like any step, so it gets the same
        # rank-death watchdog: a peer dying between its last payload
        # step and this gather must produce the bounded abort, never a
        # hung gloo collective.
        # Divergence discipline: the except branch below is only safe
        # because everything inside the try is either DETERMINISTIC
        # (the jit build — a compile error fails every rank
        # identically, so every controller misses the same
        # observation) or a COLLECTIVE (whose runtime failures are the
        # watchdog's jurisdiction, same as any payload step).  The
        # pure host-side fold of the gathered vector happens inside
        # _share_skew after the materialization and cannot fail
        # one-sided short of a 64-byte MemoryError.
        timeout_ms = settings.exchange_timeout_ms
        guard = None
        if timeout_ms > 0:
            watchdogs_armed += 1
            guard = _step_watchdog("skew-share", timeout_ms)
        try:
            _share_skew(mesh, D, ctl, entry_perf)
        except Exception:
            log.warning("mitigation skew share failed", exc_info=True)
        finally:
            if guard is not None:
                guard.set()
    for d in range(D):
        if sent[d]:
            sent_bytes_per_device[d] = (
                sent_bytes_per_device.get(d, 0) + sent[d])
        if received[d]:
            received_bytes_per_device[d] = (
                received_bytes_per_device.get(d, 0) + received[d])
    for sd, n in pair.items():
        pair_bytes_per_route[sd] = pair_bytes_per_route.get(sd, 0) + n
    last_info = {
        "steps": sched.n_steps,
        "bytes": sched.total_bytes,
        "peak_inflight_bytes": sched.peak_inflight_bytes,
        "budget": sched.budget,
        "clamped": sched.clamped,
        "sent_per_device": sent,
        "received_per_device": received,
        # (src, dst) -> payload bytes: the full routing matrix of this
        # exchange — obs.fleet folds device routes into the rank-level
        # send/recv matrix the straggler diagnosis reads.
        "pair_bytes": pair,
        # Double-buffer evidence: host pack seconds, the share of them
        # hidden behind the previous step's collective, and whether the
        # overlapped executor ran at all (>1 step + pipeline on).
        "overlap": {
            "pack_seconds": round(pack_acct["seconds"], 6),
            "hidden_seconds": round(hidden, 6),
            "hidden_fraction": (round(hidden / pack_acct["seconds"], 4)
                                if pack_acct["seconds"] > 1e-9 else 0.0),
            "pipelined": packer is not None,
        },
    }
    if codec_info is not None:
        last_info["codec"] = codec_info
    if sched.coding:
        last_info["coding"] = dict(sched.coding)
    return out


@functools.lru_cache(maxsize=None)
def _build_share(mesh, axis):
    """Tiny all_gather program for the mitigation piggyback: every
    device contributes one (entry time, fault count) row; every host
    reads the full per-device matrix."""
    import jax
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def per_device(t):
        return lax.all_gather(t, axis, tiled=True)

    return jax.jit(jax.shard_map(per_device, mesh=mesh, in_specs=(P(axis),),
                              out_specs=P(), check_vma=False))


_warned_no_clock = False


def _share_skew(mesh, D, ctl, entry_perf):
    """Share this rank's first-step entry time (barrier-aligned clock)
    and cumulative transient-retry count across the fleet, then feed the
    controller's live observation (which differences the retry counts
    per window).  ~D*12 bytes per window — noise next to the payload
    schedule it rides behind."""
    import jax

    from ..obs.fleet import _rank_of_device
    from .mesh import clock_sync

    global _warned_no_clock
    if clock_sync is None:
        # No common clock anchor (the init_distributed barrier
        # handshake failed, symmetrically — it is itself a collective):
        # raw per-host monotonic clocks measure time since each host's
        # BOOT, so cross-rank differences would be pure garbage that
        # could engage on a perfectly healthy fleet.  No observation is
        # strictly better than a wrong one.
        if not _warned_no_clock:
            _warned_no_clock = True
            log.warning(
                "mitigation: no clock handshake (mesh.clock_sync is "
                "None) — live skew observation disabled for this "
                "process; host-path stealing/speculation stay active")
        return
    nproc = jax.process_count()
    base = clock_sync["barrier_perf"]
    # Split integer/fraction lanes: jax truncates float64 inputs to
    # float32 with x64 off, whose ~8 ms quantization past a day of
    # barrier-relative time would dwarf the 20 ms jitter floor.  The
    # integer-seconds lane is exact below 2^24 s and the fraction lane
    # keeps sub-microsecond resolution at any run length.
    t = entry_perf - base
    vec = np.zeros((D, 3), dtype=np.float32)
    vec[:, 0] = np.float32(int(t))
    vec[:, 1] = np.float32(t - int(t))
    vec[:, 2] = np.float32(ctl.local_fault_count())
    out = np.asarray(_build_share(mesh, settings.mesh_axis)(vec))
    entries, fault_counts = {}, {}
    # One authoritative device->rank mapping (the same helper the fleet
    # merge and the weighted route table use — three copies of the
    # ownership assumption could silently disagree).
    for d in range(D):
        r = _rank_of_device(d, nproc, D)
        if r not in entries:
            entries[r] = float(out[d, 0]) + float(out[d, 1])
            fault_counts[r] = int(out[d, 2])
    first = min(entries.values())
    ctl.observe_window({r: t - first for r, t in entries.items()},
                       fault_counts=fault_counts)


def _pack_group(items):
    """[(seq, pid, Block)] -> blob.  Columnar: numpy lanes pickle as raw
    buffers, one serialization per group, never per record."""
    payload = [(seq, pid, (b.keys, b.values, b.h1, b.h2))
               for seq, pid, b in items]
    return pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)


def _unpack_group(blob):
    from ..blocks import Block

    return [(seq, pid, Block(k, v, h1, h2))
            for seq, pid, (k, v, h1, h2) in pickle.loads(blob)]


#: Process-level cumulative stats (observability; tests assert engagement).
total_exchanges = 0
total_bytes = 0
total_steps = 0
peak_inflight_bytes = 0  # high-water mark across every schedule run
#: Cumulative payload bytes by device index (process-level): what each
#: source device put on the wire and each destination drained — the
#: per-device view the multichip dryrun reports instead of only the
#: aggregate total.
sent_bytes_per_device = {}
received_bytes_per_device = {}
#: Cumulative (src_device, dst_device) -> payload bytes across every
#: exchange this process ran: the device-route matrix.  The runner
#: snapshots per-run deltas into ``stats()["mesh"]["exchange"]`` and
#: obs.fleet aggregates routes into the rank x rank matrix.
pair_bytes_per_route = {}
#: Cumulative per-route codec accounting (settings.exchange_codec):
#: pre-compression payload bytes vs what actually crossed the wire.
codec_raw_bytes = 0
codec_wire_bytes = 0
#: Cumulative double-buffer accounting: host pack seconds across every
#: schedule, and the share that hid behind an in-flight collective.
pack_seconds_total = 0.0
pack_hidden_seconds_total = 0.0


def mesh_shuffle_blocks(mesh, routed, coding=None):
    """Exchange one window of routed blocks across the mesh.

    ``routed``: iterable of (seq, src_shard, pid, Block) — seq is a caller
    sequence number used to restore deterministic per-partition block order
    on the receive side (the engine's group-value order is arrival order,
    reference semantics).  Destination device is ``pid % D`` — unless a
    mitigation controller holds a sticky down-weight, in which case the
    weighted routing table re-maps partitions away from the slow rank's
    devices (content-neutral: placement only, every host reads the full
    delivered set).

    Returns ``(received, bytes_moved)``: received is a list of (pid, Block)
    sorted by seq; bytes_moved counts payload bytes that crossed the
    collective (0 for a mitigation-skipped window — nothing moved).
    """
    from ..obs import trace as _trace

    global total_exchanges, total_bytes, total_steps, peak_inflight_bytes
    D = mesh_size(mesh)
    route = None
    ctl = _mitigate.active()
    if ctl is not None:
        import jax

        route = ctl.route_table(D, jax.process_count())

    def dst(pid):
        return route[pid % len(route)] if route else pid % D

    groups = {}
    for seq, src, pid, blk in routed:
        groups.setdefault((src % D, dst(pid)), []).append((seq, pid, blk))
    blobs = {sd: _pack_group(items) for sd, items in groups.items()}
    moved = sum(len(b) for b in blobs.values())
    with _trace.span("collective", "exchange", bytes=moved,
                     blobs=len(blobs)):
        recv = mesh_blob_exchange(mesh, blobs, coding=coding)
    if last_info is not None and last_info.get("skipped"):
        moved = 0  # degrade-in-place: nothing crossed the mesh
    total_exchanges += 1
    total_bytes += moved
    if last_info is not None:
        total_steps += last_info["steps"]
        peak_inflight_bytes = max(peak_inflight_bytes,
                                  last_info["peak_inflight_bytes"])
    out = []
    for (s, d), blob in recv.items():
        for seq, pid, blk in _unpack_group(blob):
            assert dst(pid) == d, (pid, d)
            out.append((seq, pid, blk))
    out.sort(key=lambda t: t[0])
    return [(pid, blk) for _seq, pid, blk in out], moved
