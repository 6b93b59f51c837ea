"""ICI shuffle microbenchmark (BASELINE.md config: "shuffle all-to-all
bandwidth"): times the full mesh keyed-fold program (local segment fold ->
all_to_all -> final fold), the ring all-reduce, and the budget-scheduled
byte exchange over the visible mesh.

On a single chip the collectives are loopback (upper bound); on a real slice
the same program measures ICI.  Run on the virtual CPU mesh for a
functional check:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python benchmarks/shuffle_bench.py --cpu

Multi-process mode spawns N local OS processes that join one
``jax.distributed`` deployment over a localhost coordinator (gloo CPU
collectives — the same code path a TPU pod runs over DCN) and drives the
byte exchange across the process boundary:

    python benchmarks/shuffle_bench.py --cpu --mproc 2

The JSON (one line, ``metric``/``value`` keyed for tools/check_bench.py)
reports ``exchange_bytes``, ``exchange_steps``, ``peak_inflight_bytes``
(the replan cost model's per-step high-water mark — asserted under
``hbm_budget``), and MB/s.
"""

import _pathfix  # noqa: F401  (repo root onto sys.path)

import argparse
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _exchange_blobs(n_dev, mb, seed=0):
    """Synthetic routed payload: every (src, dst) pair carries an uneven
    share of ``mb`` total megabytes (dst-skewed, so schedules see mixed
    piece counts)."""
    total = int(mb * 1e6)
    rng = np.random.RandomState(seed)
    weights = rng.rand(n_dev, n_dev) + 0.1
    weights /= weights.sum()
    blobs = {}
    for s in range(n_dev):
        for d in range(n_dev):
            n = int(total * weights[s, d])
            if n:
                blobs[(s, d)] = rng.randint(
                    0, 256, size=n).astype(np.uint8).tobytes()
    return blobs


def _bench_exchange(mesh, args):
    """Time the scheduled byte exchange; returns the JSON fields."""
    from dampr_tpu import settings
    from dampr_tpu.parallel import exchange as px
    from dampr_tpu.parallel.mesh import mesh_size

    n_dev = mesh_size(mesh)
    budget = (int(args.budget_mb * 1e6) if args.budget_mb
              else settings.exchange_hbm_budget)
    blobs = _exchange_blobs(n_dev, args.exchange_mb)
    payload = sum(len(b) for b in blobs.values())
    px.mesh_blob_exchange(mesh, blobs, budget=budget)  # warm (compile)
    t0 = time.time()
    for _ in range(args.iters):
        out = px.mesh_blob_exchange(mesh, blobs, budget=budget)
    ex_s = (time.time() - t0) / args.iters
    assert sum(len(b) for b in out.values()) == payload, "exchange lost bytes"
    info = px.last_info
    return {
        "exchange_bytes": payload,
        "exchange_steps": info["steps"],
        "peak_inflight_bytes": info["peak_inflight_bytes"],
        "hbm_budget": budget,
        "budget_respected": (info["peak_inflight_bytes"] <= budget
                             and not info["clamped"]),
        "exchange_MBps": round(payload / 1e6 / ex_s, 1),
    }


def _bench_straggler(mesh, args):
    """Injected-straggler scenario (docs/robustness.md "Straggler
    mitigation"): rank ``--slow-rank`` sleeps ``--slow-ms`` at every
    collective exchange step via the fault harness, and the SAME windows
    run twice — a no-mitigation control and a mitigated pass
    (``parallel.mitigate``) — under identical fresh fault plans.  Emits
    ``mitigation_engaged``, ``speculative_wins``, ``stolen_partitions``,
    ``windows_skipped`` / down-weighting, and the mitigated-vs-not wall.
    Only meaningful under ``--mproc`` (the live skew signal crosses the
    process boundary); single-process runs record the control wall and
    an un-engaged mitigation."""
    import jax

    from dampr_tpu import faults, settings
    from dampr_tpu.parallel import exchange as px
    from dampr_tpu.parallel import mitigate
    from dampr_tpu.parallel.mesh import mesh_size

    n_dev = mesh_size(mesh)
    blobs = _exchange_blobs(n_dev, min(args.exchange_mb, 1.0), seed=1)
    payload = sum(len(b) for b in blobs.values())
    px.mesh_blob_exchange(mesh, blobs)  # warm (compile) before faults
    spec = ("exchange_step:rank={},sleep_ms={},every=1,times=100000"
            .format(args.slow_rank, args.slow_ms))
    windows = args.slow_windows

    def drive():
        t0 = time.time()
        for _ in range(windows):
            out = px.mesh_blob_exchange(mesh, blobs)
            assert out == blobs, "exchange window not byte-identical"
        return time.time() - t0

    faults.configure(spec)
    try:
        control_wall = drive()
    finally:
        faults.clear()

    # Degrade-in-place requires the bounded-collective regime: arm the
    # exchange watchdog for the mitigated pass (generous deadline — it
    # exists so a diverged skip could never hang, not to fire here).
    saved_timeout = settings.exchange_timeout_ms
    if settings.exchange_timeout_ms <= 0:
        settings.exchange_timeout_ms = 120000
    ctl = mitigate.MitigationController(run_name=None)
    mitigate.start(ctl)
    faults.configure(spec)  # fresh plan: identical injected schedule
    try:
        mitigated_wall = drive()
    finally:
        faults.clear()
        mitigate.stop(ctl)
        settings.exchange_timeout_ms = saved_timeout
    s = ctl.summary()
    if jax.process_count() <= 1:
        sys.stderr.write(
            "shuffle_bench: --slow-rank without --mproc measures the "
            "control only (the live skew signal needs >= 2 ranks)\n")
    return {
        "slow_rank": args.slow_rank,
        "slow_ms": args.slow_ms,
        "slow_windows": windows,
        "straggler_payload_bytes": payload,
        "control_wall_s": round(control_wall, 3),
        "mitigated_wall_s": round(mitigated_wall, 3),
        "mitigation_speedup": (round(control_wall / mitigated_wall, 2)
                               if mitigated_wall > 1e-9 else None),
        "mitigation_engaged": s["engagements"] >= 1,
        "mitigation_windows_skipped": s["windows_skipped"],
        "speculative_wins": s["speculative_wins"],
        "stolen_partitions": s["stolen_partitions"],
        "downweighted_ranks": s["downweighted_ranks"],
        "straggler_named": s["straggler_rank"],
        "late_ratio": s["last_late_ratio"],
    }


def _obs_export(run_name, tracer, wall_start, wall, rec):
    """Per-rank artifact export for a traced bench run: trace.json +
    a minimal stats.json (schema dampr-tpu-stats/1) under the rank's
    trace dir, carrying the exchange route matrix obs.fleet folds into
    the rank x rank send/recv matrices."""
    from dampr_tpu.obs import export as _export
    from dampr_tpu.parallel import exchange as px

    proc = _export.process_section()
    tdir = _export.run_trace_dir(run_name)
    os.makedirs(tdir, exist_ok=True)
    trace_file = _export.write_trace(
        tracer, os.path.join(tdir, _export.TRACE_FILE))
    info = px.last_info or {}
    summary = {
        "schema": _export.STATS_SCHEMA,
        "run": run_name,
        "process": proc,
        "started_at": round(wall_start, 3),
        "wall_seconds": round(wall, 4),
        "n_partitions": 0,
        "stages": [],
        # records_out stays 0: the exchange bench materializes no record
        # stream (keyed_fold_records_per_s is a RATE and must not leak
        # into a count field the fleet table renders).
        "totals": {"records_out": 0,
                   "bytes_out": rec.get("exchange_bytes", 0),
                   "spill_bytes": 0},
        "mesh": {
            "folds": 0,
            "exchanges": 1,
            "exchange_bytes": rec.get("exchange_bytes", 0),
            "exchange": {
                "bytes": rec.get("exchange_bytes", 0),
                "steps": info.get("steps", 0),
                "peak_inflight_bytes": info.get("peak_inflight_bytes", 0),
                "hbm_budget": info.get("budget", 0),
                "sent_per_device": {
                    str(k): v
                    for k, v in sorted(px.sent_bytes_per_device.items())},
                "received_per_device": {
                    str(k): v for k, v in sorted(
                        px.received_bytes_per_device.items())},
                "routes": [[s, d, n] for (s, d), n in sorted(
                    px.pair_bytes_per_route.items())],
            },
        },
        "spans": tracer.span_summary(),
        "trace_file": trace_file,
    }
    spath = os.path.join(tdir, _export.STATS_FILE)
    summary["stats_file"] = spath
    _export.write_stats(summary, spath)
    return trace_file, spath


def _run_traced(args, run_name="shuffle-bench"):
    """Run the bench under a run-scoped tracer; rank 0 of a
    multi-process deployment then merges the fleet timeline (bounded
    wait for siblings) and reports the merged trace + fleet section in
    its JSON line."""
    import time as _time

    from dampr_tpu import settings
    from dampr_tpu.obs import trace as _trace

    tracer = _trace.Tracer(run_name)
    _trace.start(tracer)
    wall_start = _time.time()
    try:
        rec = _run_single(args)
    finally:
        _trace.stop(tracer)
    wall = _time.time() - wall_start
    trace_file, stats_file = _obs_export(run_name, tracer, wall_start,
                                         wall, rec)
    rec["trace_file"] = trace_file
    rec["stats_file"] = stats_file
    from dampr_tpu.obs import export as _export

    proc = _export.process_section()
    if proc.get("num_processes", 1) > 1 and not proc.get("process_id"):
        from dampr_tpu.obs import fleet as _fleet

        section = _fleet.merge_run(run_name,
                                   wait_ms=settings.fleet_wait_ms)
        if section is not None:
            rec["fleet"] = section
    return rec


def _run_single(args):
    import jax

    from dampr_tpu.ops import hashing
    from dampr_tpu.parallel import mesh_keyed_fold
    from dampr_tpu.parallel.mesh import data_mesh, process_info
    from dampr_tpu.parallel.ring import ring_allreduce

    mesh = data_mesh()
    n_dev = len(jax.devices())
    rng = np.random.RandomState(0)
    keys = rng.randint(0, args.keys, size=args.records)
    vals = np.ones(args.records, dtype=np.int32)
    h1, h2 = hashing.hash_keys(keys)
    payload_mb = args.records * 12 / 1e6  # h1 + h2 + v

    # warm (compile)
    mesh_keyed_fold(mesh, h1, h2, vals, "sum")
    t0 = time.time()
    for _ in range(args.iters):
        fh1, _fh2, fv = mesh_keyed_fold(mesh, h1, h2, vals, "sum")
    fold_s = (time.time() - t0) / args.iters
    assert int(fv.sum()) == args.records

    rec = {
        "metric": "shuffle_exchange_MBps",
        "devices": n_dev,
        "processes": process_info()["process_count"],
        "keyed_fold_MBps": round(payload_mb / fold_s, 1),
        "keyed_fold_records_per_s": round(args.records / fold_s),
    }
    rec.update(_bench_exchange(mesh, args))
    rec["value"] = rec["exchange_MBps"]
    if args.slow_rank >= 0:
        rec.update(_bench_straggler(mesh, args))

    if jax.process_count() == 1:
        x = rng.randn(n_dev * 1024, 256).astype(np.float32)
        ring_allreduce(mesh, x)  # warm
        t0 = time.time()
        for _ in range(args.iters):
            ring_allreduce(mesh, x)
        ring_s = (time.time() - t0) / args.iters
        rec["ring_allreduce_MBps"] = round(x.nbytes / 1e6 / ring_s, 1)
    return rec


def _spawn_mproc(args):
    """Parent side of --mproc: spawn N worker ranks of this same script
    joined through a localhost coordinator; rank 0's JSON line is the
    result."""
    port = _free_port()
    env_base = dict(os.environ)
    env_base.pop("XLA_FLAGS", None)
    # One process per chip: N ranks on one TPU host would each reach for
    # the chip, and all but one would fail or hang.  The ranks run on
    # the virtual CPU backend; multi-process on chips is not a path yet.
    env_base["JAX_PLATFORMS"] = "cpu"
    print("mproc: {} ranks on the virtual CPU backend (JAX_PLATFORMS=cpu), "
          "never on a TPU".format(args.mproc), file=sys.stderr, flush=True)
    procs = []
    for rank in range(args.mproc):
        env = dict(env_base)
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % args.devices_per_proc)
        env["DAMPR_TPU_COORDINATOR"] = "localhost:%d" % port
        env["DAMPR_TPU_NUM_PROCESSES"] = str(args.mproc)
        env["DAMPR_TPU_PROCESS_ID"] = str(rank)
        cmd = [sys.executable, os.path.abspath(__file__), "--worker"]
        cmd += ["--records", str(args.records), "--keys", str(args.keys),
                "--iters", str(args.iters),
                "--exchange-mb", str(args.exchange_mb),
                "--devices-per-proc", str(args.devices_per_proc)]
        if args.slow_rank >= 0:
            cmd += ["--slow-rank", str(args.slow_rank),
                    "--slow-ms", str(args.slow_ms),
                    "--slow-windows", str(args.slow_windows)]
        if args.budget_mb:
            cmd += ["--budget-mb", str(args.budget_mb)]
        if args.cpu:
            cmd.append("--cpu")
        procs.append(subprocess.Popen(
            cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env))
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=600))
    except subprocess.TimeoutExpired:
        # A dead rank wedges its siblings in the collective — kill the
        # whole deployment rather than leaking orphans until CI times out.
        for q in procs:
            q.kill()
        raise
    failed = any(p.returncode != 0 for p in procs)
    for rank, (p, (out, err)) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            sys.stderr.write("rank %d failed:\n%s\n" % (rank, err[-4000:]))
    if failed:
        raise SystemExit(1)
    # rank 0 prints the deployment's JSON line
    line = [ln for ln in outs[0][0].splitlines() if ln.startswith("{")][-1]
    print(line)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=1 << 22)
    ap.add_argument("--keys", type=int, default=1 << 16)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--exchange-mb", type=float, default=8.0,
                    help="total payload MB for the byte-exchange phase")
    ap.add_argument("--budget-mb", type=float, default=0,
                    help="exchange HBM budget override (MB); 0 = "
                         "settings.exchange_hbm_budget")
    ap.add_argument("--slow-rank", type=int, default=-1,
                    help="inject a straggler: this process rank sleeps "
                         "--slow-ms at every collective exchange step "
                         "(fault harness), and the bench reports "
                         "mitigated-vs-not wall (-1 = off)")
    ap.add_argument("--slow-ms", type=int, default=200,
                    help="straggler stall per exchange step (ms)")
    ap.add_argument("--slow-windows", type=int, default=16,
                    help="exchange windows per straggler pass")
    ap.add_argument("--mproc", type=int, default=0,
                    help="spawn N local processes joined via "
                         "jax.distributed (gloo on CPU) and bench the "
                         "exchange across the process boundary")
    ap.add_argument("--devices-per-proc", type=int, default=4,
                    help="virtual CPU devices per spawned process")
    ap.add_argument("--worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: one --mproc rank
    ap.add_argument("--cpu", action="store_true",
                    help="force the virtual CPU mesh")
    args = ap.parse_args()

    if args.mproc and not args.worker:
        _spawn_mproc(args)
        return

    import jax

    # honor --cpu and a JAX_PLATFORMS=cpu request even where the TPU plugin
    # programmatically overrides jax_platforms at interpreter start
    if args.cpu or "cpu" in os.environ.get("JAX_PLATFORMS", "").lower():
        jax.config.update("jax_platforms", "cpu")

    from dampr_tpu import settings
    from dampr_tpu.parallel.mesh import maybe_init_distributed

    maybe_init_distributed()  # joins the --mproc deployment when spawned

    if settings.trace:
        rec = _run_traced(args)
    else:
        rec = _run_single(args)
    if jax.process_index() == 0:
        print(json.dumps(rec))


if __name__ == "__main__":
    main()
