#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json``.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

In order: the seed's corpus is written by a spawn pool started before JAX
is touched; JAX and ``dampr_tpu`` start (the compile cache at the fixed
``<checkout>/.jax_cache``) and a TPU is required; the run gets a fresh
scratch root, so no earlier run's history steers its stages; the cell's
job runs until one whole job compiles and loads nothing (``setup_s`` ends
there); then jobs run back to back, one user in a closed loop, until
``--seconds`` have passed, the last one finished.  After the window the
pool computes the plain reference and every job's whole result is
compared with it.  The last line of standard output is the result.

Everything about a cell is found by name: its configuration, traffic and
cell files under ``configs/``, ``traffic/`` and ``workloads/``, its data
generator under ``data/`` (the configuration's ``"data"``, ``zipf_text``
where it names none), its pipeline under ``pipelines/`` and each metric's
reader under ``metrics/``.

``--rehearse-cpu BYTES`` runs on whatever JAX finds, with the lowering
forced on and the 1x tier cut to BYTES: for rehearsals on a machine
without the chip.  Its result names the platform it ran on.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402 - the clock starts first
import contextlib  # noqa: E402
import itertools  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

from corpus import DEFAULT_DATA, Pool, bench_dir, load  # noqa: E402

BENCH = bench_dir()
ROOT = os.path.dirname(BENCH)
WORK_ROOT = os.path.join(ROOT, ".smoke", "perfbench")
#: What a traffic file may say: one user runs jobs back to back, nothing
#: else is implemented.
TRAFFIC_KEYS = {"tier", "shard_bytes", "why"}
#: Jobs traced in a ``--trace 1`` run, from the window's first.
TRACE_JOBS = 2


def log(msg):
    print(msg, file=sys.stderr, flush=True)


class Cell(object):
    """A cell's entry in ``BENCHMARK.json`` and the files it names."""

    def __init__(self, name):
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bench = json.load(f)
        entries = [w for w in bench["workloads"] if w["name"] == name]
        if len(entries) != 1:
            raise SystemExit("no cell named {!r} in BENCHMARK.json".format(
                name))
        entry = entries[0]
        self.name = name
        self.chips = entry["chips"]
        config = [c for c in bench["configs"]
                  if c["name"] == entry["config"]][0]
        with open(os.path.join(ROOT, config["file"])) as f:
            self.config = json.load(f)
        with open(os.path.join(BENCH, "traffic",
                               entry["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        with open(os.path.join(BENCH, "workloads", name + ".json")) as f:
            self.spec = json.load(f)
        unknown = set(self.traffic) - TRAFFIC_KEYS
        if unknown:
            raise SystemExit("traffic {!r}: keys {} are not implemented".format(
                entry["traffic"], sorted(unknown)))
        if self.traffic["tier"] not in self.config["tiers"]:
            raise SystemExit("tier {} is not among the configuration's "
                             "tiers".format(self.traffic["tier"]))
        self.end_to_end = self._metrics(bench["end_to_end"])
        self.per_layer = self._metrics(bench["per_layer"])

    def _metrics(self, entries):
        return [m for m in entries
                if self.name in m.get("workloads", (self.name,))]

    def start_corpus(self, seed, work, tier_bytes=None):
        """A pool writing ``seed``'s corpus for this cell into ``work``;
        ``tier_bytes`` cuts the 1x tier for a rehearsal."""
        tier_bytes = tier_bytes or self.config["tier_bytes"]
        total = self.traffic["tier"] * tier_bytes
        shard = min(self.traffic["shard_bytes"], tier_bytes)
        pool = Pool(max(1, min(16, os.cpu_count() or 1, -(-total // shard))))
        pool.start_corpus(seed, self.config.get("data", DEFAULT_DATA),
                          self.config["corpus"], total, shard,
                          os.path.join(work, "corpus.txt"))
        return pool


class Job(object):
    __slots__ = ("start", "end", "wall", "input_bytes", "stats", "result",
                 "error", "compiles")

    def __init__(self, input_bytes):
        self.input_bytes = input_bytes
        self.stats = self.result = self.error = None


class Run(object):
    """What the metric readers read."""

    def __init__(self):
        self.jobs = []
        self.setup_s = None
        self.window_compiles = None
        self.trace = None


def run_job(form, corpus, input_bytes, outdir, name, meter):
    """One job: ``run()`` until the result is materialised, timed; its
    stats read and its emitter released after the timer."""
    from jax.profiler import TraceAnnotation

    job = Job(input_bytes)
    before = meter.compiles
    try:
        with TraceAnnotation("job.run"):
            job.start = time.perf_counter()
            em = form.run(corpus, outdir, name)
        with TraceAnnotation("job.read"):
            job.result = form.result(em, outdir)
            job.end = time.perf_counter()
        with TraceAnnotation("job.release"):
            job.stats = em.stats()
            form.release(em)
    except Exception as e:  # noqa: BLE001 - a failed job is counted
        job.end = time.perf_counter()
        job.error = repr(e)
        log("job failed: {!r}".format(e))
    job.wall = job.end - job.start
    job.compiles = meter.compiles - before
    return job


def off_path(job, path):
    """The counters of ``stats()["device"]`` by which ``job`` left the
    path its cell names (``path`` of ``workloads/<cell>.json``)."""
    dev = job.stats["device"]
    return ([k for k, lo in path["min"].items() if dev[k] < lo]
            + [k for k, hi in path["max"].items() if dev[k] > hi])


def walls(jobs):
    """Each job's wall seconds, and the programs it compiled or loaded."""
    return ", ".join("{:.3f}".format(j.wall) + (
        " ({} compiles)".format(j.compiles) if j.compiles else "")
        for j in jobs)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", type=int, default=0, metavar="BYTES",
                    help="rehearsal only: any platform, 1x tier of BYTES")
    return ap.parse_args(argv)


def device_info(jax, chips, rehearse):
    devs = jax.devices()
    if not rehearse and (devs[0].platform != "tpu" or len(devs) < chips):
        raise SystemExit("perfbench: the cell needs {} TPU chip(s), JAX "
                         "found {} {} device(s)".format(
                             chips, len(devs), devs[0].platform))
    return devs[:chips]


def memory_peak(devs):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return max(peaks)


def main(argv=None):
    args = parse_args(argv)
    cell = Cell(args.workload)
    os.makedirs(WORK_ROOT, exist_ok=True)
    work = tempfile.mkdtemp(prefix="run-", dir=WORK_ROOT)
    try:
        pool = cell.start_corpus(args.seed, work, args.rehearse_cpu)
        try:
            return measure(args, cell, pool, work)
        finally:
            pool.close()
    finally:
        shutil.rmtree(work, ignore_errors=True)


def measure(args, cell, pool, work):
    t0 = time.perf_counter()
    # The persistent compile cache sits at a fixed path in the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax

    # Serve every program from the cache, the sub-second ones too, and
    # keep it uncapped: with the chip machine's JAX_COMPILATION_CACHE_MAX_SIZE
    # JAX's size-capped LRU cache never read an entry back (PERF.md).
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    sys.path.insert(1, ROOT)  # the system under test, from this checkout
    import dampr_tpu  # noqa: F401 - places the compile cache
    from dampr_tpu import settings

    from meter import CompileMeter

    devs = device_info(jax, cell.chips, args.rehearse_cpu)
    # A fresh scratch root: no earlier run's history steers this one.
    settings.scratch_root = os.environ["DAMPR_TPU_SCRATCH"] = os.path.join(
        work, "scratch")
    if args.rehearse_cpu:
        settings.lower = "on"
        settings.hbm_budget = 1 << 30
    meter = CompileMeter()
    t_jax = time.perf_counter() - t0

    t0 = time.perf_counter()
    corpus = pool.path
    input_bytes = pool.wait_corpus()
    t_corpus = time.perf_counter() - t0
    form = load("pipelines", cell.spec["pipeline"])
    name = "perfbench-" + cell.name
    seq = itertools.count()

    def one():
        return run_job(form, corpus, input_bytes,
                       os.path.join(work, "out-%05d" % next(seq)), name,
                       meter)

    t0 = time.perf_counter()
    c0, s0, _h = meter.snapshot()
    warm = []
    # From the job after settings.cost_model_min_points matching runs, the
    # cost layer adapts from their history and may choose new shapes:
    # warm up past it, to a job that compiles nothing.
    warm_min = settings.cost_model_min_points + 1
    while len(warm) < warm_min + 2:
        warm.append(one())
        if len(warm) >= warm_min and warm[-1].compiles == 0:
            break
    c1, s1, h1 = meter.snapshot()
    t_warm = time.perf_counter() - t0
    run = Run()
    run.setup_s = time.perf_counter() - T_START
    log("setup_s {:.3f}: jax init {:.3f}, corpus {:.3f} ({} bytes), warm-up "
        "{:.3f} over {} jobs (compile or cache load {:.3f} in {} programs, "
        "{} cache hits; jobs {})".format(
            run.setup_s, t_jax, t_corpus, input_bytes, t_warm, len(warm),
            s1 - s0, c1 - c0, h1, walls(warm)))
    if meter.by_name:
        log("compile or cache load by program: " + meter.top())

    trace_dir = os.path.join(work, "trace") if args.trace else None
    window = contextlib.ExitStack()
    if trace_dir:
        # No Python tracer and only the host's critical events: each
        # traced job slows by less (PERF.md).
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        window.enter_context(jax.profiler.TraceAnnotation("perfbench.window"))
    deadline = time.perf_counter() + args.seconds
    while True:
        run.jobs.append(one())
        if trace_dir and len(run.jobs) == TRACE_JOBS:
            window.close()
            jax.profiler.stop_trace()
            trace_dir = None
        if time.perf_counter() >= deadline:
            break
    if trace_dir:
        window.close()
        jax.profiler.stop_trace()
    run.window_compiles = meter.compiles - c1
    t_window = run.jobs[-1].end - run.jobs[0].start
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak(devs)}
    log("window: {} jobs in {:.3f} s, {} compiles or cache loads; jobs "
        "{}".format(len(run.jobs), t_window, run.window_compiles,
                    walls(run.jobs)))

    breakdown = None
    if args.trace:
        from trace_reduce import reduce_trace

        t0 = time.perf_counter()
        run.trace = reduce_trace(os.path.join(work, "trace"))
        log("trace of {} jobs reduced in {:.3f} s".format(
            TRACE_JOBS, time.perf_counter() - t0))
        breakdown = run.trace["breakdown"]
        traced, rest = run.jobs[:TRACE_JOBS], run.jobs[TRACE_JOBS:]
        if rest:
            log("traced jobs' mean wall {:.3f} s, untraced after the trace "
                "{:.3f} s".format(sum(j.wall for j in traced) / len(traced),
                                  sum(j.wall for j in rest) / len(rest)))
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]

    t0 = time.perf_counter()
    want = form.reference(pool.reference_parts(cell.spec["pipeline"]))
    limits, path = cell.spec["limits"], cell.spec["path"]
    worst = dict.fromkeys(limits, 0)
    failed = 0
    for job in run.jobs:
        if job.error is not None:
            failed += 1
            continue
        got = form.compare(job.result, want)
        off = off_path(job, path)
        if off:
            log("job left the cell's path: {} in {}".format(
                off, job.stats["device"]))
        failed += bool(off) or any(got[k] > limits[k] for k in limits)
        for k in limits:
            worst[k] = max(worst[k], got[k])
    log("reference and checks: {:.3f} s".format(time.perf_counter() - t0))

    attempted = len(run.jobs)
    run.jobs = [j for j in run.jobs if j.error is None]
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = load("metrics", m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    checks = {k: {"value": worst[k], "limit": limits[k]} for k in limits}
    # The path's counters, each at its worst over the jobs.
    for bound, worst_of in (("min", min), ("max", max)):
        for k, v in path[bound].items():
            checks[k] = {"value": worst_of(
                (j.stats["device"][k] for j in run.jobs), default=None),
                bound: v}
    out = {"correct": failed == 0, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks
    for k, v in checks.items():
        log("check {}: {} ({})".format(k, v["value"], ", ".join(
            "{} {}".format(b, v[b]) for b in ("limit", "min", "max")
            if b in v)))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
