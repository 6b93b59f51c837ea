"""Global configuration for dampr_tpu.

Parity surface: the reference exposes mutable module globals in dampr/settings.py:1-37
(max_processes, compress_level, partitions, max_files_per_stage, batch_size,
memory_checker_type, max_memory_per_worker).  We keep the same "assign a module
attribute" ergonomics so reference users can switch without relearning config, and add
TPU-specific knobs (mesh shape, device batch size, spill tiers) that have no reference
analog.

Unlike the reference, per-op overrides still ride graph-node ``options`` dicts
(reference: runner.py:285/331, stagerunner.py:58-95), threaded through unchanged.
"""

import multiprocessing
import os
import sys

# ---------------------------------------------------------------------------
# Parity knobs (same names/meaning as reference dampr/settings.py)
# ---------------------------------------------------------------------------

#: Max host-side worker threads for input IO / opaque-UDF map stages.  The
#: reference forks this many processes (settings.py:5); we use threads because the
#: heavy lifting happens on-device and numpy/IO release the GIL.
max_processes = multiprocessing.cpu_count()

#: gzip compression level for spilled blocks (reference settings.py:8).
compress_level = 1

#: Number of shuffle partitions (reference settings.py:11 uses 91).  We default to a
#: multiple of typical mesh sizes so partitions map evenly onto devices.
partitions = 64

#: Upper bound on materialized block files per stage before a merge pass runs
#: (reference settings.py:16 `max_files_per_stage`).
max_files_per_stage = 50

#: Records per host block flushed to the device path (reference settings.py:20 uses
#: 1000 for pickle batches; device batches want to be much larger to amortize
#: dispatch).
batch_size = 65536

#: Execute pure per-record op chains (RecordOps) batch-at-a-time via
#: ``apply_batch`` — one tight C-level loop per op per batch — instead of
#: threading every record through nested generator frames (the reference's
#: hot loop, stagerunner.py:73-74).  Off = the record-at-a-time generator
#: lowering; outputs are identical (tests pin it), this is purely the
#: execution strategy.
batch_udf = os.environ.get("DAMPR_TPU_BATCH_UDF", "1") not in ("0", "false")

#: Byte budget per stage for in-memory blocks before spilling to the next tier
#: (replaces the reference's RSS-watermark `max_memory_per_worker`=512MB,
#: settings.py:27 + memory.py — our block sizes are known, so accounting is
#: deterministic, no /proc sampling).  Env-settable so deployment configs
#: (and autotune cold-config sessions) can pin it without code.
max_memory_per_stage = int(os.environ.get(
    "DAMPR_TPU_MEMORY_BUDGET", str(512 * 1024 * 1024)))

# ---------------------------------------------------------------------------
# TPU-native knobs (no reference analog)
# ---------------------------------------------------------------------------

#: Mesh axis name used for data-parallel sharding of record batches.
mesh_axis = "shards"

#: Stages whose materialized input is at most this many bytes skip per-
#: partition fan-out: plain record maps and sinks run as one job over the
#: concatenated refs, and associative folds reduce every partition in one
#: vectorized pass before re-splitting by hash.  Partition *identity* is
#: unchanged (outputs re-split by the same hash % P), only job granularity
#: collapses — per-partition numpy fixed costs dominate tiny stages
#: (measured: 64 partitions x ~1 ms on a 24k-record fold).
small_stage_bytes = 4 * 1024 * 1024

#: Scan sharing: map stages that read the SAME input tap (shared pipeline
#: prefixes — word_stats' four branches, TF-IDF's doc-freq + len) execute
#: fused in one pass over the chunks.  Members on the vectorized block
#: path (read_bytes / iter_byte_blocks) are served from one shared read
#: per chunk; per-record members still read their input independently.
#: Purely a scheduling change — per-stage outputs, partitioning, and
#: cleanup are unchanged.
scan_sharing = True

#: Byte-scanning block mappers (ops.text TokenCounts/DocFreq/ParseNumbers)
#: process chunks in line-aligned windows of this size instead of one
#: buffer: on this platform materializing a multi-GB contiguous bytes
#: object is pathological (measured 10.7 GB: one-shot read 196 s vs
#: windowed reads at 1.6 GB/s), and windows also bound mapper RSS by the
#: window, not the chunk.  256 MB measures within noise of a whole-buffer
#: scan at the 128 MB bench tier while keeping the 10 GB tier bounded.
scan_window_bytes = 256 * 1024 ** 2

#: When True, keyed kernels (hash/sort/segment-reduce) run through JAX on the default
#: backend; when False everything uses the numpy host fallback (useful for debugging).
use_device = os.environ.get("DAMPR_TPU_USE_DEVICE", "1") not in ("0", "false")

#: Minimum records in a block before device dispatch is worth it;
#: smaller blocks take the numpy path to dodge dispatch overhead.  None =
#: resolve by backend: the in-process cpu backend dispatches cheaply at
#: 4096; an attached accelerator needs larger batches to amortize the
#: transfer.  Set an int to pin it.
device_min_batch = (int(os.environ["DAMPR_TPU_DEVICE_MIN_BATCH"])
                    if os.environ.get("DAMPR_TPU_DEVICE_MIN_BATCH") else None)

#: Every auto-resolved threshold is at least this, so batches below it decide
#: "host" without touching (or initializing) any JAX backend.
_MIN_BATCH_FLOOR = 4096

_resolved_min_batch = None


def configure_compile_cache():
    """Keep JAX's persistent compilation cache where the deployment's
    ``JAX_COMPILATION_CACHE_DIR`` says, else in a fixed ``.jax_cache``
    directory at the checkout root: the path is part of every cache key,
    so it never carries a pid, a timestamp or a temporary name.  Runs at
    package import, before any program of this package can compile.  JAX
    reads the variable when it is imported, so before that the variable
    is set; after it, JAX's config is."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache")
    jax = sys.modules.get("jax")
    if jax is None:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    elif jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", path)


def backend():
    """The default JAX backend's platform name (``"cpu"``, ``"tpu"``...).
    Every auto-resolved setting below decides from this one call."""
    import jax

    return jax.default_backend()


def effective_device_min_batch():
    global _resolved_min_batch
    if device_min_batch is not None:
        return device_min_batch
    if _resolved_min_batch is None:
        _resolved_min_batch = 4096 if backend() == "cpu" else 1 << 16
    return _resolved_min_batch


def device_count_for_auto():
    """Visible-device count for auto-mode mesh decisions."""
    import jax

    return len(jax.devices())


def use_device_for(n):
    """Device-dispatch decision for an n-record batch.  Small batches answer
    without resolving the backend (no accidental JAX initialization)."""
    if not use_device or n < _MIN_BATCH_FLOOR:
        return False
    return n >= effective_device_min_batch()

#: HBM residency tier budget, in bytes (SURVEY §2 item 6 / §7 sketch 1):
#: numeric VALUE lanes of reduce-feeding stage outputs stay device-resident
#: between map and reduce — the reduce's collective fold consumes them
#: without a host round-trip.  Over budget, the oldest device refs offload
#: device->host (the FIRST spill step; host RAM pressure then cascades to
#: disk as usual).  0 disables the tier.  "auto" resolves by backend the
#: same way device_min_batch does: off on cpu backends (device RAM is host
#: RAM), 1 GB on an accelerator.
hbm_budget = os.environ.get("DAMPR_TPU_HBM_BUDGET", "auto")

#: Minimum records in a block before HBM residency is worth the put
#: overhead; smaller reduce-feeding blocks stay host (the local fold is
#: cheaper than a device round-trip at that size).
hbm_min_records = int(os.environ.get("DAMPR_TPU_HBM_MIN_RECORDS", "4096"))

_resolved_hbm = None


def effective_hbm_budget():
    global _resolved_hbm
    if isinstance(hbm_budget, int):
        return hbm_budget
    s = str(hbm_budget).lower()
    if s != "auto":
        return int(s)
    if _resolved_hbm is None:
        _resolved_hbm = 0 if backend() == "cpu" else 1 << 30
    return _resolved_hbm


#: Capacity slack factor for the fixed-shape all_to_all shuffle exchange
#: (MoE-style capacity: per-(src,dst) buffer = ceil(N/D) * factor).
shuffle_capacity_factor = 1.5

#: Route device-foldable associative reduces through the mesh collective
#: shuffle (local fold -> all_to_all -> final fold) instead of per-partition
#: host jobs: "auto" = when more than one device is visible, "on", "off".
#: Falls back to the host path whenever exactness can't be guaranteed
#: (object values, 32-bit lane overflow, 64-bit key collisions).
mesh_fold = os.environ.get("DAMPR_TPU_MESH_FOLD", "auto")

#: Route the *general* shuffle — non-associative group_by reduces, joins —
#: through the mesh byte exchange (parallel/exchange.py): every input
#: partition's blocks cross a fixed-shape all_to_all, windowed under the run
#: budget, with partition pid resident on device pid % D (co-partitioning
#: preserved for joins by construction).  "auto" = when more than one device
#: is visible, "on", "off".  The associative-numeric fast path (mesh_fold)
#: takes precedence where it applies.
mesh_exchange = os.environ.get("DAMPR_TPU_MESH_EXCHANGE", "auto")

#: Peak in-flight device bytes one exchange collective step may occupy
#: (send + delivered buffers, tripled by the multi-process gather
#: replication — the deterministic model in
#: :func:`dampr_tpu.parallel.replan.step_inflight_bytes`).  The byte
#: exchange decomposes every window into a schedule of chunked
#: all_to_all steps that each fit this budget, so the shuffle's device
#: working set is bounded by configuration, never by the data (the
#: memory-efficient redistribution recipe, arXiv 2112.01075).
exchange_hbm_budget = int(os.environ.get(
    "DAMPR_TPU_EXCHANGE_HBM", str(64 * 1024 ** 2)))

#: Optional explicit per-piece chunk cap (bytes) for the exchange
#: schedule, below what the budget alone allows.  0 (default) derives the
#: chunk size from ``exchange_hbm_budget``; set it when a device is
#: memory-pressured beyond what the in-flight model captures (the doctor
#: playbook's second exchange knob).
exchange_chunk_bytes = int(os.environ.get("DAMPR_TPU_EXCHANGE_CHUNK", "0"))

#: Cost-model floor for routing a redistribution over the mesh: in auto
#: mode, a stage whose recorded shuffle input (run-history corpus) is
#: under this many bytes keeps the host shuffle — collective windows pay
#: D*D pack/unpack fixed costs that dominate tiny exchanges.  Explicit
#: ``mesh_exchange="on"``/``"off"`` always wins over this heuristic.
exchange_min_bytes = int(os.environ.get(
    "DAMPR_TPU_EXCHANGE_MIN_BYTES", str(4 * 1024 ** 2)))

#: Ingest readahead window (chunks): a background thread prefetches the next
#: chunks' bytes (file IO + gzip inflate release the GIL) while the current
#: chunk computes.  0 disables.  See inputs.Readahead.
readahead_chunks = int(os.environ.get("DAMPR_TPU_READAHEAD", "2"))

#: Codec->fold overlap depth (the stage-overlapped streaming executor):
#: each map job runs its codec — decompress + tokenize/parse, the
#: ``map_blocks`` window scan — on a dedicated thread that stays this many
#: produced blocks ahead of the fold/register consumer, extending the raw-
#: byte readahead (``readahead_chunks``) up through the codec.  In-flight
#: codec output is charged byte-for-byte against the stage memory budget
#: (storage.RunStore.reserve_overlap), so overlapping displaces resident
#: blocks instead of raising the memory ceiling.  0 = serial (codec and
#: fold interleave on the job thread, the pre-round-6 behavior).
overlap_windows = int(os.environ.get("DAMPR_TPU_OVERLAP_WINDOWS", "2"))

#: Barrier-free pipelined execution (docs/pipeline.md): the plan's
#: ``pipeline`` pass marks producer->consumer stage edges ``streamed``
#: wherever byte-identity is provable (map->keyed-fold via early partial
#: folds, unfused map->map chains, sorted-run merge -> final read), and
#: the runner dissolves the stage barrier on those edges — completed
#: partitions publish into a bounded backpressure queue the consumer
#: works from while the producer is still running.  "auto"/"on" enable
#: it; "off"/"0" (the kill switch) reproduces staged execution
#: byte-identically.  Every edge decision — streamed or barrier, with
#: its reason — lands in the plan report and ``explain()`` regardless.
pipeline = os.environ.get("DAMPR_TPU_PIPELINE", "auto")


def pipeline_enabled():
    return str(pipeline).lower() not in ("off", "0", "false", "no")


#: Byte bound for the pipelined publish queue (the backpressure
#: contract): at most this many bytes of completed-but-unconsumed
#: partition output sit between a streamed edge's producer and consumer;
#: past it the publisher blocks (a ``pipe-wait`` stall span) until the
#: consumer drains.  Queued bytes are charged against the run budget
#: through ``RunStore.reserve_overlap``, so spill admission sees the
#: pressure.  0 (default) resolves to a quarter of the stage memory
#: budget at run time.
pipeline_queue_bytes = int(os.environ.get("DAMPR_TPU_PIPELINE_QUEUE", "0"))

#: Spill-lean sorted-run mode for map outputs no reduce ever consumes
#: (external sorts: ``ParseNumbers -> checkpoint``): each map job registers
#: its chunk's output as ONE key-sorted run instead of hash-fanning it into
#: ``partitions`` sub-blocks, the block-count compaction rewrite is skipped,
#: and the final read streams a k-way merge over the runs.  "auto"/"on"
#: enable it (jobs fall back to hash fan-out per chunk when keys are
#: non-numeric); "off" restores hash fan-out everywhere.  Reduce-consumed
#: outputs are never eligible — they keep hash routing, and the identity-
#: checkpoint alias gate forces a re-routing copy stage if a sorted-run
#: set ever flows toward a reduce.
sort_runs = os.environ.get("DAMPR_TPU_SORT_RUNS", "auto")

#: Maximum first-level sorted runs the final read merges directly.  At or
#: under this fan-in the output streams straight from first-level runs —
#: zero re-spill generations, each run file read once, sequentially.  Past
#: it, runs merge in generations of ``merge_fanin`` through a streamed
#: file->file pass (storage.register_stream) until the count fits.  The
#: effective cap also respects the memory budget: a merge holds one spill
#: window per run, so the planner clamps fan-in to
#: ``budget // per-run-window-bytes`` (floor 4).
merge_fanin = int(os.environ.get("DAMPR_TPU_MERGE_FANIN", "512"))


def sort_runs_enabled():
    return str(sort_runs).lower() not in ("off", "0", "false")

# ---------------------------------------------------------------------------
# Logical plan optimizer (dampr_tpu.plan — see docs/plan.md)
# ---------------------------------------------------------------------------

#: Master switch for the logical plan optimizer: every run's stage list is
#: rewritten (map fusion, combiner hoisting, sink fusion, dead-stage
#: elimination, stats-driven sizing) before execution.  Off, the graph
#: executes exactly as constructed — one stage per chained DSL call — the
#: reference's literal schedule.  Results are identical either way (the
#: optimizer-equivalence property tests pin it); this only changes how
#: many materialize boundaries the run pays.
def _env_flag(name):
    """Shared on/off env parsing: 0/false/no/off (any case) disable."""
    return os.environ.get(name, "1").lower() not in (
        "0", "false", "no", "off")


optimize = _env_flag("DAMPR_TPU_OPTIMIZE")

#: Static pipeline analysis (dampr_tpu.analyze, docs/analysis.md): UDF
#: purity/determinism classification, dispatch-safety (pickle) probes,
#: fold associativity recognition, and the jax-traceability probe that
#: widens device lowering to certified numeric UDF chains.  On (the
#: default), every run's plan report carries an ``analysis`` section,
#: fusion declines to fuse across evidence-impure UDFs, speculation
#: declines on nondeterministic UDFs, multi-process dispatch of
#: unpicklable closures fails pre-flight with a named diagnostic, and
#: certified numeric chains become device-lowerable.  Off
#: (``DAMPR_TPU_ANALYZE=0``), every hook is one flag check and plans,
#: fingerprints, and results are byte-identical to the pre-analysis
#: engine (CI pins it).
analyze = _env_flag("DAMPR_TPU_ANALYZE")

#: Per-rule kill switches (all default on; only consulted when
#: ``optimize`` is on).  plan_fuse: compose chains of pure per-record map
#: stages; plan_hoist: dissolve identity+combiner stages into their
#: producer (the map-side fold runs inside the producer's jobs);
#: plan_fuse_sinks: compose record chains into sink stages; plan_dead:
#: drop stages unreachable from any requested output or sink;
#: plan_adapt: size partitions/batches from the prior run's stats.json.
plan_fuse = _env_flag("DAMPR_TPU_PLAN_FUSE")
plan_hoist = _env_flag("DAMPR_TPU_PLAN_HOIST")
plan_fuse_sinks = _env_flag("DAMPR_TPU_PLAN_FUSE_SINKS")
plan_dead = _env_flag("DAMPR_TPU_PLAN_DEAD")
plan_adapt = _env_flag("DAMPR_TPU_PLAN_ADAPT")

#: Adaptive sizing targets (dampr_tpu.plan.cost): bytes of reduce input
#: one partition should carry (drives the adapted partition count), and
#: the byte size a map-stage output block should target when history
#: shows fat records (drives per-stage ``batch_size`` options).
plan_partition_bytes = int(os.environ.get(
    "DAMPR_TPU_PLAN_PARTITION_BYTES", str(32 * 1024 ** 2)))
plan_block_bytes = int(os.environ.get(
    "DAMPR_TPU_PLAN_BLOCK_BYTES", str(8 * 1024 ** 2)))

#: Learned per-operator cost model (dampr_tpu.plan.model, docs/tuning.md):
#: "auto" (default) fits per-operator-class throughput regressors over the
#: run-history corpus and uses them to SEARCH the knob space (partition
#: count, per-stage batch sizes, merge fan-in, overlap windows, spill
#: codec/threads, exchange budgets, shuffle placement) instead of replaying
#: medians — every choice lands in the plan report's ``cost`` section with
#: its predicted-vs-static delta.  "0"/"off" is the kill switch: the
#: adaptation layer reproduces the pre-model median-path decisions
#: byte-identically (pinned by tests).  Below the fit-confidence floor the
#: model abstains and the median path stands, with the reason recorded.
cost_model = os.environ.get("DAMPR_TPU_COST_MODEL", "auto")


def cost_model_enabled():
    return str(cost_model).lower() not in ("0", "false", "no", "off")


#: Fit-confidence floor for the cost model: an operator class needs at
#: least this many corpus measurements before its regressor participates,
#: and the whole model abstains (median fallback, reason recorded) until
#: the classes covering the plan's stages are all fit.
cost_model_min_points = int(os.environ.get(
    "DAMPR_TPU_COST_MODEL_MIN_POINTS", "3"))

#: Minimum predicted improvement (fractional) before a model choice
#: overrides the median/static decision — hysteresis so a noisy fit never
#: flips knobs for sub-noise gains.
cost_model_margin = float(os.environ.get(
    "DAMPR_TPU_COST_MODEL_MARGIN", "0.02"))

#: Closed-loop autotuning for bench drivers (dampr_tpu.obs.autotune):
#: when "on", benches that honor it (bench_tfidf) re-run their measured
#: pipeline under model-suggested knob vectors, keep the fastest
#: byte-identical configuration, and persist the winner (tuned.json +
#: the winner run's own corpus record) so the next fit sees it.  The
#: unattended CLI form is ``dampr-tpu-doctor --autotune``.  "off"
#: (default) = single-configuration runs, exactly as before.
autotune = os.environ.get("DAMPR_TPU_AUTOTUNE", "off")


def autotune_enabled():
    return str(autotune).lower() in ("on", "1", "true", "yes")


#: Trial budget for one autotune session (trial 0 is always the incoming
#: baseline configuration; the remaining trials come from the model's
#: knob search and the doctor playbook).  Bounded by construction: a
#: session never runs more than this many measured executions.
autotune_trials = int(os.environ.get("DAMPR_TPU_AUTOTUNE_TRIALS", "4"))

#: Cross-run materialization cache (dampr_tpu.plan.reuse): "on"/"1"
#: consults (and publishes to) the shared content-addressed stage cache
#: under ``reuse_dir`` so identical pipeline prefixes — across runs,
#: run NAMES, and processes — mount cached partition frames instead of
#: recomputing, and append-only input growth re-runs only the new
#: chunks.  "auto" (default) resolves OFF in ordinary processes and ON
#: inside serve-daemon workers (``serve_active``): served submissions
#: share materializations across tenants by default, exactly the
#: amortization the service exists for.  "0"/"off" pins the cache
#: fully out of the path — including inside the daemon, so the
#: reuse-off CI leg stays byte-identical end to end (plans,
#: fingerprints, and results are byte-identical either way).
reuse = os.environ.get("DAMPR_TPU_REUSE", "auto")


def reuse_enabled():
    v = str(reuse).lower()
    if v in ("on", "1", "true", "yes"):
        return True
    if v in ("off", "0", "false", "no"):
        return False
    return bool(serve_active)  # "auto": ON inside serve-daemon workers


#: Byte budget for the shared reuse cache directory.  Publishing past
#: the budget evicts least-recently-consumed entries (whole entries,
#: never single blocks) under the store's exclusive flock; mounted runs
#: are immune — consumers hardlink cached frames into their own scratch
#: before reading.
reuse_budget_bytes = int(os.environ.get("DAMPR_TPU_REUSE_BUDGET",
                                        str(2 * 1024 ** 3)))

#: Shared reuse-cache directory.  Empty (default) resolves to
#: ``<scratch_root>/reuse-cache`` at use time, so tests that repoint
#: scratch_root isolate their cache with it; co-located runs that
#: should SHARE materializations point this at one common directory.
reuse_dir = os.environ.get("DAMPR_TPU_REUSE_DIR", "")

#: Content-signature chunk granularity (bytes): input files are
#: fingerprinted in windows of this size, and append-only growth is
#: detected as a signature whose chunk list extends a cached prefix.
reuse_chunk_bytes = int(os.environ.get("DAMPR_TPU_REUSE_CHUNK",
                                       str(16 * 1024 ** 2)))

#: Deterministic seeding for ``sample(prob)``: None (default) keeps the
#: historical behavior — each worker thread draws from a time-seeded RNG,
#: so sampled pipelines are NOT reproducible run to run.  An int seeds
#: every per-thread RNG deterministically (re-derived at each run start),
#: making sampled pipelines reproducible whenever job->thread assignment
#: is deterministic — serial runs (``max_processes=1`` or single-job
#: stages) exactly, parallel runs per-thread-stream.  This is what lets
#: the optimizer-equivalence tests pin sampled pipelines.
seed = (int(os.environ["DAMPR_TPU_SEED"])
        if os.environ.get("DAMPR_TPU_SEED") else None)

# ---------------------------------------------------------------------------
# Device lowering (dampr_tpu.plan.lower + dampr_tpu.ops.lower)
# ---------------------------------------------------------------------------

#: Master switch for the device-lowering pass: fused map->fold stages
#: whose operators come from the native vocabulary (ops.text scanners
#: feeding keyed associative folds) compile into ONE jitted JAX program —
#: tokenize bounds on host, hash + dedup + segment fold on the default
#: JAX backend — instead of the host codec path.  "on"/"1" force it,
#: "off"/"0" disable it, "auto" (default) enables it whenever a
#: non-CPU accelerator backend is attached (on CPU-only hosts the native
#: C codec measures faster, so auto keeps the host path; CI forces the
#: CPU-JAX jit leg with DAMPR_TPU_LOWER=1).  Results are byte-identical
#: either way — ineligible stages and non-byte inputs always keep the
#: host path, and the program's collision check falls back per batch.
lower = os.environ.get("DAMPR_TPU_LOWER", "auto")

_resolved_lower = None


def lower_forced():
    """Was lowering EXPLICITLY forced on ("1"/"on")?  A forced switch
    wins over the stats-driven placement floor (``lower_min_records``):
    the operator asked for device execution, so accumulated history must
    not silently pin eligible stages back to host — only ``auto`` mode
    is cost-driven."""
    return str(lower).lower() in ("on", "1", "true", "yes")


def lower_enabled():
    """Is device lowering in force?  Auto resolves by backend the same
    way the HBM tier does."""
    global _resolved_lower
    s = str(lower).lower()
    if s in ("on", "1", "true", "yes"):
        return True
    if s in ("off", "0", "false", "no"):
        return False
    if _resolved_lower is None:
        _resolved_lower = backend() != "cpu"
    return _resolved_lower


#: Tokens per device program dispatch: each line-aligned scan window is
#: fed to the jitted program in batches of at most this many tokens
#: (padded to a power of two so compilations stay bounded).  Bounds the
#: padded token matrix the h2d feed stages while the previous batch's
#: program runs (the double-buffered overlap).
lower_batch = int(os.environ.get("DAMPR_TPU_LOWER_BATCH", str(1 << 18)))

#: Stats-driven placement floor: when a prior run's stats history shows
#: a lowered stage emitted fewer records than this, the cost layer
#: places it back on host — program dispatch overhead dominates tiny
#: stages (the tf.data-service argument: recorded stats pick host vs
#: device per stage).
lower_min_records = int(os.environ.get(
    "DAMPR_TPU_LOWER_MIN_RECORDS", "4096"))

#: Cross-stage device-resident handoff (docs/plan.md "Cross-stage device
#: fusion"): when the plan lowers an adjacent map producer AND its
#: consuming associative fold to the device, the producer's program
#: outputs stay HBM-resident and the fold consumes them in place —
#: skipping the d2h fetch, pickle, frame encode/decode, spill, and h2d
#: re-upload the host spill path would pay on that edge.  "auto"
#: (default) engages whenever lowering is in force AND either the HBM
#: tier has budget (a real accelerator) or lowering was explicitly
#: forced (the CPU-JAX jit leg: device memory IS host memory there, so
#: residency is free) — but an explicit ``hbm_budget=0`` declines auto
#: ("no device residency" wins); "on"/"1" force it, "off"/"0" disable
#: it.  Every
#: fallback (HBM budget exceeded, vocabulary overflow, 64-bit hash
#: collision, non-lowered consumer at run time) degrades that edge — or
#: just that batch — to the existing spill path byte-identically.
handoff = os.environ.get("DAMPR_TPU_HANDOFF", "auto")


def handoff_forced():
    return str(handoff).lower() in ("on", "1", "true", "yes")


def handoff_enabled():
    """Is the cross-stage device handoff tier in force?  Auto follows the
    lowering decision: enabled when stages lower AND device residency is
    either budgeted (HBM budget > 0) or free (the forced CPU-JAX leg) —
    but an EXPLICIT ``hbm_budget=0`` ("no device residency") always
    declines auto; only a forced ``handoff=on`` overrides it."""
    s = str(handoff).lower()
    if s in ("off", "0", "false", "no"):
        return False
    if s in ("on", "1", "true", "yes"):
        return True
    if str(hbm_budget).lower() != "auto" and effective_hbm_budget() == 0:
        return False
    return lower_enabled() and (effective_hbm_budget() > 0
                                or lower_forced())


def effective_handoff_budget():
    """Device bytes the handoff tier may keep resident: the HBM budget
    when the tier is funded, else (forced / forced-lowering CPU legs,
    where device RAM is host RAM) the run's stage memory budget."""
    b = effective_hbm_budget()
    if b > 0:
        return b
    if handoff_enabled():
        return max_memory_per_stage
    return 0


#: Spill compression policy: "auto" (default) compresses object-lane
#: blocks and writes fully-numeric blocks raw (high-entropy lanes don't
#: compress and the codec pass is core-bound both ways); "always"/"never"
#: force it.  A codec name ("gzip", "zlib", "zlib:6", "lz4", "zstd") is
#: also accepted and means "always, with that codec".
spill_compress = os.environ.get("DAMPR_TPU_SPILL_COMPRESS", "auto")

#: Frame codec used when the policy above says compress: "auto" picks the
#: best available (zstd > lz4 > zlib); explicit names take an optional
#: ":level" suffix ("zlib:6").  Unavailable optional codecs (lz4/zstd not
#: installed) fall back down the same ladder with a one-time warning;
#: gzip remains readable forever via per-frame codec ids and whole-file
#: magic sniffing (see dampr_tpu.io and docs/spill_format.md).
spill_codec = os.environ.get("DAMPR_TPU_SPILL_CODEC", "auto")

#: Background spill writer threads (dampr_tpu.io.writer.SpillWriterPool):
#: spill writes enqueue onto this many writer threads so folds never
#: block on codec+disk unless the queue is full; queued blocks'
#: in-flight bytes are charged against the stage memory budget like
#: overlap windows.  0 = synchronous spills on the evicting thread (the
#: pre-PR-3 behavior).
spill_write_threads = int(os.environ.get("DAMPR_TPU_SPILL_WRITERS", "2"))

#: Byte cap on queued-but-unwritten spill blocks (the writer pool's
#: double-buffering bound; admission is by current backlog, so in-flight
#: bytes peak at this cap plus one block).  None or 0 = half the stage
#: memory budget.  Queued bytes are budget-charged either way — they
#: displace resident blocks, never stack on top of the stage ceiling.
spill_inflight_bytes = (int(os.environ["DAMPR_TPU_SPILL_INFLIGHT"])
                        if os.environ.get("DAMPR_TPU_SPILL_INFLIGHT")
                        else None)

#: Readahead depth (frames) per spilled-run stream: merge readers and
#: final reads keep this many frames in flight on the shared read
#: executor, so decompression overlaps consumption and sibling runs'
#: frames decode in parallel.  0 = strictly serial reads.
spill_read_prefetch = int(os.environ.get("DAMPR_TPU_SPILL_PREFETCH", "2"))

#: Threads on the shared frame-read executor (process-wide; a k-way merge
#: over hundreds of runs multiplexes its prefetch onto these).
spill_read_threads = int(os.environ.get(
    "DAMPR_TPU_SPILL_READ_THREADS",
    str(min(4, multiprocessing.cpu_count()))))

#: Spill directory for host-RAM overflow (the reference's /tmp/<job> scratch tree,
#: base.py:435-469).
scratch_root = os.environ.get("DAMPR_TPU_SCRATCH", "/tmp/dampr_tpu")

#: Per-job retry budget for transient failures (flaky IO/UDF): a failing map/
#: reduce/sink job re-executes up to this many times before the run fails
#: fast with the original traceback.  The reference deadlocks on a dead
#: worker (stagerunner.py:35-38); 0 keeps plain fail-fast.  Retries are
#: CLASSIFIED (dampr_tpu.faults.classify): transient failures (flaky IO)
#: back off exponentially with jitter between attempts; deterministic
#: failures retry immediately (legacy behavior — a stateful UDF may
#: recover); fatal failures (MemoryError, kills) never retry.
job_retries = int(os.environ.get("DAMPR_TPU_JOB_RETRIES", "0"))

#: In-place retry budget for transient spill IO (background/sync frame
#: writes, frame reads, checkpoint persistence).  These retries are
#: absorbed inside the IO layer — a flaky disk never surfaces as a job
#: failure unless the budget is exhausted.  Counted in
#: ``stats()["faults"]``.
io_retries = int(os.environ.get("DAMPR_TPU_IO_RETRIES", "2"))

#: Exponential-backoff base and cap (milliseconds) for classified
#: transient retries (full jitter: each delay is uniform over
#: [0, min(cap, base * 2^attempt)]).
retry_backoff_ms = int(os.environ.get("DAMPR_TPU_RETRY_BACKOFF_MS", "50"))
retry_backoff_max_ms = int(os.environ.get(
    "DAMPR_TPU_RETRY_BACKOFF_MAX_MS", "5000"))

#: Poison-record quarantine budget: when > 0, a deterministically-failing
#: record batch on the batched-UDF map path is bisected and up to this
#: many offending records land in the run's quarantine sink
#: (``<scratch_root>/<run>/quarantine.jsonl``) instead of failing the
#: run; the stage completes with the skip count in
#: ``stats()["faults"]["quarantined"]`` and per-stage ``quarantined``
#: counters.  0 (default) = fail fast as before.
max_quarantined = int(os.environ.get("DAMPR_TPU_MAX_QUARANTINED", "0"))

#: Bounded deadline (milliseconds) for each collective exchange step
#: (``parallel.exchange.mesh_blob_exchange``).  0 (default) = no
#: watchdog.  When set, a step that has not completed within the
#: deadline — a dead rank wedging the gloo collective — makes every
#: SURVIVING rank abort cleanly: the flight recorder flushes a
#: crashdump, the timeout is recorded in the run's fault-event sidecar
#: (so the next run's shuffle routing degrades that stage to the host
#: path), and the process exits nonzero instead of hanging forever.
exchange_timeout_ms = int(os.environ.get(
    "DAMPR_TPU_EXCHANGE_TIMEOUT_MS", "0"))

#: Straggler mitigation (dampr_tpu.parallel.mitigate): when "on", every
#: run starts a per-run mitigation controller that turns the live skew
#: signal into action — work stealing from backlogged job queues,
#: speculative re-execution of straggler jobs (first-result-wins under
#: attempt-scoped commits), collective degrade-in-place when a rank is
#: persistently late at exchange steps, and sticky partition-share
#: down-weighting for pathological ranks.  "off" (the default) costs
#: zero overhead: every mitigation site is one module-global None-check,
#: the same contract as tracing/profiling.
mitigate = os.environ.get("DAMPR_TPU_MITIGATE", "off")


def mitigate_enabled():
    return str(mitigate).lower() in ("on", "1", "true", "yes")


#: Engagement threshold for the mitigation controller, two roles with
#: one meaning ("this worker is this many times slower than its peers"):
#: (a) a rank whose collective-step entry lateness is >= this multiple
#: of the OTHER ranks' mean lateness plus the 20 ms jitter floor counts
#: as pathological (deliberately not the reported ``late_ratio``, which
#: saturates at the rank count — see mitigate.observe_window); (b) a
#: host job whose elapsed time exceeds this multiple of the median
#: completed job duration becomes a speculation candidate.
speculate_threshold = float(os.environ.get(
    "DAMPR_TPU_SPECULATE_THRESHOLD", "1.5"))

#: Consecutive pathological observations before the mitigation engages
#: (and consecutive healthy probe observations before it disengages).
#: Twice this count of consecutive pathological observations escalates
#: to the sticky down-weight (the rank's partition share is reduced for
#: the remainder of the run).
speculate_after_steps = int(os.environ.get(
    "DAMPR_TPU_SPECULATE_AFTER", "3"))

#: While the collective path is degraded, every this-many skipped
#: windows one window runs through the mesh as a PROBE to re-measure
#: skew — how a mitigation engaged for a transient slow spell
#: (faults.py's windowed ``duration_ms`` slowness) disengages cleanly
#: once the rank recovers.  0 disables probing (degrade becomes sticky
#: for the run).
mitigate_probe_windows = int(os.environ.get(
    "DAMPR_TPU_MITIGATE_PROBE", "4"))

#: CAMR-style coded aggregation for keyed folds routed over the byte
#: exchange (arXiv 1901.07418): "camr" pre-folds each exchange window's
#: blocks per destination partition under the stage's associative op —
#: replicated map-side fold work traded for strictly fewer shuffle
#: bytes (duplicate keys collapse before they cross the mesh).  Applies
#: only where exactness is free: integer/bool lanes for sums (float
#: summation order would change), any numeric lane for min/max.  "off"
#: (default) ships every window's raw partials.  Byte-exactness against
#: the uncoded path is pinned by tests.
exchange_coding = os.environ.get("DAMPR_TPU_EXCHANGE_CODING", "off")


def exchange_coding_enabled():
    return str(exchange_coding).lower() in ("camr", "on", "1", "true")


#: Per-route exchange payload compression: each (src, dst) blob is
#: compressed before the chunked all_to_all schedule is planned, so the
#: schedule's HBM-budget packing and the gloo wire both see compressed
#: bytes.  "auto" (default) picks the best codec available in the
#: environment (zstd > lz4 > off — io/codecs.py ladder); a codec name
#: pins it; "off" ships raw bytes.  Wire-vs-raw byte counts land in
#: ``stats()["mesh"]["exchange"]``; byte-exactness against the uncoded
#: path is pinned by tests (decompression restores the exact payload).
exchange_codec = os.environ.get("DAMPR_TPU_EXCHANGE_CODEC", "auto")


#: Whole-run retry budget for ``run(resume="auto")``: a failed run
#: re-executes from its last durable checkpoint manifest up to this
#: many times (transient-backoff between attempts; fatal failures and
#: explicit kills never auto-resume).
run_retries = int(os.environ.get("DAMPR_TPU_RUN_RETRIES", "1"))

#: Deterministic fault-injection plan (dampr_tpu.faults): a seeded,
#: schedule-based spec naming fault sites and firing rules, e.g.
#: ``"spill_write:p=0.01;exchange_step:nth=3;seed=7"``.  Empty/None
#: (default) = injection fully disabled — every site is one
#: module-global None-check.  See docs/robustness.md for the grammar
#: and site catalog.
faults = os.environ.get("DAMPR_TPU_FAULTS") or None

#: When set, every run is wrapped in a jax.profiler trace written under this
#: directory (view with TensorBoard / xprof).  Structured per-stage metrics
#: are always available via ValueEmitter.stats regardless.
profile_dir = os.environ.get("DAMPR_TPU_PROFILE_DIR") or None

#: Run-scoped engine tracing (dampr_tpu.obs): when True every run records
#: spans at the hot engine boundaries — codec/fold in the overlapped map
#: driver, spill writes and k-way merge generations, mesh collectives and
#: byte exchanges, checkpoint persist/restore, HBM tier moves — and
#: persists a Chrome trace-event JSON (loadable in Perfetto /
#: chrome://tracing) plus a ``stats.json`` summary under
#: ``<scratch_root>/<run>/trace/``.  Off (the default) the span sites are
#: a single None-check each, so the engine's hot loops pay near-zero cost.
#: This is the engine-boundary timeline; ``profile_dir`` above remains the
#: escape hatch for a profiler-grade XLA kernel timeline.
trace = os.environ.get("DAMPR_TPU_TRACE", "0").lower() not in (
    "0", "false", "no", "off", "")

#: Override directory for trace/stats artifacts.  None (default) puts them
#: under the run's scratch root, next to its durable spill/checkpoint
#: outputs; a path pins every run's artifacts under <trace_dir>/<run>/.
trace_dir = os.environ.get("DAMPR_TPU_TRACE_DIR") or None

#: Per-operator profiler (dampr_tpu.obs.profile): when True every run
#: attributes wall time and record counts to the INDIVIDUAL user ops a
#: fused stage was built from — each composed ``apply_batch`` step, each
#: codec window per scanner, map-side partial/final folds, and the
#: device programs' build/h2d/compute/d2h sub-phases — and ships the
#: result as ``stats()["profile"]`` (plus the run-history corpus).  Off
#: (the default) every instrumentation site is one module-global
#: None-check, same contract as ``trace``/``metrics_interval_ms``; the
#: timers are per-batch/per-window, never per-record, so the on-path
#: overhead stays within the ≤3% bench gate.
profile = os.environ.get("DAMPR_TPU_PROFILE", "0").lower() not in (
    "0", "false", "no", "off", "")

#: Run-history corpus (dampr_tpu.obs.history): every finalized run
#: appends one compact summary record (plan fingerprint + stage shapes,
#: per-stage IO, critical-path verdicts, per-op profile, throughput,
#: settings snapshot) to ``<scratch_root>/<run>/history.jsonl``, bounded
#: to this many entries (oldest rewritten away past it).  The corpus
#: feeds ``plan/cost.py`` adaptation (median over matching runs instead
#: of one stats.json) and ``dampr-tpu-doctor --diff``.  0 disables
#: corpus writes entirely.
history_entries = int(os.environ.get("DAMPR_TPU_HISTORY_ENTRIES", "64"))

#: Recency bound for corpus-driven adaptation: only the most recent this-
#: many shape-matching records feed the per-stage medians (old runs under
#: different data volumes should age out of the estimate).
history_window = int(os.environ.get("DAMPR_TPU_HISTORY_WINDOW", "8"))

#: Live metrics plane (dampr_tpu.obs.metrics): sampling cadence in
#: milliseconds for the background gauge sampler.  0 (the default)
#: disables the metrics registry entirely — every instrumentation site
#: is one module-global None-check, no sampler thread is spawned, same
#: contract as ``trace``.  >0 starts a run-scoped registry + sampler:
#: gauges (budget occupancy, writer-pool queue depth, overlap windows,
#: HBM residency, records/bytes throughput) snapshot on this cadence
#: into an in-memory time series that lands in the Perfetto trace as
#: counter tracks, feeds the live progress reporter, and rides the
#: flight recorder into ``crashdump.json`` on failure.  Traced runs
#: (``trace=True``) sample at 100 ms even when this is 0, so a killed
#: traced run always leaves a crash timeline with recent samples.
metrics_interval_ms = int(os.environ.get("DAMPR_TPU_METRICS_MS", "0"))


def effective_metrics_interval_ms():
    """The sampling cadence actually in force: the explicit setting, or
    the 100 ms traced-run default (a traced run's crashdump must carry
    recent gauge samples), or 0 = metrics plane off.  A live metrics
    endpoint (``metrics_port``) also implies sampling — a scraper
    polling ``/metrics`` must see moving gauges, not a dead registry."""
    if metrics_interval_ms > 0:
        return metrics_interval_ms
    if trace or progress or metrics_port > 0:
        return 100
    return 0


#: Live metrics endpoint (dampr_tpu.obs.serve): when > 0 every run
#: starts a stdlib-only HTTP thread on this port exposing ``/metrics``
#: (Prometheus text exposition of the live registry, rank-labeled) and
#: ``/healthz``.  Multi-process deployments bind ``metrics_port +
#: process_id`` per rank so co-located ranks never collide.  0 (the
#: default) serves nothing — the run pays only the usual metrics-plane
#: cost, and with the plane off too, nothing at all.
metrics_port = int(os.environ.get("DAMPR_TPU_METRICS_PORT", "0"))

#: How long (milliseconds) rank 0 waits at finalize for its sibling
#: ranks' per-rank trace/stats artifacts before building the merged
#: fleet timeline (dampr_tpu.obs.fleet).  Ranks in a collective pipeline
#: finish near-lockstep, so the wait is normally milliseconds; a killed
#: sibling must not wedge the survivor, so past the deadline rank 0
#: merges what landed and records the missing ranks.  0 disables the
#: finalize-time merge entirely (``dampr-tpu-stats --fleet`` still
#: merges post-hoc).
fleet_wait_ms = int(os.environ.get("DAMPR_TPU_FLEET_WAIT_MS", "10000"))


#: Live in-run progress reporter (dampr_tpu.obs.progress): when True,
#: runs print a single updating console line per stage — records/s,
#: MB/s, spill backlog, ETA — to stderr on ``progress_interval_ms``
#: cadence.  Implies the metrics plane (the reporter reads its gauges),
#: so a progress-enabled run samples even with metrics_interval_ms=0.
progress = os.environ.get("DAMPR_TPU_PROGRESS", "0").lower() not in (
    "0", "false", "no", "off", "")

#: Progress reporter refresh cadence (milliseconds).
progress_interval_ms = int(os.environ.get("DAMPR_TPU_PROGRESS_MS", "500"))

#: Flight recorder ring capacity (events): the bounded tail of recent
#: spans + metric samples flushed to ``crashdump.json`` when a run dies
#: (see dampr_tpu.obs.flightrec).  Bounds the crash artifact regardless
#: of run size.  0 disables the recorder.
flight_recorder_events = int(os.environ.get(
    "DAMPR_TPU_FLIGHTREC_EVENTS", "1024"))

#: Cap on retained samples per time series (oldest samples drop past it;
#: the registry counts drops so ``stats()`` reports them).
metrics_series_cap = int(os.environ.get(
    "DAMPR_TPU_METRICS_SERIES_CAP", "4096"))

#: Structured log stream (dampr_tpu.obs.log): minimum level persisted to
#: the run-scoped ``<run>/trace/events.jsonl`` event log — one of
#: ``debug`` / ``info`` / ``warn`` / ``error``.  Empty (the default)
#: writes no event file; traced runs still stream at ``info`` (see
#: :func:`effective_log_level`) so every traced artifact set carries its
#: event tail.  With the stream inactive every emit site is one
#: module-global None-check (same contract as ``trace``/``profile``);
#: WARN+ events always reach the stdlib logger regardless.
log_level = os.environ.get("DAMPR_TPU_LOG", "").strip().lower()

#: Bound on the structured event log: past this many lines
#: ``events.jsonl`` is compacted to the newest entries (tmp + atomic
#: rename, the ``history.jsonl`` durability contract).  0 disables the
#: on-disk stream entirely (WARN+ still mirrors into the flight
#: recorder ring).
log_events_max = int(os.environ.get("DAMPR_TPU_LOG_EVENTS_MAX", "4096"))


def effective_log_level():
    """The structured-log level actually in force: the explicit
    ``log_level``, or ``info`` for traced runs (a traced artifact set
    should include its event tail), or "" = no on-disk event stream."""
    if log_level:
        return log_level
    if trace:
        return "info"
    return ""


#: Regression sentry (dampr_tpu.obs.sentry): trailing-window size for
#: the MAD anomaly check over the per-fingerprint telemetry series —
#: the newest point is judged against up to this many prior points of
#: the same plan fingerprint (at least 3 required).  0 disables the
#: finalize-time sentry check entirely (``dampr-tpu-sentry`` still
#: works post-hoc with an explicit ``--window``).
sentry_window = int(os.environ.get("DAMPR_TPU_SENTRY_WINDOW", "8"))

#: Robust z-score threshold for the sentry: a metric whose deviation
#: from the baseline window's median exceeds this many scaled MADs (in
#: the metric's bad direction) is flagged as a regression.
sentry_mad_threshold = float(os.environ.get("DAMPR_TPU_SENTRY_MAD", "3.5"))

#: Live fleet dashboard (dampr_tpu.obs.top / ``dampr-tpu-top``): refresh
#: cadence in milliseconds between endpoint polls.
top_refresh_ms = int(os.environ.get("DAMPR_TPU_TOP_REFRESH_MS", "1000"))

# ---------------------------------------------------------------------------
# Pipeline service daemon (dampr_tpu.serve / ``dampr-tpu-serve``)
# ---------------------------------------------------------------------------

#: Daemon HTTP port (``dampr-tpu-serve``).  A busy port probes upward
#: (same degradation contract as the metrics endpoint); 0 asks the OS
#: for an ephemeral port (tests).
serve_port = int(os.environ.get("DAMPR_TPU_SERVE_PORT", "9400"))

#: Daemon bind address.  Loopback by default: the wire is pickle, so
#: the protocol is trusted-client (docs/serve.md) — exposing it wider
#: is an explicit operator decision.
serve_host = os.environ.get("DAMPR_TPU_SERVE_HOST", "127.0.0.1")

#: Concurrent job slots: how many per-job worker subprocesses the
#: daemon runs at once.  Queued jobs dispatch deficit-round-robin
#: across tenants as slots free.
serve_workers = int(os.environ.get("DAMPR_TPU_SERVE_WORKERS", "2"))

#: Per-tenant admission byte budget: the sum of estimated input bytes a
#: tenant's queued + running jobs may reserve.  A submission past it is
#: rejected with the coded ``serve-reject`` event (reason ``budget``)
#: instead of queueing unboundedly; a finished or cancelled job
#: releases its reservation immediately.
serve_tenant_budget = int(os.environ.get("DAMPR_TPU_SERVE_BUDGET",
                                         str(2 * 1024 ** 3)))

#: Deficit-round-robin quantum (bytes): the byte allowance each tenant's
#: deficit counter earns per scheduling round.  Smaller = finer-grained
#: fairness between tenants with very different job sizes.
serve_quantum = int(os.environ.get("DAMPR_TPU_SERVE_QUANTUM",
                                   str(64 * 1024 ** 2)))

#: Per-tenant queue depth: submissions past this many queued jobs are
#: rejected (reason ``queue-full``) — backpressure at the door, not an
#: unbounded queue.
serve_queue_depth = int(os.environ.get("DAMPR_TPU_SERVE_QUEUE_DEPTH", "16"))

#: Per-job wall-clock timeout (milliseconds): past it the daemon
#: SIGTERMs the job's worker (which walks the crashdump path, so the
#: tenant still gets a schema-valid artifact), then SIGKILLs a
#: straggler.  0 = no timeout.  A client may pass a tighter per-job
#: ``timeout_s`` at submit.
serve_job_timeout_ms = int(os.environ.get("DAMPR_TPU_SERVE_JOB_TIMEOUT_MS",
                                          "600000"))

#: Graceful-drain deadline (milliseconds): on SIGTERM (or POST /drain)
#: the daemon stops admitting, finishes everything already admitted,
#: and terminates whatever is still running when this deadline fires.
serve_drain_ms = int(os.environ.get("DAMPR_TPU_SERVE_DRAIN_MS", "30000"))

#: Whether serve workers run traced (DAMPR_TPU_TRACE=1 in the job
#: environment).  On (default) so a killed or crashed tenant job always
#: leaves a schema-valid ``crashdump.json`` under its job directory —
#: the isolation contract's evidence trail.  Turn off only to shave the
#: trace plane's overhead from high-rate serving.
serve_trace = os.environ.get("DAMPR_TPU_SERVE_TRACE", "1").lower() not in (
    "0", "false", "no", "off", "")

#: How many terminal job records (and their job directories) the daemon
#: retains; older ones are evicted with a coded ``serve-evict`` event.
serve_jobs_keep = int(os.environ.get("DAMPR_TPU_SERVE_JOBS_KEEP", "256"))

#: Daemon state directory (job payloads, results, event log).  Empty
#: (default) resolves to ``<scratch_root>/serve`` at daemon start.
serve_dir = os.environ.get("DAMPR_TPU_SERVE_DIR", "")

#: Set (to 1) by the daemon in every worker's environment — this is how
#: ``reuse_enabled()`` resolves the "auto" reuse mode ON inside served
#: jobs and OFF everywhere else.  Not an operator knob.
serve_active = os.environ.get("DAMPR_TPU_SERVE_ACTIVE", "0").lower() not in (
    "0", "false", "no", "off", "")

#: Partition-size threshold (bytes) above which a single-input reduce streams
#: a k-way merge over hash-sorted runs instead of materializing the partition
#: (groups then arrive in hash order, not key order).  None = use
#: max_memory_per_stage.
streaming_reduce_threshold = None
