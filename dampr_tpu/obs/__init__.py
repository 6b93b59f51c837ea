"""Observability: run-scoped trace spans + per-run metrics.

Enable with ``settings.trace = True`` (env ``DAMPR_TPU_TRACE=1``).  Off
— the default — every instrumentation site costs one module-global
``None`` check, so the engine's hot loops are unaffected.  On, each run
records spans at the hot engine boundaries and persists two artifacts
under ``<scratch_root>/<run>/trace/`` (``settings.trace_dir`` overrides
the root):

**trace.json — the timeline.**  Chrome trace-event JSON (the JSON Array
Format with a ``traceEvents`` envelope).  Load it in Perfetto: open
https://ui.perfetto.dev and drag the file in (chrome://tracing works
too).  Lanes (``tid`` + ``thread_name`` metadata) map to the engine's
concurrency units: one track per map slot (pool worker), per overlapped
codec producer thread, per reduce worker, per merge generation.  Span
categories (event ``cat``):

- ``codec`` — one span per produced codec window (decompress + tokenize/
  parse) on the producer thread's lane;
- ``fold`` — map-side partial/final segment folds;
- ``stall`` — a fold consumer blocked on its producer (the per-slot view
  of devtime's ``codec_wait`` union);
- ``spill`` / ``hbm`` — budget-pressure block spills (on the background
  writer pool's lanes when ``settings.spill_write_threads`` > 0); HBM
  h2d puts and device->host offloads;
- ``spill_queue`` — a queued write's enqueue->write-start latency on its
  writer lane (how long the spill sat behind the pool's backlog);
- ``io_wait`` — a fold/register thread blocked on writer-pool
  backpressure (``writer-backpressure``), or a merge/final-read consumer
  outrunning its frame prefetch (``read-wait``);
- ``merge`` — spill-lean merge generations, streamed merge runs, k-way
  read rounds, compaction markers;
- ``collective`` — mesh keyed folds, byte exchanges, global sums;
- ``checkpoint`` — resume persist/restore/plan/gc decisions;
- ``job`` / ``stage`` — per-job spans on worker lanes; one span per
  stage on the ``stages`` lane;
- ``scan`` / ``device`` — the lowered scan's phases, one span per window
  or batch (:func:`dampr_tpu.ops.devtime.phase`): its host half
  (``bounds``, ``line-ids``, ``pad``, ``decode``, ``absorb-misses``) and
  its device half (``dispatch``, ``wait``, ``fetch``);
- ``run`` — the run's own steps around the stages (``setup``, ``gc``,
  ``cleanup``, ``finalize``, ``history``);
- ``retry`` — instant markers for re-executed jobs.

The emitted subset is documented (and CI-validated) by
``docs/trace_schema.json`` + ``tools/validate_trace.py``.

**The device trace.**  Any ``jax.profiler`` session that is collecting —
``settings.profile_dir``, a user's ``jax.profiler.trace``, a benchmark's
traced window — also gets the engine's spans, with or without
``settings.trace``: each span site opens a profiler annotation named
``dampr.<category>.<name>`` (``dampr.stage.s1:map``,
``dampr.device.wait``, ``dampr.handoff.table-probe``) on the trace's
host plane, on the profiler's clock, beside the device's ops.  Names are
stable; per-call values stay in ``trace.json``'s args.  The device
programs carry the names of their work (``jit_token_fold``,
``jit_table_probe``, ``jit_mesh_fold``, ...), so the trace's XLA modules
say which program ran.  With no session and no tracer a span site costs
one C++ ``is_enabled()`` check.

**stats.json — the summary** (schema ``dampr-tpu-stats/1``), also
returned in-memory from every run — traced or not — via
``ValueEmitter.stats()``:

- ``stages[]`` — per stage: ``kind``, ``jobs``, ``records_in/out``,
  ``bytes_in/out``, ``spill_count``/``spill_bytes`` (causal attribution:
  charged to the stage whose pressure evicted the block),
  ``merge_gens``/``merge_gen_bytes``, ``retries``, ``seconds``;
- ``devtime`` — run-scoped device/transfer/codec/codec_wait seconds and
  the lowered scan's wall-clock unions ``scan_feed``/``scan_wait``/
  ``scan_drain`` (epoch/delta snapshots of :mod:`dampr_tpu.ops.devtime`);
- ``overlap`` — configured windows, ``stall_fraction`` (codec_wait /
  wall: the codec time still on the critical path), peak in-flight bytes;
- ``io`` — the async spill subsystem's shape: ``spill_write_bytes/
  seconds/mbps`` (post-codec disk bandwidth, writer-pool thread-seconds),
  ``spill_read_bytes/seconds/mbps`` (frame reads + inflate),
  ``io_wait_seconds/fraction`` (total) and ``io_wait_write_seconds/
  fraction`` (fold-side writer backpressure only — the stall the pool
  exists to eliminate), ``writer_threads``, ``read_prefetch``,
  ``inflight_peak_bytes``;
- ``store`` — spill/merge/HBM-tier totals; ``mesh`` — collective fold/
  exchange counts and bytes; ``retries``; ``totals``;
- ``trace_file`` / ``stats_file`` — artifact paths (None untraced).

Surfacing: ``dampr-tpu-stats <run>`` pretty-prints a persisted summary;
``dampr-tpu-wc`` / ``dampr-tpu-tfidf`` accept ``--stats``; the TF-IDF
bench emits per-trial spill/trace info and the artifact paths in its
JSON line.

**The live metrics plane** (``settings.metrics_interval_ms`` /
``DAMPR_TPU_METRICS_MS``; traced runs sample at 100 ms even when unset):
a run-scoped registry (:mod:`.metrics`: counters, gauges, histograms —
off costs one None-check per site, same contract as tracing) whose
background sampler (:mod:`.sampler`) snapshots the load-bearing gauges
— budget occupancy, writer-pool queue depth/in-flight bytes, overlap
slots live/stalled, HBM residency, records/bytes throughput, merge
fan-in — into an in-memory time series.  Consumers:

- **counter tracks**: the series embed in ``trace.json`` as Chrome
  ``"ph":"C"`` events, so Perfetto renders each gauge as a counter
  track under the span lanes;
- **live progress** (:mod:`.progress`, ``settings.progress`` /
  ``DAMPR_TPU_PROGRESS=1``): one updating console line per stage —
  records/s, MB/s, spill backlog, ETA;
- **Prometheus text** (:mod:`.promtext`): ``dampr-tpu-stats --prom``
  renders a completed run in text-exposition format; ``render()``
  behind any HTTP handler serves a live one;
- **flight recorder** (:mod:`.flightrec`): a bounded ring of recent
  spans + samples, flushed to ``<run>/trace/crashdump.json`` on the
  kill/exception path — a schema-valid mini-trace (Perfetto-loadable,
  ``tools/validate_trace.py``-checked) whose last samples show e.g. the
  writer-pool queue state at death.  ``dampr-tpu-stats`` exits non-zero
  on a run directory containing one.

``stats()`` gains a ``metrics`` section (final counters, per-series
last/peak, histogram summaries) including the sampler's self-accounting:
sample count, series drops, and the ``overhead`` self-metric (sampler
wall / run wall — the plane measures its own cost).

**The diagnosis layer** (attribution + analysis over everything above):

- **per-operator profiler** (:mod:`.profile`, ``settings.profile`` /
  ``DAMPR_TPU_PROFILE=1``): fused stages attribute wall time and record
  counts to the INDIVIDUAL user ops they were built from (plan fusion
  rides provenance on the fused node); device stages decompose into
  build/h2d/compute/d2h sub-phases; ``stats()`` gains a ``profile``
  section with per-stage coverage.  Off = one None-check per site,
  hoisted to one per job in the hot loops.
- **critical-path analysis** (:mod:`.critpath`): walks the span
  timeline and names the resource that bounds each stage's wall window
  (codec / fold / spill-queue / io-read / merge / device / transfer /
  overlap-stall / mesh / host-compute) via wall-clock interval unions —
  ``stats()["critpath"]`` carries a dominant-bottleneck verdict per
  executed stage and for the whole run.
- **run-history corpus** (:mod:`.history`): every finalized run appends
  one compact record (plan fingerprint + shapes, per-stage IO, critpath
  verdicts, per-op profile, settings snapshot) to a bounded, crash-safe
  JSONL under ``<scratch_root>/<run>/history.jsonl``; ``plan/cost.py``
  adapts from medians over matching runs instead of one stats.json.
- **doctor** (:mod:`.doctor`, ``dampr-tpu-doctor``): reads a run's
  artifacts back and prints a ranked diagnosis — each finding ties a
  bottleneck verdict to concrete ``settings`` knobs; ``--diff A B``
  compares runs, ``--json`` emits the ``docs/doctor_schema.json``
  report.

**The fleet plane** (multi-process runs — docs/parallel.md):

- **rank-tagged telemetry**: every artifact carries a ``process``
  block (``process_id``/``num_processes`` via ``mesh.rank_info()``);
  rank 0 keeps the legacy ``<run>/trace/`` layout, rank k writes
  ``<run>/trace/rank<k>/``, and a killed non-zero rank's crashdump
  lands as ``crashdump.rank<k>.json`` (``dampr-tpu-stats`` scans every
  rank's dump for its exit-3 detection);
- **merged timeline** (:mod:`.fleet`): per-rank traces fold into one
  Perfetto document (one process lane per rank) aligned on the
  ``init_distributed`` barrier-timestamp handshake — no wall-clock
  trust; ``stats()["fleet"]`` carries per-rank totals, the rank x rank
  exchange send/recv matrices, and per-collective-step skew
  (entry-spread over step wall), which names the ``straggler_rank``;
- **straggler diagnosis**: :mod:`.critpath` gains the ``skew``
  resource (injected post-merge via ``apply_skew``) and
  ``dampr-tpu-doctor`` emits fleet verdicts mapping skew to concrete
  knobs; ``dampr-tpu-stats --fleet`` renders (and idempotently
  re-merges) the section;
- **live metrics endpoint** (:mod:`.serve`, ``settings.metrics_port``):
  a stdlib HTTP thread per rank serving ``/metrics`` (Prometheus text,
  rank-labeled) and ``/healthz`` while the run is in flight — rank k
  binds ``metrics_port + k``.

The consolidated guide — schemas, Perfetto counter-track how-to,
Prometheus scrape example, crashdump shape, the diagnosis taxonomy,
the fleet layer, the CI perf gate — is ``docs/observability.md``.

For a profiler-grade XLA kernel timeline (HLO names, TPU counters) use
the existing escape hatch instead: ``settings.profile_dir`` wraps the
run in ``jax.profiler.trace`` for TensorBoard/xprof.

Layering: :mod:`.trace` is the span recorder (``Tracer``, module-level
``span``/``instant``/``complete``/``timed_iter``); :mod:`.metrics` is
the metric registry (module-level ``counter_add``/``gauge_set``/
``observe``/``register_gauge``); :mod:`.sampler`, :mod:`.progress`,
:mod:`.promtext`, :mod:`.flightrec` consume it; :mod:`.export`
serializes (``write_trace``, ``write_stats``, ``load_stats``,
``format_summary``, ``load_series``).  ``MTRunner.run`` owns the
lifecycle: it starts tracer/registry/sampler/recorder, builds the
summary either way, and persists the files for traced runs.
"""

from .trace import Tracer, complete, enabled, instant, now, span  # noqa: F401
from . import export  # noqa: F401
from . import metrics  # noqa: F401
