"""Logical plan optimizer: the layer between graph construction and the
runner.

The DSL (:mod:`dampr_tpu.dampr`) compiles every chained call into its own
:class:`~dampr_tpu.graph.StageNode` — ``construction order is the
schedule`` — so an unoptimized ``memory(xs).map(f).map_values(g).filter(h)
.fold_by(k, op)`` would pay a full materialize boundary per call.  This
package rewrites the stage list before execution:

- :mod:`.ir` — plan-level views over the :class:`~dampr_tpu.graph.Graph`:
  consumer maps, mapper-chain flattening/composition, barrier detection,
  structural signatures (the idempotence witness).
- :mod:`.passes` — the pass pipeline: **map fusion** (chains of pure
  per-record ``GMap`` stages whose intermediate Source has a single
  consumer collapse into one composed mapper, preserving the tail's
  combiner/shuffler), **combiner hoisting** (an identity stage that only
  carries a map-side combiner folds into its producer), **sink fusion**
  (pure record chains compose into the sinker), and **dead-stage
  elimination** (stages unreachable from any requested output or sink are
  dropped).
- :mod:`.cost` — stats-driven adaptation: prior-run ``stats.json``
  summaries (per-stage records/bytes) size the run's partition count and
  per-stage block batch sizes, with safe static defaults when no history
  exists or the plan shape changed.
- :mod:`.explain` — the ``PBase.explain()`` surface: renders the
  before/after plan with fusion decisions and cost annotations.

Every rewrite is value-semantic: shared ``StageNode`` objects are never
mutated (handles stay freely shareable); changed stages are fresh nodes.

Wiring: ``dampr.py`` ``run()`` and ``MTRunner.run()`` both call
:func:`apply_to_runner` (idempotent — first caller wins), gated by
``settings.optimize`` (env ``DAMPR_TPU_OPTIMIZE``) with per-rule kill
switches (``settings.plan_fuse`` / ``plan_fuse_sinks`` / ``plan_dead`` /
``plan_adapt``).  The runner emits a ``plan`` trace span and a ``plan``
section in ``em.stats()`` describing stages before/after and the rules
that fired.  See ``docs/plan.md``.
"""

import time

from .. import settings
from ..obs import trace as _trace
from . import cost, explain, ir, lower, passes, pipeline
from .explain import explain_text
from .ir import graph_signature
from .passes import optimize

__all__ = ["optimize", "apply_to_runner", "explain_text", "graph_signature",
           "ir", "passes", "cost", "explain", "lower", "pipeline"]


def empty_report(graph, enabled):
    n = ir.executed_stage_count(graph)
    return {
        "enabled": enabled,
        "stages_before": n,
        "stages_after": n,
        "rules": {"fuse_maps": 0, "hoist_combiners": 0, "fuse_sinks": 0,
                  "dead_stages": 0},
        "fused": [],
        "dead": [],
        "adaptive": {"applied": False, "reason": "disabled"},
        "cost": cost.empty_cost_section("optimizer off"),
        "lowering": lower.empty_section(False),
        "shuffle": lower.empty_shuffle_section(False),
        "analysis": {"enabled": False, "stages": [], "diagnostics": [],
                     "counts": {"error": 0, "warn": 0, "info": 0}},
        "device_stages": 0,
        "seconds": 0.0,
    }


def apply_to_runner(runner, outputs):
    """Optimize ``runner.graph`` in place for the requested ``outputs`` and
    attach the plan report as ``runner.plan_report``.

    Idempotent: a runner that already carries a report is left alone, so
    the DSL entry points and ``MTRunner.run`` can both invoke it without
    double-rewriting.  Duck-typed (needs ``.graph``; everything else is
    ``getattr`` with defaults) so custom runner classes keep working.
    Returns the report (or None when the runner has no graph).
    """
    if getattr(runner, "plan_report", None) is not None:
        return runner.plan_report
    graph = getattr(runner, "graph", None)
    if graph is None or not hasattr(graph, "stages"):
        return None
    with _trace.span("plan", "apply"):
        return _apply(runner, graph, outputs)


def _apply(runner, graph, outputs):
    t0 = time.perf_counter()
    if not settings.optimize:
        report = empty_report(graph, enabled=False)
    else:
        graph, report = optimize(graph, outputs)
        runner.graph = graph
        cost.adapt(runner, graph, report)
        # Learned-cost-model layer (plan/model.py): prices this plan
        # with per-operator fits over the corpus and may override the
        # median sizing; every choice + predicted-vs-static delta lands
        # in report["cost"].  DAMPR_TPU_COST_MODEL=0 records the kill
        # switch and leaves the median decisions untouched.
        cost.apply_model(runner, graph, report)
    # Device lowering runs on BOTH legs (a placement decision over
    # whatever stage list executes, not a graph-shape rewrite): assign
    # each stage its execution target, stats history pinning tiny stages
    # to host.
    lower.apply(runner, outputs, report)
    # Host-vs-mesh shuffle routing for the redistribution stages the
    # lowering pass left on host: a plan-level choice (explicit settings
    # win, auto decides from the history corpus) the runner's dispatch
    # consults when it exchanges partitions.
    lower.apply_shuffle(runner, report)
    # Streamed-edge analysis (plan/pipeline.py): which stage barriers the
    # pipelined executor may dissolve, decided over the stage list that
    # will EXECUTE (after fusion/lowering/shuffle routing, on both
    # optimizer legs).  Decisions land in report["pipeline"] always;
    # runner dispatch hints only when settings.pipeline is on.
    pipeline.apply(runner, outputs, report)
    # Static analysis (dampr_tpu.analyze, settings.analyze): per-stage
    # purity/determinism verdicts + coded diagnostics over the stage
    # list that will EXECUTE, recorded in the report's "analysis"
    # section (rendered by explain(), shipped in stats()["plan"]).
    # Fast bytecode-only classification here — the pickle probe and the
    # randomized associativity probe run from validate()/lint (and the
    # multi-process pre-flight check), not on every run.
    if settings.analyze:
        from ..analyze import validate as _av

        try:
            report["analysis"] = _av.report_section(
                getattr(runner, "graph", graph),
                probe_traceable=settings.lower_enabled())
        except Exception:  # noqa: BLE001 - analysis never fails a run
            report["analysis"] = _av.empty_section()
    else:
        report["analysis"] = {
            "enabled": False, "stages": [], "diagnostics": [],
            "counts": {"error": 0, "warn": 0, "info": 0}}
    # Shape records ride into stats.json so the NEXT run's cost layer can
    # match its plan against this run's measurements.
    report["stage_shapes"] = ir.stage_shapes(getattr(runner, "graph", graph))
    report["seconds"] = round(time.perf_counter() - t0, 6)
    try:
        runner.plan_report = report
    except AttributeError:
        pass
    return report
