"""The fluent DSL: lazy, value-semantic pipeline construction.

Parity surface: reference dampr/dampr.py (977 LoC) — ``Dampr`` entrypoints
(memory/text/json/read_input/from_dataset, 845-912), ``PMap`` chainable
collection ops (85-652), ``ARReduce`` associative reduces (654-709),
``PReduce`` general reduces (711-766), ``PJoin`` (768-829), ``ValueEmitter``
(19-51), multi-output ``Dampr.run`` (914-945).

Semantics preserved exactly: handles are immutable (every op returns a new
handle over a copied graph), ``a_group_by`` installs a map-side combiner,
``join`` unions graphs deduping shared prefixes, results stream back
key-sorted.

Stage granularity: every chained call compiles to its own
:class:`~dampr_tpu.graph.StageNode` — the graph IS the user's logical
plan, one node per op.  Fusing consecutive per-record ops into one
executed map stage is the job of the logical plan optimizer
(:mod:`dampr_tpu.plan`, ``settings.optimize``, on by default), which
``run()`` invokes before handing the graph to the runner; ``explain()``
renders the before/after plan.  ``checkpoint()`` is the explicit
materialization barrier the optimizer never fuses across.

TPU-native difference: ``a_group_by``/``fold_by``/``count``/``sum``/``mean``
carry :class:`~dampr_tpu.ops.segment.AssocOp` descriptors, so recognized
associative folds execute as device segment kernels end-to-end instead of
per-record Python.
"""

import itertools
import json
import logging
import random
import sys
import threading
import time
import weakref

from . import faults as _faults
from . import settings
from .obs import trace as _trace
from .base import (AssocFoldReducer, ComposedMapper, Filter, FlatMap, Inspect,
                   KeyedInnerJoin, KeyedLeftJoin, KeyedOuterJoin, KeyedReduce,
                   Map, MapAllJoin, MapCrossJoin, MapKeys, MapValues, Mapper,
                   PartialReduceCombiner, Prefix, Reducer, Rekey, Sample,
                   StreamMapper, StreamReducer, Streamable, Suffix, ValueMap,
                   _identity, _shared_instance_deepcopy)
from .dataset import CatDataset, Chunker
from .graph import GMap, Graph, Source
from .inputs import MemoryInput, PathInput, UrlsInput
from .ops import segment
from .runner import MTRunner


class RunStats(list):
    """Per-run metrics handle: a list of per-stage dicts (the historical
    ``ValueEmitter.stats`` shape, kept for compatibility) that is also
    *callable* — ``emitter.stats()`` returns the full run summary dict
    (the ``stats.json`` payload: stages, devtime, spill/merge/mesh totals,
    overlap stall fraction, retry counts, trace file location).  See
    :mod:`dampr_tpu.obs`."""

    def __init__(self, stages=(), summary=None):
        super(RunStats, self).__init__(stages)
        self.summary = summary if summary is not None else {}

    def __call__(self):
        return self.summary

    @property
    def trace_file(self):
        """Path of the run's Chrome trace-event JSON (None untraced)."""
        return self.summary.get("trace_file")

    @property
    def stats_file(self):
        """Path of the persisted stats.json (None untraced)."""
        return self.summary.get("stats_file")


class ValueEmitter(object):
    """Reads values from a completed run — the shell-friendly result handle
    (reference dampr.py:19-51).  ``stats`` holds the run's per-stage metrics
    (jobs, records, seconds) and, called as ``stats()``, the full run
    summary — observability the reference lacks."""

    def __init__(self, dataset):
        self.dataset = dataset
        self.stats = RunStats()

    def stream(self):
        for _k, v in self.dataset.read():
            yield v

    def read(self, k=None):
        if k is None:
            return list(self.stream())
        return list(itertools.islice(self.stream(), k))

    def __iter__(self):
        return self.stream()

    def delete(self):
        self.dataset.delete()




log = logging.getLogger("dampr_tpu.dampr")


def _drive_runner(make_runner, sources, resume):
    """Execute a run, with crash auto-resume when ``resume="auto"``.

    Auto mode behaves like ``resume=True`` (durable per-stage
    checkpoints) plus a whole-run retry loop: a failed run rebuilds a
    FRESH runner (the old one's store/obs state died with it) and
    re-executes — :mod:`dampr_tpu.resume` restores every stage whose
    manifest survived, so only work past the last durable checkpoint
    repeats, and results are byte-identical to a cold run (the resume
    exactness contract).  Fatal failures (kills, MemoryError,
    quarantine overflow) never auto-resume; transient-classified
    failures back off with jitter between attempts.  Returns
    ``(runner, datasets)``."""
    from . import plan as _plan

    auto = isinstance(resume, str) and resume.lower() == "auto"
    attempts = (max(0, settings.run_retries) + 1) if auto else 1
    prev_quarantine = None
    for attempt in range(attempts):
        with _trace.span("run", "setup"):
            runner = make_runner()
        if prev_quarantine is not None and getattr(
                runner, "_quarantine", None) is not None:
            # The retry adopts the failed attempt's quarantine: its
            # committed records (whose stages may now restore from
            # checkpoints without re-running) keep their budget charge
            # and audit lines — the fresh runner's constructor had
            # truncated the sink, so re-materialize it.
            runner._quarantine = prev_quarantine
            prev_quarantine.rewrite_sink()
        _plan.apply_to_runner(runner, sources)
        try:
            return runner, runner.run(sources)
        except BaseException as e:
            prev_quarantine = getattr(runner, "_quarantine", None)
            kind = _faults.classify(e)
            if kind == "fatal" or attempt + 1 >= attempts:
                raise
            delay = _faults.backoff(attempt) if kind == "transient" else 0.0
            log.warning(
                "run failed (%s: %s — classified %s); auto-resume "
                "attempt %d/%d re-executes from the last durable "
                "checkpoint%s", type(e).__name__, str(e)[:300], kind,
                attempt + 2, attempts,
                " in %.0f ms" % (delay * 1000) if delay else "")
            if delay:
                time.sleep(delay)


#: Every live pipeline handle (weakly held).  ``dampr-tpu-lint`` uses
#: this to discover the pipelines a linted module constructed at import
#: time without running anything; the DSL itself never reads it.
_live_handles = weakref.WeakSet()


class PBase(object):
    def __init__(self, source, pmer):
        assert isinstance(source, Source)
        self.source = source
        self.pmer = pmer
        _live_handles.add(self)

    def run(self, name=None, **kwargs):
        """Evaluate the composed graph; returns a ValueEmitter (its ``stats``
        attribute carries per-stage timing/record counters).

        ``resume=True`` makes the run durable: each completed stage
        checkpoints its output under the run's scratch root, and a rerun
        with the SAME ``name`` skips every stage whose checkpoint is still
        valid (see :mod:`dampr_tpu.resume`).  Requires an explicit name —
        an auto-generated one can never match a previous run.

        ``resume="auto"`` adds crash recovery on top: a run that fails
        with a non-fatal error re-executes in place (up to
        ``settings.run_retries`` times, transient failures backing off
        with jitter) from its last durable checkpoint manifest, and the
        result is byte-identical to a cold run.  Fatal failures
        (``MemoryError``, kills, quarantine-budget overflow) never
        auto-resume.  See ``docs/robustness.md``.

        Input-file identity is (path, size, mtime_ns) plus a content hash
        of the first and last 64KB.  An edit that preserves size AND
        resets mtime AND touches only the interior of a file >128KB is
        therefore undetectable without a full read; pass a fresh ``name``
        (or delete the scratch root) after such an edit.

        Starting any run under a name garbage-collects scratch blocks no
        checkpoint references (skipped while another live process is
        mid-run under the same name), so finish reading (or materialize)
        any OutputDataset from a previous run of the same name before
        rerunning it.
        """
        if kwargs.get("resume") and name is None:
            raise ValueError(
                "resume=True requires a stable run name: run(name=..., "
                "resume=True)")
        if name is None:
            name = "dampr/{}".format(random.random())
        if settings.seed is not None:
            _reset_sample_rngs()
        # The logical plan optimizer (dampr_tpu.plan) rewrites the stage
        # list before execution (applied inside _drive_runner, which
        # also implements resume="auto" crash recovery: a failed run
        # re-executes from its last durable checkpoint manifest).
        runner, ds = _drive_runner(
            lambda: self.pmer.runner(name, self.pmer.graph, **kwargs),
            [self.source], kwargs.get("resume"))
        em = ValueEmitter(ds[0])
        em.stats = RunStats(
            [s.as_dict() for s in getattr(runner, "stats", [])],
            getattr(runner, "run_summary", None))
        return em

    def explain(self, name=None):
        """Render this pipeline's logical plan — the constructed stage
        list, the optimizer's rewrite (fusion decisions, eliminated
        stages), and the cost layer's adaptive annotations — WITHOUT
        executing anything.  ``name`` points at a run name whose persisted
        stats history the adaptive layer would consume (see docs/plan.md).
        """
        from . import plan as _plan

        return _plan.explain_text(self.pmer.graph, [self.source], name=name)

    def validate(self, resume=False, num_processes=1, probe=True):
        """Pre-flight diagnostics for this pipeline — the
        ``dampr-tpu-lint`` surface as an API (docs/analysis.md), WITHOUT
        executing anything.  Returns the ordered diagnostic list
        (:class:`dampr_tpu.analyze.Diagnostic`, errors first; empty =
        clean).  Runs the full probe set — serialization, randomized
        associativity, jax traceability — regardless of
        ``settings.analyze``: an explicit call is its own opt-in.
        ``num_processes > 1`` promotes unpicklable captures to errors
        (rank dispatch WILL fail on them); ``resume=True`` adds the
        checkpoint fingerprint-stability checks; ``probe=False`` keeps
        it to the fast bytecode-only classification."""
        from .analyze import validate as _av

        return _av.validate_graph(
            self.pmer.graph, resume=resume,
            num_processes=num_processes, probe_traceable=probe,
            probe_assoc=probe, probe_pickle=probe)

    def read(self, k=None, **kwargs):
        """Shorthand for run() + read()."""
        return self.run(**kwargs).read(k)

    def submit(self, url, tenant="default", **kwargs):
        """Ship this composed pipeline to a ``dampr-tpu-serve`` daemon
        instead of running it in-process; returns a
        :class:`dampr_tpu.serve.RemoteJob` (``.wait()`` / ``.result()``
        / ``.read()`` / ``.cancel()``).  The plan travels validated and
        fingerprinted — an unpicklable capture fails fast client-side
        with the coded ``DTA401`` diagnostic, and identical in-flight
        submissions coalesce onto one run daemon-side.  See
        docs/serve.md."""
        from .serve.client import ServeClient

        return ServeClient(url).submit(self, tenant=tenant, **kwargs)


class _TopKBlocks(Mapper):
    """Per-chunk top-k candidate selection at block granularity: numeric
    1D value lanes select with one np.argpartition per block, then the
    tiny per-block winners merge through nlargest.  Non-block chunks and
    object/composite lanes stream through the same decorated-pair
    nlargest as the DSL's generic path — emitted candidate records are
    identical either way: ``(1, (x, x))``."""

    __deepcopy__ = _shared_instance_deepcopy

    def __init__(self, k):
        self.k = k

    def map(self, *datasets):
        import heapq

        import numpy as np

        from .blocks import pylist

        assert len(datasets) == 1
        ds = datasets[0]
        k = self.k
        if k <= 0:
            return
        if hasattr(ds, "iter_blocks"):
            blocks = [b for b in ds.iter_blocks() if len(b)]
            if all(b.values.dtype != object and b.values.ndim == 1
                   for b in blocks):
                cands = []
                for b in blocks:
                    v = b.values
                    if len(v) > k:
                        v = v[np.argpartition(v, len(v) - k)[len(v) - k:]]
                    cands.extend((x, x) for x in pylist(v))
                for p in heapq.nlargest(k, cands):
                    yield 1, p
                return
        it = (v for _k, v in ds.read())
        for p in heapq.nlargest(k, ((x, x) for x in it)):
            yield 1, p


class PMap(PBase):
    """A lazy collection.  Every chained op lands in the graph as its own
    stage node immediately; the plan optimizer (:mod:`dampr_tpu.plan`)
    re-fuses pure per-record chains into single executed map stages at
    ``run()`` time."""

    def __init__(self, source, pmer, agg=None):
        super(PMap, self).__init__(source, pmer)
        # Vestigial (pre-plan-optimizer API): per-record ops used to queue
        # here until the next checkpoint; they now land in the graph
        # immediately, so there are never pending ops.  The attribute is
        # kept because callers probe `.agg` truthiness to decide whether a
        # checkpoint is needed before handing the graph to a runner —
        # but PASSING pending mappers would silently drop them, so fail
        # loudly instead.
        assert not agg, (
            "PMap no longer queues pending mappers; chain ops through the "
            "DSL (each lands in the graph immediately) instead of passing "
            "agg")
        self.agg = []

    # -- stage plumbing ----------------------------------------------------
    def _add_mapper(self, mapper, options=None):
        assert isinstance(mapper, Streamable)
        source, pmer = self.pmer._add_mapper([self.source], mapper,
                                             options=options)
        return PMap(source, pmer)

    def _add_map(self, f):
        return self._add_mapper(Map(f))

    def _materialized_for_reduce(self):
        """A handle whose source a GReduce may consume directly.  Map-stage
        outputs carry the hash-routing/sorted-run invariants a reduce
        depends on by construction (the runner's ``feeds_reduce`` view);
        taps, sinks, and reduce outputs get an identity copy stage — the
        re-routing pass the alias provenance gate would force anyway
        (reduce outputs are registered under the reduce job's pid with
        whatever keys the reducer emitted)."""
        for stage in self.pmer.graph.stages:
            if stage.output == self.source:
                if isinstance(stage, GMap):
                    return self
                break
        source, pmer = self.pmer._add_mapper([self.source], Map(_identity))
        return PMap(source, pmer)

    def checkpoint(self, force=False, combiner=None, options=None):
        """Install an EXPLICIT materialization barrier: the stage's output
        is computed and pinned at this boundary, and the plan optimizer
        never fuses across it (``options["barrier"]``).  Use it to share a
        sub-graph between branches (dedup happens in Graph.union) or to
        force a spill/merge boundary; a redundant barrier over an
        already-materialized input aliases at run time instead of copying.
        ``force`` is accepted for API compatibility (every checkpoint now
        materializes)."""
        opts = dict(options) if options else {}
        opts.setdefault("barrier", True)
        source, pmer = self.pmer._add_mapper(
            [self.source], Map(_identity), combiner=combiner, options=opts)
        return PMap(source, pmer)

    # -- per-record ops ----------------------------------------------------
    # Each queues a typed RecordOp (base.py): the engine executes chains of
    # these over whole batches — one tight loop per op per batch — instead
    # of per-record generator frames, and falls back to their stream()
    # lowering wherever a generator is needed.
    def map(self, f):
        """Map each value through ``f``."""
        return self._add_mapper(ValueMap(f))

    def map_values(self, f):
        """Map the second element of two-tuple values."""
        return self._add_mapper(MapValues(f))

    def map_keys(self, f):
        """Map the first element of two-tuple values."""
        return self._add_mapper(MapKeys(f))

    def prefix(self, f):
        """value -> (f(value), value)."""
        return self._add_mapper(Prefix(f))

    def suffix(self, f):
        """value -> (value, f(value))."""
        return self._add_mapper(Suffix(f))

    def filter(self, f):
        """Keep values where predicate holds."""
        return self._add_mapper(Filter(f))

    def flat_map(self, f):
        """Map values to iterables and flatten."""
        return self._add_mapper(FlatMap(f))

    def sample(self, prob):
        """Uniformly keep ``prob`` of records."""
        assert 0 <= prob <= 1.0
        return self._add_mapper(Sample(prob, _get_rand))

    def inspect(self, prefix="", exit=False):
        """Print records as they stream through (debug passthrough)."""
        ins = self._add_mapper(Inspect(prefix))
        if exit:
            ins.run()
            sys.exit(0)
        return ins

    # -- grouping ----------------------------------------------------------
    def group_by(self, key, vf=None):
        """General (non-associative) grouping; returns PReduce.  ``vf``
        defaults to the identity (records keep their value)."""
        pm = self._add_mapper(Rekey(key, vf))
        return PReduce(pm.source, pm.pmer)

    def a_group_by(self, key, vf=None):
        """Associative grouping: enables map-side combining before the
        shuffle (the combiner stage lands when the binop is known).
        ``vf`` defaults to the identity."""
        pm = self._add_mapper(Rekey(key, vf))
        return ARReduce(pm)

    def fold_by(self, key, binop, value=lambda x: x, **options):
        """Shortcut for ``a_group_by(key, value).reduce(binop)``."""
        return self.a_group_by(key, value).reduce(binop, **options)

    def fold_values(self, binop, **options):
        """Fold values by each record's EXISTING key — no re-key map pass.
        Blocks flow into the combine with their cached hash lanes and
        (numeric) value lanes intact, so the whole aggregation stays on the
        vectorized path with zero per-record Python.  Use after block
        mappers that already emit records keyed by the group key
        (ops.text.TokenCounts/DocFreq with ``pair_values=False``).  Beyond
        the reference surface: its fold_by always re-keys per record
        (reference dampr.py:406-410)."""
        return ARReduce(self).reduce(binop, **options)

    def sort_by(self, key, **options):
        """Globally sort values by a key function (results merge key-sorted).
        The re-key stage is a plain map node — a sort_by feeding further
        per-record ops fuses with them (mid-pipeline record order is not
        part of the contract; only the FINAL read merges key-sorted)."""
        return self._add_mapper(Rekey(key), options=options or None)

    def count(self, key=lambda x: x, **options):
        """Count values per key — compiles to a device segment-sum."""
        return self.a_group_by(key, lambda v: 1).reduce(segment.SUM, **options)

    def mean(self, key=lambda x: 1, value=lambda x: x, **options):
        """Per-key mean: the (sum, count) pair IS the value column — int
        and float values build a 2D composite lane the segment sum kernels
        fold in one vectorized pass (blocks._tuple_column); anything else
        falls back to an exact pairwise object-lane fold.  Same observable
        behavior as the reference's per-record tuple binop (ref
        dampr.py:445-458), different execution: the pair never exists as
        a per-record Python object on the numeric path."""
        def _pair(v):
            x = value(v)
            # count carries the value's own lane dtype so the pair stays
            # type-uniform (a mixed (float, int) tuple would force the
            # object lane); ints keep exact int64 sums.
            return (x, 1.0) if type(x) is float else (x, 1)

        def _avg(x):
            return (x[0], x[1][0] / float(x[1][1]))

        return (self.a_group_by(key, _pair)
                .reduce(segment.PAIR_SUM, **options)
                .map(_avg))

    def len(self):
        """Count all items in the collection.  The map side never touches
        records: text chunks count owned newlines, block-backed chunks sum
        block lengths (CountRecords).  Valid at ANY point in a chain —
        the handle's source always refers to the realized record stream
        (ops are stage nodes, never pending)."""
        def _sum_counts(groups):
            totals = [c for _k, cs in groups for c in cs]
            return ((1, sum(totals)),) if totals else ()

        from .ops.text import CountRecords
        return (self.custom_mapper(CountRecords())
                .partition_reduce(_sum_counts)
                .map(lambda x: x[1]))

    def topk(self, k, value=None):
        """Top-k values by a comparable sort key.  Identity-keyed
        block-backed partitions select candidates with one np.argpartition
        per block — no per-record Python; everything else decorates once
        and takes ``heapq.nlargest`` per partition.  Candidates from all
        partitions merge through one global nlargest.  Ordering criterion
        is the (sort_key, value) pair, so tie behavior matches the
        reference's heap of pairs (ref dampr.py:621-652)."""
        import heapq

        vf = value

        def _cands(values):
            pairs = (((x, x) for x in values) if vf is None
                     else ((vf(x), x) for x in values))
            return ((1, p) for p in heapq.nlargest(k, pairs))

        def _select(groups):
            cands = (p for _one, ps in groups for p in ps)
            return ((p[1], 1) for p in heapq.nlargest(k, cands))

        if vf is None:
            head = self.custom_mapper(_TopKBlocks(k))
        else:
            head = self.partition_map(_cands)
        return head.partition_reduce(_select).map(lambda x: x[0])

    # -- custom operators --------------------------------------------------
    def custom_mapper(self, mapper, name=None, **options):
        """Install a user Mapper instance as its own stage (low-level).
        A bare Streamable (no ``map``) is wrapped so the stage can drive
        it over its input dataset."""
        if isinstance(mapper, Streamable) and not isinstance(mapper, Mapper):
            mapper = ComposedMapper(Map(_identity), mapper)
        assert isinstance(mapper, Mapper)
        source, pmer = self.pmer._add_mapper([self.source], mapper,
                                             options=options or None)
        return PMap(source, pmer)

    def custom_reducer(self, reducer, name=None, **options):
        """Install a user Reducer instance (low-level)."""
        assert isinstance(reducer, Reducer)
        me = self._materialized_for_reduce()
        source, pmer = me.pmer._add_reducer([me.source], reducer,
                                            options=options or None)
        return PMap(source, pmer)

    def partition_map(self, f, **options):
        """Map a whole partition's value iterator (runs on empty partitions)."""
        return self.custom_mapper(StreamMapper(f), **options)

    def partition_reduce(self, f):
        """Reduce a whole partition's group iterator (runs on empty
        partitions)."""
        return self.custom_reducer(StreamReducer(f))

    # -- two-source ops ----------------------------------------------------
    def join(self, other):
        """Co-partitioned join with another collection; returns PJoin."""
        assert isinstance(other, PBase)
        me = self._materialized_for_reduce()
        if isinstance(other, PMap):
            other = other._materialized_for_reduce()
        pmer = Dampr(me.pmer.graph.union(other.pmer.graph))
        return PJoin(me.source, pmer, other.source)

    def cross_right(self, other, cross, memory=False):
        """Map-side cross product, loop order right-major."""
        assert isinstance(other, PMap)
        return other.cross_left(self, lambda xi, yi: cross(yi, xi), memory)

    def cross_left(self, other, cross, memory=False, **options):
        """Map-side cross product (broadcast join).  ``memory=True`` pins the
        replicated side in RAM."""
        def _cross(k1, v1, k2, v2):
            yield k1, cross(v2, v1)

        pmer = Dampr(self.pmer.graph.union(other.pmer.graph))
        source, pmer = pmer._add_mapper(
            [other.source, self.source], MapCrossJoin(_cross, cache=memory),
            combiner=None, options=options)
        return PMap(source, pmer)

    def cross_set(self, other, cross, agg=None, **options):
        """Load the whole other side through ``agg`` and pass it to every
        record."""
        def _cross(k1, v1, right):
            yield k1, cross(v1, right)

        if agg is None:
            agg = list

        def _aggregate(d):
            return agg(v for _k, v in d)

        pmer = Dampr(self.pmer.graph.union(other.pmer.graph))
        source, pmer = pmer._add_mapper(
            [other.source, self.source], MapAllJoin(_cross, _aggregate),
            combiner=None, options=options)
        return PMap(source, pmer)

    # -- persistence -------------------------------------------------------
    def cached(self, **options):
        """Materialize and pin this stage's output in RAM (never spills)."""
        options["memory"] = True
        return self.checkpoint(force=True, options=options)

    def sink(self, path):
        """Write each value as a text line into part-files under ``path``
        (durable — exempt from cleanup).  The sink node starts as an
        identity sinker; the plan optimizer composes any pure record
        chain feeding it into the sinker, so transformed records stream
        straight to disk without an intermediate materialization."""
        source, pmer = self.pmer._add_sink([self.source], Map(_identity),
                                           path=path, options=None)
        return PMap(source, pmer)

    def sink_tsv(self, path):
        """Tab-join tuple values, then sink."""
        return self.map(lambda x: u"\t".join(str(p) for p in x)).sink(path)

    def sink_json(self, path):
        """JSON-serialize values line-delimited, then sink."""
        return self.map(json.dumps).sink(path)


class ARReduce(object):
    """Associative reduce handle: folds map-side, shuffles compacted partials,
    folds again reduce-side (reference dampr.py:654-709; the decomposition is
    the reference's PartialReduceCombiner pipeline restated as segment
    kernels — see SURVEY §3.3)."""

    def __init__(self, pmap):
        self.pmap = pmap

    def reduce(self, binop, reduce_buffer=1000, **options):
        """Reduce groups with an associative binop.  ``reduce_buffer`` is
        accepted for API parity; block-size accounting replaces it.

        Plants an identity stage carrying the map-side combiner (the
        local-combine half of the shuffle) ahead of the final-fold
        reduce; the plan optimizer hoists that combiner into the
        producing map stage, so optimized runs fold map-side inside the
        producer's own jobs."""
        op = segment.as_assoc_op(binop)
        options.update({"binop": op, "reduce_buffer": reduce_buffer})
        source, pmer = self.pmap.pmer._add_mapper(
            [self.pmap.source], Map(_identity),
            combiner=PartialReduceCombiner(op), options=options)
        new_source, pmer = pmer._add_reducer(
            [source], AssocFoldReducer(op), options=options)
        return PMap(new_source, pmer)

    def first(self, **options):
        """First value seen per key."""
        return self.reduce(segment.FIRST, **options)

    def sum(self, **options):
        """Sum values per key — device segment-sum end-to-end for numeric
        values."""
        return self.reduce(segment.SUM, **options)


class PReduce(PBase):
    """General grouped collection (post group_by)."""

    def reduce(self, f):
        """``f(key, value_iter) -> value`` per group."""
        new_source, pmer = self.pmer._add_reducer([self.source], KeyedReduce(f))
        return PMap(new_source, pmer)

    def unique(self, key=lambda x: x):
        """Distinct values per group (first occurrence wins)."""
        def _uniq(k, it):
            seen = set()
            agg = []
            for v in it:
                fv = key(v)
                if fv not in seen:
                    seen.add(fv)
                    agg.append(v)
            return agg

        return self.reduce(_uniq)

    def join(self, other):
        """Join grouped data with another collection."""
        assert isinstance(other, PBase)
        if isinstance(other, PMap):
            other = other._materialized_for_reduce()
        pmer = Dampr(self.pmer.graph.union(other.pmer.graph))
        return PJoin(self.source, pmer, other.source)

    def partition_reduce(self, f):
        """Whole-partition reduce over the grouped stream."""
        new_source, pmer = self.pmer._add_reducer([self.source],
                                                  StreamReducer(f))
        return PMap(new_source, pmer)


class PJoin(PBase):
    """Join handle over two co-partitioned grouped sources."""

    def __init__(self, source, pmer, right):
        super(PJoin, self).__init__(source, pmer)
        self.right = right

    def run(self, name=None, **kwargs):
        return self.reduce(lambda l, r: (list(l), list(r))).run(name, **kwargs)

    def explain(self, name=None):
        # A bare PJoin runs through the default pairing reduce; explain
        # the plan that run() would actually execute.
        return self.reduce(
            lambda l, r: (list(l), list(r))).explain(name=name)

    def reduce(self, aggregate, many=False):
        """Inner join: ``aggregate(left_iter, right_iter)`` per matched key;
        ``many=True`` flattens the result into separate records."""
        def _reduce(k, left, right):
            return aggregate(left, right)

        source, pmer = self.pmer._add_reducer(
            [self.source, self.right], KeyedInnerJoin(_reduce, many))
        return PMap(source, pmer)

    def left_reduce(self, aggregate):
        """Left join: missing right keys see an empty iterator."""
        def _reduce(k, left, right):
            return aggregate(left, right)

        source, pmer = self.pmer._add_reducer(
            [self.source, self.right], KeyedLeftJoin(_reduce))
        return PMap(source, pmer)

    def outer_reduce(self, aggregate):
        """Full outer join: whichever side is missing a key sees an empty
        iterator.  (New capability — the reference defines but never exposes
        an outer join, and its implementation is broken: base.py:355, 366.)"""
        def _reduce(k, left, right):
            return aggregate(left, right)

        source, pmer = self.pmer._add_reducer(
            [self.source, self.right], KeyedOuterJoin(_reduce))
        return PMap(source, pmer)


class Dampr(object):
    """Entrypoint: constructors for sources + the multi-output run."""

    def __init__(self, graph=None, runner=None):
        self.graph = Graph() if graph is None else graph
        self.runner = MTRunner if runner is None else runner

    @classmethod
    def memory(cls, items, partitions=50):
        """In-memory collection (keys = positions)."""
        mi = MemoryInput(list(enumerate(items)), partitions)
        source, ng = Graph().add_input(mi)
        return PMap(source, cls(ng))

    @classmethod
    def read_input(cls, *datasets):
        """Read from datasets / chunkers directly."""
        if len(datasets) == 1:
            ds = datasets[0]
        else:
            ds = CatDataset(list(datasets))
        source, ng = Graph().add_input(ds)
        return PMap(source, cls(ng))

    @classmethod
    def text(cls, fname, chunk_size=16 * 1024 ** 2, followlinks=False):
        """Newline-delimited text from a file/dir/glob, split into byte-range
        chunks."""
        return cls.read_input(PathInput(fname, chunk_size, followlinks))

    @classmethod
    def json(cls, *args, **kwargs):
        """Line-delimited JSON records."""
        return cls.text(*args, **kwargs).map(json.loads)

    @classmethod
    def urls(cls, urls, skip_on_error=True):
        """Fetch newline-delimited text over HTTP, one chunk per URL."""
        return cls.read_input(UrlsInput(urls, skip_on_error))

    @classmethod
    def from_dataset(cls, dataset):
        """Wrap raw stage outputs / custom Dataset subclasses as an input."""
        assert isinstance(dataset, Chunker)
        source, ng = Graph().add_input(dataset)
        return PMap(source, cls(ng))

    @classmethod
    def run(cls, *pmers, **kwargs):
        """Run several graphs in one pass; shared prefixes compute once.
        Returns one ValueEmitter per argument."""
        assert len(pmers) > 0, "Need at least one graph to run!"
        sources = []
        graph = None
        pmer = None
        for i, pmer in enumerate(pmers):
            if isinstance(pmer, PJoin):
                pmer = pmer.reduce(lambda l, r: (list(l), list(r)))
            graph = pmer.pmer.graph if i == 0 else pmer.pmer.graph.union(graph)
            sources.append(pmer.source)

        if kwargs.get("resume") and kwargs.get("name") is None:
            raise ValueError(
                "resume=True requires a stable run name: Dampr.run(..., "
                "name=..., resume=True)")
        name = kwargs.pop("name", "dampr/{}".format(random.random()))
        if settings.seed is not None:
            _reset_sample_rngs()
        runner, ds = _drive_runner(
            lambda: pmer.pmer.runner(name, graph, **kwargs),
            sources, kwargs.get("resume"))
        stats = RunStats([s.as_dict() for s in getattr(runner, "stats", [])],
                         getattr(runner, "run_summary", None))
        emitters = []
        for d in ds:
            em = ValueEmitter(d)
            em.stats = stats
            emitters.append(em)
        return emitters

    # -- graph builders (value semantics) ----------------------------------
    def _add_mapper(self, *args, **kwargs):
        output, ng = self.graph.add_mapper(*args, **kwargs)
        return output, Dampr(ng)

    def _add_reducer(self, *args, **kwargs):
        output, ng = self.graph.add_reducer(*args, **kwargs)
        return output, Dampr(ng)

    def _add_sink(self, *args, **kwargs):
        output, ng = self.graph.add_sink(*args, **kwargs)
        return output, Dampr(ng)


# Per-thread RNG for sample(): jobs run on threads, and a shared Random would
# serialize them on its lock and interleave streams nondeterministically.
#
# Seeding (settings.seed, satellite of the plan-optimizer work): with a
# seed set, each thread's RNG derives deterministically from
# (seed, per-run thread index) — re-derived at every run start via
# _reset_sample_rngs() — so sampled pipelines reproduce exactly whenever
# job->thread assignment is deterministic: serial runs (max_processes=1,
# or single-job stages, where jobs execute on the stage-walk thread)
# always are.  Parallel runs get deterministic per-thread STREAMS but a
# nondeterministic job->thread mapping, so only the distribution is
# pinned — the documented limit (docs/plan.md).  Default (seed=None)
# keeps the historical time-seeded behavior.
_RAND_LOCAL = threading.local()
_RAND_LOCK = threading.Lock()
_RAND_STATE = {"epoch": 0, "next_index": None}


def _reset_sample_rngs():
    """Start a fresh deterministic RNG generation (called at run start
    when settings.seed is set): every thread re-seeds from
    (seed, index-within-run) at its next draw."""
    with _RAND_LOCK:
        _RAND_STATE["epoch"] += 1
        _RAND_STATE["next_index"] = itertools.count()


def _get_rand():
    seed = settings.seed
    st = _RAND_LOCAL
    if seed is None:
        r = getattr(st, "rand", None)
        if r is None or getattr(st, "seeded", False):
            # Random() seeds from os.urandom: always distinct per thread.
            # (The old time.time()+thread_ident seed was quantized to
            # ~16 ms steps by float64 at pthread-address magnitudes, so a
            # recycled ident within that window REPLAYED the stream.)
            r = random.Random()
            st.rand, st.seeded = r, False
        return r
    epoch = _RAND_STATE["epoch"]
    if (getattr(st, "epoch", None) != epoch
            or not getattr(st, "seeded", False)):
        with _RAND_LOCK:
            counter = _RAND_STATE["next_index"]
            if counter is None:  # seeded draw before any run: index 0 et seq
                counter = _RAND_STATE["next_index"] = itertools.count()
            idx = next(counter)
        st.rand = random.Random(seed * 1000003 + idx * 7919)
        st.epoch, st.seeded = epoch, True
    return st.rand


def setup_logging(debug=False):
    level = logging.DEBUG if debug else logging.INFO
    logging.basicConfig(
        level=level,
        format="%(asctime)s [%(levelname)s] %(name)s: %(message)s")
