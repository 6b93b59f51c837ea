"""Lowered map jobs placed over the chips of the data mesh, and the
collective fold taking each chip's device-resident partials where they
live (``parallel.placement``, ``parallel.shuffle.assemble_by_chip``).

Runs on 4 of the suite's 8 virtual CPU devices (the data mesh cut to
four), with the lowering forced on and every device-to-device copy
refused, explicit or implicit: a partial that crossed chips outside the
fold's own all_to_all fails the run instead of passing slowly.
"""

import operator
import os
import re
from collections import Counter

import jax
import numpy as np
import pytest

from dampr_tpu import Dampr, settings
from dampr_tpu.ops import hashing
from dampr_tpu.ops.text import DocFreq, TokenCounts
from dampr_tpu.parallel import mesh as mesh_mod
from dampr_tpu.parallel import placement, shuffle
from dampr_tpu.storage import BlockRef

RX = re.compile(r"[^\w]+")
CHIPS = 4
#: Map jobs per run: chunks of the corpus.
JOBS = 8


@pytest.fixture
def chips(monkeypatch):
    """The data mesh cut to ``chips.n`` devices, the lowering forced on,
    device-to-device copies refused; every knob restored."""
    full = mesh_mod.data_mesh

    class Chips(object):
        n = CHIPS

    monkeypatch.setattr(mesh_mod, "data_mesh",
                        lambda devices=None, n=None: full(devices,
                                                          n or Chips.n))
    old = (settings.lower, settings.handoff, settings.optimize,
           settings.analyze, settings.partitions)
    settings.lower, settings.handoff = "1", "auto"
    settings.optimize = settings.analyze = True
    settings.partitions = 8
    guard = jax.config.jax_transfer_guard_device_to_device
    jax.config.update("jax_transfer_guard_device_to_device",
                      "disallow_explicit")
    try:
        yield Chips
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", guard)
        (settings.lower, settings.handoff, settings.optimize,
         settings.analyze, settings.partitions) = old


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    rng = np.random.RandomState(25)
    words = ["w%d" % i for i in range(900)] + ["Tok_1", "UPPER", "a"]
    p = np.arange(1, len(words) + 1, dtype=np.float64) ** -1.1
    lines = [" ".join(rng.choice(words, size=rng.randint(1, 12),
                                 p=p / p.sum()))
             for _ in range(4000)]
    path = str(tmp_path_factory.mktemp("mesh-scan") / "corpus.txt")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return path


def reference(corpus, dedup=False):
    c = Counter()
    with open(corpus) as f:
        for line in f:
            toks = [t for t in RX.split(line.lower()) if t]
            c.update(set(toks) if dedup else toks)
    return dict(c)


def run(corpus, name, dedup=False):
    mapper = (DocFreq if dedup else TokenCounts)(mode="word", lower=True,
                                                 pair_values=False)
    em = (Dampr.text(corpus, os.path.getsize(corpus) // JOBS + 1)
          .custom_mapper(mapper).fold_values(operator.add).run(name=name))
    got = em.read()
    stats = em.stats()
    em.delete()
    return got, stats


@pytest.mark.parametrize("dedup", [False, True],
                         ids=["word-count", "doc-freq"])
def test_lowered_fold_on_four_chips_is_exact(chips, corpus, dedup):
    got, stats = run(corpus, "mesh-scan-exact", dedup)
    assert len(got) == len(dict(got))
    assert dict(got) == reference(corpus, dedup)
    dev = stats["device"]
    assert dev["handoff_edges"] == 1 and dev["handoff_degrades"] == 0
    assert dev["lower_fallbacks"] == 0
    assert dev["scan_chips"] == CHIPS
    assert dev["fold_chips"] == CHIPS
    by_chip = dev["h2d_bytes_by_chip"]
    assert len(by_chip) == CHIPS and all(by_chip)
    # The lowered feed's bytes, without the registered refs' hash lanes.
    assert 0 < sum(by_chip) <= dev["h2d_bytes"]


def test_partials_stay_on_their_jobs_chips(chips, corpus, monkeypatch):
    """Each job's refs are made on the chip its job was placed on, are
    still there when the fold's input is assembled, and their counts,
    summed before the fold, are the uncut reference's."""
    made = {}  # id(value lane) -> the device of the job's chip
    devices = list(mesh_mod.data_mesh().devices.flat)
    from_lanes = BlockRef.from_device_lanes.__func__

    def record(cls, *args, **kw):
        ref = from_lanes(cls, *args, **kw)
        made[id(ref.device_lanes()[0])] = devices[placement.current()]
        return ref

    monkeypatch.setattr(BlockRef, "from_device_lanes", classmethod(record))
    seen = []
    assemble = shuffle.assemble_by_chip

    def spy(mesh, parts):
        if not seen:
            for h1, h2, v, ok in parts:
                (dev,) = v.sharding.device_set
                assert made[id(v)] == dev  # not moved since its job
                live = (np.arange(int(h1.shape[0])) < ok
                        if not hasattr(ok, "shape")
                        else np.asarray(ok) == 1)
                seen.append((dev, np.asarray(h1)[live],
                             np.asarray(h2)[live], np.asarray(v)[live]))
        return assemble(mesh, parts)

    monkeypatch.setattr(shuffle, "assemble_by_chip", spy)
    got, stats = run(corpus, "mesh-scan-share")
    want = reference(corpus)
    assert dict(got) == want
    assert {dev for dev, *_ in seen} == set(devices)
    keys = np.asarray(list(want), dtype=object)
    h1, h2 = hashing.hash_keys(keys)
    by_hash = dict(zip(zip(h1.tolist(), h2.tolist()), want.values()))
    summed = Counter()
    for _dev, ph1, ph2, pv in seen:
        for k, v in zip(zip(ph1.tolist(), ph2.tolist()), pv.tolist()):
            summed[k] += v
    assert dict(summed) == by_hash


def test_one_chip_keeps_the_unplaced_path(chips, corpus):
    """A one-device mesh enters no default device (the programs and
    their compile keys are an unplaced run's) and counts one chip."""
    chips.n = 1
    mesh = mesh_mod.data_mesh()
    with placement.placer([object()], mesh)(None):
        assert placement.current() == 0
        assert jax.config.jax_default_device is None
    got, stats = run(corpus, "mesh-scan-one")
    assert dict(got) == reference(corpus)
    dev = stats["device"]
    assert dev["scan_chips"] == 1 and dev["fold_chips"] == 1
    (fed,) = dev["h2d_bytes_by_chip"]
    assert 0 < fed <= dev["h2d_bytes"]


def test_one_chip_assembly_is_the_plain_concatenation():
    """On a one-device mesh the assembled input is, element for element,
    every partial's lanes concatenated and zero-padded to the pow2 over
    their rows."""
    import jax.numpy as jnp

    mesh = mesh_mod.data_mesh(n=1)
    rng = np.random.RandomState(5)
    parts, want = [], [[], [], [], []]
    for n, prefix in ((512, True), (256, False), (64, True), (1000, False)):
        h1, h2 = (rng.randint(0, 2 ** 32, n, dtype=np.uint64)
                  .astype(np.uint32) for _ in range(2))
        v = rng.randint(0, 99, n).astype(np.int32)
        ok = (np.arange(n) < n // 3 if prefix
              else rng.randint(0, 2, n).astype(bool)).astype(np.uint32)
        parts.append((jnp.asarray(h1), jnp.asarray(h2), jnp.asarray(v),
                      n // 3 if prefix else jnp.asarray(ok)))
        for lane, x in zip(want, (h1, h2, v, ok)):
            lane.append(x)
    h1, h2, v, valid, n_local = shuffle.assemble_by_chip(mesh, parts)
    assert n_local == 2048
    for got, lane in zip((h1, h2, v, valid), want):
        cat = np.concatenate(lane)
        ref = np.pad(cat, (0, n_local - len(cat)))
        assert np.asarray(got).dtype == ref.dtype
        np.testing.assert_array_equal(np.asarray(got), ref)


def test_replicated_partial_splits_evenly_over_chips():
    """A replicated partial (multi-process fold programs all_gather their
    outputs) enters the fold once, each chip taking an even share of its
    own copy; a sharded one by its shards.  Refolding both with a chip's
    own partial gives the exact sums."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_mod.data_mesh(n=CHIPS)
    rng = np.random.RandomState(7)
    n = 1030  # not a multiple of the chips: the last share is short
    keys = rng.choice(300, size=n).astype(np.uint32)
    vals = rng.randint(1, 9, size=n).astype(np.int32)
    ok = (rng.rand(n) < 0.8).astype(np.uint32)
    lanes = (keys, keys * np.uint32(7), vals, ok)
    replicated = tuple(jax.device_put(x, NamedSharding(mesh, P()))
                       for x in lanes)
    pieces = shuffle.chip_pieces(mesh, replicated)
    assert [p for p, _ in pieces] == list(range(CHIPS))
    share = -(-n // CHIPS)
    for p, (h1, _h2, _v, _ok) in pieces:
        (dev,) = h1.sharding.device_set
        assert dev == mesh.devices.flat[p] and h1.shape == (share,)
    assert shuffle.part_chips(mesh, replicated) == [
        (p, share) for p in range(CHIPS)]
    sharded = tuple(jax.device_put(np.pad(x, (0, 4 * 258 - n)),
                                   NamedSharding(mesh, P("shards")))
                    for x in lanes)
    assert [rows for _p, rows in shuffle.part_chips(mesh, sharded)] == [
        258] * CHIPS
    want = Counter()
    for k, v, o in zip(keys.tolist(), vals.tolist(), ok.tolist()):
        want[k] += 2 * v * o
    fh1, _fh2, fv, fok = shuffle.mesh_keyed_refold(
        mesh, [replicated, sharded], "sum")
    live = np.asarray(fok) == 1
    got = dict(zip(np.asarray(fh1)[live].tolist(),
                   np.asarray(fv)[live].tolist()))
    assert got == {k: v for k, v in want.items() if v}


def test_uneven_chips_fold_exactly(chips, corpus, monkeypatch):
    """Chips holding 5, 2, 1 and 0 jobs fold the same answer."""
    spread = placement.spread

    def lopsided(weights, n_chips):
        plan = [0, 0, 0, 0, 0, 1, 1, 2]
        return plan[:len(weights)] + spread(weights[len(plan):], n_chips)

    monkeypatch.setattr(placement, "spread", lopsided)
    got, stats = run(corpus, "mesh-scan-uneven")
    assert dict(got) == reference(corpus)
    dev = stats["device"]
    assert dev["scan_chips"] == 3 and dev["fold_chips"] == CHIPS
    assert dev["h2d_bytes_by_chip"][3] == 0


def test_compaction_keeps_each_chips_live_rows_on_it():
    """A fold output sharded over the chips compacts shard by shard: every
    chip keeps exactly its own live rows, live-first, in a shard cut to
    the pow2 over the most live rows any chip holds."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = mesh_mod.data_mesh(n=CHIPS)
    rng = np.random.RandomState(11)
    n = 512  # rows per chip
    live = [40, 3, 0, 100]  # per chip: the largest sets the cut, 128
    ok = np.zeros(CHIPS * n, np.uint32)
    for k, m in enumerate(live):
        ok[k * n + rng.choice(n, size=m, replace=False)] = 1
    h1 = rng.randint(0, 2 ** 32, CHIPS * n, dtype=np.uint64).astype(np.uint32)
    lanes = (h1, h1 ^ np.uint32(0x5bd1e995),
             rng.randint(1, 99, CHIPS * n).astype(np.int32), ok)
    sharded = NamedSharding(mesh, P(settings.mesh_axis))
    part = tuple(jax.device_put(x, sharded) for x in lanes)
    guard = jax.config.jax_transfer_guard_device_to_device
    jax.config.update("jax_transfer_guard_device_to_device",
                      "disallow_explicit")
    try:
        out = shuffle.compact_by_chip(mesh, part)
    finally:
        jax.config.update("jax_transfer_guard_device_to_device", guard)
    assert shuffle.part_chips(mesh, out) == [(k, 128) for k in range(CHIPS)]
    for x in out:
        assert x.sharding.is_equivalent_to(sharded, 1)
    got = [np.asarray(x).reshape(CHIPS, 128) for x in out]
    for k, m in enumerate(live):
        rows = slice(k * n, (k + 1) * n)
        keep = lanes[3][rows] == 1
        assert got[3][k].tolist() == [1] * m + [0] * (128 - m)
        for lane, x in zip(got[:3], lanes[:3]):
            assert sorted(lane[k][:m].tolist()) == sorted(
                x[rows][keep].tolist())


def test_compacting_folds_stay_exact(chips, corpus, monkeypatch):
    """With the compaction rule's lane cap at one row, every partial a
    chip adds compacts the accumulated fold on every chip; the word
    count stays exact."""
    from dampr_tpu import runner

    compacted = []
    compact = shuffle.compact_by_chip

    def spy(mesh, part):
        compacted.append(len(part[0].sharding.device_set))
        return compact(mesh, part)

    monkeypatch.setattr(runner, "_REFOLD_LANE_CAP", 1)
    monkeypatch.setattr(shuffle, "compact_by_chip", spy)
    got, stats = run(corpus, "mesh-scan-compact")
    assert dict(got) == reference(corpus)
    assert compacted and set(compacted) == {CHIPS}
    assert stats["device"]["scan_chips"] == CHIPS


def test_fold_bucket_and_a2a_bytes_add_up(chips, corpus, monkeypatch):
    """Each job's ``mesh_fold`` seconds and all_to_all bytes are its own:
    nonzero, the bytes those of its fold program calls, and two jobs'
    buckets within the process-wide bucket over both."""
    from dampr_tpu.ops import devtime

    calls = []
    a2a = shuffle.a2a_bytes

    def spy(n_dev, capacity, v_dtype):
        calls.append(a2a(n_dev, capacity, v_dtype))
        return calls[-1]

    monkeypatch.setattr(shuffle, "a2a_bytes", spy)
    before = devtime.snapshot()
    per_job = []
    for i in range(2):
        del calls[:]
        _got, stats = run(corpus, "mesh-scan-a2a-%d" % i)
        dev = stats["device"]
        assert dev["fold_a2a_bytes"] > 0
        assert dev["fold_a2a_bytes"] == sum(calls)
        assert stats["devtime"]["mesh_fold"] > 0
        per_job.append(stats)
    total = devtime.delta(before)["mesh_fold"]
    assert sum(s["devtime"]["mesh_fold"] for s in per_job) <= total + 1e-3
    assert (per_job[0]["device"]["fold_a2a_bytes"]
            == per_job[1]["device"]["fold_a2a_bytes"])


def test_a2a_bytes_count_only_what_leaves_a_chip():
    assert shuffle.a2a_bytes(1, 4096, np.int32) == 0
    # four chips, each sending 3 of its 4 rows of 100 16-byte entries
    assert shuffle.a2a_bytes(4, 100, np.int32) == 4 * 3 * 100 * 16
    assert shuffle.a2a_bytes(2, 10, np.int64) == 2 * 1 * 10 * 20


@pytest.mark.parametrize("weights, n_chips, want", [
    ([5, 5, 5, 5, 5, 5], 4, [0, 1, 2, 3, 0, 1]),
    ([10, 10, 10, 3], 4, [0, 1, 2, 3]),
    ([1, 9, 1, 1], 2, [1, 0, 1, 1]),
    ([7], 1, [0]),
])
def test_spread_balances_bytes(weights, n_chips, want):
    assert placement.spread(weights, n_chips) == want


def test_placement_reaches_a_helper_thread():
    """A thread the job starts re-enters its chip (the overlap
    executor's codec producer dispatches the job's programs)."""
    import threading

    dev = jax.devices()[2]
    seen = {}
    with placement.on_chip(2, dev):
        carry = placement.carried()

    def helper():
        with carry():
            seen["chip"] = placement.current()
            seen["dev"] = jax.numpy.zeros(2).sharding.device_set

    t = threading.Thread(target=helper)
    t.start()
    t.join()
    assert seen == {"chip": 2, "dev": {dev}}
    assert placement.current() is None
