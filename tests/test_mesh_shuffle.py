"""Mesh collective shuffle tests on the 8-device CPU mesh rig.

Validates the shard_map keyed fold (local combine -> all_to_all -> final
fold) against host-computed ground truth, including the overflow-retry path
and the psum global aggregate.  These are the collectives that carry the
distributed shuffle on real ICI meshes.
"""

import collections

import numpy as np
import pytest

from dampr_tpu.ops import hashing
from dampr_tpu.parallel import mesh_global_sum, mesh_keyed_fold
from dampr_tpu.parallel.mesh import mesh_size

from conftest import reference_text


def _fold_to_dict(keyspace, fh1, fh2, fv):
    kh1, kh2 = hashing.hash_keys(np.asarray(keyspace))
    lookup = {(int(a), int(b)): k
              for k, (a, b) in zip(keyspace, zip(kh1, kh2))}
    return {lookup[(int(a), int(b))]: v
            for a, b, v in zip(fh1, fh2, fv.tolist())}


class TestMeshKeyedFold:
    def test_eight_devices(self, mesh8):
        assert mesh_size(mesh8) == 8

    def test_sum_matches_host(self, mesh8):
        rng = np.random.RandomState(7)
        keys = rng.randint(0, 1000, size=50000)
        vals = rng.randint(0, 50, size=50000).astype(np.int64)
        h1, h2 = hashing.hash_keys(keys)
        got = _fold_to_dict(list(range(1000)),
                            *mesh_keyed_fold(mesh8, h1, h2, vals, "sum"))
        want = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            want[k] = want.get(k, 0) + v
        assert got == want

    def test_sum_negative_values_scatter_path(self, mesh8):
        # Negative values must miss the nonneg scan lowering and still fold
        # exactly through the scatter path.
        rng = np.random.RandomState(11)
        keys = rng.randint(0, 500, size=30000)
        vals = rng.randint(-50, 50, size=30000).astype(np.int64)
        h1, h2 = hashing.hash_keys(keys)
        got = _fold_to_dict(list(range(500)),
                            *mesh_keyed_fold(mesh8, h1, h2, vals, "sum"))
        want = {}
        for k, v in zip(keys.tolist(), vals.tolist()):
            want[k] = want.get(k, 0) + v
        assert got == want

    def test_scan_and_scatter_lowerings_agree(self, mesh8):
        # Same nonneg data through both static lowerings of the fold program.
        from dampr_tpu.parallel import shuffle as sh
        rng = np.random.RandomState(13)
        keys = rng.randint(0, 777, size=20000)
        vals = rng.randint(0, 9, size=20000).astype(np.int64)
        h1, h2 = hashing.hash_keys(keys)
        a = _fold_to_dict(list(range(777)),
                          *mesh_keyed_fold(mesh8, h1, h2, vals, "sum"))
        # force the scatter lowering by shifting through a negative no-op
        vals2 = np.concatenate([vals, np.array([-1, 1], dtype=np.int64)])
        extra = hashing.hash_keys(np.array([999888, 999888]))
        h1b = np.concatenate([h1, extra[0]])
        h2b = np.concatenate([h2, extra[1]])
        b = _fold_to_dict(list(range(777)) + [999888],
                          *mesh_keyed_fold(mesh8, h1b, h2b, vals2, "sum"))
        assert b.pop(999888) == 0
        assert a == b

    def test_min_max(self, mesh8):
        rng = np.random.RandomState(3)
        keys = rng.randint(0, 64, size=4096)
        vals = rng.randint(-1000, 1000, size=4096).astype(np.int64)
        h1, h2 = hashing.hash_keys(keys)
        gmin = _fold_to_dict(list(range(64)),
                             *mesh_keyed_fold(mesh8, h1, h2, vals, "min"))
        gmax = _fold_to_dict(list(range(64)),
                             *mesh_keyed_fold(mesh8, h1, h2, vals, "max"))
        for k in set(keys.tolist()):
            kv = vals[keys == k]
            assert gmin[k] == kv.min()
            assert gmax[k] == kv.max()

    def test_overflow_retry_is_exact(self, mesh8):
        # Skewed keys + tiny capacity: every record hashes to few devices,
        # forcing the capacity-doubling retry loop.
        keys = np.array([1, 2] * 5000)
        vals = np.ones(10000, dtype=np.int64)
        h1, h2 = hashing.hash_keys(keys)
        got = _fold_to_dict([1, 2], *mesh_keyed_fold(
            mesh8, h1, h2, vals, "sum", capacity_factor=0.02))
        assert got == {1: 5000, 2: 5000}

    def test_string_keys_wordcount(self, mesh8):
        words = (reference_text() * 5).split()
        h1, h2 = hashing.hash_keys(words)
        fh1, fh2, fv = mesh_keyed_fold(
            mesh8, h1, h2, np.ones(len(words), dtype=np.int64), "sum")
        want = collections.Counter(words)
        got = _fold_to_dict(list(want), fh1, fh2, fv)
        assert got == dict(want)

    def test_empty(self, mesh8):
        fh1, fh2, fv = mesh_keyed_fold(
            mesh8, np.empty(0, np.uint32), np.empty(0, np.uint32),
            np.empty(0, np.int64), "sum")
        assert len(fh1) == 0

    def test_float_values(self, mesh8):
        keys = np.arange(100) % 10
        vals = np.linspace(0, 1, 100).astype(np.float32)
        h1, h2 = hashing.hash_keys(keys)
        got = _fold_to_dict(list(range(10)),
                            *mesh_keyed_fold(mesh8, h1, h2, vals, "sum"))
        for k in range(10):
            assert abs(got[k] - vals[keys == k].sum()) < 1e-4


class TestGlobalSum:
    def test_int(self, mesh8):
        vals = np.arange(10001, dtype=np.int64)
        assert mesh_global_sum(mesh8, vals) == int(vals.sum())

    def test_float(self, mesh8):
        vals = np.ones(1000, dtype=np.float32) * 0.5
        assert abs(mesh_global_sum(mesh8, vals) - 500.0) < 1e-3


def _sorted_case(rng, n_keys, n, max_v=9, n_invalid=0):
    """Random (inv, h1, h2)-sorted lanes, as the fold's sort leaves them:
    ``n - n_invalid`` valid records over ``n_keys`` keys, then the
    invalid tail."""
    kh1 = rng.randint(0, 1 << 32, size=n_keys, dtype=np.uint64).astype(
        np.uint32)
    kh2 = rng.randint(0, 1 << 32, size=n_keys, dtype=np.uint64).astype(
        np.uint32)
    ids = rng.randint(0, n_keys, size=n - n_invalid)
    h1 = np.concatenate([kh1[ids], np.zeros(n_invalid, np.uint32)])
    h2 = np.concatenate([kh2[ids], np.zeros(n_invalid, np.uint32)])
    inv = np.zeros(n, dtype=np.uint32)
    inv[n - n_invalid:] = 1
    v = rng.randint(0, max_v + 1, size=n).astype(np.int32)
    order = np.lexsort((h2, h1, inv))
    return h1[order], h2[order], v[order], inv[order]


def _segment_oracle(h1, h2, v, inv):
    """Exact per-segment totals, on the host: each run of equal
    (inv, h1, h2) totals at its last position, live iff valid."""
    n = len(h1)
    starts = np.ones(n, dtype=bool)
    starts[1:] = ((h1[1:] != h1[:-1]) | (h2[1:] != h2[:-1])
                  | (inv[1:] != inv[:-1]))
    ends = np.ones(n, dtype=bool)
    ends[:-1] = starts[1:]
    tot = np.zeros(n, dtype=np.int64)
    tot[ends] = np.add.reduceat(v.astype(np.int64), np.flatnonzero(starts))
    return tot, ends & (inv == 0)


def _one_segment():
    n = 2048
    z = np.zeros(n, dtype=np.uint32)
    return z, z.copy(), np.ones(n, dtype=np.int32), z.copy()


def _all_distinct():
    n = 1024
    a = np.arange(n, dtype=np.uint32)
    return a, a.copy(), np.full(n, 3, dtype=np.int32), np.zeros(n, np.uint32)


_SCAN_CASES = {
    "few-keys": lambda: _sorted_case(np.random.RandomState(0), 50, 1024),
    "many-keys": lambda: _sorted_case(np.random.RandomState(1), 300, 3072),
    "one-long-segment": _one_segment,
    "invalid-tail": lambda: _sorted_case(np.random.RandomState(2), 40, 1024,
                                         n_invalid=500),
    "all-distinct": _all_distinct,
}


class TestScanFoldSorted:
    """The post-sort scan chain of the nonneg-sum fold (cumsum and a
    cummax-carried segment start, no scatters) against the exact host
    oracle: the chain the mesh fold runs on the chip."""

    @pytest.mark.parametrize("case", sorted(_SCAN_CASES))
    def test_matches_host_oracle(self, case):
        import jax.numpy as jnp

        from dampr_tpu.parallel.shuffle import _scan_fold_sorted

        h1, h2, v, inv = _SCAN_CASES[case]()
        oinv, oh1, oh2, tot = _scan_fold_sorted(
            jnp.asarray(inv), jnp.asarray(h1), jnp.asarray(h2),
            jnp.asarray(v))
        want_tot, want_live = _segment_oracle(h1, h2, v, inv)
        live = np.asarray(oinv) == 0
        np.testing.assert_array_equal(live, want_live)
        np.testing.assert_array_equal(
            np.where(live, np.asarray(tot).astype(np.int64), 0),
            np.where(want_live, want_tot, 0))
        np.testing.assert_array_equal(np.asarray(oh1), h1)
        np.testing.assert_array_equal(np.asarray(oh2), h2)

    def test_local_fold_scan_lowering_matches_oracle(self):
        """``_local_fold(..., nonneg_sum=True)`` sorts unsorted lanes and
        folds them through the same chain: its live rows are the oracle's
        per-key totals."""
        import jax.numpy as jnp

        from dampr_tpu.parallel.shuffle import _local_fold

        rng = np.random.RandomState(3)
        h1, h2, v, inv = _sorted_case(rng, 100, 1024, n_invalid=24)
        shuffled = rng.permutation(len(h1))
        oinv, oh1, oh2, ov = _local_fold(
            *(jnp.asarray(a[shuffled]) for a in (inv, h1, h2, v)),
            "sum", nonneg_sum=True)
        want_tot, want_live = _segment_oracle(h1, h2, v, inv)
        want = {(int(a), int(b)): int(t) for a, b, t in
                zip(h1[want_live], h2[want_live], want_tot[want_live])}
        m = np.asarray(oinv) == 0
        got = {}
        for a, b, t in zip(np.asarray(oh1)[m], np.asarray(oh2)[m],
                           np.asarray(ov)[m]):
            assert (int(a), int(b)) not in got
            got[(int(a), int(b))] = int(t)
        assert got == want
