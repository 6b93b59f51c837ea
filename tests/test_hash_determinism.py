"""Regression tests for hashing/grouping correctness fixes (round-2 advice):

- per-item kind dispatch: a key hashes identically no matter which block it
  lands in (mixed-type blocks must route like homogeneous ones);
- arbitrary-precision int keys don't crash the int64 fast path;
- object-lane hashing is deterministic across processes (no PYTHONHASHSEED
  dependence) — required for spill-reload and multi-host partition routing;
- device segment folds respect collision-repaired group bounds;
- bool value columns round-trip exactly;
- the device FNV program hashes exactly as the numpy definition.
"""

import subprocess
import sys

import numpy as np
import pytest

from dampr_tpu.blocks import Block
from dampr_tpu.ops import hashing, segment

from conftest import reference_text


def _h(keys):
    h1, h2 = hashing.hash_keys(keys)
    return list(zip(h1.tolist(), h2.tolist()))


class TestPerItemDispatch:
    def test_str_key_same_hash_in_mixed_block(self):
        pure = _h(["x", "y"])
        mixed = _h(["x", 3, "y", (1, 2)])
        assert mixed[0] == pure[0]
        assert mixed[2] == pure[1]

    def test_int_key_same_hash_in_mixed_block(self):
        pure = _h([7, 42])
        mixed = _h([7, "a", 42])
        assert mixed[0] == pure[0]
        assert mixed[2] == pure[1]

    def test_python_equality_canonicalization_in_mixed_block(self):
        # 1 == 1.0 == True must share a hash even inside mixed batches.
        hs = _h([1, 1.0, True, "one"])
        assert hs[0] == hs[1] == hs[2]

    def test_tuple_key_same_hash_alone_and_mixed(self):
        pure = _h([(1, "a")])
        mixed = _h([5, (1, "a"), "z"])
        assert mixed[1] == pure[0]

    def test_ndarray_vs_object_list_float(self):
        arr = np.array([1.5, 2.0, -3.25], dtype=np.float64)
        via_arr = _h(arr)
        via_list = _h([1.5, 2.0, -3.25])
        assert via_arr == via_list

    def test_large_integral_float_consistency(self):
        # 2.0**62 is integral and in int64 range: same hash as the int,
        # in every container type.
        f = 2.0 ** 62
        i = 2 ** 62
        assert _h([f]) == _h([i]) == _h(np.array([f]))[0:1]


class TestBigInts:
    def test_big_int_key_does_not_crash(self):
        blk = Block.from_pairs([(2 ** 100, 1), (1, 2)])
        h1, h2 = blk.hashes()
        assert len(h1) == 2

    def test_equal_big_ints_hash_equal(self):
        assert _h([2 ** 100])[0] == _h([2 ** 100, "pad"])[0]

    def test_float_representable_big_int_matches_float(self):
        # Python: 2**200 == float(2**200) exactly, so they must co-group.
        assert _h([2 ** 200])[0] == _h([float(2 ** 200)])[0]


class TestCrossProcessDeterminism:
    def test_tuple_hash_stable_across_processes(self):
        code = (
            "from dampr_tpu.ops import hashing\n"
            "h1, h2 = hashing.hash_keys([('a', 1, 2.5), frozenset({'x', 3}), None])\n"
            "print(h1.tolist(), h2.tolist())\n"
        )
        outs = set()
        for seed in ("0", "12345"):
            r = subprocess.run(
                [sys.executable, "-c", code],
                capture_output=True, text=True, check=True,
                env={"PYTHONHASHSEED": seed, "PATH": "/usr/bin:/bin",
                     "PYTHONPATH": "/root/repo", "JAX_PLATFORMS": "cpu"},
            )
            outs.add(r.stdout.strip())
        assert len(outs) == 1, outs


class TestCollisionRepairFold:
    def test_device_fold_uses_repaired_bounds(self):
        # Force a full 64-bit hash collision between distinct keys by
        # constructing the block with equal (h1, h2) lanes.
        n_a, n_b = 600, 424  # total > device_min_batch to hit the device branch
        keys = np.array(["aa"] * n_a + ["bb"] * n_b, dtype=object)
        vals = np.concatenate([np.ones(n_a, dtype=np.int64),
                               np.full(n_b, 2, dtype=np.int64)])
        h = np.full(n_a + n_b, 77, dtype=np.uint32)
        blk = Block(keys, vals, h.copy(), h.copy())
        out = segment.fold_block(blk, segment.SUM)
        got = dict(out.iter_pairs())
        assert got == {"aa": n_a, "bb": 2 * n_b}


class TestBoolValues:
    def test_bool_values_round_trip(self):
        blk = Block.from_pairs([("k", True), ("j", False)])
        pairs = dict(blk.iter_pairs())
        assert pairs == {"k": True, "j": False}
        assert pairs["k"] is True

    def test_bool_sum_promotes_like_python(self):
        blk = Block.from_pairs([("k", True), ("k", True), ("j", False)])
        out = dict(segment.fold_block(blk, segment.SUM).iter_pairs())
        assert out == {"k": 2, "j": 0}

    def test_mixed_big_int_and_float_keeps_precision(self):
        big = 2 ** 60 + 1
        blk = Block.from_pairs([("a", big), ("b", 0.5)])
        assert dict(blk.iter_pairs())["a"] == big


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-q"]))


class TestNativeBatchHash:
    def test_native_matches_numpy_lanes(self):
        import dampr_tpu.native as nat
        from dampr_tpu.ops import hashing

        assert nat.get_lib() is not None, (
            "native library must build on this rig or the parity "
            "comparison is vacuous")
        keys = (["tok%d" % i for i in range(500)]
                + ["", "a", "é", "ÿ" * 300, "x" * 1025]
                + [b"raw\x00bytes", b""])
        with_native = hashing.hash_keys(list(keys))
        old = nat._lib, nat._tried
        nat._lib, nat._tried = None, True
        try:
            without = hashing.hash_keys(list(keys))
        finally:
            nat._lib, nat._tried = old
        import numpy as np
        np.testing.assert_array_equal(with_native[0], without[0])
        np.testing.assert_array_equal(with_native[1], without[1])

    def test_object_lane_native_matches_numpy(self):
        import dampr_tpu.native as nat
        from dampr_tpu.ops import hashing

        keys = [(i, "k%d" % i) for i in range(200)] + [None, frozenset({1})]
        a = hashing.hash_keys(list(keys))
        old = nat._lib, nat._tried
        nat._lib, nat._tried = None, True
        try:
            b = hashing.hash_keys(list(keys))
        finally:
            nat._lib, nat._tried = old
        import numpy as np
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])


def _fnv_cases():
    return {
        "reference-words": (reference_text() * 3).split(),
        "empty-and-high-bytes": ["", "é" * 20, "\xff\x80 mixed", "plain"],
        # row counts on both sides of a power of two
        "row-counts": [["k%d" % i for i in range(n)]
                       for n in (1, 511, 512, 513)],
    }


@pytest.mark.parametrize("case", sorted(_fnv_cases()))
def test_device_fnv_matches_numpy(case):
    """``_fnv_jit`` (the device FNV the lowered programs inline) against
    the numpy definition, lane for lane."""
    keys = _fnv_cases()[case]
    for ks in (keys if case == "row-counts" else [keys]):
        mat, lens = hashing.encode_str_keys(ks)
        w1, w2 = hashing._fnv_numpy(mat, lens)
        d1, d2 = hashing._fnv_jit()(mat, lens)
        np.testing.assert_array_equal(w1, np.asarray(d1))
        np.testing.assert_array_equal(w2, np.asarray(d2))
