"""Device-resident keyed-fold microbenchmark: the engine's real local-fold
kernel (`dampr_tpu.parallel.shuffle._local_fold` — dual hash lanes ->
lexsort -> segmented fold) as one jitted program whose inputs are generated
on-device — no host transfer in the timed loop.  This measures what the TPU
compute path sustains when data lives in HBM, separating kernel throughput
from the host<->device transfers bench.py's host-path numbers include.

Two lowerings are timed (see _local_fold):

- ``scan``: the nonneg-sum scan fold (cumsum + cummax carry, no scatter) —
  the count/len/doc-freq hot path;
- ``scatter``: the segment_sum lowering (general sums, min/max).

Timing is amortized: the kernel runs ``--iters`` times inside one jitted
``fori_loop`` (fresh threefry data each iteration, results folded into a
checksum), so the per-dispatch latency is paid once per measurement, not
per iteration.

Verification: one un-looped invocation's folded per-key counts are fetched
and compared exactly against a host-side np.bincount of the identical
(threefry-deterministic) id sequence.

    python benchmarks/device_fold_bench.py [--records 2**22] [--keys 65536]
"""

import _pathfix  # noqa: F401  (repo root onto sys.path)

import argparse
import functools
import json
import time

import numpy as np


def _gen(seed, n, n_keys):
    import jax
    import jax.numpy as jnp

    from dampr_tpu.ops.hashing import _mix_int_jit

    key = jax.random.PRNGKey(seed)
    ids = jax.random.randint(key, (n,), 0, n_keys, dtype=jnp.int32)
    # the engine's own dual-lane integer mix (ops/hashing._mix_int_jit)
    h1, h2 = _mix_int_jit()(ids.astype(jnp.uint32), jnp.zeros((n,),
                                                             jnp.uint32))
    vals = jnp.ones((n,), dtype=jnp.int32)
    return ids, h1, h2, vals


@functools.lru_cache(maxsize=None)
def _build_once(n, n_keys, nonneg):
    """One un-looped fold returning full arrays for exact verification."""
    import jax
    import jax.numpy as jnp

    from dampr_tpu.parallel.shuffle import _local_fold

    def program(seed):
        ids, h1, h2, vals = _gen(seed, n, n_keys)
        inv = jnp.zeros((n,), dtype=jnp.uint32)
        oinv, fh1, fh2, fv = _local_fold(inv, h1, h2, vals, "sum", nonneg)
        return oinv, fh1, fh2, fv

    return jax.jit(program)


@functools.lru_cache(maxsize=None)
def _build_loop(n, n_keys, iters, nonneg):
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dampr_tpu.parallel.shuffle import _local_fold

    def loop(seed0):
        def body(i, acc):
            ids, h1, h2, vals = _gen(seed0 + i, n, n_keys)
            inv = jnp.zeros((n,), dtype=jnp.uint32)
            oinv, fh1, fh2, fv = _local_fold(inv, h1, h2, vals, "sum",
                                             nonneg)
            return acc ^ fh1[0] ^ fv[-1].astype(jnp.uint32)

        return lax.fori_loop(0, iters, body, jnp.uint32(0))

    return jax.jit(loop)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--records", type=int, default=1 << 22)
    ap.add_argument("--keys", type=int, default=1 << 16)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()

    import jax

    results = {}
    for mode, nonneg in (("scan", True), ("scatter", False)):
        # exact verification of this lowering: fold results map back to
        # ids through the (host-mirrored) hash lanes — each distinct key
        # must appear exactly once with its exact count
        oinv, fh1, fh2, fv = _build_once(args.records, args.keys, nonneg)(0)
        host_ids = np.asarray(jax.device_get(
            _gen(0, args.records, args.keys)[0]))
        want = np.bincount(host_ids, minlength=args.keys)

        # host mirror of the device-side dual-lane mix — the engine's own
        # numpy kernel, so the verification cannot drift from the hash
        from dampr_tpu.ops.hashing import _mix_int_numpy
        kh1, kh2 = _mix_int_numpy(np.arange(args.keys, dtype=np.int64))
        id_of = {(int(a), int(b)): k for k, (a, b) in
                 enumerate(zip(kh1, kh2))}
        live = np.asarray(oinv) == 0
        got = np.zeros(args.keys, dtype=np.int64)
        f = np.asarray(fv)
        a1 = np.asarray(fh1)
        a2 = np.asarray(fh2)
        for i in np.flatnonzero(live):
            got[id_of[(int(a1[i]), int(a2[i]))]] += f[i]
        assert (got == want).all(), (
            "device fold (%s) diverged from host bincount" % mode)

        prog = _build_loop(args.records, args.keys, args.iters, nonneg)
        jax.device_get(prog(0))  # warm: compile + first run
        t0 = time.time()
        jax.device_get(prog(100))
        secs = (time.time() - t0) / args.iters
        results[mode] = secs

    print(json.dumps({
        "metric": "device_keyed_fold",
        "backend": jax.default_backend(),
        "records": args.records,
        "distinct_keys": args.keys,
        "records_per_s_scan": round(args.records / results["scan"]),
        "records_per_s_scatter": round(args.records / results["scatter"]),
        "GBps_payload_scan": round(
            args.records * 8 / results["scan"] / 1e9, 2),
        "speedup_scan_vs_scatter": round(
            results["scatter"] / results["scan"], 2),
        "verified": True,
    }))


if __name__ == "__main__":
    main()
