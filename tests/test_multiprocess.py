"""Real multi-process distributed backend: 2 OS processes join via
jax.distributed.initialize (localhost coordinator), form one 8-device
global mesh (4 virtual CPU devices per process), and drive the collective
shuffle across the process boundary — psum and the keyed fold both verified
exact on every process.  The engine leg runs full DSL pipelines (keyed fold, general
group_by exchange, range sort) across the 2-process mesh and pins them
byte-identical to the single-process host path; the replan property tests
pin the chunked-exchange schedule's HBM-budget invariants on random
shapes."""

import os
import random
import socket
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WORKER = textwrap.dedent("""
    import os, sys
    pid = int(sys.argv[1]); port = sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, "@ROOT@")
    from dampr_tpu.parallel.mesh import init_distributed, data_mesh
    init_distributed(coordinator_address="localhost:%s" % port,
                     num_processes=2, process_id=pid)
    assert jax.process_count() == 2
    assert len(jax.devices()) == 8 and len(jax.local_devices()) == 4
    import numpy as np
    from dampr_tpu import settings
    settings.device_min_batch = 1
    from dampr_tpu.ops import hashing
    from dampr_tpu.parallel import mesh_global_sum, mesh_keyed_fold
    mesh = data_mesh()
    rng = np.random.RandomState(7)
    keys = rng.randint(0, 50, size=4096)
    vals = rng.randint(0, 9, size=4096).astype(np.int64)
    h1, h2 = hashing.hash_keys(keys)
    total = mesh_global_sum(mesh, vals)
    assert total == int(vals.sum()), (total, int(vals.sum()))
    fh1, fh2, fv = mesh_keyed_fold(mesh, h1, h2, vals, "sum")
    import collections
    want = collections.Counter()
    for k, v in zip(keys.tolist(), vals.tolist()):
        want[k] += v
    kh1, kh2 = hashing.hash_keys(np.arange(50))
    lut = {(int(a), int(b)): i
           for i, (a, b) in enumerate(zip(kh1, kh2))}
    got = {lut[(int(a), int(b))]: int(v)
           for a, b, v in zip(fh1, fh2, fv)}
    assert got == dict(want), "keyed fold diverged on process %d" % pid

    # General byte exchange (exchange.py): route object-valued blocks by
    # partition id across the process-spanning mesh and verify every
    # process sees the full delivered set (gather-replicated outputs).
    from dampr_tpu.blocks import Block
    from dampr_tpu.parallel.exchange import mesh_shuffle_blocks
    D = 8
    routed = []
    expect = {}
    seq = 0
    for src in range(D):
        for tpid in (src, (src + 3) % D, src + D):
            ks = np.array(["k%d_%d" % (tpid, src)], dtype=object)
            vs = np.array([("val", tpid, src)], dtype=object)
            bh1, bh2 = hashing.hash_keys(ks)
            routed.append((seq, src, tpid, Block(ks, vs, bh1, bh2)))
            expect.setdefault(tpid, []).append((seq, ks[0]))
            seq += 1
    received, moved = mesh_shuffle_blocks(mesh, routed)
    assert moved > 0
    got_pids = {}
    for rpid, blk in received:
        for k in blk.keys:
            got_pids.setdefault(rpid, []).append(k)
    want_pids = {rpid: [k for _s, k in sorted(entries)]
                 for rpid, entries in expect.items()}
    assert got_pids == want_pids, (
        "general exchange diverged on process %d" % pid)

    # Chunked gloo exchange under a tight HBM budget: the same blobs must
    # arrive byte-identical through a multi-step replan schedule whose
    # modeled peak in-flight bytes stay under the budget on every process.
    from dampr_tpu.parallel import exchange as px, replan
    from dampr_tpu.parallel.mesh import data_mesh as _dm
    budget = 1 << 18
    rngb = np.random.RandomState(11)
    blobs = {}
    for s in range(D):
        for d in range(D):
            if (s + d) % 2 == 0:
                n = int(rngb.randint(1, 9000))
                blobs[(s, d)] = rngb.randint(
                    0, 256, size=n).astype(np.uint8).tobytes()
    delivered = px.mesh_blob_exchange(mesh, blobs, budget=budget)
    assert delivered == blobs, (
        "chunked exchange diverged on process %d" % pid)
    info = px.last_info
    assert info["steps"] > 1, info
    assert not info["clamped"], info
    assert info["peak_inflight_bytes"] <= budget, info
    assert info["peak_inflight_bytes"] == replan.plan_exchange(
        D, {sd: len(b) for sd, b in blobs.items()}, budget=budget,
        gather=True).peak_inflight_bytes
    print("PROC_%d_OK" % pid, flush=True)
""").replace("@ROOT@", ROOT)


# Engine pipelines across the 2-process mesh: every process drives the SAME
# DSL runs (input replicated; the collectives span both processes' devices
# and gather-replicate results), and each pins its mesh results
# byte-identical to the host path computed in-process with the mesh off.
_ENGINE_WORKER = textwrap.dedent("""
    import os, sys, tempfile
    pid = int(sys.argv[1]); port = sys.argv[2]
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, "@ROOT@")
    from dampr_tpu.parallel.mesh import init_distributed, data_mesh
    init_distributed(coordinator_address="localhost:%s" % port,
                     num_processes=2, process_id=pid)
    assert jax.process_count() == 2 and len(jax.devices()) == 8
    from dampr_tpu import Dampr, settings
    from dampr_tpu.runner import MTRunner
    settings.scratch_root = tempfile.mkdtemp(prefix="dampr-mp-%d-" % pid)
    settings.partitions = 8
    settings.device_min_batch = 1

    def run_pipe(pipe, name, budget=None):
        kw = {"memory_budget": budget} if budget else {}
        runner = MTRunner("%s-p%d" % (name, pid), pipe.pmer.graph, **kw)
        out = runner.run([pipe.source])[0]
        got = list(out.read())
        return got, runner

    # 1. keyed fold through the collective fold program (2 processes)
    settings.mesh_fold = "on"; settings.mesh_exchange = "off"
    data = list(range(6000))
    fold_mesh, r = run_pipe(
        Dampr.memory(data, partitions=8).count(lambda x: x % 23),
        "mp-fold-mesh")
    assert r.mesh_folds >= 1, "mesh fold never engaged"
    settings.mesh_fold = "off"
    fold_host, _ = run_pipe(
        Dampr.memory(data, partitions=8).count(lambda x: x % 23),
        "mp-fold-host")
    assert sorted(fold_mesh) == sorted(fold_host), (
        "mesh keyed fold diverged from host on process %d" % pid)

    # 2. non-associative group_by through the general byte exchange
    settings.mesh_fold = "off"; settings.mesh_exchange = "on"
    gdata = [(i % 13, i) for i in range(4000)]
    def build_g():
        return (Dampr.memory(gdata, partitions=8)
                .group_by(lambda x: x[0])
                .reduce(lambda k, vs: sorted(v[1] for v in vs)[:3]))
    g_mesh, r = run_pipe(build_g(), "mp-group-mesh")
    assert r.mesh_exchanges >= 1, "general exchange never engaged"
    assert r.mesh_exchange_steps >= 1
    assert r.mesh_exchange_peak_inflight <= settings.exchange_hbm_budget
    settings.mesh_exchange = "off"
    g_host, _ = run_pipe(build_g(), "mp-group-host")
    assert g_mesh == g_host, (
        "mesh group_by diverged from host on process %d" % pid)

    # 3. range sort: read-time redistribution through the collective
    from dampr_tpu.parallel import exchange as px
    settings.mesh_exchange = "on"
    nums = [((i * 2654435761) % 99991) - 50000 for i in range(5000)]
    before = px.total_exchanges
    s_mesh, _ = run_pipe(
        Dampr.memory(nums, partitions=8).sort_by(lambda x: x),
        "mp-sort-mesh", budget=1 << 16)
    assert px.total_exchanges > before, "range sort never hit the mesh"
    settings.mesh_exchange = "off"
    s_host, _ = run_pipe(
        Dampr.memory(nums, partitions=8).sort_by(lambda x: x),
        "mp-sort-host", budget=1 << 16)
    assert s_mesh == s_host, (
        "mesh range sort diverged from host on process %d" % pid)
    assert [v for _k, v in s_mesh] == sorted(nums)
    print("ENGINE_%d_OK" % pid, flush=True)
""").replace("@ROOT@", ROOT)


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _spawn_workers(tmp_path, source, ok_marker, timeout=240):
    port = _free_port()
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # worker sets its own device count
    script = str(tmp_path / "worker.py")
    with open(script, "w") as f:
        f.write(source)
    procs = [
        subprocess.Popen(
            [sys.executable, script, str(i), str(port)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=env)
        for i in range(2)]
    outs = []
    for p in procs:
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append((p.returncode, out, err))
    for i, (rc, out, err) in enumerate(outs):
        assert rc == 0, (i, out, err[-2000:])
        assert ok_marker % i in out, (i, out, err[-2000:])


class TestTwoProcessBackend:
    def test_keyed_fold_and_psum_across_processes(self, tmp_path):
        _spawn_workers(tmp_path, _WORKER, "PROC_%d_OK")

    def test_engine_pipelines_across_processes(self, tmp_path):
        """Full DSL runs on the 2-process mesh: keyed fold (collective
        fold program), group_by (general byte exchange), and range sort
        (read-time redistribution) — each byte-identical to the host path
        on every process."""
        _spawn_workers(tmp_path, _ENGINE_WORKER, "ENGINE_%d_OK")


class TestExchangeSchedule:
    """Host-side property tests for the replan schedule (no processes
    spawned): for random blob shapes and budgets, the chunked schedule
    must respect the configured HBM budget, cover every byte exactly
    once in piece order, and reassemble to the original blobs."""

    def _random_sizes(self, rng, n_dev):
        sizes = {}
        for s in range(n_dev):
            for d in range(n_dev):
                if rng.random() < 0.6:
                    scale = rng.choice([10, 1000, 100000])
                    sizes[(s, d)] = rng.randrange(0, scale)
        return sizes

    def test_schedule_never_exceeds_budget(self):
        from dampr_tpu.parallel import replan

        rng = random.Random(42)
        for trial in range(200):
            n_dev = rng.choice([2, 4, 8, 16])
            gather = rng.random() < 0.5
            floor = replan.step_inflight_bytes(
                n_dev, replan.MIN_CAPACITY, gather)
            budget = rng.randrange(floor, 64 * floor)
            sizes = self._random_sizes(rng, n_dev)
            sched = replan.plan_exchange(n_dev, sizes, budget=budget,
                                         gather=gather)
            assert not sched.clamped
            assert sched.peak_inflight_bytes <= budget, (
                trial, budget, sched.peak_inflight_bytes)
            for step in sched.steps:
                assert step.inflight_bytes <= budget
                # capacity stays a pow2 at or above the floor
                c = step.capacity
                assert c >= replan.MIN_CAPACITY and (c & (c - 1)) == 0

    def test_schedule_covers_every_byte_in_order(self):
        from dampr_tpu.parallel import replan

        rng = random.Random(7)
        for _trial in range(100):
            n_dev = rng.choice([2, 4, 8])
            sizes = self._random_sizes(rng, n_dev)
            floor = replan.step_inflight_bytes(
                n_dev, replan.MIN_CAPACITY, False)
            sched = replan.plan_exchange(
                n_dev, sizes, budget=rng.randrange(floor, 32 * floor))
            seen = {sd: [] for sd in sizes}
            for step in sched.steps:
                for s, d, start, stop in step.cells:
                    assert stop - start <= step.capacity
                    seen[(s, d)].append((start, stop))
            for sd, n in sizes.items():
                spans = seen[sd]
                # contiguous, in order, exactly covering [0, n)
                at = 0
                for start, stop in spans:
                    assert start == at, (sd, spans)
                    at = stop
                assert at == n, (sd, at, n)
            assert sched.total_bytes == sum(sizes.values())

    def test_tiny_budget_clamps_at_floor(self):
        from dampr_tpu.parallel import replan

        sched = replan.plan_exchange(8, {(0, 1): 4096}, budget=1)
        assert sched.clamped
        # still moves everything, at the capacity floor
        assert sched.total_bytes == 4096
        assert all(s.capacity == replan.MIN_CAPACITY
                   for s in sched.steps)

    def test_explicit_chunk_knob_narrows_pieces(self):
        from dampr_tpu import settings
        from dampr_tpu.parallel import replan

        wide = replan.plan_exchange(4, {(0, 1): 1 << 20},
                                    budget=1 << 26)
        narrow = replan.plan_exchange(4, {(0, 1): 1 << 20},
                                      budget=1 << 26,
                                      chunk_bytes=4096)
        assert narrow.n_steps > wide.n_steps
        assert all(s.capacity <= 4096 for s in narrow.steps)
        # a non-pow2 chunk is an UPPER bound — pieces must round DOWN,
        # never exceed what the memory-pressured operator asked for
        odd = replan.plan_exchange(4, {(0, 1): 1 << 20},
                                   budget=1 << 26, chunk_bytes=5000)
        assert all(s.capacity <= 5000 for s in odd.steps)
        assert max(s.capacity for s in odd.steps) == 4096
        old = settings.exchange_chunk_bytes
        settings.exchange_chunk_bytes = 4096
        try:
            via_setting = replan.plan_exchange(4, {(0, 1): 1 << 20},
                                               budget=1 << 26)
            assert via_setting.n_steps == narrow.n_steps
        finally:
            settings.exchange_chunk_bytes = old

    def test_roundtrip_through_mesh_matches_naive(self, mesh8):
        """Scheduled exchange delivers byte-identical blobs at any
        budget (in-process 8-device mesh)."""
        from dampr_tpu.parallel import exchange as px, replan

        rng = random.Random(3)
        blobs = {}
        for s in range(8):
            for d in range(8):
                if rng.random() < 0.5:
                    n = rng.randrange(0, 30000)
                    blobs[(s, d)] = bytes(
                        rng.getrandbits(8) for _ in range(min(n, 512))
                    ) * max(1, n // 512)
        want = {sd: b for sd, b in blobs.items() if b}
        for budget in (1 << 17, 1 << 20, 1 << 26):
            out = px.mesh_blob_exchange(mesh8, blobs, budget=budget)
            assert out == want, budget
            floor = replan.step_inflight_bytes(8, replan.MIN_CAPACITY,
                                               False)
            if budget >= floor:
                assert px.last_info["peak_inflight_bytes"] <= budget
