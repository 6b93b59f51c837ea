"""Compile rehearsals for a TPU v5e that is described, not attached.

Every device program the chip smoke (``chip_smoke.py``) reaches is
compiled here by the TPU compiler for a ``v5e:2x2`` topology: the lowered
token fold, the handoff table, scatter, staging and finalize programs,
the segment fold and lexsort, the Pallas segmented fold, and the mesh
keyed fold over four described chips.  Nothing runs: a pass means the chip's compiler accepts
the program at these shapes, not that it is right or fast.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports this file.
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure: no TPU compiler
        pytest.skip("no v5e:2x2 topology can be described here: {}".format(e))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)``: an argument shape on one described chip."""
    import jax
    from jax.sharding import SingleDeviceSharding

    one = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    return make


def _compile(fn, *args):
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("dedup", [False, True])
def test_token_fold_program(spec, dedup):
    """The lowered scan's program at a real bucket (rows padded to a
    power of two, ``ops/lower.py:_pad_batch``)."""
    from dampr_tpu.ops import lower

    n, L = 4096, 16
    fn = lower._token_fold_jit.__wrapped__(n, L, dedup, False, False)
    _compile(fn, spec((n, L), np.uint8), spec((n,), np.int32),
             spec((n,), np.int32))


@pytest.mark.parametrize("dedup_k", [0, 16])
def test_handoff_table_program(spec, dedup_k):
    """The table probe program at its 4096-row floor, per-line dedup
    both ways (windowed compares and the (slot, line) sort), probing a
    ``(2 * cap, 2)`` bucket index with two row gathers and no search
    loop."""
    from dampr_tpu.ops import handoff

    n, L, cap, Lcap = 4096, 16, 4096, 16
    fn = handoff._table_program.__wrapped__(n, L, cap, Lcap, True, "int32",
                                            dedup_k)
    text = _compile(fn, spec((n, L), np.uint8), spec((n,), np.int32),
                    spec((n,), np.int32), spec((2 * cap, 2), np.uint32),
                    spec((cap, Lcap), np.uint8), spec((cap,), np.int32),
                    spec((cap + 1,), np.int32))
    # One loop is left, the FNV hash over the byte columns.
    assert text.count(" while(") == 1, text.count(" while(")


def test_handoff_scatter_stage_and_finalize_programs(spec):
    """The accumulator scatter, the table-row and index-row staging
    and the job-end partition gather, at pow2 buckets."""
    from dampr_tpu.ops import handoff

    cap = 4096
    for m in handoff._UPDATE_ROWS:
        _compile(handoff._scatter_program.__wrapped__(),
                 spec((cap + 1,), np.int32), spec((m,), np.int32),
                 spec((m,), np.int32))
        _compile(handoff._set_rows_program.__wrapped__(),
                 spec((cap, 16), np.uint8), spec((cap,), np.int32),
                 spec((2 * cap, 2), np.uint32), spec((m,), np.int32),
                 spec((m, 16), np.uint8), spec((m,), np.int32),
                 spec((m,), np.int32), spec((m, 2), np.uint32))
    _compile(handoff._finalize_program.__wrapped__(),
             spec((cap + 1,), np.int32), spec((8192,), np.int32))


def test_segment_fold_and_lexsort(spec):
    from dampr_tpu.ops import segment

    n = 4096
    _compile(segment._segment_fold_jit.__wrapped__("sum", n),
             spec((n,), np.int32), spec((n,), np.int32))
    _compile(segment._lexsort_jit.__wrapped__(), spec((n,), np.uint8),
             spec((n,), np.uint32), spec((n,), np.uint32))


def test_pallas_segfold_is_a_tpu_kernel(spec):
    """16 tiles of the fused segmented fold compile through Mosaic."""
    from dampr_tpu.ops import pallas_segfold as SF

    rows = 16 * SF._ROWS
    fn = SF._segfold_call.__wrapped__(16, False)
    text = _compile(fn, *(spec((rows, SF._LANES), np.int32)
                          for _ in range(4)))
    assert "tpu_custom_call" in text


def test_mesh_keyed_fold_on_four_chips(topo):
    """The shard_map keyed fold (local fold, all_to_all, final fold)
    sharded over the four described chips."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from dampr_tpu.parallel import shuffle

    mesh = Mesh(np.asarray(topo.devices), ("shards",))
    sharded = NamedSharding(mesh, P("shards"))
    n_local = 4096
    args = [jax.ShapeDtypeStruct((4 * n_local,), dt, sharding=sharded)
            for dt in (np.uint32, np.uint32, np.int32, np.uint32)]
    fn = shuffle._build_fold_program.__wrapped__(
        mesh, 4, n_local, 1536, "sum", "int32", "shards", True)
    text = _compile(fn, *args)
    assert "all-to-all" in text


#: The four-chip word count's fold buckets: each chip's shard of the
#: fold's input (``n_local`` rows: its jobs' vocabulary-sized handoff refs
#: of up to 512 rows, or its compacted share of the keys), the capacity
#: the fold derives from it, and the rows a chip's fold output holds.
N_LOCAL = 1 << 17
CAPACITY = 49152


@pytest.mark.parametrize("prefix, rows", [(True, 512), (False, 8192)])
def test_chip_assembly_write(spec, prefix, rows):
    """One piece written into a chip's shard buffers: a handoff ref
    (valid prefix count) or a compacted piece (live-row lane)."""
    from dampr_tpu.parallel import shuffle

    i32, u32 = np.int32, np.uint32
    fn = shuffle._write_part.__wrapped__(prefix)
    bufs = [spec((N_LOCAL,), dt) for dt in (u32, u32, i32, u32)]
    piece = [spec((rows,), dt) for dt in (u32, u32, i32)]
    ok = spec((), i32) if prefix else spec((rows,), u32)
    _compile(fn, *bufs, *piece, ok, spec((), i32))


def _sharded(topo, rows, dtypes):
    """Argument shapes of ``rows`` per chip, sharded over the four
    described chips, and their mesh."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices), ("shards",))
    sharded = NamedSharding(mesh, P("shards"))
    return mesh, [jax.ShapeDtypeStruct((4 * rows,), dt, sharding=sharded)
                  for dt in dtypes]


LANES = (np.uint32, np.uint32, np.int32, np.uint32)


def test_chip_compaction(topo, spec):
    """The fold's output compacted on every chip at once: each chip's
    share sorted live-first with its live count, then cut to a prefix,
    one program each over the four described chips (the mesh axis is
    ``settings.mesh_axis``'s default); and on one chip, the one-chip
    cells' compaction."""
    from dampr_tpu.parallel import shuffle

    n = 4 * CAPACITY
    mesh, args = _sharded(topo, n, LANES)
    _compile(shuffle._shard_live_prefix_sort.__wrapped__(mesh, n), *args)
    _compile(shuffle._shard_prefix.__wrapped__(mesh, 1 << 15), *args)
    _compile(shuffle._live_prefix_sort.__wrapped__(n),
             *[spec((n,), dt) for dt in LANES])


def test_mesh_fold_at_the_four_chip_cells_bucket(topo):
    """The keyed fold over an input sharded over the four described
    chips, at the four-chip word count's bucket."""
    from dampr_tpu.parallel import shuffle

    mesh, args = _sharded(topo, N_LOCAL, LANES)
    fn = shuffle._build_fold_program.__wrapped__(
        mesh, 4, N_LOCAL, CAPACITY, "sum", "int32", "shards", True)
    assert "all-to-all" in _compile(fn, *args)
