"""Ring collectives over the mesh axis via ``lax.ppermute``.

``psum`` lets XLA pick the all-reduce algorithm; these explicit ring
implementations express the bandwidth-optimal pattern directly — each step
moves one shard to the ring neighbor, so every link carries ``(D-1)/D`` of
the payload total regardless of device count.  This is the building block
behind ring attention / ring all-reduce formulations (sequence-parallel
passes of per-shard state around the ICI/DCN ring), provided here as the
framework's ring-communication primitive and validated against ``psum``.
"""

import functools

import numpy as np

from .. import settings
from .mesh import mesh_size


@functools.lru_cache(maxsize=None)
def _ring_allreduce_program(mesh, axis, op):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh_size(mesh)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def combine(a, b):
        if op == "sum":
            return a + b
        if op == "max":
            return jnp.maximum(a, b)
        if op == "min":
            return jnp.minimum(a, b)
        raise ValueError(op)

    def per_device(x):
        # accumulate while rotating shards around the ring; after D-1 hops
        # every device holds the full reduction of all shards.
        acc = x
        rot = x
        for _ in range(n_dev - 1):
            rot = lax.ppermute(rot, axis, perm)
            acc = combine(acc, rot)
        return acc

    def program(x):
        return jax.shard_map(
            per_device, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis))(x)

    return jax.jit(program)


def ring_allreduce(mesh, x, op="sum"):
    """All-reduce a [D, ...] device-sharded array around the ring; every
    device's output shard holds the elementwise reduction across shards."""
    n_dev = mesh_size(mesh)
    x = np.asarray(x)
    assert x.shape[0] % n_dev == 0, (
        "leading dim {} must divide across {} devices".format(
            x.shape[0], n_dev))
    prog = _ring_allreduce_program(mesh, settings.mesh_axis, op)
    return np.asarray(prog(x))


@functools.lru_cache(maxsize=None)
def _ring_allgather_program(mesh, axis):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    n_dev = mesh_size(mesh)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def per_device(x):
        idx = lax.axis_index(axis)
        parts = [jnp.zeros_like(x) for _ in range(n_dev)]
        rot = x
        rid = idx  # owner id of the shard currently held in `rot`
        for _step in range(n_dev):
            hot = [(rid == j).astype(x.dtype) for j in range(n_dev)]
            parts = [p + h * rot for p, h in zip(parts, hot)]
            rot = lax.ppermute(rot, axis, perm)
            # perm sends i -> i+1, so the shard we *receive* came from our
            # ring predecessor: the held shard's owner id decreases each hop.
            rid = (rid - 1) % n_dev
        return jnp.concatenate(parts, axis=0)

    def program(x):
        return jax.shard_map(
            per_device, mesh=mesh, in_specs=(P(axis),), out_specs=P(axis))(x)

    return jax.jit(program)


def ring_allgather(mesh, x):
    """All-gather shards around the ring: input sharded [D*n, ...] ->
    output [D, D*n, ...]-equivalent where every device holds all shards
    (returned globally as [D * total, ...])."""
    prog = _ring_allgather_program(mesh, settings.mesh_axis)
    return np.asarray(prog(np.asarray(x)))
