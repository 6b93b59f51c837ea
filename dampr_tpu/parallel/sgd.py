"""Mini-batch SGD via data-parallel gradient psum — the stretch workload from
BASELINE.md (mini-batch logistic regression with map/reduce gradients).

The reference has no ML layer at all; this is the TPU-native expression of
its "aggregate partial results per partition, combine globally" pattern
(SURVEY §2 parallelism item 3): per-device gradient = the map-side partial,
``lax.psum`` = the reduce.  The matmuls are MXU-shaped: features on the
contracting dimension, batch sharded over the mesh axis.
"""

import functools

import numpy as np

from .. import settings
from .mesh import mesh_size


def init_params(n_features, seed=0):
    rng = np.random.RandomState(seed)
    return {"w": (rng.randn(n_features) * 0.01).astype(np.float32),
            "b": np.float32(0.0)}


def _loss_fn(params, X, y):
    import jax.numpy as jnp

    logits = X @ params["w"] + params["b"]
    # numerically-stable logistic loss, mean over the *local* shard
    return jnp.mean(jnp.maximum(logits, 0) - logits * y
                    + jnp.log1p(jnp.exp(-jnp.abs(logits))))


@functools.lru_cache(maxsize=None)
def _build_train_step(mesh, lr, axis):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    # Batch shards over the mesh axis, params replicated.  The shard_map
    # computes per-device shard losses; differentiation happens OUTSIDE, so
    # the cross-device gradient combine is inserted by the transpose rules
    # (an automatic psum over the replicated params) rather than hand-written
    # — hand-psum'ing inside would double-count under vma-typed shard_map.
    per_shard_loss = jax.shard_map(
        lambda p, xs, ys: jnp.expand_dims(_loss_fn(p, xs, ys), 0),
        mesh=mesh,
        in_specs=(P(), P(axis), P(axis)),
        out_specs=P(axis),
    )

    def global_loss(params, X, y):
        return jnp.mean(per_shard_loss(params, X, y))

    def step(params, X, y):
        loss, grads = jax.value_and_grad(global_loss)(params, X, y)
        new_params = jax.tree.map(lambda p, g: p - lr * g, params, grads)
        return new_params, loss

    return jax.jit(step)


def train_step(mesh, params, X, y, lr=0.1):
    """One DP SGD step over the mesh: X [B, F] and y [B] sharded on batch,
    params replicated, gradients psum'd over ICI."""
    step = _build_train_step(mesh, float(lr), settings.mesh_axis)
    return step(params, X, y)


def train(mesh, X, y, n_steps=50, lr=0.5, seed=0):
    """Full training loop; returns (params, final_loss)."""
    n_dev = mesh_size(mesh)
    n = (len(X) // n_dev) * n_dev  # equal shards
    X = np.asarray(X, dtype=np.float32)[:n]
    y = np.asarray(y, dtype=np.float32)[:n]
    params = init_params(X.shape[1], seed)
    loss = None
    for _ in range(n_steps):
        params, loss = train_step(mesh, params, X, y, lr)
    return params, float(loss)
