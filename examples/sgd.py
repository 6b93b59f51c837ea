"""Mini-batch SGD logistic regression — the BASELINE.md stretch workload:
gradients computed as a map/reduce over the dataset, combined with a device
psum over the mesh.

Two layers demonstrate the same decomposition:
1. DSL map/reduce: per-partition gradient partials via ``partition_map``,
   summed with an associative fold (the reference's only route).
2. ``dampr_tpu.parallel.sgd``: the same math as one jitted shard_map program —
   batch sharded over the mesh, gradients psum'd over ICI.

Usage: python examples/sgd.py [n_samples] [n_features] [steps]
"""

import _pathfix  # noqa: F401  (repo root onto sys.path)

import sys

import numpy as np

from dampr_tpu import Dampr, setup_logging
from dampr_tpu.parallel import sgd
from dampr_tpu.parallel.mesh import data_mesh


def dsl_gradient(pipe, w, b):
    """One gradient evaluation as a Dampr map/reduce: partials per partition,
    associative vector-sum fold."""
    def partial_grads(rows):
        gw = np.zeros_like(w)
        gb = 0.0
        n = 0
        for x, y in rows:
            logit = float(x @ w + b)
            s = 1.0 / (1.0 + np.exp(-logit))
            gw += (s - y) * x
            gb += s - y
            n += 1
        yield 1, (gw, gb, n)

    def add3(a, c):
        return (a[0] + c[0], a[1] + c[1], a[2] + c[2])

    (_, (gw, gb, n)), = (pipe.partition_map(partial_grads)
                         .fold_by(lambda _x: 1, add3, lambda x: x).read())
    return gw / n, gb / n


def build_gradient_pipeline(X, y, w, b):
    """One gradient evaluation as a handle (nothing runs until read)."""
    def partial_grads(rows):
        gw = np.zeros_like(w)
        gb = 0.0
        n = 0
        for x, yv in rows:
            logit = float(x @ w + b)
            s = 1.0 / (1.0 + np.exp(-logit))
            gw += (s - yv) * x
            gb += s - yv
            n += 1
        yield 1, (gw, gb, n)

    def add3(a, c):
        return (a[0] + c[0], a[1] + c[1], a[2] + c[2])

    return (Dampr.memory(list(zip(X, y)), partitions=8).cached()
            .partition_map(partial_grads)
            .fold_by(lambda _x: 1, add3, lambda x: x))


def lint_pipelines():
    """dampr-tpu-lint discovery hook (docs/analysis.md)."""
    rng = np.random.RandomState(0)
    X = rng.randn(16, 4).astype(np.float32)
    y = (X[:, 0] > 0).astype(np.float32)
    w = np.zeros(4, dtype=np.float32)
    return [("sgd_gradient", build_gradient_pipeline(X, y, w, 0.0))]


def main(n=4096, f=64, steps=10):
    rng = np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    true_w = rng.randn(f).astype(np.float32)
    y = (X @ true_w > 0).astype(np.float32)

    # --- DSL route: map/reduce gradients --------------------------------
    pipe = Dampr.memory(list(zip(X, y)), partitions=8).cached()
    w = np.zeros(f, dtype=np.float32)
    b = 0.0
    for step in range(steps):
        gw, gb = dsl_gradient(pipe, w, b)
        w -= 1.0 * gw
        b -= 1.0 * gb
    acc = float((((X @ w + b) > 0) == (y > 0.5)).mean())
    print("DSL map/reduce SGD:   {} steps, accuracy {:.3f}".format(steps, acc))

    # --- Mesh route: one shard_map program, psum'd grads ----------------
    mesh = data_mesh()
    params, loss = sgd.train(mesh, X, y, n_steps=steps * 4, lr=1.0)
    pred = (X @ params["w"] + params["b"]) > 0
    acc2 = float((pred == (y > 0.5)).mean())
    print("mesh psum SGD:        {} devices, loss {:.4f}, accuracy {:.3f}"
          .format(len(mesh.devices.flat), loss, acc2))


if __name__ == "__main__":
    setup_logging()
    args = [int(a) for a in sys.argv[1:4]]
    main(*args)
