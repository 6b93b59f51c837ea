"""The seeded input and the plain references' process pool.

A configuration names its data generator, ``perfbench/data/<kind>.py``,
by its ``"data"`` key (``zipf_text`` where it has none) and gives it its
``corpus`` block as parameters.  Each generator has one entry,
``shard(seed, idx, offset, nbytes, params) -> bytes``: shard ``idx`` of
the input, at most ``nbytes`` long, whose first byte lies at ``offset`` of
the whole input when every shard before it is whole.  The same
``(seed, idx)`` gives the same bytes.  Nothing here imports JAX or
``dampr_tpu``: the pool is spawned before the parent touches JAX, and its
children never do.
"""

import importlib.util
import multiprocessing
import os

#: The generator of a configuration that names none.
DEFAULT_DATA = "zipf_text"


def _make_shard(task):
    kind, seed, idx, offset, nbytes, params = task
    return load("data", kind).shard(seed, idx, offset, nbytes, params)


def load(kind, name):
    """``perfbench/<kind>/<name>.py`` as a module: data generators,
    pipelines and metrics are found by the names in ``BENCHMARK.json`` and
    the files it points to."""
    path = os.path.join(bench_dir(), kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench_{}_{}".format(kind, name.replace("-", "_").replace(
            ".", "_")), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reference_shard(task):
    form, path, start, end = task
    with open(path, "rb") as f:
        f.seek(start)
        text = f.read(end - start).decode("ascii")
    return load("pipelines", form).reference_shard(text)


class Pool(object):
    """A spawn pool that writes the corpus before the window and computes
    the reference over it after the window.  Idle in between."""

    def __init__(self, processes):
        ctx = multiprocessing.get_context("spawn")
        self._pool = ctx.Pool(processes)
        self._pending = None
        self.path = None
        self.bounds = []

    def start_corpus(self, seed, kind, params, total_bytes, shard_bytes,
                     path):
        """Start writing ``seed``'s input from generator ``kind``."""
        n = -(-total_bytes // shard_bytes)
        tasks = [(kind, seed, i, i * shard_bytes,
                  min(shard_bytes, total_bytes - i * shard_bytes), params)
                 for i in range(n)]
        self.path = path
        self._pending = self._pool.map_async(_make_shard, tasks)

    def wait_corpus(self):
        """Write the shards as one file; returns its size in bytes."""
        pos = 0
        with open(self.path, "wb") as out:
            for text in self._pending.get():
                out.write(text)
                self.bounds.append((pos, pos + len(text)))
                pos += len(text)
        self._pending = None
        return pos

    def reference_parts(self, form):
        """Each shard's part of the plain reference, in shard order."""
        return self._pool.map(
            _reference_shard,
            [(form, self.path, a, b) for a, b in self.bounds])

    def close(self):
        self._pool.terminate()
        self._pool.join()


def bench_dir():
    return os.path.dirname(os.path.abspath(__file__))
