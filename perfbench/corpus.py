"""The seeded corpus and the plain references' process pool.

Copied from ``dampr_tpu/bench_tfidf.py:corpus_text`` (the Shakespeare
shape) so that no program PR can move the yardstick; parameterised by the
configuration file.  Nothing here imports JAX or ``dampr_tpu``: the pool
is spawned before the parent touches JAX, and its children never do.
"""

import importlib.util
import multiprocessing
import os

MASK32 = 0xFFFFFFFF


def shard_text(seed, idx, nbytes, corpus):
    """Line-aligned Zipf text of at most ``nbytes`` (shorter only by the
    cut partial line), drawn from ``(seed, idx)``.  ``corpus`` holds
    ``vocabulary``, ``zipf_exponent``, ``tokens_per_line`` ([min, max])."""
    import numpy as np

    # RandomState takes 32-bit words; the driver's seeds pass 2**31.
    rng = np.random.RandomState([seed & MASK32, (seed >> 32) & MASK32, idx])
    vocab_n = corpus["vocabulary"]
    lo, hi = corpus["tokens_per_line"]
    vocab = np.array(["w%04x" % i if i > 200 else "t%d" % i
                      for i in range(vocab_n)], dtype=object)
    probs = 1.0 / np.arange(1, vocab_n + 1) ** corpus["zipf_exponent"]
    probs /= probs.sum()
    parts = []
    written = 0
    while written < nbytes:
        ids = rng.choice(vocab_n, size=(20000,), p=probs)
        lens = rng.randint(lo, hi + 1, size=2000)
        pos = 0
        out = []
        for n in lens:
            out.append(" ".join(vocab[ids[pos:pos + n]]))
            pos += n
            if pos + hi + 1 > len(ids):
                break
        chunk = "\n".join(out) + "\n"
        parts.append(chunk)
        written += len(chunk)
    text = "".join(parts)
    return text[:text.rindex("\n", 0, nbytes) + 1].encode("ascii")


def _make_shard(task):
    return shard_text(*task)


def load(kind, name):
    """``perfbench/<kind>/<name>.py`` as a module: cells, pipelines and
    metrics are found by the names in ``BENCHMARK.json``."""
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

    def start_corpus(self, seed, corpus, total_bytes, shard_bytes, path):
        n = -(-total_bytes // shard_bytes)
        tasks = [(seed, i, min(shard_bytes, total_bytes - i * shard_bytes),
                  corpus) for i in range(n)]
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
