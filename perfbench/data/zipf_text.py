"""The seeded Zipf text that stands in for the Shakespeare corpus: the
data of every configuration that names no ``data`` generator.

Copied from ``dampr_tpu/bench_tfidf.py:corpus_text`` (the Shakespeare
shape) so that no program PR can move the yardstick; parameterised by the
configuration's ``corpus`` block (``vocabulary``, ``zipf_exponent``,
``tokens_per_line`` as [min, max])."""

MASK32 = 0xFFFFFFFF


def shard(seed, idx, offset, nbytes, params):
    """Line-aligned Zipf text of at most ``nbytes`` (shorter only by the
    cut partial line), drawn from ``(seed, idx)``; ``offset`` plays no
    part."""
    import numpy as np

    # RandomState takes 32-bit words; the driver's seeds pass 2**31.
    rng = np.random.RandomState([seed & MASK32, (seed >> 32) & MASK32, idx])
    vocab_n = params["vocabulary"]
    lo, hi = params["tokens_per_line"]
    vocab = np.array(["w%04x" % i if i > 200 else "t%d" % i
                      for i in range(vocab_n)], dtype=object)
    probs = 1.0 / np.arange(1, vocab_n + 1) ** params["zipf_exponent"]
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
