"""Process start until the window opens (host clock): JAX init, the
corpus, and the warm-up jobs with what they compile or load."""


def read(run):
    return run.setup_s
