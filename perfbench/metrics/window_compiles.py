"""Programs compiled or loaded from the persistent cache inside the window
(``jax.monitoring`` backend-compile events, ``meter.py``)."""


def read(run):
    return run.window_compiles
