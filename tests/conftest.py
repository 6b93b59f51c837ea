"""Test rig: an 8-device virtual CPU mesh (SURVEY §4's 'multi-device without
a real pod' fake backend).

The suite's expectations (mesh sizes, auto-resolved thresholds, the
lowering legs CI pins) are written for that mesh, so it is forced even on
a host with an accelerator attached, where JAX would otherwise pick the
accelerator.  Three things must happen before JAX initializes a backend:
- XLA_FLAGS gains --xla_force_host_platform_device_count=8 (env, read at
  backend init);
- the platform is pinned to cpu;
- the persistent compilation cache moves to this session's scratch, so
  no test (nor a subprocess a test starts, which inherits the variable)
  writes into the checkout's cache.
"""

import atexit
import os
import shutil
import tempfile

# Session-fresh scratch root: the run-history corpus (obs.history) and
# resume checkpoints persist under scratch across pytest SESSIONS, so a
# shared /tmp/dampr_tpu would let a previous session's records steer
# stats-driven adaptation inside this one (fixed run names are reused
# all over the suite).  Within one session behavior is unchanged —
# tests still share one root, which the cross-run resume/adaptive tests
# rely on.
_SESSION = tempfile.mkdtemp(prefix="dampr-tpu-tests-")
atexit.register(shutil.rmtree, _SESSION, True)
_SCRATCH = os.path.join(_SESSION, "scratch")
os.makedirs(_SCRATCH)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(_SESSION, "jax-cache")

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_compilation_cache_dir",
                  os.environ["JAX_COMPILATION_CACHE_DIR"])

# Small blocks should still exercise the device path in tests: pin the
# dispatch threshold so backend-specific auto-resolution never de-targets
# device-branch regression tests.
os.environ.setdefault("DAMPR_TPU_USE_DEVICE", "1")
from dampr_tpu import settings as _settings  # noqa: E402

_settings.device_min_batch = 4096
_settings.scratch_root = _SCRATCH

import pytest  # noqa: E402

#: The reference repo's README, used by several kernels tests as a natural-
#: text corpus.  Containers without the reference mounted get a
#: deterministic synthetic stand-in with the same character classes the
#: real file exercises (mixed case, punctuation, digits, underscores,
#: blank lines, a little UTF-8).
_REFERENCE_README = "/root/reference/README.md"


def reference_text():
    try:
        with open(_REFERENCE_README) as f:
            return f.read()
    except OSError:
        words = ["Dampr", "map", "reduce", "Stream_Fold", "chunk42",
                 "naïve", "pipeline", "DAG", "a", "the", "of", "tokens",
                 "spill", "merge", "TPU", "block", "codec", "fold"]
        lines = []
        for i in range(120):
            row = [words[(i * 7 + j * 3) % len(words)]
                   for j in range(3 + i % 9)]
            sep = ", " if i % 4 else " -- "
            lines.append(sep.join(row) + (".", "!", "", ":")[i % 4])
            if i % 17 == 0:
                lines.append("")
        return "\n".join(lines) + "\n"


@pytest.fixture(scope="session")
def mesh8():
    import numpy as np
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    assert devs.size == 8, devs
    return Mesh(devs, ("shards",))


@pytest.fixture
def partitions8():
    """Shared: pin settings.partitions to 8 for a test, restoring after."""
    from dampr_tpu import settings

    old = settings.partitions
    settings.partitions = 8
    yield
    settings.partitions = old
