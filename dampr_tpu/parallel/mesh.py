"""Mesh construction helpers.

One logical axis (``settings.mesh_axis``) carries data-parallel record shards;
the same axis carries the all_to_all shuffle.  Multi-host topologies reuse the
identical program: jax enumerates global devices and XLA routes ICI within a
host/slice and DCN across, so nothing here is host-count-aware.
"""

import numpy as np

from .. import settings


def data_mesh(devices=None, n=None):
    """A 1-D mesh over ``devices`` (default: all) named by settings.mesh_axis."""
    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    if n is not None:
        assert n <= len(devices), (
            "requested {} devices, have {}".format(n, len(devices)))
        devices = devices[:n]
    return Mesh(np.asarray(devices), (settings.mesh_axis,))


def default_mesh():
    return data_mesh()


def mesh_size(mesh):
    return int(np.prod(list(mesh.shape.values())))


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None):
    """Join a multi-host deployment: after this, ``jax.devices()`` spans every
    host's chips and the same mesh programs run with XLA routing ICI within a
    slice and DCN across hosts — no other code changes (the mesh abstraction
    is host-count-agnostic by design, SURVEY §7 hard part 5).

    Arguments default to the DAMPR_TPU_COORDINATOR / DAMPR_TPU_NUM_PROCESSES
    / DAMPR_TPU_PROCESS_ID environment variables (the engine's own spelling,
    set per rank by launchers and the multi-process benches), falling back
    to JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES / JAX_PROCESS_ID (read
    here — jax itself only reads the coordinator address) or to full
    auto-detection on managed clusters (cloud TPU pods, Slurm, k8s).  Call
    once per process before any jax use.
    """
    import os

    import jax

    if coordinator_address is None:
        coordinator_address = (os.environ.get("DAMPR_TPU_COORDINATOR")
                               or os.environ.get("JAX_COORDINATOR_ADDRESS")
                               or None)
    if num_processes is None:
        raw = (os.environ.get("DAMPR_TPU_NUM_PROCESSES")
               or os.environ.get("JAX_NUM_PROCESSES"))
        if raw:
            num_processes = int(raw)
    if process_id is None:
        raw = (os.environ.get("DAMPR_TPU_PROCESS_ID")
               or os.environ.get("JAX_PROCESS_ID"))
        if raw is not None and raw != "":
            process_id = int(raw)

    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id

    # CPU-only deployments need an explicit cross-process collectives
    # implementation: the XLA CPU client ships none by default ("
    # Multiprocess computations aren't implemented on the CPU backend"),
    # but jaxlib bundles gloo TCP collectives — selecting them here makes
    # the same mesh programs span processes on plain CPUs (the tier-1
    # two-process backend test runs exactly this path).  Guarded: older
    # jax without the flag, or non-CPU platforms, are left untouched.
    try:
        platforms = jax.config.jax_platforms or ""
        first = platforms.split(",")[0]
        # Engage unless a non-CPU platform is explicitly selected: with
        # platform auto-detection (JAX_PLATFORMS unset) the flag is still
        # safe — it configures only the CPU client's collectives, which
        # accelerator deployments never route through.
        if (first in ("", "cpu")
                and "jax_cpu_collectives_implementation"
                in jax.config.values):
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception:  # noqa: BLE001 - best-effort; initialize() decides
        pass
    jax.distributed.initialize(**kwargs)
    global _initialized
    _initialized = True
    _clock_handshake()


_initialized = False

#: Clock-alignment record from the post-init barrier handshake, or None
#: (single-process, or the handshake collective failed).  Keys:
#: ``barrier_perf`` — this process's ``time.perf_counter()`` captured the
#: instant the post-init barrier collective RETURNED (every rank exits a
#: barrier within network latency of the same wall moment, so this value
#: is the per-rank anchor of one fleet-common instant — no wall-clock
#: trust, NTP drift never enters the merged timeline); ``barrier_wall``
#: — ``time.time()`` at the same instant (display only, never used for
#: alignment); ``method`` — which collective produced the barrier.
clock_sync = None


def _clock_handshake():
    """Barrier-timestamp handshake: run one collective every rank must
    enter, and record the per-rank monotonic clock at its exit.  The
    fleet trace merge (:mod:`dampr_tpu.obs.fleet`) subtracts each rank's
    ``barrier_perf`` from its span timestamps, so per-rank timelines
    align on the barrier instant instead of trusting wall clocks.
    Best-effort: a failed handshake leaves ``clock_sync`` None and the
    merge falls back to wall-start alignment (recorded as degraded)."""
    global clock_sync
    import time

    import jax

    if jax.process_count() <= 1:
        return
    from jax.experimental import multihost_utils

    try:
        multihost_utils.sync_global_devices("dampr_tpu_clock_handshake")
    except Exception:
        clock_sync = None
        return
    clock_sync = {
        "barrier_perf": time.perf_counter(),
        "barrier_wall": time.time(),
        "method": "sync_global_devices",
    }


def rank_info():
    """``(process_id, num_processes)`` WITHOUT forcing a jax backend
    init: once the process group is up the authoritative jax values are
    used; before that (or in never-distributed processes) the launcher
    env (``DAMPR_TPU_PROCESS_ID`` / ``DAMPR_TPU_NUM_PROCESSES``, JAX_*
    fallback) is read, defaulting to ``(0, 1)``.  This is the gate the
    observability plane tags every artifact with — it must stay safe to
    call from finalizers, crash paths, and CLI tools that never touch
    jax."""
    import os

    if _initialized:
        import jax

        return jax.process_index(), jax.process_count()
    raw_n = (os.environ.get("DAMPR_TPU_NUM_PROCESSES")
             or os.environ.get("JAX_NUM_PROCESSES"))
    raw_id = (os.environ.get("DAMPR_TPU_PROCESS_ID")
              or os.environ.get("JAX_PROCESS_ID"))
    try:
        n = int(raw_n) if raw_n else 1
        pid = int(raw_id) if raw_id not in (None, "") else 0
    except ValueError:
        return 0, 1
    if n <= 1:
        return 0, 1
    return pid, n


def maybe_init_distributed():
    """Join a multi-process deployment IF the environment configures one
    (``DAMPR_TPU_COORDINATOR`` / ``JAX_COORDINATOR_ADDRESS`` set), else
    no-op.  Idempotent — safe to call from every CLI entry point and
    bench main, so any dampr_tpu process dropped onto a pod rank with the
    coordinator env wired joins the process group before its first jax
    use with zero code changes (the pjit-spans-processes property,
    SNIPPETS [1]).  Returns True when this call performed the init."""
    import os

    if _initialized:
        return False
    if not (os.environ.get("DAMPR_TPU_COORDINATOR")
            or os.environ.get("JAX_COORDINATOR_ADDRESS")):
        return False
    init_distributed()
    return True


def process_info():
    """This process's view of the deployment, for reports and logs:
    process id/count, local vs global device counts, and whether the
    backend actually spans processes.  Touches jax (initializes the
    backend if needed) — call it for reporting, not gating; gates use
    ``settings.device_count_for_auto`` which never forces an init."""
    import jax

    return {
        "process_id": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
        "multiprocess": jax.process_count() > 1,
    }
