"""Map-job placement over the chips of the data mesh.

A lowered map stage (``ops.lower``) runs each of its jobs on one chip of
``mesh.data_mesh()`` — the same mesh the consuming collective fold runs
on — so on a D-chip host every chip scans its share of the jobs, and the
job's programs, its handoff vocabulary and the device refs it registers
all live on that chip.  The collective fold then takes each chip's refs
where they are (``shuffle.mesh_keyed_refold``).

Placement is thread-local: :func:`on_chip` makes ``jax.default_device``
the job's chip for the job thread, and :func:`carried` re-enters it on a
helper thread the job starts (the overlap executor's codec producer), so
every bare ``device_put``, ``jnp`` constructor and jitted call of the job
lands on its chip.  With one chip nothing is entered: the programs and
their compile keys are those of an unplaced run; only the chip index is
recorded, for the per-chip counters.
"""

import contextlib
import functools
import threading

_local = threading.local()


def current():
    """The mesh position of the chip this thread's work is placed on, or
    None outside any placement."""
    place = getattr(_local, "place", None)
    return None if place is None else place[0]


@contextlib.contextmanager
def on_chip(index, device=None):
    """Place this thread's work on mesh position ``index``; ``device``
    (None: leave JAX's default device alone) becomes the thread's
    default device meanwhile."""
    prev = getattr(_local, "place", None)
    _local.place = (index, device)
    try:
        if device is None:
            yield
        else:
            import jax

            with jax.default_device(device):
                yield
    finally:
        _local.place = prev


def carried():
    """A context-manager factory re-entering this thread's placement on
    another thread (a no-op factory outside any placement)."""
    place = getattr(_local, "place", None)
    if place is None:
        return contextlib.nullcontext
    return functools.partial(on_chip, *place)


def spread(weights, n_chips):
    """Chip index per job: heaviest job first onto the least-loaded chip
    (ties to the lower index), so equal jobs go round robin."""
    load = [0] * n_chips
    chips = [0] * len(weights)
    for i in sorted(range(len(weights)), key=lambda i: -weights[i]):
        c = min(range(n_chips), key=lambda c: (load[c], c))
        chips[i] = c
        load[c] += weights[i]
    return chips


def local_chips(mesh):
    """``(position, device)`` of every chip of ``mesh`` this process
    addresses, in mesh order."""
    import jax

    me = jax.process_index()
    return [(i, d) for i, d in enumerate(mesh.devices.flat)
            if d.process_index == me]


def placer(chunks, mesh):
    """``place(chunk)`` -> the context a map job over ``chunk`` runs in:
    the jobs spread over the mesh's local chips by their byte size
    (a chunk without a byte range weighs 1)."""
    chips = local_chips(mesh)

    def weight(c):
        start, end = getattr(c, "start", None), getattr(c, "end", None)
        if isinstance(start, int) and isinstance(end, int):
            return max(1, end - start)
        return 1

    at = spread([weight(c) for c in chunks], len(chips))
    by_job = {id(c): chips[k] for c, k in zip(chunks, at)}
    one = len(chips) == 1

    def place(chunk):
        pos, dev = by_job.get(id(chunk), chips[0])
        return on_chip(pos, None if one else dev)

    return place
