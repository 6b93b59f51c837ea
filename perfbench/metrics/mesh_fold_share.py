"""The wall-clock union in which the collective fold worked on the
device: each chip's assembly of the fold's input, the keyed-fold program
calls with their overflow fetch, the per-chip compaction and the final
fold's fetch (``stats()["devtime"]["mesh_fold"]``, measured in
``parallel/shuffle.py`` and ``runner._mesh_reduce``).  Summed over the
window's jobs, over their summed wall time, in %; None where the program
has no such bucket."""

KEY = "mesh_fold"


def read(run):
    if not run.jobs or any(KEY not in j.stats["devtime"] for j in run.jobs):
        return None
    busy = sum(j.stats["devtime"][KEY] for j in run.jobs)
    return 100.0 * busy / sum(j.wall for j in run.jobs)
