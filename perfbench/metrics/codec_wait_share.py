"""The wall-clock union in which every live map slot waited on its native
codec (``stats()["devtime"]["codec_wait"]``), summed over the window's
jobs, over their summed wall time, in %."""


def read(run):
    if not run.jobs:
        return None
    wait = sum(j.stats["devtime"]["codec_wait"] for j in run.jobs)
    return 100.0 * wait / sum(j.wall for j in run.jobs)
