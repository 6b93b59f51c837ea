"""Seconds of the runner's map stages (``stats()["stages"]``, kind
``map``) over job wall time, summed over the window's jobs, in %."""


def read(run):
    if not run.jobs:
        return None
    maps = sum(s["seconds"] for j in run.jobs for s in j.stats["stages"]
               if s["kind"].startswith("map"))
    return 100.0 * maps / sum(j.wall for j in run.jobs)
