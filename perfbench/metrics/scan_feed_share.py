"""The wall-clock union in which the lowered scan's host built and
launched a batch (token bounds, line ids, the padded matrix, the program
call): ``stats()["devtime"]["scan_feed"]``, measured at the dispatch
sites of ``ops/lower.py`` and ``ops/handoff.py``.  Summed over the
window's jobs, over their summed wall time, in %; None where the program
has no such bucket."""

KEY = "scan_feed"


def read(run):
    if not run.jobs or any(KEY not in j.stats["devtime"] for j in run.jobs):
        return None
    busy = sum(j.stats["devtime"][KEY] for j in run.jobs)
    return 100.0 * busy / sum(j.wall for j in run.jobs)
