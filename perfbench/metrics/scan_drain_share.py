"""The wall-clock union in which the lowered scan's host fetched a
program's outputs and worked on them (key decode, absorb, host regroup,
the handoff finalize's fetch): ``stats()["devtime"]["scan_drain"]``,
measured at the drain sites of ``ops/lower.py`` and ``ops/handoff.py``.
Summed over the window's jobs, over their summed wall time, in %; None
where the program has no such bucket."""

KEY = "scan_drain"


def read(run):
    if not run.jobs or any(KEY not in j.stats["devtime"] for j in run.jobs):
        return None
    busy = sum(j.stats["devtime"][KEY] for j in run.jobs)
    return 100.0 * busy / sum(j.wall for j in run.jobs)
