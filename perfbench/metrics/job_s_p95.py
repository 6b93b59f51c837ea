"""The 95th percentile of the window's job wall times (host clock): from
``run()`` until the result is materialised, the check outside the timer.
Linear between order statistics, as numpy's default percentile."""


def read(run):
    walls = sorted(j.wall for j in run.jobs)
    if not walls:
        return None
    pos = 0.95 * (len(walls) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(walls) - 1)
    return walls[lo] + (walls[hi] - walls[lo]) * (pos - lo)
