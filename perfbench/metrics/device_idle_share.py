"""1 - (union of device-op intervals / traced window), in %, from the
profiler trace of the window's first jobs (``trace_reduce.py``)."""


def read(run):
    if run.trace is None or run.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
