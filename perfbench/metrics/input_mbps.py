"""Input bytes of every job completed in the window over the time from the
first job's start to the last job's end (host clock), in MB/s."""


def read(run):
    if not run.jobs:
        return None
    span = run.jobs[-1].end - run.jobs[0].start
    return sum(j.input_bytes for j in run.jobs) / span / 1e6
