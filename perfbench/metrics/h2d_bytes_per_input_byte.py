"""Bytes the host fed the device (``stats()["device"]["h2d_bytes"]``: the
lowered programs' padded token feed and the HBM tier's puts) per input
byte, over the window's jobs.  Nothing when no job fed the device."""


def read(run):
    fed = sum(j.stats["device"]["h2d_bytes"] for j in run.jobs)
    if not fed:
        return None
    return fed / float(sum(j.input_bytes for j in run.jobs))
