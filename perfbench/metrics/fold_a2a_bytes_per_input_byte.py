"""Bytes the collective fold programs' all_to_all sent from one chip to
another (``stats()["device"]["fold_a2a_bytes"]``, computed per program
call from its chips, capacity and lane widths) per input byte, over the
window's jobs.  None where the program has no such counter."""

KEY = "fold_a2a_bytes"


def read(run):
    if not run.jobs or any(KEY not in j.stats["device"] for j in run.jobs):
        return None
    moved = sum(j.stats["device"][KEY] for j in run.jobs)
    return moved / float(sum(j.input_bytes for j in run.jobs))
