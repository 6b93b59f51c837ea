"""Host slot inserts into the handoff stage vocabularies
(``stats()["device"]["handoff_slot_inserts"]``) over the window's input
MB (1e6 bytes), summed over the window's jobs.  Nothing where the
program has no such counter."""


def read(run):
    devs = [j.stats["device"] for j in run.jobs]
    if not devs or any("handoff_slot_inserts" not in d for d in devs):
        return None
    mb = sum(j.input_bytes for j in run.jobs) / 1e6
    if not mb:
        return None
    return sum(d["handoff_slot_inserts"] for d in devs) / mb
