"""How evenly the lowered scan's feed spread over the chips: per job, the
least over the most of ``stats()["device"]["h2d_bytes_by_chip"]`` (the
lowered programs' host-to-device bytes on each chip), averaged over the
window's jobs, in %.  None where the program has no such list, or runs
on one chip."""

KEY = "h2d_bytes_by_chip"


def read(run):
    shares = []
    for j in run.jobs:
        by_chip = j.stats["device"].get(KEY)
        if not by_chip or len(by_chip) < 2:
            return None
        if max(by_chip):
            shares.append(min(by_chip) / float(max(by_chip)))
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
