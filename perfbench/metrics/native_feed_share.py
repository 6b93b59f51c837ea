"""The share of the lowered scan's padded batches the native feed built
(``stats()["device"]["scan_native_batches"]`` over
``["scan_pad_batches"]``), in %, summed over the window's jobs.  Nothing
where the program has no such counters or built no batch."""


def read(run):
    devs = [j.stats["device"] for j in run.jobs]
    if not devs or any("scan_pad_batches" not in d for d in devs):
        return None
    built = sum(d["scan_pad_batches"] for d in devs)
    if not built:
        return None
    return 100.0 * sum(d["scan_native_batches"] for d in devs) / built
