"""Tokens the handoff tier's table programs found in their bucket index,
byte-verified, over the tokens they probed (``stats()["device"]``
``table_probe_hits`` / ``table_probe_tokens``), over the window's jobs,
in %.  Nothing when no job probed, or where the program has no such
counters."""


def read(run):
    probed = sum(j.stats["device"].get("table_probe_tokens", 0)
                 for j in run.jobs)
    if not probed:
        return None
    hits = sum(j.stats["device"].get("table_probe_hits", 0) for j in run.jobs)
    return 100.0 * hits / probed
