"""Build a scratch checkout that holds this benchmark plus one sort cell
over gensort's records, added by new files and new entries alone, as a
later PR would add it.

    python3 perfbench/tests/scratch_sort.py <dest> [--tier-bytes N]
        [--shard-bytes M]

``<dest>`` gets ``BENCHMARK.json`` (this one's, with the configuration
``gensort-scratch`` and the cell ``sort.scratch`` appended), a copy of
``perfbench/`` with the cell's files, and a link to ``dampr_tpu``.  The
cell runs ``sort_pipeline.py`` on 1x of ``N`` bytes in shards of ``M``:

    python3 <dest>/perfbench/run.py --workload sort.scratch ...
"""

import argparse
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CONFIG = "gensort-scratch"
CELL = "sort.scratch"


def _dump(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f, indent=2)


def build(dest, tier_bytes, shard_bytes):
    bench = os.path.join(dest, "perfbench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "*.pyc"))
    os.symlink(os.path.relpath(os.path.join(ROOT, "dampr_tpu"), dest),
               os.path.join(dest, "dampr_tpu"))
    shutil.copy(os.path.join(BENCH, "tests", "sort_pipeline.py"),
                os.path.join(bench, "pipelines", "sort.py"))
    _dump(os.path.join(bench, "configs", CONFIG + ".json"), {
        "name": CONFIG,
        "source": "https://www.ordinal.com/gensort.html",
        "deployment": "an external sort of gensort -a records on one host",
        "data": "gensort",
        "tier_bytes": tier_bytes,
        "tiers": [1],
        "corpus": {},
        "guarantee": "valsort: every input record once, keys in order"})
    _dump(os.path.join(bench, "traffic", "scratch.json"), {
        "tier": 1, "shard_bytes": shard_bytes,
        "why": "one user sorts one file of records, job after job"})
    _dump(os.path.join(bench, "workloads", CELL + ".json"), {
        "pipeline": "sort",
        "limits": {"unordered": 0, "wrong_records": 0},
        "path": {"min": {}, "max": {}}})
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({
        "name": CONFIG, "source": "https://www.ordinal.com/gensort.html",
        "file": "perfbench/configs/{}.json".format(CONFIG), "reduced": [],
        "why": "scratch: gensort records through sort_by"})
    spec["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": "scratch", "chips": 1,
        "why": "scratch: one file of gensort records sorted a job"})
    _dump(os.path.join(dest, "BENCHMARK.json"), spec)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("dest")
    ap.add_argument("--tier-bytes", type=int, default=2 * 10 ** 6)
    ap.add_argument("--shard-bytes", type=int, default=10 ** 6)
    args = ap.parse_args(argv)
    build(args.dest, args.tier_bytes, args.shard_bytes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
