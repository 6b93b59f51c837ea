#!/usr/bin/env python3
"""The control of a cell's comparison: the plain reference put in the
program's place, computed one precision below what the configuration
states (TF-IDF's idf in float32 for float64; counts in int16 for the
device's int32 lanes), and compared with the reference as a run compares
the program's result.  It has to come out not correct.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 \
        [--tier-bytes BYTES]

Prints, for each seed, each compared number beside its limit.  It needs
no chip: the benchmark's own runs never run it."""

import argparse
import json
import os
import shutil
import sys
import tempfile

from corpus import load
from run import WORK_ROOT, Cell


def readings(cell, seed, tier_bytes=None):
    """The control's compared numbers on ``seed``'s corpus."""
    form = load("pipelines", cell.spec["pipeline"])
    os.makedirs(WORK_ROOT, exist_ok=True)
    work = tempfile.mkdtemp(prefix="control-", dir=WORK_ROOT)
    try:
        pool = cell.start_corpus(seed, work, tier_bytes)
        try:
            pool.wait_corpus()
            parts = pool.reference_parts(cell.spec["pipeline"])
        finally:
            pool.close()
        want = form.reference(parts)
        got = form.render(form.reference(parts, control=True),
                          os.path.join(work, "control"))
        return form.compare(got, want)
    finally:
        shutil.rmtree(work, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--tier-bytes", type=int, default=0)
    args = ap.parse_args(argv)
    cell = Cell(args.workload)
    limits = cell.spec["limits"]
    caught = True
    for seed in (int(s) for s in args.seeds.split(",")):
        got = readings(cell, seed, args.tier_bytes)
        fails = any(got[k] > limits[k] for k in limits)
        caught &= fails
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control_fails": fails, "checks": {
                              k: {"value": got[k], "limit": limits[k]}
                              for k in limits}}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
