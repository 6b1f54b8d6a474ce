#!/usr/bin/env python3
"""The control of a cell's check: the plain reference computed one
precision down (bfloat16 for the configuration's float32), put in the
program's place.  The check that decides ``correct`` must refuse it.

    python bench/control.py --workload lulesh.gather.pallas --seeds 1 2 3

For each seed it makes one cycle of the cell's requests at the cell's
own sizes, answers every pattern with the bfloat16 reference's digest,
and runs the benchmark's own check on those answers; it also runs the
check on the float32 reference's answers, which must pass.  One JSON
line per seed: the numbers compared for both.  The benchmark's runs
never call this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import reference, run  # noqa: E402


def readings(cell: run.Cell, seed: int) -> dict:
    seed = seed % run.SEED_RANGE
    rw = cell.row_width
    runs = cell.options.get("runs", 10)
    t0 = time.perf_counter()
    want, control = {}, []
    for p in cell.cycle():
        want[p["name"]] = reference.digest(p, seed, rw)
        control.append(run.Request(p, runs, 0.0, digest=reference.digest(
            p, seed, rw, "bfloat16")))
    sound = [run.Request(r.pattern, runs, 0.0, digest=want[r.pattern["name"]])
             for r in control]
    return {"workload": cell.name, "seed": seed,
            "requests": len(control),
            "sound": run.check(sound, seed, rw, want),
            "control": run.check(control, seed, rw, want),
            "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    refused = True
    for seed in args.seeds:
        doc = readings(cell, seed)
        refused &= run.passes(doc["sound"]) and not run.passes(doc["control"])
        print(json.dumps(doc), flush=True)
    return 0 if refused else 1


if __name__ == "__main__":
    sys.exit(main())
