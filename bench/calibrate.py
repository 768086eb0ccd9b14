#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from.

    python bench/calibrate.py --workload <cell> --seeds 11,12,... \\
        --seconds <s> [--controls fp8]

In one process, for each seed: one run of the cell as ``run.py`` makes it
(its own load, warm-up and window at the cell's load), then the widest gap
of the served tokens under the float32 reference (the program's reading)
and, for each control precision, the widest gap of the tokens that the
reference computed in that precision ranks first at the same positions
(the control's reading).  Prints one JSON line per seed and a summary:
the lower reading is the largest program gap, the upper the smallest
control gap.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="fp8")
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, p)
    from bench import spec
    from bench.harness import run_cell
    cell = spec.load_cell(args.workload, ROOT)
    controls = tuple(c for c in args.controls.split(",") if c)
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        res = run_cell(cell, seed, args.seconds, False, root=ROOT,
                       controls=controls, log=lambda m: print(m, flush=True))
        row = {"seed": seed, "correct": res["correct"], **res["_gaps"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"],
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print("calibrate " + json.dumps(row), flush=True)
    served = [r["served"] for r in rows if r["served"] is not None]
    summary = {"lower": max(served) if served else None}
    for c in controls:
        vals = [r[c] for r in rows if r.get(c) is not None]
        summary["upper_" + c] = min(vals) if vals else None
    print("calibrate summary " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
