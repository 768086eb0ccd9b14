#!/usr/bin/env python3
"""The knee of an open-loop cell: runs at several offered rates.

    python bench/sweep.py --workload <cell> --rates 0.5,1,1.5 \\
        --seconds <s> --seed <n>

In one process, one run per rate (no correctness check).  For each rate it
prints the requests due in the window, the output tokens per second, the
90th-percentile time to first token, and the queue of waiting requests
(the engine's ``queued`` gauge) averaged over the first and the last third
of the window: the knee is the highest rate whose queue does not grow.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        sys.path.insert(0, p)
    from bench import spec
    from bench.client import percentile
    from bench.harness import run_cell
    cell = spec.load_cell(args.workload, ROOT)
    for rate in (float(r) for r in args.rates.split(",")):
        res = run_cell(cell, args.seed, args.seconds, False, root=ROOT,
                       rate=rate, do_check=False,
                       log=lambda m: print(m, flush=True))
        run = res["_run"]
        q = [s.get("queued") or 0 for s in run["steps"]]
        third = max(1, len(q) // 3)
        e2e = run["e2e"]
        row = {"rate": rate, "due": e2e["due"], "failed": e2e["failed"],
               "output_tokens_per_s": e2e["output_tokens"] / args.seconds,
               "ttft_p90_ms": (percentile(e2e["ttft_s"], 90) * 1e3
                               if e2e["ttft_s"] else None),
               "tbt_p99_ms": (percentile(e2e["gaps_s"], 99) * 1e3
                              if e2e["gaps_s"] else None),
               "queued_first_third": sum(q[:third]) / third,
               "queued_last_third": sum(q[-third:]) / third}
        print("sweep " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
