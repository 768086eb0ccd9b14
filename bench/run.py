#!/usr/bin/env python3
"""Run one benchmark cell on the TPU this machine holds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names its configuration,
traffic mix and chip count.  The run loads, warms up, measures for
``--seconds``, checks what it served against the plain reference, and
prints one JSON object as the last line of standard output: ``correct``,
``attempted``, ``failed``, ``metrics`` (the end-to-end metrics with
``--trace 0``, the per-layer ones with ``--trace 1``), ``device`` and, last,
``check`` (each compared number beside its limit, also the last lines of
standard error).  It exits non-zero and prints no result where JAX finds
no TPU, fewer chips than the cell asks for, or no program to serve.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAIL = 2


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import spec
    try:
        cell = spec.load_cell(args.workload, ROOT)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return FAIL
    try:
        import repro.launch.serve  # noqa: F401  the system under test
    except ImportError as e:
        print(f"bench: no program to serve ({e})", file=sys.stderr)
        return FAIL
    from bench.harness import NoChip, run_cell
    log = lambda msg: print(msg, flush=True)
    try:
        res = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       root=ROOT, t_process=T_PROCESS, log=log)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return FAIL
    for name, c in res.get("check", {}).items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(result_line(res), flush=True)
    return 0


def result_line(res: dict) -> str:
    """The last line of standard output: the contract's keys, ``check``
    last."""
    out = {k: v for k, v in res.items()
           if not k.startswith("_") and k != "check"}
    if "check" in res:
        out["check"] = res["check"]
    return json.dumps(out)


if __name__ == "__main__":
    sys.exit(main())
