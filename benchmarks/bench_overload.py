"""Overload benchmark: goodput and tail latency under oversubscription.

A pool sized for ~4 concurrent sequences receives ``oversub`` x that many
requests at t=0.  Two degradation policies serve the identical workload:

* ``preempt`` (ISSUE 6, default) — the engine preempts victim sequences
  to the host KV tier when a block allocation misses and resumes them
  through the scheduler queue: admission stays aggressive, capacity is
  time-shared.
* ``fail`` — the fail-fast baseline (the pre-tier ladder): admission is
  footprint-gated, a sequence only starts once its WHOLE worst-case
  footprint provably fits, so the pool is never oversubscribed and
  nothing is ever preempted.

Both complete every request (the tests pin bit-identical streams); the
benchmark measures what the tier buys and what it costs:

* ``goodput_tok_s``  — completed tokens / wall time;
* ``ttft_ms``        — time to first token, p50/p99 across requests
  (footprint gating makes LATE requests wait for whole-sequence
  reservations, stretching the tail);
* ``preemptions`` / ``swap_out_mb`` — how hard the tier worked.

``--smoke`` runs a tiny configuration for CI (keeps the script from
bit-rotting; timings are not meaningful there).

Run:  PYTHONPATH=src python benchmarks/bench_overload.py
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_dims, init_params
from repro.serve import Engine, EngineConfig, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_one(cfg, params, policy: str, n_req: int, max_batch: int,
            max_new: int, headroom: float, warm: bool) -> dict:
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=max_batch, max_seq_len=8 * bs, pool_headroom=headroom,
        auto_release=True, overload_policy=policy))
    rng = np.random.RandomState(7)
    reqs = [Request(seq_id=i,
                    prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                    max_new_tokens=max_new) for i in range(n_req)]
    if warm:
        # compile the bucket shapes outside the timed region
        eng.submit(dataclasses.replace(reqs[0], seq_id=n_req + 1,
                                       max_new_tokens=2))
        while eng.has_unfinished():
            eng.poll()
    for r in reqs:
        eng.submit(r)
    t0 = time.perf_counter()
    ttft, tokens, steps = {}, 0, 0
    while eng.has_unfinished():
        for ro in eng.poll():
            if ro.new_token_ids and ro.seq_id < n_req:
                ttft.setdefault(ro.seq_id,
                                time.perf_counter() - t0)
                tokens += len(ro.new_token_ids)
        steps += 1
        assert steps < 400 * n_req, "engine failed to drain"
    wall = time.perf_counter() - t0
    ov = eng.stats()["overload"]
    lat = np.asarray(sorted(ttft.values())) * 1e3
    return {
        "policy": policy,
        "n_req": n_req,
        "oversub": round(n_req / max_batch, 2),
        "pool_blocks": eng.hybrid_cfg.total_slots,
        "completed": sum(1 for i in range(n_req)
                         if eng._states[i].done),
        "steps": steps,
        "wall_s": round(wall, 3),
        "goodput_tok_s": round(tokens / wall, 1),
        "ttft_ms_p50": round(float(np.percentile(lat, 50)), 1),
        "ttft_ms_p99": round(float(np.percentile(lat, 99)), 1),
        "preemptions": ov["request_preempts"],
        "swap_out_mb": round(ov["swap_bytes_out"] / 2**20, 3),
        "swap_in_mb": round(ov["swap_bytes_in"] / 2**20, 3),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--oversub", default="2,4",
                    help="comma list of oversubscription factors "
                         "(requests = factor x max_batch)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--headroom", type=float, default=0.5)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_overload.json"))
    args = ap.parse_args()
    if args.smoke:
        args.oversub, args.max_new = "2", 12
    factors = [int(x) for x in args.oversub.split(",")]

    cfg = dataclasses.replace(reduced(ARCHS[args.arch]), num_layers=2)
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results, goodput_ratio, p99_ratio, rate = [], {}, {}, {}
    for f in factors:
        n_req = f * args.max_batch
        pair = {}
        for policy in ("fail", "preempt"):
            r = run_one(cfg, params, policy, n_req, args.max_batch,
                        args.max_new, args.headroom, warm=(f == factors[0]
                                                           and policy == "fail"))
            assert r["completed"] == n_req, (policy, r)
            pair[policy] = r
            results.append(r)
            print(f"x{f} {policy:7s}: {r['goodput_tok_s']:8.1f} tok/s  "
                  f"ttft p50 {r['ttft_ms_p50']:7.1f} ms  "
                  f"p99 {r['ttft_ms_p99']:7.1f} ms  "
                  f"preempts {r['preemptions']:3d}  "
                  f"swap {r['swap_out_mb']:.2f} MB")
        key = f"oversub_{f}x"
        goodput_ratio[key] = round(pair["preempt"]["goodput_tok_s"]
                                   / pair["fail"]["goodput_tok_s"], 3)
        p99_ratio[key] = round(pair["preempt"]["ttft_ms_p99"]
                               / max(pair["fail"]["ttft_ms_p99"], 1e-9), 3)
        rate[key] = round(pair["preempt"]["preemptions"] / n_req, 3)

    record = {
        "benchmark": "overload",
        "arch": f"{args.arch} (reduced, 2 layers)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "max_batch": args.max_batch,
        "pool_headroom": args.headroom,
        "max_new_tokens": args.max_new,
        "results": results,
        "goodput_ratio_preempt_over_fail": goodput_ratio,
        "ttft_p99_ratio_preempt_over_fail": p99_ratio,
        "preemptions_per_request": rate,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
