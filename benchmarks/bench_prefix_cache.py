"""Prefix-cache benchmark: shared-system-prompt fan-out vs cache-off.

The headline workload is the ISSUE-8 acceptance shape: ``n_req``
requests that all share one ``shared_blocks``-block system prompt and
differ only in a 1-block unique tail.  Cache-off, every request
prefills its whole prompt; cache-on, request 0 publishes the shared
blocks and everyone admitted after it attaches them read-only and
forwards ONLY its tail.  Both runs complete the identical workload with
bit-identical token streams (asserted here; the differential suite in
tests/test_prefix_cache.py pins it across every engine mode), so the
comparison is pure mechanism cost/benefit:

* ``prefill_fwd_tokens`` — prompt tokens actually fed through prefill
  dispatches (summed from the admission log), the compute the cache
  skips;
* ``ttft_ms`` — time to first token per request (mean/p50/p99): fewer
  forwarded tokens admit later requests sooner;
* ``peak_pool_occupancy`` — peak distinct mapped pool slots: dedup'd
  blocks occupy ONE slot however many requests read them (note the
  cache also KEEPS published blocks resident after their sequences
  release, so under a slow-admission workload where cache-off frees
  early finishers before late arrivals allocate, cache-on peak can be
  higher — resident reuse capacity, not a leak);
* the HONEST cold-miss cost: the same fan-out with all-distinct prompts
  (every lookup misses, every insert pays hash+pin) — wall-clock ratio
  cache-on / cache-off shows what the machinery costs when it never
  helps.

``--smoke`` runs a tiny configuration for CI (keeps the script from
bit-rotting; timings are not meaningful there).

Run:  PYTHONPATH=src python benchmarks/bench_prefix_cache.py
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


def make_prompts(cfg, n_req: int, shared_blocks: int,
                 shared: bool) -> list:
    bs = cfg.kv_block_size
    rng = np.random.RandomState(11)
    sys_prompt = rng.randint(0, cfg.vocab_size, shared_blocks * bs)
    out = []
    for _ in range(n_req):
        head = (sys_prompt if shared
                else rng.randint(0, cfg.vocab_size, shared_blocks * bs))
        out.append(np.concatenate(
            [head, rng.randint(0, cfg.vocab_size, bs)]))
    return out


def run_one(cfg, params, prompts, cache: bool, max_new: int,
            warm: bool) -> dict:
    bs = cfg.kv_block_size
    n_req = len(prompts)
    nblk = len(prompts[0]) // bs
    eng = Engine(cfg, params, EngineConfig(
        max_batch=n_req, max_seq_len=(nblk + 3) * bs,
        # a bounded per-round admission budget, the real serving
        # constraint the cache relieves: cache-off must spend it
        # re-forwarding the shared prompt for every request (delaying
        # every later admission), cache-on spends it only on unique
        # tails.  Request 0 still publishes before anyone else admits —
        # followers cannot register while it consumes the budget.
        prefill_budget=2 * bs,
        auto_release=True, prefix_cache="auto" if cache else False))
    if warm:
        # compile every shape the timed wave will hit — the cache-on
        # run admits many 1-block tails per round, so the pow2-padded
        # multi-row prefix buckets (B_pad 8/16) must be compiled too,
        # not just the single-row shapes.  A warm fan-out with DISTINCT
        # content but the workload's exact shape, budget and max_batch
        # reproduces the same admission dynamics (and the same bucket
        # keys) without polluting the workload's content.  It runs
        # TWICE: the mass release at the end of a wave dirties a large
        # batch of translation entries whose delta-scatter pad size is
        # only dispatched (and jitted) once the NEXT wave starts, so
        # only a second wave — running in exactly the post-release
        # state the timed wave will see — compiles those shapes.  The
        # stats snapshot below excludes all of it.
        for wave, seed in enumerate((99, 101)):
            wrng = np.random.RandomState(seed)
            whead = wrng.randint(0, cfg.vocab_size, (nblk - 1) * bs)
            for k in range(n_req):
                eng.submit(Request(
                    seq_id=(wave + 1) * n_req + 1 + k,
                    prompt=np.concatenate(
                        [whead, wrng.randint(0, cfg.vocab_size, bs)]),
                    max_new_tokens=2))
            while eng.has_unfinished():
                eng.poll()
        base_log = len(eng.admission_log)
    else:
        base_log = 0
    pcs0 = eng.stats()["prefix_cache"]   # exclude warm-up from the stats
    for i, p in enumerate(prompts):
        eng.submit(Request(seq_id=i, prompt=p, max_new_tokens=max_new))
    t0 = time.perf_counter()
    ttft, streams, peak_occ, steps = {}, {}, 0, 0
    while eng.has_unfinished():
        for ro in eng.poll():
            if ro.new_token_ids and ro.seq_id < n_req:
                ttft.setdefault(ro.seq_id, time.perf_counter() - t0)
            if ro.seq_id < n_req:
                streams[ro.seq_id] = list(ro.token_ids)
        peak_occ = max(peak_occ, len({
            i.slot for i in eng.manager.blocks.values() if i.slot >= 0}))
        steps += 1
        assert steps < 200 * n_req, "engine failed to drain"
    wall = time.perf_counter() - t0
    fwd = sum(c.fwd_tokens for c in eng.admission_log[base_log:])
    lat = np.asarray(sorted(ttft.values())) * 1e3
    pcs = eng.stats()["prefix_cache"]
    eng.check_invariants()
    return {
        "cache": cache,
        "n_req": n_req,
        "prompt_blocks": nblk,
        "steps": steps,
        "wall_s": round(wall, 3),
        "prefill_fwd_tokens": int(fwd),
        "ttft_ms_mean": round(float(lat.mean()), 1),
        "ttft_ms_p50": round(float(np.percentile(lat, 50)), 1),
        "ttft_ms_p99": round(float(np.percentile(lat, 99)), 1),
        "peak_pool_occupancy": int(peak_occ),
        "cache_hits": pcs["hits"] - pcs0["hits"],
        "dedup_blocks": pcs["dedup_blocks"] - pcs0["dedup_blocks"],
        "bytes_saved": pcs["bytes_saved"] - pcs0["bytes_saved"],
        "streams": streams,
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--shared-blocks", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_prefix_cache.json"))
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.shared_blocks, args.max_new = 6, 4, 4

    cfg = dataclasses.replace(reduced(ARCHS[args.arch]), num_layers=2)
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results = {}
    shared = make_prompts(cfg, args.requests, args.shared_blocks, True)
    for cache in (False, True):
        r = run_one(cfg, params, shared, cache, args.max_new, warm=True)
        results["shared_on" if cache else "shared_off"] = r
        print(f"shared  cache={'on ' if cache else 'off'}: "
              f"fwd_tokens={r['prefill_fwd_tokens']:5d}  "
              f"ttft mean {r['ttft_ms_mean']:7.1f} ms  "
              f"p99 {r['ttft_ms_p99']:7.1f} ms  "
              f"peak_occ={r['peak_pool_occupancy']:3d}  "
              f"dedup={r['dedup_blocks']}")
    # the differential contract, re-checked where the numbers are made
    assert results["shared_on"]["streams"] \
        == results["shared_off"]["streams"], \
        "cache-on streams diverged from cache-off"
    # honest cold-miss: all-distinct prompts — every lookup misses,
    # every insert still pays hashing + pinning
    distinct = make_prompts(cfg, args.requests, args.shared_blocks, False)
    for cache in (False, True):
        r = run_one(cfg, params, distinct, cache, args.max_new,
                    warm=False)
        results["distinct_on" if cache else "distinct_off"] = r
        print(f"distinct cache={'on ' if cache else 'off'}: "
              f"fwd_tokens={r['prefill_fwd_tokens']:5d}  "
              f"wall {r['wall_s']:6.3f} s  hits={r['cache_hits']}")
    assert results["distinct_on"]["streams"] \
        == results["distinct_off"]["streams"], \
        "cache-on streams diverged from cache-off (distinct prompts)"
    for r in results.values():
        del r["streams"]

    on, off = results["shared_on"], results["shared_off"]
    don, doff = results["distinct_on"], results["distinct_off"]
    record = {
        "benchmark": "prefix_cache",
        "arch": f"{args.arch} (reduced, 2 layers)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "n_requests": args.requests,
        "shared_blocks": args.shared_blocks,
        "max_new_tokens": args.max_new,
        "results": results,
        "prefill_fwd_token_ratio_off_over_on": round(
            off["prefill_fwd_tokens"] / max(on["prefill_fwd_tokens"], 1),
            3),
        "ttft_mean_ratio_on_over_off": round(
            on["ttft_ms_mean"] / max(off["ttft_ms_mean"], 1e-9), 3),
        "peak_occupancy_ratio_on_over_off": round(
            on["peak_pool_occupancy"]
            / max(off["peak_pool_occupancy"], 1), 3),
        "cold_miss_wall_ratio_on_over_off": round(
            don["wall_s"] / max(doff["wall_s"], 1e-9), 3),
        "dedup_blocks": on["dedup_blocks"],
        "bytes_saved": on["bytes_saved"],
    }
    print(f"fwd-token reduction {record['prefill_fwd_token_ratio_off_over_on']}x, "
          f"ttft mean ratio {record['ttft_mean_ratio_on_over_off']}, "
          f"cold-miss wall ratio "
          f"{record['cold_miss_wall_ratio_on_over_off']}")
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
