"""Sampled vs greedy decode-step latency (ISSUE 3 satellite).

In-graph sampling (temperature / top-k / top-p with per-slot PRNG keys,
serve/sampling.py) rides inside the same jitted serve_step as greedy
argmax: the sampling math is O(B·V) element-wise work plus one sort,
dwarfed by the layer stack, so a sampled step must cost the same as a
greedy step to within noise.  This benchmark measures both (plus a
mixed greedy/sampled batch — the branch-free design means ONE trace
serves all three) and records the ratio so a regression that puts
sampling on the hot path (extra dispatch, host round-trip, per-request
python) is caught.

Emits a JSON record (default: BENCH_sampling.json at the repo root).
``--smoke`` runs a tiny configuration for CI (scripts must stay
runnable; the ratio is not asserted there — CI machines are noisy).

Run:  PYTHONPATH=src python benchmarks/bench_sampling.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import summarize_times  # noqa: E402

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_dims, init_params
from repro.serve import Engine, EngineConfig, Request, SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANTS = {
    "greedy": lambda sid: SamplingParams(),
    "sampled": lambda sid: SamplingParams(temperature=0.8, top_k=40,
                                          top_p=0.95, seed=sid),
    "mixed": lambda sid: (SamplingParams() if sid % 2 == 0 else
                          SamplingParams(temperature=0.8, top_k=40,
                                         seed=sid)),
}


def _build(cfg, params, variant: str, max_batch: int, horizon: int):
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=max_batch, max_seq_len=2 * bs + horizon + bs))
    rng = np.random.RandomState(0)
    for sid in range(max_batch):
        eng.add_request(Request(
            seq_id=sid, prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
            max_new_tokens=horizon + 2, sampling=VARIANTS[variant](sid)))
    return eng


def run_batch(cfg, params, max_batch: int, warmup: int,
              steps: int) -> list:
    """Measure every variant at one batch size with INTERLEAVED timed
    steps (greedy, sampled, mixed, greedy, ...): slow machine-load drift
    then hits all variants equally instead of whichever ran last."""
    horizon = warmup + steps + 2
    engines = {v: _build(cfg, params, v, max_batch, horizon)
               for v in VARIANTS}
    compile_s = {}
    for v, eng in engines.items():
        t0 = time.perf_counter()
        for _ in range(warmup):
            eng.step()
        compile_s[v] = time.perf_counter() - t0
    times = {v: [] for v in VARIANTS}
    for _ in range(steps):
        for v, eng in engines.items():
            t0 = time.perf_counter()
            out = eng.step()
            times[v].append(time.perf_counter() - t0)
            assert len(out) == max_batch
    results = []
    for v in VARIANTS:
        r = {"variant": v, "max_batch": max_batch, "steps": steps}
        r.update(summarize_times(times[v], compile_s=compile_s[v]))
        r["tokens_per_step_s"] = round(max_batch / (r["step_ms"] / 1e3), 1)
        results.append(r)
    return results


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batches", default="2,4")
    ap.add_argument("--steps", type=int, default=32)
    ap.add_argument("--warmup", type=int, default=6)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_sampling.json"))
    args = ap.parse_args()
    if args.smoke:
        args.batches, args.steps, args.warmup = "2", 4, 2

    cfg = reduced(ARCHS[args.arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results = []
    ratios = {}
    for mb in (int(b) for b in args.batches.split(",")):
        batch_results = run_batch(cfg, params, mb, args.warmup,
                                  args.steps)
        results.extend(batch_results)
        for r in batch_results:
            print(f"{r['variant']:8s} B={mb}: {r['step_ms']:8.2f} ms/step"
                  f"  {r['tokens_per_step_s']:8.1f} tok/s")
        by = {r["variant"]: r for r in batch_results}
        ratios[f"b{mb}"] = round(by["sampled"]["step_ms"]
                                 / by["greedy"]["step_ms"], 3)

    record = {
        "benchmark": "sampling",
        "arch": f"{args.arch} (reduced)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "results": results,
        "sampled_over_greedy_step_ratio": ratios,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"\nsampled/greedy step ratio: {ratios} (must stay ~1.0)")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
