"""Admission-throughput benchmark: batched/chunked scheduler vs the old
per-request blocking path.

Two ways to push the same request stream through the engine:

* ``per_request`` — the pre-PR admission: ``add_request`` per request,
  i.e. one blocking full-prompt prefill dispatch per request (bucket of
  batch 1), decode steps in between;
* ``batched`` — ``submit`` everything, let ``step()`` admit under the
  prefill token budget: same-length prompts share one padded-bucket
  prefill dispatch, long prompts chunk across steps, finished sequences
  auto-release so slots recycle under sustained load.

Both paths run on the SAME engine implementation and produce identical
tokens (tests/test_admission.py pins that); the benchmark isolates the
admission machinery.  Each engine is warmed with a full pass first so the
measured pass reuses compiled executables (the pow2 bucket shapes are
bounded by design).

Emits a JSON record (default: BENCH_admission.json at the repo root).

Run:  PYTHONPATH=src python benchmarks/bench_admission.py
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_dims, init_params
from repro.serve import Engine, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _requests(cfg, rng, n, blocks, sid0):
    bs = cfg.kv_block_size
    return [Request(seq_id=sid0 + i,
                    prompt=rng.randint(0, cfg.vocab_size,
                                       blocks[i % len(blocks)] * bs),
                    max_new_tokens=1)
            for i in range(n)]


def _drain(eng):
    steps = 0
    while eng.waiting or any(not r.done for r in eng.requests.values()):
        eng.step()
        steps += 1
        assert steps < 10_000
    return steps


def run_one(cfg, params, path: str, n_req: int, blocks, max_batch: int,
            budget) -> dict:
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, max_batch=max_batch,
                 max_seq_len=(max(blocks) + 2) * bs,
                 prefill_budget=budget, auto_release=True)
    rng = np.random.RandomState(0)

    def one_pass(sid0):
        reqs = _requests(cfg, rng, n_req, blocks, sid0)
        t0 = time.perf_counter()
        if path == "per_request":
            for r in reqs:
                eng.add_request(r)
                _drain(eng)          # blocking semantics: finish, recycle
            steps = 0
        else:
            for r in reqs:
                eng.submit(r)
            steps = _drain(eng)
        dt = time.perf_counter() - t0
        assert len(eng.finished) == n_req + sid0
        assert all(r.done for r in reqs)
        return dt, steps

    t0 = time.perf_counter()
    one_pass(0)                      # warmup: compile every bucket shape
    compile_s = time.perf_counter() - t0
    dt, steps = one_pass(n_req)
    tokens = int(sum(len(r.prompt) for r in
                     _requests(cfg, np.random.RandomState(0), n_req,
                               blocks, 0)))
    return {
        "path": path,
        "requests": n_req,
        "prompt_blocks": list(blocks),
        "max_batch": max_batch,
        "prefill_budget": eng.prefill_budget,
        "engine_steps": steps,
        "wall_s": round(dt, 4),
        # warmup-pass wall (XLA compiles + first-shape scatters), kept
        # OUT of the measured pass so wall_s trajectories compare
        # PR-over-PR (ISSUE 5 reporting fix)
        "compile_wall_s": round(compile_s, 4),
        "admitted_tokens_per_s": round(tokens / dt, 1),
        "requests_per_s": round(n_req / dt, 2),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_admission.json"))
    args = ap.parse_args()

    cfg = reduced(ARCHS[args.arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    bs = cfg.kv_block_size

    scenarios = {
        # same-length prompts: pure bucket-batching win
        "uniform_2blk": dict(blocks=(2,), budget=None),
        # mixed lengths, ample budget: batching across length buckets
        "mixed_2_4_8blk": dict(blocks=(2, 4, 8), budget=None),
        # tight budget: long prompts CHUNK across steps — buys decode
        # interleaving at the cost of prefix recompute, so this row is
        # expected to trade some admission throughput away
        "mixed_chunked_b4": dict(blocks=(2, 4, 8), budget=4 * bs),
    }
    results = []
    speedups = {}
    for name, sc in scenarios.items():
        per = {}
        for path in ("per_request", "batched"):
            r = run_one(cfg, params, path, args.requests, sc["blocks"],
                        args.max_batch, sc["budget"])
            r["scenario"] = name
            results.append(r)
            per[path] = r
            print(f"{name:16s} {path:12s}: {r['wall_s']:7.3f}s  "
                  f"{r['admitted_tokens_per_s']:9.1f} prompt tok/s  "
                  f"{r['requests_per_s']:6.2f} req/s")
        speedups[name] = round(per["per_request"]["wall_s"]
                               / per["batched"]["wall_s"], 2)

    record = {
        "benchmark": "admission",
        "arch": f"{args.arch} (reduced)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "results": results,
        "speedup_batched_vs_per_request": speedups,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"\nspeedup batched vs per-request: {speedups}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
