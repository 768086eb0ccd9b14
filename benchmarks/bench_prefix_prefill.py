"""Prefix-KV chunked prefill benchmark: linear vs quadratic chunk cost.

Admits one long prompt under a tight per-step prefill budget on both
paths and records, PER CHUNK, the forward-token count (from
``Engine.admission_log`` — the ground truth the tests also pin) and the
wall time of the engine step that ran the chunk:

* ``prefix_kv`` — chunks k > 0 forward only their own tokens and read
  the installed prefix from the pool: fwd_tokens is CONSTANT in chunk
  index;
* ``recompute`` — the PR-2 oracle path re-forwards the whole prefix
  every chunk: fwd_tokens grows linearly per chunk (quadratic total).

Each engine is warmed with a full admission pass first so the measured
pass reuses compiled executables (the pow2 bucket shapes are bounded by
design).

Emits a JSON record (default: BENCH_prefix_prefill.json at the repo
root).

Run:  PYTHONPATH=src python benchmarks/bench_prefix_prefill.py
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
from repro.serve import Engine, EngineConfig, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def admit_one(cfg, params, mode: str, prompt_blocks: int,
              budget_blocks: int) -> dict:
    bs = cfg.kv_block_size
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, cfg.vocab_size, prompt_blocks * bs)
    # ONE engine for warmup and measurement: the jitted step caches live
    # on the Engine's closures, so a fresh engine would re-compile every
    # bucket shape and the "measured" pass would time XLA, not admission
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=(prompt_blocks + 2) * bs,
        prefill_budget=budget_blocks * bs, prefill_mode=mode,
        auto_release=True))

    def admit(sid):
        eng.submit(Request(seq_id=sid, prompt=prompt, max_new_tokens=1))
        steps = []
        while True:
            t0 = time.perf_counter()
            eng.step()
            steps.append(time.perf_counter() - t0)
            if sid not in eng._prefilling:
                break
        while eng.has_unfinished():    # finish + auto-release the slot
            eng.step()
        return steps

    t0 = time.perf_counter()
    admit(0)                           # warmup: compile every bucket shape
    compile_s = time.perf_counter() - t0
    steps = admit(1)
    chunks = [rec for rec in eng.admission_log if rec.seq_id == 1]
    assert len(chunks) == len(steps)
    per_chunk = [{
        "chunk": i,
        "start": rec.start,
        "end": rec.end,
        "path": rec.path,
        "fwd_tokens": rec.fwd_tokens,
        "step_wall_s": round(steps[i], 5),
    } for i, rec in enumerate(chunks)]
    return {
        "mode": mode,
        "prompt_tokens": prompt_blocks * bs,
        "budget_tokens": budget_blocks * bs,
        "chunks": per_chunk,
        "total_fwd_tokens": sum(r.fwd_tokens for r in chunks),
        "admission_wall_s": round(sum(steps), 5),
        # warmup-pass wall (all XLA compiles), separated from the
        # measured admission wall (ISSUE 5 reporting fix)
        "compile_wall_s": round(compile_s, 5),
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    # 1024-token prompt, 32-token chunks: long enough that the recompute
    # path's quadratic forward dominates its dispatch overhead even on
    # the tiny reduced model (CPU); short prompts are overhead-bound and
    # understate the win
    ap.add_argument("--prompt-blocks", type=int, default=128)
    ap.add_argument("--budget-blocks", type=int, default=4)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI (8-block prompt)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_prefix_prefill.json"))
    args = ap.parse_args()
    if args.smoke:
        args.prompt_blocks, args.budget_blocks = 8, 2

    cfg = reduced(ARCHS[args.arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results = []
    for mode in ("prefix_kv", "recompute"):
        r = admit_one(cfg, params, mode, args.prompt_blocks,
                      args.budget_blocks)
        results.append(r)
        fts = [c["fwd_tokens"] for c in r["chunks"]]
        print(f"{mode:10s}: {len(fts)} chunks, fwd_tokens/chunk {fts[:6]}"
              f"{'...' if len(fts) > 6 else ''}  "
              f"total {r['total_fwd_tokens']}  "
              f"admission {r['admission_wall_s']:.3f}s")

    pre, rec = results
    # linearity: every prefix chunk forwards exactly its own tokens; only
    # the final chunk may be ragged (prompt not a budget multiple)
    for c in pre["chunks"]:
        assert c["fwd_tokens"] == c["end"] - c["start"], c
    body = {c["fwd_tokens"] for c in pre["chunks"][:-1]}
    assert len(body) <= 1, f"prefix path not linear: {body}"
    record = {
        "benchmark": "prefix_prefill",
        "arch": f"{args.arch} (reduced)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "results": results,
        "fwd_token_ratio_recompute_over_prefix": round(
            rec["total_fwd_tokens"] / pre["total_fwd_tokens"], 2),
        "admission_speedup_prefix_over_recompute": round(
            rec["admission_wall_s"] / pre["admission_wall_s"], 2),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"\nfwd-token ratio (recompute/prefix): "
          f"{record['fwd_token_ratio_recompute_over_prefix']}  "
          f"admission speedup: "
          f"{record['admission_speedup_prefix_over_recompute']}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
