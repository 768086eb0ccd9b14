"""Speculative-decode benchmark: tokens/s and acceptance vs spec-off.

Three decode workloads, each run by a spec-off engine and a spec-on
engine (same params, same prompts, token-identical streams — the tests
pin that; this script measures the speed side):

* ``repetitive``   — a repetitive-continuation workload: a small-vocab
  reduced config whose greedy continuation genuinely collapses into a
  short loop (with 32 logical tokens the argmax map reaches a fixed
  point within a few dozen tokens — measured, not assumed), decoding a
  repeated-pattern prompt.  The prompt-lookup drafter catches the loop,
  acceptance approaches 1, and one dispatch commits up to K+1 tokens.
  The headline: steady-state tokens/s must clearly beat spec-off
  (ISSUE 5 acceptance: >= 1.5x).
* ``random``       — random prompts on the standard reduced config,
  greedy: whatever acceptance the model's natural quasi-loops produce.
* ``all_rejected`` — random prompts sampled at temperature 2.0: the
  target draw almost never equals the point-mass draft, so nearly every
  window commits exactly 1 token.  This is the WORST case — the
  K+1-wide verify forward buys nothing — and pins the overhead: the
  spec-on step latency vs spec-off (K=1 keeps it near 1x even on CPU,
  where — unlike a memory-bound accelerator decode — the K+1x attention
  arithmetic of a wide window is not free).

Each workload runs at every K in ``--num-draft-tokens`` (comma list):
K is the operator's knob, small for rejection-heavy traffic, wide for
input-grounded traffic.  Timings use the warmup-excluded steady-state
summary (benchmarks/common ``summarize_times``) so
BENCH_spec_decode.json trajectories are comparable PR-over-PR.
``--smoke`` runs a tiny configuration for CI (keeps the script from
bit-rotting; ratios are printed, not asserted — CI machines are noisy).

Run:  PYTHONPATH=src python benchmarks/bench_spec_decode.py
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
from common import summarize_times  # noqa: E402

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_dims, init_params
from repro.serve import Engine, EngineConfig, Request, SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg_for(workload: str, arch: str):
    cfg = reduced(ARCHS[arch])
    if workload == "repetitive":
        # 32 logical tokens: the greedy next-token map collapses to a
        # short cycle the drafter can ride (the honest stand-in for an
        # input-grounded production workload, where the model re-emits
        # spans of its context)
        cfg = dataclasses.replace(cfg, vocab_size=32)
    return cfg


def _prompts(cfg, workload: str, n: int, blocks: int):
    bs = cfg.kv_block_size
    rng = np.random.RandomState(0)
    if workload == "repetitive":
        pat = np.asarray([3, 9, 4, 1], np.int64) % cfg.vocab_size
        p = np.tile(pat, blocks * bs // pat.size)[:blocks * bs]
        return [p.copy() for _ in range(n)]
    return [rng.randint(0, cfg.vocab_size, blocks * bs) for _ in range(n)]


def _sampling(workload: str, sid: int) -> SamplingParams:
    if workload == "all_rejected":
        # high temperature: the seeded target draw ~ uniform-ish over the
        # vocab, so a point-mass draft is accepted with probability ~1/V
        return SamplingParams(temperature=2.0, seed=sid)
    return SamplingParams()


def run_one(cfg, params, workload: str, spec: bool, K: int, max_batch: int,
            warmup: int, steps: int) -> dict:
    bs = cfg.kv_block_size
    horizon = (warmup + steps + 2) * (K + 1)
    eng = Engine(cfg, params, EngineConfig(
        max_batch=max_batch,
        max_seq_len=2 * bs + ((horizon + 2 * bs) // bs + 2) * bs,
        spec_decode="ngram" if spec else None, num_draft_tokens=K))
    for sid, prompt in enumerate(_prompts(cfg, workload, max_batch, 2)):
        eng.submit(Request(seq_id=sid, prompt=prompt,
                           max_new_tokens=horizon + 2,
                           sampling=_sampling(workload, sid)))
    t0 = time.perf_counter()
    for _ in range(warmup):
        eng.step()
    compile_s = time.perf_counter() - t0

    def n_generated():
        return sum(len(st.generated) for st in eng._states.values())

    times = []
    tok0 = n_generated()
    for _ in range(steps):
        t0 = time.perf_counter()
        out = eng.step()
        times.append(time.perf_counter() - t0)
        assert len(out) == max_batch
    tokens = n_generated() - tok0

    st = eng.stats()
    r = {
        "workload": workload,
        "engine": "spec_on" if spec else "spec_off",
        "num_draft_tokens": K if spec else 0,
        "max_batch": max_batch,
        "steps": steps,
        "tokens": tokens,
    }
    r.update(summarize_times(times, compile_s=compile_s))
    # tokens/s over EXACTLY the steady subset step_ms_mean describes
    # (compile spikes excluded; token counts are per-step uniform enough
    # at steady state)
    r["tokens_per_s"] = round(
        tokens / steps * r["n_steady_steps"] / max(r["steady_wall_s"],
                                                   1e-9), 1)
    if spec:
        r["acceptance_rate"] = round(
            st["spec_accepted"] / max(st["spec_drafted"], 1), 4)
        r["tokens_per_step"] = round(tokens / (steps * max_batch), 3)
    return r


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--num-draft-tokens", default="1,4",
                    help="comma list of window widths to sweep")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--steps", type=int, default=48)
    ap.add_argument("--warmup", type=int, default=12)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_spec_decode.json"))
    args = ap.parse_args()
    if args.smoke:
        args.max_batch, args.steps, args.warmup = 2, 6, 3
        args.num_draft_tokens = "2"
    Ks = [int(k) for k in args.num_draft_tokens.split(",")]

    results = []
    speedups, latency_ratios, acceptance = {}, {}, {}
    for workload in ("repetitive", "random", "all_rejected"):
        cfg = _cfg_for(workload, args.arch)
        dims = model_dims(cfg, tp=1)
        params = init_params(jax.random.PRNGKey(0), cfg, dims)
        off = run_one(cfg, params, workload, False, max(Ks),
                      args.max_batch, args.warmup, args.steps)
        off["workload"] = workload
        results.append(off)
        print(f"{workload:13s} spec_off  : {off['step_ms']:7.2f} ms/step"
              f"  {off['tokens_per_s']:8.1f} tok/s")
        for K in Ks:
            r = run_one(cfg, params, workload, True, K, args.max_batch,
                        args.warmup, args.steps)
            results.append(r)
            key = f"{workload}_k{K}"
            speedups[key] = round(r["tokens_per_s"]
                                  / off["tokens_per_s"], 2)
            latency_ratios[key] = round(r["step_ms_mean"]
                                        / off["step_ms_mean"], 2)
            acceptance[key] = r["acceptance_rate"]
            print(f"{workload:13s} spec_on K={K}: {r['step_ms']:7.2f} "
                  f"ms/step  {r['tokens_per_s']:8.1f} tok/s  "
                  f"acc={r['acceptance_rate']:.2%}  "
                  f"speedup={speedups[key]:.2f}x  "
                  f"latency x{latency_ratios[key]:.2f}")

    record = {
        "benchmark": "spec_decode",
        "arch": f"{args.arch} (reduced; repetitive uses vocab=32)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "num_draft_tokens": Ks,
        "results": results,
        "tokens_per_s_speedup_spec_on_over_off": speedups,
        "step_latency_ratio_spec_on_over_off": latency_ratios,
        "acceptance_rate": acceptance,
        # the two ISSUE-5 headline numbers
        "best_repetitive_speedup": max(
            v for k, v in speedups.items() if k.startswith("repetitive")),
        "worst_case_latency_ratio_k1_all_rejected": latency_ratios.get(
            "all_rejected_k1"),
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"\ntokens/s speedup spec-on/off: {speedups}")
    print(f"step-latency ratio spec-on/off (worst case = all_rejected): "
          f"{latency_ratios}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
