"""Sharded serving benchmark: step latency / throughput vs mesh shape.

The SPMD engine (ISSUE 7, DESIGN.md §sharded-serving) shards the KV
pool and the TAR/SF/flex translation structures over the mesh's
``model`` axis and translates once per step per shard.  This benchmark
drives the identical decode workload on ``mesh_shape=None`` (the
single-device baseline) and on ``(1, 2)`` / ``(2, 2)`` meshes, checks
the streams stay bit-identical, and records per-mesh step latency and
throughput.

HONEST CPU CAVEAT: on host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``) all "devices"
share the same cores, the transformer compute is fully REPLICATED
across the model axis (that is what buys bit-identical streams — no
float reductions), and every psum is real inter-"device" traffic.  So
sharding on CPU is expected to be SLOWER than the baseline; the numbers
here pin the overhead trend and the wiring, not a speedup.  The win on
real accelerators is KV/table MEMORY per device: each shard holds
``1/M`` of the pool and translation structures (``kv_bytes_per_shard``
below), which is what lets a pool too big for one device serve at all.

``--smoke`` runs a tiny configuration for CI (keeps the script from
bit-rotting; timings are not meaningful there).

Run:  PYTHONPATH=src python benchmarks/bench_sharded.py
"""
from __future__ import annotations

import os

# must precede the jax import: the mesh shapes below need 4 devices
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import argparse
import dataclasses
import json
import sys
import time

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_dims, init_params
from repro.serve import Engine, EngineConfig, Request
from repro.serve.sampling import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_one(cfg, params, mesh_shape, n_req: int, max_batch: int,
            max_new: int) -> dict:
    bs = cfg.kv_block_size
    eng = Engine(cfg, params, EngineConfig(
        max_batch=max_batch, max_seq_len=8 * bs, auto_release=True,
        mesh_shape=mesh_shape))
    rng = np.random.RandomState(7)
    reqs = [Request(seq_id=i,
                    prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                    max_new_tokens=max_new,
                    sampling=SamplingParams()) for i in range(n_req)]
    # compile the bucket shapes outside the timed region
    eng.submit(dataclasses.replace(reqs[0], seq_id=n_req + 1,
                                   max_new_tokens=2))
    while eng.has_unfinished():
        eng.poll()
    for r in reqs:
        eng.submit(r)
    outs = {}
    steps, step_s = 0, []
    t0 = time.perf_counter()
    while eng.has_unfinished():
        ts = time.perf_counter()
        for ro in eng.poll():
            if ro.seq_id <= n_req:
                outs.setdefault(ro.seq_id, []).extend(ro.new_token_ids)
        step_s.append(time.perf_counter() - ts)
        steps += 1
        assert steps < 400 * n_req, "engine failed to drain"
    wall = time.perf_counter() - t0
    eng.check_invariants()
    tokens = sum(len(v) for v in outs.values())
    kv_bytes = (np.asarray(eng.dstate["k_pool"]).nbytes
                + np.asarray(eng.dstate["v_pool"]).nbytes)
    shards = 1 if mesh_shape is None else mesh_shape[1]
    lat = np.asarray(step_s) * 1e3
    return {
        "mesh": "none" if mesh_shape is None else
                f"{mesh_shape[0]}x{mesh_shape[1]}",
        "kv_shards": shards,
        "steps": steps,
        "wall_s": round(wall, 3),
        "tok_s": round(tokens / wall, 1),
        "step_ms_p50": round(float(np.percentile(lat, 50)), 2),
        "step_ms_p99": round(float(np.percentile(lat, 99)), 2),
        "kv_bytes_per_shard": kv_bytes // shards,
        "_streams": outs,
    }


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_sharded.json"))
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.max_new = 4, 10

    cfg = dataclasses.replace(reduced(ARCHS[args.arch]), num_layers=2)
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results, ratios = [], {}
    base = None
    for ms in (None, (1, 2), (2, 2)):
        r = run_one(cfg, params, ms, args.requests, args.max_batch,
                    args.max_new)
        streams = r.pop("_streams")
        if base is None:
            base = (r, streams)
        else:
            assert streams == base[1], f"streams diverged on mesh {ms}"
            ratios[f"mesh_{r['mesh']}"] = round(
                r["step_ms_p50"] / max(base[0]["step_ms_p50"], 1e-9), 3)
        results.append(r)
        print(f"mesh {r['mesh']:4s}: {r['tok_s']:8.1f} tok/s  "
              f"step p50 {r['step_ms_p50']:7.2f} ms  "
              f"p99 {r['step_ms_p99']:7.2f} ms  "
              f"kv/shard {r['kv_bytes_per_shard'] / 2**20:.2f} MB")
    print("streams bit-identical across meshes: OK")

    record = {
        "benchmark": "sharded",
        "arch": f"{args.arch} (reduced, 2 layers)",
        "platform": jax.devices()[0].platform,
        "devices": jax.device_count(),
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "max_batch": args.max_batch,
        "n_requests": args.requests,
        "max_new_tokens": args.max_new,
        "caveat": ("CPU host devices share cores and compute is "
                   "replicated across the model axis for bit-identical "
                   "streams; expect slowdown here, not speedup — the "
                   "accelerator win is 1/M KV+table memory per shard "
                   "(kv_bytes_per_shard)"),
        "results": results,
        "step_latency_ratio_vs_single_device": ratios,
        "kv_bytes_per_shard": {f"mesh_{r['mesh']}": r["kv_bytes_per_shard"]
                               for r in results},
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
