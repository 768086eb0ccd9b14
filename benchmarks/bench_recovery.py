"""Recovery benchmark: what crash-safety costs and what it buys (ISSUE 10).

Three questions, one workload (oversubscribed serve drain, the
bench_overload shape):

* **Snapshot overhead** — the direct cost of one ``Engine.snapshot()``
  call (``snapshot_ms_mean``, one batched device_get + one pickle)
  amortized over snapshot cadence N ∈ {10, 50}
  (``snapshot_overhead_ratio.every_N`` = 1 + snap_ms / (N · step_ms);
  gated analytically because the true cost is far below run-to-run
  wall noise). End-to-end ``ResilientServe``-supervised drains at each
  cadence are also run and reported (``supervised_wall_s``) as an
  ungated sanity reference.
* **Restore latency** — ``Engine.restore`` onto a fresh engine
  (``restore_ms``): unpickle, device_put, full translation re-sync.
* **Replay vs cold re-prefill** — after a crash near the end of the
  run, finishing from the last snapshot (restore + replay the tail)
  vs restarting the whole workload from scratch
  (``recovery_speedup_replay_over_cold``, the reason snapshots exist:
  replay re-runs a bounded tail, cold recovery re-prefills every
  prompt).

``--smoke`` runs a tiny configuration for CI (keeps the script from
bit-rotting; timings are not meaningful there).

Run:  PYTHONPATH=src python benchmarks/bench_recovery.py
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
from repro.runtime import ResilientServe, ServeFaultInjector
from repro.serve import Engine, EngineConfig, Request

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mkeng(cfg, params, max_batch, injector=None):
    bs = cfg.kv_block_size
    return Engine(cfg, params, EngineConfig(
        max_batch=max_batch, max_seq_len=8 * bs, pool_headroom=0.75,
        auto_release=True, fault_injector=injector))


def _reqs(cfg, n_req, max_new):
    bs = cfg.kv_block_size
    rng = np.random.RandomState(7)
    return [Request(seq_id=i,
                    prompt=rng.randint(0, cfg.vocab_size, 2 * bs),
                    max_new_tokens=max_new) for i in range(n_req)]


def _drain(poll, unfinished, budget=20000):
    steps = 0
    while unfinished():
        poll()
        steps += 1
        assert steps < budget, "failed to drain"
    return steps


def run_baseline(cfg, params, n_req, max_batch, max_new, warm):
    # warm with the FULL workload shape so the timed drains (this one
    # and every supervised run after it) compare compile-free walls
    if warm:
        weng = _mkeng(cfg, params, max_batch)
        for r in _reqs(cfg, n_req, max_new):
            weng.submit(r)
        _drain(weng.poll, weng.has_unfinished)
    eng = _mkeng(cfg, params, max_batch)
    for r in _reqs(cfg, n_req, max_new):
        eng.submit(r)
    t0 = time.perf_counter()
    steps = _drain(eng.poll, eng.has_unfinished)
    return time.perf_counter() - t0, steps, eng


def run_supervised(cfg, params, n_req, max_batch, max_new, every):
    eng = _mkeng(cfg, params, max_batch)
    sup = ResilientServe(eng, snapshot_every=every)
    for r in _reqs(cfg, n_req, max_new):
        sup.submit(r)
    t0 = time.perf_counter()
    _drain(sup.poll, sup.has_unfinished)
    return time.perf_counter() - t0, sup


def measure_snapshot_restore(cfg, params, n_req, max_batch, max_new,
                             reps=5):
    """Direct per-call costs mid-workload (live KV + queue state)."""
    eng = _mkeng(cfg, params, max_batch)
    for r in _reqs(cfg, n_req, max_new):
        eng.submit(r)
    for _ in range(6):
        eng.poll()
    snap_ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        snap = eng.snapshot()
        snap_ms.append((time.perf_counter() - t0) * 1e3)
    nbytes = (len(snap.host_blob)
              + sum(a.nbytes for a in snap.dstate.values()))
    fresh = _mkeng(cfg, params, max_batch)
    restore_ms = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fresh.restore(snap)
        restore_ms.append((time.perf_counter() - t0) * 1e3)
    return (round(float(np.mean(snap_ms)), 3),
            round(float(np.mean(restore_ms)), 3),
            nbytes)


def measure_replay_vs_cold(cfg, params, n_req, max_batch, max_new,
                           total_steps):
    """Crash near the end of the drain: finish via restore+replay vs
    restart the whole workload cold."""
    crash_step = max(4, int(total_steps * 0.75))
    inj = ServeFaultInjector(crash_at=[(crash_step, "pre")])
    eng = _mkeng(cfg, params, max_batch, injector=inj)
    sup = ResilientServe(eng, snapshot_every=10, max_restarts=2)
    for r in _reqs(cfg, n_req, max_new):
        sup.submit(r)
    # run up to one step before the crash outside the timed region;
    # the supervisor recovers *inside* poll(), so the next poll pays
    # restore + replay and the timed region must start here
    while eng._step_count < crash_step - 1 and sup.has_unfinished():
        sup.poll()
    t_rec = time.perf_counter()
    _drain(sup.poll, sup.has_unfinished)
    replay_s = time.perf_counter() - t_rec
    assert sup.restarts == 1, "crash did not land where expected"
    # cold recovery: a new engine re-prefills EVERY prompt from scratch
    cold = _mkeng(cfg, params, max_batch)
    for r in _reqs(cfg, n_req, max_new):
        cold.submit(r)
    t0 = time.perf_counter()
    _drain(cold.poll, cold.has_unfinished)
    cold_s = time.perf_counter() - t0
    return replay_s, cold_s, crash_step


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--n-req", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--every", default="10,50",
                    help="comma list of snapshot cadences to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI configuration (keeps the script from "
                         "bit-rotting; timings not meaningful)")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_recovery.json"))
    args = ap.parse_args()
    if args.smoke:
        args.n_req, args.max_new = 4, 8

    cfg = dataclasses.replace(reduced(ARCHS[args.arch]), num_layers=2)
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    cadences = [int(x) for x in args.every.split(",")]

    reps = 1 if args.smoke else 3
    walls = []
    for i in range(reps):
        w, total_steps, _ = run_baseline(cfg, params, args.n_req,
                                         args.max_batch, args.max_new,
                                         warm=(i == 0))
        walls.append(w)
    base_s = float(np.median(walls))
    print(f"baseline drain: {base_s:.3f} s over {total_steps} steps")

    snap_ms, restore_ms, snap_bytes = measure_snapshot_restore(
        cfg, params, args.n_req, args.max_batch, args.max_new)
    print(f"snapshot {snap_ms:.2f} ms  restore {restore_ms:.2f} ms  "
          f"({snap_bytes / 2**20:.2f} MB)")

    # end-to-end supervised walls are reported for reference, but the
    # gated overhead ratio is amortized from the per-call snapshot
    # cost: the true cost (~snap_ms every N steps) is far below
    # run-to-run wall noise, so a wall/wall ratio would gate on noise
    step_ms = base_s * 1e3 / max(total_steps, 1)
    overhead, supervised_wall = {}, {}
    for every in cadences:
        sup_s, sup = run_supervised(cfg, params, args.n_req,
                                    args.max_batch, args.max_new, every)
        supervised_wall[f"every_{every}"] = round(sup_s, 3)
        overhead[f"every_{every}"] = round(
            1.0 + snap_ms / (every * step_ms), 5)
        print(f"supervised N={every:3d}: {sup_s:.3f} s end-to-end "
              f"({sup.snapshots} snapshots, amortized overhead "
              f"x{overhead[f'every_{every}']})")

    replay_s, cold_s, crash_step = measure_replay_vs_cold(
        cfg, params, args.n_req, args.max_batch, args.max_new,
        total_steps)
    speedup = round(cold_s / max(replay_s, 1e-9), 3)
    print(f"crash at step {crash_step}: replay {replay_s:.3f} s vs "
          f"cold {cold_s:.3f} s (x{speedup})")

    record = {
        "benchmark": "recovery",
        "arch": f"{args.arch} (reduced, 2 layers)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "smoke": bool(args.smoke),
        "n_req": args.n_req,
        "max_new_tokens": args.max_new,
        "baseline_wall_s": round(base_s, 3),
        "baseline_steps": total_steps,
        "supervised_wall_s": supervised_wall,
        "snapshot_overhead_ratio": overhead,
        "snapshot_ms_mean": snap_ms,
        "restore_ms": restore_ms,
        "snapshot_bytes": snap_bytes,
        "crash_step": crash_step,
        "replay_wall_s": round(replay_s, 3),
        "cold_wall_s": round(cold_s, 3),
        "recovery_speedup_replay_over_cold": speedup,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
