"""Steady-state decode step benchmark: current vs pre-PR engine hot path.

Measures per-step latency and tokens/step-second of ``Engine.step()`` at
several batch sizes, in hybrid and flexible_only translation modes, and
records the speedup of the translate-once hot path (PR 1) over a faithful
emulation of the pre-PR engine:

* pre-PR: the hybrid RSW ran inside the per-layer scan body (L
  translations per step), the engine re-translated on host for stats
  (``translate()`` + ``device_state()`` per live request), re-uploaded the
  FULL TAR/SF/flex every step, applied slot copies one ``.at[].set`` at a
  time, and paid one ``int(ctx_len[slot])`` + one
  ``int(argmax(logits[slot]))`` device sync per request per step;
* current: one translation dispatch per step, telemetry in-graph, dirty-
  delta sync, one batched copy dispatch, ONE device fetch per step.

Emits a JSON record (default: BENCH_engine_step.json at the repo root) so
the decode-step perf trajectory is tracked from this PR onward.

Run:  PYTHONPATH=src python benchmarks/bench_engine_step.py
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from common import summarize_times  # noqa: E402

from repro.configs import ARCHS, reduced
from repro.launch.compile_cache import enable_compile_cache
from repro.core import translate
from repro.models import model_dims, init_params
from repro.serve import Engine, Request
from repro.serve.decode import make_serve_step, translate_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class LegacyEngine(Engine):
    """Emulates the pre-PR hot path on top of the current engine.

    Every removed overhead is reinstated; the per-layer in-scan
    translation is emulated by forcing one extra translation dispatch per
    attention layer into the jitted step (their results are returned as
    live outputs so XLA cannot dead-code them).
    """

    def __init__(self, *args, dtype=jnp.float32, **kwargs):
        super().__init__(*args, dtype=dtype, **kwargs)
        base = make_serve_step(self.cfg, self.dims, self.spec, mesh=None,
                               dtype=dtype)
        n_extra = max(0, self._n_attn_layers - 1)
        spec = self.spec

        def legacy_step(params, dstate, tokens):
            logits, nd, st = base(params, dstate, tokens)
            # pre-PR: every attention layer re-translated all block vpns
            for i in range(n_extra):
                tr = translate_step(dstate["tar"], dstate["sf"],
                                    dstate["flex"], dstate["ctx_len"], spec)
                st[f"_layer_translation_{i}"] = tr.slots   # keep it live
            return logits, nd, st

        self._serve_step = jax.jit(legacy_step)

    def _sync_translation(self, full: bool = False) -> None:
        m = self.manager
        m.take_dirty()
        self.dstate["tar"] = jnp.asarray(m.tar)[None]
        self.dstate["sf"] = jnp.asarray(m.sf)[None]
        self.dstate["flex"] = jnp.asarray(m.flex_table.reshape(-1))[None]
        self._synced_full = True

    def _apply_copies(self) -> None:
        for src, dst in self.manager.take_pending_copies():
            self.dstate["k_pool"] = self.dstate["k_pool"].at[:, dst].set(
                self.dstate["k_pool"][:, src])
            self.dstate["v_pool"] = self.dstate["v_pool"].at[:, dst].set(
                self.dstate["v_pool"][:, src])

    def step(self):
        live = [r for r in self.requests.values() if not r.done]
        if not live:
            return {}
        m = self.manager
        bs = self.cfg.kv_block_size
        tokens = np.zeros(self.max_batch, np.int64)
        for r in live:
            slot = self._slot_of[r.seq_id]
            pos = int(self.dstate["ctx_len"][slot])     # device sync / req
            if self._n_attn_layers and pos % bs == 0:
                info = m.allocate_block(r.seq_id, pos // bs)
                if info.seg == 2:
                    info = m.swap_in(r.seq_id, pos // bs)
            tokens[slot] = r.generated[-1]
        self._apply_copies()
        self._sync_translation()

        logits, self.dstate, _ = self._serve_step(
            self.params, self.dstate, jnp.asarray(tokens))

        # host-side re-translation for stats (the pre-PR third translation)
        if self._n_attn_layers and self.track_stats:
            ts = m.device_state()
            for r in live:
                slot = self._slot_of[r.seq_id]
                pos = int(self.dstate["ctx_len"][slot])
                nblk = (pos + bs - 1) // bs
                vpns = np.array([m.cfg.vpn(slot, b) for b in range(nblk)])
                res = translate(ts, jnp.asarray(vpns, jnp.int32))
                m.record_device_stats(vpns, np.asarray(res.in_rest),
                                      np.asarray(res.accesses))
            m.run_promotions()
            self._apply_copies()

        out = {}
        for r in live:
            slot = self._slot_of[r.seq_id]
            nxt = int(jnp.argmax(logits[slot]))         # device sync / req
            r.generated.append(nxt)
            out[r.seq_id] = nxt
            if len(r.generated) >= r.max_new_tokens:
                self._states[r.seq_id].done = True
        self._ctx_host[:] = np.asarray(self.dstate["ctx_len"])
        return out


def run_one(engine_cls, cfg, params, mode: str, max_batch: int,
            warmup: int = 6, steps: int = 32) -> dict:
    bs = cfg.kv_block_size
    eng = engine_cls(cfg, params, max_batch=max_batch,
                     max_seq_len=2 * bs + (warmup + steps + bs),
                     mode=mode)
    rng = np.random.RandomState(0)
    horizon = warmup + steps + 2
    for sid in range(max_batch):
        eng.add_request(Request(seq_id=sid,
                                prompt=rng.randint(0, cfg.vocab_size,
                                                   2 * bs),
                                max_new_tokens=horizon + 2))
    t_compile = time.perf_counter()
    for _ in range(warmup):
        eng.step()
    t_compile = time.perf_counter() - t_compile
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        out = eng.step()
        times.append(time.perf_counter() - t0)
        assert len(out) == max_batch
    # median + warmup-excluded steady mean; a timed step that still hit a
    # one-time XLA compile (a fresh scatter-bucket shape) is reported
    # separately as a compile spike instead of polluting the mean
    r = {
        "engine": "legacy_emulated" if engine_cls is LegacyEngine
                  else "current",
        "mode": mode,
        "max_batch": max_batch,
        "steps": steps,
    }
    r.update(summarize_times(times, compile_s=t_compile))
    r["tokens_per_step_s"] = round(max_batch / (r["step_ms"] / 1e3), 1)
    return r


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b")
    ap.add_argument("--batches", default="2,4")
    ap.add_argument("--modes", default="hybrid,flexible_only")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "BENCH_engine_step.json"))
    args = ap.parse_args()

    cfg = reduced(ARCHS[args.arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)

    results = []
    for mode in args.modes.split(","):
        for mb in (int(b) for b in args.batches.split(",")):
            for cls in (Engine, LegacyEngine):
                r = run_one(cls, cfg, params, mode, mb, steps=args.steps)
                results.append(r)
                print(f"{r['engine']:16s} mode={mode:14s} B={mb}: "
                      f"{r['step_ms']:8.2f} ms/step  "
                      f"{r['tokens_per_step_s']:8.1f} tok/s")

    speedups = {}
    steady = {}
    for mode in args.modes.split(","):
        for mb in (int(b) for b in args.batches.split(",")):
            cur = next(r for r in results if r["engine"] == "current"
                       and r["mode"] == mode and r["max_batch"] == mb)
            leg = next(r for r in results
                       if r["engine"] == "legacy_emulated"
                       and r["mode"] == mode and r["max_batch"] == mb)
            speedups[f"{mode}_b{mb}"] = round(
                leg["step_ms"] / cur["step_ms"], 2)
            # the absolute steady-state latency headline, lifted to the
            # top level so run.py's KEY_METRICS/--diff gate can track it
            # PR-over-PR (direction: lower is better)
            steady[f"{mode}_b{mb}"] = cur["step_ms"]

    record = {
        "benchmark": "engine_step",
        "arch": f"{args.arch} (reduced)",
        "platform": jax.devices()[0].platform,
        "jax": jax.__version__,
        "results": results,
        "speedup_vs_pre_pr": speedups,
        "steady_step_ms": steady,
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"\nspeedup vs pre-PR hot path: {speedups}")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
