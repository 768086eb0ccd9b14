#!/usr/bin/env python3
"""Chip smoke: the serving engine's main path on a TPU, at real widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the SPMD engine on a 2x2 host

The default run serves granite-8b at its published widths (d_model 4096,
32 q / 8 kv heads of 128, d_ff 14336, vocab 49152), cut to 8 of its 36
layers, with bf16 weights, activations and KV pool and random weights
from a seed.  Requests go through ``Engine.submit`` / ``poll`` with the
usual scheduler, hybrid KV manager, TAR/SF/flex tables and prefix cache:
16 requests of 1024 prompt tokens, half of them sharing a 512-token
prefix and two of them identical, 64 greedy tokens each, first in
``hybrid`` mode and then in ``flexible_only`` mode.

``--four-chips`` runs only the sharded engine (``mesh_shape=(1, 4)``)
against the single-device engine on the same requests, in this process.

The run fails (non-zero exit, no ``"ok": true``) when the backend is not
a TPU, when a request does not finish with its asked length, when a token
id is out of range, when the two identical prompts give different
streams, when the prefix cache never hits, when the KV manager's or the
engine's invariants fail, when the two modes give different streams, or
when the engine's logits disagree with the float32 reference forward
(``check_logits``, on a unique request and on one whose prefix came from
the cache).  Its last line is one JSON
object naming the device.  The throughput it prints is that of one smoke
run, compiles included: it is not a benchmark.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH, LAYERS, DTYPE = "granite-8b", 8, "bfloat16"
N_LOGIT_STEPS = 4           # decode positions the logits check covers
# Logits tolerance.  The engine keeps activations, residual stream and KV
# pool in bf16 and does norms, softmax and matmul accumulation in float32:
# the numerics of ``forward`` with bf16 activations.  Its relative L2
# distance from the float32 reference (same bf16 weights) is therefore
# about that of the bf16 forward, ~1.3% at these depths: rounding order
# alone moved the ratio of the two by under 5% (0.97-1.00 at widths
# 512-1024, 8 layers, on the CPU).  A float32 -> bf16 slip in the engine
# alone adds rounding the forward does not have: an RMS norm computed in
# bf16 raised the ratio to 1.27-1.29.  1.15 passes the engine and fails
# that slip.
LOGITS_RATIO_TOL = 1.15
# A slip in code the engine shares with ``forward`` (norms, projections)
# raises both distances and leaves their ratio near 1, so the engine's
# distance is also bounded absolutely: 1.15x the bf16 forward's own
# distance at the smoke's size, 0.012605 as read on a TPU v5e.  A shared
# bf16 RMS norm raised both distances by 21-23% at widths 512-1024, 8
# layers, on the CPU (``tests/test_chip_smoke.py`` plants it).
REL_L2_MAX = LOGITS_RATIO_TOL * 0.012605
MESH_SHAPE = (1, 4)         # the --four-chips engine: 4-way model axis
FAIL = 1


@dataclasses.dataclass(frozen=True)
class Workload:
    n_requests: int = 16
    prompt_len: int = 1024
    shared_len: int = 512          # prefix shared by the even requests
    max_new: int = 64
    max_batch: int = 16
    max_seq_len: int = 4096


FOUR_CHIP_WORKLOAD = Workload(n_requests=8, max_new=16)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


TWIN = 2                    # request TWIN repeats request 0's prompt


def make_requests(cfg, wl: Workload):
    """Greedy requests: the even ones share a ``shared_len`` prefix, the
    odd ones are unique, and request ``TWIN`` repeats request 0's prompt
    (admitted in the same round, so both are computed in full).  Even
    requests of later rounds hit the prefix cache."""
    import numpy as np
    from repro.serve import Request

    rng = np.random.RandomState(0)
    shared = rng.randint(0, cfg.vocab_size, wl.shared_len)
    prompts = []
    for i in range(wl.n_requests):
        if i % 2 == 0:
            tail = rng.randint(0, cfg.vocab_size,
                               wl.prompt_len - wl.shared_len)
            prompts.append(np.concatenate([shared, tail]))
        else:
            prompts.append(rng.randint(0, cfg.vocab_size, wl.prompt_len))
    prompts[TWIN] = prompts[0].copy()
    return [Request(seq_id=i, prompt=p, max_new_tokens=wl.max_new)
            for i, p in enumerate(prompts)]


def checked_ids(requests):
    """The requests whose logits are checked: the last one (odd: unique,
    computed in full) and the last even one (admitted in the last round,
    its shared prefix attached from the prefix cache)."""
    return requests[-1].seq_id, requests[-2].seq_id


def record_logits(eng, seq_ids):
    """Keep the logits rows of each of ``seq_ids`` from every engine step
    that computes them: the prefill or prefix-KV chunk steps (a chunk's
    last position) and the decode steps (one position each), in order.
    Returns {seq_id: [(kind, row), ...]}, filled as the engine runs."""
    import numpy as np

    rows = {sid: [] for sid in seq_ids}

    def wrap(step, row_arg, kind):
        def call(params, dstate, *args, **kw):
            out = step(params, dstate, *args, **kw)
            sel = np.asarray(args[row_arg])
            for sid, got in rows.items():
                slot = eng._slot_of.get(sid)
                if slot is None:
                    continue
                if kind == "decode" and sel[slot]:
                    got.append((kind, out[0][slot]))
                elif kind == "prefill" and (sel == slot).any():
                    got.append((kind, out[0][int(np.argmax(sel == slot))]))
            return out
        return call

    # serve_step(params, dstate, tokens, active); the chunk steps take
    # slot_ids third (prefill) or fourth (prefix-KV) after dstate
    eng._serve_step = wrap(eng._serve_step, 1, "decode")
    eng._prefill_step = wrap(eng._prefill_step, 2, "prefill")
    eng._prefix_step = wrap(eng._prefix_step, 3, "prefill")
    return rows


def serve(eng, requests):
    """Submit every request, poll to completion; returns
    ({seq_id: tokens}, {seq_id: finish_reason}, window seconds)."""
    streams = {r.seq_id: [] for r in requests}
    reasons = {}
    t0 = time.perf_counter()
    for r in requests:
        eng.submit(r)
    while eng.has_unfinished():
        for out in eng.poll():
            streams[out.seq_id].extend(out.new_token_ids)
            if out.finished:
                reasons[out.seq_id] = out.finish_reason
    return streams, reasons, time.perf_counter() - t0


def check_streams(cfg, requests, streams, reasons) -> None:
    for r in requests:
        toks = streams[r.seq_id]
        check(reasons.get(r.seq_id) == "length"
              and len(toks) == r.max_new_tokens,
              f"request {r.seq_id}: {len(toks)} tokens, finish "
              f"{reasons.get(r.seq_id)!r}; asked {r.max_new_tokens}")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"request {r.seq_id}: token id outside [0, "
              f"{cfg.vocab_size})")
    check(streams[0] == streams[TWIN],
          "identical prompts gave different streams")


def reference_logits(cfg, params, tokens, dtype):
    """``forward`` over ``tokens`` on the same (bf16) weights with
    ``dtype`` activations.  float32 runs every matmul at ``highest``
    precision; bf16 runs at the engine's own default precision.  The
    weights are cast layer by layer inside the scan, so no float32 copy
    of the model is held."""
    import jax
    import jax.numpy as jnp
    from repro.models import FwdOptions, forward, model_dims

    opt = FwdOptions(attn_impl="dense", dtype=dtype)
    fwd = jax.jit(lambda p, t: forward(p, {"tokens": t}, cfg,
                                       model_dims(cfg, tp=1), opt)[0])
    t = jnp.asarray(tokens, jnp.int32)[None]
    if dtype == jnp.float32:
        with jax.default_matmul_precision("highest"):
            return fwd(params, t)[0]
    return fwd(params, t)[0]


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def check_logits(cfg, params, request, stream, rows) -> dict:
    """Engine logits for the prompt's last position and the first
    ``N_LOGIT_STEPS`` decode positions against the float32 reference
    forward over prompt + the tokens the engine fed.  The engine's
    relative L2 distance from it must stay within ``LOGITS_RATIO_TOL``
    times the distance of the model's own bf16 forward, and within
    ``REL_L2_MAX``.  Returns both distances."""
    import jax.numpy as jnp
    import numpy as np

    kinds = [k for k, _ in rows]
    check("decode" in kinds and kinds.index("decode") > 0,
          f"logits of request {request.seq_id} were not recorded")
    first = kinds.index("decode")
    got = [rows[first - 1][1]] + [r for _, r in
                                  rows[first:first + N_LOGIT_STEPS]]
    P, n, V = len(request.prompt), len(got), cfg.vocab_size
    tokens = np.concatenate([np.asarray(request.prompt),
                             np.asarray(stream[:n - 1])])
    at = slice(P - 1, P - 1 + n)
    ref32 = np.asarray(reference_logits(cfg, params, tokens, jnp.float32),
                       np.float32)[at, :V]
    ref16 = np.asarray(reference_logits(cfg, params, tokens, jnp.bfloat16),
                       np.float32)[at, :V]
    eng = np.stack([np.asarray(g, np.float32)[:V] for g in got])
    d_eng, d_fwd = rel_l2(eng, ref32), rel_l2(ref16, ref32)
    print(f"logits vs float32 reference (request {request.seq_id}, "
          f"positions {P - 1}..{P - 2 + n}): engine rel L2 {d_eng:.6f} "
          f"(per position "
          + " ".join(f"{rel_l2(e, r):.5f}" for e, r in zip(eng, ref32))
          + f"), bf16 forward {d_fwd:.6f}, ratio {d_eng / d_fwd:.4f} "
          f"(tolerance {LOGITS_RATIO_TOL}, bound {REL_L2_MAX:.6f})",
          flush=True)
    check(d_eng <= LOGITS_RATIO_TOL * d_fwd,
          f"engine logits are {d_eng / d_fwd:.4f}x as far from the float32 "
          f"reference as the bf16 forward (> {LOGITS_RATIO_TOL})")
    check(d_eng <= REL_L2_MAX,
          f"engine logits are {d_eng:.6f} from the float32 reference "
          f"(> {REL_L2_MAX:.6f})")
    return {"engine_rel_l2": d_eng, "bf16_forward_rel_l2": d_fwd}


def run_mode(cfg, params, wl: Workload, mode: str, **options):
    """Serve the workload once in ``mode``; run every stream and engine
    check.  Returns (requests, streams, {seq_id: recorded logits rows}
    for the ``checked_ids``, summary dict)."""
    from repro.launch.serve import make_engine

    requests = make_requests(cfg, wl)
    eng = make_engine(cfg, params, max_batch=wl.max_batch,
                      max_seq_len=wl.max_seq_len, dtype=DTYPE, mode=mode,
                      **options)
    unique, cached = checked_ids(requests)
    rows = record_logits(eng, (unique, cached))
    streams, reasons, dt = serve(eng, requests)
    check_streams(cfg, requests, streams, reasons)
    eng.manager.check_invariants()
    eng.check_invariants()
    st = eng.stats()
    pc = st["prefix_cache"]
    check(pc["hits"] > 0, "the prefix cache never hit")
    check(st["per_request"][cached]["cached_blocks"] > 0,
          f"request {cached} did not attach its prefix from the cache")
    tokens = sum(len(s) for s in streams.values())
    summary = {"mode": mode, "requests": len(requests), "tokens": tokens,
               "seconds": dt, "steps": eng.step_count,
               "prefix_hits": pc["hits"],
               "dedup_blocks": pc["dedup_blocks"],
               "rsw_hits": st.get("rsw_hits", 0),
               "flex_walks": st.get("flex_walks", 0)}
    del eng
    gc.collect()          # the step wrappers keep a cycle through eng
    return requests, streams, rows, summary


def check_logits_of(cfg, params, requests, streams, rows) -> dict:
    """``check_logits`` on each checked request; {seq_id: distances}."""
    by_id = {r.seq_id: r for r in requests}
    return {sid: check_logits(cfg, params, by_id[sid], streams[sid], got)
            for sid, got in rows.items()}


def smoke_one_chip(cfg, params, wl: Workload = Workload()):
    """Both translation modes at the same size.  The logits check runs on
    the hybrid run; the flexible_only run must give the same streams.
    Returns the per-mode summaries."""
    out = []
    first = None
    for mode in ("hybrid", "flexible_only"):
        requests, streams, rows, summary = run_mode(cfg, params, wl, mode)
        print(f"[{mode}] {summary['requests']} requests, "
              f"{summary['tokens']} tokens in {summary['seconds']:.3f} s "
              f"= {summary['tokens'] / summary['seconds']:.1f} tokens/s "
              f"(one smoke run, compiles included; not a benchmark); "
              f"{summary['steps']} engine steps, prefix-cache hits "
              f"{summary['prefix_hits']} ({summary['dedup_blocks']} "
              f"blocks), RSW hits {summary['rsw_hits']}, flex walks "
              f"{summary['flex_walks']}", flush=True)
        if first is None:
            first = streams
            summary["logits"] = check_logits_of(cfg, params, requests,
                                                streams, rows)
        else:
            same = streams == first
            print(f"streams identical across modes: {same}", flush=True)
            check(same, f"{mode} streams differ from the hybrid run's")
        out.append(summary)
    return out


def smoke_four_chips(cfg, params, wl: Workload = FOUR_CHIP_WORKLOAD):
    """The SPMD engine on ``MESH_SHAPE`` against the single-device engine
    on the same requests.  Both pass the logits check; the distance of
    their first decode logits and whether their streams are bit-identical
    (the CPU contract, DESIGN.md §sharded-serving) are printed.  Returns
    (that distance, bit-identical)."""
    import numpy as np

    runs = {}
    for name, mesh in (("single", None), ("mesh", MESH_SHAPE)):
        requests, streams, rows, summary = run_mode(
            cfg, params, wl, "hybrid", mesh_shape=mesh)
        print(f"[{name} mesh_shape={mesh}] {summary['tokens']} tokens in "
              f"{summary['seconds']:.3f} s (one smoke run, compiles "
              f"included; not a benchmark)", flush=True)
        check_logits_of(cfg, params, requests, streams, rows)
        unique = rows[checked_ids(requests)[0]]
        kinds = [k for k, _ in unique]
        first_dec = np.asarray(unique[kinds.index("decode")][1], np.float32)
        runs[name] = (streams, first_dec[:cfg.vocab_size])
    err = rel_l2(runs["mesh"][1], runs["single"][1])
    same = runs["mesh"][0] == runs["single"][0]
    print(f"mesh vs single-device: first decode logits rel L2 {err:.6f}; "
          f"streams bit-identical: {same}", flush=True)
    return err, same


class CompileLog:
    """Counts executables compiled (or loaded from the persistent cache)
    and the seconds spent, from JAX's own compile events."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the mesh (1, 4) engine against the "
                         "single-device engine")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}; run it from "
              "a checkout of the repository", file=sys.stderr)
        return FAIL
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import load_model

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return FAIL
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return FAIL
    print(f"device_kind: {dev.device_kind} (platform {dev.platform}, "
          f"{len(devices)} devices)", flush=True)
    cache = enable_compile_cache()
    print(f"compile cache: {cache}", flush=True)
    compiles = CompileLog()
    t0 = time.perf_counter()
    cfg, params = load_model(ARCH, layers=LAYERS, dtype=DTYPE)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}, {cfg.num_layers} of 36 layers, d_model "
          f"{cfg.d_model}, {cfg.num_heads}/{cfg.num_kv_heads} heads, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {n_params / 1e9:.3f} B "
          f"params in {DTYPE} (made in {time.perf_counter() - t0:.1f} s)",
          flush=True)
    try:
        if args.four_chips:
            smoke_four_chips(cfg, params)
        else:
            smoke_one_chip(cfg, params)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return FAIL
    print(f"compiles: {compiles.count} executables in "
          f"{compiles.seconds:.1f} s ({compiles.cache_hits} from the "
          f"persistent cache)", flush=True)
    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
