import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# The lines above MUST run before any other import (jax locks the device
# count at first initialization).  Placeholder CPU devices stand in for the
# production TPU mesh: 16x16 = one pod, 2x16x16 = two pods.  The CPU pin
# is inherited by every child cell process (``--all``): a dry run never
# takes an accelerator, so it cannot contend for a chip that belongs to a
# serving process.
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the real step function (train_step /
prefill_step / serve_step) with production shardings, lowers it against
ShapeDtypeStruct inputs (no allocation), compiles it, and records

  * compiled.memory_analysis()  — proves the cell fits per-device HBM,
  * compiled.cost_analysis()    — FLOPs/bytes for the roofline,
  * parsed collective traffic   — bytes per device by collective kind,

into benchmarks/results/dryrun/<arch>__<shape>__<mesh>.json.

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k --mesh pod
  python -m repro.launch.dryrun --all [--mesh pod|multipod|both] [-j N]
  python -m repro.launch.dryrun --list
"""
import argparse
import json
import subprocess
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
RESULTS_DIR = os.path.join(ROOT, "benchmarks", "results", "dryrun")


def _cell_list():
    from repro.configs import ARCHS, SHAPES, cell_applicable
    cells = []
    for arch in sorted(ARCHS):
        for shape in SHAPES:
            ok, why = cell_applicable(ARCHS[arch], shape)
            cells.append((arch, shape.name, ok, why))
    return cells


def build_cell(arch: str, shape_name: str, multi_pod: bool,
               perf_variant: str = "baseline"):
    """Returns (lowered, meta) for one cell."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, shape_cell, cell_applicable
    from repro.models import FwdOptions, model_dims, init_params
    from repro.dist.sharding import ShardingRules, make_pins, param_shardings
    from repro.train import (TrainConfig, make_train_step, abstract_state,
                             state_shardings)
    from repro.serve.decode import (make_decode_spec, make_serve_step,
                                    abstract_decode_state,
                                    decode_state_shardings)
    from repro.serve.prefill import make_prefill_step
    from repro.launch.mesh import make_production_mesh, data_axes_for
    from repro.launch import perf_variants

    cfg = get_config(arch)
    shape = shape_cell(shape_name)
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        raise SystemExit(f"SKIP: {why}")

    mesh = make_production_mesh(multi_pod=multi_pod)
    da = data_axes_for(mesh)
    tp = mesh.shape["model"]
    G = 1
    for a in da:
        G *= mesh.shape[a]
    dims = model_dims(cfg, tp=tp)
    rules = ShardingRules(data_axes=da, zero_params=cfg.zero_shard_params)
    cfg, rules, fwd_over = perf_variants.apply(perf_variant, cfg, rules,
                                               shape, multi_pod)
    pins = make_pins(mesh, rules)
    B, S = shape.global_batch, shape.seq_len
    dtype = jnp.bfloat16

    params_abs = jax.eval_shape(
        lambda k: init_params(k, cfg, dims, dtype=dtype),
        jax.random.PRNGKey(0))
    params_sh = param_shardings(params_abs, rules, mesh)
    sd = jax.ShapeDtypeStruct

    def batch_abs_sh(with_labels: bool):
        b = {"tokens": sd((B, S), jnp.int32)}
        s = {"tokens": NamedSharding(mesh, P(da, None))}
        if with_labels:
            b["labels"] = sd((B, S), jnp.int32)
            s["labels"] = NamedSharding(mesh, P(da, None))
        if cfg.frontend != "none":
            b["frontend"] = sd((B, cfg.frontend_tokens, cfg.d_model), dtype)
            s["frontend"] = NamedSharding(mesh, P(da, None, None))
        return b, s

    meta = {"arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": shape.kind, "tp": tp, "data_shards": G,
            "params": cfg.param_count(),
            "active_params": cfg.active_param_count(),
            "perf_variant": perf_variant}

    if shape.kind == "train":
        use_megatron = fwd_over.pop("_megatron", False)
        fwd = FwdOptions(attn_impl="flash_jax", dtype=dtype, remat=cfg.remat,
                         q_chunk=1024, kv_chunk=1024, moe_groups=G,
                         **fwd_over)
        tc = TrainConfig(dtype=dtype, grad_compression=multi_pod,
                         microbatches=cfg.train_microbatches,
                         accum_dtype=(jnp.bfloat16
                                      if cfg.optimizer == "adafactor"
                                      else jnp.float32))
        state_abs = abstract_state(cfg, dims, tc, param_dtype=dtype)
        loss_override = None
        if use_megatron:
            if cfg.family != "dense":
                raise SystemExit("SKIP: megatron variant is dense-only")
            from repro.dist.megatron import (make_megatron_forward,
                                             megatron_param_shardings)
            mfwd = make_megatron_forward(
                cfg, dims, mesh, da, attn_impl="flash_jax",
                triangular=fwd.triangular_schedule, remat=cfg.remat)

            def loss_override(params, batch):
                logits, aux, _ = mfwd(params, batch)
                labels = batch["labels"]
                mask = (labels >= 0).astype(jnp.float32)
                logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
                ll = jnp.take_along_axis(
                    logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
                ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
                return ce, {"ce": ce, "loss": ce}

            p_sh = megatron_param_shardings(state_abs["params"], mesh, rules)
            state_sh = state_shardings(state_abs, mesh, rules)
            state_sh["params"] = p_sh
            if "opt" in state_sh and "m" in state_sh["opt"]:
                state_sh["opt"] = {"m": p_sh, "v": p_sh}
        else:
            state_sh = state_shardings(state_abs, mesh, rules)
        step = make_train_step(cfg, dims, tc, fwd, mesh, rules,
                               loss_override=loss_override)
        batch_abs, batch_sh = batch_abs_sh(True)
        with mesh:
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              donate_argnums=(0,)
                              ).lower(state_abs, batch_abs)
        return lowered, meta

    # ---- inference cells ----
    mode = "striped" if shape_name == "long_500k" else "batch"
    seq_eff = S + (cfg.frontend_tokens if cfg.family == "vlm" else 0)
    spec = make_decode_spec(cfg, seq_eff, B, G, mode=mode, data_axes=da)
    dstate_abs = abstract_decode_state(cfg, dims, spec, B, G, dtype)
    dstate_sh = decode_state_shardings(dstate_abs, mesh, spec)

    if shape.kind == "prefill":
        fwd = FwdOptions(attn_impl="flash_jax", dtype=dtype, remat=False,
                         q_chunk=1024, kv_chunk=1024, moe_groups=G,
                         **fwd_over)
        step = make_prefill_step(cfg, dims, spec, mesh, pins, fwd)
        batch_abs, batch_sh = batch_abs_sh(False)
        nblk = seq_eff // spec.block_size
        slots_abs = sd((B, nblk), jnp.int32)
        slots_sh = NamedSharding(mesh, P(da, None))
        row_abs = sd((B,), jnp.int32)       # slot_ids / ctx / last_pos
        row_sh = NamedSharding(mesh, P(da))
        with mesh:
            lowered = jax.jit(step, in_shardings=(
                params_sh, dstate_sh, batch_sh, slots_sh,
                row_sh, row_sh, row_sh),
                donate_argnums=(1,)
                ).lower(params_abs, dstate_abs, batch_abs, slots_abs,
                        row_abs, row_abs, row_abs)
        return lowered, meta

    if shape.kind == "decode":
        if fwd_over.pop("_kv_int8", False):
            # int8 KV pool (vLLM-style quantized cache): halves the decode
            # memory term; dequant scale folded for structural analysis
            for k in ("k_pool", "v_pool"):
                if k in dstate_abs:
                    dstate_abs[k] = jax.ShapeDtypeStruct(
                        dstate_abs[k].shape, jnp.int8)
        step = make_serve_step(cfg, dims, spec, mesh, pins, dtype)
        tokens_abs = sd((B,), jnp.int32)
        tokens_sh = NamedSharding(mesh, P(da) if mode == "batch" and
                                  B % G == 0 else P())
        with mesh:
            lowered = jax.jit(step, in_shardings=(
                params_sh, dstate_sh, tokens_sh),
                donate_argnums=(1,)
                ).lower(params_abs, dstate_abs, tokens_abs)
        return lowered, meta

    raise ValueError(shape.kind)


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             perf_variant: str = "baseline", save_hlo: bool = False) -> dict:
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import hlo_analysis

    # monotonic clock for durations: time.time() can jump under NTP
    t0 = time.perf_counter()
    lowered, meta = build_cell(arch, shape_name, multi_pod, perf_variant)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = hlo_analysis.analyze_collectives(hlo)
    costs = hlo_analysis.loop_corrected_costs(compiled, hlo)
    weighted = hlo_analysis.analyze_costs(hlo)

    n_dev = 512 if multi_pod else 512  # host device count; mesh uses subset
    result = dict(meta)
    result.update({
        "ok": True,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "flops_per_device_raw": float(ca.get("flops", 0.0)),
        "bytes_per_device_raw": float(ca.get("bytes accessed", 0.0)),
        "flops_per_device": weighted["flops_weighted"],
        "bytes_per_device": weighted["bytes_weighted"],
        "top_computations": weighted["top_computations"],
        "loop_trip_counts": costs["loop_trip_counts"],
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        "collectives": coll,
        "hlo_chars": len(hlo),
    })
    if save_hlo:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        import gzip
        tag = f"{arch}__{shape_name}__{result['mesh']}__{perf_variant}"
        with gzip.open(os.path.join(RESULTS_DIR, tag + ".hlo.gz"),
                       "wt") as f:
            f.write(hlo)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="pod",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--perf-variant", default="baseline")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("-j", type=int, default=2, help="parallel cells (--all)")
    args = ap.parse_args()

    if args.list:
        for arch, shape, ok, why in _cell_list():
            print(f"{'RUN ' if ok else 'SKIP'} {arch:26s} {shape:12s} {why}")
        return

    os.makedirs(RESULTS_DIR, exist_ok=True)

    if args.all:
        meshes = {"pod": [False], "multipod": [True],
                  "both": [False, True]}[args.mesh]
        jobs = []
        for arch, shape, ok, why in _cell_list():
            for mp in meshes:
                tag = f"{arch}__{shape}__{'2x16x16' if mp else '16x16'}"
                out = os.path.join(RESULTS_DIR, tag + ".json")
                if not ok:
                    with open(out, "w") as f:
                        json.dump({"arch": arch, "shape": shape,
                                   "mesh": "2x16x16" if mp else "16x16",
                                   "ok": True, "skipped": True,
                                   "skip_reason": why}, f, indent=1)
                    print(f"SKIP {tag}: {why}")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape,
                       "--mesh", "multipod" if mp else "pod"]
                jobs.append((tag, cmd, out))
        running = []
        failed = []
        while jobs or running:
            while jobs and len(running) < args.j:
                tag, cmd, out = jobs.pop(0)
                env = dict(os.environ)
                env["PYTHONPATH"] = os.path.join(ROOT, "src")
                p = subprocess.Popen(cmd, env=env,
                                     stdout=subprocess.PIPE,
                                     stderr=subprocess.STDOUT, text=True)
                running.append((tag, p, out, time.perf_counter()))
            time.sleep(1.0)
            for item in list(running):
                tag, p, out, t0 = item
                if p.poll() is None:
                    continue
                running.remove(item)
                dt = time.perf_counter() - t0
                if p.returncode == 0 and os.path.exists(out):
                    print(f"PASS {tag} ({dt:.0f}s)")
                else:
                    failed.append(tag)
                    log = p.stdout.read() if p.stdout else ""
                    with open(out.replace(".json", ".log"), "w") as f:
                        f.write(log)
                    print(f"FAIL {tag} ({dt:.0f}s) — see "
                          f"{out.replace('.json', '.log')}")
        print(f"\n{'ALL CELLS PASS' if not failed else 'FAILED: ' + str(failed)}")
        sys.exit(1 if failed else 0)

    # single cell
    assert args.arch and args.shape, "--arch and --shape required"
    for mp in ({"pod": [False], "multipod": [True],
                "both": [False, True]}[args.mesh]):
        result = run_cell(args.arch, args.shape, mp,
                          perf_variant=args.perf_variant,
                          save_hlo=args.save_hlo)
        tag = (f"{args.arch}__{args.shape}__{result['mesh']}"
               + ("" if args.perf_variant == "baseline"
                  else f"__{args.perf_variant}"))
        out = os.path.join(RESULTS_DIR, tag + ".json")
        with open(out, "w") as f:
            json.dump(result, f, indent=1)
        mb = result["memory"]
        print(f"{tag}: lower {result['lower_s']}s compile "
              f"{result['compile_s']}s | args "
              f"{mb['argument_bytes']/2**30:.2f} GiB temp "
              f"{mb['temp_bytes']/2**30:.2f} GiB | flops/dev "
              f"{result['flops_per_device']:.3e} | coll "
              f"{result['collectives']['collective_bytes_per_device']/2**30:.3f} GiB")


if __name__ == "__main__":
    main()
