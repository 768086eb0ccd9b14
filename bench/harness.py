"""One run of one cell: load, warm up, measure, check.

``run_cell`` builds the cell's engine through ``repro.launch.serve.
make_engine`` on weights the configuration's reference module makes from
the seed, warms every program shape the cell's traffic can reach, runs the
mix (its own warm-up first, so the prefix cache holds what the sessions
need), measures for ``seconds``, and then checks a sample of what was
served against the plain reference.  With ``trace`` a profiler trace of
part of the window is reduced to the per-layer metrics.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import check, roofline, spec, warmup
from bench.client import Client, end_to_end, percentile

GRACE_S = 60.0          # how long past the window a first token is awaited
TRACE_FRACTION = 0.25   # share of the window traced, from its middle on
TRACE_MAX_S = 8.0


class NoChip(RuntimeError):
    pass


class CompileLog:
    """Counts the executables a process obtains, from JAX's own compile
    events: each one is compiled or, where ``cache_hits`` counts it, loaded
    from the persistent cache."""

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, fun_name=None, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration
            self.names.append(str(fun_name))

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


# JAX's monitoring listeners cannot be taken off again, so one log serves
# every run in a process (calibrate.py and sweep.py make several).
_COMPILES: Optional[CompileLog] = None


def compile_log() -> CompileLog:
    global _COMPILES
    if _COMPILES is None:
        _COMPILES = CompileLog()
    return _COMPILES


def enable_cache(root: str) -> str:
    """JAX's persistent cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path in the checkout; every executable is kept, so
    only the first run of a cell in a checkout compiles."""
    import jax
    where = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", where)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


class StepSink:
    """MetricsLogger sink keeping what the per-layer readers use."""

    def __init__(self):
        self.steps: List[Dict[str, Any]] = []

    def emit(self, event):
        if event.get("kind") == "step":
            self.steps.append({k: event.get(k) for k in
                               ("step", "live", "queued", "rsw_hits",
                                "flex_walks")})

    def close(self):
        pass


def arch_config(config: dict):
    """The engine's ArchConfig, every size taken from the config file."""
    from repro.configs import get_config
    base = get_config(config["arch"])
    return dataclasses.replace(
        base, num_layers=int(config["num_hidden_layers"]),
        d_model=int(config["hidden_size"]),
        num_heads=int(config["num_attention_heads"]),
        num_kv_heads=int(config["num_key_value_heads"]),
        head_dim=int(config.get("head_dim") or config["hidden_size"]
                     // config["num_attention_heads"]),
        d_ff=int(config["intermediate_size"]),
        vocab_size=int(config["vocab_size"]),
        qkv_bias=bool(config.get("attention_bias", False)),
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        kv_block_size=int(config["kv_block_size"]))


@dataclasses.dataclass
class Run:
    """What a per-layer reader may read."""
    cell: spec.Cell
    dims: roofline.Dims
    peaks: Optional[Dict[str, float]]
    chips: int
    max_batch: int
    window: tuple
    trace_window: Optional[tuple]
    client: Client
    steps: List[Dict[str, Any]]
    stats: Dict[str, Any]
    trace: Optional[Dict[str, Any]]


class Tracer:
    """Starts the profiler at ``t_start`` and stops it ``length`` later,
    between engine steps; ``bench.trace`` spans exactly the traced part."""

    def __init__(self, out_dir: str, t_start: float, length: float):
        self.dir, self.t_start, self.t_stop = out_dir, t_start, t_start + length
        self.state = "idle"
        self.span = None
        self.window = None

    def hook(self, now: float) -> None:
        import jax
        if self.state == "idle" and now >= self.t_start:
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.span = jax.profiler.TraceAnnotation("bench.trace")
            self.span.__enter__()
            self.window = [time.perf_counter(), None]
            self.state = "on"
        elif self.state == "on" and now >= self.t_stop:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state != "on":
            return
        self.window[1] = time.perf_counter()
        self.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.state = "done"


def _memory_peak(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             root: str = spec.ROOT, t_process: Optional[float] = None,
             require_tpu: bool = True, cache: bool = True,
             controls=(), rate: Optional[float] = None,
             do_check: bool = True, log=print) -> Dict[str, Any]:
    """One run; returns the result line's fields plus the run's readings
    (``_run``) and the compared numbers (``check``)."""
    t_process = time.perf_counter() if t_process is None else t_process
    import jax
    devices = jax.devices()
    dev = devices[0]
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {dev.platform!r}")
    if len(devices) < cell.chips:
        raise NoChip(f"cell {cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devices)}")
    used = devices[:cell.chips]
    pk = roofline.peaks(dev.device_kind) if require_tpu else None
    log(f"device: platform {dev.platform}, kind {dev.device_kind!r}, "
        f"{len(devices)} devices, cell {cell.name} uses {cell.chips}")
    if cache:
        log(f"compile cache: {enable_cache(root)}")
    compiles = compile_log()

    from repro.launch.serve import make_engine
    from repro.serve import MetricsLogger, Request

    config = dict(cell.config)
    params_cell = dict(cell.params)
    if rate is not None:
        params_cell["rate"] = rate
    ref = spec.load_module(cell.search, "configs", config["reference"])
    dims = roofline.Dims.of(config)
    cfg = arch_config(config)
    params = ref.make_params(config, seed)
    jax.block_until_ready(params)
    sink = StepSink()
    mesh = config.get("mesh_shape")
    engine = make_engine(
        cfg, params, max_batch=int(config["max_batch"]),
        max_seq_len=int(config["max_seq_len"]),
        dtype=config["torch_dtype"], mode=config["mode"],
        metrics=MetricsLogger([sink]),
        mesh_shape=tuple(mesh) if mesh else None)

    # ---- warm every program shape the traffic can reach -----------------
    gen = spec.load_module(cell.search, "mixes", cell.mix["kind"])
    horizon = float(cell.mix["warmup_s"]) + seconds + GRACE_S
    traffic = gen.Traffic(cell.mix, config, params_cell, seed, horizon)
    bs = dims.block
    shapes = warmup.plan(traffic.bounds(engine.prefill_budget), bs,
                         engine.prefill_budget, int(config["max_batch"]),
                         cell.mix["warm_rows"])
    t_w = time.perf_counter()
    c_w = compiles.count
    wrng = np.random.default_rng(seed ^ 0x5EED)
    sid = 0
    for group in warmup.groups(shapes, bs, dims.vocab, wrng):
        for prompt in group:
            sid -= 1
            engine.submit(Request(seq_id=sid, prompt=prompt,
                                  max_new_tokens=1))
        while engine.has_unfinished():
            engine.poll()
    log(f"warm-up of {len(shapes)} prefill/prefix shapes: "
        f"{time.perf_counter() - t_w:.1f} s, {compiles.count - c_w} "
        f"compiles")

    # ---- the mix: its own warm-up, then the window ----------------------
    tracer = None
    span = None
    if trace:
        span = jax.profiler.TraceAnnotation
    t0 = time.perf_counter()
    client = Client(engine, Request, traffic, t0, dims, span=span)
    ws = t0 + traffic.warmup_s
    we = ws + seconds
    client.run_until(ws)
    ws = max(ws, time.perf_counter())
    we = ws + seconds
    setup_s = ws - t_process
    c0, h0 = compiles.count, compiles.cache_hits
    step0 = engine.step_count
    hook = None
    if trace:
        length = min(TRACE_MAX_S, max(1.0, seconds * TRACE_FRACTION))
        tdir = os.path.join(root, ".bench_out", "trace", cell.name)
        tracer = Tracer(tdir, ws + (seconds - length) / 2, length)
        hook = tracer.hook
    client.run_until(we, hook=hook)
    if tracer is not None:
        tracer.stop()
    we_real = time.perf_counter()
    step1 = engine.step_count
    in_window = (compiles.count - c0, compiles.cache_hits - h0)
    client.drain_first_tokens(ws, we, GRACE_S)
    log(f"compiles inside the window: {in_window[0]} "
        f"(of them loaded from the persistent cache: {in_window[1]})"
        + (f": {sorted(set(compiles.names[c0:c0 + in_window[0]]))}"
           if in_window[0] else ""))
    e2e = end_to_end(client, ws, we, missing_s=(time.perf_counter() - ws))
    due = e2e["due"]
    late = e2e["late_s"]
    log(f"requests due in the window: attempted {due}, succeeded "
        f"{due - e2e['failed']}, failed {e2e['failed']}; generator late "
        f"by mean {np.mean(late) * 1e3 if late else 0:.2f} ms, max "
        f"{max(late) * 1e3 if late else 0:.2f} ms; window "
        f"{we_real - ws:.3f} s")
    memory_peak = _memory_peak(used)
    stats = engine.stats()
    steps = [s for s in sink.steps if step0 < s["step"] <= step1]

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(used), "memory_peak_bytes": memory_peak}
    if not trace:
        vals = {
            "output_tokens_per_s": e2e["output_tokens"] / seconds,
            "ttft_p90_ms": (percentile(e2e["ttft_s"], 90) * 1e3
                            if e2e["ttft_s"] else None),
            "tbt_p99_ms": (percentile(e2e["gaps_s"], 99) * 1e3
                           if e2e["gaps_s"] else None),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            v = vals.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        red = None
        if tracer is not None and tracer.state == "done":
            from bench import trace as tr
            try:
                red = tr.load_reduced(tracer.dir,
                                      collectives=cell.chips > 1)
            except ValueError as e:
                if require_tpu:
                    raise
                log(f"trace not reduced: {e}")
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            breakdown = {"device_ops": [[k, v] for k, v in
                                        red["device_ops"]],
                         "idle_gaps": red["idle_gaps"]}
        run = Run(cell=cell, dims=dims, peaks=pk, chips=cell.chips,
                  max_batch=int(config["max_batch"]), window=(ws, we),
                  trace_window=tuple(tracer.window) if red else None,
                  client=client, steps=steps, stats=stats, trace=red)
        for m in cell.per_layer:
            reader = spec.load_module(cell.search, "metrics", m["name"])
            v = reader.read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # ---- free the program's state, then the reference check -------------
    recs = list(client.recs.values())
    client.engine = None
    del engine
    gc.collect()
    result = {"correct": None, "attempted": due, "failed": e2e["failed"],
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    if do_check:
        chk = cell.params["check"]
        crng = np.random.default_rng(seed ^ 0xC0FFEE)
        picked = check.sample([r for r in recs if r.rid >= 0], crng,
                              int(chk["requests"]), int(chk["tokens"]))
        t_c = time.perf_counter()
        g = check.gaps(ref, config, params, picked, controls)
        widest = float(g["served"].max()) if g["served"].size else None
        limit = float(chk["gap_limit"])
        result["correct"] = widest is not None and widest <= limit
        result["check"] = {"widest_gap": {"value": widest, "limit": limit}}
        result["_gaps"] = {k: (float(v.max()) if v.size else None)
                           for k, v in g.items()}
        log(f"reference check: {len(picked)} requests, "
            f"{int(g['served'].size)} served tokens, "
            f"{time.perf_counter() - t_c:.1f} s")
    result["_run"] = {"setup_s": setup_s, "e2e": e2e, "in_window": in_window,
                      "steps": steps, "stats": stats}
    del params
    gc.collect()
    return result
