"""The routed-expert model's reference (``configs/moe_swa_gqa.py``), the
``moe_*`` scope attribution (``bench/moe_scopes.py``) and its four readers.

Scope attribution and the readers run on a synthetic trace (planes as
``scopes.read_planes`` returns them): a decode and a prefill program whose
ops carry ``moe_*`` scopes inside ``ffn``, and one program without them.
"""
import math
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

from bench import moe_scopes, roofline, scopes, spec, trace

SEARCH = spec.search_dirs(spec.ROOT)
REF = spec.load_module(SEARCH, "configs", "moe_swa_gqa")
CELL = "mellum2-12b-a2.5b.unique_batch"
PEAKS = roofline.peaks("TPU v5 lite")
DEC = "jit(serve_step)/while/body/closed_call/ffn/"
PRE = "jit(prefill_step)/while/body/ffn/"


def _config():
    return dict(spec.load_cell(CELL).config)


def _tiny(n_layers=1, types=("sliding_attention",), window=4):
    return {
        "hidden_size": 16, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 8, "num_hidden_layers": n_layers, "vocab_size": 32,
        "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 8, "norm_topk_prob": True,
        "layer_types": list(types), "sliding_window": window,
        "rope_parameters": {
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 1e4},
            "full_attention": {"rope_type": "yarn", "rope_theta": 1e4,
                               "factor": 4, "beta_fast": 32, "beta_slow": 1,
                               "original_max_position_embeddings": 64,
                               "attention_factor": 1.1}},
        "rms_norm_eps": 1e-6, "torch_dtype": "float32"}


# ------------------------------------------------------------ reference

def test_sliding_layer_sees_only_its_window():
    """One sliding layer of window 4: the logits at position p depend on
    tokens p-3..p only; a full layer sees every earlier token."""
    config = _tiny()
    params = REF.make_params(config, 3)
    toks = np.arange(12) % 32
    other = toks.copy()
    other[0] = 31
    at = list(range(12))
    a = REF.logits_at(config, params, toks, at)
    b = REF.logits_at(config, params, other, at)
    assert not np.allclose(a[:4], b[:4])
    np.testing.assert_array_equal(a[4:], b[4:])
    config = _tiny(types=("full_attention",))
    params = REF.make_params(config, 3)
    a = REF.logits_at(config, params, toks, at)
    b = REF.logits_at(config, params, other, at)
    assert not np.allclose(a[4:], b[4:])


def test_fp8_control_moves_the_logits():
    config = _tiny(2, ("sliding_attention", "full_attention"))
    params = REF.make_params(config, 5)
    toks = np.arange(20) % 32
    a = REF.logits_at(config, params, toks, range(20))
    b = REF.logits_at(config, params, toks, range(20), "fp8")
    assert np.abs(a - b).max() > 1e-2


def test_bfloat16_reading_lies_between_float32_and_fp8():
    """Operands rounded to bfloat16 move the logits, by less than float8
    moves them; a precision the reference does not know is refused."""
    config = _tiny(2, ("sliding_attention", "full_attention"))
    params = REF.make_params(config, 5)
    toks = np.arange(20) % 32
    a = REF.logits_at(config, params, toks, range(20))
    bf = np.abs(a - REF.logits_at(config, params, toks, range(20),
                                  "bfloat16")).max()
    f8 = np.abs(a - REF.logits_at(config, params, toks, range(20),
                                  "fp8")).max()
    assert 0 < bf < f8 / 4
    with pytest.raises(KeyError):
        REF.logits_at(config, params, toks, range(20), "float16")


def test_yarn_frequencies_are_the_closed_form():
    rope = _config()["rope_parameters"]["full_attention"]
    inv = REF.inv_frequencies(rope, 128)
    i = np.arange(64)
    base = 5e5 ** (-2.0 * i / 128)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi))
                     / (2 * math.log(5e5)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi))
                     / (2 * math.log(5e5)))
    assert (low, high) == (18, 35)
    np.testing.assert_allclose(inv[:low + 1], base[:low + 1])
    np.testing.assert_allclose(inv[high:], base[high:] / 16)
    assert REF.rope_scale(rope) == pytest.approx(1.27725887)


def test_keys_attended_are_capped_by_the_window():
    config = _config()                    # 6 sliding of 1024, 2 full
    assert REF.keys_attended(config, 0) == 8
    assert REF.keys_attended(config, 3000) == 6 * 1024 + 2 * 3001
    for start, end in ((0, 5), (0, 3000), (1000, 1030), (2000, 3100)):
        assert REF.keys_attended_span(config, start, end) == sum(
            REF.keys_attended(config, p) for p in range(start, end))
    assert REF.keys_attended_span(config, 7, 7) == 0


def test_expert_counts_at_published_widths():
    config = _config()
    assert REF.expert_flops(config, 1) == 6 * 2304 * 896
    assert REF.expert_bytes(config, 64) == 64 * 3 * 2304 * 896 * 2
    assert REF.router_flops(config, 1) == 2 * 2304 * 64 * 8
    assert REF.router_bytes(config) == 2304 * 64 * 8 * 2
    # attention projections and router of one token at position 0
    per_layer = 2 * 2304 * (32 * 128 + 2 * 4 * 128) + 2 * 32 * 128 * 2304
    assert REF.non_expert_flops(config, 1, 0, 0) == \
        8 * per_layer + 2 * 2304 * 64 * 8


# ------------------------------------------------------------- the trace

@pytest.mark.parametrize("tf_op,scope", [
    (DEC + "moe_experts/dot_general", "moe_experts"),
    (DEC + "moe_route/sort;" + DEC + "attn/add", "moe_route"),
    (PRE + "moe_combine/moe_experts/x", "moe_experts"),
    (DEC + "mul", None),
    ("jit(serve_step)/attn/gather", None),
])
def test_innermost_moe_scope(tf_op, scope):
    assert moe_scopes.moe_scope(tf_op) == scope


@pytest.mark.parametrize("name,tf_op,scope", [
    ("ragged-dot-none.2", "ragged-dot-none", "moe_experts"),
    ("ragged-dot-metadata", "", "moe_experts"),
    ("fusion.9", PRE + "moe_route/gather", "moe_route"),
    ("fusion.9", "jit(prefill_step)/attn/dot", None),
])
def test_ragged_dot_ops_are_the_experts(name, tf_op, scope):
    assert moe_scopes.op_scope(name, tf_op) == scope


def _planes():
    """Two decode executions (0.6 ms of MoE each), one prefill (1.2 ms),
    one program without scopes; times in ns, window 0-10 ms."""
    ms = 1_000_000
    mods = [("jit_serve_step(1)", 1 * ms, 2 * ms),
            ("jit_prefill_step(2)", 3 * ms, 5 * ms),
            ("jit_serve_step(1)", 6 * ms, 7 * ms),
            ("jit__scatter_delta(3)", 8 * ms, 8.5 * ms)]
    ops = []
    for t0 in (1 * ms, 6 * ms):
        ops += [("while.1", t0, t0 + ms, "jit(serve_step)/while"),
                ("fusion.1", t0, t0 + 0.4 * ms, DEC + "moe_experts/dot"),
                ("fusion.2", t0 + 0.4 * ms, t0 + 0.5 * ms,
                 DEC + "moe_route/sort"),
                ("fusion.3", t0 + 0.5 * ms, t0 + 0.6 * ms,
                 DEC + "moe_combine/dot"),
                ("fusion.4", t0 + 0.6 * ms, t0 + ms,
                 "jit(serve_step)/while/body/attn/dot")]
    # the TPU compiler's grouped matmul ops carry no name stack
    ops += [("ragged-dot-none", 3 * ms, 3.6 * ms, "ragged-dot-none"),
            ("ragged-dot-none.1", 3.6 * ms, 4 * ms, ""),
            ("fusion.6", 4 * ms, 4.2 * ms, PRE + "moe_route/gather"),
            ("fusion.7", 4.2 * ms, 5 * ms, "jit(prefill_step)/attn/dot"),
            ("fusion.8", 8 * ms, 8.5 * ms, "jit(_scatter_delta)/scatter")]
    return {"devices": {"/device:TPU:0": {"modules": mods, "ops": ops}},
            "host": [(trace.WINDOW_SPAN, 0.0, 10 * ms)]}


@pytest.fixture
def planes(monkeypatch):
    p = _planes()
    monkeypatch.setattr(scopes, "read_planes", lambda path: p)
    return p


def test_reduce_sums_moe_self_time_by_program(planes):
    red = moe_scopes.reduce("unused")
    assert (red["decode_n"], red["prefill_n"]) == (2, 1)
    assert red["decode_s"] == pytest.approx(
        {"moe_experts": 0.8e-3, "moe_route": 0.2e-3, "moe_combine": 0.2e-3})
    assert red["prefill_s"] == pytest.approx(
        {"moe_experts": 1e-3, "moe_route": 0.2e-3})


def _run(tmp_path, steps, recs=(), window=(0.0, 10.0),
         trace_window=(2.0, 3.0)):
    tdir = tmp_path / ".bench_out" / "trace" / CELL / "plugins"
    tdir.mkdir(parents=True)
    (tdir / "host.xplane.pb").write_bytes(b"")
    cell = spec.load_cell(CELL)
    cell.search = [str(tmp_path / "bench")] + list(SEARCH)
    return types.SimpleNamespace(
        cell=cell, trace={}, window=window, trace_window=trace_window,
        peaks=PEAKS, chips=1, dims=roofline.Dims.of(cell.config),
        stats={"moe": {"steps": list(steps)}, "per_request": {}},
        client=types.SimpleNamespace(recs={r.rid: r for r in recs}))


def _read(run, name):
    return spec.load_module(SEARCH, "metrics", name).read(run)


def test_moe_time_readers(tmp_path, planes):
    rec = types.SimpleNamespace(rid=0, prompt=np.zeros(2048), tokens=[1, 2],
                                times=[2.5, 2.6])
    run = _run(tmp_path, [], recs=[rec])
    assert _read(run, "moe.decode_ms") == pytest.approx(0.6)
    # 1.2 ms of prefill MoE over the 2048 prompt tokens of the request
    # whose first token came in the traced window
    assert _read(run, "moe.prefill_ms_per_ktok") == pytest.approx(
        1.2 / 2.048)


def test_roofline_counts_the_experts_hit(tmp_path, planes):
    config = _config()
    steps = [(2.1, "prefill", 2048 * 8 * 8, 8 * 64),
             (2.2, "decode", 16 * 8 * 8, 8 * 55),
             (2.3, "decode", 16 * 8 * 8, 8 * 55),
             (5.0, "decode", 16 * 8 * 8, 8 * 64)]      # outside the trace
    run = _run(tmp_path, steps)
    least = 0.0
    for _, _, pairs, hit in steps[:3]:
        flops = REF.expert_flops(config, pairs) + REF.router_flops(
            config, pairs / 8)
        nbytes = REF.expert_bytes(config, hit) + REF.router_bytes(config)
        least += max(flops / PEAKS["bf16_flops_per_s"],
                     nbytes / PEAKS["hbm_bytes_per_s"])
    got = _read(run, "moe.roofline")
    assert got == pytest.approx(100 * least / 2.4e-3)
    # a decode step reading 55 experts' weights: bound by the bytes
    assert REF.expert_bytes(config, 8 * 55) / PEAKS["hbm_bytes_per_s"] > \
        REF.expert_flops(config, 1024) / PEAKS["bf16_flops_per_s"]


def test_step_mfu_counts_pairs_attention_and_head(tmp_path, planes):
    config = _config()
    rec = types.SimpleNamespace(rid=0, prompt=np.zeros(1536),
                                tokens=[1, 2, 3], times=[1.0, 1.1, 20.0])
    steps = [(1.0, "prefill", 1536 * 64, 512), (1.1, "decode", 64, 64),
             (20.0, "decode", 64, 64)]                  # after the window
    run = _run(tmp_path, steps, recs=[rec])
    flops = (REF.non_expert_flops(
        config, 1536 + 1, REF.keys_attended_span(config, 0, 1536)
        + REF.keys_attended(config, 1536), 2)
        + REF.expert_flops(config, 1536 * 64 + 64))
    assert _read(run, "moe.step_mfu") == pytest.approx(
        100 * flops / (10.0 * PEAKS["bf16_flops_per_s"]))


def test_readers_read_none_without_scopes_or_counters(tmp_path,
                                                      monkeypatch):
    # the parent's programs: no moe_* scope, no counter in stats()
    p = _planes()
    for dev in p["devices"].values():
        dev["ops"] = [(n, s, e, t.replace("moe_", "x_"))
                      for n, s, e, t in dev["ops"]]
    monkeypatch.setattr(scopes, "read_planes", lambda path: p)
    run = _run(tmp_path, [])
    run.stats = {"per_request": {}}
    for name in ("moe.decode_ms", "moe.prefill_ms_per_ktok",
                 "moe.roofline", "moe.step_mfu"):
        assert _read(run, name) is None, name
    run.trace = None
    assert _read(run, "moe.decode_ms") is None


def test_engine_counts_read_by_the_readers():
    """The readers' field: ``stats()["moe"]["steps"]`` holds (time, kind,
    routed pairs, experts hit) per execution, as the engine fetches it."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models import init_params, model_dims
    from repro.serve import Engine, EngineConfig, Request
    cfg = reduced(get_config("mellum2-12b-a2.5b"))
    params = init_params(jax.random.PRNGKey(0), cfg, model_dims(cfg))
    eng = Engine(cfg, params, EngineConfig(max_batch=2, max_seq_len=64,
                                           dtype=jnp.float32))
    eng.submit(Request(seq_id=0, prompt=np.arange(16) % cfg.vocab_size,
                       max_new_tokens=3))
    while eng.has_unfinished():
        eng.poll()
    moe = eng.stats()["moe"]
    kinds = [k for _, k, _, _ in moe["steps"]]
    assert kinds == ["prefill", "decode", "decode"]
    k, n = cfg.moe_top_k, cfg.num_layers
    assert [p for _, _, p, _ in moe["steps"]] == [16 * k * n, k * n, k * n]
    assert all(h == k * n for _, kind, _, h in moe["steps"]
               if kind == "decode")
    assert moe["decode"] == {"executions": 2, "routed_pairs": 2 * k * n,
                             "experts_hit": 2 * k * n}
