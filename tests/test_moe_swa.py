"""Mellum2-style serving: sparse experts routed without dropping, sliding-
window and full attention layers mixed, YaRN on the full layers.

The engine (``make_engine``'s path: scheduler, hybrid KV pool, prefix cache,
chunked and prefix-read prefill, paged decode) is compared with the plain
reference ``bench/configs/moe_swa_gqa.py`` on seeded random weights at a
small size of the same structure: one 4-layer period (3 sliding, 1 full),
window 16, blocks of 8, 8 experts top-2, YaRN on the full layer.  Both run
in float32, so their logits agree to rounding; the reference's own float8
control must miss the same tolerance.
"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench.configs import moe_swa_gqa as ref  # noqa: E402
from repro.configs import get_config, reduced  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import moe as M  # noqa: E402
from repro.serve import (Engine, EngineConfig, MemorySink,  # noqa: E402
                         MetricsLogger, Request)

ARCH = "mellum2-12b-a2.5b"
# float32 engine against the float32 HIGHEST reference: the logits (about
# unit scale) differ only by summation order, about 1e-6; float8 operands
# move them by about 0.4, so 1e-3 lies two orders from either
LOGIT_TOL = 1e-3
TYPE_NAMES = {"sliding": "sliding_attention", "full": "full_attention"}


def small_arch():
    return dataclasses.replace(reduced(get_config(ARCH)), num_kv_heads=2)


def small_config(cfg):
    """The reference's config dict for ``cfg``, keyed as config.json."""
    def rope(t):
        r = cfg.rope_of(t)
        if r.yarn_factor <= 0:
            return {"rope_type": "default", "rope_theta": r.theta}
        return {"rope_type": "yarn", "rope_theta": r.theta,
                "factor": r.yarn_factor,
                "original_max_position_embeddings": r.yarn_original_max_pos,
                "beta_fast": r.yarn_beta_fast, "beta_slow": r.yarn_beta_slow,
                "attention_factor": r.attention_factor}

    return {
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
        "num_hidden_layers": cfg.num_layers, "vocab_size": cfg.vocab_size,
        "num_experts": cfg.moe_num_experts,
        "num_experts_per_tok": cfg.moe_top_k,
        "moe_intermediate_size": cfg.moe_d_ff, "norm_topk_prob": True,
        "layer_types": [TYPE_NAMES[cfg.layer_type(l)]
                        for l in range(cfg.num_layers)],
        "sliding_window": cfg.sliding_window,
        "rope_parameters": {TYPE_NAMES[t]: rope(t)
                            for t in ("sliding", "full")},
        "rms_norm_eps": cfg.norm_eps, "torch_dtype": "float32",
    }


@pytest.fixture(scope="module")
def model():
    cfg = small_arch()
    config = small_config(cfg)
    return cfg, config, ref.make_params(config, 1234)


def _record_logits(eng):
    """Wrap the engine's step programs: every (position, logits row) of
    slot 0 they compute, in order."""
    rows = []

    def prefill(fn, ctx_arg):
        def run(*a, **k):
            out = fn(*a, **k)
            rows.append((int(a[ctx_arg][0]) - 1, np.asarray(out[0][0])))
            return out
        return run

    def decode(*a, **k):
        pos = int(np.asarray(a[1]["ctx_len"])[0])
        out = serve(*a, **k)
        if bool(np.asarray(a[3])[0]):
            rows.append((pos, np.asarray(out[0][0])))
        return out

    serve = eng._serve_step
    eng._prefill_step = prefill(eng._prefill_step, 5)
    eng._prefix_step = prefill(eng._prefix_step, 6)
    eng._serve_step = decode
    return rows


def _serve(eng, prompt, max_new):
    rows = _record_logits(eng)
    r = Request(seq_id=len(eng.finished) + len(eng.requests), prompt=prompt,
                max_new_tokens=max_new)
    eng.submit(r)
    while eng.has_unfinished():
        eng.poll()
    return np.concatenate([prompt, r.generated]), rows


def _compare(config, params, seq, rows):
    at = [p for p, _ in rows]
    got = np.stack([l[:config["vocab_size"]] for _, l in rows])
    want = ref.logits_at(config, params, seq, at, "float32")
    fp8 = ref.logits_at(config, params, seq, at, "fp8")
    return np.abs(got - want).max(), np.abs(fp8 - want).max()


def _engine(cfg, params, budget):
    return Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=96, prefill_budget=budget,
        dtype=jnp.float32, auto_release=True))


@pytest.mark.parametrize("case", ["chunked_prefill", "decode_past_window",
                                  "prefix_hit_across_window"])
def test_engine_matches_reference(model, case):
    cfg, config, params = model
    rng = np.random.RandomState(7)
    bs = cfg.kv_block_size
    if case == "chunked_prefill":
        # 40 prompt tokens in chunks of 16: a recompute chunk, then two
        # prefix-KV chunks whose window reaches back into earlier chunks
        eng = _engine(cfg, params, 2 * bs)
        seq, rows = _serve(eng, rng.randint(0, cfg.vocab_size, 5 * bs), 4)
        assert {c.path for c in eng.admission_log} == {"recompute",
                                                       "prefix_kv"}
    elif case == "decode_past_window":
        eng = _engine(cfg, params, None)
        seq, rows = _serve(eng, rng.randint(0, cfg.vocab_size, bs), 40)
        assert max(p for p, _ in rows) > 2 * cfg.sliding_window
    else:
        eng = _engine(cfg, params, None)
        first = rng.randint(0, cfg.vocab_size, 6 * bs)
        _serve(eng, first, 2)
        # 4 cached blocks attach; the tail's window (16) straddles their
        # last two and its own first block
        prompt = np.concatenate([first[:4 * bs],
                                 rng.randint(0, cfg.vocab_size, 2 * bs)])
        seq, rows = _serve(eng, prompt, 6)
        assert eng.stats()["prefix_cache"]["hits"] >= 1
        assert any(c.path == "prefix_kv" and c.start == 4 * bs
                   for c in eng.admission_log)
    engine_gap, fp8_gap = _compare(config, params, seq, rows)
    assert engine_gap < LOGIT_TOL, engine_gap
    assert fp8_gap > LOGIT_TOL, fp8_gap
    assert eng.stats()["moe"]["prefill"]["executions"] >= 1


def test_step_events_carry_the_moe_counters(model):
    """The per-step [decode, prefill] deltas of routed pairs and experts
    hit in the logger's step events sum to ``stats()["moe"]``."""
    cfg, _, params = model
    mem = MemorySink()
    eng = Engine(cfg, params, EngineConfig(
        max_batch=2, max_seq_len=96, prefill_budget=2 * cfg.kv_block_size,
        dtype=jnp.float32, auto_release=True, metrics=MetricsLogger([mem])))
    rng = np.random.RandomState(3)
    for i, n in enumerate((3, 5)):
        eng.submit(Request(seq_id=i, max_new_tokens=4, prompt=rng.randint(
            0, cfg.vocab_size, n * cfg.kv_block_size)))
    while eng.has_unfinished():
        eng.poll()
    steps = [e for e in mem.events if e["kind"] == "step"]
    moe = eng.stats()["moe"]
    for key, field in (("moe_routed_pairs", "routed_pairs"),
                       ("moe_experts_hit", "experts_hit")):
        for j, kind in enumerate(("decode", "prefill")):
            assert sum(e[key][j] for e in steps) == moe[kind][field] > 0
    # 8 prompt tokens a block, every one routed to top_k experts per layer
    per_token = cfg.moe_top_k * cfg.num_layers
    assert moe["prefill"]["routed_pairs"] == 8 * cfg.kv_block_size * per_token
    assert moe["decode"]["routed_pairs"] == 2 * 3 * per_token


def test_dropless_equals_per_token_sum_when_all_tokens_pick_one_expert():
    """Every token routes to expert 0 (and 1): capacity 1.25 would keep
    a quarter of them; the drop-free FFN keeps all."""
    D, F, E, T, k = 16, 8, 8, 32, 2
    p = M.init_moe(jax.random.PRNGKey(0), D, F, E)
    p["router"]["w"] = jnp.zeros((D, E)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(1), (T, D))) + 0.1
    probs = jax.nn.softmax(x @ p["router"]["w"], axis=-1)
    g, idx = jax.lax.top_k(probs, k)
    assert (idx[:, 0] == 0).all() and (idx[:, 1] == 1).all()
    g = g / g.sum(-1, keepdims=True)
    want = sum(g[:, j:j + 1] * (
        (jax.nn.silu(x @ p["gate"][e]) * (x @ p["up"][e])) @ p["down"][e])
        for j, e in enumerate((0, 1)))
    got, counts = M.moe_dropless(p, x, top_k=k)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert counts.tolist() == [T * k, 2]
    dec, dcounts = M.moe_decode(p, x, top_k=k)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert dcounts.tolist() == [T * k, 2]
    capped, aux = M.moe_layer(p, x[None], top_k=k, capacity_factor=1.25)
    assert float(aux["fraction_dropped"]) > 0.5
    assert not np.allclose(np.asarray(capped[0]), np.asarray(want),
                           atol=1e-3)


def test_dropless_leaves_padding_out():
    p = M.init_moe(jax.random.PRNGKey(2), 16, 8, 4)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 6, 16))
    valid = jnp.arange(6)[None, :] < jnp.array([6, 2])[:, None]
    out, counts = M.moe_dropless(p, x, top_k=2, valid=valid)
    full, _ = M.moe_dropless(p, x, top_k=2)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(full[0]),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(out[1, :2]),
                               np.asarray(full[1, :2]), rtol=1e-6, atol=1e-6)
    assert not np.asarray(out[1, 2:]).any()
    assert int(counts[0]) == 8 * 2


def test_grouped_matmul_rowwise_equals_ragged_dot():
    """The CPU branch of the experts' grouped matmul computes what the
    TPU's ``lax.ragged_dot`` computes; rows past the groups are no
    expert's."""
    E, K, N = 4, 8, 6
    gs = jnp.array([3, 0, 5, 1], jnp.int32)
    xs = jax.random.normal(jax.random.PRNGKey(4), (11, K))
    w = jax.random.normal(jax.random.PRNGKey(5), (E, K, N))
    got = M._expert_matmul(xs, w, gs)
    want = jax.lax.ragged_dot(xs, w, gs)
    np.testing.assert_allclose(np.asarray(got[:9]), np.asarray(want[:9]),
                               rtol=1e-5, atol=1e-5)


def test_yarn_table_equals_closed_form():
    """Mellum2's full-layer table: head_dim 128, theta 5e5, factor 16 over
    8192 positions, beta 32 / 1.  The ramp runs over dimensions
    floor(128 ln(8192 / (64 pi)) / (2 ln 5e5)) = 18 to
    ceil(128 ln(8192 / (2 pi)) / (2 ln 5e5)) = 35."""
    rope = get_config(ARCH).rope_of("full")
    inv, scale = L.rope_table(128, rope)
    i = np.arange(64, dtype=np.float64)
    base = 5e5 ** (-2.0 * i / 128)
    ramp = np.clip((i - 18) / (35 - 18), 0.0, 1.0)
    want = base * (1.0 - ramp) + base / 16.0 * ramp
    np.testing.assert_allclose(inv, want, rtol=2e-6)
    assert scale == pytest.approx(0.1 * math.log(16) + 1.0)
    dflt, one = L.rope_table(128, get_config(ARCH).rope_of("sliding"))
    np.testing.assert_allclose(dflt, base, rtol=2e-6)
    assert one == 1.0


def test_config_file_agrees_with_registry():
    """The harness reads some sizes from the file and the rest from the
    registry: every shape key must agree."""
    with open(os.path.join(ROOT, "bench", "configs",
                           "mellum2-12b-a2.5b.json")) as fh:
        f = json.load(fh)
    c = get_config(f["arch"])
    assert f["reduced"] == ["num_hidden_layers"]
    assert f["published"]["num_hidden_layers"] == c.num_layers == 28
    assert f["num_hidden_layers"] == 8 and 8 % len(c.layer_types) == 0
    assert (f["hidden_size"], f["num_attention_heads"],
            f["num_key_value_heads"], f["head_dim"], f["vocab_size"],
            f["intermediate_size"]) == (c.d_model, c.num_heads,
                                        c.num_kv_heads, c.head_dim,
                                        c.vocab_size, c.d_ff)
    assert (f["num_experts"], f["num_experts_per_tok"],
            f["moe_intermediate_size"]) == (c.moe_num_experts, c.moe_top_k,
                                            c.moe_d_ff)
    assert all(t == "sparse" for t in f["mlp_layer_types"])
    assert c.moe_every == 1 and f["norm_topk_prob"] is True
    assert f["layer_types"] == [TYPE_NAMES[c.layer_type(l)]
                                for l in range(c.num_layers)]
    assert f["sliding_window"] == c.sliding_window
    assert f["rms_norm_eps"] == c.norm_eps
    assert f["rope_theta"] == c.rope_theta
    assert not f["attention_bias"] and not c.qkv_bias
    assert not f["tie_word_embeddings"] and not c.tie_embeddings
    assert f["kv_block_size"] == c.kv_block_size
    for t in ("sliding", "full"):
        r, fr = c.rope_of(t), f["rope_parameters"][TYPE_NAMES[t]]
        assert fr["rope_theta"] == r.theta
        if fr["rope_type"] == "yarn":
            assert (fr["factor"], fr["original_max_position_embeddings"],
                    fr["beta_fast"], fr["beta_slow"],
                    fr["attention_factor"]) == (
                r.yarn_factor, r.yarn_original_max_pos, r.yarn_beta_fast,
                r.yarn_beta_slow, r.attention_factor)
        else:
            assert fr["rope_type"] == "default" and r.yarn_factor == 0


def test_windowed_model_refused_where_no_window_is_applied(model):
    cfg, _, params = model
    with pytest.raises(NotImplementedError, match="spec_decode"):
        Engine(cfg, params, EngineConfig(max_batch=1, max_seq_len=64,
                                         spec_decode="ngram"))
    with pytest.raises(NotImplementedError, match="prefix_gather"):
        Engine(cfg, params, EngineConfig(max_batch=1, max_seq_len=64,
                                         prefix_gather="paged"))
