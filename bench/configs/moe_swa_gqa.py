"""Plain reference of a sparse-expert decoder whose attention layers mix a
sliding window with full attention (Mellum2 family), the weights the
benchmark serves, both made here from the seed, and the operation and byte
counts of its expert layers.

Architecture, as the published config describes it: token embedding; per
layer a pre-norm RMSNorm, attention with ``num_attention_heads`` query
heads sharing ``num_key_value_heads`` key/value heads of ``head_dim``, no
projection bias, causal softmax; on the layers ``layer_types`` names
``sliding_attention`` a query sees only the keys with ``q - k <
sliding_window`` (transformers' ``kv_idx > q_idx - sliding_window``);
rotary embedding (rotate-half form) from the layer type's
``rope_parameters``: ``default`` at ``rope_theta``, or ``yarn`` (arXiv:
2309.00071; frequencies as transformers' ``_compute_yarn_parameters``
makes them, cos and sin multiplied by ``attention_factor``); a second
RMSNorm and a routed SwiGLU FFN: softmax over the ``num_experts`` router
logits, the ``num_experts_per_tok`` largest, renormalised to sum to 1
(``norm_topk_prob``), each token's output the gate-weighted sum of its
experts' ``silu(x Wg) * (x Wu)) Wd`` of width ``moe_intermediate_size``,
for every token whatever the load (no capacity, nothing dropped); no
shared expert; residual adds; a final RMSNorm and an untied LM head.

Departures from the published model, none of which changes a width:
the weights are random; no per-head q/k norm (``config.json`` declares
none); no multi-token-prediction head (``config.json`` declares none and
serving reads only the next-token logits).

The reference runs in float32 with every matrix product at ``HIGHEST``
precision, one layer at a time, attention in blocks of queries and the
experts one at a time over all tokens (unselected experts weigh 0), so
it fits beside the weights on one chip.  ``precision="fp8"`` is the
control: the same forward with every matrix-product operand (router
included) rounded to float8 e4m3 with per-row or per-column scales, the
step below the bfloat16 the configuration states.  ``precision=
"bfloat16"`` rounds the same operands to bfloat16 instead: what a plain
forward at the configuration's own precision computes, so its gaps are
those that rounding alone gives where the router's softmax scores nearly
tie.

The weight tree is laid out as the engine reads it (``layers.attn.q.w``,
``layers.moe.router.w``, ``layers.moe.gate`` (L, E, d, f) ...).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.configs.dense_gqa import HI, PAD, Q_BLOCK, _q8, _rms, seed_key

SLIDING = "sliding_attention"
DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def _dims(c):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["num_hidden_layers"], c["vocab_size"], c["num_experts"],
            c["num_experts_per_tok"], c["moe_intermediate_size"])


def make_params(config: dict, seed: int):
    """Random weights from ``seed``, in ``torch_dtype``, on the device."""
    d, h, kv, hd, n_layers, vocab, E, _, f = _dims(config)
    dt = jnp.dtype(config["torch_dtype"])

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, dt)
                    * jnp.asarray(scale, dt)).astype(dt)

        def proj(d_in, d_out):
            return {"w": normal((n_layers, d_in, d_out),
                                1.0 / math.sqrt(d_in))}

        def norm(shape):
            return (1.0 + normal(shape, 0.1)).astype(dt)

        return {
            "embed": {"table": normal((vocab, d), 0.02)},
            "final_norm": norm((d,)),
            "lm_head": {"table": normal((vocab, d), 0.02)},
            "layers": {
                "norm1": norm((n_layers, d)),
                "attn": {"q": proj(d, h * hd), "k": proj(d, kv * hd),
                         "v": proj(d, kv * hd), "o": proj(h * hd, d)},
                "norm2": norm((n_layers, d)),
                "moe": {
                    "router": proj(d, E),
                    "gate": normal((n_layers, E, d, f), 1.0 / math.sqrt(d)),
                    "up": normal((n_layers, E, d, f), 1.0 / math.sqrt(d)),
                    "down": normal((n_layers, E, f, d), 1.0 / math.sqrt(f)),
                },
            },
        }

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------- rotary

def inv_frequencies(rope: dict, hd: int) -> np.ndarray:
    """(hd / 2,) float64 inverse frequencies of one ``rope_parameters``
    entry, and nothing else: the closed forms, written out."""
    base = float(rope["rope_theta"])
    i = np.arange(hd // 2, dtype=np.float64)
    inv = base ** (-2.0 * i / hd)
    if rope.get("rope_type", "default") == "default":
        return inv
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):      # the dimension that turns ``rotations``
        return hd * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim_of(rope.get("beta_slow", 1))), hd - 1)
    if low == high:
        high += 0.001
    interp_share = np.clip((i - low) / (high - low), 0.0, 1.0)
    return inv / factor * interp_share + inv * (1.0 - interp_share)


def rope_scale(rope: dict) -> float:
    if rope.get("rope_type", "default") == "default":
        return 1.0
    af = rope.get("attention_factor")
    return float(af) if af is not None else (
        0.1 * math.log(float(rope["factor"])) + 1.0)


def _rope(x, pos, inv, scale):
    """x (S, heads, hd), rotate-half form; cos and sin times ``scale``."""
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


# ------------------------------------------------------------- the layer

PRECISIONS = ("float32", "bfloat16", "fp8")


def _round(x, axis, prec):
    """A matrix-product operand as ``prec`` holds it (float32 values);
    float8 takes one scale per slice along ``axis``."""
    if prec == "fp8":
        return _q8(x, axis)
    if prec == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    return x


def _mm(x, w, prec):
    return jnp.matmul(_round(x, -1, prec), _round(w, 0, prec), precision=HI)


@functools.partial(jax.jit, static_argnames=("eps", "prec"))
def _head(x, norm, table, *, eps, prec):
    hn = _rms(x, norm.astype(jnp.float32), eps)
    t = table.astype(jnp.float32)
    return jnp.matmul(_round(hn, -1, prec), _round(t, -1, prec).T,
                      precision=HI)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "prec"))
def _layer(x, w, inv, scale, window, *, dims, eps, prec):
    """One decoder layer over x (S, d) float32; w holds bf16 leaves."""
    d, h, kv, hd, top_k, norm_topk = dims
    S = x.shape[0]
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(S)
    a = w["attn"]
    hn = _rms(x, f(w["norm1"]), eps)
    q = _rope(_mm(hn, f(a["q"]["w"]), prec).reshape(S, h, hd), pos, inv,
              scale)
    k = _rope(_mm(hn, f(a["k"]["w"]), prec).reshape(S, kv, hd), pos, inv,
              scale)
    v = _mm(hn, f(a["v"]["w"]), prec).reshape(S, kv, hd)
    q, k, v = _round(q, -1, prec), _round(k, -1, prec), _round(v, -1, prec)
    g = h // kv
    kk = jnp.repeat(k, g, axis=1)
    vv = jnp.repeat(v, g, axis=1)

    def block(qb, start):
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / math.sqrt(hd)
        qpos = start + jnp.arange(qb.shape[0])
        dist = qpos[:, None] - pos[None, :]
        keep = (dist >= 0) & (dist < window)
        s = jnp.where(keep[None], s, -1e30)
        p = _round(jax.nn.softmax(s, axis=-1), -1, prec)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    o = jax.lax.map(lambda i: block(
        jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK), i * Q_BLOCK),
        jnp.arange(S // Q_BLOCK))
    x = x + _mm(o.reshape(S, h * hd), f(a["o"]["w"]), prec)

    hn = _rms(x, f(w["norm2"]), eps)
    m = w["moe"]
    probs = jax.nn.softmax(_mm(hn, f(m["router"]["w"]), prec), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    if norm_topk:
        top = top / top.sum(-1, keepdims=True)
    E = probs.shape[-1]
    gates = jnp.zeros_like(probs).at[jnp.arange(S)[:, None], idx].set(top)

    def expert(acc, e):
        y = jax.nn.silu(_mm(hn, f(m["gate"][e]), prec)) * _mm(
            hn, f(m["up"][e]), prec)
        return acc + gates[:, e, None] * _mm(y, f(m["down"][e]), prec), None

    ffn, _ = jax.lax.scan(expert, jnp.zeros_like(x), jnp.arange(E))
    return x + ffn


def layer_tables(config: dict):
    """Per layer: (inverse frequencies, cos/sin scale, window)."""
    hd = _dims(config)[3]
    out = []
    for l in range(config["num_hidden_layers"]):
        kind = config["layer_types"][l]
        rope = config["rope_parameters"][kind]
        window = (config["sliding_window"] if kind == SLIDING
                  else 1 << 30)
        out.append((inv_frequencies(rope, hd).astype(np.float32),
                    np.float32(rope_scale(rope)), np.int32(window)))
    return out


def logits_at(config: dict, params, tokens, at, precision: str = "float32"):
    """Logits (len(at), vocab) float32 of the reference forward over
    ``tokens`` at positions ``at`` (each position predicts the next)."""
    d, h, kv, hd, n_layers, vocab, E, top_k, f = _dims(config)
    if precision not in PRECISIONS:
        raise KeyError(precision)
    toks = np.asarray(tokens, np.int32)
    S = -(-len(toks) // PAD) * PAD
    padded = np.zeros(S, np.int32)
    padded[:len(toks)] = toks
    x = jnp.take(params["embed"]["table"], jnp.asarray(padded),
                 axis=0).astype(jnp.float32)
    eps = float(config["rms_norm_eps"])
    dims = (d, h, kv, hd, top_k, bool(config.get("norm_topk_prob", True)))
    for l, (inv, scale, window) in enumerate(layer_tables(config)):
        w = jax.tree.map(lambda a, l=l: a[l], params["layers"])
        x = _layer(x, w, jnp.asarray(inv), scale, window, dims=dims,
                   eps=eps, prec=precision)
    xs = jnp.take(x, jnp.asarray(np.asarray(at, np.int32)), axis=0)
    return np.asarray(_head(xs, params["final_norm"],
                            params["lm_head"]["table"], eps=eps,
                            prec=precision))


# ------------------------------------------- operations and bytes counted

def expert_flops(config: dict, pairs: float) -> float:
    """FLOPs of ``pairs`` routed (token, expert) pairs: three matrices."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return 6.0 * d * f * pairs


def expert_bytes(config: dict, hit: float) -> float:
    """Weight bytes of ``hit`` experts (a layer's expert read once)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    return 3.0 * d * f * hit * DTYPE_BYTES[config["torch_dtype"]]


def router_flops(config: dict, tokens: float) -> float:
    """Router FLOPs of ``tokens`` forwarded, over every layer."""
    return (2.0 * config["hidden_size"] * config["num_experts"]
            * config["num_hidden_layers"] * tokens)


def router_bytes(config: dict) -> float:
    """Router weight bytes of one execution, over every layer."""
    return (config["hidden_size"] * config["num_experts"]
            * config["num_hidden_layers"]
            * DTYPE_BYTES[config["torch_dtype"]])


def keys_attended(config: dict, pos: int) -> int:
    """Keys the token at ``pos`` attends, summed over the layers (its
    window caps a sliding layer's)."""
    n = config["num_hidden_layers"]
    types = config["layer_types"][:n]
    w = config["sliding_window"]
    return sum(min(pos + 1, w) if t == SLIDING else pos + 1 for t in types)


def keys_attended_span(config: dict, start: int, end: int) -> int:
    """``keys_attended`` summed over positions [start, end)."""
    if end <= start:
        return 0
    n = config["num_hidden_layers"]
    types = config["layer_types"][:n]
    w = config["sliding_window"]
    full = (end * (end + 1) - start * (start + 1)) // 2

    def capped(a, b):           # sum of min(p + 1, w) over p in [a, b)
        lo = min(max(a, 0), w)
        hi = min(b, w)
        tri = (hi * (hi + 1) - lo * (lo + 1)) // 2 if hi > lo else 0
        return tri + w * max(0, b - max(a, w))
    return sum(capped(start, end) if t == SLIDING else full for t in types)


def non_expert_flops(config: dict, tokens: int, keys: int,
                     logits: int) -> float:
    """Model FLOPs outside the experts: attention projections and router
    of ``tokens`` forwarded, scores and weighted sum over ``keys``
    attended (summed over layers), the LM head for ``logits`` rows."""
    d = config["hidden_size"]
    h = config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    hd = config.get("head_dim") or d // h
    n = config["num_hidden_layers"]
    proj = 2.0 * d * (h * hd + 2 * kv * hd) + 2.0 * h * hd * d
    return (proj * n * tokens + router_flops(config, tokens)
            + 4.0 * h * hd * keys + 2.0 * d * config["vocab_size"] * logits)
