"""Plain reference of a dense GQA decoder (Llama / Granite-code / Qwen2
family), and the weights the benchmark serves, both made here from the seed.

Architecture, as the published configs describe it: token embedding; per
layer a pre-norm RMSNorm, attention with ``num_attention_heads`` query
heads sharing ``num_key_value_heads`` key/value heads of ``head_dim``,
rotary position embedding on queries and keys (``rope_theta``, the
rotate-half form), optional bias on the q/k/v projections
(``attention_bias``), causal softmax; a second RMSNorm and a SwiGLU MLP
(``silu(x Wg) * (x Wu)) Wd``); residual adds; a final RMSNorm and the LM
head (``lm_head``, or the embedding when ``tie_word_embeddings``).

The reference runs in float32 with every matrix product at ``HIGHEST``
precision, one layer at a time and attention in blocks of queries, so it
fits beside the weights on one chip.  ``precision="fp8"`` is the control:
the same forward with every matrix-product operand rounded to float8 e4m3
(per-row or per-column scales), the step below the bfloat16 the
configuration states.

The weight tree is laid out as the engine reads it (``embed.table``,
``layers.attn.q.w`` ...); it is made in one jitted call on the device.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
Q_BLOCK = 512          # query rows per attention block
PAD = 1024             # sequences are padded to a multiple of this


def _dims(c):
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    return (d, h, c["num_key_value_heads"], c.get("head_dim") or d // h,
            c["intermediate_size"], c["num_hidden_layers"], c["vocab_size"])


def seed_key(seed: int):
    """A PRNG key from any non-negative integer up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_params(config: dict, seed: int):
    """Random weights from ``seed``, in ``torch_dtype``, on the device."""
    d, h, kv, hd, ff, n_layers, vocab = _dims(config)
    dt = jnp.dtype(config["torch_dtype"])
    bias = bool(config.get("attention_bias", False))
    tied = bool(config.get("tie_word_embeddings", False))

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            return (jax.random.normal(next(ks), shape, dt)
                    * jnp.asarray(scale, dt)).astype(dt)

        def proj(d_in, d_out, with_bias=False):
            p = {"w": normal((n_layers, d_in, d_out), 1.0 / math.sqrt(d_in))}
            if with_bias:
                p["b"] = normal((n_layers, d_out), 0.02)
            return p

        def norm(shape):
            return (1.0 + normal(shape, 0.1)).astype(dt)

        params = {
            "embed": {"table": normal((vocab, d), 0.02)},
            "final_norm": norm((d,)),
            "layers": {
                "norm1": norm((n_layers, d)),
                "attn": {"q": proj(d, h * hd, bias), "k": proj(d, kv * hd, bias),
                         "v": proj(d, kv * hd, bias), "o": proj(h * hd, d)},
                "norm2": norm((n_layers, d)),
                "mlp": {"gate": proj(d, ff), "up": proj(d, ff),
                        "down": proj(ff, d)},
            },
        }
        if not tied:
            params["lm_head"] = {"table": normal((vocab, d), 0.02)}
        return params

    return jax.jit(build)(seed_key(seed))


# ---------------------------------------------------------------- reference

def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(x, w, fp8):
    """x (..., din) @ w (din, dout) in float32 at HIGHEST precision."""
    if fp8:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, pos, theta):
    """x (S, heads, hd), rotate-half form."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.partial(jax.jit, static_argnames=("dims", "eps", "theta", "fp8"))
def _layer(x, w, *, dims, eps, theta, fp8):
    """One decoder layer over x (S, d) float32; w holds bf16 leaves."""
    d, h, kv, hd, ff = dims
    S = x.shape[0]
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(S)
    a = w["attn"]
    hn = _rms(x, f(w["norm1"]), eps)
    q = _mm(hn, f(a["q"]["w"]), fp8)
    k = _mm(hn, f(a["k"]["w"]), fp8)
    v = _mm(hn, f(a["v"]["w"]), fp8)
    if "b" in a["q"]:
        q, k, v = q + f(a["q"]["b"]), k + f(a["k"]["b"]), v + f(a["v"]["b"])
    q = _rope(q.reshape(S, h, hd), pos, theta)
    k = _rope(k.reshape(S, kv, hd), pos, theta)
    v = v.reshape(S, kv, hd)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    g = h // kv
    kk = jnp.repeat(k, g, axis=1)                    # (S, h, hd)
    vv = jnp.repeat(v, g, axis=1)

    def block(qb, start):
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision=HI) / math.sqrt(hd)
        qpos = start + jnp.arange(qb.shape[0])
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, vv, precision=HI)

    nb = S // Q_BLOCK
    o = jax.lax.map(lambda i: block(
        jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK), i * Q_BLOCK),
        jnp.arange(nb))
    o = o.reshape(S, h * hd)
    x = x + _mm(o, f(a["o"]["w"]), fp8)
    hn = _rms(x, f(w["norm2"]), eps)
    m = w["mlp"]
    y = jax.nn.silu(_mm(hn, f(m["gate"]["w"]), fp8)) * _mm(
        hn, f(m["up"]["w"]), fp8)
    return x + _mm(y, f(m["down"]["w"]), fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head(x, norm, table, *, eps, fp8):
    hn = _rms(x, norm.astype(jnp.float32), eps)
    t = table.astype(jnp.float32)
    if fp8:
        return jnp.matmul(_q8(hn, -1), _q8(t, -1).T, precision=HI)
    return jnp.matmul(hn, t.T, precision=HI)


def logits_at(config: dict, params, tokens, at, precision: str = "float32"):
    """Logits (len(at), vocab) float32 of the reference forward over
    ``tokens`` at positions ``at`` (each position predicts the next)."""
    d, h, kv, hd, ff, n_layers, vocab = _dims(config)
    fp8 = {"float32": False, "fp8": True}[precision]
    toks = np.asarray(tokens, np.int32)
    S = -(-len(toks) // PAD) * PAD
    padded = np.zeros(S, np.int32)
    padded[:len(toks)] = toks
    x = jnp.take(params["embed"]["table"], jnp.asarray(padded),
                 axis=0).astype(jnp.float32)
    eps = float(config["rms_norm_eps"])
    theta = float(config["rope_theta"])
    for l in range(n_layers):
        w = jax.tree.map(lambda a, l=l: a[l], params["layers"])
        x = _layer(x, w, dims=(d, h, kv, hd, ff), eps=eps, theta=theta,
                   fp8=fp8)
    head = params.get("lm_head", params["embed"])["table"]
    xs = jnp.take(x, jnp.asarray(np.asarray(at, np.int32)), axis=0)
    return np.asarray(_head(xs, params["final_norm"], head, eps=eps,
                            fp8=fp8))
