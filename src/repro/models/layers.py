"""Dense building blocks: norms, RoPE, projections, SwiGLU MLP.

Conventions:
* params are nested dicts of arrays; per-layer stacks are built by the
  transformer builders (leading layer axis, consumed by ``lax.scan``).
* every function takes ``pins`` — a callable ``pins(name, x) -> x`` that
  applies ``with_sharding_constraint`` when a mesh is active (identity by
  default).  Names are stable contract points for dist/sharding.py.
* dtype discipline: params stored in ``param_dtype``; activations compute
  in ``dtype`` with fp32 accumulations where it matters (norm, softmax).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pins = Callable[[str, jax.Array], jax.Array]


def no_pins(name: str, x: jax.Array) -> jax.Array:
    return x


# ------------------------------------------------------------------ norms

@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
    return out.astype(dt)


def _rms_norm_fwd(x, scale, eps):
    return rms_norm(x, scale, eps), (x, scale)


def _rms_norm_bwd(eps, res, g):
    """fp32 internal math, activation-grad emitted in x.dtype: keeps the
    cross-shard dx all-reduces in bf16 (they dominated the train cells'
    collective term at 2x the bytes in fp32 — EXPERIMENTS.md §Perf)."""
    x, scale = res
    x32 = x.astype(jnp.float32)
    g32 = g.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    rstd = jax.lax.rsqrt(var + eps)
    xhat = x32 * rstd
    gs = g32 * scale.astype(jnp.float32)
    dx = rstd * (gs - xhat * jnp.mean(gs * xhat, axis=-1, keepdims=True))
    dscale = jnp.sum(g32 * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


rms_norm.defvjp(_rms_norm_fwd, _rms_norm_bwd)


def gated_rms_norm(y: jax.Array, z: jax.Array, scale: jax.Array,
                   eps: float = 1e-5) -> jax.Array:
    """Mamba2's RMSNormGated: norm(y) * silu(z)."""
    return (rms_norm(y, scale, eps)
            * jax.nn.silu(z).astype(y.dtype)).astype(y.dtype)


def init_norm(d: int, dtype=jnp.float32) -> jax.Array:
    return jnp.ones((d,), dtype)


# ------------------------------------------------------------------- RoPE

def rope_frequencies(head_dim: int, theta: float, dtype=jnp.float32):
    exponents = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return (1.0 / (theta ** exponents)).astype(dtype)


def yarn_frequencies(head_dim: int, theta: float, factor: float,
                     original_max_pos: int, beta_fast: float,
                     beta_slow: float) -> np.ndarray:
    """YaRN inverse frequencies (arXiv:2309.00071), as transformers'
    ``_compute_yarn_parameters`` defines them (``truncate`` on): each
    frequency blends the original (extrapolated) and the ``factor``-times
    slower (interpolated) one, by a linear ramp over the dimensions whose
    rotation count over ``original_max_pos`` positions lies between
    ``beta_fast`` and ``beta_slow``.  float32, shape (head_dim / 2,)."""
    def corr_dim(rotations):
        return (head_dim * math.log(original_max_pos
                                    / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    pos_freqs = theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                          / head_dim)
    ramp = np.clip((np.arange(head_dim // 2, dtype=np.float32) - low)
                   / (high - low), 0.0, 1.0)
    extrapolate = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extrapolate) \
        + (1.0 / pos_freqs) * extrapolate
    return inv.astype(np.float32)


def rope_table(head_dim: int, rope) -> Tuple[np.ndarray, float]:
    """(inverse frequencies (head_dim / 2,) float32, cos/sin scale) of a
    ``configs.base.Rope``."""
    if rope.yarn_factor > 0:
        inv = yarn_frequencies(head_dim, rope.theta, rope.yarn_factor,
                               rope.yarn_original_max_pos,
                               rope.yarn_beta_fast, rope.yarn_beta_slow)
    else:
        inv = 1.0 / (rope.theta ** (np.arange(0, head_dim, 2,
                                              dtype=np.float32) / head_dim))
    return inv.astype(np.float32), float(rope.attention_factor)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[jax.Array] = None,
               scale: Optional[jax.Array] = None) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq).

    ``inv_freq`` (head_dim / 2,) and ``scale`` (cos/sin factor) replace
    the default table at ``theta`` (a layer's own table, ``rope_table``).
    """
    hd = x.shape[-1]
    freqs = rope_frequencies(hd, theta) if inv_freq is None else inv_freq
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., None, :]                       # (..., seq, 1, hd/2)
    sin = jnp.sin(angles)[..., None, :]
    if scale is not None:
        cos, sin = cos * scale, sin * scale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------ projections

def init_linear(key, d_in: int, d_out: int, dtype=jnp.float32,
                bias: bool = False, scale: Optional[float] = None) -> dict:
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
               ).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p: dict, x: jax.Array) -> jax.Array:
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


# -------------------------------------------------------------- SwiGLU MLP

def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.float32) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "gate": init_linear(k1, d_model, d_ff, dtype),
        "up": init_linear(k2, d_model, d_ff, dtype),
        "down": init_linear(k3, d_ff, d_model, dtype,
                            scale=1.0 / math.sqrt(d_ff)),
    }


def mlp(p: dict, x: jax.Array, pins: Pins = no_pins) -> jax.Array:
    h = jax.nn.silu(linear(p["gate"], x)) * linear(p["up"], x)
    h = pins("act_ff", h)
    return linear(p["down"], h)


# ------------------------------------------------------- attention (GQA)

def init_attention(key, d_model: int, n_heads: int, n_kv: int, head_dim: int,
                   qkv_bias: bool = False, dtype=jnp.float32) -> dict:
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "q": init_linear(kq, d_model, n_heads * head_dim, dtype, bias=qkv_bias),
        "k": init_linear(kk, d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "v": init_linear(kv, d_model, n_kv * head_dim, dtype, bias=qkv_bias),
        "o": init_linear(ko, n_heads * head_dim, d_model, dtype,
                         scale=1.0 / math.sqrt(n_heads * head_dim)),
    }


def qkv_project(p: dict, x: jax.Array, xkv: jax.Array, n_heads: int,
                n_kv: int, head_dim: int, positions, kv_positions,
                rope_theta: float, pins: Pins = no_pins, rope=()):
    """Returns q (B,S,H,hd), k/v (B,Skv,Kv,hd) with RoPE applied;
    ``rope`` = (inv_freq, scale) is the layer's own table."""
    B, S, _ = x.shape
    Skv = xkv.shape[1]
    q = linear(p["q"], x).reshape(B, S, n_heads, head_dim)
    k = linear(p["k"], xkv).reshape(B, Skv, n_kv, head_dim)
    v = linear(p["v"], xkv).reshape(B, Skv, n_kv, head_dim)
    if rope or rope_theta > 0:
        q = apply_rope(q, positions, rope_theta, *rope)
        k = apply_rope(k, kv_positions, rope_theta, *rope)
    q = pins("act_q", q)
    k = pins("act_kv", k)
    v = pins("act_kv", v)
    return q, k, v


def init_embedding(key, vocab: int, d_model: int, dtype=jnp.float32) -> dict:
    return {"table": (jax.random.normal(key, (vocab, d_model), jnp.float32)
                      * 0.02).astype(dtype)}


def embed(p: dict, tokens: jax.Array, pins: Pins = no_pins) -> jax.Array:
    out = jnp.take(p["table"], tokens, axis=0)
    return pins("act_btd", out)


def unembed(p: dict, x: jax.Array, logical_vocab: int,
            pins: Pins = no_pins) -> jax.Array:
    """Project to (padded) vocab; padded ids masked to a large negative."""
    logits = x @ p["table"].T.astype(x.dtype)
    vpad = logits.shape[-1]
    if vpad > logical_vocab:
        mask = (jnp.arange(vpad) < logical_vocab)
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    return pins("logits", logits)
