"""Attention implementations: dense reference + memory-safe chunked flash.

* ``dense_attention`` — materializes scores; oracle for tests and small runs.
* ``flash_attention_jax`` — two-level chunked online-softmax attention
  (lax.map over query chunks, lax.scan over KV chunks).  HLO stays compact
  (two nested while loops) and per-tile memory is bounded, which is what
  lets the 32k-prefill cells lower at scale.  The Pallas kernel in
  ``repro.kernels.flash_attention`` implements the same schedule for TPU.

Both support GQA (n_heads = g * n_kv) and causal/full masks.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_fold(q: jax.Array, n_kv: int):
    B, S, H, D = q.shape
    g = H // n_kv
    return q.reshape(B, S, n_kv, g, D), g


def dense_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    q_offset=0,
                    kv_len: Optional[jax.Array] = None,
                    window=None) -> jax.Array:
    """q: (B,Sq,H,hd), k/v: (B,Skv,Kv,hd) -> (B,Sq,H,hd).

    ``q_offset`` positions the queries inside the causal mask: a scalar
    (train/prefill, all rows share the offset) or a ``(B,)`` array — the
    prefix-KV chunk forward, where each row's chunk starts at its own
    already-installed context length.  The score/softmax/weighted-sum math
    is identical in both branches (only the mask construction differs), so
    a chunk query attending over [gathered prefix + own chunk] K/V laid
    out at their absolute positions reproduces the full-sequence forward
    bit for bit.

    ``window`` (causal only; a scalar, may be traced) keeps the keys with
    ``q - k < window``: a sliding-window layer's band.
    """
    B, Sq, H, D = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    qf, g = _gqa_fold(q, Kv)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qf.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        cpos = jnp.arange(Skv)
        off = jnp.asarray(q_offset)
        if off.ndim == 0:
            qpos = jnp.arange(Sq) + off
            mask = qpos[:, None] >= cpos[None, :]
            if window is not None:
                mask &= (qpos[:, None] - cpos[None, :]) < window
            s = jnp.where(mask, s, NEG_INF)
        else:                                  # per-row offsets (B,)
            qpos = off[:, None] + jnp.arange(Sq)[None, :]
            mask = qpos[:, :, None] >= cpos[None, None, :]   # (B, Sq, Skv)
            if window is not None:
                mask &= (qpos[:, :, None] - cpos[None, None, :]) < window
            s = jnp.where(mask[:, None, None], s, NEG_INF)
    if kv_len is not None:
        valid = jnp.arange(Skv)[None, :] < kv_len[:, None]     # (B, Skv)
        s = jnp.where(valid[:, None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqc,bckd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def causal_attention_parts(q: jax.Array, k: jax.Array, v: jax.Array):
    """Unnormalized causal attention over a chunk's OWN K/V.

    q: (B,S,H,hd), k/v: (B,S,Kv,hd) -> (o_weighted (B,S,H,hd) f32,
    m (B,S,H), l (B,S,H)) — the intra-chunk half of the prefix-KV merge,
    sharing the (m, l) contract of ``kernels.paged_attention`` so the two
    halves combine with a flash-decoding online-softmax correction
    (``merge_attention_parts``).  The mask is chunk-relative: query i
    attends chunk positions j <= i regardless of where the chunk sits in
    the sequence (the installed prefix is entirely in the other part).
    """
    B, Sq, H, D = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    qf, g = _gqa_fold(q, Kv)
    scale = 1.0 / math.sqrt(D)
    s = jnp.einsum("bqkgd,bckd->bkgqc", qf.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1)                                   # (B,Kv,g,Sq)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(mask, p, 0.0)
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgqc,bckd->bkgqd", p, v.astype(jnp.float32))
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return o, m.transpose(0, 3, 1, 2).reshape(B, Sq, H), \
        l.transpose(0, 3, 1, 2).reshape(B, Sq, H)


def merge_attention_parts(parts):
    """Flash-decoding combine: [(o_weighted, m, l), ...] -> normalized o.

    Each part is an unnormalized online-softmax partial over a disjoint
    KV range (pool prefix / own chunk / other shards); a part with l == 0
    everywhere (empty prefix) drops out exactly.
    """
    m_glob = functools.reduce(jnp.maximum, [m for _, m, _ in parts])
    o = sum(o * jnp.exp(m - m_glob)[..., None] for o, m, _ in parts)
    l = sum(l * jnp.exp(m - m_glob) for _, m, l in parts)
    return o / jnp.maximum(l, 1e-30)[..., None]


def pick_chunk(n: int, target: int) -> int:
    """Largest divisor of n that is <= target (prefer multiples of 128)."""
    target = min(target, n)
    best = 1
    for c in range(target, 0, -1):
        if n % c == 0:
            if c % 128 == 0:
                return c
            best = max(best, c) if best == 1 else best
    return best


def flash_attention_jax(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        q_chunk: int = 512,
                        kv_chunk: int = 512,
                        q_offset: int = 0,
                        triangular_schedule: bool = False) -> jax.Array:
    """Chunked online-softmax attention.

    ``triangular_schedule``: for causal attention, skip KV chunks entirely
    above the diagonal (per-query-chunk dynamic trip count).  This is the
    §Perf "causal flash wastes half its FLOPs" optimization; the baseline
    scans every KV chunk and masks.
    """
    B, Sq, H, D = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    g = H // Kv
    q_chunk = pick_chunk(Sq, q_chunk)
    kv_chunk = pick_chunk(Skv, kv_chunk)
    nq, nk = Sq // q_chunk, Skv // kv_chunk
    scale = 1.0 / math.sqrt(D)

    qc = q.reshape(B, nq, q_chunk, Kv, g, D).transpose(1, 0, 2, 3, 4, 5)
    kc = k.reshape(B, nk, kv_chunk, Kv, D)
    vc = v.reshape(B, nk, kv_chunk, Kv, D)

    def one_q_chunk(args):
        qi, q_i = args                                   # q_i (B,qc,Kv,g,D)
        q32 = q_i.astype(jnp.float32) * scale

        def kv_step(carry, j):
            m, l, o = carry
            k_j = jax.lax.dynamic_index_in_dim(kc, j, axis=1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vc, j, axis=1, keepdims=False)
            s = jnp.einsum("bqkgd,bckd->bkgqc", q32,
                           k_j.astype(jnp.float32))      # (B,Kv,g,qc,kc)
            if causal:
                qpos = qi * q_chunk + jnp.arange(q_chunk) + q_offset
                cpos = j * kv_chunk + jnp.arange(kv_chunk)
                mask = qpos[:, None] >= cpos[None, :]
                s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bkgqc,bckd->bkgqd", p, v_j.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        m0 = jnp.full((B, Kv, g, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, g, q_chunk), jnp.float32)
        o0 = jnp.zeros((B, Kv, g, q_chunk, D), jnp.float32)
        if causal and triangular_schedule:
            # only chunks at or below the diagonal contribute
            n_active = jnp.minimum(
                (qi * q_chunk + q_chunk - 1 + q_offset) // kv_chunk + 1, nk)
            (m, l, o), _ = jax.lax.scan(
                lambda c, j: jax.lax.cond(j < n_active,
                                          lambda: kv_step(c, j),
                                          lambda: (c, None)),
                (m0, l0, o0), jnp.arange(nk))
        else:
            (m, l, o), _ = jax.lax.scan(kv_step, (m0, l0, o0), jnp.arange(nk))
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return o.transpose(0, 3, 1, 2, 4).reshape(B, q_chunk, Kv * g, D)

    out = jax.lax.map(one_q_chunk, (jnp.arange(nq), qc))   # (nq,B,qc,H,D)
    return out.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, D).astype(q.dtype)


def attention(q, k, v, *, impl: str = "dense", causal: bool = True,
              q_offset: int = 0, q_chunk: int = 512, kv_chunk: int = 512,
              triangular_schedule: bool = False,
              kv_len: Optional[jax.Array] = None, window=None) -> jax.Array:
    if impl == "dense":
        return dense_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_len=kv_len, window=window)
    if window is not None:
        raise NotImplementedError(f"attention impl {impl!r} has no "
                                  "sliding-window mask; use 'dense'")
    if impl == "flash_jax":
        if kv_len is not None:
            raise NotImplementedError("flash_jax is for train/prefill "
                                      "(full-length KV)")
        return flash_attention_jax(
            q, k, v, causal=causal, q_offset=q_offset, q_chunk=q_chunk,
            kv_chunk=kv_chunk, triangular_schedule=triangular_schedule)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        # the Mosaic-lowered TPU kernel; the interpreter is for tests only
        return fa_ops.flash_attention(q, k, v, causal=causal,
                                      interpret=False)
    raise ValueError(f"unknown attention impl {impl!r}")
