"""Pure-jnp oracle for paged attention over the hybrid pool.

Supports one query per sequence (decode, ``q (B, H, D)``) and multi-token
queries (``q (B, Q, H, D)``) in two flavours:

* prefix-KV chunked prefill: every query of a row attends the same pool
  extent ``ctx_len[b]`` — the installed prefix.  Causal structure
  *within* a chunk is the caller's separate part (see
  ``models.attention.causal_attention_parts``), merged through the
  unnormalized ``(o_weighted, m, l)`` contract this oracle shares with
  the Pallas kernel.
* speculative-decode verify: ``ctx_len (B, Q)`` gives every query its
  OWN extent — query ``i`` of the draft window attends ``pos + i + 1``
  pool positions, the exact mask sequential decode would apply, so the
  causal structure of the window lives entirely in the pool read (the
  K+1 tokens' K/V are written to their pool slots before the read).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_pool_blocks(pool, slots):
    """The translated-slot read path: ``pool (slots, bs, KV, D)`` gathered
    at ``slots (B, nblk)`` (negative entries clamp to slot 0 and must be
    masked by the caller) -> ``(B, nblk, bs, KV, D)``."""
    return pool[jnp.maximum(slots, 0)]


def paged_attention_ref(q, k_pool, v_pool, slots, ctx_len, *,
                        tok_offset: int = 0, tok_stride: int = 1,
                        block_tokens: int | None = None):
    """Same contract as the kernel: returns (o_weighted, m, l).

    ``q`` is (B, H, D) — decode, one token per sequence — or (B, Q, H, D)
    — Q chunk tokens per sequence; outputs follow the query rank:
    (B[, Q], H, D) / (B[, Q], H).  ``ctx_len`` bounds the attended pool
    positions: ``(B,)`` applies one extent to every query of the row
    (prefix read), ``(B, Q)`` gives each query its own extent (the
    speculative-verify shape: query ``i`` sees ``pos + i + 1`` tokens).
    A query with extent 0 contributes l == 0 so the flash-decoding
    combine drops it exactly.
    """
    k = gather_pool_blocks(k_pool, slots)               # (B, nblk, bs, KV, D)
    v = gather_pool_blocks(v_pool, slots)
    return paged_attention_blocks(q, k, v, slots, ctx_len,
                                  tok_offset=tok_offset,
                                  tok_stride=tok_stride,
                                  block_tokens=block_tokens)


def paged_attention_blocks(q, k, v, slots, ctx_len, *,
                           tok_offset: int = 0, tok_stride: int = 1,
                           block_tokens: int | None = None, window=None):
    """``paged_attention_ref`` with the gather already done.

    ``k``/``v`` are the PRE-GATHERED per-row blocks ``(B, nblk, bs, KV, D)``
    — exactly ``gather_pool_blocks(pool, slots)``, or the sharded engine's
    psum-reconstructed blocks (where a ``slots < 0`` row carries zeros
    instead of the clamp-gathered slot-0 data; both are bitwise-safe, the
    mask below NEG_INFs those scores before they contribute).  ``slots``
    is still taken for the validity mask.

    ``window`` (a scalar, may be traced) is a sliding-window layer's band
    for queries that sit at position ``ctx_len - 1`` (decode, verify):
    positions below ``ctx_len - window`` are masked too.
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, Q, H, D = q.shape
    nblk, bs, KV = k.shape[1], k.shape[2], k.shape[3]
    if block_tokens is None:
        block_tokens = bs
    g = H // KV
    scale = 1.0 / math.sqrt(D)

    pos = (jnp.arange(nblk)[:, None] * block_tokens
           + tok_offset + jnp.arange(bs)[None, :] * tok_stride)  # (nblk, bs)
    if ctx_len.ndim == 1:
        # one extent per row, identical for every query (broadcast at the
        # query axis keeps the 1-D path's arrays — and results — bitwise
        # unchanged)
        ctx_q = ctx_len[:, None]                        # (B, 1)
    else:
        ctx_q = ctx_len                                 # (B, Q)
    valid = ((pos[None, None] < ctx_q[:, :, None, None])
             & (slots >= 0)[:, None, :, None])          # (B, Qc, nblk, bs)
    if window is not None:
        valid &= pos[None, None] >= (ctx_q - window)[:, :, None, None]
    vflat = valid.reshape(B, 1, 1, valid.shape[1], -1)  # (B,1,1,Qc,nblk*bs)

    qk = q.astype(jnp.float32).reshape(B, Q, KV, g, D)
    s = jnp.einsum("bqkgd,bjtkd->bkgqjt", qk, k.astype(jnp.float32)) * scale
    s = s.reshape(B, KV, g, Q, nblk * bs)
    s = jnp.where(vflat, s, NEG_INF)
    m = s.max(axis=-1)                                  # (B, KV, g, Q)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(vflat, p, 0.0)
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgqn,bnkd->bkgqd", p,
                   v.astype(jnp.float32).reshape(B, nblk * bs, KV, D))
    o = o.transpose(0, 3, 1, 2, 4).reshape(B, Q, H, D)
    m = m.transpose(0, 3, 1, 2).reshape(B, Q, H)
    l = l.transpose(0, 3, 1, 2).reshape(B, Q, H)
    if squeeze:
        return o[:, 0], m[:, 0], l[:, 0]
    return o, m, l


def normalize(o, l):
    return o / jnp.maximum(l, 1e-30)[..., None]
