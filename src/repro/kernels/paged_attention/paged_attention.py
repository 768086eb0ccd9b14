"""Pallas TPU kernel: paged attention over the hybrid KV pool.

Seq-major (vLLM-layout analogue): each sequence's query tokens attend over
its logical blocks; physical slots come from the Utopia hybrid translation
(the RSW kernel's output), delivered via *scalar prefetch* so the BlockSpec
``index_map`` can steer the DMA of each grid step to the right pool slot —
the TPU analogue of the paper's "translation resolved before the data
access, overlapped with the previous tile's compute" (software pipelining
replaces the paper's RSW ∥ L2-TLB parallelism).

Queries may be a single token per sequence (decode, ``q (B, H, D)``) or a
whole prefill chunk (prefix-KV admission, ``q (B, Q, H, D)``): the chunk
tokens of one query block ride in the same VMEM tile and share each pool
block's DMA.  All Q queries of a row attend the same extent ``ctx_len[b]``
(the installed prefix); the chunk-internal causal part is computed outside
and combined through the (m, l) outputs.

Grid: (batch, query blocks, num_blocks); the query tiles of a block loop
inside the step.  Scratch carries the online-softmax (m, l, acc) across
the block dimension.  Outputs are the *unnormalized* weighted values
plus (m, l) so the caller can combine partial results across model shards
(flash-decoding psum combine) before normalizing.

Holes (slot == -1: unmapped/swapped blocks) and tokens past the context
length are masked; hole blocks are clamped to slot 0 for the DMA and fully
masked in the body.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# query rows (query x head) per grid step: bounds the f32 accumulator and
# the lane-padded m/l tiles in VMEM to 1 MiB each at head_dim 128
MAX_ROWS = 2048


def _paged_attn_kernel(slots_ref, ctx_ref, q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref,
                       acc_ref, m_scr, l_scr, *, q_tile: int,
                       block_tokens: int, tok_offset: int, tok_stride: int,
                       n_blocks: int):
    b = pl.program_id(0)
    qb = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    _, Qb, H, D = q_ref.shape
    _, bs, KV, _ = k_ref.shape
    g = H // KV
    R, C = q_tile * H, bs * KV
    scale = 1.0 / math.sqrt(D)
    # 2-D layout: rows are (query, head) pairs, columns (token, kv head)
    # pairs.  Every head scores every column and the columns of the other
    # kv heads are masked — KV-fold redundant MXU work on a memory-bound
    # read, in exchange for no 3-D/4-D relayout inside the kernel.
    k = k_ref[0].astype(jnp.float32).reshape(C, D)
    v = v_ref[0].astype(jnp.float32).reshape(C, D)

    row = jax.lax.broadcasted_iota(jnp.int32, (R, C), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (R, C), 1)
    slot = slots_ref[b, j]
    # global token positions of this (block, local-token-shard) tile
    pos = j * block_tokens + tok_offset + (col // KV) * tok_stride
    head_ok = ((row % H) // g == col % KV) & (slot >= 0)

    def tile(t, carry):
        # the block's query tiles share the pool block loaded above
        q0 = t * q_tile
        q = q_ref[0, pl.ds(q0, q_tile)].astype(jnp.float32).reshape(R, D)
        if len(ctx_ref.shape) == 1:
            # one extent for every query of the row (decode / prefix read)
            ext = ctx_ref[b]
        else:
            # per-query extents (speculative verify): query i of the draft
            # window sees ctx_ref[b, i] pool tokens — the sequential causal
            # mask, applied inside the shared pool-block DMA.  SMEM yields
            # scalars only, so the (R, C) extent is assembled per query.
            qrow = row // H
            ext = jnp.zeros((R, C), jnp.int32)
            for i in range(q_tile):
                ext = jnp.where(qrow == i, ctx_ref[b, qb * Qb + q0 + i], ext)
        valid = head_ok & (pos < ext)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid, s, NEG_INF)                # (R, C)

        rows = pl.ds(pl.multiple_of(t * R, R), R)
        m_prev = m_scr[rows, :]                         # (R, 1)
        l_prev = l_scr[rows, :]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        acc_ref[rows, :] = acc_ref[rows, :] * corr + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[rows, :] = m_new
        l_scr[rows, :] = l_new
        return carry

    jax.lax.fori_loop(0, Qb // q_tile, tile, 0)

    @pl.when(j == n_blocks - 1)
    def _finish():
        o_ref[0] = acc_ref[...].reshape(Qb, H, D).astype(o_ref.dtype)
        m_ref[0] = m_scr[...].astype(m_ref.dtype)
        l_ref[0] = l_scr[...].astype(l_ref.dtype)


def paged_attention_pallas(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                           slots: jax.Array, ctx_len: jax.Array, *,
                           tok_offset: int = 0, tok_stride: int = 1,
                           block_tokens: int | None = None,
                           interpret: bool):
    """q (B,H,D) or (B,Q,H,D); k/v_pool (slots, bs_local, KV, D);
    slots (B, nblk) int32; ctx_len (B,) int32 — or (B, Q) for the
    speculative-verify shape, giving every query its own attended
    extent.  Returns (o_weighted (B[,Q],H,D), m (B[,Q],H),
    l (B[,Q],H)) — output rank follows the query rank.

    ``tok_offset``/``tok_stride`` describe which global token positions the
    local pool token-shard holds (model-axis token striping); on a single
    shard use (0, 1) and ``block_tokens = bs_local``.  ``interpret`` has no
    default: the CPU tests pass True (the Pallas interpreter), the chip
    path False (Mosaic).

    Queries are scored ``q_tile`` at a time, so the (q_tile * H, bs * KV)
    score tile stays in VMEM; the tiles of a query block (at most
    ``MAX_ROWS`` query rows) loop inside one grid step and share each pool
    block's DMA.  A chunk longer than a query block reads the pool once
    per block (a grid axis).
    """
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, None]
    B, Q, H, D = q.shape
    n_slots, bs, KV, _ = k_pool.shape
    nblk = slots.shape[1]
    if block_tokens is None:
        block_tokens = bs
    q_tile = max(1, min(Q, 256 // H))
    n_tiles = -(-Q // q_tile)
    per_block = min(n_tiles, max(1, MAX_ROWS // (q_tile * H)))
    n_qblk = -(-n_tiles // per_block)
    per_block = -(-n_tiles // n_qblk)           # even out the blocks
    q_block = per_block * q_tile
    Qp = n_qblk * q_block
    if Qp != Q:
        q = jnp.pad(q, ((0, 0), (0, Qp - Q), (0, 0), (0, 0)))
        if ctx_len.ndim == 2:
            ctx_len = jnp.pad(ctx_len, ((0, 0), (0, Qp - Q)))
    R = q_block * H
    kernel = functools.partial(
        _paged_attn_kernel, q_tile=q_tile, block_tokens=block_tokens,
        tok_offset=tok_offset,
        tok_stride=tok_stride, n_blocks=nblk)
    kv_spec = pl.BlockSpec((1, bs, KV, D),
                           lambda b, i, j, slots, ctx:
                           (jnp.maximum(slots[b, j], 0), 0, 0, 0))
    q_spec = pl.BlockSpec((1, q_block, H, D),
                          lambda b, i, j, slots, ctx: (b, i, 0, 0))
    ml_spec = pl.BlockSpec((1, R, 1), lambda b, i, j, slots, ctx: (b, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                     # slots, ctx_len
        grid=(B, n_qblk, nblk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, ml_spec, ml_spec],
        scratch_shapes=[
            pltpu.VMEM((R, D), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
        ],
    )
    o, m, l = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Qp, H, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Qp * H, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Qp * H, 1), jnp.float32),
        ],
        interpret=interpret,
    )(slots, ctx_len, q, k_pool, v_pool)
    o = o[:, :Q]
    m = m.reshape(B, Qp, H)[:, :Q]
    l = l.reshape(B, Qp, H)[:, :Q]
    if squeeze:
        return o[:, 0], m[:, 0], l[:, 0]
    return o, m, l
