"""Pallas TPU kernel: tiled causal flash attention (train/prefill hot spot).

Grid (batch, q_head, q_tiles, kv_tiles); online softmax carried in VMEM
scratch across the kv_tiles dimension.  GQA is handled in the BlockSpec
index map (kv head = q head // group).  Causal tiles entirely above the
diagonal are skipped via ``pl.when`` (block-triangular schedule — the same
optimization the pure-JAX path exposes as ``triangular_schedule``).

MXU alignment: q/kv tiles default to 128 x head_dim with head_dim >= 128 in
every assigned arch except the reduced smoke configs (interpret mode does
not enforce alignment; production sizes are asserted in ops.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_scr, l_scr, *,
                  causal: bool, q_tile: int, kv_tile: int, n_kv_tiles: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    run = True
    if causal:
        run = kj * kv_tile <= qi * q_tile + q_tile - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (qt, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (kt, D)
        v = v_ref[0, 0].astype(jnp.float32)
        D = q.shape[-1]
        s = (q @ k.T) * (1.0 / math.sqrt(D))           # (qt, kt)
        if causal:
            qpos = qi * q_tile + jnp.arange(q_tile)
            kpos = kj * kv_tile + jnp.arange(kv_tile)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + p.sum(axis=-1)
        m_scr[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + p @ v

    @pl.when(kj == n_kv_tiles - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...] /
                       jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, q_tile: int = 128,
                           kv_tile: int = 128, interpret: bool):
    """q (B,S,H,D); k/v (B,S,KV,D) -> (B,S,H,D).

    ``interpret`` has no default: the CPU tests pass True (the Pallas
    interpreter), the chip path False (Mosaic)."""
    B, Sq, H, D = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    g = H // KV
    q_tile = min(q_tile, Sq)
    kv_tile = min(kv_tile, Skv)
    assert Sq % q_tile == 0 and Skv % kv_tile == 0
    nq, nk = Sq // q_tile, Skv // kv_tile
    # layout: (B, H, S, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    kernel = functools.partial(_flash_kernel, causal=causal, q_tile=q_tile,
                               kv_tile=kv_tile, n_kv_tiles=nk)
    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, q_tile, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, kv_tile, D),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, kv_tile, D),
                         lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q_tile, D),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_tile, D), jnp.float32),
            pltpu.VMEM((q_tile,), jnp.float32),
            pltpu.VMEM((q_tile,), jnp.float32),
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
