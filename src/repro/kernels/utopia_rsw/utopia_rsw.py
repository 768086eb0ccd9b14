"""Pallas TPU kernel: Utopia RestSeg Walk (hybrid translation).

The paper's RSW (§5.2) adapted to the TPU memory hierarchy:

* TAR and SF live wholly in VMEM (kernel operands with full-array
  BlockSpecs) — the analogue of the paper's dedicated 2 KB TAR/SF SRAM
  caches, except sized so the *entire* structure is resident (a 512 MB-
  equivalent RestSeg needs ~600 KB of TAR+SF, well under VMEM).
* Tag matching is a one-hot select over the TAR: a data-dependent row
  gather is slow/unsupported on the VPU, while comparing a
  (n_sets, tile) set-index iota against the tile's set indices and
  reducing over the set axis is plain elementwise work plus a sublane
  reduction.  This is the central hardware-adaptation decision recorded
  in DESIGN.md (§TAR-match-one-hot).
* The FlexSeg fallback is the same one-hot select over the flat flex
  table, only consumed for lanes whose RSW missed (the paper's "FSW only
  on RSW miss").  A vpn outside the flex table reads as unmapped.

Vpns ride the lane axis (a ``(1, tile)`` block per grid step); per-set
values ride the sublane axis as ``(n, 1)`` columns, so every select is a
broadcast compare and every reduction runs over sublanes.

Grid: one program per tile of ``tile`` vpns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.hashes import get_hash


def _select(onehot, col):
    """Exact one-hot select: ``col`` (n, 1) int32 >= 0 at the row each lane
    of ``onehot`` (n, tile) marks -> (1, tile) int32 (0 where no row is
    marked).  A value can exceed 2^24 and would round in a float32 sum,
    mis-hitting; each 16-bit half is exactly representable (a one-hot
    column selects a single value, so the reduction is exact too) and the
    halves recombine in int32."""
    lo = (col & 0xFFFF).astype(jnp.float32)
    hi = ((col >> 16) & 0xFFFF).astype(jnp.float32)
    lo_s = jnp.sum(jnp.where(onehot, lo, 0.0), axis=0, keepdims=True)
    hi_s = jnp.sum(jnp.where(onehot, hi, 0.0), axis=0, keepdims=True)
    return lo_s.astype(jnp.int32) | (hi_s.astype(jnp.int32) << 16)


def _rsw_kernel(vpn_ref, tar_ref, sf_ref, flex_ref, slot_ref, in_rest_ref,
                mapped_ref, *, hash_name: str):
    vpn = vpn_ref[...]                                  # (1, tile)
    tar = tar_ref[...]                                  # (n_sets, assoc)
    n_sets, assoc = tar.shape
    tile = vpn.shape[1]
    set_idx = get_hash(hash_name)(vpn, n_sets).astype(jnp.int32)

    # --- set filtering (SF probe) + tag matching via one-hot select ----
    onehot = jax.lax.broadcasted_iota(jnp.int32, (n_sets, tile), 0) == set_idx
    counters = _select(onehot, sf_ref[...])             # (1, tile)
    tag = vpn + 1
    hit = jnp.zeros_like(vpn, jnp.bool_)
    way = jnp.zeros_like(vpn)
    for a in reversed(range(assoc)):                    # lowest way wins
        eq = _select(onehot, tar[:, a:a + 1]) == tag
        hit = hit | eq
        way = jnp.where(eq, a, way)
    hit = hit & (counters > 0)
    rest_slot = set_idx * assoc + jnp.where(hit, way, 0)

    # --- flexible fallback (flat block table, consumed on miss only) ---
    n_flex = flex_ref.shape[0]
    fsel = jax.lax.broadcasted_iota(jnp.int32, (n_flex, tile), 0) == vpn
    flex_slot = _select(fsel, flex_ref[...] + 1) - 1    # -1: unmapped
    slot = jnp.where(hit, rest_slot, flex_slot)
    mapped = hit | (flex_slot >= 0)

    slot_ref[...] = jnp.where(mapped, slot, -1).astype(jnp.int32)
    in_rest_ref[...] = hit.astype(jnp.int32)
    mapped_ref[...] = mapped.astype(jnp.int32)


def rsw_pallas(vpns: jax.Array, tar: jax.Array, sf: jax.Array,
               flex_flat: jax.Array, *, hash_name: str = "modulo",
               tile: int = 128, interpret: bool):
    """vpns (N,) int32 -> (slot (N,), in_rest (N,), mapped (N,)) int32.

    ``interpret`` has no default: the CPU tests pass True (the Pallas
    interpreter), the chip path False (Mosaic)."""
    n = vpns.shape[0]
    pad = (-n) % tile
    vp = (jnp.pad(vpns, (0, pad)) if pad else vpns).astype(jnp.int32)[None]
    n_pad = vp.shape[1]
    kernel = functools.partial(_rsw_kernel, hash_name=hash_name)
    full = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    lanes = pl.BlockSpec((1, tile), lambda i: (0, i))
    sf_col = sf.astype(jnp.int32)[:, None]
    flex_col = flex_flat.astype(jnp.int32)[:, None]
    slot, in_rest, mapped = pl.pallas_call(
        kernel,
        grid=(n_pad // tile,),
        in_specs=[
            lanes,
            full(tar.shape),          # TAR: fully VMEM-resident
            full(sf_col.shape),       # SF: fully VMEM-resident
            full(flex_col.shape),     # flat flex table (validation config)
        ],
        out_specs=[lanes] * 3,
        out_shape=[jax.ShapeDtypeStruct((1, n_pad), jnp.int32)] * 3,
        interpret=interpret,
    )(vp, tar.astype(jnp.int32), sf_col, flex_col)
    return slot[0, :n], in_rest[0, :n], mapped[0, :n]
