"""SPMD decode step: hybrid-translated paged attention + recurrent states.

Layout (design §5): the KV pool is sharded
    (L_attn, slots, block_tokens, KV, hd)
          P(None, DATA,       MODEL, None, None)

* slots over the DATA axes — in **batch mode** each data group owns the
  sequences (and all their blocks) of its batch shard: every gather is
  local.  In **striped mode** (long_500k, batch 1) the single sequence's
  blocks are dealt round-robin over the data groups.
* block tokens over MODEL — each model shard holds a contiguous token
  sub-range of every block; partial softmax results are psum-combined
  (flash-decoding).  This sidesteps GQA-head divisibility entirely
  (kv_heads never needs to divide the model axis).

Translation (the paper's technique) runs **exactly once per step**
(DESIGN.md §translate-once): ``translate_step`` resolves every block vpn
of every group — plus the current block being written — in one hybrid
RSW/flex lookup *before* the layer scan, and the resolved slot table is
what flows into every attention layer.  The per-layer work is pure
gather/scatter over pre-resolved slots; no translation structure is
touched inside the scan body (O(B·nblk) translation per step instead of
O(L·B·nblk)).  The same pass emits the per-vpn telemetry (in_rest /
accesses / mapped) the engine feeds back to the promotion policy, so the
host never re-translates.

Everything outside paged attention (projections, MoE, mamba recurrence,
lm head) stays in pjit/GSPMD land with sharding constraints.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers as Lmod
from repro.models import transformer as Tmod
from repro.models.transformer import ModelDims
from repro.models.ssm import MambaCache, mamba_decode_step
from repro.models.moe import moe_decode
from repro.core.tar_sf import RestSegState, rsw, probe_rows
from repro.core.hashes import get_hash
from repro.core.partition import Partition
from repro.dist.sharding import kv_state_specs
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_attention_blocks)
from .sampling import sample_tokens


@dataclasses.dataclass(frozen=True)
class DecodeSpec:
    block_size: int              # tokens per KV block (global)
    max_blocks_per_seq: int      # per data group in striped mode
    slots_per_group: int
    n_sets: int
    assoc: int
    mode: str = "batch"          # batch | striped
    hash_name: str = "modulo"
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    use_kernels: bool = False    # Pallas path (TPU); ref path otherwise
    # prefix-KV chunked prefill: how chunk queries read the installed
    # prefix blocks.  "exact" materializes the gathered K/V at their
    # absolute positions and reuses the recompute forward's dense softmax
    # (bit-identical oracle contract); "paged" is the Q>1
    # kernels/paged_attention read merged by an online-softmax combine
    # (linear memory, kernel-ready; equal up to float associativity).
    prefix_gather: str = "exact"
    # KV/translation sharding over the model axis (DESIGN.md
    # §sharded-serving).  0 = legacy layout: mesh != None selects the
    # token-split flash-decoding path (dryrun compile cells).  >= 1 = the
    # SPMD engine layout: the pool is slot-sharded by the set-index /
    # block-range Partition, the whole step runs under one shard_map, and
    # every float op is replicated so streams stay bitwise identical to
    # mesh=None.  Requires ``part`` to be passed to the step factories.
    kv_shards: int = 0

    @property
    def nblk(self) -> int:
        return self.max_blocks_per_seq


def make_decode_spec(cfg: ArchConfig, seq_len: int, batch: int,
                     data_size: int, mode: str = "batch",
                     headroom: float = 1.25,
                     data_axes: Tuple[str, ...] = ("data",)) -> DecodeSpec:
    bs = cfg.kv_block_size
    total_blocks = (seq_len + bs - 1) // bs * batch
    if mode == "batch":
        blocks_per_group = total_blocks // data_size
        max_blocks = (seq_len + bs - 1) // bs
    else:  # striped: one (or few) seqs, blocks dealt over groups
        blocks_per_group = total_blocks // data_size
        max_blocks = ((seq_len + bs - 1) // bs) // data_size
    assoc = 8
    slots = max(assoc * 2, int(blocks_per_group * headroom))
    rest = max(assoc, int(slots * 0.75) // assoc * assoc)
    slots = rest + max(assoc, slots - rest)
    return DecodeSpec(block_size=bs, max_blocks_per_seq=max_blocks,
                      slots_per_group=slots, n_sets=rest // assoc,
                      assoc=assoc, mode=mode, hash_name=cfg.hash_name
                      if hasattr(cfg, "hash_name") else "modulo",
                      data_axes=data_axes)


# ----------------------------------------------------------- decode state

def abstract_decode_state(cfg: ArchConfig, dims: ModelDims, spec: DecodeSpec,
                          batch: int, data_size: int,
                          dtype=jnp.bfloat16,
                          part: Optional[Partition] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct pytree of the decode state (dry-run friendly).

    With ``part`` (the SPMD engine layout, ``spec.kv_shards >= 1``) the
    pool and translation tables take the shard-padded sizes: every
    shard's chunk is identically shaped, padded TAR rows stay zero and
    padded flex entries -1, so the padded lookup is bit-identical to the
    unpadded one.  ``data_size`` must be 1 in that layout (the data axis
    replicates engine state; it scales *compute* only).
    """
    sd = jax.ShapeDtypeStruct
    G = data_size
    n_attn = sum(cfg.attn_on_layer(l) for l in range(cfg.num_layers))
    n_ssm = cfg.num_layers - n_attn if cfg.family in ("hybrid", "ssm") else 0
    seqs_per_group = max(1, batch // G) if spec.mode == "batch" else batch
    if part is not None and G != 1:
        raise ValueError("sharded decode state requires data_size == 1")
    st: Dict[str, Any] = {}
    if n_attn:
        pool_slots = part.pool_slots if part is not None \
            else G * spec.slots_per_group
        pool = (n_attn, pool_slots, spec.block_size,
                max(dims.n_kv, 1), dims.head_dim)
        n_sets = part.n_sets_padded if part is not None else spec.n_sets
        flex_len = part.vpn_padded if part is not None \
            else seqs_per_group * spec.max_blocks_per_seq
        st["k_pool"] = sd(pool, dtype)
        st["v_pool"] = sd(pool, dtype)
        st["tar"] = sd((G, n_sets, spec.assoc), jnp.int32)
        st["sf"] = sd((G, n_sets), jnp.int32)
        st["flex"] = sd((G, flex_len), jnp.int32)
    if n_ssm:
        md = dims.mamba
        st["ssm"] = sd((n_ssm, batch, md.n_heads, md.head_dim, md.d_state),
                       jnp.float32)
        st["conv"] = sd((n_ssm, batch, md.conv_width - 1, md.conv_channels),
                        dtype)
    if cfg.is_encoder_decoder:
        st["cross_k"] = sd((cfg.num_layers, batch, cfg.frontend_tokens,
                            dims.n_kv, dims.head_dim), dtype)
        st["cross_v"] = sd((cfg.num_layers, batch, cfg.frontend_tokens,
                            dims.n_kv, dims.head_dim), dtype)
    st["ctx_len"] = sd((batch,), jnp.int32)
    # per-slot sampling state (serve/sampling.py): the engine scatters a
    # request's SamplingParams here at admission; zeros = greedy argmax
    st["samp_temp"] = sd((batch,), jnp.float32)
    st["samp_topk"] = sd((batch,), jnp.int32)
    st["samp_topp"] = sd((batch,), jnp.float32)
    st["samp_key"] = sd((batch, 2), jnp.uint32)
    return st


def init_decode_state(cfg, dims, spec, batch, data_size, dtype=jnp.float32,
                      part: Optional[Partition] = None):
    abstract = abstract_decode_state(cfg, dims, spec, batch, data_size, dtype,
                                     part=part)
    st = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), abstract)
    if "flex" in st:
        st["flex"] = st["flex"] - 1            # -1 = unmapped
    return st


def decode_state_shardings(state_shape, mesh: Mesh, spec: DecodeSpec):
    da, ma = spec.data_axes, spec.model_axis
    table = {
        "k_pool": P(None, da, ma, None, None),
        "v_pool": P(None, da, ma, None, None),
        "tar": P(da, None, None),
        "sf": P(da, None),
        "flex": P(da, None),
        "ssm": P(None, da if spec.mode == "batch" else None, ma, None, None),
        "conv": P(None, da if spec.mode == "batch" else None, None, ma),
        "cross_k": P(None, da if spec.mode == "batch" else None, None,
                     None, None),
        "cross_v": P(None, da if spec.mode == "batch" else None, None,
                     None, None),
        "ctx_len": P(),
        "samp_temp": P(),
        "samp_topk": P(),
        "samp_topp": P(),
        "samp_key": P(),
        # speculative-decode token history (serve/spec_decode.py): the
        # engine installs it only when spec decoding is configured, so
        # the spec-off decode state stays exactly the PR-4 pytree
        "hist": P(),
    }

    def guard(name, leaf):
        sp = list(table[name])[:leaf.ndim]
        sp += [None] * (leaf.ndim - len(sp))
        out = []
        for dim, axes in zip(leaf.shape, sp):
            if axes is None:
                out.append(None)
                continue
            ax = (axes,) if isinstance(axes, str) else tuple(axes)
            size = int(np.prod([mesh.shape[a] for a in ax]))
            out.append(axes if dim % size == 0 else None)
        return NamedSharding(mesh, P(*out))

    return {k: guard(k, v) for k, v in state_shape.items()}


# --------------------------------------------- once-per-step translation

class StepTranslation(NamedTuple):
    """Result of the single hybrid translation performed per decode step.

    Group-major: ``G`` leads every device array so the same structure
    serves the mesh path (``P(da, ...)`` — each data group reads row ``g``)
    and the single-device engine (``G == 1``).
    """

    slots: jnp.ndarray      # (G, B_loc, nblk) int32 resolved pool slot, -1
    w_slot: jnp.ndarray     # (G, B_loc) int32 slot of the block being written
    w_valid: jnp.ndarray    # (G, B_loc) bool: mapped & owned by the group
    in_rest: jnp.ndarray    # (G, B_loc, nblk) bool — resolved by the RSW
    mapped: jnp.ndarray     # (G, B_loc, nblk) bool
    accesses: jnp.ndarray   # (G, B_loc, nblk) int32 structure accesses
    vpns: jnp.ndarray       # (B_loc, nblk) int32 local vpn grid


def _hybrid_lookup(vpns: jax.Array, tar: jax.Array, sf: jax.Array,
                   flex_flat: jax.Array, hash_name: str):
    """Hybrid RSW ∥ flex lookup with ``translate()``-compatible accounting.

    This is the ONLY translation primitive the decode step may touch, and
    it must be called exactly once per step (guarded by
    tests/test_engine_hotpath.py::test_translation_runs_once_per_step).
    The RestSeg walk itself is the canonical ``core.tar_sf.rsw`` — one
    source of truth for the paper's RSW semantics; only the flat flex
    gather and the access accounting live here.
    Returns (slot, in_rest, mapped, accesses), each shaped like ``vpns``.
    """
    rest = RestSegState(tar=tar, sf=sf, meta=jnp.zeros_like(tar))
    r = rsw(rest, vpns.astype(jnp.int32), hash_name)
    flex_slot = flex_flat[vpns]
    slot = jnp.where(r.hit, r.slot,
                     jnp.where(flex_slot >= 0, flex_slot, -1))
    mapped = r.hit | (flex_slot >= 0)
    # SF probe (1) + TAR set read unless SF filtered (1) + flex walk on miss
    accesses = (1 + jnp.where(r.sf_skipped, 0, 1)
                + jnp.where(r.hit, 0, 1))
    return (slot.astype(jnp.int32), r.hit, mapped,
            accesses.astype(jnp.int32))


def _hybrid_lookup_sharded(vpns: jax.Array, tar_l: jax.Array,
                           sf_l: jax.Array, flex_l: jax.Array,
                           hash_name: str, part: Partition,
                           model_axis: str):
    """Sharded hybrid lookup: probe the LOCAL table shards, psum-combine.

    Runs under shard_map over ``model_axis``: ``tar_l (spm, assoc)`` /
    ``sf_l (spm,)`` are this shard's set-index range of the TAR/SF
    tables, ``flex_l (vpm,)`` its vpn range of the flat flex table;
    ``vpns`` is replicated.  Each shard probes only the queries whose
    set (resp. vpn) it owns — contributions are combined with integer
    psums, so the result is EXACTLY the global lookup (no float
    reduction): bit-identical slots/telemetry to ``_hybrid_lookup`` on
    the unsharded tables.  Padded TAR rows are all-zero (tags store
    vpn+1, so 0 never matches) and padded flex entries -1, which is why
    the clipped out-of-range probes below cannot spuriously hit.

    Like ``_hybrid_lookup`` this is the ONLY translation primitive the
    sharded decode step may touch, called exactly once per step (pinned
    by tests/test_sharded_serve.py).
    """
    m = jax.lax.axis_index(model_axis)
    spm = part.sets_per_shard
    vpm = part.vpns_per_shard
    set_g = get_hash(hash_name)(vpns.astype(jnp.int32),
                                part.n_sets).astype(jnp.int32)
    mine = (set_g // spm) == m
    loc = jnp.clip(set_g - m * spm, 0, spm - 1)
    l_hit, l_way, l_skip = probe_rows(tar_l[loc], sf_l[loc],
                                      vpns.astype(jnp.int32))
    hit = jax.lax.psum(
        jnp.where(mine & l_hit, 1, 0), model_axis) > 0
    way = jax.lax.psum(
        jnp.where(mine & l_hit, l_way + 1, 0), model_axis) - 1
    sf_skipped = jax.lax.psum(
        jnp.where(mine, l_skip.astype(jnp.int32), 0), model_axis) > 0
    mine_f = (vpns // vpm) == m
    ent = flex_l[jnp.clip(vpns - m * vpm, 0, vpm - 1)]
    # shift by 2 so both "not mine" (0) and "unmapped" (-1 -> 1) slot in
    # below zero after the un-shift; exactly one shard owns each vpn
    flex_slot = jax.lax.psum(
        jnp.where(mine_f, ent + 2, 0), model_axis) - 2
    slot = jnp.where(hit, set_g * part.assoc + jnp.maximum(way, 0),
                     jnp.where(flex_slot >= 0, flex_slot, -1))
    mapped = hit | (flex_slot >= 0)
    accesses = (1 + jnp.where(sf_skipped, 0, 1)
                + jnp.where(hit, 0, 1))
    return (slot.astype(jnp.int32), hit, mapped,
            accesses.astype(jnp.int32))


def _translate_queries(lookup, tar: jax.Array, sf: jax.Array,
                       flex: jax.Array, positions: jax.Array,
                       spec: DecodeSpec) -> StepTranslation:
    """Shared skeleton of the once-per-step translation dispatch.

    Builds the per-group query grid (every block vpn of every sequence
    plus the current write block), runs ``lookup(tar_g, sf_g, flex_g,
    vpns)`` once over it, and packs the ``StepTranslation``.  The lookup
    itself is injected so the single-device and sharded paths share one
    skeleton while keeping separately pin-able primitives.
    """
    G = tar.shape[0]
    nblk = spec.max_blocks_per_seq
    bs = spec.block_size
    B = positions.shape[0]
    if spec.mode == "batch":
        B_loc = B // G
        pos_g = positions.reshape(G, B_loc)
    else:
        B_loc = B
        pos_g = jnp.broadcast_to(positions[None, :], (G, B))
    seq = jnp.arange(B_loc, dtype=jnp.int32)
    grid = (seq[:, None] * nblk
            + jnp.arange(nblk, dtype=jnp.int32)[None, :])   # (B_loc, nblk)

    if spec.mode == "batch":
        cur_block = pos_g // bs
        owner = jnp.ones((G, B_loc), bool)
    else:  # striped: block b lives on group b % G, locally at b // G
        cur_block_global = pos_g // bs
        owner = (cur_block_global % G) == jnp.arange(
            G, dtype=jnp.int32)[:, None]
        cur_block = cur_block_global // G
    # an idle/released slot's ctx_len keeps advancing with the batch, so
    # its current block can run past the sequence's vpn range — without
    # this bound its cur_vpn would alias ANOTHER sequence's vpns and the
    # write below would scatter garbage into a live block
    in_range = cur_block < nblk
    cur_block = jnp.minimum(cur_block, nblk - 1)
    cur_vpn = seq[None, :] * nblk + cur_block               # (G, B_loc)

    n_read = B_loc * nblk
    queries = jnp.concatenate(
        [jnp.broadcast_to(grid.reshape(-1)[None, :], (G, n_read)), cur_vpn],
        axis=1)                                             # (G, n_read+B_loc)
    slot, hit, mapped, acc = jax.vmap(lookup)(tar, sf, flex, queries)

    shape3 = (G, B_loc, nblk)
    return StepTranslation(
        slots=slot[:, :n_read].reshape(shape3),
        w_slot=slot[:, n_read:],
        w_valid=mapped[:, n_read:] & owner & in_range,
        in_rest=hit[:, :n_read].reshape(shape3),
        mapped=mapped[:, :n_read].reshape(shape3),
        accesses=acc[:, :n_read].reshape(shape3),
        vpns=grid,
    )


def translate_step(tar: jax.Array, sf: jax.Array, flex: jax.Array,
                   positions: jax.Array, spec: DecodeSpec
                   ) -> StepTranslation:
    """Translate ALL block vpns of ALL groups once — the step's only
    translation dispatch.

    tar (G, n_sets, assoc), sf (G, n_sets), flex (G, seqs*nblk) are the
    per-group translation structures; ``positions`` (B,) the pre-step
    context lengths.  The current block's write-slot lookup is batched
    into the same dispatch (it is just ``B_loc`` extra vpns).
    """
    return _translate_queries(
        lambda t, s, f, v: _hybrid_lookup(v, t, s, f, spec.hash_name),
        tar, sf, flex, positions, spec)


def translate_step_sharded(tar_l: jax.Array, sf_l: jax.Array,
                           flex_l: jax.Array, positions: jax.Array,
                           spec: DecodeSpec, part: Partition
                           ) -> StepTranslation:
    """Sharded translate-once dispatch (runs under shard_map).

    Same contract as ``translate_step`` — one dispatch per step, LOGICAL
    slot numbering in the returned ``StepTranslation`` (bit-identical to
    ``mesh=None``) — but each shard probes only its own TAR/SF set range
    and flex vpn range; integer psums combine the verdicts.
    """
    assert spec.mode == "batch", "sharded serving is batch-mode only"
    return _translate_queries(
        lambda t, s, f, v: _hybrid_lookup_sharded(
            v, t, s, f, spec.hash_name, part, spec.model_axis),
        tar_l, sf_l, flex_l, positions, spec)


# ------------------------------------------------- paged attention (SPMD)

def _paged_attn_shardmap(q, k_new, v_new, k_pool_l, v_pool_l, slots, w_slot,
                         w_valid, pos, *, spec: DecodeSpec,
                         mesh: Mesh, n_kv: int, head_dim: int):
    """Write + attention over PRE-RESOLVED slots inside shard_map.

    q: (B, H, hd); k_new/v_new: (B, KV, hd); k/v_pool_l: one layer's pool
    (G*slots, bs, KV, hd); slots (G, B_loc, nblk); w_slot/w_valid
    (G, B_loc); pos: (B,) pre-step context lengths (write position AND
    attention extent).  No translation structure is consumed here —
    translation happened once in ``translate_step``.
    Returns (attn_out (B, H, hd) fp32, k_pool_l', v_pool_l').
    """
    da, ma = spec.data_axes, spec.model_axis
    TP = int(np.prod([mesh.shape[a] for a in (ma,)]))
    bs = spec.block_size
    bs_loc = bs // TP
    batch_mode = spec.mode == "batch"

    def local(q, k_new, v_new, kp, vp, slots, w_slot, w_valid, pos):
        # shapes: q (B_loc, H, hd); kp (slots, bs_loc, KV, hd);
        # slots (1, B_loc, nblk) -> squeeze group dim
        slots, w_slot, w_valid = slots[0], w_slot[0], w_valid[0]
        m_idx = jax.lax.axis_index(ma)
        if len(da) == 1:
            g_idx = jax.lax.axis_index(da[0])
        else:
            g_idx = (jax.lax.axis_index(da[0]) * mesh.shape[da[1]]
                     + jax.lax.axis_index(da[1]))

        # ---- write current token's K/V into its pre-resolved slot -------
        tok = pos % bs
        own_tok = (tok // bs_loc) == m_idx
        t_loc = tok % bs_loc
        own = w_valid & own_tok
        # unowned rows scatter to an out-of-bounds slot and are DROPPED —
        # clamping them to slot 0 would collide with a real sequence's
        # block and clobber its fresh write (duplicate-index scatter)
        w_target = jnp.where(own, w_slot, kp.shape[0])
        kp = kp.at[w_target, t_loc].set(k_new.astype(kp.dtype),
                                        mode="drop")
        vp = vp.at[w_target, t_loc].set(v_new.astype(vp.dtype),
                                        mode="drop")

        # ---- paged attention over translated blocks ---------------------
        if batch_mode:
            block_tokens = bs
            tok_offset = m_idx * bs_loc
        else:
            block_tokens = mesh_G * bs
            tok_offset = g_idx * bs + m_idx * bs_loc
        o, m, l = paged_attention_ref(
            q, kp, vp, slots, pos + 1, tok_offset=tok_offset, tok_stride=1,
            block_tokens=block_tokens)
        combine = (ma,) if batch_mode else tuple(da) + (ma,)
        m_glob = jax.lax.pmax(m, combine)
        corr = jnp.exp(m - m_glob)
        o = jax.lax.psum(o * corr[..., None], combine)
        l = jax.lax.psum(l * corr, combine)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
        return out, kp, vp

    mesh_G = int(np.prod([mesh.shape[a] for a in da]))
    dspec = P(da) if batch_mode else P()
    in_specs = (
        P(da, None, None) if batch_mode else P(None, None, None),  # q
        P(da, None, None) if batch_mode else P(None, None, None),  # k_new
        P(da, None, None) if batch_mode else P(None, None, None),  # v_new
        P(da, ma, None, None),                                     # k_pool
        P(da, ma, None, None),                                     # v_pool
        P(da, None, None),                                         # slots
        P(da, None),                                               # w_slot
        P(da, None),                                               # w_valid
        dspec,                                                     # pos
    )
    out_specs = (
        P(da, None, None) if batch_mode else P(None, None, None),
        P(da, ma, None, None),
        P(da, ma, None, None),
    )
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(q, k_new, v_new, k_pool_l, v_pool_l, slots, w_slot, w_valid,
              pos)


# ------------------------------------- slot-sharded pool (SPMD engine)

def _psum_gather_blocks(pool, slots, part: Partition, model_axis: str,
                        layer=None):
    """Gather blocks by LOGICAL slot from the slot-sharded pool, exactly.

    Runs under shard_map over ``model_axis``: ``pool`` is this shard's
    contiguous physical-slot chunk ``(slots_per_shard, bs, KV, hd)`` or,
    with ``layer``, the whole ``(L, slots_per_shard, bs, KV, hd)`` carry,
    read at that layer in the same gather; ``slots`` the replicated
    logical slot ids (any leading shape, -1 =
    unmapped).  Each shard gathers the blocks it owns, then an INTEGER
    psum over the raw bits assembles the replicated result — float
    psums would tie bit-identity to reduction order; bit psums of
    disjoint one-hot contributions cannot.  Unowned / -1 rows contribute
    zero bits, so missing slots come back as all-zero blocks (which the
    valid-slot masking inside paged attention renders harmless).
    """
    m = jax.lax.axis_index(model_axis)
    cps = part.slots_per_shard
    phys = part.phys(slots)
    mine = (slots >= 0) & ((phys // cps) == m)
    idx = jnp.where(mine, phys - m * cps, 0)
    g = pool[idx] if layer is None else pool[layer, idx]
    mask = mine.reshape(mine.shape + (1,) * (g.ndim - mine.ndim))
    if g.dtype == jnp.float32:
        bits = jax.lax.bitcast_convert_type(g, jnp.int32)
        bits = jax.lax.psum(jnp.where(mask, bits, 0), model_axis)
        return jax.lax.bitcast_convert_type(bits, jnp.float32)
    # 16-bit dtypes (bf16/f16): widen the bit pattern to int32 for psum
    bits = jax.lax.bitcast_convert_type(g, jnp.uint16).astype(jnp.int32)
    bits = jax.lax.psum(jnp.where(mask, bits, 0), model_axis)
    return jax.lax.bitcast_convert_type(bits.astype(jnp.uint16), g.dtype)


def _paged_attn_shard_local(q, k_new, v_new, kp, vp, i,
                            trans: StepTranslation, pos,
                            spec: DecodeSpec, part: Partition):
    """Slot-sharded write + paged attention (runs under shard_map).

    The sharded twin of ``_paged_attn_local_ref``: the current token's
    K/V scatter into layer ``i`` of this shard's carried pools is
    ownership-masked (only the shard owning the physical slot writes;
    everyone else drops out of bounds), the block gather is the exact
    bit-psum assembly, and the attention math itself is the SAME
    replicated ``paged_attention_blocks`` — bitwise identical output to
    the mesh-free reference.
    """
    m = jax.lax.axis_index(spec.model_axis)
    cps = part.slots_per_shard
    slots = trans.slots[0]                          # (B, nblk) logical
    w_slot, w_valid = trans.w_slot[0], trans.w_valid[0]
    wp = part.phys(w_slot)
    kp, vp = _pool_write(kp, vp, i, wp - m * cps, pos % spec.block_size,
                         k_new, v_new, w_valid & ((wp // cps) == m))
    k = _psum_gather_blocks(kp, slots, part, spec.model_axis, i)
    v = _psum_gather_blocks(vp, slots, part, spec.model_axis, i)
    o, mx, l = paged_attention_blocks(q, k, v, slots, pos + 1)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out, kp, vp


# ---------------------------------------------- shared decode sublayers
#
# One definition each for the pieces the scalar decode step and the
# speculative verify step (serve/spec_decode.py) must keep EXACTLY in
# sync — the lossless spec contract rests on the two paths computing the
# same function.  All are rank-generic over the leading axes: the scalar
# step passes (B, D) activations, the verify step (B, K+1, D); the
# reshapes are identities for the scalar shapes, so the scalar trace is
# bitwise the pre-refactor one.

@jax.named_scope("kv_pool")
def _pool_write(kp, vp, i, ws, t, k_new, v_new, valid):
    """Write new tokens' K/V into layer ``i`` of the scan-carried
    ``(L, slots, bs, KV, hd)`` pools, in place, at ``[i, ws, t]``.

    The one pool write of every decode scan body (scalar, verify,
    slot-sharded): ``ws``/``t``/``valid`` share a leading shape — ``(B,)``
    or the verify window's ``(B, K+1)`` — and ``k_new``/``v_new`` add
    ``(KV, hd)``.  Rows that are not ``valid`` (unmapped, inactive, not
    this shard's) scatter to an out-of-bounds slot and are DROPPED —
    clamping them to slot 0 would collide with a live block and clobber
    its fresh write.
    """
    ws = jnp.where(valid, ws, kp.shape[1])
    return (kp.at[i, ws, t].set(k_new.astype(kp.dtype), mode="drop"),
            vp.at[i, ws, t].set(v_new.astype(vp.dtype), mode="drop"))


def _pool_blocks(pool, i, slots):
    """Layer ``i``'s blocks at ``slots`` (negative entries clamp to slot
    0 and are masked by paged attention), gathered straight from the
    carried ``(L, slots, ...)`` pool in ONE gather — ``pool[i][slots]``
    would slice the whole layer out first."""
    return pool[i, jnp.maximum(slots, 0)]


@jax.named_scope("ffn")
def decode_ffn(blk, x, cfg: ArchConfig, pins, valid=None):
    """Post-attention FFN sublayer (dense MLP or decode-time MoE) ->
    (x, MoE [routed pairs, experts hit] of the ``valid`` rows, or None
    for a dense MLP)."""
    h = Lmod.rms_norm(x, blk["norm2"].astype(jnp.float32), cfg.norm_eps)
    counts = None
    if "moe" in blk:
        lead = h.shape[:-1]
        out, counts = moe_decode(
            blk["moe"], h.reshape(-1, h.shape[-1]), top_k=cfg.moe_top_k,
            valid=None if valid is None else valid.reshape(-1), pins=pins)
        out = out.reshape(*lead, -1)
    else:
        out = Lmod.mlp(blk["mlp"], h, pins)
    return x + pins("dec_bd", out), counts


def decode_cross(blk, x, ck, cv, cfg: ArchConfig, dims: ModelDims, pins
                 ) -> jax.Array:
    """Audio cross-attention over the installed per-slot cross K/V."""
    lead = x.shape[:-1]                       # (B,) or (B, Q)
    B = lead[0]
    h = Lmod.rms_norm(x, blk["norm_x"].astype(jnp.float32), cfg.norm_eps)
    q = Lmod.linear(blk["cross"]["q"], h)
    g = dims.n_heads // dims.n_kv
    qf = q.reshape(B, -1, dims.n_kv, g,
                   dims.head_dim).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bfkd->bqkgf", qf, ck.astype(jnp.float32))
    s = s / math.sqrt(dims.head_dim)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bqkgf,bfkd->bqkgd", p, cv.astype(jnp.float32))
    o = o.reshape(*lead, -1).astype(x.dtype)
    return x + pins("dec_bd", Lmod.linear(blk["cross"]["o"], o))


@jax.named_scope("lm_head")
def project_logits(params, x, cfg: ArchConfig, dims: ModelDims, pins
                   ) -> jax.Array:
    """Final norm -> (tied) head matmul -> vocab-pad mask -> pins."""
    x = Lmod.rms_norm(x, params["final_norm"].astype(jnp.float32),
                      cfg.norm_eps)
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = x @ head["table"].T.astype(x.dtype)
    vpad = logits.shape[-1]
    if vpad > dims.logical_vocab:
        mask = jnp.arange(vpad) < dims.logical_vocab
        logits = jnp.where(mask, logits, jnp.asarray(-1e9, logits.dtype))
    return pins("dec_logits", logits)


# --------------------------------------------------------- full serve step

def make_serve_step(cfg: ArchConfig, dims: ModelDims, spec: DecodeSpec,
                    mesh: Optional[Mesh] = None, pins=Lmod.no_pins,
                    dtype=jnp.bfloat16, part: Optional[Partition] = None):
    """Returns serve_step(params, dstate, tokens (B,)) ->
    (logits (B, V), new dstate, stats).  One new token per live sequence.

    With ``mesh`` and ``spec.kv_shards >= 1`` (+ ``part``, the engine's
    SPMD layout) the WHOLE step body runs under one shard_map over the
    mesh: translation probes per-shard table ranges, the KV pool is
    slot-sharded, and all float compute is replicated — token streams
    stay bitwise identical to ``mesh=None`` (DESIGN.md
    §sharded-serving).  With ``kv_shards == 0`` a mesh selects the
    legacy token-split flash-decoding path (dryrun compile cells).

    ``stats`` carries the step's translation telemetry (``in_rest`` /
    ``accesses`` / ``mapped`` / ``slots``, all group-major) plus the
    greedy ``next_token`` (B,) — everything the engine needs from the
    device in ONE fetch.  Translation runs exactly once, before the layer
    scan (see ``translate_step``).

    ``active`` (B,) bool (optional) marks the batch slots that are
    decoding this step.  Inactive slots — mid-prefill under the chunked
    admission scheduler, released, or already finished — neither write
    their current KV block (their drifting position could land inside a
    *mapped* block another chunk just installed) nor advance ``ctx_len``.
    ``active=None`` (the pre-scheduler calling convention) treats every
    slot as live.
    """

    def qkv_decode(blk, x, positions, kind=None):
        B = x.shape[0]
        h = Lmod.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
        q = Lmod.linear(blk["attn"]["q"], h).reshape(B, dims.n_heads,
                                                     dims.head_dim)
        k = Lmod.linear(blk["attn"]["k"], h).reshape(B, dims.n_kv,
                                                     dims.head_dim)
        v = Lmod.linear(blk["attn"]["v"], h).reshape(B, dims.n_kv,
                                                     dims.head_dim)
        if kind is not None or cfg.rope_theta > 0:
            rope = () if kind is None else (kind["inv_freq"],
                                            kind["rope_scale"])
            q = Lmod.apply_rope(q[:, None], positions[:, None],
                                cfg.rope_theta, *rope)[:, 0]
            k = Lmod.apply_rope(k[:, None], positions[:, None],
                                cfg.rope_theta, *rope)[:, 0]
        return q, k, v

    sharded = mesh is not None and spec.kv_shards >= 1
    if sharded and part is None:
        raise ValueError("spec.kv_shards >= 1 requires a Partition")
    if mesh is not None and cfg.mixed_attention:
        raise NotImplementedError(
            "the mesh decode paths have no per-layer window or rotary "
            f"table; serve {cfg.name} without a mesh")

    @jax.named_scope("attn")
    def attn_sublayer(blk, x, kp, vp, i, trans, positions, kind=None):
        """Attention of layer ``i``; ``kp``/``vp`` are the carried
        ``(L, ...)`` pools, returned with this layer's write done."""
        B = x.shape[0]
        q, k, v = qkv_decode(blk, x, positions, kind)
        if sharded:
            out, kp, vp = _paged_attn_shard_local(
                q, k, v, kp, vp, i, trans, positions, spec, part)
        elif mesh is not None:
            # the legacy token-split path serves no cell (dryrun compile
            # cells only) and keeps a local slice of the layer's pools
            kp_l = jax.lax.dynamic_index_in_dim(kp, i, 0, keepdims=False)
            vp_l = jax.lax.dynamic_index_in_dim(vp, i, 0, keepdims=False)
            out, kp_l, vp_l = _paged_attn_shardmap(
                q, k, v, kp_l, vp_l, trans.slots, trans.w_slot,
                trans.w_valid, positions,
                spec=spec, mesh=mesh, n_kv=dims.n_kv, head_dim=dims.head_dim)
            kp = jax.lax.dynamic_update_index_in_dim(kp, kp_l, i, 0)
            vp = jax.lax.dynamic_update_index_in_dim(vp, vp_l, i, 0)
        else:
            out, kp, vp = _paged_attn_local_ref(
                q, k, v, kp, vp, i, trans, positions, spec,
                window=None if kind is None else kind["window"])
        o = Lmod.linear(blk["attn"]["o"], out.reshape(B, -1).astype(x.dtype))
        return x + pins("dec_bd", o), kp, vp

    def ffn_sublayer(blk, x, act):
        return decode_ffn(blk, x, cfg, pins, valid=act)

    def mamba_sublayer(blk, x, ssm, conv):
        h = Lmod.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
        out, cache = mamba_decode_step(
            blk["mamba"], h, MambaCache(conv=conv, state=ssm), dims.mamba)
        return x + pins("dec_bd", out), cache.state, cache.conv

    def cross_sublayer(blk, x, ck, cv, ctx_valid):
        return decode_cross(blk, x, ck, cv, cfg, dims, pins)

    n_attn = sum(cfg.attn_on_layer(l) for l in range(cfg.num_layers))

    def serve_step(params, dstate, tokens, active=None, *, sample=False):
        positions = dstate["ctx_len"]
        act = (jnp.ones_like(positions, jnp.bool_) if active is None
               else active.astype(jnp.bool_))
        x = jnp.take(params["embed"]["table"], tokens, axis=0).astype(dtype)
        x = pins("dec_bd", x)
        fam = cfg.family
        new_state = dict(dstate)
        stats: Dict[str, jax.Array] = {}

        # ---- the step's single translation dispatch ----------------------
        trans = None
        if n_attn:
            with jax.named_scope("translate"):
                if sharded:
                    trans = translate_step_sharded(
                        dstate["tar"], dstate["sf"], dstate["flex"],
                        positions, spec, part)
                else:
                    trans = translate_step(dstate["tar"], dstate["sf"],
                                           dstate["flex"], positions, spec)
                # group-major view of the active mask gates the KV write
                G = dstate["tar"].shape[0]
                if spec.mode == "batch":
                    act_g = act.reshape(G, -1)
                else:
                    act_g = jnp.broadcast_to(act[None, :],
                                             (G, act.shape[0]))
                trans = trans._replace(w_valid=trans.w_valid & act_g)
            stats.update(slots=trans.slots, in_rest=trans.in_rest,
                         mapped=trans.mapped, accesses=trans.accesses)

        n_layers = cfg.num_layers
        if fam in ("dense", "moe", "vlm", "audio"):
            # KV pools ride in the scan CARRY at their full (L, ...)
            # shape (single live buffer; xs/ys would double-buffer the
            # multi-TB pool): each layer scatters its tokens' K/V into
            # the carry in place and gathers its blocks from it
            xs = {"blk": params["layers"],
                  "idx": jnp.arange(n_layers, dtype=jnp.int32)}
            if fam == "audio":
                xs["ck"] = dstate["cross_k"]
                xs["cv"] = dstate["cross_v"]
            if cfg.mixed_attention:
                xs["kind"] = Tmod.layer_kinds(cfg, dims.head_dim)

            def body(carry, xl):
                x, kp, vp = carry
                blk = xl["blk"]
                i = xl["idx"]
                x, kp, vp = attn_sublayer(blk, x, kp, vp, i, trans,
                                          positions, xl.get("kind"))
                if fam == "audio":
                    x = cross_sublayer(blk, x, xl["ck"], xl["cv"], None)
                x, counts = ffn_sublayer(blk, x, act)
                return (x, kp, vp), counts

            (x, kp, vp), counts = jax.lax.scan(
                body, (x, dstate["k_pool"], dstate["v_pool"]), xs)
            new_state["k_pool"], new_state["v_pool"] = kp, vp
            if counts is not None:
                stats["moe"] = counts.sum(axis=0)
        elif fam == "ssm":
            xs = {"blk": params["layers"], "ssm": dstate["ssm"],
                  "conv": dstate["conv"]}

            def body(x, xl):
                x, s, c = mamba_sublayer(xl["blk"], x, xl["ssm"], xl["conv"])
                return x, {"ssm": s, "conv": c}

            x, ys = jax.lax.scan(body, x, xs)
            # inactive rows keep their recurrent state (the scan advanced
            # every row with whatever token the engine padded in)
            new_state["ssm"] = jnp.where(
                act[None, :, None, None, None], ys["ssm"], dstate["ssm"])
            new_state["conv"] = jnp.where(
                act[None, :, None, None], ys["conv"], dstate["conv"])
        elif fam == "hybrid":
            g = cfg.attn_every
            n_groups = cfg.num_layers // g
            n_mamba = g - 1
            xs = {"blk": params["layers"],
                  "idx": jnp.arange(n_groups, dtype=jnp.int32),
                  "ssm": dstate["ssm"].reshape(
                      (n_groups, n_mamba) + dstate["ssm"].shape[1:]),
                  "conv": dstate["conv"].reshape(
                      (n_groups, n_mamba) + dstate["conv"].shape[1:])}

            def body(carry, xl):
                x, kp, vp = carry
                blk = xl["blk"]
                gi = xl["idx"]
                ssm_out, conv_out = [], []
                for i in range(g):
                    if i < g - 1:
                        sub = jax.tree.map(lambda a, i=i: a[i], blk["mamba"])
                        x, s, c = mamba_sublayer(sub, x, xl["ssm"][i],
                                                 xl["conv"][i])
                        ssm_out.append(s)
                        conv_out.append(c)
                    else:
                        x, kp, vp = attn_sublayer(
                            blk["attn"], x, kp, vp, gi, trans, positions)
                    x, _ = ffn_sublayer(Tmod.hybrid_ffn_select(cfg, blk, i),
                                        x, act)
                return (x, kp, vp), {"ssm": jnp.stack(ssm_out),
                                     "conv": jnp.stack(conv_out)}

            (x, kp, vp), ys = jax.lax.scan(
                body, (x, dstate["k_pool"], dstate["v_pool"]), xs)
            new_state["k_pool"], new_state["v_pool"] = kp, vp
            new_state["ssm"] = jnp.where(
                act[None, :, None, None, None],
                ys["ssm"].reshape(dstate["ssm"].shape), dstate["ssm"])
            new_state["conv"] = jnp.where(
                act[None, :, None, None],
                ys["conv"].reshape(dstate["conv"].shape), dstate["conv"])
        else:
            raise ValueError(fam)

        logits = project_logits(params, x, cfg, dims, pins)
        # per-slot sampling in-graph: the engine reads token ids, not the
        # (B, V) logits, so the per-step fetch stays O(B).  Greedy rows
        # (samp_temp == 0) take the exact argmax path; sampled rows fold
        # the slot's PRNG key with the pre-step position, making a token
        # a pure function of (seed, position) — independent of admission
        # schedule or batch composition.  ``sample`` is trace-static
        # (jit static_argnames): an all-greedy batch compiles an
        # argmax-only executable with none of the sort/softmax/gumbel
        # work on its hot path.  The default is False so callers that
        # never pass it (dryrun cost cells, direct step tests) keep the
        # pre-sampling argmax trace; the engine passes it explicitly
        with jax.named_scope("sample"):
            if sample:
                stats["next_token"] = sample_tokens(
                    logits, dstate["samp_temp"], dstate["samp_topk"],
                    dstate["samp_topp"], dstate["samp_key"], positions)
            else:
                stats["next_token"] = jnp.argmax(logits, axis=-1
                                                 ).astype(jnp.int32)
        # only active slots advance: an idle slot's ctx_len must not drift
        # (pre-scheduler it advanced unconditionally, which is why the
        # stale-write bound in translate_step exists)
        new_state["ctx_len"] = (dstate["ctx_len"]
                                + act.astype(dstate["ctx_len"].dtype))
        return logits, new_state, stats

    if not sharded:
        return serve_step

    def serve_step_sharded(params, dstate, tokens, active=None, *,
                           sample=False):
        # the whole step under ONE shard_map: params and batch arrays
        # replicated (P() prefix-broadcasts over the pytrees), decode
        # state per kv_state_specs.  ``sample`` is trace-static, so the
        # shard_map is (re)built per sample value under the engine's
        # static_argnames jit — same retrace behaviour as the local step.
        act = (jnp.ones_like(dstate["ctx_len"], jnp.bool_) if active is None
               else active.astype(jnp.bool_))
        sspecs = kv_state_specs(dstate, spec)
        fn = jax.shard_map(
            functools.partial(serve_step, sample=sample),
            mesh=mesh, in_specs=(P(), sspecs, P(), P()),
            out_specs=(P(), sspecs, P()), check_vma=False)
        return fn(params, dstate, tokens, act)

    return serve_step_sharded


# ------------------------------------------------ single-device reference

def _paged_attn_local_ref(q, k_new, v_new, kp, vp, i,
                          trans: StepTranslation, pos,
                          spec: DecodeSpec, window=None):
    """Mesh-free reference used by the engine on one device (G=1, TP=1).

    Consumes the pre-resolved ``StepTranslation`` — no translation here.
    Writes the current token into layer ``i`` of the carried pools and
    attends over that layer's blocks gathered from them (within
    ``window`` of the new token, on a sliding-window layer).
    """
    slots = trans.slots[0]                          # (B, nblk)
    kp, vp = _pool_write(kp, vp, i, trans.w_slot[0], pos % spec.block_size,
                         k_new, v_new, trans.w_valid[0])
    o, m, l = paged_attention_blocks(q, _pool_blocks(kp, i, slots),
                                     _pool_blocks(vp, i, slots),
                                     slots, pos + 1, window=window)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype)
    return out, kp, vp
