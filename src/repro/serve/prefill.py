"""SPMD prefill steps: install prompt caches into the hybrid KV pool.

Two ways to admit a prompt chunk, sharing the install/scatter machinery:

* ``make_prefill_step`` — the full-(re)compute forward: the batch rows
  hold the WHOLE prefix up to the chunk end; the training-style forward
  recomputes every position and only the chunk's new blocks are
  installed.  Exact, but a chunked admission pays O(chunks²) compute.
* ``make_prefix_prefill_step`` — the prefix-KV chunk forward (chunk
  k > 0): the rows hold ONLY the chunk's new tokens; attention layers
  attend over (a) the prefix's already-installed pool blocks, gathered
  through the translated ``prefix_slots``, concatenated with (b) the
  chunk's own causal K/V — while recurrent (SSM/conv) layers continue
  from the saved per-slot state instead of recomputing it.  Chunk cost
  is linear in chunk length, independent of how long the prefix already
  is.

Re-admission after preemption (PR 6) enters through the SAME two steps:
a resumed sequence's saved KV is scattered back bitwise first, then any
not-yet-prefilled prompt tail continues as ordinary chunks — chunk k>0
prefix-KV against the restored blocks, no recompute.  There is no
separate resume forward; graceful degradation reuses this machinery.

Prefix-cache hits (PR 8, DESIGN.md §prefix-cache) enter the same way:
a request whose leading blocks matched the content-addressed cache
starts prefill AT THE TAIL — its first chunk is already a k>0 chunk
whose ``prefix_slots`` point at the cache-attached read-only blocks.
No prefill step knows about the cache; it only ever sees installed
prefix blocks, which is why cache-on streams are bit-identical to
cache-off (the PR-4 installed==recomputed pin carries the contract).

One dispatch admits a whole *bucket* of sequences: the prompts' K/V are
computed by the forward, then scattered into the pool slots the manager
translated (``slots`` input, produced host-side by fault-based
allocation) for ALL sequences at once.  The scatter runs inside
shard_map so every write is local to the (data-group, token-shard) that
owns the slot.

Calling convention shared by both steps (the admission scheduler's
contract):

* ``batch["tokens"]`` (B, S) — right-padded token rows.  Causal
  attention makes right padding safe: position t never attends beyond t,
  so every real position's activations are exact regardless of the pad
  tail.
* ``slots`` / ``new_slots`` (B, nblk) int32 — pool slot per cache block
  to install; ``-1`` blocks are DROPPED (pad blocks, blocks a previous
  chunk already installed, prefix-shared blocks).  The recompute step
  indexes blocks absolutely; the prefix step indexes them chunk-locally.
* ``slot_ids`` (B,) int32 — the batch slot each row belongs to; ``-1``
  rows (bucket padding) write nothing at all.
* ``ctx`` (B,) int32 — the post-install context length per row.
* ``last_pos`` (B,) int32 — index of the final real token in the logits
  sequence dim (per-row: rows are padded to the bucket length).

``ctx_len`` is scattered to PARTICIPATING slots only.  The pre-fix code
did ``jnp.full_like(dstate["ctx_len"], ctx)`` — stomping the context
length of every live sequence in the batch, which is what broke
continuous batching (ISSUE 2's headline bug).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import (FwdOptions, forward, dense_attention,
                          causal_attention_parts, merge_attention_parts)
from repro.models import layers as Lmod
from repro.models.layers import no_pins
from repro.models.ssm import MambaCache, mamba_forward
from repro.models.transformer import (ModelDims, _ffn, hybrid_ffn_select,
                                      layer_kinds)
from repro.core.partition import Partition
from repro.dist.sharding import kv_state_specs
from repro.kernels.paged_attention.ref import (gather_pool_blocks,
                                               paged_attention_ref,
                                               paged_attention_blocks)
from .decode import DecodeSpec, _psum_gather_blocks
from .sampling import sample_tokens


def _scatter_pool(pool, cache, slots, mesh: Mesh, spec: DecodeSpec):
    """pool (L, G*slots, bs, KV, hd)  P(None, da, ma, None, None)
    cache (L, B, nblk, bs, KV, hd)    P(None, da, None, ma, None, None)
    slots (B, nblk) int32             P(da, None)
    """
    da, ma = spec.data_axes, spec.model_axis

    def local(pool, cache, slots):
        L = pool.shape[0]
        Bl, nblk = slots.shape
        flat = cache.reshape(L, Bl * nblk, *cache.shape[3:])
        sl = slots.reshape(-1)
        idx = jnp.where(sl >= 0, sl, pool.shape[1])  # invalid -> dropped
        return pool.at[:, idx].set(flat.astype(pool.dtype), mode="drop")

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(None, da, ma, None, None),
                  P(None, da, None, ma, None, None),
                  P(da, None)),
        out_specs=P(None, da, ma, None, None),
        check_vma=False)
    return fn(pool, cache, slots)


# --------------------------------------------------- shared install logic

@jax.named_scope("kv_install")
def _install_kv(spec, mesh, dstate, new_state, caches, eff_slots, B,
                part: Optional[Partition] = None):
    """Scatter per-layer chunk K/V (L, B, S, KV, hd) into the pool at
    ``eff_slots`` (B, nblk); -1 entries (pads / already-installed /
    shared blocks) are dropped, never clamped.

    With ``part`` (running under the SPMD engine's whole-step shard_map)
    the scatter is ownership-masked: each shard converts the logical
    slots to physical, keeps only the ones inside its own chunk, and
    drops the rest out of bounds — installs route only to the owning
    shard, bitwise the same blocks the local path writes.
    """
    k, v = caches["k"], caches["v"]              # (L_attn, B, S_tot, KV, hd)
    L, _, S_tot, KV, hd = k.shape
    bs = spec.block_size
    nblk = S_tot // bs
    k = k.reshape(L, B, nblk, bs, KV, hd)
    v = v.reshape(L, B, nblk, bs, KV, hd)
    if part is not None:
        m = jax.lax.axis_index(spec.model_axis)
        cps = part.slots_per_shard
        sl = eff_slots.reshape(-1)
        ph = part.phys(sl)
        mine = (sl >= 0) & ((ph // cps) == m)
        idx = jnp.where(mine, ph - m * cps, dstate["k_pool"].shape[1])
        new_state["k_pool"] = dstate["k_pool"].at[:, idx].set(
            k.reshape(L, B * nblk, bs, KV, hd
                      ).astype(dstate["k_pool"].dtype), mode="drop")
        new_state["v_pool"] = dstate["v_pool"].at[:, idx].set(
            v.reshape(L, B * nblk, bs, KV, hd
                      ).astype(dstate["v_pool"].dtype), mode="drop")
    elif mesh is not None:
        con = NamedSharding(mesh, P(None, spec.data_axes, None,
                                    spec.model_axis, None, None))
        k = jax.lax.with_sharding_constraint(k, con)
        v = jax.lax.with_sharding_constraint(v, con)
        new_state["k_pool"] = _scatter_pool(
            dstate["k_pool"], k, eff_slots, mesh, spec)
        new_state["v_pool"] = _scatter_pool(
            dstate["v_pool"], v, eff_slots, mesh, spec)
    else:
        sl = eff_slots.reshape(-1)
        # -1 -> out-of-bounds, dropped (clamping to 0 would clobber
        # whichever live sequence owns pool slot 0)
        idx = jnp.where(sl >= 0, sl, dstate["k_pool"].shape[1])
        new_state["k_pool"] = dstate["k_pool"].at[:, idx].set(
            k.reshape(L, B * nblk, bs, KV, hd
                      ).astype(dstate["k_pool"].dtype), mode="drop")
        new_state["v_pool"] = dstate["v_pool"].at[:, idx].set(
            v.reshape(L, B * nblk, bs, KV, hd
                      ).astype(dstate["v_pool"].dtype), mode="drop")


def _install_recurrent(dstate, new_state, mc, sid, B):
    """Install per-row SSM/conv states at ``sid`` (pad rows scatter out of
    bounds and drop)."""
    state = mc.state if hasattr(mc, "state") else mc
    conv = mc.conv if hasattr(mc, "conv") else None
    st = state.reshape((-1, B) + dstate["ssm"].shape[2:])
    cv = conv.reshape((-1, B) + dstate["conv"].shape[2:])
    new_state["ssm"] = dstate["ssm"].at[:, sid].set(st, mode="drop")
    new_state["conv"] = dstate["conv"].at[:, sid].set(
        cv.astype(dstate["conv"].dtype), mode="drop")


@jax.named_scope("sample")
def _first_token_stats(dstate, last, sid, ctx, n_slots, sample):
    """First generated token per row, computed in-graph so the engine can
    fold it into its single per-step device fetch.

    Sampled rows use the row's per-slot SamplingParams (scattered by the
    engine BEFORE the dispatch).  Fold position is ctx - 1: a token
    sampled from k context tokens folds k - 1, matching the decode step
    (pre-step ctx_len = k) so the stream is chunking- and
    schedule-independent.  Padding rows gather slot 0's params; their
    token is never read.  ``sample`` is trace-static, default False: an
    all-greedy bucket keeps the pre-sampling argmax-only trace.
    """
    if sample:
        sid_safe = jnp.clip(sid, 0, n_slots - 1)
        fold = jnp.maximum(ctx.astype(jnp.int32) - 1, 0)
        return {"next_token": sample_tokens(
            last, dstate["samp_temp"][sid_safe],
            dstate["samp_topk"][sid_safe], dstate["samp_topp"][sid_safe],
            dstate["samp_key"][sid_safe], fold)}
    return {"next_token": jnp.argmax(last, axis=-1).astype(jnp.int32)}


# ------------------------------------------------- full-(re)compute step

def make_prefill_step(cfg: ArchConfig, dims: ModelDims, spec: DecodeSpec,
                      mesh: Optional[Mesh] = None, pins=no_pins,
                      fwd: FwdOptions = FwdOptions(),
                      part: Optional[Partition] = None):
    """Returns prefill_step(params, dstate, batch, slots, slot_ids, ctx,
    last_pos) -> (last_logits (B, V), new dstate, stats).

    ``stats["next_token"]`` is the first generated token per row, computed
    in-graph (see ``_first_token_stats``).  With ``spec.kv_shards >= 1``
    (+ ``part``) the whole step runs under one shard_map over ``mesh``:
    the forward is replicated, only the pool scatter is ownership-routed
    (DESIGN.md §sharded-serving) — logits and installed blocks stay
    bitwise identical to ``mesh=None``.
    """
    fwd_collect = FwdOptions(**{**fwd.__dict__, "collect_cache": True})
    sharded = mesh is not None and spec.kv_shards >= 1
    if sharded and part is None:
        raise ValueError("spec.kv_shards >= 1 requires a Partition")
    part_in = part if sharded else None

    def prefill_step(params, dstate, batch, slots, slot_ids, ctx, last_pos,
                     *, sample=False):
        if cfg.moe_num_experts and "seq_len" not in batch:
            # bucket padding stays out of the drop-free MoE
            batch = {**batch, "seq_len": jnp.where(slot_ids >= 0, ctx, 0)}
        logits, aux, caches = forward(params, batch, cfg, dims, fwd_collect,
                                      pins)
        new_state = dict(dstate)
        B = batch["tokens"].shape[0]
        row_ok = slot_ids >= 0
        n_slots = dstate["ctx_len"].shape[0]
        # padding rows scatter out of bounds and are dropped
        sid = jnp.where(row_ok, slot_ids, n_slots).astype(jnp.int32)

        if caches.get("k") is not None and "k_pool" in dstate:
            eff_slots = jnp.where(row_ok[:, None], slots, -1)
            _install_kv(spec, None if sharded else mesh, dstate, new_state,
                        caches, eff_slots, B, part=part_in)
        if "ssm" in dstate and caches.get("ssm") is not None:
            _install_recurrent(dstate, new_state, caches["ssm"], sid, B)
        if cfg.is_encoder_decoder and "cross_k" in dstate:
            new_state["cross_k"] = dstate["cross_k"].at[:, sid].set(
                caches["ck"].astype(dstate["cross_k"].dtype), mode="drop")
            new_state["cross_v"] = dstate["cross_v"].at[:, sid].set(
                caches["cv"].astype(dstate["cross_v"].dtype), mode="drop")

        # THE bugfix: scatter ctx_len to participating slots only — never
        # touch the other sequences' context lengths
        new_state["ctx_len"] = dstate["ctx_len"].at[sid].set(
            ctx.astype(dstate["ctx_len"].dtype), mode="drop")

        last = jnp.take_along_axis(
            logits, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        stats = _first_token_stats(dstate, last, sid, ctx, n_slots, sample)
        if cfg.moe_num_experts:
            stats["moe"] = aux["moe_counts"]
        return last, new_state, stats

    if not sharded:
        return prefill_step

    def prefill_step_sharded(params, dstate, batch, slots, slot_ids, ctx,
                             last_pos, *, sample=False):
        sspecs = kv_state_specs(dstate, spec)
        fn = jax.shard_map(
            functools.partial(prefill_step, sample=sample),
            mesh=mesh, in_specs=(P(), sspecs) + (P(),) * 5,
            out_specs=(P(), sspecs, P()), check_vma=False)
        return fn(params, dstate, batch, slots, slot_ids, ctx, last_pos)

    return prefill_step_sharded


# ---------------------------------------------------- prefix-KV chunk step

def make_prefix_prefill_step(cfg: ArchConfig, dims: ModelDims,
                             spec: DecodeSpec,
                             mesh: Optional[Mesh] = None, pins=no_pins,
                             fwd: FwdOptions = FwdOptions(),
                             gather: Optional[str] = None,
                             part: Optional[Partition] = None):
    """Chunk-k (k > 0) prefill: forward ONLY the chunk's new tokens.

    Returns prefix_prefill_step(params, dstate, batch, new_slots,
    prefix_slots, slot_ids, ctx, prefix_ctx, last_pos) ->
    (last_logits (B, V), new dstate, stats) where

    * ``batch["tokens"]`` (B, S) — the chunk's tokens only, right-padded;
    * ``new_slots`` (B, S_pad/bs) — install slot per CHUNK-LOCAL block;
    * ``prefix_slots`` (B, nblk_buf) — the translated pool slot of every
      absolute block below the row's prefix (entries at/after the chunk
      start, and pad rows, are -1);
    * ``prefix_ctx`` (B,) — installed prefix tokens (frontend included):
      the absolute position of the chunk's first token;
    * ``ctx`` (B,) — post-install context length (= prefix_ctx + take).

    Attention layers attend over the gathered prefix blocks concatenated
    with the chunk's own causal K/V; recurrent layers continue from the
    per-slot saved ssm/conv state (state passing, no recompute); audio
    decoders read the installed per-layer cross K/V instead of re-running
    the encoder.  With ``gather="exact"`` (the default, via
    ``spec.prefix_gather``) the combined K/V is materialized at its
    absolute block positions and fed to the SAME dense softmax as the
    recompute forward — installed blocks and logits are bit-identical to
    full recompute, which is the differential-oracle contract.
    ``gather="paged"`` instead reads the pool through the Q>1
    ``kernels/paged_attention`` path (ref, or Pallas when
    ``spec.use_kernels``) and merges with the chunk-causal part by an
    online-softmax combine — O(chunk) memory and kernel-ready, equal to
    "exact" up to float associativity.
    """
    sharded = mesh is not None and spec.kv_shards >= 1
    if mesh is not None and not sharded:
        raise NotImplementedError(
            "prefix-KV prefill is single-host for now; the SPMD admission "
            "path (ROADMAP) still drives the recompute prefill")
    if sharded and part is None:
        raise ValueError("spec.kv_shards >= 1 requires a Partition")
    if gather is None:
        gather = spec.prefix_gather
    if gather not in ("exact", "paged"):
        raise ValueError(f"unknown prefix gather impl {gather!r}")
    if sharded and spec.use_kernels:
        raise NotImplementedError(
            "Pallas prefix gather is single-device; the sharded engine "
            "drives the ref path")
    if cfg.mixed_attention and gather != "exact":
        raise NotImplementedError(
            "the paged prefix gather has no per-layer window or rotary "
            "table; use prefix_gather='exact' for this model")
    opt = fwd
    bs = spec.block_size
    fam = cfg.family

    @jax.named_scope("prefix_read")
    def attn_read(q, k_new, v_new, kp_l, vp_l, prefix_slots, prefix_ctx,
                  window=None):
        B, S, H, hd = q.shape
        KV = k_new.shape[2]
        if gather == "paged":
            if sharded:
                # exact bit-psum assembly of the owned blocks, then the
                # SAME replicated Q>1 attention math
                gk = _psum_gather_blocks(kp_l, prefix_slots, part,
                                         spec.model_axis)
                gv = _psum_gather_blocks(vp_l, prefix_slots, part,
                                         spec.model_axis)
                pool = paged_attention_blocks(q, gk, gv, prefix_slots,
                                              prefix_ctx)
            elif spec.use_kernels:
                from repro.kernels.paged_attention.paged_attention import (
                    paged_attention_pallas)
                # the Mosaic-lowered TPU kernel; the interpreter is for
                # the kernel tests only
                pool = paged_attention_pallas(
                    q, kp_l, vp_l, prefix_slots, prefix_ctx,
                    interpret=False)
            else:
                pool = paged_attention_ref(q, kp_l, vp_l, prefix_slots,
                                           prefix_ctx)
            own = causal_attention_parts(q, k_new, v_new)
            return merge_attention_parts([pool, own]).astype(q.dtype)
        # exact: place [gathered prefix | chunk K/V] at their absolute
        # block positions and run the recompute forward's own softmax
        nblk_buf = prefix_slots.shape[1]
        nblk_chunk = S // bs
        if sharded:
            # missing (-1) blocks come back all-zero from the bit-psum
            # gather; the ok-mask below zeroes them again (idempotent),
            # so this is bitwise the clamp-gather + mask of mesh=None
            gk = _psum_gather_blocks(kp_l, prefix_slots, part,
                                     spec.model_axis)
            gv = _psum_gather_blocks(vp_l, prefix_slots, part,
                                     spec.model_axis)
        else:
            gk = gather_pool_blocks(kp_l, prefix_slots)  # (B,nbuf,bs,KV,hd)
            gv = gather_pool_blocks(vp_l, prefix_slots)
        ok = (prefix_slots >= 0)[..., None, None, None]
        gk = jnp.where(ok, gk, 0.0).astype(k_new.dtype)
        gv = jnp.where(ok, gv, 0.0).astype(v_new.dtype)
        ck = k_new.reshape(B, nblk_chunk, bs, KV, hd)
        cv = v_new.reshape(B, nblk_chunk, bs, KV, hd)
        start_blk = (prefix_ctx // bs).astype(jnp.int32)
        j = jnp.arange(nblk_buf, dtype=jnp.int32)
        is_prefix = j[None, :] < start_blk[:, None]
        cj = jnp.clip(j[None, :] - start_blk[:, None], 0, nblk_chunk - 1)
        ck_g = jnp.take_along_axis(ck, cj[..., None, None, None], axis=1)
        cv_g = jnp.take_along_axis(cv, cj[..., None, None, None], axis=1)
        sel = is_prefix[..., None, None, None]
        # buffer blocks past the row's chunk end hold clipped duplicates;
        # they sit above every real query position, so the causal mask
        # removes them exactly (same tail-padding argument as the pow2
        # length buckets)
        k_full = jnp.where(sel, gk, ck_g).reshape(B, nblk_buf * bs, KV, hd)
        v_full = jnp.where(sel, gv, cv_g).reshape(B, nblk_buf * bs, KV, hd)
        return dense_attention(q, k_full, v_full, causal=True,
                               q_offset=prefix_ctx, window=window)

    @jax.named_scope("attn")
    def attn_sublayer(blk, x, kp_l, vp_l, prefix_slots, positions,
                      prefix_ctx, kind=None):
        B, S, _ = x.shape
        h = Lmod.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
        h = pins("act_full", h)
        rope = () if kind is None else (kind["inv_freq"],
                                        kind["rope_scale"])
        q, k, v = Lmod.qkv_project(blk["attn"], h, h, dims.n_heads,
                                   dims.n_kv, dims.head_dim, positions,
                                   positions, cfg.rope_theta, pins,
                                   rope=rope)
        o = attn_read(q, k, v, kp_l, vp_l, prefix_slots, prefix_ctx,
                      None if kind is None else kind["window"])
        o = Lmod.linear(blk["attn"]["o"], o.reshape(B, S, -1))
        return x + pins("act_btd", o), (k, v)

    def mamba_sublayer(blk, x, ssm0, conv0, chunk_len):
        h = Lmod.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
        h = pins("act_full", h)
        out, cache = mamba_forward(blk["mamba"], h, dims.mamba,
                                   chunk=cfg.ssm_chunk, pins=pins,
                                   initial_state=ssm0, initial_conv=conv0,
                                   seq_len=chunk_len, return_state=True)
        return x + pins("act_btd", out), cache

    def cross_sublayer(blk, x, ck, cv):
        B, S, _ = x.shape
        h = Lmod.rms_norm(x, blk["norm_x"].astype(jnp.float32), cfg.norm_eps)
        q = Lmod.linear(blk["cross"]["q"], h).reshape(B, S, dims.n_heads,
                                                      dims.head_dim)
        o = dense_attention(q, ck, cv, causal=False)
        return x + pins("act_btd",
                        Lmod.linear(blk["cross"]["o"], o.reshape(B, S, -1)))

    def prefix_prefill_step(params, dstate, batch, new_slots, prefix_slots,
                            slot_ids, ctx, prefix_ctx, last_pos, *,
                            sample=False):
        tokens = batch["tokens"]
        B, S = tokens.shape
        x = Lmod.embed(params["embed"], tokens, pins).astype(opt.dtype)
        positions = (prefix_ctx[:, None].astype(jnp.int32)
                     + jnp.arange(S, dtype=jnp.int32)[None, :])
        row_ok = slot_ids >= 0
        n_slots = dstate["ctx_len"].shape[0]
        sid = jnp.where(row_ok, slot_ids, n_slots).astype(jnp.int32)
        sid_safe = jnp.clip(slot_ids, 0, n_slots - 1)
        # per-row real chunk length: the recurrent mask that makes the
        # pow2 pad tail an exact identity transition of the SSM state
        chunk_len = (ctx - prefix_ctx).astype(jnp.int32)
        # the chunk's real tokens: bucket padding stays out of the MoE
        tok_ok = None
        if cfg.moe_num_experts:
            tok_ok = ((jnp.arange(S, dtype=jnp.int32)[None, :]
                       < chunk_len[:, None]) & row_ok[:, None])
        moe_counts = None

        if fam in ("dense", "moe", "vlm"):
            xs = {"blk": params["layers"],
                  "kp": dstate["k_pool"], "vp": dstate["v_pool"]}
            if cfg.mixed_attention:
                xs["kind"] = layer_kinds(cfg, dims.head_dim)

            def body(x, xl):
                x, (k, v) = attn_sublayer(xl["blk"], x, xl["kp"], xl["vp"],
                                          prefix_slots, positions,
                                          prefix_ctx, xl.get("kind"))
                x, aux = _ffn(xl["blk"], x, cfg, dims, opt, pins,
                              valid=tok_ok)
                ys = {"k": k, "v": v}
                if aux is not None:
                    ys["moe"] = aux["moe_counts"]
                return x, ys

            x, ys = jax.lax.scan(body, x, xs)
            caches = {"k": ys["k"], "v": ys["v"]}
            if "moe" in ys:
                moe_counts = ys["moe"].sum(axis=0)
        elif fam == "ssm":
            xs = {"blk": params["layers"],
                  "ssm": dstate["ssm"][:, sid_safe],
                  "conv": dstate["conv"][:, sid_safe]}

            def body(x, xl):
                x, cache = mamba_sublayer(xl["blk"], x, xl["ssm"],
                                          xl["conv"], chunk_len)
                return x, {"state": cache.state, "conv": cache.conv}

            x, ys = jax.lax.scan(body, x, xs)
            caches = {"ssm": MambaCache(conv=ys["conv"], state=ys["state"])}
        elif fam == "hybrid":
            g = cfg.attn_every
            n_groups = cfg.num_layers // g
            n_mamba = g - 1
            xs = {"blk": params["layers"],
                  "kp": dstate["k_pool"], "vp": dstate["v_pool"],
                  "ssm": dstate["ssm"][:, sid_safe].reshape(
                      (n_groups, n_mamba, B) + dstate["ssm"].shape[2:]),
                  "conv": dstate["conv"][:, sid_safe].reshape(
                      (n_groups, n_mamba, B) + dstate["conv"].shape[2:])}

            def body(x, xl):
                blk = xl["blk"]
                ssm_out, conv_out = [], []
                k = v = None
                for i in range(g):
                    if i < g - 1:
                        sub = jax.tree.map(lambda a, i=i: a[i], blk["mamba"])
                        x, cache = mamba_sublayer(sub, x, xl["ssm"][i],
                                                  xl["conv"][i], chunk_len)
                        ssm_out.append(cache.state)
                        conv_out.append(cache.conv)
                    else:
                        x, (k, v) = attn_sublayer(
                            blk["attn"], x, xl["kp"], xl["vp"],
                            prefix_slots, positions, prefix_ctx)
                    x, _ = _ffn(hybrid_ffn_select(cfg, blk, i), x, cfg,
                                dims, opt, pins, valid=tok_ok)
                return x, {"k": k, "v": v, "ssm": jnp.stack(ssm_out),
                           "conv": jnp.stack(conv_out)}

            x, ys = jax.lax.scan(body, x, xs)
            caches = {"k": ys["k"], "v": ys["v"],
                      "ssm": MambaCache(conv=ys["conv"], state=ys["ssm"])}
        elif fam == "audio":
            xs = {"blk": params["layers"],
                  "kp": dstate["k_pool"], "vp": dstate["v_pool"],
                  "ck": dstate["cross_k"][:, sid_safe],
                  "cv": dstate["cross_v"][:, sid_safe]}

            def body(x, xl):
                x, (k, v) = attn_sublayer(xl["blk"], x, xl["kp"], xl["vp"],
                                          prefix_slots, positions,
                                          prefix_ctx)
                x = cross_sublayer(xl["blk"], x, xl["ck"], xl["cv"])
                x, _ = _ffn(xl["blk"], x, cfg, dims, opt, pins)
                return x, {"k": k, "v": v}

            x, ys = jax.lax.scan(body, x, xs)
            caches = {"k": ys["k"], "v": ys["v"]}
        else:
            raise ValueError(fam)

        new_state = dict(dstate)
        if caches.get("k") is not None and "k_pool" in dstate:
            eff_slots = jnp.where(row_ok[:, None], new_slots, -1)
            _install_kv(spec, None, dstate, new_state, caches,
                        eff_slots, B, part=part if sharded else None)
        if "ssm" in dstate and caches.get("ssm") is not None:
            _install_recurrent(dstate, new_state, caches["ssm"], sid, B)
        # no cross install: chunk 0 (recompute) ran the encoder and
        # installed the per-layer cross K/V this step just read

        new_state["ctx_len"] = dstate["ctx_len"].at[sid].set(
            ctx.astype(dstate["ctx_len"].dtype), mode="drop")

        with jax.named_scope("lm_head"):
            x = Lmod.rms_norm(x, params["final_norm"].astype(jnp.float32),
                              cfg.norm_eps)
            head = (params["embed"] if cfg.tie_embeddings
                    else params["lm_head"])
            logits = Lmod.unembed(head, x, dims.logical_vocab, pins)
        last = jnp.take_along_axis(
            logits, last_pos[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        stats = _first_token_stats(dstate, last, sid, ctx, n_slots, sample)
        if moe_counts is not None:
            stats["moe"] = moe_counts
        return last, new_state, stats

    if not sharded:
        return prefix_prefill_step

    def prefix_step_sharded(params, dstate, batch, new_slots, prefix_slots,
                            slot_ids, ctx, prefix_ctx, last_pos, *,
                            sample=False):
        sspecs = kv_state_specs(dstate, spec)
        fn = jax.shard_map(
            functools.partial(prefix_prefill_step, sample=sample),
            mesh=mesh, in_specs=(P(), sspecs) + (P(),) * 7,
            out_specs=(P(), sspecs, P()), check_vma=False)
        return fn(params, dstate, batch, new_slots, prefix_slots, slot_ids,
                  ctx, prefix_ctx, last_pos)

    return prefix_step_sharded
