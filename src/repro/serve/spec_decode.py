"""Speculative decoding on the serve path: draft → verify → accept,
K tokens per dispatch, in one graph.

Utopia's thesis is that per-access translation cost dominates when every
access pays the full lookup; the decode hot path has the same shape —
every generated token pays one full dispatch (RSW/TAR translate + layer
stack + device fetch).  Speculative decoding amortizes that fixed
per-step cost across a window of K draft tokens: ONE ``translate_step``,
ONE dispatch and ONE ``device_get`` now yield up to K+1 accepted tokens
(the SPARTA amortize-translation-across-accesses strategy, PAPERS.md).

Pieces, all in-graph so ``Engine.step()`` keeps its single-fetch
contract:

* **drafter** — self-drafted n-gram / prompt lookup: each slot's token
  history (``dstate["hist"]``, prompt scattered at admission, generated
  tokens appended in-graph) is matched against its own last ``ngram``
  tokens; the K tokens that followed the most recent earlier occurrence
  are proposed.  No second model, no extra dispatch, no host round-trip.
* **verify** — the target model runs over all K+1 window positions
  (the committed token plus K drafts) in one forward: K/V for every
  window position is written to its pool slot first (write slots are
  *gathered from the step's single translation* — no second lookup),
  then the Q>1 paged-attention path reads the pool with PER-QUERY
  extents ``pos + i + 1`` — exactly the mask sequential decode applies,
  so each position's logits match the non-speculative step's bitwise.
* **accept** — exact-match for greedy rows; for sampled rows the
  position-folded per-slot PRNG draw plays a maximal gumbel coupling of
  the rejection sampler (serve/sampling.py): lossless, and the emitted
  stream is token-identical to the non-speculative stream in BOTH
  modes (the differential oracle in tests/test_spec_decode.py).

Rejected tails need no device-side KV rewind: positions at or beyond the
advanced ``ctx_len`` are masked by every later read and rewritten before
they are ever attended.  The *engine* rewinds the host-visible state —
variable-length commit, eos/max-token truncation (with a ``ctx_len``
scatter back), and deallocation of blocks a rejected tail had crossed
into (``HybridKVManager.free_block``).

Recurrent (ssm/conv) families are not supported here — state rollback
for rejected tokens is not cheap — and the engine falls back to
non-speculative decode with a warn-once (ROADMAP item).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.core.partition import Partition
from repro.dist.sharding import kv_state_specs
from repro.models import layers as Lmod
from repro.models.transformer import ModelDims
from repro.kernels.paged_attention.ref import paged_attention_blocks
from .decode import (DecodeSpec, _pool_blocks, _pool_write,
                     _psum_gather_blocks, decode_cross, decode_ffn,
                     project_logits, translate_step, translate_step_sharded)
from .sampling import sample_tokens_q, verify_draft_tokens

# families whose decode state is position-indexed only (KV pool / cross
# K/V): a rejected tail costs nothing to abandon.  ssm/hybrid carry
# recurrent state that every fed token mutates — rolling it back would
# need a per-layer state checkpoint per window position.
SPEC_FAMILIES = ("dense", "moe", "vlm", "audio")


def propose_ngram_drafts(hist: jax.Array, ctx: jax.Array, K: int,
                         ngram: int = 2) -> jax.Array:
    """In-graph prompt-lookup drafter.

    ``hist (B, H) int32`` — per-slot token history with the CURRENT token
    already written at position ``ctx[b]`` (unknown positions hold -1);
    ``ctx (B,)`` — the current token's position.  Returns ``(B, K)``
    proposed continuation tokens: the tokens that followed the most
    recent earlier occurrence of the history's last ``ngram``-gram.  When
    no earlier occurrence exists (or the match runs off the known
    history) the current token is repeated — any proposal is *valid*
    (verification is lossless); an unlikely one just accepts nothing.
    """
    B, H = hist.shape
    pos = jnp.arange(H, dtype=jnp.int32)[None, :]            # candidate end j
    match = (pos >= ngram - 1) & (pos < ctx[:, None])
    for d in range(ngram):
        suf = jnp.take_along_axis(
            hist, jnp.maximum(ctx[:, None] - d, 0), axis=1)  # (B, 1)
        # hist[j - d] via roll; j >= ngram-1 >= d keeps the wrap masked
        match = match & (jnp.roll(hist, d, axis=1) == suf)
    j_star = jnp.max(jnp.where(match, pos, -1), axis=1)      # (B,) latest
    has = j_star >= 0
    idx = j_star[:, None] + 1 + jnp.arange(K, dtype=jnp.int32)[None]
    known = idx <= ctx[:, None]
    gathered = jnp.take_along_axis(hist, jnp.clip(idx, 0, H - 1), axis=1)
    t0 = jnp.take_along_axis(hist, jnp.clip(ctx[:, None], 0, H - 1), axis=1)
    drafts = jnp.where(has[:, None] & known, gathered, t0)
    return jnp.maximum(drafts, 0)                            # -1 guard


def make_spec_decode_step(cfg: ArchConfig, dims: ModelDims,
                          spec: DecodeSpec, num_draft_tokens: int,
                          mesh=None, pins=Lmod.no_pins,
                          dtype=jnp.bfloat16, ngram: int = 2,
                          part: Partition = None):
    """Returns spec_step(params, dstate, tokens (B,), active, *, sample)
    -> (logits (B, K+1, V), new dstate, stats).

    ``stats`` carries the usual translation telemetry plus
    ``acc_tokens (B, K+1)`` / ``n_emit (B,)`` (commit
    ``acc_tokens[b, :n_emit[b]]``) and ``draft_tokens (B, K)`` — all
    in-graph, so the engine's fetch stays single.  ``dstate`` must hold
    the ``hist`` history buffer (the engine installs it when speculative
    decoding is configured).  Translation runs exactly once
    (``translate_step``); the K+1 per-position write slots are gathered
    from its result, never re-looked-up.
    """
    sharded = mesh is not None and spec.kv_shards >= 1
    if mesh is not None and not sharded:
        raise NotImplementedError(
            "speculative decode is single-host for now; the SPMD serve "
            "path (ROADMAP) drives the non-speculative step")
    if sharded and part is None:
        raise ValueError("spec.kv_shards >= 1 requires a Partition")
    if cfg.mixed_attention:
        raise NotImplementedError(
            "the speculative verify step has no per-layer window or rotary "
            f"table; serve {cfg.name} without spec_decode")
    if cfg.family not in SPEC_FAMILIES:
        raise ValueError(
            f"speculative decode does not support family {cfg.family!r} "
            "(recurrent state rollback); the engine falls back to "
            "non-speculative decode")
    K = int(num_draft_tokens)
    if K < 1:
        raise ValueError(f"num_draft_tokens must be >= 1, got {K}")
    Qw = K + 1
    bs = spec.block_size
    nblk = spec.max_blocks_per_seq
    fam = cfg.family

    def qkv_verify(blk, x, positions):
        B = x.shape[0]
        h = Lmod.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
        q = Lmod.linear(blk["attn"]["q"], h).reshape(B, Qw, dims.n_heads,
                                                     dims.head_dim)
        k = Lmod.linear(blk["attn"]["k"], h).reshape(B, Qw, dims.n_kv,
                                                     dims.head_dim)
        v = Lmod.linear(blk["attn"]["v"], h).reshape(B, Qw, dims.n_kv,
                                                     dims.head_dim)
        if cfg.rope_theta > 0:
            q = Lmod.apply_rope(q, positions, cfg.rope_theta)
            k = Lmod.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    @jax.named_scope("attn")
    def attn_sublayer(blk, x, kp, vp, i, slots_b, w_slot, w_valid,
                      positions, ctx_q):
        B = x.shape[0]
        q, k, v = qkv_verify(blk, x, positions)
        # write ALL window positions' K/V into their pre-resolved slots of
        # layer i of the carried pools; invalid (unmapped / inactive /
        # out-of-range) positions drop
        t_loc = positions % bs
        if sharded:
            # ownership-masked write + exact bit-psum gather; the Q>1
            # attention math itself is the same replicated path
            m_idx = jax.lax.axis_index(spec.model_axis)
            cps = part.slots_per_shard
            wp = part.phys(w_slot)
            kp, vp = _pool_write(kp, vp, i, wp - m_idx * cps, t_loc, k, v,
                                 w_valid & ((wp // cps) == m_idx))
            gk = _psum_gather_blocks(kp, slots_b, part, spec.model_axis, i)
            gv = _psum_gather_blocks(vp, slots_b, part, spec.model_axis, i)
        else:
            kp, vp = _pool_write(kp, vp, i, w_slot, t_loc, k, v, w_valid)
            gk, gv = _pool_blocks(kp, i, slots_b), _pool_blocks(vp, i, slots_b)
        # per-query extents pos+i+1: the sequential causal mask, inside
        # one pool read (verify-shaped Q>1 paged attention)
        o, m_, l_ = paged_attention_blocks(q, gk, gv, slots_b, ctx_q)
        out = (o / jnp.maximum(l_, 1e-30)[..., None]).astype(q.dtype)
        o_p = Lmod.linear(blk["attn"]["o"],
                          out.reshape(B, Qw, -1).astype(x.dtype))
        return x + pins("dec_bd", o_p), kp, vp

    n_layers = cfg.num_layers

    def spec_step(params, dstate, tokens, active=None, *, sample=False):
        pos0 = dstate["ctx_len"]                       # fed token's position
        B = pos0.shape[0]
        act = (jnp.ones_like(pos0, jnp.bool_) if active is None
               else active.astype(jnp.bool_))
        row = jnp.arange(B, dtype=jnp.int32)
        t0 = tokens.astype(jnp.int32)
        hist = dstate["hist"]
        H = hist.shape[1]
        # current token enters the history BEFORE drafting: the drafter
        # matches the ngram that ENDS at it (inactive rows drop)
        p_safe = jnp.where(act & (pos0 < H), pos0, H)
        hist = hist.at[row, p_safe].set(t0, mode="drop")
        drafts = propose_ngram_drafts(hist, pos0, K, ngram)    # (B, K)
        seq_toks = jnp.concatenate([t0[:, None], drafts], axis=1)  # (B, Qw)
        positions = (pos0[:, None]
                     + jnp.arange(Qw, dtype=jnp.int32)[None, :])
        ctx_q = positions + 1                          # per-query extents

        x = jnp.take(params["embed"]["table"], seq_toks,
                     axis=0).astype(dtype)
        x = pins("dec_bd", x)
        new_state = dict(dstate)
        stats = {}

        # ---- the step's single translation dispatch ----------------------
        with jax.named_scope("translate"):
            if sharded:
                trans = translate_step_sharded(
                    dstate["tar"], dstate["sf"], dstate["flex"], pos0, spec,
                    part)
            else:
                trans = translate_step(dstate["tar"], dstate["sf"],
                                       dstate["flex"], pos0, spec)
            slots_b = trans.slots[0]                   # (B, nblk); G == 1
            # per-position write slots GATHERED from the one translation —
            # position pos+i lives in block (pos+i)//bs, already resolved
            blk_idx = jnp.clip(positions // bs, 0, nblk - 1)
            w_slot = jnp.take_along_axis(slots_b, blk_idx, axis=1)
            w_map = jnp.take_along_axis(trans.mapped[0], blk_idx, axis=1)
            w_valid = w_map & (positions < nblk * bs) & act[:, None]
        stats.update(slots=trans.slots, in_rest=trans.in_rest,
                     mapped=trans.mapped, accesses=trans.accesses)

        xs = {"blk": params["layers"],
              "idx": jnp.arange(n_layers, dtype=jnp.int32)}
        if fam == "audio":
            xs["ck"] = dstate["cross_k"]
            xs["cv"] = dstate["cross_v"]

        def body(carry, xl):
            x, kp, vp = carry
            blk = xl["blk"]
            i = xl["idx"]
            x, kp, vp = attn_sublayer(blk, x, kp, vp, i, slots_b, w_slot,
                                      w_valid, positions, ctx_q)
            if fam == "audio":
                x = decode_cross(blk, x, xl["ck"], xl["cv"], cfg, dims,
                                 pins)
            x, _ = decode_ffn(blk, x, cfg, pins)
            return (x, kp, vp), None

        (x, kp, vp), _ = jax.lax.scan(
            body, (x, dstate["k_pool"], dstate["v_pool"]), xs)
        new_state["k_pool"], new_state["v_pool"] = kp, vp

        logits = project_logits(params, x, cfg, dims, pins)

        # ---- in-graph accept: greedy exact-match / seeded coupled
        # rejection sampling — every target draw folds its ABSOLUTE
        # position, the same key the non-speculative step would fold
        with jax.named_scope("sample"):
            if sample:
                tgt = sample_tokens_q(logits, dstate["samp_temp"],
                                      dstate["samp_topk"],
                                      dstate["samp_topp"],
                                      dstate["samp_key"], positions)
            else:
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            acc_tokens, n_emit = verify_draft_tokens(tgt, drafts)

        # emitted token i sits at position pos+i+1; rejected tails and
        # inactive rows drop (garbage must not enter the match history)
        wpos = positions + 1
        emit_ok = ((jnp.arange(Qw, dtype=jnp.int32)[None, :]
                    < n_emit[:, None]) & act[:, None] & (wpos < H))
        wp = jnp.where(emit_ok, wpos, H)
        hist = hist.at[row[:, None], wp].set(acc_tokens, mode="drop")
        new_state["hist"] = hist

        # variable-length advance, in-graph (single-fetch contract): only
        # active rows move, by exactly the emitted-token count
        new_state["ctx_len"] = (dstate["ctx_len"]
                                + jnp.where(act, n_emit, 0).astype(
                                    dstate["ctx_len"].dtype))
        stats["acc_tokens"] = acc_tokens
        stats["n_emit"] = n_emit
        stats["draft_tokens"] = drafts
        return logits, new_state, stats

    if not sharded:
        return spec_step

    def spec_step_sharded(params, dstate, tokens, active=None, *,
                          sample=False):
        act = (jnp.ones_like(dstate["ctx_len"], jnp.bool_) if active is None
               else active.astype(jnp.bool_))
        sspecs = kv_state_specs(dstate, spec)
        fn = jax.shard_map(
            functools.partial(spec_step, sample=sample),
            mesh=mesh, in_specs=(P(), sspecs, P(), P()),
            out_specs=(P(), sspecs, P()), check_vma=False)
        return fn(params, dstate, tokens, act)

    return spec_step_sharded
