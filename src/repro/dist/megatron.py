"""Hand-scheduled Megatron tensor-parallel forward (dense family).

The GSPMD forward in ``repro.models`` lets the compiler place the
collectives; this module writes them out explicitly inside a
``shard_map`` — the Megatron schedule:

* vocab-sharded embedding: local masked gather + ``psum`` over the model
  axis;
* per layer: column-parallel q/k/v (heads sliced over the model axis,
  KV heads replicated when ``n_kv % TP != 0`` — the MQA case), local
  attention over the head slice, row-parallel output projection closed by
  one ``psum``; column-parallel gate/up + row-parallel down ``psum`` for
  the MLP;
* vocab-sharded unembed closed by a tiled ``all_gather``.

Numerics must match the GSPMD forward bit-for-tolerance — that equivalence
is the test (tests/test_dist.py::TestMegatronExplicit).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import layers as L
from repro.models.attention import attention
from repro.models.transformer import ModelDims, _zero_aux

from .sharding import ShardingRules, _path_names


def _kv_sharded(dims: ModelDims, tp: int) -> bool:
    return (dims.n_kv * dims.head_dim) % tp == 0 and dims.n_kv % tp == 0


def _mega_spec(path: Tuple[str, ...], dims: ModelDims, tp: int, M: str):
    """PartitionSpec for one parameter leaf under the explicit schedule."""
    name = path[-1]
    if "norm" in name:
        return P()
    if name == "table":
        return P(M, None) if dims.vocab % tp == 0 else P()
    parent = path[-2] if len(path) > 1 else ""
    if name == "w":
        if parent == "q" or (parent in ("k", "v") and _kv_sharded(dims, tp)):
            return P(None, None, M)          # column-parallel (stacked)
        if parent in ("k", "v"):
            return P(None, None, None)       # replicated KV (MQA)
        if parent == "o":
            return P(None, M, None)          # row-parallel
        if parent in ("gate", "up"):
            return P(None, None, M)
        if parent == "down":
            return P(None, M, None)
        return P()
    if name == "b":
        if parent == "q" or (parent in ("k", "v") and _kv_sharded(dims, tp)):
            return P(None, M)
        return P()
    return P()


def megatron_param_shardings(params, mesh: Mesh, rules: ShardingRules):
    """NamedShardings matching the explicit schedule's in_specs."""
    M = rules.model_axis
    tp = mesh.shape[M]
    vocab_div = params["embed"]["table"].shape[0] % tp == 0
    kv_div = params["layers"]["attn"]["k"]["w"].shape[-1] % tp == 0

    def leaf(path, x):
        names = _path_names(path)
        name = names[-1]
        parent = names[-2] if len(names) > 1 else ""
        if name == "table":
            spec = P(M, None) if vocab_div else P()
        elif name == "w" and (parent in ("q", "gate", "up")
                              or (parent in ("k", "v") and kv_div)):
            spec = P(None, None, M)
        elif name == "w" and parent in ("o", "down"):
            spec = P(None, M, None)
        elif name == "b" and (parent == "q"
                              or (parent in ("k", "v") and kv_div)):
            spec = P(None, M)
        else:
            spec = P()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf, params)


def make_megatron_forward(cfg: ArchConfig, dims: ModelDims, mesh: Mesh,
                          data_axes: Tuple[str, ...] = ("data",),
                          attn_impl: str = "dense",
                          triangular: bool = False, remat: bool = False,
                          model_axis: str = "model"):
    """Returns fwd(params, batch) -> (logits, aux, None) for dense models."""
    if cfg.family != "dense":
        raise ValueError("explicit megatron schedule is dense-only")
    DA = tuple(data_axes)
    M = model_axis
    tp = mesh.shape[M]
    kv_sh = _kv_sharded(dims, tp)
    vocab_sh = dims.vocab % tp == 0
    H_loc = dims.n_heads // tp
    KV_loc = dims.n_kv // tp if kv_sh else dims.n_kv

    def local(params, tokens):
        m_idx = jax.lax.axis_index(M)
        B, S = tokens.shape
        pos = jnp.arange(S)[None, :]

        # ---- vocab-sharded embedding -------------------------------------
        table = params["embed"]["table"]
        if vocab_sh:
            v_loc = table.shape[0]
            idx = tokens - m_idx * v_loc
            ok = (idx >= 0) & (idx < v_loc)
            x = jnp.take(table, jnp.clip(idx, 0, v_loc - 1), axis=0)
            x = jnp.where(ok[..., None], x, 0)
            x = jax.lax.psum(x, M)
        else:
            x = jnp.take(table, tokens, axis=0)

        def layer(x, blk):
            # attention: column-parallel qkv, row-parallel o
            h = L.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
            q = L.linear(blk["attn"]["q"], h).reshape(
                B, S, H_loc, dims.head_dim)
            k = L.linear(blk["attn"]["k"], h).reshape(
                B, S, KV_loc, dims.head_dim)
            v = L.linear(blk["attn"]["v"], h).reshape(
                B, S, KV_loc, dims.head_dim)
            if cfg.rope_theta > 0:
                q = L.apply_rope(q, pos, cfg.rope_theta)
                k = L.apply_rope(k, pos, cfg.rope_theta)
            o = attention(q, k, v, impl=attn_impl, causal=True,
                          triangular_schedule=triangular)
            o = L.linear(blk["attn"]["o"], o.reshape(B, S, -1))
            x = x + jax.lax.psum(o, M)
            # MLP: column-parallel gate/up, row-parallel down
            h = L.rms_norm(x, blk["norm2"].astype(jnp.float32), cfg.norm_eps)
            p = blk["mlp"]
            ff = jax.nn.silu(L.linear(p["gate"], h)) * L.linear(p["up"], h)
            x = x + jax.lax.psum(L.linear(p["down"], ff), M)
            return x, None

        body = jax.checkpoint(layer) if remat else layer
        x, _ = jax.lax.scan(body, x, params["layers"])

        # ---- final norm + vocab-sharded unembed --------------------------
        x = L.rms_norm(x, params["final_norm"].astype(jnp.float32),
                       cfg.norm_eps)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = x @ head["table"].T.astype(x.dtype)
        if vocab_sh:
            logits = jax.lax.all_gather(logits, M, axis=2, tiled=True)
        if dims.vocab > dims.logical_vocab:
            mask = jnp.arange(dims.vocab) < dims.logical_vocab
            logits = jnp.where(mask, logits,
                               jnp.asarray(-1e9, logits.dtype))
        return logits

    def param_specs(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: _mega_spec(_path_names(path), dims, tp, M),
            params)

    def fwd(params, batch):
        tokens = batch["tokens"]
        fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(param_specs(params), P(DA, None)),
            out_specs=P(DA, None, None), check_vma=False)
        logits = fn(params, tokens)
        return logits, _zero_aux(cfg, moe_capacity=True), None

    return fwd
