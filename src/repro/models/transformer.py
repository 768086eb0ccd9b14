"""Model builders for all assigned families.

One functional model per family, all sharing the scan-over-layers pattern
(compact HLO: an 80-layer model lowers as one while loop).  Families:

* dense / moe / vlm  -> decoder-only LM (vlm prepends stub patch embeddings)
* audio              -> whisper-style enc-dec (stub frame embeddings)
* hybrid             -> jamba groups: [7 x mamba + 1 x attn], MoE every 2nd ffn
* ssm                -> mamba2 stack (attention-free)

``forward`` returns (logits, aux); ``mode="prefill"`` additionally returns
per-layer KV (and SSM caches) for the serving engine to install into the
hybrid-translated KV pool.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, resolve
from . import layers as L
from .attention import attention
from .moe import init_moe, moe_dropless, moe_layer
from .ssm import MambaDims, mamba_dims, init_mamba, mamba_forward


class ModelDims(NamedTuple):
    n_heads: int
    n_kv: int
    head_dim: int
    vocab: int            # padded
    logical_vocab: int
    d_ff: int
    mamba: Optional[MambaDims]
    tp: int
    moe_d_ff: int         # expert width (padded like d_ff)


def model_dims(cfg: ArchConfig, tp: int = 1) -> ModelDims:
    r = resolve(cfg, tp)
    md = None
    if cfg.family in ("hybrid", "ssm"):
        md = mamba_dims(cfg.d_model, cfg.ssm_state, cfg.ssm_head_dim,
                        cfg.ssm_expand, cfg.ssm_conv_width, tp=tp)
    moe_ff = -(-cfg.moe_d_ff // tp) * tp if cfg.moe_d_ff else r.d_ff
    return ModelDims(n_heads=r.num_heads, n_kv=r.num_kv_heads,
                     head_dim=cfg.resolved_head_dim, vocab=r.vocab_size,
                     logical_vocab=cfg.vocab_size, d_ff=r.d_ff, mamba=md,
                     tp=tp, moe_d_ff=moe_ff)


def layer_kinds(cfg: ArchConfig, head_dim: int) -> Dict[str, jax.Array]:
    """Per-layer attention kind, stacked over the depth for the layer
    scans (``cfg.mixed_attention`` only): ``window`` (L,) int32 keys a
    query attends (``NO_WINDOW`` on full layers), ``inv_freq`` (L, hd/2)
    and ``rope_scale`` (L,) the layer's rotary table."""
    tables = {t: L.rope_table(head_dim, cfg.rope_of(t))
              for t in ("sliding", "full")}
    types = [cfg.layer_type(l) for l in range(cfg.num_layers)]
    return {
        "window": jnp.asarray([cfg.layer_window(l)
                               for l in range(cfg.num_layers)], jnp.int32),
        "inv_freq": jnp.asarray(np.stack([tables[t][0] for t in types])),
        "rope_scale": jnp.asarray([tables[t][1] for t in types],
                                  jnp.float32),
    }


# --------------------------------------------------------------------- init

def _init_attn_block(key, cfg: ArchConfig, dims: ModelDims, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "norm1": L.init_norm(cfg.d_model, dtype),
        "attn": L.init_attention(k1, cfg.d_model, dims.n_heads, dims.n_kv,
                                 dims.head_dim, cfg.qkv_bias, dtype),
    }


def _init_ffn(key, cfg: ArchConfig, dims: ModelDims, dtype, use_moe: bool):
    if use_moe:
        return {"norm2": L.init_norm(cfg.d_model, dtype),
                "moe": init_moe(key, cfg.d_model, dims.moe_d_ff,
                                cfg.moe_num_experts, dtype)}
    return {"norm2": L.init_norm(cfg.d_model, dtype),
            "mlp": L.init_mlp(key, cfg.d_model, dims.d_ff, dtype)}


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(key, cfg: ArchConfig, dims: ModelDims, dtype=jnp.float32):
    keys = jax.random.split(key, 16)
    params: Dict[str, Any] = {
        "embed": L.init_embedding(keys[0], dims.vocab, cfg.d_model, dtype),
        "final_norm": L.init_norm(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.init_embedding(keys[1], dims.vocab,
                                             cfg.d_model, dtype)
    if cfg.frontend != "none":
        params["frontend_proj"] = L.init_linear(keys[2], cfg.d_model,
                                                cfg.d_model, dtype)

    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        lkeys = jax.random.split(keys[3], cfg.num_layers)
        blocks = []
        for i, lk in enumerate(lkeys):
            ka, kf = jax.random.split(lk)
            blk = _init_attn_block(ka, cfg, dims, dtype)
            blk.update(_init_ffn(kf, cfg, dims, dtype, cfg.moe_on_layer(i)))
            blocks.append(blk)
        params["layers"] = _stack(blocks)
    elif fam == "ssm":
        lkeys = jax.random.split(keys[3], cfg.num_layers)
        blocks = [{"norm1": L.init_norm(cfg.d_model, dtype),
                   "mamba": init_mamba(lk, dims.mamba, dtype)}
                  for lk in lkeys]
        params["layers"] = _stack(blocks)
    elif fam == "hybrid":
        g = cfg.attn_every                       # sublayers per group
        n_groups = cfg.num_layers // g
        gkeys = jax.random.split(keys[3], n_groups)
        groups = []
        for gk in gkeys:
            sk = jax.random.split(gk, 2 * g + 2)
            mambas = [
                {"norm1": L.init_norm(cfg.d_model, dtype),
                 "mamba": init_mamba(sk[i], dims.mamba, dtype)}
                for i in range(g - 1)]
            attn = _init_attn_block(sk[g - 1], cfg, dims, dtype)
            mlps, moes = [], []
            for i in range(g):
                if cfg.moe_on_layer(i):
                    moes.append(_init_ffn(sk[g + i], cfg, dims, dtype, True))
                else:
                    mlps.append(_init_ffn(sk[g + i], cfg, dims, dtype, False))
            grp = {"mamba": _stack(mambas), "attn": attn}
            # a group may be all-MLP (MoE-free hybrid) or all-MoE;
            # _stack([]) is not a tree, so only present kinds get a key —
            # the scan bodies select per-sublayer statically via
            # cfg.moe_on_layer, never touching an absent kind
            if mlps:
                grp["mlp"] = _stack(mlps)
            if moes:
                grp["moe"] = _stack(moes)
            groups.append(grp)
        params["layers"] = _stack(groups)
    elif fam == "audio":
        ekeys = jax.random.split(keys[4], cfg.encoder_layers)
        enc = []
        for ek in ekeys:
            ka, kf = jax.random.split(ek)
            blk = _init_attn_block(ka, cfg, dims, dtype)
            blk.update(_init_ffn(kf, cfg, dims, dtype, False))
            enc.append(blk)
        params["encoder"] = {"layers": _stack(enc),
                             "final_norm": L.init_norm(cfg.d_model, dtype)}
        dkeys = jax.random.split(keys[5], cfg.num_layers)
        dec = []
        for dk in dkeys:
            ka, kc, kf = jax.random.split(dk, 3)
            blk = _init_attn_block(ka, cfg, dims, dtype)
            blk["norm_x"] = L.init_norm(cfg.d_model, dtype)
            blk["cross"] = L.init_attention(kc, cfg.d_model, dims.n_heads,
                                            dims.n_kv, dims.head_dim,
                                            cfg.qkv_bias, dtype)
            blk.update(_init_ffn(kf, cfg, dims, dtype, False))
            dec.append(blk)
        params["layers"] = _stack(dec)
    else:
        raise ValueError(fam)
    return params


# ------------------------------------------------------------------ forward

@dataclasses.dataclass(frozen=True)
class FwdOptions:
    attn_impl: str = "dense"           # dense | flash_jax | pallas
    dtype: Any = jnp.float32
    remat: bool = False
    q_chunk: int = 512
    kv_chunk: int = 512
    triangular_schedule: bool = False
    collect_cache: bool = False        # prefill: emit per-layer KV/SSM caches
    moe_groups: int = 1                # MoE dispatch groups (= DP shards)


@jax.named_scope("attn")
def _self_attn(blk, x, cfg, dims, opt, pins, causal=True, kind=None):
    """``kind`` (a layer's slice of ``layer_kinds``) gives the layer its
    own rotary table and window."""
    B, S, _ = x.shape
    pos = jnp.arange(S)[None, :]
    h = L.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
    # gather the d-sharded activation ONCE, in bf16, for q/k/v to share
    # (without the pin GSPMD emits one fp32 all-gather per consumer: 7x
    # the bytes — measured on granite-8b, EXPERIMENTS.md §Perf)
    h = pins("act_full", h)
    theta = cfg.rope_theta if causal else 0.0   # encoder: no rope (stub pos)
    rope = () if kind is None else (kind["inv_freq"], kind["rope_scale"])
    q, k, v = L.qkv_project(blk["attn"], h, h, dims.n_heads, dims.n_kv,
                            dims.head_dim, pos, pos, theta, pins, rope=rope)
    o = attention(q, k, v, impl=opt.attn_impl, causal=causal,
                  q_chunk=opt.q_chunk, kv_chunk=opt.kv_chunk,
                  triangular_schedule=opt.triangular_schedule,
                  window=None if kind is None else kind["window"])
    o = L.linear(blk["attn"]["o"], o.reshape(B, S, -1))
    return x + pins("act_btd", o), (k, v)


@jax.named_scope("ffn")
def _ffn(blk, x, cfg, dims, opt, pins, moe_capacity=False, valid=None):
    """FFN sublayer -> (x, aux).  MoE: training's capacity-bounded
    dispatch (``moe_capacity``; aux = its losses) or serving's drop-free
    one (aux = ``{"moe_counts": [routed pairs, experts hit]}`` of the
    tokens where ``valid``)."""
    h = L.rms_norm(x, blk["norm2"].astype(jnp.float32), cfg.norm_eps)
    h = pins("act_full", h)
    if "moe" in blk and moe_capacity:
        out, aux = moe_layer(blk["moe"], h, top_k=cfg.moe_top_k,
                             capacity_factor=cfg.moe_capacity_factor,
                             n_groups=opt.moe_groups, pins=pins)
        return x + pins("act_btd", out), aux
    if "moe" in blk:
        out, counts = moe_dropless(blk["moe"], h, top_k=cfg.moe_top_k,
                                   valid=valid)
        return x + pins("act_btd", out), {"moe_counts": counts}
    out = L.mlp(blk["mlp"], h, pins)
    return x + pins("act_btd", out), None


def hybrid_ffn_select(cfg: ArchConfig, blk, i: int):
    """The group-local FFN params for sublayer ``i`` of a hybrid group:
    the MoE stack when ``cfg.moe_on_layer(i)``, else the corresponding
    stacked MLP.  One source of truth for the group-local index
    arithmetic — the train forward, the decode step and the prefix-KV
    chunk step all select through here."""
    n_moe_before = sum(cfg.moe_on_layer(j) for j in range(i))
    if cfg.moe_on_layer(i):
        return jax.tree.map(lambda a, j=n_moe_before: a[j], blk["moe"])
    return jax.tree.map(lambda a, j=i - n_moe_before: a[j], blk["mlp"])


def _zero_aux(cfg: ArchConfig, moe_capacity: bool):
    if cfg.moe_num_experts and not moe_capacity:
        return {"moe_counts": jnp.zeros((2,), jnp.int32)}
    return {"lb_loss": jnp.zeros((), jnp.float32),
            "z_loss": jnp.zeros((), jnp.float32),
            "fraction_dropped": jnp.zeros((), jnp.float32)}


def _acc_aux(acc, aux):
    if aux is None:
        return acc
    return {k: acc[k] + aux[k] for k in acc}


def _mamba_block(blk, x, cfg, dims, opt, pins, collect=False,
                 seq_len=None):
    h = L.rms_norm(x, blk["norm1"].astype(jnp.float32), cfg.norm_eps)
    h = pins("act_full", h)
    out, state = mamba_forward(blk["mamba"], h, dims.mamba,
                               chunk=cfg.ssm_chunk, pins=pins,
                               seq_len=seq_len, return_state=collect)
    return x + pins("act_btd", out), state


def _decoder_body(cfg: ArchConfig, dims: ModelDims, opt: FwdOptions, pins,
                  seq_len=None, moe_capacity=False):
    """Returns the scan body for the family's stacked layers; with
    ``cfg.mixed_attention`` each step also takes its layer's kind.

    ``seq_len`` (B,) is forwarded to the recurrent (mamba) sublayers so
    right-padded bucket rows install exact SSM states (pad positions are
    identity transitions), and keeps the pad positions out of the
    drop-free MoE; attention sublayers need no mask — causal attention
    never reads past the query position."""
    fam = cfg.family
    mixed = cfg.mixed_attention

    def ffn(blk, x):
        valid = None
        if seq_len is not None and cfg.moe_num_experts:
            valid = jnp.arange(x.shape[1])[None, :] < seq_len[:, None]
        return _ffn(blk, x, cfg, dims, opt, pins, moe_capacity, valid)

    def body(carry, xl):
        blk, kind = xl if mixed else (xl, None)
        x, aux = carry
        cache = {}
        if fam in ("dense", "moe", "vlm"):
            x, (k, v) = _self_attn(blk, x, cfg, dims, opt, pins, kind=kind)
            x, a = ffn(blk, x)
            aux = _acc_aux(aux, a)
            if opt.collect_cache:
                cache = {"k": k, "v": v}
        elif fam == "ssm":
            x, state = _mamba_block(blk, x, cfg, dims, opt, pins,
                                    collect=opt.collect_cache,
                                    seq_len=seq_len)
            if opt.collect_cache:
                cache = {"ssm": state}
        elif fam == "hybrid":
            g = cfg.attn_every
            ssm_states = []
            for i in range(g):
                if i < g - 1:
                    sub = jax.tree.map(lambda a, i=i: a[i], blk["mamba"])
                    x, st = _mamba_block(sub, x, cfg, dims, opt, pins,
                                         collect=opt.collect_cache,
                                         seq_len=seq_len)
                    if opt.collect_cache:
                        ssm_states.append(st)
                    k = v = None
                else:
                    x, (k, v) = _self_attn(blk["attn"], x, cfg, dims, opt, pins)
                x, a = ffn(hybrid_ffn_select(cfg, blk, i), x)
                aux = _acc_aux(aux, a)
            if opt.collect_cache:
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *ssm_states)
                cache = {"k": k, "v": v, "ssm": stacked}
        else:
            raise ValueError(fam)
        return (x, aux), cache

    return body


def _encoder(params, frames, cfg, dims, opt, pins):
    x = L.linear(params["frontend_proj"], frames.astype(opt.dtype))

    def body(x, blk):
        x, _ = _self_attn(blk, x, cfg, dims, opt, pins, causal=False)
        x, _ = _ffn(blk, x, cfg, dims, opt, pins)
        return x, None

    if opt.remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"]["layers"])
    return L.rms_norm(x, params["encoder"]["final_norm"].astype(jnp.float32),
                      cfg.norm_eps)


def _audio_decoder_body(cfg, dims, opt, pins, enc_out):
    def body(carry, blk):
        x, aux = carry
        x, (k, v) = _self_attn(blk, x, cfg, dims, opt, pins)
        # cross attention over encoder output
        B, S, _ = x.shape
        h = L.rms_norm(x, blk["norm_x"].astype(jnp.float32), cfg.norm_eps)
        pos = jnp.arange(S)[None, :]
        epos = jnp.arange(enc_out.shape[1])[None, :]
        q, ck, cv = L.qkv_project(blk["cross"], h, enc_out, dims.n_heads,
                                  dims.n_kv, dims.head_dim, pos, epos, 0.0,
                                  pins)
        o = attention(q, ck, cv, impl=opt.attn_impl, causal=False,
                      q_chunk=opt.q_chunk, kv_chunk=opt.kv_chunk)
        x = x + pins("act_btd",
                     L.linear(blk["cross"]["o"], o.reshape(B, S, -1)))
        x, a = _ffn(blk, x, cfg, dims, opt, pins)
        aux = _acc_aux(aux, a)
        cache = {"k": k, "v": v, "ck": ck, "cv": cv} if opt.collect_cache else {}
        return (x, aux), cache

    return body


def forward(params, batch: Dict[str, jax.Array], cfg: ArchConfig,
            dims: ModelDims, opt: FwdOptions = FwdOptions(),
            pins: L.Pins = L.no_pins, *, moe_capacity: bool = False):
    """batch: tokens (B,S) [+ frontend (B,F,D) for vlm/audio] [+ seq_len
    (B,) real row lengths].

    Returns (logits (B,S,vocab_pad), aux, caches) — caches None unless
    ``opt.collect_cache``.  MoE layers route drop-free (serving; aux
    holds ``moe_counts``) unless ``moe_capacity`` selects training's
    capacity-bounded dispatch and its losses.
    """
    tokens = batch["tokens"]
    seq_len = batch.get("seq_len")     # (B,) real row lengths (recurrent
                                       # families' pad-exact state installs)
    x = L.embed(params["embed"], tokens, pins).astype(opt.dtype)
    n_front = 0
    enc_out = None
    if cfg.family == "vlm":
        front = L.linear(params["frontend_proj"],
                         batch["frontend"].astype(opt.dtype))
        x = jnp.concatenate([front, x], axis=1)
        n_front = front.shape[1]
        x = pins("act_btd", x)
    elif cfg.family == "audio":
        enc_out = _encoder(params, batch["frontend"], cfg, dims, opt, pins)
        enc_out = pins("act_btd", enc_out)

    if cfg.family == "audio":
        body = _audio_decoder_body(cfg, dims, opt, pins, enc_out)
    else:
        body = _decoder_body(cfg, dims, opt, pins, seq_len, moe_capacity)
    if opt.remat:
        body = jax.checkpoint(body)
    xs = params["layers"]
    if cfg.mixed_attention:
        xs = (xs, layer_kinds(cfg, dims.head_dim))
    (x, aux), caches = jax.lax.scan(body, (x, _zero_aux(cfg, moe_capacity)),
                                    xs)

    with jax.named_scope("lm_head"):
        x = L.rms_norm(x, params["final_norm"].astype(jnp.float32),
                       cfg.norm_eps)
        if n_front:
            x = jax.lax.slice_in_dim(x, n_front, x.shape[1], axis=1)
        head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
        logits = L.unembed(head, x, dims.logical_vocab, pins)
    if cfg.family == "audio" and opt.collect_cache:
        caches = dict(caches)
        caches["enc_out"] = enc_out
    return logits, aux, (caches if opt.collect_cache else None)


def loss_fn(params, batch, cfg: ArchConfig, dims: ModelDims,
            opt: FwdOptions = FwdOptions(), pins: L.Pins = L.no_pins,
            moe_loss_weight: float = 0.01, z_loss_weight: float = 1e-3):
    logits, aux, _ = forward(params, batch, cfg, dims, opt, pins,
                             moe_capacity=True)
    labels = batch["labels"]
    mask = (labels >= 0).astype(jnp.float32)
    labels_safe = jnp.maximum(labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels_safe[..., None], axis=-1)[..., 0]
    ce = -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)
    loss = ce
    metrics = {"ce": ce}
    if cfg.moe_num_experts:
        loss = loss + moe_loss_weight * aux["lb_loss"] \
            + z_loss_weight * aux["z_loss"]
        metrics.update({k: aux[k] for k in
                        ("lb_loss", "z_loss", "fraction_dropped")})
    metrics["loss"] = loss
    return loss, metrics
