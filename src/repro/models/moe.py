"""Top-k Mixture of Experts with grouped, capacity-bounded dispatch.

GShard semantics (top-k, capacity factor, token dropping) implemented with
*index tables* instead of (T, E, C) one-hot einsums: per token-group we
scatter token ids into an (E, C) table and gather expert inputs from it.
This keeps dispatch cost O(T·D) data movement (no T·E·C·D one-hot matmul,
which at 1M tokens x 128 experts would dwarf the expert compute itself).

Sharding: the group dim ``g`` maps onto the data axes and the expert dim
onto the model axis (expert parallelism) — the pins "moe_*" constraints in
dist/sharding.py steer GSPMD to the all-to-all-style exchange.

Aux losses: Switch-style load balancing + router z-loss.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .layers import Pins, no_pins, init_linear


def init_moe(key, d_model: int, d_ff: int, n_experts: int,
             dtype=jnp.float32) -> dict:
    kr, kg, ku, kd = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "router": init_linear(kr, d_model, n_experts, jnp.float32),
        "gate": (jax.random.normal(kg, (n_experts, d_model, d_ff), jnp.float32)
                 * s_in).astype(dtype),
        "up": (jax.random.normal(ku, (n_experts, d_model, d_ff), jnp.float32)
               * s_in).astype(dtype),
        "down": (jax.random.normal(kd, (n_experts, d_ff, d_model), jnp.float32)
                 * s_out).astype(dtype),
    }


def _capacity(tokens_per_group: int, n_experts: int, top_k: int,
              capacity_factor: float) -> int:
    c = int(math.ceil(tokens_per_group * top_k / n_experts
                      * capacity_factor))
    return max(4, ((c + 3) // 4) * 4)


def moe_layer(p: dict, x: jax.Array, *, top_k: int,
              capacity_factor: float = 1.25, n_groups: int = 1,
              pins: Pins = no_pins) -> Tuple[jax.Array, dict]:
    """x: (B, S, D) -> (out, aux).

    ``n_groups``: token groups for local dispatch (set to the DP shard
    count so each group's scatter/gather stays device-local).
    """
    B, S, D = x.shape
    E = p["gate"].shape[0]
    T = B * S
    if T % n_groups:
        n_groups = 1
    Tg = T // n_groups
    C = _capacity(Tg, E, top_k, capacity_factor)
    xg = x.reshape(n_groups, Tg, D)
    xg = pins("moe_gtd", xg)

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32),
                        p["router"]["w"])                     # (g,Tg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # (g,Tg,k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(axis=-1, keepdims=True), 1e-9)

    # position of each (token, k) in its expert queue, per group
    sel = jax.nn.one_hot(expert_idx, E, dtype=jnp.int32)      # (g,Tg,k,E)
    flat_sel = sel.reshape(n_groups, Tg * top_k, E)
    pos_all = jnp.cumsum(flat_sel, axis=1) - flat_sel         # (g,Tg*k,E)
    pos = (pos_all * flat_sel).sum(-1).reshape(n_groups, Tg, top_k)
    keep = pos < C
    gate_vals = gate_vals * keep

    # --- dispatch: scatter token ids into the (E, C) index table ----------
    pos_c = jnp.where(keep, pos, C)                           # dropped -> col C
    table = jnp.zeros((n_groups, E, C + 1), jnp.int32)
    tok_ids = jnp.broadcast_to(
        jnp.arange(Tg, dtype=jnp.int32)[None, :, None],
        (n_groups, Tg, top_k))
    g_ids = jnp.broadcast_to(
        jnp.arange(n_groups, dtype=jnp.int32)[:, None, None],
        (n_groups, Tg, top_k))
    table = table.at[
        g_ids.reshape(-1), expert_idx.reshape(-1), pos_c.reshape(-1)
    ].set(tok_ids.reshape(-1) + 1)
    table = table[:, :, :C]                                   # drop spill col
    occupied = table > 0

    # --- expert compute over gathered inputs ------------------------------
    safe = jnp.maximum(table - 1, 0)                          # (g,E,C)
    # gather: per group, rows of xg at `safe`
    xin = jax.vmap(lambda xrow, idx: xrow[idx])(xg, safe)     # (g,E,C,D)
    xin = jnp.where(occupied[..., None], xin, 0.0)
    xin = pins("moe_gecd", xin).astype(x.dtype)
    h = jax.nn.silu(jnp.einsum("gecd,edf->gecf", xin,
                               p["gate"].astype(x.dtype)))
    h = h * jnp.einsum("gecd,edf->gecf", xin, p["up"].astype(x.dtype))
    h = pins("moe_gecf", h)
    out_e = jnp.einsum("gecf,efd->gecd", h, p["down"].astype(x.dtype))
    out_e = pins("moe_gecd", out_e)

    # --- combine: gather each token's expert outputs back -----------------
    out_tok = jax.vmap(
        lambda oe, e_idx, p_idx: oe[e_idx, p_idx]             # (Tg,k,D)
    )(out_e, expert_idx, jnp.minimum(pos_c, C - 1))
    out = jnp.einsum("gtkd,gtk->gtd", out_tok,
                     gate_vals.astype(x.dtype))
    out = pins("moe_gtd", out)

    # --- aux losses --------------------------------------------------------
    me = probs.mean(axis=(0, 1))                              # (E,)
    ce = sel.astype(jnp.float32).sum(axis=2).mean(axis=(0, 1))
    lb_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    dropped = 1.0 - keep.mean()
    aux = {"lb_loss": lb_loss, "z_loss": z_loss,
           "fraction_dropped": dropped.astype(jnp.float32)}
    return out.reshape(B, S, D).astype(x.dtype), aux


def _route(p: dict, x: jax.Array, top_k: int):
    """Softmax over the router's logits in float32, the top ``top_k``
    experts, gates renormalised to sum to 1: (gates (T, k) f32,
    experts (T, k) int32) for x (T, D)."""
    logits = x.astype(jnp.float32) @ p["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    return gate_vals, expert_idx


def _counts(expert_idx: jax.Array, valid: jax.Array, n_experts: int):
    """[routed pairs, distinct experts hit] (int32) of the valid tokens."""
    hit = jnp.zeros((n_experts,), jnp.int32).at[expert_idx].max(
        jnp.broadcast_to(valid[:, None], expert_idx.shape).astype(jnp.int32))
    pairs = valid.astype(jnp.int32).sum() * expert_idx.shape[-1]
    return jnp.stack([pairs, hit.sum()])


def _expert_matmul(xs: jax.Array, w: jax.Array,
                   group_sizes: jax.Array) -> jax.Array:
    """xs (R, K) rows sorted by expert, ``group_sizes`` (E,) rows each
    (rows past their sum belong to none); w (E, K, N) -> (R, N).

    On the TPU one grouped matmul (``lax.ragged_dot``, XLA's native
    ragged dot).  Elsewhere — the CPU tests — each row multiplies its
    own expert's matrix: ``ragged_dot``'s fallback there is a dense
    product over every expert whose rounding follows the row count, and
    a row's result must not depend on which other rows share the call
    (the prefix-KV prefill is bit-identical to the recompute one)."""
    def rowwise(xs, w, group_sizes):
        ends = jnp.cumsum(group_sizes)
        e = jnp.searchsorted(ends, jnp.arange(xs.shape[0]), side="right")
        w_row = jnp.take(w, jnp.minimum(e, w.shape[0] - 1), axis=0)
        return jnp.einsum("rk,rkn->rn", xs, w_row)

    return jax.lax.platform_dependent(
        xs, w, group_sizes, tpu=jax.lax.ragged_dot, default=rowwise)


def moe_dropless(p: dict, x: jax.Array, *, top_k: int,
                 valid: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Drop-free routed FFN for serving prefill: x (..., D) -> (out,
    counts [routed pairs, experts hit]).

    Every (token, expert) pair is computed, whatever the load: the pairs
    are sorted by expert and each expert's rows go through its matrices
    in one grouped matmul (``_expert_matmul``), so the expert FLOPs are
    those of the routed pairs, not of every expert for every token.
    Tokens where ``valid`` (same leading shape) is False — bucket padding
    — are routed to no expert and return 0.
    """
    lead, D = x.shape[:-1], x.shape[-1]
    E = p["gate"].shape[0]
    xt = x.reshape(-1, D)
    T = xt.shape[0]
    ok = (jnp.ones((T,), bool) if valid is None
          else valid.reshape(T).astype(bool))
    with jax.named_scope("moe_route"):
        gate_vals, expert_idx = _route(p, xt, top_k)
        pair_ok = jnp.repeat(ok, top_k)
        # padding pairs sort behind every expert's group (id E)
        flat_e = jnp.where(pair_ok, expert_idx.reshape(-1), E)
        order = jnp.argsort(flat_e, stable=True)
        group_sizes = jnp.zeros((E,), jnp.int32).at[flat_e].add(
            1, mode="drop")
        xs = jnp.take(xt, order // top_k, axis=0)           # (T*k, D)
        counts = _counts(expert_idx, ok, E)
    with jax.named_scope("moe_experts"):
        dt = x.dtype
        h = jax.nn.silu(_expert_matmul(xs, p["gate"].astype(dt),
                                       group_sizes))
        h = h * _expert_matmul(xs, p["up"].astype(dt), group_sizes)
        ys = _expert_matmul(h, p["down"].astype(dt), group_sizes)
    with jax.named_scope("moe_combine"):
        # rows past the last group are no expert's: zero, never garbage
        ys = jnp.where(pair_ok[order][:, None], ys, 0)
        y = jnp.zeros_like(ys).at[order].set(ys).reshape(T, top_k, D)
        w = jnp.where(ok[:, None], gate_vals, 0.0).astype(dt)
        out = jnp.einsum("tkd,tk->td", y, w)
    return out.reshape(*lead, D), counts


def moe_decode(p: dict, x: jax.Array, *, top_k: int,
               valid: Optional[jax.Array] = None,
               pins: Pins = no_pins) -> Tuple[jax.Array, jax.Array]:
    """Decode-time MoE for small token counts: every (sharded) expert
    computes all B tokens; gates mask the result (no capacity, no drop).
    At a few dozen tokens a step reads every expert's weights anyway, so
    the extra FLOPs cost no time while the step is bound by those bytes.
    Returns (out (B, D), counts [routed pairs, experts hit] of the
    ``valid`` rows)."""
    B, D = x.shape
    E = p["gate"].shape[0]
    ok = jnp.ones((B,), bool) if valid is None else valid.astype(bool)
    with jax.named_scope("moe_route"):
        gate_vals, expert_idx = _route(p, x, top_k)
        w = jnp.einsum("bke,bk->be", jax.nn.one_hot(expert_idx, E),
                       gate_vals)
        counts = _counts(expert_idx, ok, E)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(jnp.einsum("bd,edf->ebf", x,
                                   p["gate"].astype(x.dtype)))
        h = h * jnp.einsum("bd,edf->ebf", x, p["up"].astype(x.dtype))
        out_e = jnp.einsum("ebf,efd->ebd", h, p["down"].astype(x.dtype))
    with jax.named_scope("moe_combine"):
        out = jnp.einsum("be,ebd->bd", w.astype(x.dtype), out_e)
    return out, counts
