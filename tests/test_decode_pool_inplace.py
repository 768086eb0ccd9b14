"""The decode scan bodies write each token's K/V into the scan-carried
``(L, slots, bs, KV, hd)`` pools in place and gather a layer's blocks
straight from them; no layer's pool is sliced out or written back.

* Bitwise oracle: the reference is the earlier layer body — slice layer
  ``i``'s pools out of the carry, scatter the new K/V into the copy,
  gather the blocks from the copy, write the whole layer back — put in
  place of the two pool helpers, ``decode._pool_write`` and
  ``decode._pool_blocks``, which every scan body (scalar dense, hybrid,
  speculative verify) goes through.  Logits, tokens and both pools must
  match bit for bit over several steps with ragged contexts, an inactive
  row, a write that crosses a block boundary and a shared
  (prefix-cached) slot.
* Structural guard: the optimized HLO's loop body holds no
  dynamic-slice, dynamic-update-slice or copy of one layer's pool.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, reduced
from repro.kernels.paged_attention.ref import gather_pool_blocks
from repro.models import init_params, model_dims
from repro.serve import decode, spec_decode
from repro.serve.decode import DecodeSpec, init_decode_state, make_serve_step
from repro.serve.spec_decode import make_spec_decode_step

B, NBLK = 4, 4
# 13 slots: the layer pool (13, bs, KV, hd) is a shape nothing else in
# the step program has, so the structural guard can find it by shape
SLOTS = 13
# logical blocks of each row -> pool slot.  Row 1 reads row 0's first
# block (a prefix-cached, shared slot) and writes only its own block 1;
# row 2 is inactive; row 0 crosses from block 1 into block 2 on the
# third step (the verify window crosses on the first)
BLOCKS = {0: [2, 5, 9], 1: [2, 7], 2: [0, 4, 11], 3: [12]}
ACTIVE = np.array([True, True, False, True])


def _ctx(bs):
    return np.array([2 * bs - 2, bs + 3, 2 * bs + 4, 3], np.int32)


def _spec(cfg):
    return DecodeSpec(block_size=cfg.kv_block_size, max_blocks_per_seq=NBLK,
                      slots_per_group=SLOTS, n_sets=2, assoc=4)


def _setup(arch, dtype=jnp.bfloat16):
    cfg = reduced(ARCHS[arch])
    dims = model_dims(cfg, tp=1)
    params = init_params(jax.random.PRNGKey(0), cfg, dims)
    spec = _spec(cfg)
    st = init_decode_state(cfg, dims, spec, B, 1, dtype=dtype)
    rng = np.random.RandomState(1)
    flex = -np.ones((1, B * NBLK), np.int32)
    for row, slots in BLOCKS.items():
        flex[0, row * NBLK:row * NBLK + len(slots)] = slots
    st["flex"] = jnp.asarray(flex)
    st["ctx_len"] = jnp.asarray(_ctx(cfg.kv_block_size))
    for name in ("k_pool", "v_pool"):
        st[name] = jnp.asarray(rng.randn(*st[name].shape), dtype)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, B), jnp.int32)
    return cfg, dims, params, spec, st, tokens


# ------------------------------------------- the earlier layer body

def _slice_write_back(kp, vp, i, ws, t, k_new, v_new, valid):
    """Slice layer ``i`` out, scatter into the copy, write it all back."""
    kp_l = jax.lax.dynamic_index_in_dim(kp, i, 0, keepdims=False)
    vp_l = jax.lax.dynamic_index_in_dim(vp, i, 0, keepdims=False)
    ws = jnp.where(valid, ws, kp_l.shape[0])
    kp_l = kp_l.at[ws, t].set(k_new.astype(kp_l.dtype), mode="drop")
    vp_l = vp_l.at[ws, t].set(v_new.astype(vp_l.dtype), mode="drop")
    return (jax.lax.dynamic_update_index_in_dim(kp, kp_l, i, 0),
            jax.lax.dynamic_update_index_in_dim(vp, vp_l, i, 0))


def _slice_gather(pool, i, slots):
    """Gather the blocks from layer ``i``'s slice of the pool."""
    return gather_pool_blocks(
        jax.lax.dynamic_index_in_dim(pool, i, 0, keepdims=False), slots)


def _install_slice_write_back(monkeypatch):
    """Put the earlier layer body in every scan body."""
    for mod in (decode, spec_decode):
        monkeypatch.setattr(mod, "_pool_write", _slice_write_back)
        monkeypatch.setattr(mod, "_pool_blocks", _slice_gather)


def _run_serve(cfg, dims, params, spec, st, tokens, n=3):
    step = jax.jit(make_serve_step(cfg, dims, spec, dtype=jnp.bfloat16))
    out = []
    for _ in range(n):
        logits, st, stats = step(params, st, tokens, jnp.asarray(ACTIVE))
        tokens = stats["next_token"]
        out.append(jax.device_get((logits, stats["next_token"],
                                   st["k_pool"], st["v_pool"])))
    return out


def _bits(a):
    a = np.asarray(a)
    return a.view(f"u{a.dtype.itemsize}") if a.dtype.kind == "V" or \
        np.issubdtype(a.dtype, np.floating) else a


def _assert_bitwise(got, want, names):
    for step, (g, w) in enumerate(zip(got, want)):
        for name, a, b in zip(names, g, w):
            assert np.asarray(a).dtype == np.asarray(b).dtype, name
            np.testing.assert_array_equal(_bits(a), _bits(b),
                                          err_msg=f"{name}, step {step}")


@pytest.mark.parametrize("arch", ["granite-8b", "jamba-1.5-large-398b"])
def test_serve_step_bitwise_equals_slice_write_back(arch, monkeypatch):
    cfg, dims, params, spec, st, tokens = _setup(arch)
    got = _run_serve(cfg, dims, params, spec, st, tokens)
    _install_slice_write_back(monkeypatch)
    want = _run_serve(cfg, dims, params, spec, st, tokens)
    _assert_bitwise(got, want, ("logits", "next_token", "k_pool", "v_pool"))
    # the state exercises what it should: the inactive row's blocks and
    # the shared block are left as they were, and row 0's third write
    # crossed into its block 2
    k_in, k_out = _bits(st["k_pool"]), _bits(got[-1][2])
    for slot in BLOCKS[2] + BLOCKS[0][:1]:
        np.testing.assert_array_equal(k_out[:, slot], k_in[:, slot])
    assert not np.array_equal(k_out[:, BLOCKS[0][2], 0],
                              k_in[:, BLOCKS[0][2], 0])


def _run_verify(cfg, dims, params, spec, st, tokens, K=3, n=2):
    bs = cfg.kv_block_size
    hist = -np.ones((B, NBLK * bs), np.int32)
    rng = np.random.RandomState(2)
    for row, c in enumerate(_ctx(bs)):
        # a repeated pattern, so the drafter proposes and some accept
        hist[row, :c] = np.tile(rng.randint(0, cfg.vocab_size, 3),
                                c // 3 + 1)[:c]
    st = dict(st, hist=jnp.asarray(hist))
    step = jax.jit(make_spec_decode_step(cfg, dims, spec, K,
                                         dtype=jnp.bfloat16))
    out = []
    for _ in range(n):
        logits, st, stats = step(params, st, tokens, jnp.asarray(ACTIVE))
        n_emit = stats["n_emit"]
        tokens = jnp.take_along_axis(stats["acc_tokens"],
                                     (n_emit - 1)[:, None], axis=1)[:, 0]
        out.append(jax.device_get((logits, stats["acc_tokens"],
                                   st["k_pool"], st["v_pool"])))
    return out


def test_spec_verify_bitwise_equals_slice_write_back(monkeypatch):
    cfg, dims, params, spec, st, tokens = _setup("granite-8b")
    got = _run_verify(cfg, dims, params, spec, st, tokens)
    _install_slice_write_back(monkeypatch)
    want = _run_verify(cfg, dims, params, spec, st, tokens)
    _assert_bitwise(got, want, ("logits", "acc_tokens", "k_pool", "v_pool"))


# ----------------------------------------------------- structural guard

_CALLED = re.compile(r"(?:calls|to_apply|body|condition)=%([\w.\-]+)"
                     r"|branch_computations=\{([^}]*)\}")
_SHAPE = re.compile(r"\b(?:bf16|f16|f32)\[([\d,]*)\]")


def _computations(hlo: str):
    """name -> instruction lines of each computation of an HLO module."""
    comps, name = {}, None
    for line in hlo.splitlines():
        # a computation's header starts its line; instructions are indented
        m = re.match(r"(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$", line)
        if m:
            name = m.group(1)
            comps[name] = []
        elif name is not None and line.strip() not in ("", "}"):
            comps[name].append(line)
    return comps


def _loop_body_lines(hlo: str):
    """The instructions of every while body, with every computation they
    call (fusions, nested calls), transitively."""
    comps = _computations(hlo)
    todo = [m.group(1) for lines in comps.values() for line in lines
            for m in [re.search(r"\bwhile\(.*\bbody=%([\w.\-]+)", line)]
            if m]
    assert todo, "no while loop in the step program"
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for m in _CALLED.finditer(line):
                if m.group(1):
                    todo.append(m.group(1))
                else:
                    todo += [c.strip().lstrip("%")
                             for c in m.group(2).split(",")]
    return [line for name in seen for line in comps[name]]


def _layer_pool_ops(fn, args, layer_shape):
    """Loop-body dynamic-slice / dynamic-update-slice / copy lines that
    produce or take an array of one layer pool's shape (or that shape
    with a leading unit axis, as a slice of the stacked pool has)."""
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    want = {layer_shape, (1,) + layer_shape}
    hits = []
    for line in _loop_body_lines(hlo):
        if not re.search(r"\s(dynamic-slice|dynamic-update-slice|copy)\(",
                         line):
            continue
        shapes = {tuple(int(d) for d in s.split(",") if d)
                  for s in _SHAPE.findall(line)}
        if shapes & want:
            hits.append(line.strip())
    return hits


def _step_program(kind, cfg, dims, params, spec, st, tokens):
    act = jnp.asarray(ACTIVE)
    if kind == "serve":
        return (make_serve_step(cfg, dims, spec, dtype=jnp.bfloat16),
                (params, st, tokens, act))
    hist = -jnp.ones((B, NBLK * cfg.kv_block_size), jnp.int32)
    return (make_spec_decode_step(cfg, dims, spec, 3, dtype=jnp.bfloat16),
            (params, dict(st, hist=hist), tokens, act))


@pytest.mark.parametrize("kind", ["serve", "verify"])
def test_loop_body_moves_no_layer_pool(kind, monkeypatch):
    cfg, dims, params, spec, st, tokens = _setup("granite-8b")
    layer_shape = tuple(st["k_pool"].shape[1:])
    assert layer_shape[0] == SLOTS
    fn, args = _step_program(kind, cfg, dims, params, spec, st, tokens)
    # The copy of the whole (L, ...) pool at loop entry remains: the step
    # does not donate its decode state.  Donating it (``donate_argnums``
    # on the serve and verify jits) is the follow-up that removes it.
    assert _layer_pool_ops(fn, args, layer_shape) == []
    # the guard does find the earlier body's slices and write-backs
    _install_slice_write_back(monkeypatch)
    fn, args = _step_program(kind, cfg, dims, params, spec, st, tokens)
    assert _layer_pool_ops(fn, args, layer_shape)
