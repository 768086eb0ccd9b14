"""Ahead-of-time compiles of the Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: lowering
with ``interpret=False`` against a *described* v5e device runs Mosaic,
which refuses what the Pallas interpreter accepts (unsupported shape
casts, vector loads from SMEM, integer argmax).  Shapes are granite-8b's
serving widths in bf16: 32 q heads and 8 kv heads of 128, KV blocks of 64
tokens, a batch of 16 sequences of up to 4096 tokens.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every pytest worker imports
every test file.  All chip-compile tests live in this one file so that
they share that worker.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas)
from repro.kernels.paged_attention.paged_attention import (
    paged_attention_pallas)
from repro.kernels.utopia_rsw.utopia_rsw import rsw_pallas

H, KV, D = 32, 8, 128            # granite-8b attention widths
BS, B, NBLK = 64, 16, 64         # block tokens, batch, blocks per seq
N_SLOTS = 1280                   # pool of B * NBLK * 1.25 blocks
N_SETS, ASSOC = 120, 8           # RestSeg: 75% of the pool, 8-way


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel itself is in the program, not an XLA fallback
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_rsw_compiles(one_chip):
    n = B * NBLK
    _compile(lambda v, t, s, f: rsw_pallas(v, t, s, f, interpret=False),
             one_chip, ((n,), jnp.int32), ((N_SETS, ASSOC), jnp.int32),
             ((N_SETS,), jnp.int32), ((n,), jnp.int32))


@pytest.mark.parametrize("q_shape,ctx_shape", [
    ((B, H, D), (B,)),                  # decode: one query per sequence
    ((4, 64, H, D), (4,)),              # prefix read: a chunk, one extent
    ((4, 256, H, D), (4,)),             # a chunk of four query blocks
    ((B, 5, H, D), (B, 5)),             # spec verify: per-query extents
], ids=["decode", "prefix_read", "prefix_read_long", "verify"])
def test_paged_attention_compiles(one_chip, q_shape, ctx_shape):
    pool = (N_SLOTS, BS, KV, D)
    _compile(lambda q, k, v, s, c: paged_attention_pallas(
                 q, k, v, s, c, interpret=False),
             one_chip, (q_shape, jnp.bfloat16), (pool, jnp.bfloat16),
             (pool, jnp.bfloat16), ((q_shape[0], NBLK), jnp.int32),
             (ctx_shape, jnp.int32))


def test_flash_attention_compiles(one_chip):
    S = 2048
    _compile(lambda q, k, v: flash_attention_pallas(
                 q, k, v, causal=True, interpret=False),
             one_chip, ((1, S, H, D), jnp.bfloat16),
             ((1, S, KV, D), jnp.bfloat16), ((1, S, KV, D), jnp.bfloat16))


def test_dropless_moe_compiles_to_ragged_dot(one_chip):
    """Mellum2's routed FFN at its widths (64 experts of 896 over d 2304,
    top 8) for 2,048 prompt tokens: the grouped matmul is the TPU's
    ragged dot, not a dense product over every expert."""
    from repro.models.moe import moe_dropless
    E, Dm, F, T = 64, 2304, 896, 2048
    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
    p = {"router": {"w": sd((Dm, E))}, "gate": sd((E, Dm, F)),
         "up": sd((E, Dm, F)), "down": sd((E, F, Dm))}
    compiled = jax.jit(lambda p, x: moe_dropless(p, x, top_k=8)).lower(
        p, sd((T, Dm))).compile()
    assert "ragged-dot" in compiled.as_text()
