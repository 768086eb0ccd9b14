"""Jitted wrapper for the flash attention kernel."""
from __future__ import annotations

import functools

import jax

from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "q_tile", "kv_tile",
                                             "interpret", "use_kernel"))
def flash_attention(q, k, v, *, causal: bool = True, q_tile: int = 128,
                    kv_tile: int = 128, interpret: bool,
                    use_kernel: bool = True):
    if not use_kernel:
        return flash_attention_ref(q, k, v, causal=causal)
    return flash_attention_pallas(q, k, v, causal=causal, q_tile=q_tile,
                                  kv_tile=kv_tile, interpret=interpret)
