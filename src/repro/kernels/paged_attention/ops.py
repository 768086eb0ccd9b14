"""Jitted wrapper: full decode attention = RSW translate + paged attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .paged_attention import paged_attention_pallas
from .ref import paged_attention_ref, normalize


@functools.partial(jax.jit, static_argnames=("tok_offset", "tok_stride",
                                             "block_tokens", "interpret",
                                             "use_kernel", "combine_axes"))
def paged_attention(q, k_pool, v_pool, slots, ctx_len, *,
                    tok_offset: int = 0, tok_stride: int = 1,
                    block_tokens=None, interpret: bool,
                    use_kernel: bool = True, combine_axes=()):
    """Decode attention over translated KV blocks.

    ``combine_axes``: mesh axis names to psum-combine partial softmax
    results over (flash-decoding across token/slot shards).  Empty outside
    shard_map.  ``interpret`` has no default: True runs the Pallas
    interpreter (CPU tests), False lowers the kernel through Mosaic (TPU).
    Returns normalized output (B, H, D).
    """
    fn = paged_attention_pallas if use_kernel else paged_attention_ref
    kwargs = dict(tok_offset=tok_offset, tok_stride=tok_stride,
                  block_tokens=block_tokens)
    if use_kernel:
        kwargs["interpret"] = interpret
    o, m, l = fn(q, k_pool, v_pool, slots, ctx_len, **kwargs)
    if combine_axes:
        m_glob = jax.lax.pmax(m, combine_axes)
        corr = jnp.exp(m - m_glob)
        o = jax.lax.psum(o * corr[..., None], combine_axes)
        l = jax.lax.psum(l * corr, combine_axes)
    return normalize(o, l).astype(q.dtype)
