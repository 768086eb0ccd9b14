"""Jitted public wrapper for the RSW kernel."""
from __future__ import annotations

import functools

import jax

from .utopia_rsw import rsw_pallas
from .ref import rsw_ref


@functools.partial(jax.jit, static_argnames=("hash_name", "tile", "interpret",
                                             "use_kernel"))
def utopia_rsw(vpns, tar, sf, flex_flat, *, hash_name: str = "modulo",
               tile: int = 128, interpret: bool,
               use_kernel: bool = True):
    """Hybrid translate a batch of vpns.

    Returns (slot, in_rest, mapped) int32 arrays of shape ``vpns.shape``.
    ``use_kernel=False`` dispatches to the pure-jnp oracle (CPU fast path).
    ``interpret`` has no default: True runs the Pallas interpreter (CPU
    tests), False lowers the kernel through Mosaic (TPU).
    """
    shape = vpns.shape
    flat = vpns.reshape(-1)
    if use_kernel:
        out = rsw_pallas(flat, tar, sf, flex_flat, hash_name=hash_name,
                         tile=tile, interpret=interpret)
    else:
        out = rsw_ref(flat, tar, sf, flex_flat, hash_name=hash_name)
    return tuple(o.reshape(shape) for o in out)
