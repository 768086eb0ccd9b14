"""Pure-jnp oracle for the RSW kernel (wraps the core hybrid translation)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.hashes import get_hash


def rsw_ref(vpns: jax.Array, tar: jax.Array, sf: jax.Array,
            flex_flat: jax.Array, *, hash_name: str = "modulo"):
    n_sets, assoc = tar.shape
    h = get_hash(hash_name)
    set_idx = h(vpns.astype(jnp.int32), n_sets).astype(jnp.int32)
    tags = tar[set_idx]                                  # (N, assoc)
    counters = sf[set_idx]
    eq = tags == (vpns[:, None].astype(jnp.int32) + 1)
    hit = jnp.any(eq, axis=-1) & (counters > 0)
    way = jnp.argmax(eq, axis=-1).astype(jnp.int32)
    rest_slot = set_idx * assoc + jnp.where(hit, way, 0)
    # a vpn outside the flex table reads as unmapped, as in the kernel
    n_flex = flex_flat.shape[0]
    in_table = (vpns >= 0) & (vpns < n_flex)
    flex_slot = jnp.where(in_table, flex_flat[jnp.clip(vpns, 0, n_flex - 1)],
                          -1)
    slot = jnp.where(hit, rest_slot, flex_slot)
    mapped = hit | (flex_slot >= 0)
    return (jnp.where(mapped, slot, -1).astype(jnp.int32),
            hit.astype(jnp.int32), mapped.astype(jnp.int32))
