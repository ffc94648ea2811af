"""jit'd public wrapper for the cache_lookup kernels.

Device ids are int32 (TPU-native); the int64 host sentinel CACHE_PAD maps
to INT32_MAX here. Queries use -1 for padding (never hits).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.cache_lookup.cache_lookup import cache_lookup as _kernel
from repro.kernels.cache_lookup.ref import cache_lookup_ref

#: a plain int: importing this module must not initialise a JAX backend
INT32_SENTINEL = 2 ** 31 - 1


def to_device_ids(ids64) -> jax.Array:
    """Clamp the int64 CACHE_PAD sentinel into int32 space."""
    return jnp.where(ids64 >= INT32_SENTINEL, INT32_SENTINEL,
                     ids64).astype(jnp.int32)


@partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def cache_lookup(cache_ids: jax.Array, cache_feats: jax.Array,
                 query: jax.Array, base: jax.Array, *,
                 use_kernel: bool = False, interpret: bool = False
                 ) -> Tuple[jax.Array, jax.Array]:
    if use_kernel:
        return _kernel(cache_ids.astype(jnp.int32),
                       cache_feats, query.astype(jnp.int32), base,
                       interpret=interpret)
    return cache_lookup_ref(cache_ids, cache_feats, query, base)
