"""jit'd public wrapper for the fused feature-assembly kernel.

Three interchangeable backends, all bit-identical on the same inputs
(every output row is a copy of exactly one source row):

  * ``"fused"``  -- the Pallas single-pass kernel (TPU; ``interpret=True``
    runs it on CPU for validation).
  * ``"ref"``    -- the pure-jnp fused oracle (CPU default; one traced
    where-chain, no kernel).
  * ``"staged"`` -- the legacy three-stage chain (``cache_lookup`` then
    local-shard overlay), kept as the interpret-mode oracle the parity
    suite pins the fused kernel to.

``backend="auto"`` resolves to ``"fused"`` on TPU and ``"ref"``
elsewhere, so the epoch programs pick the right path per platform with
no caller changes.  ``cache_ids=None`` assembles cache-less (the
on-demand baseline): local shard over pulled residuals only.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.assemble.assemble import assemble as _kernel
from repro.kernels.assemble.assemble import lane_width, rows_view
from repro.kernels.assemble.ref import assemble_ref
from repro.kernels.cache_lookup.ops import cache_lookup

BACKENDS = ("auto", "fused", "ref", "staged")


def resolve_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"assemble backend {backend!r} not in {BACKENDS}")
    if backend == "auto":
        return "fused" if jax.default_backend() == "tpu" else "ref"
    return backend


def local_merge(table: jnp.ndarray, base, query: jnp.ndarray,
                fallback: jnp.ndarray) -> jnp.ndarray:
    """Overlay this worker's shard rows onto ``fallback`` where the
    queried device id is locally owned (slot in [0, n_per)); padding ids
    (-1) are never local. The final stage of the legacy chain."""
    n_per = table.shape[0]
    slot = query - base
    local = (slot >= 0) & (slot < n_per)
    rows = table[jnp.clip(slot, 0, n_per - 1)]
    return jnp.where(local[:, None], rows.astype(fallback.dtype), fallback)


def _staged(table, base, cache_ids, cache_feats, query, pulled,
            use_kernel, interpret):
    """The legacy three-stage chain: pulled -> C_s merge -> local
    overlay. Three (m, d) materializations; retained as the oracle."""
    if cache_ids is None:
        return local_merge(table, base, query, pulled)
    merged, _ = cache_lookup(cache_ids, cache_feats, query, pulled,
                             use_kernel=use_kernel, interpret=interpret)
    return local_merge(table, base, query, merged)


def source_views(table: jax.Array, cache_feats: Optional[jax.Array],
                 backend: str):
    """-> (table, cache_feats, width): the sources as ``backend`` reads
    them, and the width of the pulled rows it reads with them. The fused
    kernel copies whole 128-lane rows, so it takes the lane-padded row
    views (``rows_view``) and pulled rows ``lane_width(d)`` wide; the
    other backends take the arrays as they are and ``d``. A caller that
    assembles many steps from one table builds these once, outside its
    step loop: inside it, XLA pads the table again on every step."""
    d = table.shape[-1]
    if resolve_backend(backend) != "fused":
        return table, cache_feats, d
    return (rows_view(table),
            None if cache_feats is None else rows_view(cache_feats),
            lane_width(d))


@partial(jax.jit, static_argnames=("backend", "interpret"))
def assemble_features(table: jax.Array, base, cache_ids: Optional[jax.Array],
                      cache_feats: Optional[jax.Array], query: jax.Array,
                      pulled: jax.Array, *, backend: str = "auto",
                      interpret: bool = False) -> jax.Array:
    """Single-pass per-step feature assembly (DESIGN.md §3, §6.3).

    table (n_per, d) this worker's shard; base scalar first device slot;
    cache_ids (n_hot,) sorted int32 / None; cache_feats (n_hot, d) /
    None; query (m,) int32 device ids (-1 padded); pulled (m, d) a2a
    residual buffer -> (m, d) assembled rows, priority local > C_s >
    pulled. The fused backend also takes ``table`` and ``cache_feats``
    as their ``rows_view`` and ``pulled`` lane-padded (``source_views``),
    and then returns rows as wide as ``pulled``.
    """
    backend = resolve_backend(backend)
    if backend == "staged":
        return _staged(table, base, cache_ids, cache_feats, query, pulled,
                       use_kernel=interpret, interpret=interpret)
    if cache_ids is None:
        cache_ids = jnp.zeros((0,), jnp.int32)
        cache_feats = jnp.zeros((0,) + pulled.shape[1:], pulled.dtype)
    if backend == "fused":
        return _kernel(table, base, cache_ids.astype(jnp.int32),
                       cache_feats, query.astype(jnp.int32), pulled,
                       interpret=interpret)
    return assemble_ref(table, base, cache_ids, cache_feats, query, pulled)
