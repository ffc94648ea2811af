"""Pallas TPU kernel: fused single-pass feature assembly (DESIGN.md §3).

Replaces the legacy three-stage assembly chain of the device epoch
(``pull_shard`` scatter -> ``cache_lookup.search`` -> ``merge_gather`` ->
jnp local-shard overlay) with ONE kernel pass over the query rows.

Two phases, one output materialization:

  1. *classify* (metadata, (m,)-shaped): the tiled VPU mask-sum binary
     search over the sorted hot-set ids (``cache_lookup.search``, shared
     -- it is already dense vector work) plus the arithmetic ownership
     test ``base <= q < base + n_per``, folded into ONE scalar-prefetch
     vector: per row, the source selector (pulled / cache / local) in the
     top bits and the row to gather from that source in the low bits.
  2. *select* -- ``pl.pallas_call`` over grid ``(m,)`` whose BlockSpec
     index maps gather the cache row, the local-shard row and the pulled
     row for each query, and whose body writes the winning row ONCE.
     The legacy chain materialized three full ``(m, d)`` buffers
     (merge_gather output, the local-shard gather, the final where);
     this path writes exactly one.

TPU layout: every source is viewed as ``(rows, 1, d)`` and gathered one
``(1, d)`` row per grid step, so each block's last two dims equal the
array's (a ``(1, d)`` block of an ``(rows, d)`` array is refused by the
TPU compiler). The scalar-prefetch vector lives in SMEM (1 MiB on v5e),
so rows are processed in chunks of ``MAX_PREFETCH_ROWS``; arbitrary
``m`` / ``n_hot`` / ``d`` are accepted.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cache_lookup.cache_lookup import SENTINEL, search

#: per-row source selector values (top bits of the scalar-prefetched code)
SRC_PULLED, SRC_CACHE, SRC_LOCAL = 0, 1, 2
#: the code packs ``src << SRC_SHIFT | row`` into a non-negative int32;
#: rows stay below 2**29
SRC_SHIFT = 29
ROW_MASK = (1 << SRC_SHIFT) - 1

#: select-pass rows per pallas_call: 4 B of SMEM code per row, so one
#: call stays at 512 KiB of the chip's SMEM
MAX_PREFETCH_ROWS = 1 << 17


def classify(cache_ids: jax.Array, query: jax.Array, base, n_per: int,
             interpret: bool = False) -> jax.Array:
    """-> (m,) int32 code: ``src << SRC_SHIFT | row`` where ``row`` is
    the cache row (SRC_CACHE), the shard slot (SRC_LOCAL) or unused 0
    (SRC_PULLED, which reads the query's own pulled row)."""
    n_hot = cache_ids.shape[0]
    if max(n_hot, n_per) > ROW_MASK:
        raise ValueError(f"row ids past 2**{SRC_SHIFT}: n_hot={n_hot}, "
                         f"n_per={n_per}")
    pos, hit = search(cache_ids, query, interpret=interpret,
                      name="assemble_search")
    slot = query - base
    local = (slot >= 0) & (slot < n_per)
    cpos = jnp.minimum(pos, max(n_hot - 1, 0))
    lslot = jnp.clip(slot, 0, n_per - 1)
    src = jnp.where(local, SRC_LOCAL, jnp.where(hit, SRC_CACHE, SRC_PULLED))
    row = jnp.where(local, lslot, jnp.where(hit, cpos, 0))
    return ((src << SRC_SHIFT) | row).astype(jnp.int32)


def _row_of(code, i, want):
    """Index-map helper: the row to fetch from source ``want`` for query
    ``i`` (row 0 when another source wins -- fetched, never selected)."""
    c = code[i]
    return jnp.where((c >> SRC_SHIFT) == want, c & ROW_MASK, 0)


def _select_kernel(code, cache_ref, table_ref, pulled_ref, o_ref):
    s = code[pl.program_id(0)] >> SRC_SHIFT
    o_ref[...] = jnp.where(
        s == SRC_LOCAL, table_ref[...].astype(o_ref.dtype),
        jnp.where(s == SRC_CACHE, cache_ref[...].astype(o_ref.dtype),
                  pulled_ref[...]))


def _select(code, cache3, table3, pulled3, start: int, n: int,
            interpret: bool) -> jax.Array:
    """Select pass over query rows ``[start, start + n)`` -> (n, 1, d)."""
    d = pulled3.shape[-1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n,),
        in_specs=[
            pl.BlockSpec((None, 1, d),
                         lambda i, c: (_row_of(c, i, SRC_CACHE), 0, 0)),
            pl.BlockSpec((None, 1, d),
                         lambda i, c: (_row_of(c, i, SRC_LOCAL), 0, 0)),
            pl.BlockSpec((None, 1, d), lambda i, c: (start + i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, d), lambda i, c: (i, 0, 0)),
    )
    return pl.pallas_call(
        _select_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, d), pulled3.dtype),
        interpret=interpret,
        name="assemble_select",
    )(code[start:start + n], cache3, table3, pulled3)


def assemble(table: jax.Array, base, cache_ids: jax.Array,
             cache_feats: jax.Array, query: jax.Array, pulled: jax.Array,
             interpret: bool = False) -> jax.Array:
    """Fused assembly: table (n_per, d); base scalar; cache_ids (n_hot,)
    sorted int32; cache_feats (n_hot, d); query (m,) int32; pulled (m, d)
    -> (m, d)."""
    n_per = table.shape[0]
    m, d = pulled.shape
    if m == 0:
        return pulled
    if cache_feats.shape[0] == 0:
        # sentinel row: the selector can never pick it (no hits), but the
        # BlockSpec index map needs an addressable row 0
        cache_ids = jnp.full((1,), SENTINEL, jnp.int32)
        cache_feats = jnp.zeros((1, d), cache_feats.dtype)
    code = classify(cache_ids, query, base, n_per, interpret=interpret)
    cache3 = cache_feats.reshape(-1, 1, d)
    table3 = table.reshape(n_per, 1, d)
    pulled3 = pulled.reshape(m, 1, d)
    parts = [_select(code, cache3, table3, pulled3, st,
                     min(MAX_PREFETCH_ROWS, m - st), interpret)
             for st in range(0, m, MAX_PREFETCH_ROWS)]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return out.reshape(m, d)
