"""Pallas TPU kernels for the device-resident steady cache C_s.

Two fused stages (DESIGN.md §3 kernels):

  1. ``search``  -- positions of queries in the SORTED cache-id vector.
     TPU adaptation: instead of a per-lane binary search (serial, gather-
     heavy), each (Tc x Tq) tile computes comparison-mask partial sums on
     the VPU:  pos(q) = #&#123;ids < q&#125;,  hit(q) = any(ids == q).  Queries
     ride the lanes as a (1, m) row and cache ids the sublanes as an
     (n_hot, 1) column, so every block is 2-D and (8, 128)-aligned (the
     TPU compiler refuses 1-D blocks that do not match the XLA tiling
     of a 1-D array) and the sublane reduction lands lane-dense. The
     cache-id column streams through VMEM in Tc-sized tiles, so n_hot is
     unbounded by VMEM and every op is dense vector work.
  2. ``merge_gather`` -- one cached feature row per grid step, selected by
     a scalar-prefetched BlockSpec index map, merged over the pre-filled
     base buffer (hits win, misses keep the SyncPull value).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_TQ = 512           # queries per tile (lanes, multiple of 128)
DEFAULT_TC = 512           # cache ids per tile (sublanes, multiple of 8)

#: int32 cache sentinel: compares >= every real device id, so padding the
#: cache-id vector with it never perturbs ``pos = #{ids < q}`` or ``hit``.
SENTINEL = 2 ** 31 - 1


def pad_to(x: jax.Array, mult: int, axis: int, value) -> jax.Array:
    """Pad ``x`` along ``axis`` up to the next multiple of ``mult`` with a
    constant. No-op (and no copy) when already aligned; this is how the
    kernels accept arbitrary m / n_hot / d instead of asserting
    divisibility (an awkward batch size used to crash the compiled
    epoch)."""
    n = x.shape[axis]
    rem = (-n) % mult
    if rem == 0:
        return x
    width = [(0, 0)] * x.ndim
    width[axis] = (0, rem)
    return jnp.pad(x, width, constant_values=value)


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


def _search_kernel(q_ref, ids_ref, pos_ref, hit_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        pos_ref[...] = jnp.zeros_like(pos_ref)
        hit_ref[...] = jnp.zeros_like(hit_ref)

    q = q_ref[...]                     # (1, Tq)
    ids = ids_ref[...]                 # (Tc, 1)
    pos_ref[...] += jnp.sum((ids < q).astype(jnp.int32), axis=0,
                            keepdims=True)
    hit_ref[...] = jnp.maximum(
        hit_ref[...], jnp.max((ids == q).astype(jnp.int32), axis=0,
                              keepdims=True))


def search(cache_ids: jax.Array, query: jax.Array, tq: int = DEFAULT_TQ,
           tc: int = DEFAULT_TC, interpret: bool = False,
           name: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """cache_ids (n_hot,) sorted int32; query (m,) int32 -> (pos, hit).
    ``name`` names the Pallas call (and so its op in a profile).

    Arbitrary ``m`` / ``n_hot`` (including 0-sized caches) are handled by
    internal padding: queries pad with -1 (matches nothing, pos rows
    sliced off), cache ids pad with the INT32_MAX sentinel (sorts after
    every real id, so no real query's rank or hit changes). Sentinel
    queries NEVER hit -- they would otherwise match the padded cache
    tail -- matching the jnp oracle's contract.
    """
    m = query.shape[0]
    if m == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.bool_))
    if cache_ids.shape[0] == 0:
        cache_ids = jnp.full((1,), SENTINEL, jnp.int32)
    tq = min(tq, _round_up(m, 128))
    tc = min(tc, _round_up(cache_ids.shape[0], 8))
    query = pad_to(query, tq, 0, -1)
    cache_ids = pad_to(cache_ids, tc, 0, SENTINEL)
    mp = query.shape[0]
    n_hot = cache_ids.shape[0]
    pos, hit = pl.pallas_call(
        _search_kernel,
        grid=(mp // tq, n_hot // tc),
        in_specs=[pl.BlockSpec((1, tq), lambda i, j: (0, i)),
                  pl.BlockSpec((tc, 1), lambda i, j: (j, 0))],
        out_specs=[pl.BlockSpec((1, tq), lambda i, j: (0, i)),
                   pl.BlockSpec((1, tq), lambda i, j: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((1, mp), jnp.int32),
                   jax.ShapeDtypeStruct((1, mp), jnp.int32)],
        interpret=interpret,
        name=name,
    )(query.reshape(1, mp), cache_ids.reshape(n_hot, 1))
    return pos[0, :m], (hit[0, :m] > 0) & (query[:m] != SENTINEL)


def _merge_kernel(pos, hit, feats_ref, base_ref, o_ref):
    i = pl.program_id(0)
    h = hit[i]
    f = feats_ref[...].astype(o_ref.dtype)
    b = base_ref[...]
    o_ref[...] = jnp.where(h, f, b)


def merge_gather(cache_feats: jax.Array, base: jax.Array, pos: jax.Array,
                 hit: jax.Array, d_tile: int = 128,
                 interpret: bool = False) -> jax.Array:
    """base (m, d) pre-filled buffer; cached rows win where hit.

    A feature dim not divisible by ``d_tile`` pads internally (both
    operands, sliced off the output) instead of asserting.
    """
    m, d0 = base.shape
    if cache_feats.shape[0] == 0:       # empty cache: nothing can hit
        return base
    dt = min(d0, d_tile)
    if d0 % dt:
        cache_feats = pad_to(cache_feats, dt, 1, 0)
        base = pad_to(base, dt, 1, 0)
    d = base.shape[1]
    n_hot = cache_feats.shape[0]
    pos_c = jnp.minimum(pos, n_hot - 1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,         # pos, hit
        grid=(m, d // dt),
        in_specs=[
            pl.BlockSpec((1, dt), lambda i, k, p, h: (p[i], k)),
            pl.BlockSpec((1, dt), lambda i, k, p, h: (i, k)),
        ],
        out_specs=pl.BlockSpec((1, dt), lambda i, k, p, h: (i, k)),
    )
    out = pl.pallas_call(
        _merge_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, d), base.dtype),
        interpret=interpret,
    )(pos_c, hit, cache_feats, base)
    return out[:, :d0]


def cache_lookup(cache_ids: jax.Array, cache_feats: jax.Array,
                 query: jax.Array, base: jax.Array,
                 interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    pos, hit = search(cache_ids, query, interpret=interpret)
    merged = merge_gather(cache_feats, base, pos, hit, interpret=interpret)
    return merged, hit
