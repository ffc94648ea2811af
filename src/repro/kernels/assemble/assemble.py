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
  2. *select* -- ``pl.pallas_call`` over blocks of R query rows. The
     three sources stay in HBM; for each row of a block the kernel reads
     the row's code and issues ONE DMA, from the source the code names,
     into the row's slot of the ``(R, 1, dp)`` output block, then waits
     for the block's copies; the pipeline writes the block back. The
     legacy chain materialized three full ``(m, d)`` buffers
     (merge_gather output, the local-shard gather, the final where);
     this path writes exactly one, and every row is a bit-copy of one
     source row.

TPU layout (what the TPU compiler accepts):

  * A DMA slice of a row must span whole 128-lane groups, so every
    source is viewed as ``(rows, 1, dp)`` with ``d`` zero-padded to
    ``dp``, the next multiple of 128 (``rows_view``). A ``(1, d)`` row
    slice with ``d`` = 100 or 602 is refused.
  * A one-row slice of a 2-D ``(R, dp)`` VMEM buffer is refused (its row
    dim is tiled by 8), so the block is ``(R, 1, dp)``: one row per
    8-sublane tile. R follows from ``dp`` (``block_rows``).
  * ``table`` and ``cache_feats`` may arrive as their views and
    ``pulled`` already ``dp`` wide: callers that run many steps on one
    table build its view once, outside their step loop, so no step pads
    or copies the table (``ops.source_views``). A ``dp``-wide array
    views as ``(rows, 1, dp)`` by a bitcast at ``dp`` = 128 and by a
    relayout copy otherwise.
  * The scalar-prefetch vector lives in SMEM (1 MiB on v5e), so rows are
    processed in chunks of ``MAX_PREFETCH_ROWS``; arbitrary ``m`` /
    ``n_hot`` / ``d`` are accepted.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.cache_lookup.cache_lookup import SENTINEL, search

#: per-row source selector values (top bits of the scalar-prefetched code)
SRC_PULLED, SRC_CACHE, SRC_LOCAL = 0, 1, 2
#: a select-pass block whose rows come from more than one source
SRC_MIXED = -1
#: the code packs ``src << SRC_SHIFT | row`` into a non-negative int32;
#: rows stay below 2**29
SRC_SHIFT = 29
ROW_MASK = (1 << SRC_SHIFT) - 1

#: select-pass rows per pallas_call: 4 B of SMEM code per row, so one
#: call stays at 512 KiB of the chip's SMEM
MAX_PREFETCH_ROWS = 1 << 17

#: TPU vreg tile: rows are padded to whole 128-lane groups, and a
#: ``(1, dp)`` VMEM row occupies 8 sublanes
LANES, SUBLANES = 128, 8
#: VMEM for one select-pass output block (the pipeline double-buffers it)
BLOCK_VMEM_BYTES = 2 << 20
MAX_BLOCK_ROWS = 1024
#: row copies issued per iteration of the select pass's issue loop
UNROLL = 8


def classify(cache_ids: jax.Array, query: jax.Array, base, n_per: int,
             interpret: bool = False) -> jax.Array:
    """-> (m,) int32 code: ``src << SRC_SHIFT | row`` where ``row`` is
    the cache row (SRC_CACHE), the shard slot (SRC_LOCAL) or the query's
    own position (SRC_PULLED: its pulled row; padding ids read theirs,
    which is zeros)."""
    n_hot, m = cache_ids.shape[0], query.shape[0]
    if max(n_hot, n_per, m) > ROW_MASK:
        raise ValueError(f"row ids past 2**{SRC_SHIFT}: n_hot={n_hot}, "
                         f"n_per={n_per}, m={m}")
    pos, hit = search(cache_ids, query, interpret=interpret,
                      name="assemble_search")
    slot = query - base
    local = (slot >= 0) & (slot < n_per)
    cpos = jnp.minimum(pos, max(n_hot - 1, 0))
    lslot = jnp.clip(slot, 0, n_per - 1)
    src = jnp.where(local, SRC_LOCAL, jnp.where(hit, SRC_CACHE, SRC_PULLED))
    row = jnp.where(local, lslot,
                    jnp.where(hit, cpos, jnp.arange(m, dtype=jnp.int32)))
    return ((src << SRC_SHIFT) | row).astype(jnp.int32)


def lane_width(d: int) -> int:
    """``d`` rounded up to whole 128-lane groups."""
    return pl.cdiv(d, LANES) * LANES


def pad_lanes(x: jax.Array) -> jax.Array:
    """Zero-pad the last dim of ``x`` to ``lane_width``; a no-op when it
    is already a multiple of 128."""
    d = x.shape[-1]
    pad = [(0, 0)] * (x.ndim - 1) + [(0, lane_width(d) - d)]
    return x if d == lane_width(d) else jnp.pad(x, pad)


def rows_view(x: jax.Array) -> jax.Array:
    """(rows, d) -> (rows, 1, dp): the layout the select pass copies rows
    from. A bitcast when ``d`` = 128; otherwise a copy of ``x``."""
    x = pad_lanes(x)
    return x.reshape(x.shape[0], 1, x.shape[1])


def block_rows(dp: int, itemsize: int) -> int:
    """Select-pass rows per grid step for rows of ``dp`` lanes: one
    ``(1, dp)`` row of the ``(R, 1, dp)`` VMEM block fills a whole
    8-sublane tile, so R rows take ``R * 8 * dp * itemsize`` bytes; R
    keeps that near ``BLOCK_VMEM_BYTES``, a multiple of 8 in
    ``[8, MAX_BLOCK_ROWS]`` (512 at 128 f32 lanes, 96 at 640)."""
    rows = BLOCK_VMEM_BYTES // (SUBLANES * dp * itemsize)
    return max(SUBLANES, min(MAX_BLOCK_ROWS, rows // SUBLANES * SUBLANES))


def _select_kernel(code, block_src, cache_ref, table_ref, pulled_ref,
                   o_ref, sem, *, unroll: int):
    """One block of R query rows: one DMA per row, from the source its
    code names into the row's slot of the output block, then one wait
    for the block's R copies (a DMA semaphore counts bytes, so a
    descriptor the size of the block waits for all of them).

    The per-row branch on the source costs more than the copy's issue
    (about 38 against 15 ns a row on v5e), so a block whose rows all
    share one source (``block_src``) issues its copies without it;
    ``SRC_MIXED`` blocks branch per row.
    The last block's slots past the query rows are filled from valid rows
    (the code is edge-padded) and not written back."""
    rows = o_ref.shape[0]
    b = pl.program_id(0)
    first = b * rows
    sources = ((SRC_LOCAL, table_ref), (SRC_CACHE, cache_ref),
               (SRC_PULLED, pulled_ref))

    def copy(ref, j, c):
        pltpu.make_async_copy(ref.at[pl.ds(c & ROW_MASK, 1)],
                              o_ref.at[pl.ds(j, 1)], sem).start()

    def issue(one_row):
        def group(g, carry):
            for u in range(unroll):
                j = g * unroll + u
                one_row(j, code[first + j])
            return carry
        jax.lax.fori_loop(0, rows // unroll, group, 0)

    for want, ref in sources:
        @pl.when(block_src[b] == want)
        def _(ref=ref):
            issue(partial(copy, ref))

    def mixed(j, c):
        for want, ref in sources:
            @pl.when(c >> SRC_SHIFT == want)
            def _(ref=ref):
                copy(ref, j, c)

    @pl.when(block_src[b] == SRC_MIXED)
    def _():
        issue(mixed)

    pltpu.make_async_copy(o_ref, o_ref, sem).wait()


def _select(code, cache3, table3, pulled3, interpret: bool) -> jax.Array:
    """Select pass over the rows ``code`` names -> (n, 1, dp); pulled rows
    are addressed by their position in ``pulled3``."""
    n = code.shape[0]
    dp = pulled3.shape[-1]
    rows = min(n, block_rows(dp, pulled3.dtype.itemsize))
    blocks = pl.cdiv(n, rows)
    code = jnp.pad(code, (0, blocks * rows - n), mode="edge")
    src = (code >> SRC_SHIFT).reshape(blocks, rows)
    block_src = jnp.where((src == src[:, :1]).all(axis=1), src[:, 0],
                          SRC_MIXED)
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(blocks,),
        in_specs=[hbm, hbm, hbm],
        out_specs=pl.BlockSpec((rows, 1, dp), lambda b, c, s: (b, 0, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        partial(_select_kernel, unroll=math.gcd(rows, UNROLL)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, 1, dp), pulled3.dtype),
        interpret=interpret,
        name="assemble_select",
    )(code, block_src, cache3, table3, pulled3)


def assemble(table: jax.Array, base, cache_ids: jax.Array,
             cache_feats: jax.Array, query: jax.Array, pulled: jax.Array,
             interpret: bool = False) -> jax.Array:
    """Fused assembly: table (n_per, d); base scalar; cache_ids (n_hot,)
    sorted int32; cache_feats (n_hot, d); query (m,) int32; pulled (m, d)
    -> (m, d). ``table`` and ``cache_feats`` may also be given as their
    ``rows_view``, and every width may be the lane-padded one: the
    output is as wide as ``pulled``."""
    n_per = table.shape[0]
    m, d = pulled.shape
    if m == 0:
        return pulled
    dt = pulled.dtype
    if cache_feats.shape[0] == 0:
        # sentinel row: the selector can never pick it (no hits), but
        # the copy descriptors need an addressable row 0
        cache_ids = jnp.full((1,), SENTINEL, jnp.int32)
        cache_feats = jnp.zeros((1, lane_width(d)), dt)
    code = classify(cache_ids, query, base, n_per, interpret=interpret)
    cache3, table3 = (x.astype(dt) if x.ndim == 3
                      else rows_view(x.astype(dt))
                      for x in (cache_feats, table))
    pulled3 = rows_view(pulled)
    parts = [_select(code[st:st + MAX_PREFETCH_ROWS], cache3, table3,
                     pulled3, interpret)
             for st in range(0, m, MAX_PREFETCH_ROWS)]
    out = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return out[:, 0, :d]
