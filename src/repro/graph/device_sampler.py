"""Device-resident whole-epoch schedule compiler (DESIGN.md §2.2).

Ports the sort-bound middle of ``KHopSampler.sample_epoch_batched`` --
the composite-key segment-unique, frontier membership, new-source
extraction and local-index resolution -- onto the accelerator as JAX
ops (a stable ``jax.lax.sort`` for the key sort, scatter/gather tables
for the unique-inverse), plus device remote-frequency counting and
hot-set ordering. The result is BIT-IDENTICAL to the numpy compiler:
every derived quantity is a deterministic function of the sorted unique
key set (frontier keys are globally distinct and ``np.unique`` outputs
are sets), so no sort-stability caveat survives into the payload.

RNG contract (the part that does NOT move): numpy's
``Generator.integers`` with broadcast (per-row) bounds consumes its
Philox stream data-dependently (masked rejection sampling), which no
fixed-shape device program can replay. The per-batch offset draws
therefore stay on the host -- the EXACT ``rngs[i].integers`` calls
``sample_batch`` makes, one independent stream per ``H(s0, w, e, i)``
(Prop 3.1) -- and the device consumes their output. Determinism is
preserved blockwise by construction, not re-derived.

Fallbacks (all bit-equal by definition -- they ARE the numpy path):
  * composite key spaces past ``KEY_INT32_MAX_SLOTS`` (device sorts are
    int32-only: jax canonicalizes int64 away without x64 mode),
  * empty epochs (``nb == 0``).

Static shapes: per-layer streams pad to power-of-two buckets with the
INT32_MAX sentinel, so XLA traces once per (bucket, nb, span) tuple and
epochs re-use each other's compiled steps.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.graph.sampler import (FlatEpoch, KEY_INT32_MAX_SLOTS,
                                 KHopSampler, _starts, rng_from)

#: int32 padding sentinel: sorts after every real composite key (key
#: spaces are gated below 2^31, so max real key <= 2^31 - 2).
SENT = 2 ** 31 - 1

#: dense scatter-table bound for the unique-inverse / frontier-membership
#: lookups (int32 slots; same budget class as gnn_step's stamp table).
#: Wider key spaces use searchsorted instead -- still device ops, just
#: O(n log n) lanes instead of O(n) table probes.
DEVICE_TABLE_MAX_SLOTS = 1 << 26


def _bucket(n: int) -> int:
    """Power-of-two pad bucket (>= 128): bounds distinct XLA traces at
    log2(stream) per layer instead of one per exact shape."""
    return 128 if n <= 128 else 1 << (n - 1).bit_length()


def _pad_i32(x: np.ndarray, n_pad: int, fill: int = SENT) -> jnp.ndarray:
    out = np.full(n_pad, fill, np.int32)
    out[:x.shape[0]] = x
    return jnp.asarray(out)


def stable_sort(keys: jax.Array, payload: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Sort int32 composite ``(batch, id)`` keys ascending, permuting
    ``payload`` along (stable). One global sort acts per batch, since
    keys never cross segment boundaries; SENT-padded tails sort last.
    Returns ``(sorted_keys, sorted_payload_or_None)``."""
    if payload is None:
        return jax.lax.sort(keys, is_stable=True), None
    ks, ps = jax.lax.sort((keys, payload), num_keys=1, is_stable=True)
    return ks, ps


# ---------------------------------------------------------------------------
# the per-layer device step
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("nb", "span", "use_table"))
def _frontier_step(cand_key: jax.Array, cur_key: jax.Array,
                   cur_within: jax.Array, counts: jax.Array, *,
                   nb: int, span: int, use_table: bool
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One sampler layer's segment-unique on device.

    cand_key (n_pad,) int32 composite ``batch * span + src`` edge keys,
    SENT-padded; cur_key (c_pad,) the current frontier's composite keys
    (globally unique), SENT-padded; cur_within (c_pad,) each frontier
    node's within-batch position; counts (nb,) per-batch frontier sizes.

    Returns (src_idx, ext_key, ext_counts): per-edge local source index
    into the NEXT frontier (pad slots garbage, host slices), the compact
    ascending stream of new composite keys (SENT-padded), and per-batch
    new-source counts -- exactly ``np.unique`` + setdiff semantics.
    """
    n_pad = cand_key.shape[0]
    ks = nb * span

    # segment-unique: ONE global sort acts per batch (composite keys
    # never cross segment boundaries), then head flags + compaction
    sk, _ = stable_sort(cand_key)
    valid = sk != SENT
    head = valid & jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    rank = jnp.cumsum(head.astype(jnp.int32)) - 1
    uk = jnp.full(n_pad, SENT, jnp.int32).at[
        jnp.where(head, rank, n_pad)].set(sk, mode="drop")
    valid_u = uk != SENT

    # frontier membership + old-slot resolution
    if use_table:
        # dense probes over the key space: frontier table answers both
        # "is this unique key old" and "at which within-batch position"
        cur_tbl = jnp.full(ks, -1, jnp.int32).at[cur_key].set(
            cur_within, mode="drop")          # SENT pads drop (>= ks)
        old_within = cur_tbl[jnp.minimum(uk, ks - 1)]
    else:
        cks, cw = stable_sort(cur_key, cur_within)
        pos = jnp.minimum(jnp.searchsorted(cks, uk),
                          cks.shape[0] - 1).astype(jnp.int32)
        old_within = jnp.where(cks[pos] == uk, cw[pos], -1)
    is_new = valid_u & (old_within < 0)

    # compact new sources (ascending per batch == setdiff1d contract)
    ext_rank = jnp.cumsum(is_new.astype(jnp.int32)) - 1
    n_ext = ext_rank[-1] + 1
    ext_key = jnp.full(n_pad, SENT, jnp.int32).at[
        jnp.where(is_new, ext_rank, n_pad)].set(uk, mode="drop")
    bounds = jnp.arange(nb, dtype=jnp.int32) * jnp.int32(span)
    ext_starts = jnp.concatenate(
        [jnp.searchsorted(ext_key, bounds).astype(jnp.int32),
         n_ext[None]])
    ext_counts = jnp.diff(ext_starts)

    # resolve each UNIQUE key once: old keys sit at their frontier
    # position, new keys at prefix + extra rank; then fan out to edges
    ub = jnp.clip(jnp.where(valid_u, uk, 0) // jnp.int32(span), 0, nb - 1)
    uk_local = jnp.where(is_new,
                         counts[ub] + ext_rank - ext_starts[ub],
                         old_within)
    if use_table:
        val_tbl = jnp.full(ks, 0, jnp.int32).at[uk].set(
            uk_local, mode="drop")
        src_idx = val_tbl[jnp.minimum(cand_key, ks - 1)]
    else:
        inv = jnp.searchsorted(
            uk, jnp.minimum(cand_key, ks - 1)).astype(jnp.int32)
        src_idx = uk_local[jnp.minimum(inv, n_pad - 1)]
    return src_idx, ext_key, ext_counts


# ---------------------------------------------------------------------------
# the epoch driver (host orchestration + draws, device segment-unique)
# ---------------------------------------------------------------------------

def sample_epoch_batched_device(sampler: KHopSampler, s0: int, worker: int,
                                epoch: int, train_nodes: np.ndarray
                                ) -> FlatEpoch:
    """Whole-epoch compile with the per-layer segment-unique on device;
    bit-identical to ``sample_epoch_batched`` (the differential suite
    pins it array-for-array). Falls back to the numpy compiler for
    int64 key spaces and empty epochs."""
    g = sampler.graph
    L = len(sampler.fanouts)
    span = int(g.num_nodes)
    seed_batches = sampler.epoch_seed_batches(s0, worker, epoch,
                                              train_nodes)
    nb = len(seed_batches)
    if nb == 0 or nb * span >= KEY_INT32_MAX_SLOTS:
        return sampler.sample_epoch_batched(s0, worker, epoch, train_nodes)

    seeds_flat = np.concatenate(seed_batches).astype(np.int64)
    seed_counts = np.fromiter((b.shape[0] for b in seed_batches),
                              np.int64, nb)
    seed_starts = _starts(seed_counts)
    rngs = [rng_from(s0, worker, epoch, i) for i in range(nb)]
    use_table = nb * span <= DEVICE_TABLE_MAX_SLOTS
    bids = np.arange(nb, dtype=np.int32)

    cur = seeds_flat                 # flat frontier, batch-segmented
    counts, starts = seed_counts, seed_starts
    num_dst = np.zeros((L, nb), np.int64)
    rev_src: List[np.ndarray] = []
    rev_dst: List[np.ndarray] = []
    rev_mask: List[np.ndarray] = []
    rev_starts: List[np.ndarray] = []

    for j, fanout in enumerate(reversed(sampler.fanouts)):
        num_dst[L - 1 - j] = counts
        batch_of = np.repeat(bids, counts)
        within = np.arange(cur.shape[0], dtype=np.int64) \
            - starts[batch_of]
        deg = (g.indptr[cur + 1] - g.indptr[cur]).astype(np.int64)
        hi = np.maximum(deg, 1)
        offs = np.empty((cur.shape[0], fanout), np.int64)
        for i in range(nb):     # host Philox: the RNG contract (§2.2)
            sl = slice(starts[i], starts[i + 1])
            offs[sl] = rngs[i].integers(
                0, hi[sl][:, None], size=(int(counts[i]), fanout))
        src_pos = g.indptr[cur][:, None] + offs
        zero = np.flatnonzero(deg == 0)
        if zero.size:
            src_pos[zero] = 0
        src_flat = g.indices[src_pos].reshape(-1).astype(np.int32,
                                                         copy=False)
        mask = np.repeat(deg > 0, fanout)
        if zero.size:
            bad = np.flatnonzero(~mask)
            src_flat[bad] = cur[bad // fanout]

        dst_idx = np.repeat(within, fanout).astype(np.int32)
        ecount = counts * fanout
        n_edges = int(ecount.sum())
        cand_key = (np.repeat(bids, ecount).astype(np.int32)
                    * np.int32(span) + src_flat)
        cur_key = (batch_of.astype(np.int32) * np.int32(span)
                   + cur.astype(np.int32, copy=False))

        n_pad, c_pad = _bucket(n_edges), _bucket(cur.shape[0])
        d_src, d_ext, d_cnt = _frontier_step(
            _pad_i32(cand_key, n_pad),
            _pad_i32(cur_key, c_pad),
            _pad_i32(within.astype(np.int32), c_pad, fill=0),
            jnp.asarray(counts.astype(np.int32)),
            nb=nb, span=span, use_table=use_table)

        src_idx = np.asarray(d_src)[:n_edges].astype(np.int32,
                                                     copy=False)
        ext_counts = np.asarray(d_cnt).astype(np.int64)
        n_ext = int(ext_counts.sum())
        ext_key = np.asarray(d_ext)[:n_ext].astype(np.int64)
        ext_batch = ext_key // span
        ext_id = ext_key - ext_batch * span
        ext_starts = _starts(ext_counts)
        ewithin = np.arange(n_ext, dtype=np.int64) \
            - ext_starts[ext_batch]

        # next frontier: dst prefix then the new unique sources
        new_counts = counts + ext_counts
        new_starts = _starts(new_counts)
        new_cur = np.empty(int(new_starts[-1]), np.int64)
        new_cur[new_starts[batch_of] + within] = cur
        new_cur[new_starts[ext_batch] + counts[ext_batch]
                + ewithin] = ext_id

        rev_src.append(src_idx)
        rev_dst.append(dst_idx)
        rev_mask.append(mask)
        rev_starts.append(_starts(ecount))
        cur, counts, starts = new_cur, new_counts, new_starts

    return FlatEpoch(
        epoch=epoch, worker=worker, seeds=seeds_flat,
        seed_starts=seed_starts, input_nodes=cur, input_starts=starts,
        num_dst=num_dst,
        edge_src=list(reversed(rev_src)),
        edge_dst=list(reversed(rev_dst)),
        edge_mask=list(reversed(rev_mask)),
        edge_starts=list(reversed(rev_starts)))


# ---------------------------------------------------------------------------
# device remote-frequency counting + hot-set ordering
# ---------------------------------------------------------------------------

@jax.jit
def _freq_step(r: jax.Array):
    m_pad = r.shape[0]
    sk, _ = stable_sort(r)
    valid = sk != SENT
    head = valid & jnp.concatenate(
        [jnp.ones((1,), bool), sk[1:] != sk[:-1]])
    rank = jnp.cumsum(head.astype(jnp.int32)) - 1
    nu = rank[-1] + 1
    uk = jnp.full(m_pad, SENT, jnp.int32).at[
        jnp.where(head, rank, m_pad)].set(sk, mode="drop")
    # run lengths: start index of each unique value, then boundary diff
    iota = jnp.arange(m_pad, dtype=jnp.int32)
    st = jnp.zeros(m_pad + 1, jnp.int32).at[
        jnp.where(head, rank, m_pad + 1)].set(iota, mode="drop")
    st = st.at[jnp.minimum(nu, m_pad)].set(
        jnp.sum(valid.astype(jnp.int32)))
    freq = jnp.diff(st)
    return uk, freq, nu


@jax.jit
def _hot_order(ids: jax.Array, freq: jax.Array) -> jax.Array:
    """ids by (freq desc, id asc): SENT-padded slots sort last (their
    sort key +1 exceeds every real ``-freq <= -1``)."""
    negf = jnp.where(ids != SENT, -freq, 1)
    _, sid = jax.lax.sort((negf, ids), num_keys=2)
    return sid


def device_remote_freq(remote: np.ndarray, span: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """``np.unique(remote, return_counts=True)`` as device ops (sort +
    run-length compaction). ``remote`` is the flat stream of remote
    input-node ids; ids are unique per batch, so run lengths ARE the
    per-batch indicator sums the paper's freq(.) wants."""
    if remote.size == 0 or span >= KEY_INT32_MAX_SLOTS:
        ids, freq = (np.unique(remote, return_counts=True)
                     if remote.size else (np.zeros(0, np.int64),) * 2)
        return ids.astype(np.int64), np.asarray(freq, np.int64)
    m_pad = _bucket(remote.size)
    uk, freq, nu = _freq_step(_pad_i32(remote.astype(np.int64), m_pad))
    k = int(nu)
    return (np.asarray(uk)[:k].astype(np.int64),
            np.asarray(freq)[:k].astype(np.int64))


def device_select_hot_set(remote_ids: np.ndarray, remote_freq: np.ndarray,
                          n_hot: int) -> np.ndarray:
    """``core.schedule.select_hot_set`` with the (freq desc, id asc)
    ordering done by a device lexicographic sort; the top-k slice and
    final ascending sort stay host-side (k <= n_hot rows)."""
    k = min(n_hot, remote_ids.shape[0])
    if k <= 0:
        return np.zeros(0, np.int64)
    if remote_ids.size and int(remote_ids.max()) >= SENT:
        from repro.core.schedule import select_hot_set
        return select_hot_set(remote_ids, remote_freq, n_hot)
    m_pad = _bucket(remote_ids.shape[0])
    sid = _hot_order(_pad_i32(remote_ids, m_pad),
                     _pad_i32(remote_freq.astype(np.int32), m_pad,
                              fill=0))
    return np.sort(np.asarray(sid)[:k].astype(np.int64))
