"""SPMD cache-first feature exchange: the device realisation of the
paper's VectorPull / SyncPull over a flat ``("data",)`` or hierarchical
``("dcn", "data")`` mesh (DESIGN.md §6; topology layer §6.7).

Host-sim counterpart: ``repro.core.fetch.ShardedFeatureStore``. Here the
"distributed KV store" is a partition-sharded feature table resident in
device memory -- ``table[(P, n_per, d)]`` sharded on its leading dim over
``data`` -- and a remote fetch is one ``all_to_all`` round trip:

  1. every worker sends each owner the (deduped, offline-enumerated) slot
     requests it needs from that owner   -- ids up the wire,
  2. each owner gathers the rows from its local shard,
  3. a second ``all_to_all`` returns the rows, which the requester
     scatters into its padded (m_max, d) batch buffer by ``send_pos``.

The request matrix is the PULL-PLAN WIRE FORMAT (DESIGN.md §6.2), built
OFFLINE by ``build_pull_plan`` from the deterministic schedule -- this is
what makes the exchange a static-shape collective XLA can overlap with
compute, instead of a dynamic RPC storm.

On a hierarchical mesh (``repro.dist.topology.Topology``) the plan is
TWO-TIER: ``pack_pull_lanes_two_tier`` splits each worker's misses by
whether the owner shares its host -- same-host lanes ride a cheap
intra-host ``all_to_all`` over the ici ``data`` axis (owner addressed
by LOCAL device index), cross-host lanes a separate batched exchange
over the flattened ``("dcn", "data")`` axis pair. The union of the two
tiers is bit-equal to the flat plan (the parity property pins it), and
``pull_shard_two_tier`` scatter-adds both tiers' disjoint contributions
into one buffer, bit-equal to the flat pull.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.kernels.cache_lookup.ops import cache_lookup


@dataclasses.dataclass(frozen=True)
class PullPlan:
    """One worker's residual-miss requests for one batch.

    Wire format (DESIGN.md §6.2): row ``p`` of each array is this
    worker's request lane to owner ``p``; lanes are padded to the
    epoch-level ``k_max`` so every step reuses one compiled program.
    ``send_pos`` is the destination row in the requester's padded
    (m_max, d) feature buffer -- the owner never needs it, it rides
    along host-side only.
    """
    send_ids: np.ndarray    # (P, k_max) int32  requested ids (0 padded)
    send_pos: np.ndarray    # (P, k_max) int32  dst row in the batch buffer
    send_mask: np.ndarray   # (P, k_max) bool   lane validity
    counts: np.ndarray      # (P,) int32        true request count per owner

    @property
    def k_max(self) -> int:
        return int(self.send_ids.shape[1])

    def payload_bytes(self, row_bytes: int) -> int:
        """Feature bytes actually requested (un-padded)."""
        return int(self.counts.sum()) * row_bytes

    def wire_bytes(self, row_bytes: int) -> int:
        """Feature bytes moved by the padded all_to_all return leg."""
        return int(self.send_ids.size) * row_bytes

    def request_bytes(self) -> int:
        """Id bytes moved by the padded all_to_all REQUEST leg (the
        first collective in ``pull_shard`` ships the full (P, k_max)
        int32 id matrix) -- previously unaccounted, so the return leg's
        ``wire_bytes`` understated the true wire total by P*k_max*4."""
        return int(self.send_ids.size) * int(self.send_ids.itemsize)


def build_pull_plan(ids: np.ndarray, pos: np.ndarray, owner: np.ndarray,
                    num_parts: int, k_max: int) -> PullPlan:
    """Pack (id -> buffer position) requests into per-owner lanes.

    ids (m,) requested node ids (negative = padding, dropped); pos (m,)
    destination rows, same length; owner (N,) id -> owning worker. Exact
    duplicate (id, pos) pairs are deduped to one lane slot; the same id
    at *distinct* positions keeps one slot per position (each output row
    must receive its feature -- ids are already unique per batch in the
    GNN path, where the sampler dedupes ``input_nodes``).

    Raises ValueError when any owner's request count exceeds ``k_max``
    (silent truncation would drop features and corrupt training).
    """
    ids = np.asarray(ids)
    pos = np.asarray(pos)
    if ids.shape != pos.shape:
        raise ValueError(f"ids/pos length mismatch: {ids.shape} vs {pos.shape}")
    valid = ids >= 0
    ids, pos = ids[valid].astype(np.int64), pos[valid].astype(np.int64)
    if ids.size:
        pairs = np.unique(np.stack([ids, pos], axis=1), axis=0)
        ids, pos = pairs[:, 0], pairs[:, 1]
    dest = np.asarray(owner)[ids].astype(np.int64)
    # validate BEFORE bincount: a negative owner would crash it with an
    # opaque "negative values" error, and the historical post-hoc
    # ``counts.size > num_parts`` check only caught the too-HIGH side
    if ids.size and (int(dest.min()) < 0 or int(dest.max()) >= num_parts):
        raise ValueError(f"owner id out of range: [{dest.min()}, "
                         f"{dest.max()}] not in [0, {num_parts})")
    counts = np.bincount(dest, minlength=num_parts).astype(np.int32)
    if ids.size and int(counts.max()) > k_max:
        over = np.flatnonzero(counts > k_max)
        raise ValueError(
            f"pull plan overflow: owners {over.tolist()} requested "
            f"{counts[over].tolist()} rows > k_max={k_max}; raise k_max "
            f"(epoch_k_max gives the exact bound)")

    send_ids = np.zeros((num_parts, k_max), np.int32)
    send_pos = np.zeros((num_parts, k_max), np.int32)
    send_mask = np.zeros((num_parts, k_max), bool)
    order = np.argsort(dest, kind="stable")
    start = np.zeros(num_parts + 1, np.int64)
    np.cumsum(counts, out=start[1:])
    lane = np.arange(ids.size) - start[dest[order]]
    send_ids[dest[order], lane] = ids[order].astype(np.int32)
    send_pos[dest[order], lane] = pos[order].astype(np.int32)
    send_mask[dest[order], lane] = True
    return PullPlan(send_ids=send_ids, send_pos=send_pos,
                    send_mask=send_mask, counts=counts)


def _fast_key_fits(num_groups: int, num_parts: int, span_i: int,
                   span_p: int) -> bool:
    """True when the rebased composite (group, id, pos) key fits int64
    headroom (< 2**62), i.e. the single-sort fast path is safe. Spans
    are REBASED extents (``max - min + 1``), not absolute maxima --
    exposed for the boundary regression tests."""
    return num_groups * num_parts * span_i * span_p < 2 ** 62


def pack_pull_lanes(ids: np.ndarray, pos: np.ndarray, group: np.ndarray,
                    owner: np.ndarray, num_groups: int, num_parts: int,
                    k_max: int, assume_unique: bool = False):
    """Batched ``build_pull_plan``: pack MANY batches' requests into
    per-(group, owner) lanes in one vectorized pass (DESIGN.md §6.6).

    ids/pos/group/owner are aligned (n,) arrays -- one element per
    requested (id -> buffer position), ``group`` the flat batch ordinal
    (e.g. ``step * P + worker``) and ``owner`` the owning worker of each
    id. Negative ids (padding) are dropped; exact (group, id, pos)
    duplicates collapse to one lane slot; lanes within a (group, owner)
    pair are ordered by ascending (id, pos) -- all three semantics
    identical to calling ``build_pull_plan`` once per group, which the
    collation parity tests pin. ``assume_unique=True`` skips the dedupe
    pass -- valid when ids are unique within each group, the sampler's
    ``input_nodes`` invariant.

    -> (send_ids, send_pos, send_mask) of shape (num_groups, num_parts,
    k_max) plus counts (num_groups, num_parts). Raises on lane overflow
    (silent truncation would corrupt training) and out-of-range owners.
    """
    ids = np.asarray(ids, dtype=np.int64)       # no copy when already i64
    pos = np.asarray(pos, dtype=np.int64)
    group = np.asarray(group, dtype=np.int64)
    owner = np.asarray(owner, dtype=np.int64)
    valid = ids >= 0
    if not valid.all():
        ids, pos, group, owner = (a[valid] for a in (ids, pos, group,
                                                     owner))
    if ids.size and (owner.min() < 0 or owner.max() >= num_parts):
        raise ValueError(f"owner id out of range: [{owner.min()}, "
                         f"{owner.max()}] not in [0, {num_parts})")
    shape = (num_groups, num_parts, k_max)
    send_ids = np.zeros(shape, np.int32)
    send_pos = np.zeros(shape, np.int32)
    send_mask = np.zeros(shape, bool)
    counts = np.zeros((num_groups, num_parts), np.int32)
    if not ids.size:
        return send_ids, send_pos, send_mask, counts
    gidx = group * num_parts + owner
    # (group, id, pos) ordering via ONE composite int64 key when the
    # value ranges allow it -- a single introsort beats the 3-key
    # lexsort ~3x at epoch scale. Stability is irrelevant: the key is
    # unique per lane except for EXACT duplicates, which dedupe anyway.
    # Keys are REBASED to the observed min so only the id/pos SPANS
    # spend key bits: a large device-id base (big P*n_per meshes put
    # every id near P*n_per) must not push an epoch whose actual id
    # range is tiny onto the slow lexsort fallback.
    imin, pmin = int(ids.min()), int(pos.min())
    span_i = int(ids.max()) - imin + 1
    span_p = int(pos.max()) - pmin + 1
    if _fast_key_fits(num_groups, num_parts, span_i, span_p):
        key = (gidx * span_i + (ids - imin)) * span_p + (pos - pmin)
        order = np.argsort(key)
        if not assume_unique:
            k_s = key[order]
            keep = np.ones(k_s.size, bool)  # drop exact duplicate lanes
            keep[1:] = k_s[1:] != k_s[:-1]
            order = order[keep]
    else:                                   # huge spans: lexsort fallback
        order = np.lexsort((pos, ids, gidx))
        if not assume_unique:
            g0, i0, p0 = gidx[order], ids[order], pos[order]
            keep = np.ones(g0.size, bool)
            keep[1:] = ((g0[1:] != g0[:-1]) | (i0[1:] != i0[:-1])
                        | (p0[1:] != p0[:-1]))
            order = order[keep]
    g_s, i_s, p_s = gidx[order], ids[order], pos[order]
    cnt = np.bincount(g_s, minlength=num_groups * num_parts)
    if int(cnt.max()) > k_max:
        over = np.flatnonzero(cnt > k_max)
        raise ValueError(
            f"pull plan overflow: (group, owner) pairs "
            f"{[divmod(int(o), num_parts) for o in over[:8].tolist()]} "
            f"requested {cnt[over[:8]].tolist()} rows > k_max={k_max}; "
            f"raise k_max (epoch_k_max gives the exact bound)")
    start = np.zeros(cnt.size + 1, np.int64)
    np.cumsum(cnt, out=start[1:])
    lane = np.arange(g_s.size) - start[g_s]
    flat = g_s * k_max + lane
    send_ids.reshape(-1)[flat] = i_s.astype(np.int32)
    send_pos.reshape(-1)[flat] = p_s.astype(np.int32)
    send_mask.reshape(-1)[flat] = True
    counts[:] = cnt.reshape(num_groups, num_parts)
    return send_ids, send_pos, send_mask, counts


def pack_pull_lanes_two_tier(ids: np.ndarray, pos: np.ndarray,
                             group: np.ndarray, owner: np.ndarray,
                             requester: np.ndarray, num_groups: int,
                             topo, k_max_intra: int, k_max_inter: int,
                             assume_unique: bool = False):
    """Topology-aware ``pack_pull_lanes``: split each request by whether
    its owner shares the requester's host (DESIGN.md §6.7).

    ``requester`` is the flat worker ordinal issuing each request,
    aligned with ids/pos/group/owner; ``topo`` a
    ``repro.dist.topology.Topology``. Same-host requests pack into
    ``(num_groups, D, k_max_intra)`` lanes addressed by the owner's
    LOCAL device index (the intra-host ``all_to_all`` over the ici axis
    only spans D peers); cross-host requests pack into ``(num_groups,
    P, k_max_inter)`` lanes addressed by the owner's flat ordinal (the
    DCN-tier exchange over the flattened axis pair spans all P). Ids
    stay GLOBAL in both tiers -- the serving side's slot arithmetic is
    base-relative regardless of which wire the request rode.

    -> (intra, inter): two ``pack_pull_lanes``-shaped 4-tuples
    (send_ids, send_pos, send_mask, counts). Their union is bit-equal
    to the flat-mesh ``pack_pull_lanes`` output (each lane appears in
    exactly one tier, same per-(group, owner) ascending (id, pos)
    order), which the two-tier parity property pins.
    """
    ids = np.asarray(ids, dtype=np.int64)
    pos = np.asarray(pos, dtype=np.int64)
    group = np.asarray(group, dtype=np.int64)
    owner = np.asarray(owner, dtype=np.int64)
    requester = np.asarray(requester, dtype=np.int64)
    valid = ids >= 0
    if not valid.all():
        ids, pos, group, owner, requester = (
            a[valid] for a in (ids, pos, group, owner, requester))
    P_ = topo.num_workers
    if ids.size and (owner.min() < 0 or owner.max() >= P_):
        raise ValueError(f"owner id out of range: [{owner.min()}, "
                         f"{owner.max()}] not in [0, {P_})")
    same = topo.same_host(owner, requester)
    intra = pack_pull_lanes(
        ids[same], pos[same], group[same], topo.local_of(owner[same]),
        num_groups, topo.devices_per_host, k_max_intra,
        assume_unique=assume_unique)
    inter = pack_pull_lanes(
        ids[~same], pos[~same], group[~same], owner[~same],
        num_groups, P_, k_max_inter, assume_unique=assume_unique)
    return intra, inter


def pull_shard(table: jnp.ndarray, send_ids: jnp.ndarray,
               send_pos: jnp.ndarray, send_mask: jnp.ndarray,
               base, m_max: int, axis="data",
               width: Optional[int] = None) -> jnp.ndarray:
    """Per-device exchange body; call inside shard_map over ``axis``
    (the flat worker axis ``"data"``, or a mesh-axis tuple like
    ``("dcn", "data")`` whose row-major flattening is the worker order).

    table (n_per, d) this worker's shard; send_* (G, k) its request
    lanes, one row per member of the ``axis`` group; base this worker's
    first global slot. -> (m_max, d) buffer with requested rows
    scattered to ``send_pos`` (other rows zero). Padding lanes may
    request owner-slot 0; the requester's send_mask zeroes them at
    scatter, so the mask never has to cross the wire.

    ``width``: the buffer's lanes, ``d`` or more: rows land in its first
    ``d`` lanes and the rest stay zero (the fused assembly kernel reads
    rows padded to whole 128-lane groups, ``kernels/assemble``); only the
    ``d`` lanes cross the wire.
    """
    n_per, d = table.shape
    req = jax.lax.all_to_all(send_ids, axis, 0, 0)        # (G, k) asks TO me
    slot = jnp.clip(req - base, 0, n_per - 1)
    rows = table[slot]                                    # (G, k, d) serve
    got = jax.lax.all_to_all(rows, axis, 0, 0)            # (G, k, d) mine
    out = jnp.zeros((m_max, width or d), table.dtype)
    pos = jnp.where(send_mask, send_pos, 0).reshape(-1)
    contrib = jnp.where(send_mask.reshape(-1, 1), got.reshape(-1, d), 0)
    return out.at[pos, :d].add(contrib)


def pull_shard_two_tier(table: jnp.ndarray, send: dict, base, m_max: int,
                        ici_axis="data", world_axes=("dcn", "data"),
                        width: Optional[int] = None) -> jnp.ndarray:
    """Two-tier exchange body for a hierarchical mesh (DESIGN.md §6.7).

    ``send`` holds the two-tier lanes from ``pack_pull_lanes_two_tier``:
    ``intra_*`` (D, k_i) same-host requests exchanged over the cheap ici
    ``ici_axis`` (owner = LOCAL device index, ids remain global -- slot
    arithmetic on the serving side is base-relative either way), and
    ``inter_*`` (P, k_x) cross-host requests over the flattened
    ``world_axes`` pair. The two tiers' request sets are DISJOINT (a
    miss is same-host xor cross-host) and every real position receives
    exactly one nonzero contribution, so scatter-adding both tiers into
    one zero buffer is bit-equal to the flat single-tier pull.
    ``width`` as in ``pull_shard``.
    """
    n_per, d = table.shape
    out = jnp.zeros((m_max, width or d), table.dtype)
    for pre, axis in (("intra", ici_axis), ("inter", world_axes)):
        sid, spo, sma = (send[f"{pre}_ids"], send[f"{pre}_pos"],
                         send[f"{pre}_mask"])
        req = jax.lax.all_to_all(sid, axis, 0, 0)
        rows = table[jnp.clip(req - base, 0, n_per - 1)]
        got = jax.lax.all_to_all(rows, axis, 0, 0)
        pos = jnp.where(sma, spo, 0).reshape(-1)
        contrib = jnp.where(sma.reshape(-1, 1), got.reshape(-1, d), 0)
        out = out.at[pos, :d].add(contrib)
    return out


def pull_features(mesh, table: jnp.ndarray, send_ids: jnp.ndarray,
                  send_pos: jnp.ndarray, send_mask: jnp.ndarray,
                  offsets: jnp.ndarray, m_max: int) -> jnp.ndarray:
    """All-worker a2a feature pull against the partition-sharded table.

    table (P, n_per, d) sharded over ``data``; send_* (P, P, k_max) --
    dim 0 the requesting worker (sharded), dim 1 the owner lane;
    offsets (P,) int32 first global slot of each partition.
    -> (P, m_max, d) per-worker scattered feature buffers.
    """
    def body(tbl, sid, spo, sma, off):
        return pull_shard(tbl[0], sid[0], spo[0], sma[0],
                          off.reshape(-1)[0], m_max)[None]

    return shard_map(
        body, mesh=mesh,
        in_specs=(P("data"), P("data"), P("data"), P("data"), P("data")),
        out_specs=P("data"), check_vma=False,
    )(table, send_ids, send_pos, send_mask, offsets)


def cache_gather(cache_ids: jnp.ndarray, cache_feats: jnp.ndarray,
                 query: jnp.ndarray, base: jnp.ndarray):
    """Hot-set C_s merge: overlay cache hits onto a pre-filled buffer.

    cache_ids (n_hot,) SORTED int32 (INT32_MAX padded); cache_feats
    (n_hot, d); query (m,) ids (-1 = padding, never hits); base (m, d)
    buffer already holding pulled/local rows. -> (merged, hit_mask).
    On TPU this is the fused Pallas ``cache_lookup`` kernel; the jnp
    oracle runs everywhere else.
    """
    return cache_lookup(cache_ids, cache_feats, query, base)
