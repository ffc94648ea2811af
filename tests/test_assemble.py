"""Parity suite for the fused single-pass feature assembly
(kernels/assemble) and the vectorized epoch collation (DESIGN.md §3,
§6.6).

The fused kernel (interpret mode), the pure-jnp fused oracle and the
legacy three-stage staged chain must be EXACTLY equal (every output row
is a copy of exactly one source row, so no tolerance); the vectorized
``collate_device_epoch`` must be batch-for-batch identical to the
per-(step, worker) loop reference on a real randomized schedule.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _hyp import ALL_HEALTH_CHECKS, given, settings
from _uneven import build_uneven_case
from strategies import (ASSEMBLE_KINDS, assemble_cases,
                        build_assemble_case, pull_request_sets,
                        uneven_worker_cases)
from repro.core import merge_pad_bounds
from repro.dist import (empty_caches, epoch_k_max, collate_device_epoch,
                        collate_device_epoch_loop, pack_pull_lanes,
                        build_pull_plan, prefetch_stream)
from repro.kernels.assemble.ops import assemble_features, resolve_backend
from repro.models.gnn import GNNConfig, init_params, loss_fn

CACHE_PAD32 = np.int32(2 ** 31 - 1)

_case = build_assemble_case         # shared builder (tests/strategies.py)


# ---------------------------------------------------------------------------
# fused assemble: three backends, bit-exact
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", list(ASSEMBLE_KINDS))
def test_assemble_backends_exact_equal(kind):
    rng = np.random.default_rng(hash(kind) % 2 ** 31)
    args = _case(kind, rng)
    staged = np.asarray(assemble_features(*args, backend="staged",
                                          interpret=True))
    ref = np.asarray(assemble_features(*args, backend="ref"))
    fused = np.asarray(assemble_features(*args, backend="fused",
                                         interpret=True))
    np.testing.assert_array_equal(ref, staged)
    np.testing.assert_array_equal(fused, staged)


@settings(max_examples=8, deadline=None,
          suppress_health_check=ALL_HEALTH_CHECKS)
@given(assemble_cases())
def test_assemble_backends_property(args):
    """Backend parity over DRAWN query mixes and shapes (m/n_hot/d with
    no relation to the kernel tile sizes): the single-pass jnp oracle
    and the fused kernel must reproduce the staged chain bit-exactly on
    every drawn case."""
    staged = np.asarray(assemble_features(*args, backend="staged",
                                          interpret=True))
    ref = np.asarray(assemble_features(*args, backend="ref"))
    fused = np.asarray(assemble_features(*args, backend="fused",
                                         interpret=True))
    np.testing.assert_array_equal(ref, staged)
    np.testing.assert_array_equal(fused, staged)


def test_assemble_empty_cache_and_cacheless():
    """n_hot=0 stacked cache and the cache-less (None) on-demand call
    must agree with the staged chain: local rows win, everything else
    keeps its pulled value."""
    rng = np.random.default_rng(7)
    table, base, _, _, q, pulled = _case("mixed", rng)
    d = pulled.shape[1]
    empty_ids = jnp.zeros((0,), jnp.int32)
    empty_feats = jnp.zeros((0, d), jnp.float32)
    want = np.asarray(assemble_features(table, base, empty_ids,
                                        empty_feats, q, pulled,
                                        backend="staged"))
    for backend in ("ref", "fused"):
        got = np.asarray(assemble_features(table, base, empty_ids,
                                           empty_feats, q, pulled,
                                           backend=backend,
                                           interpret=True))
        np.testing.assert_array_equal(got, want, err_msg=backend)
        got_none = np.asarray(assemble_features(table, base, None, None,
                                                q, pulled,
                                                backend=backend,
                                                interpret=True))
        np.testing.assert_array_equal(got_none, want, err_msg=backend)


def test_assemble_awkward_shapes():
    """Internal padding: m/n_hot/d with no relation to the tile sizes
    (the pre-padding kernels asserted divisibility and crashed)."""
    rng = np.random.default_rng(11)
    args = _case("mixed", rng, n_per=19, d=129, n_hot=13, m=41)
    staged = np.asarray(assemble_features(*args, backend="staged",
                                          interpret=True))
    fused = np.asarray(assemble_features(*args, backend="fused",
                                         interpret=True))
    np.testing.assert_array_equal(fused, staged)


@pytest.mark.parametrize("rows", [7, 16, 20])
def test_assemble_select_pass_in_chunks(monkeypatch, rows):
    """Past ``MAX_PREFETCH_ROWS`` query rows the select pass runs in
    several calls (its code vector lives in SMEM); the joined output is
    bit-equal to the reference. Blocks of 8 rows: a chunk of 7 is one
    short block, 16 two whole ones, 20 two and a partial one, and the
    last chunk of the 41 rows a single row."""
    from repro.kernels.assemble import assemble as kernel

    monkeypatch.setattr(kernel, "MAX_PREFETCH_ROWS", rows)
    monkeypatch.setattr(kernel, "MAX_BLOCK_ROWS", 8)
    args = _case("mixed", np.random.default_rng(5), m=41)
    ref = np.asarray(assemble_features(*args, backend="ref"))
    fused = np.asarray(kernel.assemble(*args, interpret=True))
    np.testing.assert_array_equal(fused, ref)


def _block_case(name, rng):
    """Query mixes placed against the select pass's blocks of R rows
    (``block_rows``: 512 at d=100, 96 at d=602)."""
    from repro.kernels.assemble import assemble as kernel

    kind, d, blocks = SELECT_BLOCK_CASES[name]
    R = kernel.block_rows(kernel.lane_width(d), 4)
    m = max(1, int(blocks * R))
    if kind != "uniform":
        return _case(kind, rng, n_per=4 * R, d=d, m=m)
    # the cells' traffic: whole blocks of local rows, then a block mixing
    # local rows with the step's padding, then a block of padding only
    table, base, cids, cfeats, q, pulled = _case("all_local", rng,
                                                 n_per=4 * R, d=d, m=m)
    tail = np.arange(m) >= 2 * R + 5
    q = jnp.where(tail, -1, q)
    pulled = jnp.where(tail[:, None], 0.0, pulled)
    return table, base, cids, cfeats, q, pulled


#: name -> (query mix, d, length in blocks)
SELECT_BLOCK_CASES = {
    "below_one_block": ("mixed", 100, 0.2),
    "one_row_past_a_block_d100": ("mixed", 100, 1 + 1 / 512),
    "one_row_past_a_block_d602": ("mixed", 602, 1 + 1 / 96),
    "mixed_blocks_d602": ("mixed", 602, 2.5),
    "uniform_then_padded_blocks_d100": ("uniform", 100, 4),
    "uniform_then_padded_blocks_d602": ("uniform", 602, 4),
}


@pytest.mark.parametrize("name", list(SELECT_BLOCK_CASES))
@pytest.mark.parametrize("views", [False, True])
def test_assemble_select_blocks_exact(name, views):
    """The blocked select pass against the reference, bit for bit, at
    widths that are not multiples of 128: fewer rows than one block, one
    row past a block boundary, blocks mixing all three sources with
    padding ids (per-row branch), and whole blocks of one source
    (branch-free) followed by a padded tail. ``views``: the epoch
    programs' path -- lane-padded features, the table and hot set passed
    as their row views -- whose extra lanes come out zero."""
    from repro.kernels.assemble import assemble as kernel
    from repro.kernels.assemble.ref import assemble_ref

    args = _block_case(name, np.random.default_rng(len(name)))
    table, base, cids, cfeats, q, pulled = args
    d = pulled.shape[1]
    want = np.asarray(assemble_ref(*args))
    if views:
        got = np.asarray(kernel.assemble(
            kernel.rows_view(table), base, cids, kernel.rows_view(cfeats),
            q, kernel.pad_lanes(pulled), interpret=True))
        assert got.shape == (q.shape[0], kernel.lane_width(d))
        assert not got[:, d:].any()
        got = got[:, :d]
    else:
        got = np.asarray(kernel.assemble(*args, interpret=True))
    np.testing.assert_array_equal(got.view(np.uint32),
                                  want.view(np.uint32))
    if SELECT_BLOCK_CASES[name][0] == "mixed":
        src = np.asarray(kernel.classify(cids, q, base, table.shape[0],
                                         interpret=True)) >> kernel.SRC_SHIFT
        R = kernel.block_rows(kernel.lane_width(d), 4)
        first = src[:R]
        assert set(first) == {kernel.SRC_LOCAL, kernel.SRC_CACHE,
                              kernel.SRC_PULLED}
        assert (np.asarray(q)[:R] < 0).any()


def test_assemble_priority_local_over_cache():
    """A locally owned id that ALSO appears in the cache serves the
    shard row (priority local > C_s > pulled), matching the staged
    chain's overlay order."""
    d, n_per = 8, 4
    table = jnp.asarray(np.arange(n_per * d, dtype=np.float32
                                  ).reshape(n_per, d))
    base = jnp.int32(0)
    cids = jnp.asarray(np.array([1, 2], np.int32))
    cfeats = jnp.asarray(-np.ones((2, d), np.float32))
    q = jnp.asarray(np.array([1, 2, 9], np.int32))   # 9: out of shard
    pulled = jnp.asarray(np.full((3, d), 7.0, np.float32))
    for backend in ("staged", "ref", "fused"):
        out = np.asarray(assemble_features(table, base, cids, cfeats, q,
                                           pulled, backend=backend,
                                           interpret=True))
        np.testing.assert_array_equal(out[0], np.asarray(table)[1])
        np.testing.assert_array_equal(out[1], np.asarray(table)[2])
        np.testing.assert_array_equal(out[2], 7.0 * np.ones(d))


def test_resolve_backend():
    assert resolve_backend("auto") in ("fused", "ref")
    assert resolve_backend("staged") == "staged"
    with pytest.raises(ValueError, match="backend"):
        resolve_backend("nope")


# ---------------------------------------------------------------------------
# gather_agg inside a full loss_fn grad
# ---------------------------------------------------------------------------

def test_gather_agg_backend_inside_loss_fn_grad():
    """The fused aggregation backend must reproduce the segment_sum
    oracle's loss AND parameter gradients through ``loss_fn`` (custom
    VJP correctness), on the collated fan-out-regular edge layout."""
    rng = np.random.default_rng(3)
    B, m, d, fo, L = 8, 40, 24, 5, 2
    kw = dict(kind="sage", in_dim=d, hidden_dim=16, num_classes=7,
              num_layers=L)
    cfg_ref = GNNConfig(**kw)
    cfg_ker = GNNConfig(**kw, fanouts=(fo, fo),
                        agg_backend="pallas_interpret")
    params = init_params(cfg_ref, jax.random.key(0))
    feats = jnp.asarray(rng.normal(size=(m, d)).astype(np.float32))
    es, ed, em = [], [], []
    for nd in (30, 12):            # padded tail rows fully masked
        E = nd * fo
        src = rng.integers(0, m, size=E).astype(np.int32)
        msk = rng.random(E) > 0.2
        msk[:fo] = False           # one zero-degree dst row
        es.append(jnp.asarray(src))
        ed.append(jnp.asarray(np.repeat(np.arange(nd, dtype=np.int32),
                                        fo)))
        em.append(jnp.asarray(msk))
    labels = jnp.asarray(rng.integers(0, 7, size=B).astype(np.int32))
    smask = jnp.asarray(np.ones(B, bool))

    def run(cfg):
        def lf(p):
            return loss_fn(cfg, p, feats, es, ed, em, labels, smask)
        return jax.value_and_grad(lf, has_aux=True)(params)

    (l_ref, _), g_ref = run(cfg_ref)
    (l_ker, _), g_ker = run(cfg_ker)
    np.testing.assert_allclose(np.asarray(l_ker), np.asarray(l_ref),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_ker)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=2e-6)


def test_gnn_config_guards():
    with pytest.raises(ValueError, match="fanouts"):
        GNNConfig(kind="sage", in_dim=4, hidden_dim=4, num_classes=2,
                  num_layers=1, agg_backend="pallas")
    with pytest.raises(ValueError, match="agg_backend"):
        GNNConfig(kind="sage", in_dim=4, hidden_dim=4, num_classes=2,
                  num_layers=1, agg_backend="warp")
    with pytest.raises(ValueError, match="entries"):
        GNNConfig(kind="sage", in_dim=4, hidden_dim=4, num_classes=2,
                  num_layers=2, fanouts=(5,), agg_backend="pallas")


def test_assemble_sentinel_query_with_padded_cache():
    """Regression: a CACHE_PAD query against a cache whose size forces
    internal sentinel padding must stay bit-exact across backends (the
    padded tail used to register as a hit in the kernel search)."""
    rng = np.random.default_rng(13)
    args = _case("padded", rng, n_per=32, d=64, n_hot=1500, m=40,
                 P_=64)
    staged = np.asarray(assemble_features(*args, backend="staged",
                                          interpret=True))
    ref = np.asarray(assemble_features(*args, backend="ref"))
    fused = np.asarray(assemble_features(*args, backend="fused",
                                         interpret=True))
    np.testing.assert_array_equal(ref, staged)
    np.testing.assert_array_equal(fused, staged)


# ---------------------------------------------------------------------------
# vectorized collation vs the loop reference
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sched_case():
    """Randomized real schedule incl. uneven workers (worker 2 empty,
    worker 3 half a batch)."""
    return build_uneven_case(P_=4, B=16, epochs=2, n_hot=64)


def _assert_epochs_equal(a, b, edge_layers):
    for k in ("input_nodes", "labels", "seed_mask", "send_ids",
              "send_pos", "send_mask"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    for l in range(edge_layers):
        for k in ("edge_src", "edge_dst", "edge_mask"):
            np.testing.assert_array_equal(a[k][l], b[k][l],
                                          err_msg=f"{k}[{l}]")


@pytest.mark.parametrize("epoch", [0, 1])
@pytest.mark.parametrize("cache_kind", ["hot", "empty"])
def test_vectorized_collation_identical_to_loop(sched_case, epoch,
                                                cache_kind):
    g, pg, schedules, dv = sched_case
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(epoch) for ws in schedules]
    caches = (empty_caches(4, g.feat_dim) if cache_kind == "empty"
              else [dv.remap_cache(es.cache_ids) for es in es_list])
    k_max = epoch_k_max(es_list, caches, dv)
    S = max(es.num_batches for es in es_list)
    vec = collate_device_epoch(es_list, caches, dv, g.labels, 16, m_max,
                               edge_max, k_max, S)
    loop = collate_device_epoch_loop(es_list, caches, dv, g.labels, 16,
                                     m_max, edge_max, k_max, S)
    _assert_epochs_equal(vec, loop, len(edge_max))


def test_vectorized_collation_padded_steps(sched_case):
    """Global num_steps > every worker's batch count: tail steps fully
    masked on both paths."""
    g, pg, schedules, dv = sched_case
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    k_max = epoch_k_max(es_list, caches, dv)
    S = max(es.num_batches for es in es_list) + 3
    vec = collate_device_epoch(es_list, caches, dv, g.labels, 16, m_max,
                               edge_max, k_max, S)
    loop = collate_device_epoch_loop(es_list, caches, dv, g.labels, 16,
                                     m_max, edge_max, k_max, S)
    _assert_epochs_equal(vec, loop, len(edge_max))
    assert not vec["send_mask"][-3:].any()
    assert (vec["input_nodes"][-3:] == -1).all()


@pytest.mark.parametrize("cache_kind", ["hot", "empty"])
def test_collation_counts_rows_and_leaves_edge_dst_out(sched_case,
                                                       cache_kind):
    """``stats`` counts the rows with a node, and those their own
    worker's shard holds, as the padded ``input_nodes`` shows them;
    ``with_edge_dst=False`` leaves ``edge_dst`` out and every other
    array as it was."""
    g, pg, schedules, dv = sched_case
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    caches = (empty_caches(4, g.feat_dim) if cache_kind == "empty"
              else [dv.remap_cache(es.cache_ids) for es in es_list])
    k_max = epoch_k_max(es_list, caches, dv)
    S = max(es.num_batches for es in es_list) + 1
    args = (es_list, caches, dv, g.labels, 16, m_max, edge_max, k_max, S)
    stats = {}
    full = collate_device_epoch(*args, stats=stats)
    rows = full["input_nodes"]
    base = dv.offsets.reshape(1, -1, 1)
    local = (rows >= base) & (rows < base + dv.n_per)
    assert stats == {"valid_rows": int((rows >= 0).sum()),
                     "local_rows": int(local.sum())}
    assert 0 < stats["local_rows"] < stats["valid_rows"]
    lean = collate_device_epoch(*args, with_edge_dst=False)
    assert lean["edge_dst"] == [None] * len(edge_max)
    for k in ("input_nodes", "labels", "seed_mask", "send_ids",
              "send_pos", "send_mask"):
        np.testing.assert_array_equal(lean[k], full[k], err_msg=k)
    for l in range(len(edge_max)):
        for k in ("edge_src", "edge_mask"):
            np.testing.assert_array_equal(lean[k][l], full[k][l])


def test_classify_fallback_matches_stamp_table(sched_case, monkeypatch):
    """Id spaces past STAMP_TABLE_MAX_SLOTS take the per-worker binary
    search branch; it must classify identically."""
    import repro.dist.gnn_step as gs

    g, pg, schedules, dv = sched_case
    es_list = [ws.epoch(0) for ws in schedules]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    flat = gs._epoch_flat(es_list, dv)
    want_miss, want_owner = gs._classify_misses(flat, caches, dv)
    monkeypatch.setattr(gs, "STAMP_TABLE_MAX_SLOTS", 0)
    got_miss, got_owner = gs._classify_misses(flat, caches, dv)
    np.testing.assert_array_equal(got_miss, want_miss)
    np.testing.assert_array_equal(got_owner, want_owner)
    assert want_miss.any()


@settings(max_examples=3, deadline=None,
          suppress_health_check=ALL_HEALTH_CHECKS)
@given(uneven_worker_cases())
def test_vectorized_collation_property_on_drawn_schedules(case):
    """Vectorized == loop collation on DRAWN uneven schedules: random
    batch sizes, cache budgets (incl. 0), seeds, and zero/partial-train
    workers (tests/strategies.py) -- both epochs, hot and empty caches."""
    g, pg, schedules, dv = case
    m_max, edge_max = merge_pad_bounds(schedules)
    for epoch in range(2):
        es_list = [ws.epoch(epoch) for ws in schedules]
        B = max(1, max((b.seeds.shape[0] for es in es_list
                        for b in es.batches), default=1))
        for caches in (empty_caches(4, g.feat_dim),
                       [dv.remap_cache(es.cache_ids) for es in es_list]):
            k_max = epoch_k_max(es_list, caches, dv)
            S = max(es.num_batches for es in es_list)
            if S == 0:      # every worker drawn empty: nothing to pad
                continue
            args = (es_list, caches, dv, g.labels, B, m_max, edge_max,
                    k_max, S)
            _assert_epochs_equal(collate_device_epoch(*args),
                                 collate_device_epoch_loop(*args),
                                 len(edge_max))


def test_vectorized_collation_rejects_truncation(sched_case):
    g, pg, schedules, dv = sched_case
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    S = max(es.num_batches for es in es_list)
    with pytest.raises(ValueError, match="more batches"):
        collate_device_epoch(es_list, caches, dv, g.labels, 16, m_max,
                             edge_max, 10_000, S - 1)


@settings(max_examples=12, deadline=None,
          suppress_health_check=ALL_HEALTH_CHECKS)
@given(pull_request_sets())
def test_pack_pull_lanes_matches_per_group_build_pull_plan(case):
    """The batched lane packer vs one build_pull_plan per group on DRAWN
    requests with duplicates and padding ids (k_max sized to run exactly
    full on some draws)."""
    per_group, owner_of, P_, k_max = case
    G = len(per_group)
    ids = np.concatenate([gi for gi, _ in per_group]) \
        if per_group else np.zeros(0, np.int64)
    pos = np.concatenate([gp for _, gp in per_group]) \
        if per_group else np.zeros(0, np.int64)
    grp = np.concatenate([np.full(gi.shape[0], gidx)
                          for gidx, (gi, _) in enumerate(per_group)]) \
        if per_group else np.zeros(0, np.int64)
    valid = ids >= 0
    sids, spos, smask, counts = pack_pull_lanes(
        ids[valid], pos[valid], grp[valid], owner_of[ids[valid]],
        G, P_, k_max)
    for gidx, (gi, gp) in enumerate(per_group):
        plan = build_pull_plan(gi.astype(np.int32), gp.astype(np.int32),
                               owner_of, P_, k_max)
        np.testing.assert_array_equal(sids[gidx], plan.send_ids)
        np.testing.assert_array_equal(spos[gidx], plan.send_pos)
        np.testing.assert_array_equal(smask[gidx], plan.send_mask)
        np.testing.assert_array_equal(counts[gidx], plan.counts)


def test_pack_pull_lanes_overflow_raises():
    owner_of = np.zeros(64, np.int64)
    ids = np.arange(10)
    with pytest.raises(ValueError, match="k_max"):
        pack_pull_lanes(ids, ids, np.zeros(10, np.int64),
                        owner_of[ids], 1, 1, 4)


# ---------------------------------------------------------------------------
# prefetch stream: the wrapped final pull ships no real lanes
# ---------------------------------------------------------------------------

def test_prefetch_stream_masks_wrapped_final_plan():
    rng = np.random.default_rng(9)
    S, P_, k = 5, 4, 3
    send = {
        "send_ids": jnp.asarray(rng.integers(1, 99, size=(S, P_, k)
                                             ).astype(np.int32)),
        "send_pos": jnp.asarray(rng.integers(0, 50, size=(S, P_, k)
                                             ).astype(np.int32)),
        "send_mask": jnp.asarray(rng.random((S, P_, k)) > 0.3),
    }
    out = jax.tree.map(np.asarray, prefetch_stream(send))
    # steps 0..S-2 carry step i+1's plan untouched
    for key in ("send_ids", "send_pos", "send_mask"):
        np.testing.assert_array_equal(out[key][:-1],
                                      np.asarray(send[key])[1:])
    # the wrapped final element is fully masked with zero lanes
    assert not out["send_mask"][-1].any()
    assert (out["send_ids"][-1] == 0).all()
    assert (out["send_pos"][-1] == 0).all()
    # fetch accounting: exactly the lanes of steps 1..S-1 survive
    want = int(np.asarray(send["send_mask"])[1:].sum())
    assert int(out["send_mask"].sum()) == want
