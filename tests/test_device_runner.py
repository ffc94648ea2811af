"""Host-side (single-device) tests for the multi-epoch device runner's
collation layer: uneven-worker padding, empty-epoch pad metadata, and the
global static bounds the one-compilation property rests on. The on-mesh
runner itself is exercised by tests/test_distributed.py on 4 emulated
devices."""
import numpy as np
import pytest

from _uneven import build_uneven_case
from repro.core import merge_pad_bounds
from repro.core.schedule import epoch_edge_maxima
from repro.dist import collate_device_epoch, empty_caches, epoch_k_max


@pytest.fixture(scope="module")
def uneven():
    """4 partitions; worker 2 keeps NO train nodes, worker 3 half a batch."""
    return build_uneven_case(P_=4, B=16, epochs=2, n_hot=64)


def test_uneven_schedule_shapes(uneven):
    g, pg, schedules, dv = uneven
    assert schedules[2].epoch(0).num_batches == 0
    assert 0 < schedules[3].epoch(0).num_batches < \
        schedules[0].epoch(0).num_batches


def test_epoch_edge_maxima_empty_epoch(uneven):
    """Regression: es.batches[0] indexed unconditionally -> IndexError."""
    g, pg, schedules, dv = uneven
    es = schedules[2].epoch(0)
    assert es.num_batches == 0
    # layer count now rides the FlatEpoch layout, so the empty epoch
    # reports all-zero maxima even without the num_layers hint
    assert epoch_edge_maxima(es) == [0, 0]
    assert epoch_edge_maxima(es, num_layers=2) == [0, 0]
    es0 = schedules[0].epoch(0)
    assert all(e > 0 for e in epoch_edge_maxima(es0))


def test_pad_bounds_survive_empty_epochs(uneven):
    """An all-empty worker must report zero bounds without collapsing the
    layer list, and populated workers keep real bounds."""
    g, pg, schedules, dv = uneven
    m2, em2 = schedules[2].pad_bounds()
    assert m2 == 0 and all(e == 0 for e in em2)
    m0, em0 = schedules[0].pad_bounds()
    assert m0 > 0 and len(em0) == 2 and all(e > 0 for e in em0)


def test_collate_pads_short_workers_with_masked_steps(uneven):
    """Regression: es.batches[i] indexed for all num_steps -> IndexError
    for short/zero-batch workers. Tail steps must be fully masked."""
    g, pg, schedules, dv = uneven
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    S = max(es.num_batches for es in es_list)
    k_max = epoch_k_max(es_list, caches, dv)
    out = collate_device_epoch(es_list, caches, dv, g.labels, 16, m_max,
                               edge_max, k_max, S)
    nb3 = es_list[3].num_batches
    # worker 2: every step empty; worker 3: tail beyond its batches empty
    for w, lo in ((2, 0), (3, nb3)):
        assert (out["input_nodes"][lo:, w] == -1).all()
        assert not out["seed_mask"][lo:, w].any()
        assert not out["send_mask"][lo:, w].any()
        for l in range(len(edge_max)):
            assert not out["edge_mask"][l][lo:, w].any()
    # populated worker keeps real content
    assert (out["input_nodes"][0, 0] >= 0).any()
    assert out["send_mask"][:, 0].sum() > 0


def test_collate_rejects_truncating_num_steps(uneven):
    g, pg, schedules, dv = uneven
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    caches = [dv.remap_cache(es.cache_ids) for es in es_list]
    S = max(es.num_batches for es in es_list)
    with pytest.raises(ValueError, match="more batches"):
        collate_device_epoch(es_list, caches, dv, g.labels, 16, m_max,
                             edge_max, 10_000, S - 1)


def test_empty_caches_route_everything_through_lanes(uneven):
    """Baseline collation key: with empty C_s every remote id is a miss,
    so lane counts equal the per-batch unique remote counts."""
    g, pg, schedules, dv = uneven
    m_max, edge_max = merge_pad_bounds(schedules)
    es_list = [ws.epoch(0) for ws in schedules]
    nocache = empty_caches(4, g.feat_dim)
    k_max = epoch_k_max(es_list, nocache, dv)
    out = collate_device_epoch(es_list, nocache, dv, g.labels, 16, m_max,
                               edge_max, k_max,
                               max(es.num_batches for es in es_list))
    for w, es in enumerate(es_list):
        want = sum(int((pg.owner[b.input_nodes] != w).sum())
                   for b in es.batches)
        got = int(out["send_mask"][:, w].sum())
        assert got == want


def test_one_worker_runner_assembles_every_row_locally():
    """One worker owns the whole graph: the assembly source counters
    report every valid row local, none cached or pulled, and the report's
    dict carries them."""
    from repro.core import build_schedule
    from repro.dist import DeviceRapidGNNRunner, DeviceView, make_mesh
    from repro.graph import KHopSampler, load_dataset, partition_graph
    from repro.models import GNNConfig
    from repro.train import AdamW

    g = load_dataset("tiny")
    pg = partition_graph(g, 1, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=16)
    schedules = [build_schedule(sampler, pg, worker=0, s0=3, num_epochs=1,
                                n_hot=32)]
    cfg = GNNConfig(kind="sage", in_dim=g.feat_dim, hidden_dim=16,
                    num_classes=g.num_classes, num_layers=2)
    runner = DeviceRapidGNNRunner(schedules, DeviceView.build(pg), cfg,
                                  AdamW(lr=3e-3), make_mesh((1,), ("data",)),
                                  16, g.labels)
    (rep,) = runner.run()
    assert 0 < rep.valid_rows < rep.padded_rows
    assert (rep.local_rows, rep.cache_rows, rep.pulled_rows) == (
        rep.valid_rows, 0, 0)
    d = rep.to_dict()
    assert (d["local_rows"], d["cache_rows"], d["pulled_rows"]) == (
        rep.valid_rows, 0, 0)
