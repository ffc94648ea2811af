"""Device schedule-compiler suite (ISSUE 6 / DESIGN.md §2.2).

``sample_epoch_batched_device`` must be BIT-identical to the numpy
``sample_epoch_batched`` compiler over arbitrary drawn graphs (zero-
degree nodes, empty/tiny train sets), on BOTH lookup paths (dense table
and searchsorted) and through both fallbacks (int64 key spaces, empty
epochs). The key sort must be stable (numpy's stable argsort order);
device hot-set selection must reproduce ``select_hot_set``;
the background ``SpillWriter`` must round-trip bit-exact and surface
writer-thread failures; lazy schedules must rebuild bit-equal epochs.
"""
import dataclasses
import os
import tempfile

import numpy as np
import pytest

from _hyp import ALL_HEALTH_CHECKS, given, settings
from strategies import build_sampler_graph, sampler_epoch_cases
from repro.graph import load_dataset, partition_graph, KHopSampler
import repro.graph.device_sampler as dsm
from repro.graph.device_sampler import (device_remote_freq,
                                        device_select_hot_set,
                                        sample_epoch_batched_device)
from repro.core import build_schedule
from repro.core.schedule import (SpillWriter, _build_epoch,
                                 load_epoch_npz, select_hot_set,
                                 spill_path)


def assert_flat_bit_equal(ref, got):
    """Every FlatEpoch array AND dtype identical -- the §2.2 contract."""
    assert (ref.epoch, ref.worker) == (got.epoch, got.worker)
    assert ref.num_batches == got.num_batches
    assert ref.num_layers == got.num_layers
    for f in ("seeds", "seed_starts", "input_nodes", "input_starts",
              "num_dst"):
        a, b = getattr(ref, f), getattr(got, f)
        np.testing.assert_array_equal(a, b, err_msg=f)
        assert a.dtype == b.dtype, f
    for l in range(ref.num_layers):
        for f in ("edge_src", "edge_dst", "edge_mask", "edge_starts"):
            a, b = getattr(ref, f)[l], getattr(got, f)[l]
            np.testing.assert_array_equal(a, b, err_msg=f"{f}[{l}]")
            assert a.dtype == b.dtype, f"{f}[{l}]"


# ---- device compiler vs numpy compiler (the tentpole contract) -----------

@settings(max_examples=10, deadline=None,
          suppress_health_check=ALL_HEALTH_CHECKS)
@given(sampler_epoch_cases())
def test_device_compiler_bit_equal_to_batched(case):
    """For ANY drawn (graph, train, fanouts, B): the device compiler's
    FlatEpoch is bit-equal to the numpy compiler's -- including
    zero-degree nodes, empty train sets and batch_size > |train|."""
    g, train, fanouts, B, s0, w, e = case
    sampler = KHopSampler(g, fanouts=list(fanouts), batch_size=B)
    ref = sampler.sample_epoch_batched(s0, w, e, train)
    got = sample_epoch_batched_device(sampler, s0, w, e, train)
    assert_flat_bit_equal(ref, got)


def test_device_compiler_searchsorted_path(monkeypatch):
    """Key spaces past the dense-table budget switch to searchsorted
    membership/inverse lookups -- still bit-equal."""
    g = build_sampler_graph(5, n=60, n_zero=10)
    train = np.arange(60, dtype=np.int64)
    s = KHopSampler(g, fanouts=[4, 3], batch_size=9)
    ref = s.sample_epoch_batched(13, 1, 2, train)
    monkeypatch.setattr(dsm, "DEVICE_TABLE_MAX_SLOTS", 0)
    got = sample_epoch_batched_device(s, 13, 1, 2, train)
    assert_flat_bit_equal(ref, got)


def test_device_compiler_int64_key_fallback(monkeypatch):
    """Key spaces past the int32 bound take the numpy wide-key path
    (device sorts are int32-only) -- equal to the per-batch oracle."""
    import repro.graph.sampler as sampler_mod
    from test_schedule_compiler import assert_batches_bit_equal

    g = build_sampler_graph(3, n=50, n_zero=8)
    train = np.arange(50, dtype=np.int64)
    s = KHopSampler(g, fanouts=[3, 2], batch_size=7)
    monkeypatch.setattr(dsm, "KEY_INT32_MAX_SLOTS", 0)
    monkeypatch.setattr(sampler_mod, "KEY_INT32_MAX_SLOTS", 0)
    got = sample_epoch_batched_device(s, 11, 0, 1, train)
    monkeypatch.undo()
    assert_batches_bit_equal(s.sample_epoch(11, 0, 1, train),
                             got.to_batches())


def test_device_compiler_empty_epoch():
    g = build_sampler_graph(1, n=20)
    s = KHopSampler(g, fanouts=[3], batch_size=4)
    got = sample_epoch_batched_device(s, 5, 0, 0,
                                      np.zeros(0, np.int64))
    assert got.num_batches == 0


# ---- build_schedule end to end: all three compilers ----------------------

def test_build_schedule_device_compiler_identical():
    """On a real partitioned graph the device compiler produces the
    SAME schedule as batched/loop: payload, remote ids/freqs, hot set,
    pad bounds."""
    from test_schedule_compiler import _assert_epochs_equal

    g = load_dataset("tiny")
    pg = partition_graph(g, 4, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=16)
    kw = dict(s0=42, num_epochs=2, n_hot=64)
    for w in (0, 2):
        wb = build_schedule(sampler, pg, worker=w, compiler="batched",
                            **kw)
        wd = build_schedule(sampler, pg, worker=w, compiler="device",
                            **kw)
        for e in range(2):
            a, b = wb.epoch(e), wd.epoch(e)
            _assert_epochs_equal(a, b)
            for f in ("remote_ids", "remote_freq", "cache_ids"):
                assert getattr(a, f).dtype == getattr(b, f).dtype, f
        assert wb.pad_bounds() == wd.pad_bounds()
    with pytest.raises(ValueError):
        build_schedule(sampler, pg, worker=0, compiler="bogus", **kw)


# ---- device remote-frequency + hot-set ordering --------------------------

def test_device_remote_freq_matches_unique():
    rng = np.random.default_rng(4)
    remote = rng.integers(0, 97, size=500).astype(np.int64)
    ids, freq = device_remote_freq(remote, span=100)
    ri, rf = np.unique(remote, return_counts=True)
    np.testing.assert_array_equal(ids, ri)
    np.testing.assert_array_equal(freq, rf)
    assert ids.dtype == np.int64 and freq.dtype == np.int64
    # empty and wide-span fallbacks
    for r, span in ((np.zeros(0, np.int64), 10),
                    (remote, 2 ** 40)):
        ids, freq = device_remote_freq(r, span=span)
        ri, rf = (np.unique(r, return_counts=True) if r.size
                  else (np.zeros(0, np.int64), np.zeros(0, np.int64)))
        np.testing.assert_array_equal(ids, ri)
        np.testing.assert_array_equal(freq, rf)


def test_device_hot_set_matches_host():
    """(freq desc, id asc) prefix incl. ties straddling the boundary."""
    ids = np.array([10, 11, 12, 13, 14], np.int64)
    freq = np.array([3, 1, 2, 1, 1], np.int64)
    for n_hot in (0, 3, 4, 99):
        np.testing.assert_array_equal(
            device_select_hot_set(ids, freq, n_hot),
            select_hot_set(ids, freq, n_hot))
    rng = np.random.default_rng(9)
    ids = np.unique(rng.integers(0, 5000, size=700)).astype(np.int64)
    freq = rng.integers(1, 6, size=ids.shape[0]).astype(np.int64)
    np.testing.assert_array_equal(device_select_hot_set(ids, freq, 64),
                                  select_hot_set(ids, freq, 64))


# ---- the schedule compiler's key sort: stable lax.sort -------------------

def _sort_case(name):
    rng = np.random.default_rng(7)
    if name == "sentinel_tail":
        keys = rng.integers(0, 1 << 20, size=1024).astype(np.int32)
        keys[1000:] = dsm.SENT
    elif name == "duplicates":
        keys = rng.integers(0, 7, size=256).astype(np.int32)
    elif name == "keys_only":
        keys = np.array([5, 3, 5, 1], np.int32)
    else:
        keys = np.zeros(0, np.int32)
    payload = (None if name == "keys_only"
               else np.arange(keys.shape[0], dtype=np.int32))
    return keys, payload


@pytest.mark.parametrize("name", ["sentinel_tail", "duplicates",
                                  "keys_only", "empty"])
def test_stable_sort_matches_numpy_stable(name):
    """Equal keys keep input order, so the payload is permuted exactly
    as numpy's stable argsort permutes it."""
    keys, payload = _sort_case(name)
    gk, gp = dsm.stable_sort(keys, payload)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(gk), keys[order])
    if payload is None:
        assert gp is None
    else:
        np.testing.assert_array_equal(np.asarray(gp), payload[order])


# ---- SpillWriter: background npz writes ----------------------------------

def _tiny_epoch():
    g = load_dataset("tiny")
    pg = partition_graph(g, 2, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=32)
    local = pg.local_nodes[0]
    tm = pg.graph.train_mask
    train = local[tm[local]] if tm is not None else local
    return _build_epoch(sampler, pg, 0, 7, 0, train, 64)


def test_spill_writer_round_trip():
    """An epoch written by the background writer reloads bit-equal --
    the spill regression the off-critical-path move must not break."""
    from test_schedule_compiler import _assert_epochs_equal

    es = _tiny_epoch()
    with tempfile.TemporaryDirectory() as td:
        path = spill_path(td, 0, 0)
        w = SpillWriter()
        try:
            w.submit(path, es)
            w.flush()
            back = load_epoch_npz(path)
        finally:
            w.close()
    _assert_epochs_equal(es, back)
    for f in ("seed_starts", "input_starts"):
        np.testing.assert_array_equal(getattr(back.flat, f),
                                      getattr(es.flat, f))


def test_spill_writer_raises_on_failed_write():
    """Writer-thread failures surface on the submitting thread at the
    next flush/close, never silently drop an epoch."""
    es = _tiny_epoch()
    w = SpillWriter()
    try:
        w.submit(os.path.join(os.sep, "nonexistent-dir!", "x.npz"), es)
        with pytest.raises(RuntimeError, match="spill write failed"):
            w.flush()
    finally:
        try:
            w.close()
        except RuntimeError:
            pass


# ---- lazy (device-resident) schedules ------------------------------------

def test_lazy_schedule_rebuilds_bit_equal():
    """lazy=True drops payloads AND skips spill; epoch(e) re-runs the
    compiler on demand and must reproduce the eager build exactly."""
    from test_schedule_compiler import _assert_epochs_equal

    g = load_dataset("tiny")
    pg = partition_graph(g, 4, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=16)
    kw = dict(worker=1, s0=3, num_epochs=2, n_hot=64)
    eager = build_schedule(sampler, pg, **kw)
    lazy = build_schedule(sampler, pg, lazy=True, **kw)
    assert all(e is None for e in lazy.epochs)
    assert lazy.spill_dir is None and lazy.builder is not None
    for e in range(2):
        _assert_epochs_equal(eager.epoch(e), lazy.epoch(e))
    assert eager.pad_bounds() == lazy.pad_bounds()
    # lazy overrides a spill request: device-resident means no disk
    with tempfile.TemporaryDirectory() as td:
        lz = build_schedule(sampler, pg, spill_dir=td, lazy=True, **kw)
        assert lz.spill_dir is None and os.listdir(td) == []


# ---- campaign plumbing ---------------------------------------------------

def test_cellspec_schedule_backend_field():
    from repro.eval.spec import CellSpec

    c = CellSpec(backend="device", system="rapidgnn", dataset="tiny",
                 batch_size=16, workers=4, n_hot=64, epochs=1,
                 schedule_backend="device")
    assert CellSpec.from_dict(c.to_dict()) == c
    assert c.effective_compiler == "device"
    # the backend toggle is NOT part of the differential pairing key:
    # schedules are bit-identical either way (this suite pins it)
    assert c.scenario_key() == dataclasses.replace(
        c, schedule_backend="numpy").scenario_key()
    assert dataclasses.replace(
        c, schedule_backend="numpy").effective_compiler == "batched"
    with pytest.raises(ValueError):
        CellSpec(backend="host", system="rapidgnn", dataset="tiny",
                 batch_size=16, workers=4, n_hot=64, epochs=1,
                 schedule_backend="bogus")
