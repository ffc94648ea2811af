"""SAGE/GCN's mean aggregation on the sampler's dst-major, fan-out-regular
layout (``models/gnn.py``): the reshape path, which the program takes
where the fan-outs are known, against the ``segment_sum`` oracle that
runs without them -- forward, loss and gradients, the dst-prefix rows
and the zero tail past them, padded edge counts that are no multiple of
the fan-out, zero-degree rows -- and the epoch program the device runner
builds from a sampler's schedule: fan-outs read off the schedule, the
``scatter_free_layers`` counter, and no scatter in the forward's
``aggregate`` ops."""
import dataclasses
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import GNNConfig
from repro.models.gnn import _aggregate, forward, init_params, loss_fn

FANOUTS, ND, M, D = (4, 3), (9, 5), 30, 6


def make_blocks(case, seed=0):
    """Dst-major blocks, ``FANOUTS[l]`` edges per dst row over ``ND[l]``
    real dst rows. Every case pads each layer's edge list with masked
    edges (src 0, dst 0) to a length that is no multiple of its fan-out,
    as the benchmark cells' edge floors are. ``zero_degree`` masks every
    edge of two rows as self-loops, as the sampler does; ``padded_rows``
    adds fully masked dst rows past the real ones before the tail."""
    rng = np.random.default_rng(seed)
    blocks, n_src = [], M
    for fo, n in zip(FANOUTS, ND):
        rows = n + (2 if case == "padded_rows" else 0)
        src = rng.integers(0, n_src, size=rows * fo).astype(np.int32)
        dst = np.repeat(np.arange(rows), fo).astype(np.int32)
        mask = dst < n
        if case == "zero_degree":
            for r in (0, n - 1):
                mask[dst == r] = False
                src[dst == r] = r
        src[~mask & (dst >= n)] = 0
        dst[~mask & (dst >= n)] = 0
        tail = fo + fo // 2 + 1
        blocks.append((np.concatenate([src, np.zeros(tail, np.int32)]),
                       np.concatenate([dst, np.zeros(tail, np.int32)]),
                       np.concatenate([mask, np.zeros(tail, bool)])))
        n_src = n
    return [tuple(jnp.asarray(a) for a in b) for b in blocks]


def configs(kind):
    kw = dict(kind=kind, in_dim=D, hidden_dim=8, num_classes=5,
              num_layers=len(FANOUTS), precision="highest")
    return GNNConfig(**kw, fanouts=FANOUTS), GNNConfig(**kw)


def primitives(fn, *args):
    """Names of every primitive in ``fn``'s jaxpr, nested ones too."""
    out = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            out.add(eqn.primitive.name)
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if hasattr(sub, "jaxpr"):
                        walk(getattr(sub.jaxpr, "jaxpr", sub.jaxpr))
                    elif hasattr(sub, "eqns"):
                        walk(sub)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return out


@pytest.mark.parametrize("kind", ["sage", "gcn"])
@pytest.mark.parametrize("case", ["plain", "zero_degree", "padded_rows"])
def test_reshape_mean_matches_the_segment_oracle(kind, case):
    """Loss and gradients to float32 rounding; each layer's mean over
    its ``E // F`` dst-prefix rows equal to the oracle's, whose rows
    past them are zero; the seed logits equal, and the rows past the
    last layer's dst prefix zero."""
    cfg, oracle = configs(kind)
    blocks = make_blocks(case)
    rng = np.random.default_rng(1)
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.asarray(rng.normal(size=a.shape),
                                        jnp.float32),
        init_params(cfg, jax.random.key(2)))
    x = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    es, ed, em = ([b[i] for b in blocks] for i in range(3))
    B = ND[-1]
    labels = jnp.asarray(rng.integers(0, 5, size=B), jnp.int32)
    seed_mask = jnp.asarray(np.arange(B) < B - 1)

    h = x
    for l, (src, dst, mask) in enumerate(blocks):
        got = _aggregate(cfg, l, h, src, dst, mask)
        want = _aggregate(oracle, l, h, src, dst, mask)
        nd = src.shape[0] // FANOUTS[l]
        assert got.shape == (nd, D) and want.shape == (h.shape[0], D)
        np.testing.assert_allclose(got, want[:nd], rtol=1e-6, atol=1e-7)
        assert not np.asarray(want[nd:]).any()
        h = jnp.asarray(rng.normal(size=(ND[l], D)), jnp.float32)

    got = forward(cfg, params, x, es, ed, em)
    want = forward(oracle, params, x, es, ed, em)
    assert got.shape == want.shape == (M, 5)
    np.testing.assert_allclose(got[:B], want[:B], rtol=1e-5, atol=1e-6)
    assert not np.asarray(got[es[-1].shape[0] // FANOUTS[-1]:]).any()

    def run(c):
        return jax.value_and_grad(lambda p: loss_fn(
            c, p, x, es, ed, em, labels, seed_mask)[0])(params)

    (l_got, g_got), (l_want, g_want) = run(cfg), run(oracle)
    np.testing.assert_allclose(l_got, l_want, rtol=2e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kind", ["sage", "gcn"])
def test_unknown_fanouts_keep_the_segment_path(kind):
    """Without fan-outs the forward scatters (``segment_sum``); with
    them it has no scatter at all."""
    cfg, oracle = configs(kind)
    blocks = make_blocks("plain")
    params = init_params(cfg, jax.random.key(0))
    x = jnp.zeros((M, D), jnp.float32)
    es, ed, em = ([b[i] for b in blocks] for i in range(3))

    def prims(c):
        return primitives(lambda p: forward(c, p, x, es, ed, em), params)

    assert "scatter-add" in prims(oracle)
    assert not {p for p in prims(cfg) if "scatter" in p}


# -- the device runner ------------------------------------------------------

@pytest.fixture(scope="module")
def one_worker():
    """The tiny graph on one worker, one scheduled epoch at fan-outs
    (5, 5). -> (graph, partition, schedules)."""
    from repro.core import build_schedule
    from repro.graph import KHopSampler, load_dataset, partition_graph

    g = load_dataset("tiny")
    pg = partition_graph(g, 1, "greedy")
    sampler = KHopSampler(g, fanouts=[5, 5], batch_size=16)
    return g, pg, [build_schedule(sampler, pg, worker=0, s0=3,
                                  num_epochs=1, n_hot=32)]


def make_runner(world, schedules=None, **cfg_kw):
    from repro.dist import DeviceRapidGNNRunner, DeviceView, make_mesh
    from repro.train import AdamW

    g, pg, sched = world
    kw = dict(kind="sage", in_dim=g.feat_dim, hidden_dim=16,
              num_classes=g.num_classes, num_layers=2)
    kw.update(cfg_kw)
    return DeviceRapidGNNRunner(schedules or sched, DeviceView.build(pg),
                                GNNConfig(**kw), AdamW(lr=3e-3),
                                make_mesh((1,), ("data",)), 16, g.labels)


def test_runner_reads_the_fanouts_off_the_schedule(one_worker):
    from repro.dist.runner import epoch_fanouts

    es = one_worker[2][0].epoch(0)
    assert es.num_batches > 1
    assert epoch_fanouts(es) == [5, 5]
    runner = make_runner(one_worker)
    assert runner.cfg.fanouts == (5, 5)
    assert runner.scatter_free_layers == 2
    # GCN takes the same path; GAT keeps fan-outs that agree
    assert make_runner(one_worker, kind="gcn").scatter_free_layers == 2
    gat = make_runner(one_worker, kind="gat", heads=2, fanouts=(5, 5))
    assert gat.cfg.fanouts == (5, 5)


def test_runner_refuses_fanouts_the_schedule_disagrees_with(one_worker):
    with pytest.raises(ValueError, match="disagree"):
        make_runner(one_worker, kind="gat", heads=2, fanouts=(3, 5))


def test_an_irregular_batch_keeps_the_segment_path(one_worker):
    """One batch whose layer-0 block lost an edge: no fan-out fits every
    batch, so the program keeps the configuration and its scatter."""
    from repro.core.schedule import EpochSchedule
    from repro.dist.runner import epoch_fanouts

    ws = one_worker[2][0]
    es = ws.epoch(0)
    batches = list(es.batches)
    b0 = batches[0]
    blk = b0.blocks[0]
    cut = dataclasses.replace(blk, edge_src=blk.edge_src[:-1],
                              edge_dst=blk.edge_dst[:-1],
                              edge_mask=blk.edge_mask[:-1])
    batches[0] = dataclasses.replace(b0, blocks=[cut] + b0.blocks[1:])
    odd = EpochSchedule(0, batches=batches, cache_ids=es.cache_ids,
                        m_max=es.m_max)
    assert epoch_fanouts(odd) is None
    sched = [dataclasses.replace(ws, epochs=[odd], epoch_meta=None,
                                 builder=None)]
    runner = make_runner(one_worker, schedules=sched)
    assert runner.cfg.fanouts is None
    (rep,) = runner.run()
    assert rep.scatter_free_layers == 0
    assert np.isfinite(rep.losses).all()
    # the segment_sum mean reads edge_dst: the stage ships it
    dst = runner._stage(0)["batches"]["edge_dst"]
    assert [d.shape for d in dst] == [(runner.num_steps, 1, e)
                                      for e in runner.edge_max]


def forward_aggregate_opcodes(hlo_text):
    """Opcodes of the instructions whose ``op_name`` lies in the
    forward's ``aggregate`` scope (backward ops carry ``transpose(``)."""
    out = []
    for line in hlo_text.splitlines():
        inst = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(",
                        line)
        name = re.search(r'op_name="([^"]*)"', line)
        if inst and name and re.search(r"(^|/)aggregate(/|$)",
                                       name.group(1)) \
                and "transpose(" not in name.group(1):
            out.append(inst.group(1))
    return out


def test_the_epoch_program_runs_scatter_free(one_worker):
    """A two-layer SAGE epoch through the runner: ``scatter_free_layers``
    reads 2 in the report and its dict, and the compiled epoch program's
    forward ``aggregate`` ops gather and reduce but never scatter."""
    runner = make_runner(one_worker)
    (rep,) = runner.run()
    assert rep.scatter_free_layers == 2
    assert rep.to_dict()["scatter_free_layers"] == 2
    assert np.isfinite(rep.losses).all()

    staged = runner._stage(0)
    # nothing in the program reads edge_dst: the stage leaves it out
    assert staged["batches"]["edge_dst"] == [None, None]
    with runner.mesh:
        text = runner._fn.lower(
            runner.params, runner.opt_state, jnp.asarray(runner.dv.table),
            jnp.asarray(runner.dv.offsets), staged["cids"],
            staged["cfeats"], staged["batches"]).compile().as_text()
    ops = forward_aggregate_opcodes(text)
    assert "gather" in ops
    assert not [op for op in ops if "scatter" in op], ops
