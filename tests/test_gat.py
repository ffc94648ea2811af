"""The program's ``gat`` kind (``models/gnn.py``) against a plain GAT
written in ``_gat_ref.py``: forward, loss and gradients on hand-built
blocks, one epoch of the device runner at P=1 here and at P=2 on two
emulated CPU devices, the dst-major layout its softmax reshape relies
on, and the configurations it refuses."""
import os
import subprocess
import sys

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import _gat_ref
from repro.models import GNNConfig
from repro.models.gnn import forward, init_params, loss_fn

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_blocks(case, fanouts, nd, m, seed=0):
    """Dst-major, fan-out-regular blocks: layer ``l`` has ``nd[l]`` real
    dst rows over ``nd[l-1]`` (``m`` for layer 0) source rows, and one
    fully masked padded dst row past them. ``case`` plants masked
    edges, a row with no valid in-edge, or duplicate neighbours."""
    rng = np.random.default_rng(seed)
    blocks, n_src = [], m
    for l, (fo, n) in enumerate(zip(fanouts, nd)):
        rows = n + 1
        src = rng.integers(0, n_src, size=rows * fo).astype(np.int32)
        dst = np.repeat(np.arange(rows), fo).astype(np.int32)
        mask = dst < n
        if case == "masked":
            mask[1::3] &= False
        elif case == "self_only":
            mask[dst == 1] = False
        elif case == "duplicates":
            src[dst == 0] = src[0]
            src[(dst == 1) & (np.arange(rows * fo) % fo < 2)] = 2
        src[~mask & (dst >= n)] = 0
        blocks.append((src, dst, mask, n))
        n_src = n
    return blocks


CASES = [(case, heads, layers) for case in
         ("plain", "masked", "self_only", "duplicates")
         for heads, layers in ((1, 2), (3, 2))] + [("masked", 2, 1),
                                                   ("duplicates", 2, 3)]


@pytest.mark.parametrize("case,heads,layers", CASES)
def test_forward_loss_and_grads_match_the_plain_gat(case, heads, layers):
    fanouts = (3, 2, 2)[:layers]
    nd, m = (6, 4, 3)[3 - layers:], 10
    cfg = GNNConfig(kind="gat", in_dim=5, hidden_dim=4, heads=heads,
                    num_classes=3, num_layers=layers, fanouts=fanouts)
    params = init_params(cfg, jax.random.key(1))
    rng = np.random.default_rng(2)
    # nonzero biases, so their gradients and the head mean are exercised
    params = jax.tree.map(
        lambda a: a + 0.1 * jnp.asarray(rng.normal(size=a.shape),
                                        jnp.float32), params)
    x = jnp.asarray(rng.normal(size=(m, 5)), jnp.float32)
    blocks = make_blocks(case, fanouts, nd, m)
    B = nd[-1]
    labels = jnp.asarray(rng.integers(0, 3, size=B), jnp.int32)
    seed_mask = jnp.asarray(np.arange(B) < B - 1)
    es, ed, em = ([jnp.asarray(b[i]) for b in blocks] for i in range(3))

    got = jax.jit(lambda p: forward(cfg, p, x, es, ed, em))(params)
    assert got.shape == (m, 3)
    want = jax.jit(lambda p: _gat_ref.logits(p, x, blocks))(params)
    np.testing.assert_allclose(got[:B], want, rtol=1e-5, atol=1e-6)
    assert np.isfinite(np.asarray(got)).all()

    def prog(p):
        return loss_fn(cfg, p, x, es, ed, em, labels, seed_mask)[0]

    l_prog, g_prog = jax.jit(jax.value_and_grad(prog))(params)
    l_ref, g_ref = _gat_ref.loss_and_grad(blocks)(params, x, labels,
                                                  seed_mask)
    np.testing.assert_allclose(l_prog, l_ref, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(g_prog), jax.tree.leaves(g_ref)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-6)


def test_padded_rows_and_masked_edges_stay_finite():
    """A block with every edge masked and all-zero rows: each row keeps
    its self-loop, so nothing is a softmax over an empty set."""
    cfg = GNNConfig(kind="gat", in_dim=4, hidden_dim=2, heads=2,
                    num_classes=3, num_layers=2, fanouts=(2, 2))
    params = init_params(cfg, jax.random.key(0))
    es = [jnp.zeros(8, jnp.int32), jnp.zeros(4, jnp.int32)]
    em = [jnp.zeros(8, bool), jnp.zeros(4, bool)]
    x = jnp.zeros((6, 4), jnp.float32)

    def f(p):
        return loss_fn(cfg, p, x, es, es, em, jnp.zeros(2, jnp.int32),
                       jnp.ones(2, bool))[0]

    loss, g = jax.jit(jax.value_and_grad(f))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(a)).all() for a in jax.tree.leaves(g))


def test_the_sampled_edges_are_dst_major_and_fanout_regular():
    """The layout the softmax reshape relies on: in every batch of a
    3-layer schedule, and in its collated device epoch, edge ``k`` of a
    layer belongs to dst row ``k // fanout``, and the padded tail is
    masked."""
    from repro.core import merge_pad_bounds
    from repro.dist import DeviceView, collate_device_epoch, empty_caches

    fanouts = (3, 2, 2)
    g, pg, schedules = _gat_ref.runner_case(1, train_per_worker=40,
                                            batch=8, fanouts=fanouts)
    es = schedules[0].epoch(0)
    for i in range(es.num_batches):
        for fo, blk in zip(fanouts, es.flat.batch(i).blocks):
            E = blk.edge_dst.shape[0]
            assert E == blk.num_dst * fo
            np.testing.assert_array_equal(blk.edge_dst,
                                          np.arange(E) // fo)
    m_max, edge_max = merge_pad_bounds(schedules)
    assert [e % fo for e, fo in zip(edge_max, fanouts)] == [0, 0, 0]
    out = collate_device_epoch([es], empty_caches(1, g.feat_dim),
                               DeviceView.build(pg), g.labels, 8, m_max,
                               edge_max, 1, es.num_batches)
    for l, fo in enumerate(fanouts):
        dst, mask = out["edge_dst"][l][:, 0], out["edge_mask"][l][:, 0]
        k = np.arange(edge_max[l])
        nd = es.flat.num_dst[l]
        for i in range(es.num_batches):
            real = k < nd[i] * fo
            np.testing.assert_array_equal(dst[i][real], k[real] // fo)
            assert not mask[i][~real].any()


def test_one_worker_runner_matches_the_plain_gat():
    losses, ref_losses, norms = _gat_ref.runner_matches_reference(1)
    assert len(losses) == 3
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-5)
    for leaf, (a, b) in norms.items():
        assert abs(a - b) <= 1e-4 * max(b, 1e-3), (leaf, a, b)


def test_two_worker_runner_matches_the_plain_gat():
    """P=2 on two emulated CPU devices, in a process of its own (the
    device count locks when JAX starts)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_dist_checks.py"),
         "gat"], capture_output=True, text=True, timeout=420, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "gat_runner OK" in r.stdout


def _sage_forward_as_before(cfg, params, features, edge_src, edge_dst,
                            edge_mask):
    """The ``sage`` forward as it read before the ``gat`` kind."""
    h, m = features, features.shape[0]
    for l, layer in enumerate(params["layers"]):
        with jax.named_scope("aggregate"):
            msg = h[edge_src[l]] * edge_mask[l][:, None].astype(h.dtype)
            summed = jax.ops.segment_sum(msg, edge_dst[l], num_segments=m)
            cnt = jax.ops.segment_sum(edge_mask[l].astype(h.dtype),
                                      edge_dst[l], num_segments=m)
            agg = summed / jnp.maximum(cnt, 1.0)[:, None]
        h_new = h @ layer["w_self"] + agg @ layer["w_neigh"] + layer["b"]
        if l < cfg.num_layers - 1:
            h_new = jax.nn.relu(h_new)
        h = h_new
    return h


def test_the_sage_program_is_unchanged():
    cfg = GNNConfig(kind="sage", in_dim=6, hidden_dim=8, num_classes=3,
                    num_layers=2)
    params = init_params(cfg, jax.random.key(0))
    args = (jnp.zeros((12, 6)), [jnp.zeros(20, jnp.int32)] * 2,
            [jnp.zeros(20, jnp.int32)] * 2, [jnp.zeros(20, bool)] * 2)

    def jaxpr(f):
        return str(jax.make_jaxpr(
            lambda p, *a: jax.grad(lambda q: jnp.sum(f(cfg, q, *a)))(p))(
                params, *args))

    assert jaxpr(forward) == jaxpr(_sage_forward_as_before)


@pytest.mark.parametrize("kind", ["sage", "gcn", "gat"])
def test_every_matrix_product_takes_the_configured_precision(kind):
    """Forward and backward: the transposes keep the precision."""
    cfg = GNNConfig(kind=kind, in_dim=5, hidden_dim=4, heads=2,
                    num_classes=3, num_layers=2, fanouts=(2, 2),
                    precision="highest")
    params = init_params(cfg, jax.random.key(0))
    blocks = make_blocks("plain", (2, 2), (4, 2), 8)
    args = (jnp.zeros((8, 5)), [jnp.asarray(b[0]) for b in blocks],
            [jnp.asarray(b[1]) for b in blocks],
            [jnp.asarray(b[2]) for b in blocks],
            jnp.zeros(2, jnp.int32), jnp.ones(2, bool))
    text = str(jax.make_jaxpr(jax.grad(
        lambda p: loss_fn(cfg, p, *args)[0]))(params))
    dots = text.count("dot_general")
    assert dots >= 4
    assert text.count("Precision.HIGHEST, Precision.HIGHEST") == dots


def test_an_unknown_precision_is_refused():
    with pytest.raises(ValueError, match="unknown precision 'fp8'"):
        GNNConfig(kind="sage", in_dim=4, hidden_dim=2, num_classes=3,
                  num_layers=2, precision="fp8")


@pytest.mark.parametrize("kw,match", [
    (dict(agg_backend="pallas"), "runs on agg_backend 'segment' only"),
    (dict(agg_backend="pallas_interpret"),
     "runs on agg_backend 'segment' only"),
    (dict(fanouts=None), "needs cfg.fanouts"),
    (dict(fanouts=(2, 2)), "needs cfg.fanouts")])
def test_configurations_gat_refuses(kw, match):
    base = dict(kind="gat", in_dim=4, hidden_dim=2, heads=2, num_classes=3,
                num_layers=3, fanouts=(2, 2, 2))
    with pytest.raises(ValueError, match=match):
        GNNConfig(**dict(base, **kw))


def test_a_padded_edge_count_off_the_fanout_is_refused():
    cfg = GNNConfig(kind="gat", in_dim=4, hidden_dim=2, heads=2,
                    num_classes=3, num_layers=1, fanouts=(3,))
    params = init_params(cfg, jax.random.key(0))
    with pytest.raises(ValueError, match="no multiple of its fan-out 3"):
        forward(cfg, params, jnp.zeros((6, 4)), [jnp.zeros(7, jnp.int32)],
                [jnp.zeros(7, jnp.int32)], [jnp.zeros(7, bool)])
