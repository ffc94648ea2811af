"""A plain GAT for the tests: one softmax per dst row, written as a loop
over the rows and their edges, in float32 at ``precision="highest"``.

It reads the edges as concrete numpy arrays, so each call traces one
small program per block layout; keep the blocks tiny. Layer ``l`` maps
the rows ``h`` of its sources to its ``nd`` dst rows: each dst row ``v``
attends over the sources of its valid in-edges (duplicates count once
each) and over itself, per head, with LeakyReLU(0.2) scores; hidden
layers concatenate their heads and apply ELU, the last averages them.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def layer_out(layer, h, src, dst, mask, nd, last):
    H, C = layer["att_src"].shape
    z = jnp.dot(h, layer["lin"], precision=HIGHEST).reshape(-1, H, C)
    rows = []
    for v in range(nd):
        us = [int(u) for u, d, k in zip(src, dst, mask) if k and d == v]
        zu = z[np.asarray(us + [v])]                          # (n, H, C)
        e = (jnp.sum(zu * layer["att_src"], -1)
             + jnp.sum(z[v] * layer["att_dst"], -1))          # (n, H)
        alpha = jax.nn.softmax(jnp.where(e > 0, e, 0.2 * e), axis=0)
        out = jnp.sum(alpha[..., None] * zu, axis=0)          # (H, C)
        rows.append(out.mean(0) if last else out.reshape(H * C))
    out = (jnp.stack(rows) + layer["bias"]
           + jnp.dot(h[:nd], layer["skip_w"], precision=HIGHEST)
           + layer["skip_b"])
    return out if last else jax.nn.elu(out)


def logits(params, x, blocks):
    """``blocks`` per layer ``(src, dst, mask, nd)``; -> the last layer's
    ``nd`` rows."""
    h, L = x, len(params["layers"])
    for l, (layer, (src, dst, mask, nd)) in enumerate(
            zip(params["layers"], blocks)):
        h = layer_out(layer, h, src, dst, mask, nd, l == L - 1)
    return h


def loss(params, x, blocks, labels, seed_mask):
    """Mean NLL over the masked seeds, rows ``[0, len(labels))``."""
    lg = logits(params, x, blocks)[:labels.shape[0]]
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, -1),
                               labels[:, None], -1)[:, 0]
    w = seed_mask.astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.maximum(jnp.sum(w), 1.0)


def loss_and_grad(blocks):
    """-> jitted ``(params, x, labels, seed_mask) -> (loss, grads)`` over
    the fixed ``blocks``."""
    return jax.jit(jax.value_and_grad(
        lambda p, x, labels, seed_mask: loss(p, x, blocks, labels,
                                             seed_mask)))


def batch_blocks(b):
    """A sampled batch's blocks as ``(src, dst, mask, nd)``."""
    return [(blk.edge_src, blk.edge_dst, blk.edge_mask, blk.num_dst)
            for blk in b.blocks]


def train(params, steps, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam over ``steps``: each a list of per-worker ``(x, blocks,
    labels, seed_mask)``, losses and gradients averaged over workers.
    -> (per-step mean losses, final params)."""
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    losses = []
    for t, per_worker in enumerate(steps, start=1):
        outs = [loss_and_grad(w[1])(params, w[0], *w[2:])
                for w in per_worker]
        losses.append(float(np.mean([float(o[0]) for o in outs])))
        g = jax.tree.map(lambda *a: sum(a) / len(a), *[o[1] for o in outs])
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
    return losses, params


def runner_case(P, train_per_worker=12, batch=4, fanouts=(2, 2, 2)):
    """The tiny graph partitioned over ``P`` workers with
    ``train_per_worker`` train nodes each (so ``train_per_worker /
    batch`` steps an epoch) and one scheduled epoch per worker.
    -> (graph, partition, schedules)."""
    from repro.core import build_schedule
    from repro.graph import KHopSampler, load_dataset, partition_graph

    g = load_dataset("tiny")
    # load_dataset caches: a copy takes the edited train_mask, so tests
    # that share the cached instance in this process stay unaffected
    g = dataclasses.replace(g, train_mask=g.train_mask.copy())
    pg = partition_graph(g, P, "greedy")
    mask = np.zeros(g.num_nodes, bool)
    for w in range(P):
        mask[np.flatnonzero(pg.owner == w)[:train_per_worker]] = True
    pg.graph.train_mask = mask
    sampler = KHopSampler(g, fanouts=list(fanouts), batch_size=batch)
    schedules = [build_schedule(sampler, pg, worker=w, s0=11, num_epochs=1,
                                n_hot=16) for w in range(P)]
    return g, pg, schedules


def runner_matches_reference(P):
    """Train one epoch of a 3-layer GAT through ``DeviceRapidGNNRunner``
    on ``P`` devices and through ``train``; -> (program losses,
    reference losses, {leaf: (program change norm, reference change
    norm)})."""
    from repro.dist import DeviceRapidGNNRunner, DeviceView, make_mesh
    from repro.models import GNNConfig, init_params
    from repro.train import AdamW

    batch, fanouts, lr = 4, (2, 2, 2), 3e-3
    g, pg, schedules = runner_case(P, batch=batch, fanouts=fanouts)
    cfg = GNNConfig(kind="gat", in_dim=g.feat_dim, hidden_dim=4, heads=2,
                    num_classes=g.num_classes, num_layers=3,
                    fanouts=fanouts)
    p0 = jax.device_get(init_params(cfg, jax.random.key(5)))
    runner = DeviceRapidGNNRunner(schedules, DeviceView.build(pg), cfg,
                                  AdamW(lr=lr),
                                  make_mesh((P,), ("data",)), batch,
                                  g.labels)
    (rep,) = runner.run(params=p0)
    prog = jax.device_get(runner.params)

    steps = []
    for j in range(runner.num_steps):
        per_worker = []
        for ws in schedules:
            b = ws.epoch(0).flat.batch(j)
            lab = np.zeros(batch, np.int32)
            sm = np.zeros(batch, bool)
            lab[:b.seeds.shape[0]] = g.labels[b.seeds]
            sm[:b.seeds.shape[0]] = True
            per_worker.append((jnp.asarray(g.features[b.input_nodes]),
                               batch_blocks(b), jnp.asarray(lab),
                               jnp.asarray(sm)))
        steps.append(per_worker)
    ref_losses, ref = train(p0, steps, lr)

    def norms(a):
        return {f"{l}.{k}": float(np.linalg.norm(
            np.asarray(layer[k], np.float64)
            - np.asarray(p0["layers"][l][k], np.float64)))
            for l, layer in enumerate(a["layers"]) for k in layer}

    n_prog, n_ref = norms(prog), norms(ref)
    return (list(map(float, rep.losses)), ref_losses,
            {k: (n_prog[k], n_ref[k]) for k in n_ref})
