"""Training launcher.

Two modes:
  * GNN (the paper's workload): ``--workload gnn`` runs the full RapidGNN
    pipeline (schedule -> cache -> prefetch -> train) or the DGL-style
    baseline on a synthetic benchmark graph.
  * LM  (assigned archs):      ``--workload lm --arch <id>`` runs the
    reduced variant of an assigned architecture on synthetic token data
    (CPU-sized end-to-end driver; the full configs are dry-run only).

Examples:
  PYTHONPATH=src python -m repro.launch.train --workload gnn \
      --dataset reddit_sim --system rapidgnn --epochs 5
  PYTHONPATH=src python -m repro.launch.train --workload lm \
      --arch smollm-360m --steps 50
"""
from __future__ import annotations

import argparse
import time

import numpy as np

from repro.compile_cache import enable_compile_cache


def run_gnn(args) -> None:
    import jax
    from repro.graph import load_dataset, partition_graph, KHopSampler
    from repro.core import (build_schedule, ShardedFeatureStore,
                            RapidGNNRunner, BaselineRunner, NetworkModel)
    from repro.models import (GNNConfig, init_params, make_train_step,
                              batch_to_device)
    from repro.train import AdamW, save_checkpoint

    g = load_dataset(args.dataset)
    pg = partition_graph(g, args.workers, args.partition)
    sampler = KHopSampler(g, fanouts=[25, 10], batch_size=args.batch_size)
    ws = build_schedule(sampler, pg, worker=0, s0=args.seed,
                        num_epochs=args.epochs, n_hot=args.n_hot)

    cfg = GNNConfig(kind=args.model, in_dim=g.feat_dim, hidden_dim=256,
                    num_classes=g.num_classes, num_layers=2,
                    fanouts=tuple(sampler.fanouts))
    params = init_params(cfg, jax.random.key(args.seed))
    opt = AdamW(lr=3e-3)
    opt_state = opt.init(params)
    step = make_train_step(cfg, opt)
    state = {"params": params, "opt": opt_state, "hist": []}

    def train_fn(feats, cb):
        batch = batch_to_device(cb, feats)
        state["params"], state["opt"], aux = step(state["params"],
                                                  state["opt"], batch)
        state["hist"].append((float(aux["loss"]), float(aux["acc"])))
        return float(aux["loss"])

    net = NetworkModel(enabled=args.network_model)
    store = ShardedFeatureStore(pg, worker=0, net=net)
    runner_cls = (RapidGNNRunner if args.system == "rapidgnn"
                  else BaselineRunner)
    kw = {"Q": args.Q} if args.system == "rapidgnn" else {}
    runner = runner_cls(ws, store, batch_size=args.batch_size,
                        train_fn=train_fn, **kw)
    t0 = time.time()
    metrics = runner.run()
    wall = time.time() - t0
    tot = metrics.totals()
    print(f"\n== {args.system} on {args.dataset} "
          f"({args.workers}w, batch {args.batch_size}) ==")
    print(f"wall {wall:.1f}s  epochs {args.epochs}  "
          f"final loss {state['hist'][-1][0]:.3f}  "
          f"acc {state['hist'][-1][1]:.3f}")
    for k in ("rpc_count", "remote_bytes", "vector_pull_bytes",
              "hit_rate", "fetch_stall_s", "modeled_net_time_s"):
        v = tot[k]
        print(f"  {k}: {v:.4g}")
    if args.ckpt:
        save_checkpoint(args.ckpt, state["params"],
                        step=len(state["hist"]))
        print("checkpoint saved to", args.ckpt)


def run_lm(args) -> None:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_reduced
    from repro.data.pipeline import synthetic_lm_batches
    from repro.models.transformer import init_params, lm_loss
    from repro.train import AdamW, save_checkpoint
    from functools import partial

    cfg = get_reduced(args.arch)
    params = init_params(cfg, jax.random.key(args.seed))
    opt = AdamW(lr=3e-4, weight_decay=0.01, max_grad_norm=1.0)
    opt_state = opt.init(params)

    @jax.jit
    def step(p, o, b):
        (loss, _), g = jax.value_and_grad(
            lambda pp: lm_loss(cfg, pp, b), has_aux=True)(p)
        p2, o2 = opt.update(g, o, p)
        return p2, o2, loss

    t0 = time.time()
    losses = []
    for i, batch in enumerate(synthetic_lm_batches(
            cfg, batch=args.batch_size, seq=args.seq, steps=args.steps,
            s0=args.seed)):
        params, opt_state, loss = step(params, opt_state, batch)
        losses.append(float(loss))
        if i % 10 == 0:
            print(f"step {i:4d}  loss {losses[-1]:.4f}")
    print(f"\n== lm {args.arch} (reduced) == {args.steps} steps "
          f"in {time.time()-t0:.1f}s; loss {losses[0]:.3f} -> "
          f"{losses[-1]:.3f}")
    assert losses[-1] < losses[0], "training must reduce loss"
    if args.ckpt:
        save_checkpoint(args.ckpt, params, step=args.steps)
        print("checkpoint saved to", args.ckpt)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", choices=["gnn", "lm"], default="gnn")
    # gnn
    ap.add_argument("--dataset", default="ogbn_products_sim")
    ap.add_argument("--system", choices=["rapidgnn", "baseline"],
                    default="rapidgnn")
    ap.add_argument("--model", choices=["sage", "gcn"], default="sage")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--partition", default="metis")
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--n-hot", type=int, default=4096)
    ap.add_argument("--Q", type=int, default=4)
    ap.add_argument("--network-model", action="store_true",
                    help="charge modelled 10GbE time on critical-path fetches")
    # lm
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    # common
    ap.add_argument("--batch-size", type=int, default=1000)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--ckpt", default="")
    args = ap.parse_args()
    enable_compile_cache()
    if args.workload == "gnn":
        run_gnn(args)
    else:
        if args.batch_size == 1000:
            args.batch_size = 8
        run_lm(args)


if __name__ == "__main__":
    main()
