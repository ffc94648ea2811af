"""A CPU-sized cell for the harness's tests, and the program faults the
check has to catch.

  python -m chipbench.tests._tiny <fault> <workers>

runs that cell through ``harness.run_cell`` with ``fault`` planted in the
program (``none`` for a clean run) and prints the result line. Run it
with ``JAX_PLATFORMS=cpu``, and for four workers with four XLA CPU
devices (``XLA_FLAGS=--xla_force_host_platform_device_count=4``), in a
process of its own: the device count locks when JAX starts.
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))

from chipbench import harness  # noqa: E402

#: the program reads ~1e-7 on the CPU, the control and each fault 1e-3 or
#: more on at least one number at this size
LIMITS = {"loss.1": 1e-5, "loss.2": 1e-5, "loss.3": 1e-5, "grad": 1e-5,
          "update": 1e-5, "blocks": 0}

def tiny_config() -> dict:
    return {"name": "tiny", "model": "sage", "fanouts": [5, 5],
            "hidden_dim": 32, "num_layers": 2, "n_hot": 64, "feat_dim": 32,
            "num_classes": 8, "num_nodes": 1000, "avg_degree": 8.0,
            "train_frac": 0.5, "num_clusters": 8, "zipf_a": 1.0,
            "p_intra": 0.7, "graph_seed": 0,
            "optimizer": {"name": "adamw", "lr": 0.003, "b1": 0.9,
                          "b2": 0.999, "eps": 1e-8, "weight_decay": 0.0}}


def tiny_cell(workers: int) -> "harness.Cell":
    return harness.Cell(
        name=f"tiny.p{workers}", chips=workers, config=tiny_config(),
        traffic={"workers": workers, "partition": "metis",
                 "batch_size": 16, "epochs": 2},
        bounds={"pad_floor": {"m_max": 200, "edge_max": [400, 100],
                              "k_max": 8}, "limits": dict(LIMITS)},
        end_to_end=[{"name": n, "unit": u} for n, u in (
            ("seed_nodes_per_s", "nodes/s"), ("peak_hbm_bytes", "bytes"),
            ("setup_s", "s"))],
        per_layer=[])


def plant(fault: str) -> None:
    """Break the program's timed path underneath the harness."""
    import jax.numpy as jnp
    from repro.dist import gnn_step
    from repro.train import optim

    if fault == "state":            # a step that returns its state as is
        optim.AdamW.update = (lambda self, grads, state, params,
                              lr_scale=1.0: (params, state))
    elif fault == "half":           # half of each batch left out
        orig = gnn_step.loss_fn

        def half(cfg, params, feats, es, ed, em, labels, seed_mask):
            keep = jnp.arange(seed_mask.shape[0]) < seed_mask.sum() // 2
            return orig(cfg, params, feats, es, ed, em, labels,
                        seed_mask & keep)
        gnn_step.loss_fn = half
    elif fault == "answer":         # one leaf's update applied twice
        orig_u = optim.AdamW.update

        def doubled(self, grads, state, params, lr_scale=1.0):
            new, st = orig_u(self, grads, state, params, lr_scale)
            old = params["layers"][0]["w_neigh"]
            new["layers"][0]["w_neigh"] = old + 2 * (
                new["layers"][0]["w_neigh"] - old)
            return new, st
        optim.AdamW.update = doubled
    elif fault == "exchange":       # the all_to_all pull left out
        orig_p = gnn_step.pull_shard

        def no_pull(*args, **kw):
            return jnp.zeros_like(orig_p(*args, **kw))
        gnn_step.pull_shard = no_pull
    elif fault == "sample":         # a sampled source that is no neighbour
        from repro.graph import KHopSampler

        orig_s = KHopSampler.sample_epoch_batched

        def bad_sources(self, *args, **kw):
            f = orig_s(self, *args, **kw)
            ok = f.edge_mask[0]
            f.edge_src[0][ok] = (f.edge_src[0][ok] + 1) % 3
            return f
        KHopSampler.sample_epoch_batched = bad_sources
    elif fault != "none":
        raise ValueError(fault)


def main(argv=None) -> int:
    fault, workers = (argv or sys.argv[1:])[:2]
    plant(fault)
    t0 = time.perf_counter()
    result = harness.run_cell(
        tiny_cell(int(workers)), 2 ** 33 + 11, 0.2, False,
        {"platform": "cpu", "kind": "cpu", "count": int(workers)}, t0,
        log=lambda m: print(m, file=sys.stderr))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
