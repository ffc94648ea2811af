"""GNN production-mesh dry-run: the paper's own workload at pod scale.

Lowers + compiles the device-distributed RapidGNN pipelined epoch
(cache-first a2a feature pull + GraphSAGE train step, 1-step prefetch
overlap) for P = 256 (single pod) or 512 (multi-pod) workers using
ShapeDtypeStruct stand-ins -- no allocation, same contract as the
transformer dry-run.

  PYTHONPATH=src python -m repro.launch.dryrun_gnn [--multi-pod]
"""
import os
import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import GNNConfig
from repro.train.optim import AdamW
from repro.dist.gnn_step import make_ondemand_epoch, make_pipelined_epoch
from repro.dist.mesh import make_mesh
from repro.launch.dryrun import collective_bytes, emulate_host_devices


def specs(P_, S, m_max, edge_max, B, n_per, d, n_hot, k_max, n_classes):
    f32, i32, i64 = jnp.float32, jnp.int32, jnp.int64

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt)

    batches = {
        "input_nodes": sds((S, P_, m_max), i64),
        "labels": sds((S, P_, B), i32),
        "seed_mask": sds((S, P_, B), jnp.bool_),
        "send_ids": sds((S, P_, P_, k_max), i32),
        "send_pos": sds((S, P_, P_, k_max), i32),
        "send_mask": sds((S, P_, P_, k_max), jnp.bool_),
        "edge_src": [sds((S, P_, e), i32) for e in edge_max],
        "edge_dst": [sds((S, P_, e), i32) for e in edge_max],
        "edge_mask": [sds((S, P_, e), jnp.bool_) for e in edge_max],
    }
    table = sds((P_, n_per, d), f32)
    offsets = sds((P_, 1), i32)
    cache_ids = sds((P_, n_hot), i64)
    cache_feats = sds((P_, n_hot, d), f32)
    return table, offsets, cache_ids, cache_feats, batches


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--baseline", action="store_true",
                    help="lower the on-demand (no cache, non-overlapped) "
                         "baseline epoch instead of the pipelined one")
    ap.add_argument("--assemble-backend", default="auto",
                    choices=("auto", "fused", "ref", "staged"),
                    help="feature-assembly path: fused single-pass "
                         "Pallas kernel, jnp fused reference, or the "
                         "legacy staged chain (auto: fused on TPU, "
                         "ref elsewhere)")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()
    emulate_host_devices()
    P_ = 512 if args.multi_pod else 256
    mesh = make_mesh((P_,), ("data",))

    # paper-scale per-worker shapes: OGBN-Papers100M-like partition
    d, B, n_hot, k_max, m_max = 128, 1000, 32768, 4096, 60_000
    n_per, S = 220_000, 8              # nodes/worker, steps (scan dim)
    edge_max = [m_max * 2, B * 25]
    cfg = GNNConfig(kind="sage", in_dim=d, hidden_dim=256, num_classes=172,
                    num_layers=2)
    opt = AdamW(lr=3e-3)

    params_s = jax.eval_shape(
        lambda k: __import__("repro.models.gnn", fromlist=["init_params"]
                             ).init_params(cfg, k), jax.random.key(0))
    opt_s = jax.eval_shape(opt.init, params_s)

    table, offsets, cids, cfeats, batches = specs(
        P_, S, m_max, edge_max, B, n_per, d, n_hot, k_max, 172)

    t0 = time.time()
    with mesh:
        if args.baseline:
            epoch_fn = make_ondemand_epoch(
                cfg, opt, mesh, m_max,
                assemble_backend=args.assemble_backend)
            lowered = jax.jit(epoch_fn).lower(params_s, opt_s, table,
                                              offsets, batches)
        else:
            epoch_fn = make_pipelined_epoch(
                cfg, opt, mesh, m_max,
                assemble_backend=args.assemble_backend)
            lowered = jax.jit(epoch_fn).lower(params_s, opt_s, table,
                                              offsets, cids, cfeats,
                                              batches)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        cl = collective_bytes(compiled.as_text())
    rec = {
        "workload": ("rapidgnn-sage-ondemand" if args.baseline
                     else "rapidgnn-sage"), "workers": P_,
        "mesh": f"{P_} (data)",
        "assemble_backend": args.assemble_backend,
        "compile_s": round(time.time() - t0, 1),
        "memory": {
            "argument_size_bytes": mem.argument_size_in_bytes,
            "temp_size_bytes": mem.temp_size_in_bytes,
        },
        "collectives": cl,
        "per_worker": {"n_per": n_per, "feat_dim": d, "n_hot": n_hot,
                       "k_max": k_max, "m_max": m_max, "batch": B,
                       "steps": S},
    }
    os.makedirs(args.out, exist_ok=True)
    tag = f"rapidgnn_gnn__pod{2 if args.multi_pod else 1}"
    if args.baseline:
        tag += "__ondemand"
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps(rec, indent=1))
    print("GNN production-mesh dry-run OK")


if __name__ == "__main__":
    main()
