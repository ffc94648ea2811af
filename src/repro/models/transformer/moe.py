"""Mixture-of-Experts: top-k router + sort-free capacity dispatch,
expert-parallel over the `model` mesh axis.

RapidGNN tie-in (DESIGN.md §4): MoE dispatch is the transformer's
"remote feature fetch" -- data-dependent sparse access to sharded state.
The deterministic schedule makes per-expert loads enumerable offline, so
capacity C is a *static* bound (the analogue of k_max in the a2a pull)
rather than a runtime reallocation.

Parallel layout: tokens stay sharded over (pod, data); experts are sharded
over `model` (E_local = E / tp per shard). Each model shard routes the
full token set (router weights replicated -- FLOPs are negligible),
dispatches only tokens choosing ITS experts into an (E_local, C, d)
buffer, applies its expert FFNs, and psums partial outputs over `model`.
This trades the classic a2a for one psum of the activations -- the same
volume as a TP FFN -- and is the paper-faithful "cache-local first" shape.
An a2a variant is evaluated in the perf hillclimb (EXPERIMENTS.md §Perf).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.transformer.common import ArchConfig, dense_init


def init_moe_params(cfg: ArchConfig, key: jax.Array,
                    dtype=jnp.float32) -> Dict[str, jax.Array]:
    E, d, ff = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    k1, k2, k3, k4 = jax.random.split(key, 4)
    return {
        "router": dense_init(k1, (d, E), 0, dtype),
        "w1": dense_init(k2, (E, d, ff), 1, dtype),   # gate proj
        "w3": dense_init(k3, (E, d, ff), 1, dtype),   # up proj
        "w2": dense_init(k4, (E, ff, d), 1, dtype),   # down proj
    }


def capacity(cfg: ArchConfig, tokens: int) -> int:
    import math
    c = math.ceil(cfg.top_k * tokens * cfg.capacity_factor
                  / cfg.num_experts)
    return max(c, 4)


def moe_local(params: Dict[str, jax.Array], x: jnp.ndarray,
              cfg: ArchConfig, e_offset: jnp.ndarray | int,
              n_local: int, cap: Optional[int] = None) -> jnp.ndarray:
    """Partial MoE output from experts [e_offset, e_offset+n_local).

    x (T, d) local tokens; expert weights already sliced to n_local.
    Caller psums partials over the expert-parallel axis.
    """
    T, d = x.shape
    E, k = cfg.num_experts, cfg.top_k
    C = cap if cap is not None else capacity(cfg, T)
    act = cfg.activation()

    logits = x.astype(jnp.float32) @ params["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # (T, E)
    top_p, top_e = jax.lax.top_k(probs, k)                   # (T, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    e_flat = top_e.reshape(-1)                               # (T*k,)
    p_flat = top_p.reshape(-1)
    t_flat = jnp.repeat(jnp.arange(T), k)

    e_loc = e_flat - e_offset
    mine = (e_loc >= 0) & (e_loc < n_local)
    key = jnp.where(mine, e_loc, n_local)                    # bucket E_l = drop

    # position of each token within its expert queue (dispatch order)
    onehot = jax.nn.one_hot(key, n_local + 1, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                # exclusive
    pos = jnp.take_along_axis(pos, key[:, None], axis=1)[:, 0]
    keep = mine & (pos < C)

    # scatter tokens into the (E_local, C, d) buffer (dropped -> row E_l)
    be = jnp.where(keep, key, n_local)
    bp = jnp.where(keep, pos, 0)
    buf = jnp.zeros((n_local + 1, C, d), x.dtype)
    buf = buf.at[be, bp].add(x[t_flat])
    buf = buf[:n_local]

    h = jnp.einsum("ecd,edf->ecf", buf, params["w1"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", buf, params["w3"].astype(x.dtype))
    y_e = jnp.einsum("ecf,efd->ecd", act(h) * u,
                     params["w2"].astype(x.dtype))           # (E_l, C, d)

    # combine back to tokens
    y_tok = y_e[jnp.where(keep, key, 0), bp]                 # (T*k, d)
    w = (p_flat * keep).astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[t_flat].add(y_tok * w[:, None])
    return out


def moe_apply(params: Dict[str, jax.Array], x: jnp.ndarray,
              cfg: ArchConfig, mesh=None, dp_spec=None,
              cap: Optional[int] = None) -> jnp.ndarray:
    """x (B, S, d) -> (B, S, d). With a mesh, experts shard over `model`
    via a fully-manual shard_map; without, all experts run locally."""
    B, S, d = x.shape
    x2 = x.reshape(B * S, d)
    if mesh is None or mesh.shape.get("model", 1) == 1:
        out = moe_local(params, x2, cfg, 0, cfg.num_experts, cap=cap)
        return out.reshape(B, S, d)

    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    tp = mesh.shape["model"]
    n_local = cfg.num_experts // tp
    dp = dp_spec if dp_spec is not None else tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    dp_size = 1
    for a in (dp if isinstance(dp, tuple) else (dp,)):
        dp_size *= mesh.shape[a]
    if (B * S) % dp_size != 0:       # e.g. decode with global_batch=1
        dp = None

    if cfg.moe_resident_experts:
        # weight-stationary: experts over `model`, FF over dp; tokens are
        # replicated into the block (the allgather GSPMD inserts is tiny
        # at decode) and FF partials psum over dp. Weights never move.
        def body_ws(router, w1, w2, w3, xl):
            p = {"router": router, "w1": w1[0], "w2": w2[0],
                 "w3": w3[0]}
            off = jax.lax.axis_index("model") * n_local
            out = moe_local(p, xl, cfg, off, n_local, cap=cap)
            axes = ("model",) + ((dp if isinstance(dp, tuple) else (dp,))
                                 if dp else ())
            return jax.lax.psum(out, axes)

        wspec1 = P("model", None, None, dp)    # (tp, E_l, d, ff/dp)
        wspec2 = P("model", None, dp, None)
        out = shard_map(
            body_ws, mesh=mesh,
            in_specs=(P(), wspec1, wspec2, wspec1, P()),
            out_specs=P(),
        )(params["router"],
          params["w1"].reshape(tp, n_local, *params["w1"].shape[1:]),
          params["w2"].reshape(tp, n_local, *params["w2"].shape[1:]),
          params["w3"].reshape(tp, n_local, *params["w3"].shape[1:]),
          x2)
        return out.reshape(B, S, d)

    def body(router, w1, w2, w3, xl):
        p = {"router": router, "w1": w1[0], "w2": w2[0], "w3": w3[0]}
        off = jax.lax.axis_index("model") * n_local
        out = moe_local(p, xl, cfg, off, n_local, cap=cap)
        return jax.lax.psum(out, "model")

    espec = P("model")
    out = shard_map(
        body, mesh=mesh,
        in_specs=(P(), espec, espec, espec, P(dp, None)),
        out_specs=P(dp, None),
    )(params["router"],
      params["w1"].reshape(tp, n_local, *params["w1"].shape[1:]),
      params["w2"].reshape(tp, n_local, *params["w2"].shape[1:]),
      params["w3"].reshape(tp, n_local, *params["w3"].shape[1:]),
      x2)
    return out.reshape(B, S, d)
