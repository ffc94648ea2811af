"""Sequence-parallel decode attention over the `model` mesh axis.

The KV cache is sharded on its SEQUENCE dim (DESIGN.md §5): each model
shard holds S/tp cache slots, runs flash-decode partials over its slice
(Pallas kernel on TPU, jnp oracle elsewhere), and the (acc, m, l) partials
are psum-combined -- numerically identical to unsharded attention (tested
against the oracle). This is what makes 500k-token caches fit a v5e and
frees GQA kv-head counts from having to divide the TP axis.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.kernels.flash_decode.ops import flash_decode
from repro.kernels.flash_decode.ref import finalize


def sharded_decode_attention(mesh, q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, length: jnp.ndarray, *,
                             attn_softcap: float = 0.0,
                             scale=None) -> jnp.ndarray:
    """q (B,1,H,dh); caches (B,S,kvH,dh) seq-sharded over `model`;
    length (B,) -> (B,1,H,dh)."""
    tp = mesh.shape.get("model", 1)
    S = k_cache.shape[1]
    assert S % tp == 0
    s_local = S // tp
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    # batch may not divide dp (e.g. long_500k global_batch=1): replicate
    B = q.shape[0]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if B % dp_size != 0:
        dp = None

    def body(qb, kb, vb, ln):
        # local shard covers absolute positions [idx*s_local, ...)
        idx = jax.lax.axis_index("model")
        base = idx * s_local

        def one(qi, ki, vi, li):
            # valid count within this shard
            ln_loc = jnp.clip(li - base, 0, s_local)
            acc, m, l = flash_decode(qi, ki, vi, ln_loc,
                                     scale=scale, softcap=attn_softcap)
            return acc, m, l

        acc, m, l = jax.vmap(one)(qb[:, 0], kb, vb, ln)
        m_g = jax.lax.pmax(m, "model")
        w = jnp.exp(m - m_g)
        acc_g = jax.lax.psum(acc * w[..., None], "model")
        l_g = jax.lax.psum(l * w, "model")
        out = jax.vmap(finalize)(acc_g, l_g)
        return out[:, None].astype(qb.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp), P(dp, "model"), P(dp, "model"), P(dp)),
        out_specs=P(dp))(q, k_cache, v_cache, length)
