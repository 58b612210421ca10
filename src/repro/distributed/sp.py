"""Sequence-parallel (flash-decoding style) attention for sharded KV caches.

When a decode cell shards the KV cache's *sequence* dim over the "model"
axis (granite/qwen decode_32k, all long_500k cells — see
``cache_shardings``), the reference decode attention makes XLA reduce
softmax statistics across shards op-by-op.  This module gives the explicit
shard_map version: each shard computes attention over its local KV slice
plus (max, sum-exp) statistics; one tiny ``psum`` pair combines them —
the flash-decoding two-pass reduction, with bytes O(B·H) instead of
O(B·H·T).

``sp_decode_attention`` is a drop-in for one-token decode given already-
rotated q and the local cache shard; validated against the dense reference
in tests/test_distributed.py.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["sp_decode_attention"]


def sp_decode_attention(q, k_shard, v_shard, valid_mask, axis: str = "model"):
    """q (B,1,H,D) replicated over ``axis``; k/v (B,T_local,KV,D) = the
    local sequence shard; valid_mask (B,T_local) marks filled slots.

    Returns (B,1,H,D), numerically identical to attention over the full
    gathered cache (up to fp roundoff).  Call inside shard_map with
    in_specs (P(), P(None, axis, None, None), ..., P(None, axis)).
    """
    B, _, H, D = q.shape
    KV = k_shard.shape[2]
    G = H // KV
    qg = q.reshape(B, KV, G, D)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k_shard).astype(jnp.float32)
    s = s / jnp.sqrt(D).astype(jnp.float32)
    neg = jnp.finfo(jnp.float32).min
    s = jnp.where(valid_mask[:, None, None, :], s, neg)
    # local statistics
    m_loc = s.max(axis=-1)                                   # (B,KV,G)
    p = jnp.exp(s - m_loc[..., None])
    l_loc = p.sum(axis=-1)
    o_loc = jnp.einsum("bkgt,btkd->bkgd", p.astype(q.dtype), v_shard)
    # global combine: two scalars per head + one vector — O(B*H*D) bytes
    m_glob = jax.lax.pmax(m_loc, axis)
    scale = jnp.exp(m_loc - m_glob)
    l_glob = jax.lax.psum(l_loc * scale, axis)
    o_glob = jax.lax.psum(o_loc * scale[..., None].astype(o_loc.dtype), axis)
    out = o_glob / jnp.maximum(l_glob, 1e-30)[..., None].astype(o_glob.dtype)
    return out.reshape(B, 1, H, D)


def make_sp_decode(mesh, axis: str = "model"):
    """shard_map wrapper: full-shape (B,1,H,D) q + seq-sharded (B,T,KV,D)."""
    def fn(q, k, v, valid):
        return jax.shard_map(
            lambda q_, k_, v_, m_: sp_decode_attention(q_, k_, v_, m_, axis),
            mesh=mesh,
            in_specs=(P(), P(None, axis, None, None),
                      P(None, axis, None, None), P(None, axis)),
            out_specs=P(),
        )(q, k, v, valid)

    return fn
