"""Jitted wrappers: pad queries/candidates to block sizes, d/K to MXU sizes."""

import functools

import jax
import jax.numpy as jnp

from .._interpret import resolve_interpret
from .kernel import BLOCK_N, BLOCK_Q, l2_dist_pallas, l2_top1_pallas


@functools.partial(jax.jit, static_argnames=("interpret", "block_q"))
def l2_top1(queries, centroids, block_q: int = BLOCK_Q,
            interpret: bool | None = None):
    """Nearest centroid per query: ``(argmin (nq,) i32, min_d (nq,) f32)``
    over squared L2, padded to kernel block shapes.  ``interpret=None``
    interprets on the CPU backend and compiles everywhere else."""
    nq, d = queries.shape
    k = centroids.shape[0]
    if nq == 0 or k == 0:
        return (jnp.zeros((nq,), jnp.int32),
                jnp.full((nq,), jnp.inf, jnp.float32))
    pad_q = (-nq) % block_q
    pad_d = (-d) % 128
    pad_k = (-k) % 128
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, pad_d)))
    # padded centroids must not win the argmin: push them to +inf distance
    cp = jnp.pad(centroids.astype(jnp.float32), ((0, pad_k), (0, pad_d)))
    if pad_k:
        cp = cp.at[k:, 0].set(3e18)
    idx, val = l2_top1_pallas(qp, cp, block_q=block_q,
                              interpret=resolve_interpret(interpret))
    return idx[:nq, 0], val[:nq, 0]


@functools.partial(jax.jit,
                   static_argnames=("interpret", "block_q", "block_n"))
def l2_dist(queries, cands, block_q: int = BLOCK_Q, block_n: int = BLOCK_N,
            interpret: bool | None = None):
    """queries (NQ, d), cands (N, d) -> (NQ, N) f32 squared L2 distances.

    Zero-pads d (distance-preserving) and both row counts to block
    multiples; padded rows/columns are sliced off, so callers never see
    them.  NQ = 0 or N = 0 short-circuits to an empty result (Pallas grids
    must be non-empty).  ``interpret=None`` interprets on the CPU backend
    and compiles everywhere else.
    """
    nq, d = queries.shape
    n = cands.shape[0]
    if nq == 0 or n == 0:
        return jnp.zeros((nq, n), jnp.float32)
    pad_q = (-nq) % block_q
    pad_n = (-n) % block_n
    pad_d = (-d) % 128
    qp = jnp.pad(queries.astype(jnp.float32), ((0, pad_q), (0, pad_d)))
    cp = jnp.pad(cands.astype(jnp.float32), ((0, pad_n), (0, pad_d)))
    out = l2_dist_pallas(qp, cp, block_q=block_q, block_n=block_n,
                         interpret=resolve_interpret(interpret))
    return out[:nq, :n]
