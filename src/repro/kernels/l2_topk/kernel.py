"""Pallas TPU kernel: tiled L2 distance + fused arg-top1.

The IVF coarse-probe and k-means assignment hot loop: for a tile of
queries, compute squared L2 distances to all K centroids with one MXU
matmul (||q||^2 - 2 q.c + ||c||^2) and reduce to (argmin, min) without
writing the (BLOCK_Q, K) distance tile to HBM.

Grid: (ceil(NQ / BLOCK_Q),).  Centroids are VMEM-resident across grid
steps (constant index_map): K*d*4 bytes — e.g. 2048 x 128 f32 = 1 MB.
MXU dims: BLOCK_Q x d x K, all multiples of 128 by construction (ops.py
pads d and K).

TPU layout rules shape both kernels: every block is 2-D (Mosaic and XLA
tile rank-1 arrays differently), so the candidate norms are computed in
the kernel from a transposed ``(d, block)`` view — the lane-dense row
``||c||^2`` broadcasts straight against the ``(rows, block)`` dot tile —
and per-query outputs are ``(rows, 1)`` columns.  Dots run at
``Precision.HIGHEST`` (f32 contraction): the scan engines' exact
re-score band (``repro.ann.scan.rescore_eps``) assumes f32 error, which a
one-pass bf16 MXU product would exceed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["l2_top1_pallas", "l2_dist_pallas", "BLOCK_Q", "BLOCK_N"]

BLOCK_Q = 256
BLOCK_N = 512


def _l2_tile(q, c):
    """(rows, d) x (block, d) -> (rows, block) squared L2, f32."""
    ct = c.astype(jnp.float32).T                               # (d, block)
    dots = jnp.dot(q, ct, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    qn = jnp.sum(q * q, axis=1, keepdims=True)                 # (rows, 1)
    cn = jnp.sum(ct * ct, axis=0, keepdims=True)               # (1, block)
    return qn - 2.0 * dots + cn


def _l2_kernel(q_ref, c_ref, idx_ref, val_ref):
    dist = _l2_tile(q_ref[...], c_ref[...])                    # (BLOCK_Q, K)
    m = jnp.min(dist, axis=1, keepdims=True)
    cols = jax.lax.broadcasted_iota(jnp.int32, dist.shape, 1)
    # first column attaining the minimum (argmin's tie rule)
    idx_ref[...] = jnp.min(jnp.where(dist == m, cols, dist.shape[1]),
                           axis=1, keepdims=True)
    val_ref[...] = m


def l2_top1_pallas(queries: jnp.ndarray, centroids: jnp.ndarray,
                   block_q: int = BLOCK_Q, *, interpret: bool):
    """queries (NQ, d), centroids (K, d) -> (argmin (NQ, 1) i32, min (NQ, 1) f32)."""
    nq, d = queries.shape
    k = centroids.shape[0]
    assert nq % block_q == 0
    grid = (nq // block_q,)
    return pl.pallas_call(
        _l2_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i: (i, 0)),
            pl.BlockSpec((k, d), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, 1), jnp.int32),
            jax.ShapeDtypeStruct((nq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(queries, centroids)


def _l2_dist_kernel(q_ref, c_ref, out_ref):
    out_ref[...] = _l2_tile(q_ref[...], c_ref[...])


def l2_dist_pallas(queries: jnp.ndarray, cands: jnp.ndarray,
                   block_q: int = BLOCK_Q, block_n: int = BLOCK_N, *,
                   interpret: bool):
    """queries (NQ, d), cands (N, d) -> full (NQ, N) squared-L2 matrix.

    The batched-IVF scan shape: one query tile against candidate tiles
    gathered from the deduplicated probed clusters.  Unlike
    :func:`l2_top1_pallas` the distance tile IS the output (the scan layer
    does its own per-query masked top-k over a padded candidate block), so
    the grid is 2-D and each step emits a (block_q, block_n) tile.
    """
    nq, d = queries.shape
    n = cands.shape[0]
    assert nq % block_q == 0 and n % block_n == 0
    grid = (nq // block_q, n // block_n)
    return pl.pallas_call(
        _l2_dist_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        interpret=interpret,
    )(queries, cands)
