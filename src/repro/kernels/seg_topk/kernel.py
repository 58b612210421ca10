"""Pallas TPU kernel: segmented top-k select over padded distance rows.

The device half of the scan engines' "never ship the ``(qb, C_pad)``
block to the host" contract: each query row holds ``lens[i]`` valid
candidate distances followed by padding, and the kernel reduces the row
to its ``k`` smallest entries *on device*, so only ``(nq, k)`` values and
columns cross to the host.

Selection order is the lexicographic ``(value asc, column asc)`` minimum
— the same order ``jax.lax.top_k`` of the negated row produces (ties,
including ties at ``+inf``, go to the lower column) — so the Pallas
kernel and the XLA fallback in ``ops.py`` are bit-identical, which is
what lets the scan layer swap engines without perturbing results.

Grid: (ceil(NQ / block_q),).  Each step holds one ``(block_q, N)`` row
tile in VMEM and makes ``k`` selections (``jax.lax.fori_loop``).  Because
selections come out in strictly increasing ``(value, column)`` order, the
entries still available are exactly those lexicographically above the
previous pick — so the loop carries only that pick, never a ``(block_q,
N)`` taken-mask (this also excludes already-taken ``+inf`` entries, which
value masking alone could not).  Each selection scans the row in
``chunk``-column slices (an inner ``fori_loop``), keeping the working set
a few vregs wide whatever ``N`` is.  ``k`` is a compile-time constant;
callers bucket it to bound retraces.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["seg_topk_pallas", "SEG_BLOCK_Q", "SEG_CHUNK"]

# rows per grid step: one f32 sublane tile
SEG_BLOCK_Q = 8
# columns scanned per inner step; ops.py pads N to a multiple of it
SEG_CHUNK = 2048


def _seg_topk_kernel(d_ref, len_ref, vals_ref, idx_ref, *, k: int,
                     chunk: int):
    bq, n = d_ref.shape
    ln = len_ref[...]                           # (bq, 1)
    tcol = jax.lax.broadcasted_iota(jnp.int32, (bq, k), 1)
    ccol = jax.lax.broadcasted_iota(jnp.int32, (bq, chunk), 1)

    def pick(t, carry):
        pm, pj, vals, idxs = carry              # previous pick (bq, 1)

        def scan(c, best):
            bm, bj = best
            c0 = pl.multiple_of(c * chunk, chunk)
            cols = ccol + c0
            d = d_ref[:, pl.ds(c0, chunk)].astype(jnp.float32)
            d = jnp.where(cols < ln, d, jnp.inf)
            # still available: lexicographically above the previous pick
            avail = (d > pm) | ((d == pm) & (cols > pj))
            v = jnp.where(avail, d, jnp.inf)
            m = jnp.min(v, axis=1, keepdims=True)
            j = jnp.min(jnp.where(avail & (v == m), cols, n), axis=1,
                        keepdims=True)
            better = (m < bm) | ((m == bm) & (j < bj))
            return jnp.where(better, m, bm), jnp.where(better, j, bj)

        m, j = jax.lax.fori_loop(
            0, n // chunk, scan,
            (jnp.full((bq, 1), jnp.inf, jnp.float32),
             jnp.full((bq, 1), n, jnp.int32)))
        j = jnp.minimum(j, n - 1)               # k > n guard (ops pads n >= k)
        vals = jnp.where(tcol == t, m, vals)
        idxs = jnp.where(tcol == t, j, idxs)
        return m, j, vals, idxs

    init = (jnp.full((bq, 1), -jnp.inf, jnp.float32),
            jnp.full((bq, 1), -1, jnp.int32),
            jnp.full((bq, k), jnp.inf, jnp.float32),
            jnp.zeros((bq, k), jnp.int32))
    _, _, vals, idxs = jax.lax.fori_loop(0, k, pick, init)
    vals_ref[...] = vals
    idx_ref[...] = idxs


def seg_topk_pallas(dists: jnp.ndarray, lens: jnp.ndarray, k: int,
                    block_q: int = SEG_BLOCK_Q, chunk: int = SEG_CHUNK, *,
                    interpret: bool):
    """dists (NQ, N) f32, lens (NQ, 1) i32 -> (vals (NQ, k) f32, idx (NQ, k) i32).

    ``NQ`` must be a ``block_q`` multiple, ``N`` a ``chunk`` multiple and
    ``N >= k`` (``ops.py`` pads all three).  Row ``i``'s columns at or past
    ``lens[i]`` count as ``+inf``.
    """
    nq, n = dists.shape
    assert nq % block_q == 0 and n % chunk == 0 and n >= k
    grid = (nq // block_q,)
    return pl.pallas_call(
        functools.partial(_seg_topk_kernel, k=k, chunk=chunk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_q, n), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
            pl.BlockSpec((block_q, k), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nq, k), jnp.float32),
            jax.ShapeDtypeStruct((nq, k), jnp.int32),
        ],
        interpret=interpret,
    )(dists, lens)
