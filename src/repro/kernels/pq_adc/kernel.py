"""Pallas TPU kernel: fused PQ asymmetric-distance scan.

Faiss scans inverted lists scalar-wise (one table lookup per subquantizer
per code).  The TPU adaptation keeps the LUT resident in VMEM and turns
each subquantizer's gather into a one-hot select: the ``(ksub, BLOCK_N)``
mask ``slot == code`` picks one LUT entry per column, and a sublane sum
reduces it.  The sum adds one entry to zeros, so every per-subquantizer
term is the LUT value exactly (no MXU rounding) and only the ``m``-term
accumulation is in f32 arithmetic.

Layouts are lane-dense for the TPU: codes arrive transposed as
``(m, N)`` so a code tile is ``(m, BLOCK_N)``, the LUT arrives as
``(ksub, m)`` so subquantizer ``j`` is a ``(ksub, 1)`` column, and the
output is a ``(1, N)`` row.  All blocks are 2-D, so the kernel also
lowers under ``jax.vmap`` over per-query LUTs (the batch axis becomes a
squeezed leading block dimension) — the way the IVF scan engine calls it.

Grid: (N / BLOCK_N,); the LUT is broadcast to every grid step via a
constant index_map.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["pq_adc_pallas", "BLOCK_N"]

BLOCK_N = 1024


def _adc_kernel(codes_ref, lut_ref, out_ref):
    codes = codes_ref[...]            # (m, BLOCK_N) int32
    lut = lut_ref[...]                # (ksub, m) f32
    ksub, m = lut.shape
    slot = jax.lax.broadcasted_iota(jnp.int32, (ksub, codes.shape[1]), 0)
    acc = jnp.zeros((1, codes.shape[1]), jnp.float32)
    for j in range(m):
        hit = slot == codes[j:j + 1, :]
        acc = acc + jnp.sum(jnp.where(hit, lut[:, j:j + 1], 0.0),
                            axis=0, keepdims=True)
    out_ref[...] = acc


def pq_adc_pallas(codes_t: jnp.ndarray, lut_t: jnp.ndarray,
                  block_n: int = BLOCK_N, *, interpret: bool) -> jnp.ndarray:
    """codes_t (m, N) int32, lut_t (ksub, m) f32 -> (1, N) f32 distances.

    N must be a multiple of block_n (ops.py pads and transposes).
    """
    m, n = codes_t.shape
    ksub = lut_t.shape[0]
    assert n % block_n == 0
    grid = (n // block_n,)
    return pl.pallas_call(
        _adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, block_n), lambda i: (0, i)),
            pl.BlockSpec((ksub, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_n), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(codes_t, lut_t)
