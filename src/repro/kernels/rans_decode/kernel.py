"""Pallas TPU kernel: interleaved-lane rANS decode (32/16 variant).

This is the paper's decode hot-path, re-architected for a vector machine
(DESIGN.md §3.1): L lanes decode one symbol per step in lockstep; the ANS
state vector lives in registers/VMEM; renormalization is branchless —

  * the consume mask is a vector compare (head < 2^16),
  * the words each lane needs are *contiguous and lane-ordered* in the
    stream (proved by the encoder-mirror property), so an exclusive
    prefix-sum over the mask yields each lane's word index — no
    scatter/compaction, one gather per step,
  * the model is a static quantized pmf: three (2^r,) VMEM tables
    (slot->symbol / freq / start) turn Eq. (2)-(3) into gathers + uint32
    multiply-adds.  All arithmetic is 32-bit (head in [2^16, 2^32)) —
    TPUs have no native 64-bit integer datapath.

Grid is 1 program; the step loop is a ``fori_loop`` carrying (heads, ptr).
VMEM: words (W*4) + tables (3 * 2^r * 4) + out (T rows * L * 4); ops.py
bounds W and T so the working set stays a few MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["rans_decode_pallas", "LANES"]

LANES = 128


def _decode_kernel(heads_ref, words_ref, sym_t_ref, freq_t_ref, start_t_ref,
                   out_ref, *, rows: int, r: int):
    mask = jnp.uint32((1 << r) - 1)
    low = jnp.uint32(1 << 16)

    def step(t, carry):
        heads, ptr = carry
        cf = heads & mask                                    # (L,) uint32
        sym = jnp.take(sym_t_ref[...], cf.astype(jnp.int32))     # gathers
        f = jnp.take(freq_t_ref[...], cf.astype(jnp.int32)).astype(jnp.uint32)
        c = jnp.take(start_t_ref[...], cf.astype(jnp.int32)).astype(jnp.uint32)
        heads = f * (heads >> jnp.uint32(r)) + cf - c
        need = heads < low
        # exclusive prefix-sum -> per-lane word index within this step's group
        k = jnp.cumsum(need.astype(jnp.int32)) - need.astype(jnp.int32)
        idx = ptr + k
        w = jnp.take(words_ref[...], idx).astype(jnp.uint32)
        heads = jnp.where(need, (heads << jnp.uint32(16)) | w, heads)
        ptr = ptr + jnp.sum(need.astype(jnp.int32))
        out_ref[pl.ds(t, 1), :] = sym[None, :]
        return heads, ptr

    heads0 = heads_ref[...]
    init = (heads0, jnp.int32(0))
    jax.lax.fori_loop(0, rows, step, init)


def rans_decode_pallas(heads, words, sym_t, freq_t, start_t, rows: int,
                       r: int, *, interpret: bool):
    """heads (L,) u32; words (W,) u32 (16-bit values); tables (2^r,) i32.

    Returns (rows, L) int32 symbols (row-major decode order).
    """
    L = heads.shape[0]
    W = words.shape[0]
    tsz = sym_t.shape[0]
    return pl.pallas_call(
        functools.partial(_decode_kernel, rows=rows, r=r),
        in_specs=[
            pl.BlockSpec((L,), lambda: (0,)),
            pl.BlockSpec((W,), lambda: (0,)),
            pl.BlockSpec((tsz,), lambda: (0,)),
            pl.BlockSpec((tsz,), lambda: (0,)),
            pl.BlockSpec((tsz,), lambda: (0,)),
        ],
        out_specs=pl.BlockSpec((rows, L), lambda: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, L), jnp.int32),
        interpret=interpret,
    )(heads, words, sym_t, freq_t, start_t)
