"""Pallas TPU kernel: batched rank1 queries on a bit-packed vector.

Wavelet-tree select/rank is the paper's full-random-access path (§4.1);
rank over a packed bitvector = superblock prefix + in-range word popcounts.
TPU adaptation: SWAR popcount on uint32 words (the VPU has no popcount
instruction; the standard 4-op bit-slide is used), queries processed as a
(BLOCK_Q,) vector, the <=16 words between superblock boundary and the query
position handled by an unrolled masked loop of vector gathers.

Inputs: words (W,) u32 (packed bits), super (S,) i32 (cumulative ones at
every 16-word boundary), queries (Q,) i32 (bit positions).  Output: (Q,) i32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["wt_rank_pallas", "BLOCK_Q", "WORDS_PER_SUPER"]

BLOCK_Q = 256
WORDS_PER_SUPER = 16


def _popcount32(v: jnp.ndarray) -> jnp.ndarray:
    v = v - ((v >> jnp.uint32(1)) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> jnp.uint32(2)) & jnp.uint32(0x33333333))
    v = (v + (v >> jnp.uint32(4))) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> jnp.uint32(24)


def _rank_kernel(words_ref, super_ref, q_ref, out_ref):
    q = q_ref[...].astype(jnp.int32)                 # (BLOCK_Q,) bit positions
    word_idx = q >> 5
    bit_idx = (q & 31).astype(jnp.uint32)
    sup_idx = word_idx // WORDS_PER_SUPER
    base_word = sup_idx * WORDS_PER_SUPER
    acc = jnp.take(super_ref[...], sup_idx).astype(jnp.uint32)
    words = words_ref[...]
    for j in range(WORDS_PER_SUPER):                 # unrolled masked scan
        w = jnp.take(words, base_word + j)
        full = (base_word + j) < word_idx
        partial = (base_word + j) == word_idx
        pmask = (jnp.uint32(1) << bit_idx) - jnp.uint32(1)
        cnt_full = _popcount32(w)
        cnt_part = _popcount32(w & pmask)
        acc = acc + jnp.where(full, cnt_full, 0) + jnp.where(partial, cnt_part, 0)
    out_ref[...] = acc.astype(jnp.int32)


def wt_rank_pallas(words, super_cum, queries, block_q: int = BLOCK_Q, *,
                   interpret: bool):
    nq = queries.shape[0]
    assert nq % block_q == 0
    W = words.shape[0]
    S = super_cum.shape[0]
    return pl.pallas_call(
        _rank_kernel,
        grid=(nq // block_q,),
        in_specs=[
            pl.BlockSpec((W,), lambda i: (0,)),
            pl.BlockSpec((S,), lambda i: (0,)),
            pl.BlockSpec((block_q,), lambda i: (i,)),
        ],
        out_specs=pl.BlockSpec((block_q,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((nq,), jnp.int32),
        interpret=interpret,
    )(words, super_cum, queries)
