"""Jitted wrapper: packs bits, builds superblock cums, pads queries."""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .._interpret import resolve_interpret
from .kernel import BLOCK_Q, WORDS_PER_SUPER, wt_rank_pallas


def pack_bits_u32(bits: np.ndarray):
    """bits (N,) 0/1 -> (words u32 (W,), super_cum i32 (S,)) little-endian."""
    n = len(bits)
    W = -(-n // 32)
    pad = np.zeros(W * 32, np.uint8)
    pad[:n] = bits
    words = pad.reshape(W, 32).astype(np.uint32)
    words = (words << np.arange(32, dtype=np.uint32)).sum(axis=1, dtype=np.uint32)
    # pad words to a superblock multiple (+1 slack superblock for gathers)
    Wp = (-(-W // WORDS_PER_SUPER) + 1) * WORDS_PER_SUPER
    words = np.concatenate([words, np.zeros(Wp - W, np.uint32)])
    counts = np.bitwise_count(words).astype(np.int64)
    cum = np.concatenate([[0], np.cumsum(counts)])
    super_cum = cum[::WORDS_PER_SUPER][: Wp // WORDS_PER_SUPER + 1].astype(np.int32)
    return words, super_cum


@functools.partial(jax.jit, static_argnames=("interpret",))
def wt_rank(words, super_cum, queries, interpret: bool | None = None):
    """``rank1(i)`` for each query position over the packed bitvector:
    superblock cumulative popcounts + an in-block popcount on device.
    ``interpret=None`` interprets on the CPU backend and compiles
    everywhere else."""
    nq = queries.shape[0]
    pad = (-nq) % BLOCK_Q
    q = jnp.pad(queries.astype(jnp.int32), (0, pad))
    out = wt_rank_pallas(words, super_cum, q,
                         interpret=resolve_interpret(interpret))
    return out[:nq]
