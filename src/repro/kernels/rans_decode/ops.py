"""Jitted wrapper: pads the word stream and returns (rows, L) symbols.

Encode-side counterpart: ``repro.core.vrans.VRans16Encoder`` with a static
quantized pmf (see ``make_tables``).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .._interpret import resolve_interpret
from .kernel import LANES, rans_decode_pallas


def make_tables(freqs: np.ndarray, r: int):
    """freqs (A,) summing to 2^r -> (slot->sym, slot->freq, slot->start)."""
    assert freqs.sum() == (1 << r)
    starts = np.cumsum(freqs) - freqs
    sym_t = np.repeat(np.arange(len(freqs)), freqs).astype(np.int32)
    freq_t = freqs[sym_t].astype(np.int32)
    start_t = starts[sym_t].astype(np.int32)
    return sym_t, freq_t, start_t


@functools.partial(jax.jit, static_argnames=("rows", "r", "interpret"))
def rans_decode(heads, words, sym_t, freq_t, start_t, rows: int, r: int,
                interpret: bool | None = None):
    """Decode rows*L symbols; heads (L,) u32, words (W,) u16/u32.

    ``interpret=None`` interprets on the CPU backend and compiles
    everywhere else."""
    L = heads.shape[0]
    words = jnp.pad(words.astype(jnp.uint32), (0, L))  # slack for masked gathers
    return rans_decode_pallas(
        heads.astype(jnp.uint32), words,
        sym_t.astype(jnp.int32), freq_t.astype(jnp.int32),
        start_t.astype(jnp.int32), rows, r,
        interpret=resolve_interpret(interpret))
