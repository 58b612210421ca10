"""Jitted public wrapper for the PQ ADC kernel (pads N to the block size)."""

import functools

import jax
import jax.numpy as jnp

from .._interpret import resolve_interpret
from .kernel import BLOCK_N, pq_adc_pallas


@functools.partial(jax.jit, static_argnames=("interpret", "block_n"))
def pq_adc(codes, lut, block_n: int = BLOCK_N, interpret: bool | None = None):
    """codes (N, m) any int dtype, lut (m, ksub) f32 -> (N,) f32.

    N = 0 short-circuits (Pallas grids must be non-empty).
    ``interpret=None`` interprets on the CPU backend and compiles
    everywhere else.
    """
    n = codes.shape[0]
    if n == 0:
        return jnp.zeros((0,), jnp.float32)
    pad = (-n) % block_n
    codes_t = jnp.pad(codes.astype(jnp.int32), ((0, pad), (0, 0))).T
    out = pq_adc_pallas(codes_t, lut.astype(jnp.float32).T, block_n=block_n,
                        interpret=resolve_interpret(interpret))
    return out[0, :n]
