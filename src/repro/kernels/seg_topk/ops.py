"""Jitted wrappers for the segmented top-k select (Pallas + XLA fallback).

Both engines implement one contract::

    seg_topk(dists (NQ, N), lens (NQ,), k) -> (vals (NQ, k) f32 ascending,
                                               idx  (NQ, k) i32)

Row ``i``'s columns at or past ``lens[i]`` count as ``+inf``; selection
order is the lexicographic ``(value asc, column asc)`` minimum, so the
two engines are **bit-identical** for every input — including rows whose
genuine distances are ``+inf`` and rows shorter than ``k`` (slots past
the ``lens[i]`` real candidates come back as ``val=+inf`` pointing at the
lowest masked/padding columns).  Callers that must distinguish a real
``+inf`` hit from padding filter by ``idx < lens[i]`` — that is exactly
what the scan layer's device-select path does.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .._interpret import resolve_interpret
from .kernel import SEG_BLOCK_Q, SEG_CHUNK, seg_topk_pallas

__all__ = ["seg_topk", "seg_topk_xla"]


@functools.partial(jax.jit, static_argnames=("k", "block_q", "interpret"))
def seg_topk(dists, lens, k: int, block_q: int = SEG_BLOCK_Q,
             interpret: bool | None = None):
    """Pallas engine: pad rows/columns to kernel shape, select on device.

    ``interpret=None`` interprets on the CPU backend and compiles
    everywhere else."""
    nq, n = dists.shape
    if nq == 0 or k == 0:
        return (jnp.full((nq, k), jnp.inf, jnp.float32),
                jnp.zeros((nq, k), jnp.int32))
    lens = jnp.minimum(lens.astype(jnp.int32), n)
    n_eff = max(n, k)
    n_eff += (-n_eff) % 128
    chunk = min(n_eff, SEG_CHUNK)
    pad_q = (-nq) % block_q
    pad_n = n_eff + (-n_eff) % chunk - n
    dp = jnp.pad(dists.astype(jnp.float32), ((0, pad_q), (0, pad_n)))
    # padding rows: lens 0, all +inf
    lp = jnp.pad(lens, (0, pad_q))[:, None]
    vals, idx = seg_topk_pallas(dp, lp, k, block_q=block_q, chunk=chunk,
                                interpret=resolve_interpret(interpret))
    return vals[:nq], idx[:nq]


@functools.partial(jax.jit, static_argnames=("k",))
def seg_topk_xla(dists, lens, k: int):
    """XLA engine: ``lax.top_k`` of the negated masked row.

    ``lax.top_k`` breaks value ties (including at ``-inf``) toward the
    lower index, which is the kernel's ``(value, column)`` order exactly.
    """
    nq, n = dists.shape
    if nq == 0 or k == 0:
        return (jnp.full((nq, k), jnp.inf, jnp.float32),
                jnp.zeros((nq, k), jnp.int32))
    lens = jnp.minimum(lens.astype(jnp.int32), n)
    cols = jnp.arange(n, dtype=jnp.int32)[None, :]
    masked = jnp.where(cols < lens[:, None], dists.astype(jnp.float32),
                       jnp.inf)
    if n < k:                                # widen with masked columns
        masked = jnp.pad(masked, ((0, 0), (0, k - n)),
                         constant_values=jnp.inf)
    neg, idx = jax.lax.top_k(-masked, k)
    return -neg, idx.astype(jnp.int32)
