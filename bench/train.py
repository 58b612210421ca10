"""The benchmark's own training of the coarse centroids and PQ codebooks.

A configuration's quantizers are trained here, from the seed, on a
sample of the base (faiss's rule: at most 256 training points per
centroid), and handed to the program's build.  So the reference in
``bench/reference.py`` holds the same centroids and codebooks without
taking anything the program made.

Lloyd's algorithm on the device, all iterations in one jitted call, with
float32 matmuls at ``Precision.HIGHEST`` (a TPU's default is one bf16
pass).  An empty cluster keeps its previous centroid.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["sample_rows", "kmeans", "pq_codebooks"]

HI = jax.lax.Precision.HIGHEST


def sample_rows(n: int, count: int, seed: int) -> np.ndarray:
    """Sorted indices of ``min(n, count)`` distinct rows drawn from ``seed``."""
    rng = np.random.default_rng([seed, 104729])
    if count >= n:
        return np.arange(n)
    return np.sort(rng.choice(n, size=count, replace=False))


@functools.partial(jax.jit, static_argnames=("k", "iters"))
def _lloyd(x, init_idx, k: int, iters: int):
    c0 = x[init_idx]
    xn = jnp.sum(x * x, axis=1, keepdims=True)

    def step(_, c):
        d = xn - 2.0 * jnp.matmul(x, c.T, precision=HI) \
            + jnp.sum(c * c, axis=1)[None]
        a = jnp.argmin(d, axis=1)
        sums = jax.ops.segment_sum(x, a, num_segments=k)
        cnt = jax.ops.segment_sum(jnp.ones_like(a, jnp.float32), a,
                                  num_segments=k)
        return jnp.where(cnt[:, None] > 0,
                         sums / jnp.maximum(cnt, 1.0)[:, None], c)

    return jax.lax.fori_loop(0, iters, step, c0)


def kmeans(x: np.ndarray, k: int, iters: int, seed: int) -> np.ndarray:
    """(k, d) float32 centroids of ``x`` from ``seed``."""
    rng = np.random.default_rng([seed, 15485863])
    init = np.sort(rng.choice(x.shape[0], size=k, replace=False))
    c = _lloyd(jnp.asarray(x, jnp.float32), jnp.asarray(init), k=k,
               iters=iters)
    return np.asarray(c, np.float32)


def pq_codebooks(x: np.ndarray, m: int, ksub: int, iters: int,
                 seed: int) -> np.ndarray:
    """(m, ksub, d/m) float32 codebooks, one k-means per sub-space."""
    dsub = x.shape[1] // m
    return np.stack([kmeans(np.ascontiguousarray(x[:, j * dsub:(j + 1) * dsub]),
                            ksub, iters, seed + 1 + j) for j in range(m)])
