"""Seeded SIFT-shaped data: the base and the query pools.

The SIFT1M base itself (ann-benchmarks ``sift-128-euclidean``) is not in
the repository, so rows are drawn from a fixed distribution that holds
the properties an IVF deployment feels:

* a decaying spectrum: the variance of the i-th principal direction
  falls as ``i ** -spectrum_decay`` (SIFT's PCA spectrum decays so, with
  some 15-20 directions holding most of it);
* broad, overlapping clusters: ``components`` Gaussian components whose
  centres spread ``spread`` times as far as their rows, with
  gamma(4)-distributed weights, so k-means lists come out balanced, each
  near ``n / nlist`` rows, and not merged over tight modes;
* a random rotation, so no coordinate is special.

``bench/tests/test_data.py`` holds the shape to that: the imbalance of
the IVF lists, the rows a query's probes cover against
``nprobe * n / nlist``, and recall@10 of IVF-flat.

The distribution (rotation, component centres and weights) comes from
the configuration's ``distribution_seed`` and is the same in every run,
as SIFT's descriptor distribution is.  The base rows are drawn from the
run's ``--seed`` on the device, in one jitted call.  The query pool is
a fixed set drawn from the same distribution (the ``query_seed``
stream), as a benchmark's query file is; a run's ``--seed`` only orders
it.  A traffic mix may weight the components its queries come from
(``topics``): ``data`` draws them as the base does, ``zipf`` ranks the
components by weight and draws the r-th with weight ``r ** -s``.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Distribution", "distribution", "make_base", "query_pool",
           "order"]

HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class Distribution:
    mixing: np.ndarray       # (d, d) f32: rotation times the spectrum
    centres: np.ndarray      # (components, d) f32, before mixing
    weights: np.ndarray      # (components,) f64, sums to 1


def distribution(p: dict) -> Distribution:
    """The fixed distribution of a configuration's ``data`` entry."""
    rng = np.random.default_rng(p["distribution_seed"])
    d = p["d"]
    rot, _ = np.linalg.qr(rng.standard_normal((d, d)))
    scale = np.arange(1, d + 1, dtype=np.float64) ** (-p["spectrum_decay"]
                                                      / 2.0)
    centres = rng.standard_normal((p["components"], d)) * p["spread"]
    w = rng.gamma(4.0, 1.0, size=p["components"])
    return Distribution((rot * scale[None]).astype(np.float32),
                        centres.astype(np.float32), w / w.sum())


def _key(seed: int, stream: int):
    seed %= 1 << 64
    k = jax.random.fold_in(jax.random.key(stream), np.uint32(seed >> 32))
    return jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))


@functools.partial(jax.jit, static_argnames=("n",))
def _draw(key, mixing, centres, cdf, n: int):
    kc, kz = jax.random.split(key)
    which = jnp.searchsorted(cdf, jax.random.uniform(kc, (n,)), side="right")
    which = jnp.minimum(which, centres.shape[0] - 1)
    z = jax.random.normal(kz, (n, mixing.shape[0]), jnp.float32)
    return jnp.matmul(z + centres[which], mixing.T, precision=HI)


def _sample(dist: Distribution, weights: np.ndarray, n: int, seed: int,
            stream: int) -> np.ndarray:
    cdf = np.cumsum(weights / weights.sum()).astype(np.float32)
    out = _draw(_key(seed, stream), jnp.asarray(dist.mixing),
                jnp.asarray(dist.centres), jnp.asarray(cdf), n=n)
    return np.asarray(out, np.float32)


def make_base(p: dict, n: int, seed: int) -> np.ndarray:
    """(n, d) float32 base rows of the distribution ``p``, from ``seed``."""
    dist = distribution(p)
    return _sample(dist, dist.weights, n, seed, 1)


def topic_weights(dist: Distribution, topics: dict) -> np.ndarray:
    """Weight of each component for a traffic mix's ``topics`` entry."""
    kind = topics["kind"]
    if kind == "data":
        return dist.weights
    if kind == "zipf":
        rank = np.empty(len(dist.weights))
        rank[np.argsort(-dist.weights, kind="stable")] = np.arange(
            1, len(dist.weights) + 1)
        return rank ** -float(topics["s"])
    raise ValueError(f"unknown topic distribution {kind!r}")


def query_pool(p: dict, count: int, topics: dict, stream: int) -> np.ndarray:
    """The fixed pool of ``count`` queries of stream ``stream``."""
    dist = distribution(p)
    return _sample(dist, topic_weights(dist, topics), count,
                   p["query_seed"], 2 + stream)


def order(count: int, seed: int) -> np.ndarray:
    """The order in which a run with ``seed`` takes a pool of ``count``."""
    return np.random.default_rng([seed % (1 << 64), 7919]).permutation(count)
