"""Plain reference of the served semantics, independent of ``repro``.

What an IVF deployment promises, written out directly:

* every base row belongs to the list of its nearest centroid;
* a query probes the ``nprobe`` lists whose centroids are nearest,
  nearest first;
* it is answered with the ``k`` candidates of those lists nearest to it:
  by exact squared L2 (flat payload) or by the sum of the query's PQ
  look-up table over each row's nearest codewords (PQ payload); ties go
  to the earlier list in probe order, then the smaller id.

Distances are float64 on the host, whose rounding lies some ten orders
below float32's and so decides nothing here.  The assignment of a million
rows is the one heavy step; it runs on the device in float32 at
``Precision.HIGHEST`` and every row whose two nearest centroids lie
within a float32 rounding band of each other is settled again in float64
on the host.  The centroids and codebooks come from ``bench/train.py``,
never from the program.

``brute_force_topk`` (the ground truth of ``recall_at_10``) follows the
brute force and recall arithmetic of the repository's ``chip_smoke.py``
as it stood when this benchmark was written, kept here so that a change
to the program cannot move the yardstick.

``precision="bfloat16"`` gives the control: the same computation on
inputs rounded to bfloat16 (float32 accumulation, as a bf16 kernel
would), the precision below the configuration's float32.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Reference", "brute_force_topk", "nearest"]

HI = jax.lax.Precision.HIGHEST
BLOCK_ROWS = 1 << 16
BRUTE_BLOCK_Q = 128


def _round(a: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float32":
        return np.asarray(a, np.float32)
    if precision == "bfloat16":
        return np.asarray(a, np.float32).astype(jnp.bfloat16).astype(
            np.float32)
    raise ValueError(f"unknown precision {precision!r}")


@jax.jit
def _top2(x, c):
    d = (jnp.sum(x * x, axis=1, keepdims=True)
         - 2.0 * jnp.matmul(x, c.T, precision=HI)
         + jnp.sum(c * c, axis=1)[None])
    v, i = jax.lax.top_k(-d, 2)
    return -v, i


def nearest(x: np.ndarray, c: np.ndarray) -> np.ndarray:
    """Index of each row's nearest centroid, exact (ties to the lower)."""
    x = np.ascontiguousarray(x, np.float32)
    n = x.shape[0]
    out = np.empty(n, np.int64)
    cd = jnp.asarray(c, jnp.float32)
    unsure = []
    for r0 in range(0, n, BLOCK_ROWS):
        blk = x[r0:r0 + BLOCK_ROWS]
        m = blk.shape[0]
        if m < BLOCK_ROWS:
            blk = np.concatenate([blk, np.zeros((BLOCK_ROWS - m, x.shape[1]),
                                                np.float32)])
        v, i = _top2(jnp.asarray(blk), cd)
        v, i = np.asarray(v)[:m], np.asarray(i)[:m]
        out[r0:r0 + m] = i[:, 0]
        # float32 error of the expanded form, with wide headroom
        band = 1e-4 * (1.0 + np.abs(v[:, 0]) + np.einsum(
            "nd,nd->n", x[r0:r0 + m], x[r0:r0 + m]))
        unsure.append(r0 + np.nonzero(v[:, 1] - v[:, 0] <= band)[0])
    rows = np.concatenate(unsure)
    c64 = np.asarray(c, np.float64)
    cn = np.einsum("kd,kd->k", c64, c64)
    for r0 in range(0, len(rows), 4096):
        r = rows[r0:r0 + 4096]
        x64 = x[r].astype(np.float64)
        d = (np.einsum("nd,nd->n", x64, x64)[:, None] - 2.0 * x64 @ c64.T
             + cn[None])
        out[r] = np.argmin(d, axis=1)
    return out


@functools.partial(jax.jit, static_argnames=("k",))
def _brute_block(q, base, bn, k: int):
    d = bn[None] - 2.0 * jnp.matmul(q, base.T, precision=HI)
    return jax.lax.top_k(-d, k)[1]


def brute_force_topk(base: np.ndarray, queries: np.ndarray,
                     k: int) -> np.ndarray:
    """Exact-precision (f32 HIGHEST) top-``k`` ids over the whole base."""
    bd = jnp.asarray(base, jnp.float32)
    bn = jnp.sum(bd * bd, axis=1)
    out = np.empty((len(queries), k), np.int64)
    for q0 in range(0, len(queries), BRUTE_BLOCK_Q):
        blk = queries[q0:q0 + BRUTE_BLOCK_Q]
        m = blk.shape[0]
        pad = np.zeros((BRUTE_BLOCK_Q, base.shape[1]), np.float32)
        pad[:m] = blk
        out[q0:q0 + m] = np.asarray(_brute_block(jnp.asarray(pad), bd, bn,
                                                 k=k))[:m]
    del bd, bn
    return out


@dataclasses.dataclass
class Reference:
    """The lists, payload and search of one configuration, from scratch."""

    base: np.ndarray                      # (n, d) f32, as the program got it
    centroids: np.ndarray                 # (nlist, d) f32
    nprobe: int
    k: int
    codebooks: Optional[np.ndarray] = None  # (m, ksub, dsub) f32, PQ only
    precision: str = "float32"

    def __post_init__(self):
        self.x = _round(self.base, self.precision)
        self.c = _round(self.centroids, self.precision)
        assign = nearest(self.x, self.c)
        self.order = np.argsort(assign, kind="stable")
        self.sizes = np.bincount(assign, minlength=len(self.c))
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)])
        self.codes = None
        if self.codebooks is not None:
            self.cb = _round(self.codebooks, self.precision)
            m, _, dsub = self.cb.shape
            self.codes = np.stack(
                [nearest(self.x[:, j * dsub:(j + 1) * dsub], self.cb[j])
                 for j in range(m)], axis=1)

    # -- per query ----------------------------------------------------------
    def _acc(self):
        return np.float64 if self.precision == "float32" else np.float32

    def probes(self, q: np.ndarray) -> np.ndarray:
        """The ``nprobe`` nearest lists of each query, nearest first."""
        d = self._dist_matrix(_round(q, self.precision), self.c)
        return np.argsort(d, axis=1, kind="stable")[:, :self.nprobe]

    def _dist_matrix(self, q: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """(nq, n) squared L2, ``|q|^2 - 2 q.r + |r|^2`` in the accumulator
        type (float64 for the reference: exact far past float32's band)."""
        acc = self._acc()
        qq, r = np.asarray(q, acc), np.asarray(rows, acc)
        return (np.einsum("qd,qd->q", qq, qq)[:, None] - 2.0 * (qq @ r.T)
                + np.einsum("nd,nd->n", r, r)[None])

    def lut(self, q: np.ndarray) -> np.ndarray:
        """(m, ksub) PQ look-up table of one query."""
        m, ksub, dsub = self.cb.shape
        q = _round(q[None], self.precision)[0]
        acc = self._acc()
        diff = self.cb.astype(acc) - q.reshape(m, 1, dsub).astype(acc)
        t = np.einsum("mkd,mkd->mk", diff, diff)
        return _round(t, self.precision) if self.precision != "float32" else t

    def distance(self, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
        """The reference's distance of each row ``ids`` to one query."""
        if self.codes is None:
            return self._dist_matrix(_round(q[None], self.precision),
                                     self.x[ids])[0]
        return self._adc(self.lut(q), self.codes[ids])

    @staticmethod
    def _adc(lut: np.ndarray, codes: np.ndarray) -> np.ndarray:
        return lut[np.arange(codes.shape[1])[None], codes].sum(axis=1)

    def search(self, queries: np.ndarray):
        """(ids (nq, k) int64, dists (nq, k)) — -1/inf past the candidates.

        List by list: each probed list is scored once against every query
        that probes it, then each query's candidates are taken in probe
        order, so a stable sort on distance breaks ties by (probe rank, id).
        """
        probes = self.probes(queries)
        nq = len(queries)
        qr = _round(queries, self.precision)
        luts = ([self.lut(q) for q in queries] if self.codes is not None
                else None)
        part = {}
        for lst in np.unique(probes):
            lo, hi = self.offsets[lst], self.offsets[lst + 1]
            if hi == lo:
                continue
            rows = self.order[lo:hi]
            who = np.nonzero((probes == lst).any(axis=1))[0]
            if self.codes is None:
                dm = self._dist_matrix(qr[who], self.x[rows])
                for j, qi in enumerate(who):
                    part[qi, lst] = dm[j]
            else:
                codes = self.codes[rows]
                for qi in who:
                    part[qi, lst] = self._adc(luts[qi], codes)
        ids = np.full((nq, self.k), -1, np.int64)
        dists = np.full((nq, self.k), np.inf)
        for i in range(nq):
            keep = [p for p in probes[i] if (i, p) in part]
            if not keep:
                continue
            d = np.concatenate([part[i, p] for p in keep])
            rows = np.concatenate([self.order[self.offsets[p]:
                                              self.offsets[p + 1]]
                                   for p in keep])
            best = np.argsort(d, kind="stable")[:self.k]
            ids[i, :len(best)] = rows[best]
            dists[i, :len(best)] = d[best]
        return ids, dists
