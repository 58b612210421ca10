"""Batched compressed-IVF scan engine — the paper's §4.1 at batch scale.

``IVFIndex.search_ref`` scans one query and one probed cluster at a time in
Python; fine as a correctness oracle, useless for throughput and for
measuring the paper's headline claim (id compression costs *no* search
runtime).  This module is the batched replacement, the blocked-scan layer
Faiss and Zoom get their throughput from:

1. **Coarse probe** for the whole query batch at once (one distance matrix
   against the centroids, shared with the oracle so probe sets are
   bit-identical).
2. **Cluster dedup + arena gather**: the union of probed clusters across a
   query block is gathered once into a contiguous "arena" of vectors / PQ
   codes (each cluster appears once however many queries probe it).  The
   gather runs on the device, from the index's payload, which the first
   search places in HBM (:class:`repro.ann.payload.PayloadStore`): only
   the arena's int32 row numbers cross to the device per block.
3. **Blocked scoring** of the query block against the arena through the
   Pallas kernels (``l2_dist`` / ``pq_adc``; interpret-mode on CPU) or a
   pure-XLA fallback — both jitted once per bucketed shape.
4. **Exact top-k**: the short-list within the kernel-error band of the
   (topk + ``RESCORE_SLACK``)-th best kernel distance is re-scored with
   the *same numpy scalar path the oracle uses*, so returned ids **and
   distances** are bit-identical to ``search_ref`` (kernel float error
   only reorders the short-list, never the result); the short-lists'
   rows come back from the device in one copy a block.  The short-list is
   cut either host-side (a stable masked argsort over the pulled
   ``(qb, C_pad)`` block) or **device-side** (``select="device"``): a
   jitted candidate gather + segmented top-k (``repro.kernels.seg_topk``)
   runs on device and only ``(qb, K)`` shortlist values/offsets cross to
   the host — never the padded block (``stats.host_block_bytes`` /
   ``stats.device_select`` are the ledger).  Both cuts produce the same
   short-list *set*, so results are bit-identical across
   ``select`` × ``engine``.
5. **Vectorized late id resolution** (§4.1): the winning ``(cluster,
   offset)`` pairs of all queries are resolved in one pass — per-cluster
   decode through an LRU :class:`DecodedListCache` for stream codecs
   (ROC/gap-ANS), random ``access`` for EF/compact/uncompressed, ``select``
   for wavelet trees.  Each needed cluster is decoded at most once per
   batch (and usually zero times once the cache is warm).

Batching contract: results are a pure function of (index, queries, nprobe,
topk) — independent of ``query_block``, engine choice, and cache state.
Only the stats differ.
"""

from __future__ import annotations

import contextlib
import functools
import time
from collections import OrderedDict
from typing import Callable, Dict, List

import numpy as np

from .stats import SearchStats, span

__all__ = [
    "batched_search",
    "batched_flat_search",
    "MERGE_KEY_PAD",
    "coarse_probes",
    "select_topk",
    "score_rows_flat",
    "resolve_ids_batch",
    "rescore_eps",
    "pack_merge_keys",
    "DecodedListCache",
    "CacheOwnerMixin",
]

# extra short-list entries re-scored exactly: kernel scoring only has to get
# the top-k *set* right up to this slack, never the exact float ordering.
RESCORE_SLACK = 8
# shortlist columns whose payload rows ride with each device-select run:
# a query's band (``_shortlist``) rarely holds more than topk +
# RESCORE_SLACK candidates; a block where one holds more pulls its rows
# after the cut instead
SHORTLIST_ROWS = 32
DEFAULT_QUERY_BLOCK = 64
# select="auto" tile gate (the kernel_min analogue): on CPU the host numpy
# select competes with an interpreted/jitted device select plus its dispatch,
# so only candidate rows at least this wide take the device path; off-CPU
# auto always selects on device.
SELECT_MIN_CPU = 4096


# ---------------------------------------------------------------------------
# shared numpy primitives (used by BOTH search_ref and the batched engine so
# parity is by construction)
# ---------------------------------------------------------------------------

def coarse_probes(queries: np.ndarray, centroids: np.ndarray,
                  nprobe: int) -> np.ndarray:
    """(nq, min(nprobe, nlist)) probed clusters, nearest first, stable ties."""
    qc = (
        np.sum(queries**2, 1, keepdims=True)
        - 2.0 * queries @ centroids.T
        + np.sum(centroids**2, 1)[None]
    )
    nprobe = min(nprobe, centroids.shape[0])
    return np.argsort(qc, axis=1, kind="stable")[:, :nprobe]


def select_topk(d: np.ndarray, topk: int) -> np.ndarray:
    """Indices of the ``topk`` smallest entries, ties to the earlier index."""
    return np.argsort(d, kind="stable")[: min(topk, d.shape[0])]


def score_rows_flat(rows: np.ndarray, q: np.ndarray) -> np.ndarray:
    """Squared L2 of each row to ``q`` — the oracle's scalar scoring path."""
    diff = rows - q[None]
    return np.einsum("nd,nd->n", diff, diff)


def rescore_eps(d: int, bound: float, qn: float, factor: float = 16.0) -> float:
    """Error band of the kernels' expanded ``qn - 2qc + cn`` f32 scoring.

    The expanded form cancels catastrophically for near-duplicate vectors,
    so kernel distances near a decision ``bound`` may be mis-ranked by up
    to the cancellation error; exact decisions must re-score everything
    within this band.  ``factor`` carries headroom over the d-term f32
    contraction bound — too wide only re-scores a few extra rows, never
    breaks parity.  Shared by the IVF shortlist extension and the graph
    engine's beam-admission pruning so both use one audited bound.
    """
    scale = 1.0 + abs(float(bound)) + float(qn)
    return factor * d * float(np.finfo(np.float32).eps) * scale


def _shortlist(select, nvalid: np.ndarray, topk: int, d: int,
               qn: np.ndarray, width: int):
    """The re-score shortlist of one query block: the one band decision.

    Kernel distances only have to get the top-k *set* right.  The
    expanded ``qn - 2qc + cn`` form cancels catastrophically for
    near-duplicate vectors, so row ``i`` keeps every candidate within
    ``rescore_eps`` of its ``take``-th smallest kernel distance,
    ``take = min(topk + RESCORE_SLACK, nvalid[i])``, and the exact
    re-score sees every potential top-k member.

    ``select(K)`` returns a tuple whose first two entries are the block's
    kernel distances, ascending along each row, and their candidate
    columns, both ``(qb, K')``; further entries ride along (the device
    select's arena positions and payload rows).  ``nvalid[i]`` counts row ``i``'s real
    candidates (columns at or past it are padding) and ``qn[i]`` is its
    query norm (0 for PQ).  While a row with more than ``K'`` candidates
    still has its ``K'``-th value inside its band, ``K`` doubles, up to
    ``width``, and ``select`` runs again; a ``select`` that returns
    whole sorted rows never runs twice.

    Returns ``(out, thr, cut)``: the last ``select`` output, the
    per-row thresholds (``-inf`` for an empty row), and ``cut(i)``,
    the indices into row ``i`` of ``out`` inside its band, padding
    dropped (a real ``+inf`` keeps its column), in candidate order.
    """
    take = np.minimum(topk + RESCORE_SLACK, nvalid)
    K = min(_bucket(int(take.max(initial=0)), floor=16), width)
    while True:
        out = select(K)
        vals = out[0]
        k_sel = vals.shape[1]
        thr = np.full(len(nvalid), -np.inf)
        retry = False
        for i in np.flatnonzero(take):
            bound = float(vals[i, take[i] - 1])
            thr[i] = bound + rescore_eps(d, bound, float(qn[i]))
            if nvalid[i] > k_sel and vals[i, k_sel - 1] <= thr[i]:
                retry = True            # the band may reach past the cut
        if not retry or K >= width:
            break
        K = min(2 * K, width)

    def cut(i: int) -> np.ndarray:
        cols = out[1][i]
        cnt = int(np.searchsorted(vals[i], thr[i], side="right"))
        kept = np.flatnonzero(cols[:cnt] < nvalid[i])
        return kept[np.argsort(cols[kept])]

    return out, thr, cut


# ---------------------------------------------------------------------------
# decoded-list LRU cache
# ---------------------------------------------------------------------------

class DecodedListCache:
    """Byte-budgeted cache over decoded id lists, LRU or 2Q.

    ``resolve_ids`` used to rebuild its decode cache per call; this one
    lives on the index, so a warm serving loop decodes each hot cluster
    once, not once per request batch.

    ``policy="lru"`` (default) is plain recency eviction.  ``policy="2q"``
    is a segmented LRU: first touch lands an entry in a *probation*
    segment, a second touch promotes it to a *protected* segment (capped
    at ``HOT_FRACTION`` of the budget, demoting its own LRU tail back to
    probation), and eviction always drains probation first — so a scan
    over many cold clusters can no longer flush the clusters that skewed
    query traffic keeps hot.

    Keys are any hashables: the IVF path uses ``(epoch, cluster)`` pairs,
    the graph path uses node ids — appends create fresh keys and never
    alias warm ones, so ingest needs no cache invalidation at all (only
    compaction, which renumbers epochs, calls :meth:`clear`).

    It is also where id resolution is timed: ``decode_s`` / ``decode_ids``
    for the decodes of :meth:`get` (span ``ids.decode``), and
    ``wt_select_s`` / ``wt_select_ids`` for the wavelet-tree selects of
    :meth:`select` (span ``ids.wt_select``), which cache nothing.
    """

    HOT_FRACTION = 0.75

    def __init__(self, max_bytes: int = 64 << 20, policy: str = "lru"):
        if policy not in ("lru", "2q"):
            raise ValueError(f"unknown cache policy {policy!r} "
                             "(options: lru, 2q)")
        self.max_bytes = int(max_bytes)
        self.policy = policy
        self._lists: "OrderedDict[object, np.ndarray]" = OrderedDict()
        self._hot: "OrderedDict[object, np.ndarray]" = OrderedDict()
        self._hot_bytes = 0
        self.bytes = 0
        self.hits = 0
        self.decodes = 0
        self.decode_s = 0.0            # lifetime seconds inside decode()
        self.decode_ids = 0            # lifetime ids those decodes produced
        self.wt_select_s = 0.0         # lifetime seconds inside select()
        self.wt_select_ids = 0         # lifetime ids those selects resolved
        self.evictions = 0
        self.promotions = 0

    def __len__(self) -> int:
        return len(self._lists) + len(self._hot)

    def _evict(self) -> None:
        # probation (or the sole LRU segment) drains first; the protected
        # segment is only touched once probation is empty
        while self.bytes > self.max_bytes and len(self) > 1:
            if self._lists:
                _, old = self._lists.popitem(last=False)
            else:
                _, old = self._hot.popitem(last=False)
                self._hot_bytes -= old.nbytes
            self.bytes -= old.nbytes
            self.evictions += 1

    def _shrink_hot(self) -> None:
        cap = self.HOT_FRACTION * self.max_bytes
        while self._hot_bytes > cap and len(self._hot) > 1:
            key, old = self._hot.popitem(last=False)
            self._hot_bytes -= old.nbytes
            self._lists[key] = old          # demote to probation MRU

    def get(self, key, decode: Callable[[], np.ndarray]) -> np.ndarray:
        hot = self._hot.get(key)
        if hot is not None:
            self._hot.move_to_end(key)
            self.hits += 1
            return hot
        hit = self._lists.get(key)
        if hit is not None:
            self.hits += 1
            if self.policy == "2q":
                del self._lists[key]        # second touch: promote
                self._hot[key] = hit
                self._hot_bytes += hit.nbytes
                self.promotions += 1
                self._shrink_hot()
            else:
                self._lists.move_to_end(key)
            return hit
        t0 = time.perf_counter()
        with span("ids.decode"):
            arr = np.asarray(decode())
        self.decode_s += time.perf_counter() - t0
        self.decode_ids += len(arr)
        self.decodes += 1
        self._lists[key] = arr
        self.bytes += arr.nbytes
        self._evict()
        return arr

    def select(self, tree, ks: np.ndarray, occs: np.ndarray) -> np.ndarray:
        """``tree.select_batch(ks, occs)``, timed and counted.

        A wavelet tree resolves each ``(cluster, offset)`` pair by select
        and decodes no list, so nothing is cached; the call runs under the
        span ``ids.wt_select`` and adds to ``wt_select_s`` and
        ``wt_select_ids``.
        """
        t0 = time.perf_counter()
        with span("ids.wt_select"):
            ids = tree.select_batch(ks, occs)
        self.wt_select_s += time.perf_counter() - t0
        self.wt_select_ids += len(ids)
        return ids

    def invalidate(self, key) -> None:
        """Drop one entry (not counted as an eviction); no-op if absent."""
        old = self._lists.pop(key, None)
        if old is None:
            old = self._hot.pop(key, None)
            if old is not None:
                self._hot_bytes -= old.nbytes
        if old is not None:
            self.bytes -= old.nbytes

    def clear(self) -> None:
        self._lists.clear()
        self._hot.clear()
        self._hot_bytes = 0
        self.bytes = 0

    def set_budget(self, max_bytes: int) -> None:
        """Change the byte budget, evicting entries down to it."""
        self.max_bytes = int(max_bytes)
        self._evict()
        if self.policy == "2q":
            self._shrink_hot()

    def stats(self) -> Dict[str, float]:
        out = {
            "entries": len(self),
            "bytes": self.bytes,
            "hits": self.hits,
            "decodes": self.decodes,
            "decode_s": self.decode_s,
            "decode_ids": self.decode_ids,
            "evictions": self.evictions,
        }
        if self.policy == "2q":
            out["promotions"] = self.promotions
            out["protected_entries"] = len(self._hot)
        return out


class CacheOwnerMixin:
    """Cache plumbing shared by ``IVFIndex`` and ``GraphIndex``.

    Builds the :class:`DecodedListCache` from the owner's declared
    ``cache_bytes`` / ``cache_policy`` fields, and re-attaches one on
    unpickle (``__setstate__``) so indexes pickled before the cache —
    or before the ``cache_policy`` field — existed keep working without
    per-access ``hasattr`` checks.
    """

    def _new_cache(self) -> DecodedListCache:
        budget = getattr(self, "cache_bytes", None)
        policy = getattr(self, "cache_policy", None) or "lru"
        if budget is not None:
            return DecodedListCache(max_bytes=int(budget), policy=policy)
        return DecodedListCache(policy=policy)

    @property
    def decoded_cache(self) -> DecodedListCache:
        return self._decoded_cache

    def __getstate__(self) -> dict:
        state = dict(self.__dict__)
        state.pop("_decoded_cache", None)   # transient derived state
        return state

    def __setstate__(self, state: dict) -> None:
        self.__dict__.update(state)
        if "_decoded_cache" not in self.__dict__:
            self._decoded_cache = self._new_cache()


# ---------------------------------------------------------------------------
# vectorized late id resolution (§4.1)
# ---------------------------------------------------------------------------

def resolve_ids_batch(index, clusters: np.ndarray,
                      offsets: np.ndarray) -> np.ndarray:
    """Resolve all ``(cluster, offset)`` pairs in one pass.

    Offsets are positions in the logical (all-epochs) cluster list; the
    index's :class:`repro.core.epoch.EpochStore` routes each pair to its
    epoch and resolves it there — stream codecs (ROC/gap-ANS) decode each
    distinct ``(epoch, cluster)`` at most once per call through the
    index's :class:`DecodedListCache`; EF/compact/uncompressed use random
    access; wavelet trees use ``select``.
    """
    return index._ids.resolve(clusters, offsets, index.decoded_cache)


# ---------------------------------------------------------------------------
# jitted scoring backends
# ---------------------------------------------------------------------------

def _bucket(n: int, floor: int = 1024) -> int:
    """Next power-of-two >= n (floored) — bounds jit retraces per shape."""
    b = floor
    while b < n:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _jax():
    import jax  # deferred so numpy-only use of the index never imports jax

    return jax


def _l2_xla(q, a):
    """``(qb, d) x (n, d) -> (qb, n)`` expanded squared L2 in plain XLA.

    The dot runs at ``Precision.HIGHEST``: a TPU's default f32 matmul is
    one bf16 pass, whose error would exceed the ``rescore_eps`` band the
    exact re-score relies on (the CPU ignores the flag).
    """
    jnp = _jax().numpy
    qn = jnp.sum(q * q, axis=1, keepdims=True)
    an = jnp.sum(a * a, axis=1)
    dots = jnp.matmul(q, a.T, precision=_jax().lax.Precision.HIGHEST)
    return qn - 2.0 * dots + an[None]


def _gather_rows(payload, rows):
    """``payload[rows]`` on the device: a block's arena, or the rows its
    exact re-score reads.  Every row number is in range by construction
    (padding slots hold row 0, which no candidate column maps to)."""
    return payload.at[rows].get(mode="promise_in_bounds")


@functools.lru_cache(maxsize=None)
def _row_puller():
    """Jitted :func:`_gather_rows`: the rows of a block's cut in one pull
    (after a host select, or a band wider than ``SHORTLIST_ROWS``)."""
    return _jax().jit(_gather_rows)


# The IVF scorers take the device-resident payload and the block's
# padded arena rows, gather the arena and score it in one program.

@functools.lru_cache(maxsize=None)
def _flat_scorers():
    jax, jnp = _jax(), _jax().numpy

    @functools.partial(jax.jit, static_argnames=("interpret",))
    def pallas(q, payload, rows, interpret):
        from ..kernels.l2_topk import l2_dist

        return l2_dist(q, _gather_rows(payload, rows), interpret=interpret)

    @jax.jit
    def xla(q, payload, rows):
        return _l2_xla(q, _gather_rows(payload, rows))

    return {"pallas": pallas, "xla": xla}


@functools.lru_cache(maxsize=None)
def _adc_scorers():
    jax, jnp = _jax(), _jax().numpy

    @functools.partial(jax.jit, static_argnames=("interpret",))
    def pallas(luts, payload, rows, interpret):
        from ..kernels.pq_adc import pq_adc

        # vmap over per-query LUTs; codes (the arena) are shared.
        codes = _gather_rows(payload, rows)
        return jax.vmap(
            lambda lut: pq_adc(codes, lut, interpret=interpret)
        )(luts)

    @jax.jit
    def xla(luts, payload, rows):
        codes = _gather_rows(payload, rows)
        m = codes.shape[1]
        sub = jnp.arange(m)[None, :]

        # sequential over queries: keeps peak memory at one (U, m) gather
        # instead of materializing the (QB, U, m) cube.
        def one(lut):
            return lut[sub, codes].sum(axis=1).astype(jnp.float32)

        return jax.lax.map(one, luts)

    return {"pallas": pallas, "xla": xla}


@functools.lru_cache(maxsize=None)
def _device_selector():
    """Jitted candidate gather + segmented top-k, fused on device.

    From the tiny per-block metadata (probed clusters per query, arena
    span start/size per cluster) the candidate->arena-position map is
    recomputed on device, the scored block is gathered in place, and the
    segmented top-k (``repro.kernels.seg_topk``) cuts each row to its
    ``k`` smallest ``(value, column)`` pairs — so the ``(qb, C_pad)``
    distance block never crosses the device boundary; only ``(qb, k)``
    values, candidate columns and arena positions return to the host,
    with the payload rows (``payload[arena_rows[pos]]``) of each row's
    first ``min(k, SHORTLIST_ROWS)`` columns for the exact re-score.

    The map is a dense compare over the P probes, with no search loop and
    no per-column gather: probe ``j`` owns columns ``[lo_j, cum_j)`` of
    its row (the host's ``_spans_concat`` order; a zero-size probe owns
    none), and a column's arena position is the column plus its owner's
    shift ``start_of[probes_j] - lo_j``.  Padding columns, past the row's
    total, own nothing: they are masked to ``+inf`` and the host drops
    their positions.  The only gather over ``(qb, C_pad)`` is the
    distance gather from ``dmat``.
    """
    jax, jnp = _jax(), _jax().numpy

    @functools.partial(jax.jit,
                       static_argnames=("c_pad", "k", "engine", "interpret"))
    def run(dmat, probes, start_of, size_of, payload, arena_rows, c_pad, k,
            engine, interpret):
        from ..kernels.seg_topk import seg_topk, seg_topk_xla

        pp = size_of[probes]                       # (qb_pad, P)
        cum = jnp.cumsum(pp, axis=1)
        lo = cum - pp                              # probe j's first column
        delta = start_of[probes] - lo              # arena pos - column
        col = jnp.arange(c_pad, dtype=jnp.int32)
        own = (lo[:, :, None] <= col) & (col < cum[:, :, None])
        pos = col + jnp.sum(jnp.where(own, delta[:, :, None], 0), axis=1)
        total = cum[:, -1][:, None]
        valid = col[None, :] < total
        pos = jnp.clip(pos, 0, dmat.shape[1] - 1).astype(jnp.int32)
        d = jnp.where(valid, jnp.take_along_axis(dmat, pos, axis=1),
                      jnp.inf)
        lens = jnp.minimum(total[:, 0], c_pad).astype(jnp.int32)
        if engine == "pallas":
            vals, cols = seg_topk(d, lens, k, interpret=interpret)
        else:
            vals, cols = seg_topk_xla(d, lens, k)
        pos_sel = jnp.take_along_axis(pos, cols, axis=1)
        rows_sel = _gather_rows(payload, _gather_rows(
            arena_rows, pos_sel[:, :min(k, SHORTLIST_ROWS)]))
        return vals, cols, pos_sel, rows_sel

    return run


def _resolve_select(select: str, c_pad: int, select_min: int) -> bool:
    """True when this block's top-k runs on device (see ``batched_search``)."""
    if select == "host":
        return False
    if select == "device":
        return True
    if select != "auto":
        raise ValueError(f"unknown select mode {select!r} "
                         "(options: auto, host, device)")
    return c_pad >= select_min


def _resolve_engine(engine: str) -> str:
    if engine == "auto":
        # interpret-mode Pallas is a correctness path, not a fast path:
        # on CPU the plain-XLA scorer is the performant batched fallback.
        return "pallas" if _jax().default_backend() != "cpu" else "xla"
    if engine not in ("pallas", "xla"):
        raise ValueError(f"unknown scan engine {engine!r}")
    return engine


# ---------------------------------------------------------------------------
# the batched search
# ---------------------------------------------------------------------------

def _spans_concat(starts: np.ndarray, lens: np.ndarray) -> np.ndarray:
    """concat(arange(s, s+l) for s, l in zip(starts, lens)) without a loop."""
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    cum = np.cumsum(lens) - lens
    idx = np.arange(total, dtype=np.int64)
    return np.repeat(starts - cum, lens) + idx


MERGE_KEY_PAD = np.uint64(np.iinfo(np.uint64).max)

# merge-key layout: (probe_rank << 40) | in-cluster offset.  40 offset bits
# cap any single cluster at 2^40 rows; the remaining 24 rank bits cap nprobe
# at 2^24.  Both are astronomically past realistic shapes, but a silent
# wrap would corrupt the sharded merge order, so packing checks explicitly.
MERGE_KEY_OFFSET_BITS = 40
MERGE_KEY_RANK_BITS = 64 - MERGE_KEY_OFFSET_BITS


def pack_merge_keys(ranks: np.ndarray, offs: np.ndarray) -> np.ndarray:
    """``(probe_rank << 40) | offset`` uint64 tie-order keys, overflow-checked.

    Raises ``OverflowError`` instead of silently wrapping: an offset at or
    above ``2^40`` would leak into the rank field and a rank at or above
    ``2^24`` would wrap off the top, either of which reorders the sharded
    router's ``(dist, key)`` merge.
    """
    ranks = np.asarray(ranks, np.uint64)
    offs = np.asarray(offs, np.uint64)
    if offs.size and int(offs.max()) >= (1 << MERGE_KEY_OFFSET_BITS):
        raise OverflowError(
            f"in-cluster offset {int(offs.max())} needs more than "
            f"{MERGE_KEY_OFFSET_BITS} merge-key bits")
    if ranks.size and int(ranks.max()) >= (1 << MERGE_KEY_RANK_BITS):
        raise OverflowError(
            f"probe rank {int(ranks.max())} needs more than "
            f"{MERGE_KEY_RANK_BITS} merge-key bits")
    return (ranks << np.uint64(MERGE_KEY_OFFSET_BITS)) | offs


class _Stages:
    """Timers, upload bytes and compile counts of one ``batched_search``.

    Each stage runs under a profiler span of its name (``scan.<stage>``)
    and adds its ``perf_counter`` time to ``seconds``; ``put`` counts the
    bytes it hands to the device; ``dispatch`` puts a ``scan.compile``
    span around a program whose static signature this process has not
    dispatched before.
    """

    def __init__(self):
        self.seconds = {"arena": 0.0, "upload": 0.0, "select": 0.0,
                        "rescore": 0.0}
        self.upload_bytes = 0
        self.new_shapes = 0

    @contextlib.contextmanager
    def stage(self, name: str):
        t0 = time.perf_counter()
        with span(f"scan.{name}"):
            yield
        self.seconds[name] += time.perf_counter() - t0

    def put(self, *arrays: np.ndarray):
        """Hand ``arrays`` to the device in one batched copy."""
        self.upload_bytes += sum(a.nbytes for a in arrays)
        return _jax().device_put(arrays)

    def dispatch(self, signature: tuple, fn, *args, **kwargs):
        if signature in _DISPATCHED:
            return fn(*args, **kwargs)
        with span("scan.compile"):
            out = fn(*args, **kwargs)
        _DISPATCHED.add(signature)
        self.new_shapes += 1
        return out


# static signatures ``(kind, qb_pad, u_pad, P, c_pad_b, K, engine)`` of the
# scorer, device-select and re-score pull programs dispatched so far
# (``kind`` names the program and its fixed shapes: ``flat:<n>x<d>:<dtype>``
# and ``pq:<n>x<m>:<dtype>`` for the payload a scorer gathers from,
# ``select:<nlist>:<n>x<w>:<dtype>``, and ``rows:<n>x<w>:<dtype>`` for the
# host cut's row pull, whose padded row count stands in ``u_pad``; a
# field a program lacks gives 0).  jit
# caches are process-wide, so the set is too: a signature missing here
# traces and compiles (or loads from the persistent cache) inside its
# dispatch.
_DISPATCHED: set = set()


def batched_search(index, queries: np.ndarray, nprobe: int = 16,
                   topk: int = 10, engine: str = "auto",
                   query_block: int = DEFAULT_QUERY_BLOCK,
                   with_keys: bool = False, select: str = "auto",
                   select_min: int | None = None):
    """Batched IVF search; bit-identical to ``index.search_ref``.

    Returns ``(ids (nq, topk) int64, dists (nq, topk) f32, SearchStats)``.

    ``select`` places the top-k cut: ``"host"`` pulls the scored
    ``(qb, C_pad)`` block and argsorts in numpy; ``"device"`` runs the
    jitted gather + segmented top-k (``repro.kernels.seg_topk``, same
    ``engine`` choice as the scorer) so only ``(qb, K)`` shortlists cross
    to the host; ``"auto"`` takes the device path when the candidate row
    is at least ``select_min`` wide (default: ``SELECT_MIN_CPU`` on CPU,
    always on accelerators).  Both paths cut the *same* short-list set —
    every candidate within the kernel-error band of the
    (topk + ``RESCORE_SLACK``)-th best kernel distance — and the exact
    re-score decides, so results are bit-identical across
    ``select`` × ``engine``; only ``stats.host_block_bytes`` /
    ``stats.device_select`` differ.

    ``with_keys=True`` additionally fills ``stats.merge_keys`` with a
    (nq, topk) uint64 array: each result's position in the monolithic
    stable candidate order, ``(probe_rank << 40) | in-cluster offset``
    (padding slots = ``MERGE_KEY_PAD``).  Candidates of one query are
    concatenated probe-by-probe then offset-by-offset, so this key is
    exactly the order ``select_topk`` breaks distance ties with — a
    sharded router that merges per-shard results by ``(dist, key)``
    reproduces the unsharded output bit-for-bit even under duplicate
    vectors (repro.shard.service).

    The index's payload is placed on the device by the first call (span
    ``scan.payload_put``, ``stats.payload_put_bytes``); each block
    uploads only its arena's int32 payload rows, gathers the arena on
    the device inside the scoring program, and pulls its short-lists'
    rows back in one transfer for the exact re-score.

    Each stage runs under a profiler span (``scan.search`` around the
    call; ``scan.coarse_probes``, ``scan.arena``, ``scan.upload``,
    ``scan.score``, ``scan.select``, ``scan.rescore`` and ``ids.resolve``
    inside it; ``scan.compile`` around a first dispatch) and is timed
    into the returned stats whether or not a profiler runs.
    """
    from .pq import ProductQuantizer

    engine = _resolve_engine(engine)
    if select not in ("auto", "host", "device"):
        raise ValueError(f"unknown select mode {select!r} "
                         "(options: auto, host, device)")
    t0 = time.perf_counter()
    with span("scan.search"):
        queries = np.asarray(queries)
        nq = queries.shape[0]
        all_ids = np.zeros((nq, topk), np.int64)
        all_d = np.full((nq, topk), np.inf, np.float32)
        with span("scan.coarse_probes"):
            probes = coarse_probes(queries, index.centroids, nprobe)
        tables = index.pq.adc_tables(queries) if index.pq is not None else None
        use_pq = index.pq is not None
        interpret = _jax().default_backend() == "cpu"
        if select_min is None:
            select_min = SELECT_MIN_CPU if interpret else 1
        if use_pq:
            scorer = _adc_scorers()[engine]
        else:
            scorer = _flat_scorers()[engine]
        score_kw = {"interpret": interpret} if engine == "pallas" else {}
        # the payload goes to the device once (the first search, or the
        # first after an add or a load); every block gathers from it there
        payload = index.payload
        payload_put_bytes = payload.place()
        payload_d = payload.device
        puller = _row_puller()
        rows_kind = "{}x{}:{}".format(*payload.shape, payload.dtype)
        score_kind = f"{'pq' if use_pq else 'flat'}:{rows_kind}"

        offsets, sizes = index.offsets, index.sizes
        ndis = 0
        nbatches = 0
        host_block_bytes = 0
        n_dev_select = 0
        select_calls = 0
        rescore_rows = 0
        stages = _Stages()
        distinct: set = set()
        cache = index.decoded_cache
        decodes_before, decode_s_before = cache.decodes, cache.decode_s
        decode_ids_before = cache.decode_ids
        wt_select_s_before = cache.wt_select_s
        wt_select_ids_before = cache.wt_select_ids
        # winning (cluster, offset) pairs across the whole call, resolved in
        # one pass at the end
        res_q: List[np.ndarray] = []
        res_slot: List[np.ndarray] = []
        res_cluster: List[np.ndarray] = []
        res_offset: List[np.ndarray] = []
        res_key: List[np.ndarray] = []
        all_keys = (np.full((nq, topk), MERGE_KEY_PAD, np.uint64)
                    if with_keys else None)

        for q0 in range(0, nq, query_block):
            q1 = min(nq, q0 + query_block)
            qb = q1 - q0
            nbatches += 1
            blk_probes = probes[q0:q1]
            with stages.stage("arena"):
                # --- dedup probed clusters; the arena's payload rows -------
                uniq = np.unique(blk_probes)
                uniq_sizes = sizes[uniq].astype(np.int64)
                keep = uniq_sizes > 0
                uniq, uniq_sizes = uniq[keep], uniq_sizes[keep]
                distinct.update(int(k) for k in uniq)
                arena_start = np.cumsum(uniq_sizes) - uniq_sizes
                u_rows = int(uniq_sizes.sum())
                arena_rows = _spans_concat(offsets[uniq], uniq_sizes)
                # cluster id -> arena span start (dense map over probed ids)
                start_of = np.full(index.nlist, -1, dtype=np.int64)
                size_of = np.zeros(index.nlist, dtype=np.int64)
                start_of[uniq] = arena_start
                size_of[uniq] = uniq_sizes
                if with_keys:
                    # probe rank of each cluster per query (same for every
                    # shard of a shared-quantizer plan, since probes only
                    # depend on centroids)
                    rank_of = np.zeros((qb, index.nlist), np.uint64)
                    rank_of[np.arange(qb)[:, None], blk_probes] = np.arange(
                        blk_probes.shape[1], dtype=np.uint64)[None]

                # --- per-query candidate rows (probe order == oracle) ------
                pp_sizes = size_of[blk_probes]              # (qb, P)
                cand_lens = pp_sizes.sum(axis=1)
                live = np.flatnonzero(cand_lens)
                ndis += int(cand_lens.sum())
                c_pad = int(cand_lens.max()) if qb else 0
                if c_pad == 0:
                    continue
                on_device = _resolve_select(select, c_pad, select_min)
                if not on_device:
                    # the host cut reads each candidate's arena position
                    flat_pos = _spans_concat(start_of[blk_probes].ravel(),
                                             pp_sizes.ravel())
                    cand_pos = np.full((qb, c_pad), -1, dtype=np.int64)
                    row_ids = np.repeat(np.arange(qb), cand_lens)
                    col_ids = np.concatenate(
                        [np.arange(c) for c in cand_lens])
                    cand_pos[row_ids, col_ids] = flat_pos

                # bucketed padding (not fixed query_block): a max-wait flush
                # of a few queries must not score query_block-worth of
                # phantom LUTs/rows.  Padding arena slots hold payload row
                # 0; no candidate column maps to them.
                u_pad = _bucket(u_rows)
                qb_pad = _bucket(qb, floor=8)
                rows_pad = np.zeros(u_pad, np.int32)
                rows_pad[:u_rows] = arena_rows
                # the device select's per-block metadata: padded probes and
                # the dense cluster -> arena span maps
                meta = ()
                if on_device:
                    probes_pad = np.zeros((qb_pad, blk_probes.shape[1]),
                                          np.int32)
                    probes_pad[:qb] = blk_probes
                    meta = (probes_pad, np.maximum(start_of, 0).astype(
                        np.int32), size_of.astype(np.int32))
                if use_pq:
                    lhs = np.zeros((qb_pad,) + tables.shape[1:], np.float32)
                    lhs[:qb] = tables[q0:q1]
                else:
                    lhs = np.zeros((qb_pad, index.d), np.float32)
                    lhs[:qb] = queries[q0:q1]
                    qn_host = np.einsum("qd,qd->q",
                                        queries[q0:q1].astype(np.float32),
                                        queries[q0:q1].astype(np.float32))

            # --- gather the arena and score it (lhs: queries or LUTs) ------
            with stages.stage("upload"):
                lhs_d, rows_d, *meta_d = stages.put(lhs, rows_pad, *meta)
            with span("scan.score"):
                dmat = stages.dispatch(
                    (score_kind, qb_pad, u_pad, 0, 0, 0, engine), scorer,
                    lhs_d, payload_d, rows_d, **score_kw)

            def finish(i, qi, pos, rows):
                # exact re-score of one query's short-list: ``pos`` holds
                # the selected arena positions in candidate (oracle concat)
                # order, so select_topk's stable tie-break reproduces the
                # oracle's; ``rows`` are their payload rows.
                if use_pq:
                    d_exact = ProductQuantizer.adc_score(rows, tables[qi])
                else:
                    d_exact = score_rows_flat(rows, queries[qi])
                best = select_topk(d_exact, topk)
                n_found = best.shape[0]
                all_d[qi, :n_found] = d_exact[best]
                # (cluster, offset) from arena position
                p = pos[best]
                span_of = np.searchsorted(arena_start, p, side="right") - 1
                res_q.append(np.full(n_found, qi, np.int64))
                res_slot.append(np.arange(n_found, dtype=np.int64))
                res_cluster.append(uniq[span_of])
                res_offset.append(p - arena_start[span_of])
                if with_keys:
                    res_key.append(pack_merge_keys(
                        rank_of[i, uniq[span_of]], p - arena_start[span_of]))

            def rescore(pos, rows=None):
                # re-score each live query's short-list: ``pos[j]`` are
                # query ``live[j]``'s selected arena positions, ``rows[j]``
                # their payload rows; without ``rows`` every row of the
                # block comes back from the device in one pull
                nonlocal rescore_rows
                lens = [len(p) for p in pos]
                rescore_rows += sum(lens)
                if rows is None:
                    want = arena_rows[np.concatenate(pos)]
                    r_pad = _bucket(len(want), floor=256)
                    want_pad = np.zeros(r_pad, np.int32)
                    want_pad[:len(want)] = want
                    got = stages.dispatch(
                        (f"rows:{rows_kind}", 0, r_pad, 0, 0, 0, "xla"),
                        puller, payload_d, *stages.put(want_pad))
                    rows = np.split(np.asarray(got)[:len(want)],
                                    np.cumsum(lens)[:-1])
                for i, p, r in zip(live, pos, rows):
                    finish(i, q0 + i, p, r)

            qn = np.zeros(qb) if use_pq else qn_host
            if on_device:
                # --- device-side segmented top-k ---------------------------
                # the (qb, C_pad) block stays on device: a jitted gather +
                # seg_topk returns (qb, K) shortlist values / candidate
                # columns / arena positions, and K doubles while any row's
                # band might extend past it (``_shortlist``)
                n_dev_select += 1
                with stages.stage("select"):
                    runner = _device_selector()
                    c_pad_b = _bucket(c_pad, floor=128)

                    def select_k(K):
                        nonlocal select_calls, host_block_bytes
                        select_calls += 1
                        out = stages.dispatch(
                            (f"select:{index.nlist}:{rows_kind}", qb_pad,
                             u_pad, blk_probes.shape[1], c_pad_b, K, engine),
                            runner, dmat, *meta_d, payload_d, rows_d,
                            c_pad=c_pad_b, k=K,
                            engine=engine, interpret=interpret)
                        # one round of copies: the shortlist and its rows
                        out = _jax().device_get(list(out))
                        host_block_bytes += sum(a.nbytes for a in out[:3])
                        return [a[:qb] for a in out]

                    (_, _, sel_pos, sel_rows), _, cut = _shortlist(
                        select_k, cand_lens, topk, index.d, qn, c_pad_b)
                with stages.stage("rescore"):
                    picks = [cut(i) for i in live]
                    pos = [sel_pos[i, c].astype(np.int64)
                           for i, c in zip(live, picks)]
                    # a band past the rows that came with the shortlist
                    # sends the block to one pull after the cut
                    wide = any(c.size and c.max() >= sel_rows.shape[1]
                               for c in picks)
                    rescore(pos, None if wide else
                            [sel_rows[i, c] for i, c in zip(live, picks)])
            else:
                # --- host-side stable top-k over the pulled padded block ---
                with stages.stage("select"):
                    dmat = np.asarray(dmat)
                    host_block_bytes += dmat.nbytes
                    dmat = dmat[:qb]
                    safe_pos = np.clip(cand_pos, 0, max(0, u_pad - 1))
                    d_blk = np.where(
                        cand_pos >= 0,
                        np.take_along_axis(dmat, safe_pos, axis=1),
                        np.inf,
                    ).astype(np.float32)
                    order = np.argsort(d_blk, axis=1, kind="stable")
                    sorted_blk = (np.take_along_axis(d_blk, order, axis=1),
                                  order)
                with stages.stage("rescore"):
                    # whole sorted rows: one pass, no retry.  Candidate
                    # columns are the oracle's concat positions, so the
                    # cut's candidate order is the oracle's tie order.
                    _, _, cut = _shortlist(lambda K: sorted_blk, cand_lens,
                                           topk, index.d, qn, c_pad)
                    rescore([cand_pos[i, order[i, cut(i)]] for i in live])
            with stages.stage("arena"):
                # release the block's arrays on the host and the device
                # inside the stage, not at the next fill or the return
                del lhs, rows_pad, meta, lhs_d, rows_d, meta_d, dmat

        # --- late id resolution: one pass over every winning pair ----------
        t_res = time.perf_counter()
        with span("ids.resolve"):
            if res_q:
                rq = np.concatenate(res_q)
                rs = np.concatenate(res_slot)
                ids = resolve_ids_batch(index, np.concatenate(res_cluster),
                                        np.concatenate(res_offset))
                all_ids[rq, rs] = ids
                if with_keys:
                    all_keys[rq, rs] = np.concatenate(res_key)
        resolve_s = time.perf_counter() - t_res

        stats = SearchStats(
            wall_s=time.perf_counter() - t0,
            ndis=ndis,
            id_resolve_s=resolve_s,
            decodes=cache.decodes - decodes_before,
            distinct_probed=len(distinct),
            batches=nbatches,
            engine=engine,
            host_block_bytes=host_block_bytes,
            device_select=n_dev_select,
            arena_s=stages.seconds["arena"],
            upload_s=stages.seconds["upload"],
            select_s=stages.seconds["select"],
            rescore_s=stages.seconds["rescore"],
            decode_s=cache.decode_s - decode_s_before,
            decode_ids=cache.decode_ids - decode_ids_before,
            wt_select_s=cache.wt_select_s - wt_select_s_before,
            wt_select_ids=cache.wt_select_ids - wt_select_ids_before,
            upload_bytes=stages.upload_bytes,
            select_calls=select_calls,
            new_shapes=stages.new_shapes,
            payload_put_bytes=payload_put_bytes,
            rescore_rows=rescore_rows,
            merge_keys=all_keys,
        )
    return all_ids, all_d, stats


# ---------------------------------------------------------------------------
# batched flat (brute-force) search
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _flat_select_runner():
    """Jitted score + segmented top-k for the flat path, fused on device."""
    jax, jnp = _jax(), _jax().numpy

    @functools.partial(
        jax.jit, static_argnames=("k", "engine", "interpret", "nvalid"))
    def run(qblk, base, k, engine, interpret, nvalid):
        from ..kernels.seg_topk import seg_topk, seg_topk_xla

        if engine == "pallas":
            from ..kernels.l2_topk import l2_dist

            dmat = l2_dist(qblk, base, interpret=interpret)
        else:
            dmat = _l2_xla(qblk, base)
        lens = jnp.full(qblk.shape[0], nvalid, jnp.int32)
        if engine == "pallas":
            return seg_topk(dmat, lens, k, interpret=interpret)
        return seg_topk_xla(dmat, lens, k)

    return run


def batched_flat_search(vecs: np.ndarray, queries: np.ndarray,
                        topk: int = 10, engine: str = "auto",
                        query_block: int = DEFAULT_QUERY_BLOCK):
    """Kernel-scored brute-force search; bit-identical to the numpy loop.

    Scores each query block against the whole base through the same
    engines the IVF path uses (``l2_dist`` Pallas kernel or plain XLA),
    cuts the short-list with the device-side segmented top-k
    (``repro.kernels.seg_topk``) so only ``(qb, K)`` shortlists ever
    reach the host, and re-scores the short-list with the oracle's numpy
    scalar path (``score_rows_flat`` + ``select_topk``) — so ids **and**
    distances match ``np.argsort(score_rows_flat(...))`` exactly, ties
    to the lower row, for either engine.

    Returns ``(ids (nq, topk) int64, dists (nq, topk) f32, SearchStats)``
    with ``engine="flat-pallas"`` / ``"flat-xla"``.
    """
    jnp = _jax().numpy
    engine = _resolve_engine(engine)
    interpret = _jax().default_backend() == "cpu"
    t0 = time.perf_counter()
    vecs = np.ascontiguousarray(np.asarray(vecs, np.float32))
    queries = np.asarray(queries, np.float32)
    nq, d = queries.shape
    n = vecs.shape[0]
    all_ids = np.zeros((nq, topk), np.int64)
    all_d = np.full((nq, topk), np.inf, np.float32)
    runner = _flat_select_runner()
    n_pad = _bucket(max(n, 1))
    base = np.zeros((n_pad, d), np.float32)
    base[:n] = vecs
    base_dev = jnp.asarray(base)
    nbatches = 0
    host_block_bytes = 0
    n_dev_select = 0
    for q0 in range(0, nq, query_block):
        q1 = min(nq, q0 + query_block)
        qb = q1 - q0
        nbatches += 1
        n_dev_select += 1
        qb_pad = _bucket(qb, floor=8)
        qblk = np.zeros((qb_pad, d), np.float32)
        qblk[:qb] = queries[q0:q1]
        qblk_dev = jnp.asarray(qblk)
        qn_host = np.einsum("qd,qd->q", qblk[:qb], qblk[:qb])

        def select_k(K):
            nonlocal host_block_bytes
            out = [np.asarray(a) for a in runner(
                qblk_dev, base_dev, k=K, engine=engine, interpret=interpret,
                nvalid=n)]
            host_block_bytes += sum(a.nbytes for a in out)
            return [a[:qb] for a in out]

        (_, cols), _, cut = _shortlist(select_k, np.full(qb, n), topk, d,
                                       qn_host, n_pad)
        for i in range(qb):
            qi = q0 + i
            if n == 0:
                continue
            rows = cols[i, cut(i)].astype(np.int64)
            d_exact = score_rows_flat(vecs[rows], queries[qi])
            best = select_topk(d_exact, topk)
            n_found = best.shape[0]
            all_ids[qi, :n_found] = rows[best]
            all_d[qi, :n_found] = d_exact[best]

    stats = SearchStats(
        wall_s=time.perf_counter() - t0,
        ndis=n * nq,
        id_resolve_s=0.0,
        batches=nbatches,
        engine=f"flat-{engine}",
        host_block_bytes=host_block_bytes,
        device_select=n_dev_select,
    )
    return all_ids, all_d, stats
