"""IVF index with pluggable id/code compression — the paper's main testbed.

Build: k-means coarse quantizer (K clusters), vectors stored per cluster
(flat f32 or PQ codes, PQ codes optionally Pólya-coded per Eq. 6-7; the
grouped rows are one :class:`repro.ann.payload.PayloadStore`, on the
device from the first batched search on), ids
stored through any ``repro.core.codecs`` codec (paper's online setting:
one stream per cluster) or jointly through a wavelet tree (full random
access, §4.1).

Id (and Pólya code) storage is **epoched** (:class:`repro.core.epoch.
EpochStore`): ``build`` seals one epoch over ``[0, n)``; each ``add``
seals a new epoch over just the appended rows, so ingest entropy-codes
O(Δ) data instead of re-encoding the whole index, and ``compact`` folds
the epochs back into one blob to recover single-universe rates.  The
scanner is oblivious — per-cluster storage stays globally grouped
(offsets/sizes/arena gathers unchanged) and the concatenated per-epoch
lists are globally sorted, so only ``resolve_ids`` routes through epochs.

Search implements the paper's late-id-resolution trick: the scanner keeps
``(cluster, offset)`` pairs in the top-k structure and resolves actual ids
only for the final results — per-cluster decode (ROC/gap), random access
(EF/compact), or ``select`` (WT).

``search`` is the batched engine (repro.ann.scan): cluster-deduplicated
blocked scanning through the Pallas kernels with one id-resolution pass
per call.  ``search_ref`` keeps the original per-query/per-probe Python
loop as the bit-exact test oracle.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from ..core.epoch import EpochStore
from ..core.polya import PolyaCodec
from .kmeans import assign, kmeans
from .payload import PayloadStore
from .pq import ProductQuantizer
from .scan import (CacheOwnerMixin, batched_search, coarse_probes,
                   resolve_ids_batch, score_rows_flat, select_topk)
from .stats import SearchStats

__all__ = ["IVFIndex", "SearchStats"]


@dataclasses.dataclass
class IVFIndex(CacheOwnerMixin):
    nlist: int
    id_codec: str = "roc"
    pq: Optional[ProductQuantizer] = None
    code_codec: Optional[str] = None     # None | "polya"
    cache_bytes: Optional[int] = None    # DecodedListCache budget (None = default)
    cache_policy: str = "lru"            # "lru" | "2q"
    max_epochs: Optional[int] = None     # auto-compact past this epoch count

    def build(self, x: np.ndarray, seed: int = 0,
              centroids: Optional[np.ndarray] = None) -> "IVFIndex":
        self.n, self.d = x.shape
        self.centroids = (centroids if centroids is not None
                          else kmeans(x, self.nlist, iters=8, seed=seed))
        assign_ = assign(x, self.centroids)
        order = np.argsort(assign_, kind="stable")
        self.cluster_of = assign_
        sizes = np.bincount(assign_, minlength=self.nlist)
        self.offsets = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        self.sizes = sizes
        ids_sorted = order.astype(np.int64)
        self._lists = [
            ids_sorted[self.offsets[k]: self.offsets[k + 1]]
            for k in range(self.nlist)
        ]
        # --- vectors / codes, cluster-grouped ---------------------------------
        if self.pq is not None:
            if self.pq.codebooks is None:
                self.pq.train(x)
            self.payload = PayloadStore(self.pq.encode(x)[order])
        else:
            self.payload = PayloadStore(x[order].astype(np.float32))
        # --- id compression: one epoch over [0, n) ------------------------------
        self._ids = EpochStore(self.nlist, self.id_codec)
        self._ids.append(self._lists, 0, self.n)
        # --- optional code compression ------------------------------------------
        if self.code_codec == "polya" and self.pq is not None:
            self._polya = PolyaCodec()
            self._code_blobs = [self._polya.encode(self._per_cluster())]
        else:
            self._code_blobs = None
        self._decoded_cache = self._new_cache()
        return self

    # -- online ingest (epoch scheme) ---------------------------------------------
    def add(self, x: np.ndarray) -> "IVFIndex":
        """Append new vectors to a built index (ids ``n .. n+len(x)-1``).

        Seals one new epoch over exactly the appended rows: only Δ ids
        (and Δ PQ codes) are entropy-coded — existing epoch blobs, wavelet
        trees and warm cache entries are untouched.  New ids are larger
        than every existing id, so appending to each cluster's tail keeps
        storage order == sorted order (the invariant ``resolve_ids``
        relies on) across epochs.
        """
        x = np.asarray(x, np.float32)
        if x.ndim == 1:
            x = x[None]
        m = x.shape[0]
        if m == 0:
            return self
        self.append_epoch(x, np.arange(self.n, self.n + m, dtype=np.int64), m)
        return self

    def append_epoch(self, x_new: np.ndarray, new_ids: np.ndarray,
                     count: int) -> "IVFIndex":
        """Seal the epoch ``[n, n + count)`` holding the given rows.

        Monolithically ``add`` passes every new row; a cluster shard
        passes only the rows whose cluster it owns but the *global*
        ``count``, so epoch boundaries (and therefore every owned blob's
        relative universe) stay identical across shards — the byte-parity
        the sharded merge relies on.  ``new_ids`` must be strictly
        ascending global ids inside the epoch range.
        """
        base = self.n
        x_new = np.asarray(x_new, np.float32).reshape(-1, self.d)
        new_ids = np.asarray(new_ids, np.int64)
        if x_new.shape[0] != new_ids.shape[0]:
            raise ValueError("one id per appended row")
        if new_ids.size and (
                int(new_ids[0]) < base
                or int(new_ids[-1]) >= base + count
                or np.any(np.diff(new_ids) <= 0)):
            raise ValueError(
                f"epoch ids must be strictly ascending within "
                f"[{base}, {base + count})")
        if new_ids.size:
            assign_new = assign(x_new, self.centroids)
            new_codes = self.pq.encode(x_new) if self.pq is not None else None
        else:
            assign_new = np.zeros(0, np.int64)
            new_codes = None
        # regroup per-cluster storage with the new rows appended in id order
        # (O(n) memcpy — cheap next to entropy coding, and it keeps the
        # batched scanner's offsets/sizes/arena layout unchanged)
        rel_lists: List[np.ndarray] = []
        epoch_codes: List[np.ndarray] = []
        parts: List[np.ndarray] = []
        held = self.payload.host()
        new_rows = new_codes if self.pq is not None else x_new
        for k in range(self.nlist):
            sel = assign_new == k
            rel_lists.append(new_ids[sel] - base)
            self._lists[k] = np.concatenate([self._lists[k], new_ids[sel]])
            parts.append(held[self.offsets[k]: self.offsets[k + 1]])
            if sel.any():
                parts.append(new_rows[sel])
            if self.pq is not None:
                epoch_codes.append(
                    new_codes[sel] if new_codes is not None
                    else np.zeros((0, self.pq.m), np.uint8))
        self.sizes = self.sizes + np.bincount(assign_new, minlength=self.nlist)
        self.offsets = np.concatenate([[0], np.cumsum(self.sizes)]).astype(np.int64)
        # the regrouped rows go back to the host; the next search places them
        self.payload = PayloadStore(np.concatenate(parts, axis=0))
        # cluster_of stays universe-sized; only locally-held rows are known
        # (a shard leaves its unowned slots at 0, same as the RIDX loader)
        ext = np.zeros(count, np.int64)
        ext[new_ids - base] = assign_new
        self.cluster_of = np.concatenate(
            [np.asarray(self.cluster_of, np.int64), ext])
        self._ids.append(rel_lists, base, count)
        if self._code_blobs is not None:
            self._code_blobs.append(self._polya.encode(epoch_codes))
        self.n = base + count
        # appends never alias warm (epoch, cluster) cache keys, so no cache
        # invalidation here; compaction renumbers epochs and must clear
        if self.max_epochs is not None and self._ids.n_epochs > self.max_epochs:
            self.compact()
        return self

    @property
    def n_epochs(self) -> int:
        return self._ids.n_epochs

    def compact(self) -> "IVFIndex":
        """Fold every epoch into one ``[0, n)`` blob set.

        Re-encodes all ids (and Pólya codes) against the single global
        universe — the paper's compression rates again, at O(n) cost.
        Run it off the ingest path (``max_epochs`` threshold, or a
        service's background tick) to bound the epoch bpv overhead.
        """
        self._ids.compact(self._lists, self.n)
        if self._code_blobs is not None:
            self._code_blobs = [self._polya.encode(self._per_cluster())]
        # epoch indices restarted at 0: stale (epoch, cluster) keys would alias
        self.decoded_cache.clear()
        return self

    # -- sizes -------------------------------------------------------------------
    def id_bits(self) -> int:
        return self._ids.id_bits()

    def bits_per_id(self) -> float:
        return self.id_bits() / self.n

    def code_bits_per_element(self) -> float:
        if self._code_blobs is None:
            return 8.0
        bits = sum(int(b["bits"]) for b in self._code_blobs)
        elems = sum(int(sum(b["sizes"])) * int(b["m"])
                    for b in self._code_blobs)
        return bits / max(1, elems)

    @property
    def _code_blob(self):
        # legacy single-blob view (v1 RIVF container): exact for one epoch,
        # re-encoded from the global grouping otherwise
        if self._code_blobs is None:
            return None
        if len(self._code_blobs) == 1:
            return self._code_blobs[0]
        return self._polya.encode(self._per_cluster())

    def _per_cluster(self) -> List[np.ndarray]:
        """The payload's rows split by cluster (one pull of the store)."""
        held = self.payload.host()
        return [held[self.offsets[k]: self.offsets[k + 1]]
                for k in range(self.nlist)]

    # -- id resolution (the §4.1 trick) --------------------------------------------
    def resolve_ids(self, clusters: np.ndarray, offsets: np.ndarray) -> np.ndarray:
        """(cluster, offset) pairs -> database ids, decoding lazily.

        Note: lists were encoded SORTED; the scanner's offsets refer to
        storage order, so build/searching keeps storage order == sorted
        order (ids within a cluster are sorted by construction here, and
        epoch concatenation preserves it).  Grouped one-pass resolution;
        stream codecs decode each distinct (epoch, cluster) at most once
        per call through the index's cache.
        """
        t0 = time.perf_counter()
        out = resolve_ids_batch(self, clusters, offsets)
        self._last_resolve_s = time.perf_counter() - t0
        return out

    # -- search ---------------------------------------------------------------------
    def search(self, queries: np.ndarray, nprobe: int = 16, topk: int = 10,
               engine: str = "auto", query_block: int = 64,
               with_keys: bool = False, select: str = "auto",
               select_min: int | None = None):
        """Batched search (repro.ann.scan). Returns (ids, dists, SearchStats).

        Bit-identical to :meth:`search_ref`; ``engine`` picks the scoring
        backend ("pallas" kernels, "xla", or "auto" = pallas off-CPU);
        ``select`` places the top-k cut host- or device-side (segmented
        top-k, ``repro.kernels.seg_topk``) — results are identical either
        way, only ``stats.host_block_bytes``/``stats.device_select``
        change.  ``with_keys`` fills ``stats.merge_keys`` with the stable
        tie-order keys the sharded router merges by (``batched_search``).
        """
        return batched_search(self, queries, nprobe=nprobe, topk=topk,
                              engine=engine, query_block=query_block,
                              with_keys=with_keys, select=select,
                              select_min=select_min)

    def search_ref(self, queries: np.ndarray, nprobe: int = 16,
                   topk: int = 10):
        """Reference per-query/per-probe scan — the batched engine's oracle.

        Deterministic by construction: shared coarse probe, stable top-k
        (ties to the earlier candidate in probe order), scalar numpy
        scoring.  O(nq * nprobe) Python overhead — test/debug use only.
        """
        t0 = time.perf_counter()
        nq = queries.shape[0]
        probes = coarse_probes(queries, self.centroids, nprobe)
        tables = self.pq.adc_tables(queries) if self.pq is not None else None
        all_ids = np.zeros((nq, topk), np.int64)
        all_d = np.full((nq, topk), np.inf, np.float32)
        ndis = 0
        res_s = 0.0
        distinct: set = set()
        decodes0 = self.decoded_cache.decodes
        held = self.payload.host()
        for qi in range(nq):
            cand_d: List[np.ndarray] = []
            cand_k: List[np.ndarray] = []
            cand_o: List[np.ndarray] = []
            for k in probes[qi]:
                lo, hi = self.offsets[k], self.offsets[k + 1]
                if hi == lo:
                    continue
                distinct.add(int(k))
                if self.pq is not None:
                    d = ProductQuantizer.adc_score(held[lo:hi], tables[qi])
                else:
                    d = score_rows_flat(held[lo:hi], queries[qi])
                ndis += hi - lo
                cand_d.append(d)
                cand_k.append(np.full(hi - lo, k, np.int32))
                cand_o.append(np.arange(hi - lo, dtype=np.int32))
            if not cand_d:
                continue
            d = np.concatenate(cand_d)
            kk = np.concatenate(cand_k)
            oo = np.concatenate(cand_o)
            sel = select_topk(d, topk)
            # late id resolution (paper §4.1)
            ids = self.resolve_ids(kk[sel], oo[sel])
            res_s += self._last_resolve_s
            n_found = len(sel)
            all_ids[qi, :n_found] = ids
            all_d[qi, :n_found] = d[sel]
        wall = time.perf_counter() - t0
        return all_ids, all_d, SearchStats(
            wall_s=wall, ndis=ndis, id_resolve_s=res_s,
            decodes=self.decoded_cache.decodes - decodes0,
            distinct_probed=len(distinct), batches=0, engine="ref")
