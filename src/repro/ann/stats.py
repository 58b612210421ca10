"""Per-call search statistics, shared by every index type.

One stats shape for the whole index layer (IVF scan, graph best-first,
flat brute force) so ``repro.serve.AnnService`` and the benchmarks can
aggregate decode/latency counters without caring which structure served
the batch.  Fields that do not apply to a given index type stay at their
zero default (e.g. ``visited`` for IVF, ``batches`` for graphs).

The sharded router (``repro.shard.ShardedAnnService``) reports through
the same shape: :func:`combine_stats` sums the per-shard counters of one
scattered batch (wall time is the *max* across shards — they run in
parallel) and the fault layer fills ``shards`` / ``shards_failed`` /
``partial`` / ``retries`` so a degraded answer is visible in-band
instead of as an exception.

:func:`span` names a stage of the search in the JAX profiler's trace.
The IVF engine times the same stages into ``SearchStats`` (``arena_s``,
``upload_s``, ``select_s``, ``rescore_s``, ``decode_s``), so the per-call
split is recorded whether or not a profiler runs; ``decode_ids`` counts the
ids those decodes produced, so ``decode_s / decode_ids`` is the decode's
cost per id whichever lists the call happened to miss.
"""

from __future__ import annotations

import contextlib
import dataclasses
import sys
from typing import Optional, Sequence

__all__ = ["SearchStats", "combine_stats", "span"]

_NO_SPAN = contextlib.nullcontext()


def span(name: str, **meta):
    """A host span ``name`` in the JAX profiler's trace, tagged with ``meta``.

    ``jax.profiler.TraceAnnotation`` when jax is already imported (it
    writes on the clock of the device ops, so a trace charges each idle
    device gap to the innermost span over it; without a running profiler
    it records nothing), else a shared no-op context: numpy-only use of an
    index never imports jax.  Never use it inside a jitted function: it
    would time the trace, not the run.
    """
    jax = sys.modules.get("jax")
    if jax is None:
        return _NO_SPAN
    return jax.profiler.TraceAnnotation(name, **meta)


@dataclasses.dataclass
class SearchStats:
    wall_s: float
    ndis: int                  # distance evaluations this call
    id_resolve_s: float        # late id-resolution time (IVF §4.1; 0 for graphs)
    decodes: int = 0           # id-list decode events this call (LRU misses)
    distinct_probed: int = 0   # distinct clusters probed across the batch (IVF)
    batches: int = 0           # query blocks scanned (0 for search_ref/graphs)
    engine: str = "ref"        # "pallas" | "xla" | "ref" | "graph*" | "flat"
    visited: int = 0           # graph nodes expanded (0 for IVF/flat)
    steps: int = 0             # lockstep beam iterations (batched graph only)
    frontier_size: int = 0     # sum of active beams over steps (graph batched)
    dedup_hits: int = 0        # same-step friend-list fetches shared across beams
    # -- device-side top-k select ledger (repro.kernels.seg_topk) ------------
    # bytes of device-computed distance data copied to the host this call:
    # the full (qb, C_pad) block on the host-select path, only the (qb, K)
    # shortlists on the device-select path — the proof the block never
    # materialized host-side when device_select covers every block/step
    host_block_bytes: int = 0
    device_select: int = 0     # query blocks / graph steps selected on device
    # -- stages of the batched IVF scan (0 elsewhere); each ``*_s`` is the
    # perf_counter time of the profiler span of the same stage, summed over
    # the call's query blocks -------------------------------------------------
    arena_s: float = 0.0       # scan.arena: dedup, arena fill and release
    upload_s: float = 0.0      # scan.upload: host->device copy of the arena
    select_s: float = 0.0      # scan.select: top-k cut incl. device wait
    rescore_s: float = 0.0     # scan.rescore: exact host re-score
    decode_s: float = 0.0      # ids.decode: id-list decodes (in id_resolve_s)
    decode_ids: int = 0        # ids produced by those decodes
    upload_bytes: int = 0      # bytes handed to the device (retries included)
    select_calls: int = 0      # device-select runs incl. K-doubling retries
    new_shapes: int = 0        # scorer/select signatures first seen this call
    # -- sharded-serving aggregation (repro.shard) ---------------------------
    shards: int = 0            # shards scattered to (0 = unsharded call)
    shards_failed: int = 0     # shards that missed the deadline / died
    partial: bool = False      # True when results merged from < all shards
    retries: int = 0           # per-shard attempts beyond the first
    # (nq, topk) uint64 stable-merge keys, only filled when the caller asked
    # for them (``with_keys=True``): the monolithic tie order of each result,
    # so a sharded merge can reproduce the unsharded output bit-for-bit.
    merge_keys: Optional[object] = dataclasses.field(
        default=None, repr=False, compare=False)


def combine_stats(parts: Sequence[SearchStats], *, wall_s: float,
                  merge_s: float = 0.0) -> SearchStats:
    """Sum per-shard stats of one scattered batch into one report.

    Counters add; ``wall_s`` is supplied by the caller (shards run
    concurrently, so per-shard walls overlap — pass the scatter+merge
    wall clock); ``merge_s`` is folded into ``id_resolve_s`` as the
    router's post-search bookkeeping cost.  ``engine`` is taken from the
    first part (shards of one plan share an engine).
    """
    out = SearchStats(wall_s=wall_s, ndis=0, id_resolve_s=merge_s,
                      engine=parts[0].engine if parts else "ref")
    for s in parts:
        out.ndis += s.ndis
        out.id_resolve_s += s.id_resolve_s
        out.decodes += s.decodes
        out.distinct_probed += s.distinct_probed
        out.batches += s.batches
        out.visited += s.visited
        out.steps += s.steps
        out.frontier_size += s.frontier_size
        out.dedup_hits += s.dedup_hits
        out.host_block_bytes += s.host_block_bytes
        out.device_select += s.device_select
        out.arena_s += s.arena_s
        out.upload_s += s.upload_s
        out.select_s += s.select_s
        out.rescore_s += s.rescore_s
        out.decode_s += s.decode_s
        out.decode_ids += s.decode_ids
        out.upload_bytes += s.upload_bytes
        out.select_calls += s.select_calls
        out.new_shapes += s.new_shapes
        out.retries += s.retries
    return out
