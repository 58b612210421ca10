#!/usr/bin/env python3
"""Serve compressed-id ANN indexes on one TPU chip and check the answers.

Drives the main path through the entry points a user calls
(``repro.api.index_factory`` -> ``repro.serve.AnnService`` /
``repro.shard.ShardedAnnService``) at the ann-benchmarks SIFT1M shape
(``sift-128-euclidean``: 1,000,000 x 128 float32, L2, k=10), generated
from ``--seed`` with the ``sift-like`` preset; nothing is downloaded.

* Phase A: ``IVF1024,ids=roc`` with nprobe 16 over the full 1M rows.
  ``engine="auto"`` must resolve to the Pallas kernels with the top-k cut
  on the device; ids and dists must equal ``IVFIndex.search_ref`` bit for
  bit on the first 32 queries; recall@10 against brute force is printed.
* Phase B: ``IVF1024,PQ8x8,ids=roc,codes=polya`` (the ``pq_adc`` kernel
  path), same checks, over the same 1M rows.
* Phase C: ``NSG16,ids=roc`` served with ef 32 over the first ``N_GRAPH``
  rows; parity with ``GraphIndex.search_ref`` on every query.
* Phase D: phase A's index split into 4 shards, all on the one chip,
  served by ``ShardedAnnService``; merged ids and dists must equal the
  monolith's.

Phase C is cut in rows only because its graph build is per-node
Python; the cut is printed.  Each phase serves its queries twice as
small requests: the first pass includes compilation, the second gives
the informational QPS.  Times are host wall clock.

Any failed check ends the run with a non-zero exit.  The run stops at
once unless JAX's first device is a TPU.  The last line of standard
output is one JSON object naming the device.

    python3 chip_smoke.py [--seed 0]
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

N_FULL = 1_000_000
N_GRAPH = 100_000
N_QUERIES = 384
REQUEST = 4          # queries per client request
MAX_BATCH = 64       # service micro-batch
K = 10
NPROBE = 16
GRAPH_EF = 32
N_PARITY = 32        # IVF queries checked against search_ref
N_GRAPH_PARITY = N_QUERIES  # graph queries checked (search_ref is cheap)
SHARDS = 4


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def brute_force_topk(base: np.ndarray, queries: np.ndarray,
                     k: int) -> np.ndarray:
    """Exact top-k ids by squared L2 (numpy, 64 queries at a time)."""
    out = np.empty((len(queries), k), np.int64)
    bn = np.einsum("nd,nd->n", base, base)
    for i in range(0, len(queries), 64):
        d = bn[None] - 2.0 * (queries[i:i + 64] @ base.T)
        part = np.argpartition(d, k, axis=1)[:, :k]
        order = np.argsort(np.take_along_axis(d, part, 1), axis=1,
                           kind="stable")
        out[i:i + 64] = np.take_along_axis(part, order, 1)
    return out


def recall_at_k(ids: np.ndarray, gt: np.ndarray) -> float:
    return float(np.mean([len(set(a) & set(b)) / gt.shape[1]
                          for a, b in zip(ids, gt)]))


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "not reported" if peak is None else f"{peak} B"


def serve_twice(svc, queries: np.ndarray):
    """Submit ``queries`` as ``REQUEST``-sized requests, twice.

    Returns ``(dists, ids, cold_s, warm_s, tickets)`` of the second
    pass; the first pass (timed as ``cold_s``) compiles every shape the
    stream needs, and the stats are reset between the passes.
    """
    def once():
        t0 = time.perf_counter()
        tickets = [svc.submit(queries[i:i + REQUEST])
                   for i in range(0, len(queries), REQUEST)]
        svc.flush()
        wall = time.perf_counter() - t0
        return (np.concatenate([t.dists for t in tickets]),
                np.concatenate([t.ids for t in tickets]), wall, tickets)

    _, _, cold, _ = once()
    svc.reset_stats()
    dists, ids, warm, tickets = once()
    return dists, ids, cold, warm, tickets


def report(phase: str, build_s: float, cold_s: float, warm_s: float,
           nq: int, engine: str, st: dict, device) -> None:
    select = "device" if st["device_selects"] > 0 else "host"
    log(f"[{phase}] build {build_s:.3f} s, first pass (compiles included) "
        f"{cold_s:.3f} s, served {nq / warm_s:.1f} QPS "
        f"({st['batches']} batches, mean {st['mean_batch']:.1f} q/batch, "
        f"p50 latency {st['p50_latency_s'] * 1e3:.3f} ms)")
    log(f"[{phase}] engine={engine} select={select} "
        f"device_selects={st['device_selects']} "
        f"host_block_bytes={st['host_block_bytes']}; "
        f"peak device memory {peak_bytes(device)}")


def ivf_phase(phase: str, spec: str, base: np.ndarray, queries: np.ndarray,
              device):
    """Build ``spec`` over ``base``, serve, check engine/select/parity."""
    from repro.api import index_factory
    from repro.serve import AnnService, BatchPolicy

    t0 = time.perf_counter()
    idx = index_factory(spec).build(base, seed=1)
    build_s = time.perf_counter() - t0
    svc = AnnService(idx, topk=K, nprobe=NPROBE, engine="auto",
                     policy=BatchPolicy(max_batch=MAX_BATCH))
    dists, ids, cold, warm, _ = serve_twice(svc, queries)
    st = svc.stats()
    engine = svc.last_stats.engine
    report(phase, build_s, cold, warm, len(queries), engine, st, device)
    if engine != "pallas":
        fail(f"[{phase}] engine resolved to {engine!r}, not 'pallas'")
    if st["device_selects"] <= 0:
        fail(f"[{phase}] no query block took the device top-k")
    ref_ids, ref_d, _ = idx.ivf.search_ref(queries[:N_PARITY],
                                           nprobe=NPROBE, topk=K)
    if not (np.array_equal(ids[:N_PARITY], ref_ids)
            and np.array_equal(dists[:N_PARITY], ref_d)):
        fail(f"[{phase}] served results differ from search_ref")
    log(f"[{phase}] parity: ids and dists bit-identical to search_ref "
        f"on {N_PARITY} queries")
    gt = brute_force_topk(base, queries, K)
    log(f"[{phase}] recall@{K} vs brute force: {recall_at_k(ids, gt):.4f}")
    return idx, dists, ids


def graph_phase(base: np.ndarray, queries: np.ndarray, device) -> None:
    from repro.api import index_factory
    from repro.serve import AnnService, BatchPolicy

    spec = "NSG16,ids=roc"
    t0 = time.perf_counter()
    idx = index_factory(spec).build(base, seed=1)
    build_s = time.perf_counter() - t0
    svc = AnnService(idx, topk=K, ef=GRAPH_EF, engine="auto",
                     policy=BatchPolicy(max_batch=MAX_BATCH))
    dists, ids, cold, warm, _ = serve_twice(svc, queries)
    st = svc.stats()
    engine = svc.last_stats.engine
    report("C", build_s, cold, warm, len(queries), engine, st, device)
    if engine != "graph-pallas":
        fail(f"[C] engine resolved to {engine!r}, not 'graph-pallas'")
    ref_ids, ref_d, _ = idx.graph.search_ref(queries[:N_GRAPH_PARITY],
                                             ef=GRAPH_EF, topk=K)
    if not (np.array_equal(ids[:N_GRAPH_PARITY], ref_ids)
            and np.array_equal(dists[:N_GRAPH_PARITY], ref_d)):
        fail("[C] served results differ from search_ref")
    log(f"[C] parity: ids and dists bit-identical to search_ref on "
        f"{N_GRAPH_PARITY} queries")
    gt = brute_force_topk(base, queries, K)
    log(f"[C] recall@{K} vs brute force: {recall_at_k(ids, gt):.4f}")


def sharded_phase(idx, queries: np.ndarray, mono_d: np.ndarray,
                  mono_i: np.ndarray, device) -> None:
    from repro.serve import BatchPolicy
    from repro.shard import ShardedAnnService, plan_shards

    t0 = time.perf_counter()
    plan = plan_shards(idx, SHARDS)
    plan_s = time.perf_counter() - t0
    log(f"[D] {SHARDS} shards by {plan.by}: "
        f"{[s.n_local for s in plan.shards]} rows, all on {device.device_kind}")
    with ShardedAnnService(plan, topk=K, nprobe=NPROBE, engine="auto",
                           policy=BatchPolicy(max_batch=MAX_BATCH)) as svc:
        dists, ids, cold, warm, tickets = serve_twice(svc, queries)
        st = svc.stats()
    engine = tickets[-1].stats.engine
    report("D", plan_s, cold, warm, len(queries), engine, st, device)
    if engine != "pallas":
        fail(f"[D] engine resolved to {engine!r}, not 'pallas'")
    if st["device_selects"] <= 0:
        fail("[D] no query block took the device top-k")
    if st["partial_batches"]:
        fail("[D] a shard failed during the healthy run")
    if not (np.array_equal(ids, mono_i) and np.array_equal(dists, mono_d)):
        fail("[D] merged sharded results differ from the monolith")
    log(f"[D] parity: merged ids and dists bit-identical to the monolith "
        f"on {len(queries)} queries")


def run(seed: int, device) -> None:
    from repro.data.synthetic import make_dataset

    t0 = time.perf_counter()
    base, queries = make_dataset("sift-like", N_FULL, N_QUERIES, seed=seed)
    log(f"data: sift-like {base.shape[0]} x {base.shape[1]} float32 base, "
        f"{len(queries)} queries, seed {seed} "
        f"({time.perf_counter() - t0:.3f} s)")
    log(f"serving: {REQUEST}-query requests, max batch {MAX_BATCH}, k={K}")

    log(f"[A] IVF1024,ids=roc nprobe={NPROBE}, n={N_FULL}")
    idx_a, d_a, i_a = ivf_phase("A", "IVF1024,ids=roc", base, queries,
                                device)

    log(f"[B] IVF1024,PQ8x8,ids=roc,codes=polya nprobe={NPROBE}, "
        f"n={N_FULL}")
    ivf_phase("B", "IVF1024,PQ8x8,ids=roc,codes=polya", base, queries,
              device)

    log(f"[C] NSG16,ids=roc ef={GRAPH_EF}, n={N_GRAPH} "
        f"(cut from {N_FULL}: per-node Python graph build)")
    graph_phase(base[:N_GRAPH], queries, device)

    log(f"[D] ShardedAnnService over phase A's index, {SHARDS} shards")
    sharded_phase(idx_a, queries, d_a, i_a, device)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke: no package at {SRC / 'repro'}; run this "
                 "script from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.jax_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax

    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{device.platform!r}); this script runs only on a TPU")
    log(f"device: {device.platform} {device.device_kind} x "
        f"{len(jax.devices())}, jax {jax.__version__}, "
        f"compile cache {cache_dir}")
    run(args.seed, device)
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
