"""Spans and stage counters of the batched IVF scan.

The program names its stages in the JAX profiler's trace
(``repro.ann.stats.span``) and times the same stages into
``SearchStats`` (``arena_s``, ``upload_s``, ``select_s``, ``rescore_s``,
``decode_s``, ``decode_ids``, ``wt_select_s``, ``wt_select_ids``,
``upload_bytes``, ``select_calls``, ``new_shapes``).  These
tests read a CPU profile for the span tree and check each counter
against what the shapes of the call say it must be.
"""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.ann import scan
from repro.ann.ivf import IVFIndex
from repro.ann.pq import ProductQuantizer
from repro.ann.stats import SearchStats, combine_stats
from repro.api import index_factory
from repro.serve.ann_service import SCAN_STAGE_KEYS, AnnService, BatchPolicy

jax.config.update("jax_platforms", "cpu")

PREFIXES = ("serve.", "scan.", "ids.", "pq.")
TIMED = ("arena_s", "upload_s", "select_s", "rescore_s", "id_resolve_s")
SPECS = {"flat": "IVF16,ids=roc", "pq": "IVF16,PQ8x8,ids=roc"}


def _data(n=1500, d=32, nq=20, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, d)).astype(np.float32),
            rng.standard_normal((nq, d)).astype(np.float32))


@pytest.fixture(scope="module")
def data():
    return _data()


def _ivf(base, kind, codec="roc"):
    pq = ProductQuantizer(m=8, bits=8) if kind == "pq" else None
    return IVFIndex(nlist=16, id_codec=codec, pq=pq).build(base, seed=1)


def _host_spans(log_dir):
    """(name, line, start_ns, end_ns) of the program's host spans."""
    from jax.profiler import ProfileData

    path = sorted(Path(log_dir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append((ev.name, li, s, s + int(ev.duration_ns)))
    return out


def _nested(spans, child, parent):
    """Every ``child`` span lies inside a ``parent`` span on its thread
    (``parent``: one name, or a tuple of names any of which may hold it)."""
    parents = (parent,) if isinstance(parent, str) else parent
    kids = [s for s in spans if s[0] == child]
    outer = [s for s in spans if s[0] in parents]
    return bool(kids) and all(
        any(p[1] == c[1] and p[2] <= c[2] and c[3] <= p[3] for p in outer)
        for c in kids)


@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_profile_holds_the_span_tree(tmp_path, monkeypatch, data, kind):
    base, queries = data
    monkeypatch.setattr(scan, "_DISPATCHED", set())
    svc = AnnService(index_factory(SPECS[kind]).build(base, seed=1),
                     topk=10, nprobe=4, policy=BatchPolicy(max_batch=8))
    with jax.profiler.trace(str(tmp_path)):
        for q in np.array_split(queries, 3):
            svc.submit(q)
        svc.flush()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    tree = [("serve.flush", "scan.search"),
            ("scan.search", "scan.coarse_probes"),
            ("scan.search", "scan.arena"),
            ("scan.search", "scan.upload"),
            ("scan.search", "scan.score"),
            ("scan.search", "scan.select"),
            ("scan.search", "scan.rescore"),
            ("scan.search", "ids.resolve"),
            ("ids.resolve", "ids.decode"),
            ("scan.search", "scan.payload_put"),
            # the scorer's first dispatch, and the re-score's row pull
            (("scan.score", "scan.rescore"), "scan.compile")]
    if kind == "pq":
        tree.append(("scan.search", "pq.adc_tables"))
    for parent, child in tree:
        assert _nested(spans, child, parent), (child, parent, sorted(names))
    # bare event names: the batch id rides as a stat, not in the name
    assert all("#" not in n and "=" not in n for n in names)


@pytest.mark.parametrize("codec", ["wt", "roc"])
def test_wt_select_span_only_where_a_tree_resolves(tmp_path, data, codec):
    base, queries = data
    svc = AnnService(index_factory(f"IVF16,PQ8x8,ids={codec}").build(
        base, seed=1), topk=10, nprobe=4, policy=BatchPolicy(max_batch=8))
    with jax.profiler.trace(str(tmp_path)):
        for q in np.array_split(queries, 3):
            svc.submit(q)
        svc.flush()
    spans = _host_spans(tmp_path)
    names = {s[0] for s in spans}
    if codec == "wt":
        assert _nested(spans, "ids.wt_select", "ids.resolve")
        assert "ids.decode" not in names
    else:
        assert "ids.wt_select" not in names
        assert _nested(spans, "ids.decode", "ids.resolve")


def _expected_upload(idx, queries, nprobe, query_block, select_calls,
                     rescore_rows):
    """Bytes ``batched_search`` hands to the device, from padded shapes.

    The payload itself is on the device already: per block only the
    arena's int32 payload rows cross, and, where the host cuts, the
    int32 row numbers of the one re-score pull (``rescore_rows`` of the
    block, padded)."""
    probes = scan.coarse_probes(queries, idx.centroids, nprobe)
    total = 0
    for q0 in range(0, len(queries), query_block):
        blk = probes[q0:q0 + query_block]
        u_pad = scan._bucket(int(idx.sizes[np.unique(blk)].sum()))
        qb_pad = scan._bucket(len(blk), floor=8)
        if idx.pq is not None:
            total += qb_pad * idx.pq.m * idx.pq.ksub * 4      # f32 LUTs
        else:
            total += qb_pad * idx.d * 4                       # f32 queries
        total += u_pad * 4                                    # arena rows
        if select_calls:
            # once a block, whatever its select runs: (qb_pad, P) int32
            # probes + two int32 nlist maps; the shortlist's rows ride
            # with each run (no band here is wider than SHORTLIST_ROWS,
            # so nothing is pulled after the cut)
            total += qb_pad * blk.shape[1] * 4 + 2 * idx.nlist * 4
        else:
            total += scan._bucket(rescore_rows[q0 // query_block],
                                  floor=256) * 4              # pulled rows
    return total


@pytest.mark.parametrize("select", ["host", "device"])
@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_upload_bytes_follow_padded_shapes(data, kind, select):
    base, queries = data
    idx = _ivf(base, kind)
    per_block, pulled = [], []
    for q0 in range(0, len(queries), 8):
        _, _, st = idx.search(queries[q0:q0 + 8], nprobe=4, topk=10,
                              select=select)
        per_block.append(st.select_calls)
        pulled.append(st.rescore_rows)
    _, _, st = idx.search(queries, nprobe=4, topk=10, select=select,
                          query_block=8)
    assert st.batches == 3
    assert st.select_calls == sum(per_block)
    assert st.rescore_rows == sum(pulled)
    assert st.payload_put_bytes == 0          # placed by the first search
    assert st.upload_bytes == _expected_upload(
        idx, queries, 4, 8, per_block if select == "device" else None,
        pulled)


def test_device_select_counts_its_runs(data):
    base, queries = data
    _, _, st = _ivf(base, "flat").search(queries, nprobe=4, topk=10,
                                         select="device", query_block=8)
    assert st.select_calls >= st.device_select > 0
    _, _, st = _ivf(base, "flat").search(queries, nprobe=4, topk=10,
                                         select="host")
    assert st.select_calls == st.device_select == 0


def test_k_doubling_retries_are_counted():
    # 300 copies of one row tie at distance 0 with the query: the re-score
    # band holds all of them, so the 32-wide shortlist doubles until it
    # covers them, and each doubling is one more select run
    base, _ = _data(n=1200, d=32)
    base = np.concatenate([base, np.repeat(base[:1], 300, axis=0)])
    idx = _ivf(base, "flat")
    ids, d, st = idx.search(base[:1], nprobe=2, topk=10, select="device")
    ids_r, d_r, _ = idx.search_ref(base[:1], nprobe=2, topk=10)
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)
    assert st.device_select == 1
    assert st.select_calls > st.device_select


@pytest.mark.parametrize("codec", ["roc", "gap_ans", "ef", "unc64"])
def test_decode_seconds_iff_decodes(data, codec):
    base, queries = data
    idx = _ivf(base, "flat", codec)
    for _ in range(2):        # cold cache, then (for stream codecs) warm
        _, _, st = idx.search(queries, nprobe=4, topk=10)
        assert (st.decode_s > 0) == (st.decodes > 0)
        assert st.decode_s <= st.id_resolve_s
    if codec in ("roc", "gap_ans"):
        assert st.decodes == 0 and idx.decoded_cache.decodes > 0
        assert idx.decoded_cache.stats()["decode_s"] > 0


@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_decode_ids_counts_decoded_list_lengths(data, kind):
    base, queries = data
    idx = _ivf(base, kind)
    cache = idx.decoded_cache
    _, _, cold = idx.search(queries, nprobe=4, topk=10)
    # a cold cache large enough to keep every list holds what was decoded
    assert cold.decodes == len(cache) > 0 and cache.evictions == 0
    decoded = sum(len(v) for v in cache._lists.values())
    assert cold.decode_ids == decoded == cache.decode_ids
    assert cache.stats()["decode_ids"] == decoded
    _, _, warm = idx.search(queries, nprobe=4, topk=10)
    assert warm.decodes == 0 and warm.decode_ids == 0


def test_decode_ids_zero_on_cache_hits():
    c = scan.DecodedListCache()
    c.get("a", lambda: np.arange(5))
    c.get("b", lambda: np.arange(7))
    assert (c.decodes, c.decode_ids) == (2, 12)
    c.get("a", lambda: np.arange(100))
    c.get("b", lambda: np.arange(100))
    assert (c.hits, c.decodes, c.decode_ids) == (2, 2, 12)


@pytest.mark.parametrize("codec", ["wt", "wt1", "roc"])
def test_wt_select_ids_count_the_resolved_ids(data, codec):
    base, queries = data
    idx = _ivf(base, "flat", codec)
    for _ in range(2):        # no cache: a tree selects on every call
        d, _, st = idx.search(queries, nprobe=4, topk=10, query_block=8)
        if codec == "roc":
            assert st.wt_select_ids == 0 and st.wt_select_s == 0
            continue
        # late resolution resolves every filled result slot, by select
        assert st.wt_select_ids == np.isfinite(d).sum() == d.size
        assert 0 < st.wt_select_s <= st.id_resolve_s
        assert st.decodes == 0 and st.decode_ids == 0
    cache = idx.decoded_cache
    assert cache.wt_select_ids == (0 if codec == "roc" else 2 * d.size)


def test_combine_stats_sums_wt_select_counters():
    parts = [SearchStats(wall_s=1.0, ndis=1, id_resolve_s=0.1,
                         wt_select_s=s, wt_select_ids=n)
             for s, n in ((0.5, 640), (0.0, 0), (0.25, 3))]
    out = combine_stats(parts, wall_s=1.0)
    assert (out.wt_select_s, out.wt_select_ids) == (0.75, 643)


def test_service_wt_select_sum_and_reset(data):
    base, queries = data
    svc = AnnService(_ivf(base, "pq", "wt"), topk=10, nprobe=4,
                     policy=BatchPolicy(max_batch=8))
    want_s, want_ids = 0.0, 0
    for q in np.array_split(queries, 3):
        svc.submit(q)
        svc.flush()
        want_s += svc.last_stats.wt_select_s
        want_ids += svc.last_stats.wt_select_ids
    st = svc.stats()
    assert st["wt_select_ids"] == want_ids == 10 * len(queries)
    assert st["wt_select_s"] == want_s > 0
    svc.reset_stats()
    assert svc.stats()["wt_select_ids"] == svc.stats()["wt_select_s"] == 0


def test_combine_stats_sums_decode_ids():
    parts = [SearchStats(wall_s=1.0, ndis=1, id_resolve_s=0.1,
                         decode_ids=n) for n in (3, 0, 909)]
    assert combine_stats(parts, wall_s=1.0).decode_ids == 912


def test_service_decode_ids_sum_and_reset(data):
    base, queries = data
    idx = _ivf(base, "flat")
    svc = AnnService(idx, topk=10, nprobe=4, policy=BatchPolicy(max_batch=8))
    want = 0
    for q in np.array_split(queries, 3):
        svc.submit(q)
        svc.flush()
        want += svc.last_stats.decode_ids
    assert svc.stats()["decode_ids"] == want
    assert want == idx.decoded_cache.decode_ids > 0
    svc.reset_stats()
    assert svc.stats()["decode_ids"] == 0


@pytest.mark.parametrize("select", ["host", "device"])
@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_timed_parts_fit_in_the_call(data, kind, select):
    base, queries = data
    _, _, st = _ivf(base, kind).search(queries, nprobe=4, topk=10,
                                       select=select, query_block=8)
    parts = [getattr(st, k) for k in TIMED]
    assert all(p >= 0 for p in parts)
    assert sum(parts) <= st.wall_s
    assert 0 <= st.decode_s <= st.id_resolve_s


@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_new_shapes_counts_first_dispatches(monkeypatch, data, kind):
    base, queries = data
    idx = _ivf(base, kind)
    monkeypatch.setattr(scan, "_DISPATCHED", set())
    _, _, first = idx.search(queries, nprobe=4, topk=10, select="device")
    _, _, again = idx.search(queries, nprobe=4, topk=10, select="device")
    # a scorer and at least one select width
    assert first.new_shapes >= 2
    assert again.new_shapes == 0
    assert len(scan._DISPATCHED) == first.new_shapes


def test_service_sums_stage_counters(data):
    base, queries = data
    svc = AnnService(index_factory(SPECS["pq"]).build(base, seed=1),
                     topk=10, nprobe=4, select="device",
                     policy=BatchPolicy(max_batch=8))
    flushed = []
    for q in np.array_split(queries, 4):
        svc.submit(q)
        svc.flush()
        flushed.append(svc.last_stats)
    st = svc.stats()
    for key in SCAN_STAGE_KEYS:
        want = 0
        for s in flushed:
            want += getattr(s, key)
        assert st[key] == want, key
    assert st["upload_bytes"] > 0 and st["select_calls"] >= 4
    svc.reset_stats()
    st = svc.stats()
    assert all(st[key] == 0 for key in SCAN_STAGE_KEYS)


def test_combine_stats_sums_stage_counters():
    parts = [SearchStats(wall_s=1.0, ndis=1, id_resolve_s=0.1, arena_s=0.5,
                         upload_s=0.25, select_s=0.125, rescore_s=0.0625,
                         decode_s=0.03125, upload_bytes=7, select_calls=3,
                         new_shapes=1)] * 2
    out = combine_stats(parts, wall_s=1.0)
    assert (out.arena_s, out.upload_s, out.select_s, out.rescore_s,
            out.decode_s) == (1.0, 0.5, 0.25, 0.125, 0.0625)
    assert (out.upload_bytes, out.select_calls, out.new_shapes) == (14, 6, 2)


def test_other_engines_leave_stage_counters_at_zero(data):
    base, queries = data
    idx = _ivf(base, "flat")
    _, _, ref = idx.search_ref(queries[:4], nprobe=4, topk=10)
    _, _, flat = scan.batched_flat_search(base, queries[:4], topk=10)
    _, _, graph = index_factory("NSG8,ids=roc").build(base[:300]).search(
        queries[:4], k=10)
    for st in (ref, flat, graph):
        assert all(getattr(st, k) == 0 for k in SCAN_STAGE_KEYS), st.engine


def test_span_never_imports_jax():
    # the scan engine and its decode cache name their spans without
    # importing jax: before jax is loaded every span is one shared no-op
    code = ("import sys; from repro.ann import scan, stats; "
            "c = scan.DecodedListCache(); c.get(0, lambda: [1, 2]); "
            "assert 'jax' not in sys.modules; "
            "assert c.decode_s > 0 and c.decodes == 1; "
            "assert stats.span('scan.a') is stats.span('ids.b', x=1); "
            "print('ok')")
    src = str(Path(__file__).resolve().parents[1] / "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env={**os.environ, "PYTHONPATH": src})
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"
