"""Epoched id/code storage — online ingest parity and accounting.

The acceptance contract for the epoch scheme (repro.core.epoch): after
ANY sequence of add / compact / save / load, search results must be
bit-identical — ids AND distances — to a from-scratch rebuild over the
same rows, for every id codec and both engines.  Plus the satellites:
(epoch, cluster) cache keying, the 2Q cache policy, merge-key overflow
guards, RIDX v3 round-trips with id_bits accounting, and sharded
routed ingest.
"""

import numpy as np
import pytest

from repro.ann.ivf import IVFIndex
from repro.ann.scan import (DecodedListCache, MERGE_KEY_OFFSET_BITS,
                            MERGE_KEY_RANK_BITS, pack_merge_keys)
from repro.api import index_factory, load_index, parse_spec, save_index
from repro.core.epoch import EpochStore
from repro.shard import plan_shards
from repro.shard.service import ShardedAnnService

ID_CODECS = ["unc64", "unc32", "compact", "ef", "roc", "gap_ans", "wt", "wt1"]
D = 20


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    return (rng.normal(size=(400, D)).astype(np.float32),
            rng.normal(size=(90, D)).astype(np.float32),
            rng.normal(size=(10, D)).astype(np.float32))


def _rebuilt(spec, x_all, centroids, seed=0):
    """From-scratch oracle over the full row set (shared quantizer)."""
    idx = index_factory(spec)
    if hasattr(idx, "ivf"):
        return idx.build(x_all, seed=seed, centroids=centroids)
    return idx.build(x_all, seed=seed)


# ---------------------------------------------------------------------------
# IVF add/search parity matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ids", ID_CODECS)
def test_ivf_add_parity_all_codecs(data, ids):
    x, extra, q = data
    spec = f"IVF10,ids={ids}"
    idx = index_factory(spec).build(x, seed=0)
    idx.add(extra[:40])
    idx.add(extra[40:41])          # single-row epoch
    idx.add(extra[41:0:-1][:0])    # empty add is a no-op
    idx.add(extra[41:])
    assert idx.ivf.n_epochs == 4
    ref = _rebuilt(spec, np.concatenate([x, extra]), idx.ivf.centroids)
    d1, i1, _ = idx.search(q, k=10)
    d2, i2, _ = ref.search(q, k=10)
    assert np.array_equal(i1, i2)
    assert np.array_equal(d1, d2)
    # reference engine agrees too
    ir, dr, _ = idx.ivf.search_ref(q, topk=10)
    assert np.array_equal(i1, ir) and np.array_equal(d1, dr)
    # compaction changes bytes, never results
    idx.ivf.compact()
    assert idx.ivf.n_epochs == 1
    d3, i3, _ = idx.search(q, k=10)
    assert np.array_equal(i1, i3) and np.array_equal(d1, d3)
    assert idx.ivf.id_bits() == ref.ivf.id_bits()


def test_ivf_pq_polya_add_parity(data):
    x, extra, q = data
    spec = "IVF10,PQ4x8,ids=roc,codes=polya"
    idx = index_factory(spec).build(x, seed=0)
    idx.add(extra[:50])
    idx.add(extra[50:])
    ref = index_factory(spec)
    ref.ivf.pq = idx.ivf.pq        # shared codebooks: the same quantization
    ref.build(np.concatenate([x, extra]), seed=0, centroids=idx.ivf.centroids)
    d1, i1, _ = idx.search(q, k=10)
    d2, i2, _ = ref.search(q, k=10)
    assert np.array_equal(i1, i2) and np.array_equal(d1, d2)
    # per-epoch Pólya streams cover every stored code
    assert sum(int(sum(b["sizes"])) for b in idx.ivf._code_blobs) == idx.ivf.n
    idx.ivf.compact()
    d3, i3, _ = idx.search(q, k=10)
    assert np.array_equal(i1, i3) and np.array_equal(d1, d3)
    assert idx.ivf.code_bits_per_element() == ref.ivf.code_bits_per_element()


def test_ivf_max_epochs_autocompact(data):
    x, extra, _ = data
    idx = index_factory("IVF10,ids=roc,max_epochs=2").build(x, seed=0)
    for lo in range(0, 80, 10):
        idx.add(extra[lo:lo + 10])
        assert idx.ivf.n_epochs <= 2
    assert idx.n == x.shape[0] + 80


# ---------------------------------------------------------------------------
# graph indexes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["NSG8,ids=roc", "HNSW8,ids=ef",
                                  "HNSW8,ids=gap_ans"])
def test_graph_add_engines_agree(data, spec):
    x, extra, q = data
    idx = index_factory(spec).build(x[:200], seed=0)
    idx.add(extra[:15])
    idx.add(extra[15:30])
    assert idx.graph.n_epochs > 1
    i1, d1, _ = idx.graph.search(q, ef=64, topk=10)
    i2, d2, _ = idx.graph.search_ref(q, ef=64, topk=10)
    assert np.array_equal(i1, i2) and np.array_equal(d1, d2)
    idx.graph.compact()
    assert idx.graph.n_epochs == 1
    i3, d3, _ = idx.graph.search(q, ef=64, topk=10)
    assert np.array_equal(i1, i3) and np.array_equal(d1, d3)


def test_graph_max_epochs_autocompact(data):
    x, extra, _ = data
    idx = index_factory("HNSW8,ids=roc,max_epochs=2").build(x[:150], seed=0)
    for lo in range(0, 30, 10):
        idx.add(extra[lo:lo + 10])
        assert idx.graph.n_epochs <= 2


# ---------------------------------------------------------------------------
# RIDX v3 round-trips
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["IVF10,ids=roc", "IVF10,ids=wt1",
                                  "IVF10,PQ4x8,ids=ef,codes=polya",
                                  "HNSW8,ids=roc"])
def test_ridx_v3_roundtrip_mid_ingest(data, tmp_path, spec):
    x, extra, q = data
    idx = index_factory(spec).build(x, seed=0)
    idx.add(extra[:30])
    idx.add(extra[30:60])
    path = tmp_path / "i.ridx"
    save_index(idx, path)
    idx2 = load_index(path)
    inner = getattr(idx, "ivf", None) or idx.graph
    inner2 = getattr(idx2, "ivf", None) or idx2.graph
    assert inner2.n_epochs == inner.n_epochs
    assert inner2.id_bits() == inner.id_bits()      # bpv accounting round-trips
    d1, i1, _ = idx.search(q, k=10)
    d2, i2, _ = idx2.search(q, k=10)
    assert np.array_equal(i1, i2) and np.array_equal(d1, d2)
    # add-after-load continues the epoch sequence losslessly
    idx.add(extra[60:])
    idx2.add(extra[60:])
    d3, i3, _ = idx.search(q, k=10)
    d4, i4, _ = idx2.search(q, k=10)
    assert np.array_equal(i3, i4) and np.array_equal(d3, d4)


def test_spec_roundtrip_ingest_keys():
    s = "IVF32,ids=roc,cache_policy=2q,max_epochs=4"
    assert str(parse_spec(s)) == s
    assert parse_spec(s).max_epochs == 4
    with pytest.raises(ValueError):
        parse_spec("Flat,cache_policy=2q")
    with pytest.raises(ValueError):
        parse_spec("Flat,max_epochs=3")
    with pytest.raises(ValueError):
        parse_spec("IVF32,cache_policy=mru")
    with pytest.raises(ValueError):
        parse_spec("IVF32,max_epochs=0")


def test_memory_ledger_reports_epochs(data):
    x, extra, _ = data
    idx = index_factory("IVF10,ids=roc").build(x, seed=0)
    idx.add(extra[:30])
    led = idx.memory_ledger()
    assert led["epochs"] == 2.0
    idx.ivf.compact()
    assert idx.memory_ledger()["epochs"] == 1.0


# ---------------------------------------------------------------------------
# epoch-aware caching
# ---------------------------------------------------------------------------

def test_add_preserves_warm_cache_entries(data):
    """Appending never invalidates warm (epoch, cluster) entries; only
    compaction (which renumbers epochs) clears the cache."""
    x, extra, q = data
    idx = index_factory("IVF10,ids=roc").build(x, seed=0)
    idx.search(q, k=10)
    cache = idx.ivf.decoded_cache
    warm = len(cache)
    assert warm > 0
    idx.add(extra[:30])
    assert len(cache) >= warm               # nothing evicted by the add
    d0 = cache.decodes
    idx.search(q, k=10)
    # old epochs hit the warm entries; only epoch-1 lists decode fresh
    assert cache.decodes - d0 <= idx.ivf.nlist
    idx.ivf.compact()
    assert len(cache) == 0


def test_cache_2q_scan_resistance():
    row = np.arange(10, dtype=np.int64)
    cache = DecodedListCache(max_bytes=4 * row.nbytes, policy="2q")
    # touch A twice -> protected
    cache.get("A", lambda: row.copy())
    cache.get("A", lambda: row.copy())
    assert cache.stats()["promotions"] == 1
    # a burst of one-shot keys must not evict the protected entry
    for i in range(20):
        cache.get(("scan", i), lambda: row.copy())
    d0 = cache.decodes
    cache.get("A", lambda: row.copy())
    assert cache.decodes == d0              # A survived the scan
    st = cache.stats()
    assert st["protected_entries"] >= 1
    assert st["bytes"] <= 4 * row.nbytes


def test_cache_lru_stats_shape_unchanged():
    cache = DecodedListCache(max_bytes=1 << 10)
    cache.get("k", lambda: np.zeros(4, np.int64))
    assert set(cache.stats()) == {"entries", "bytes", "hits", "decodes",
                                  "decode_s", "decode_ids", "evictions"}


def test_cache_policy_via_factory(data):
    x, _, q = data
    idx = index_factory("IVF10,ids=roc,cache_policy=2q").build(x, seed=0)
    assert idx.ivf.decoded_cache.policy == "2q"
    idx.search(q, k=10)
    idx.search(q, k=10)
    assert idx.ivf.decoded_cache.stats()["promotions"] > 0


def test_cache_survives_pickle_roundtrip(data):
    import pickle

    x, _, q = data
    idx = index_factory("IVF10,ids=roc,cache_policy=2q").build(x, seed=0)
    idx.search(q, k=10)
    ivf2 = pickle.loads(pickle.dumps(idx.ivf))
    assert ivf2.decoded_cache.policy == "2q"      # __setstate__ re-attaches
    assert len(ivf2.decoded_cache) == 0
    i, d, _ = ivf2.search(q, topk=10)
    d0, i0, _ = idx.search(q, k=10)
    assert np.array_equal(i, i0) and np.array_equal(d, d0)


# ---------------------------------------------------------------------------
# merge-key packing guards
# ---------------------------------------------------------------------------

def test_pack_merge_keys_boundaries():
    offs = np.array([0, (1 << MERGE_KEY_OFFSET_BITS) - 1], np.int64)
    ranks = np.array([(1 << MERGE_KEY_RANK_BITS) - 1, 0], np.int64)
    keys = pack_merge_keys(ranks, offs)
    assert keys.dtype == np.uint64
    assert int(keys[1]) == (1 << MERGE_KEY_OFFSET_BITS) - 1
    with pytest.raises(OverflowError):
        pack_merge_keys(np.array([0]), np.array([1 << MERGE_KEY_OFFSET_BITS]))
    with pytest.raises(OverflowError):
        pack_merge_keys(np.array([1 << MERGE_KEY_RANK_BITS]), np.array([0]))


# ---------------------------------------------------------------------------
# epoch store unit behavior
# ---------------------------------------------------------------------------

def test_epoch_store_rejects_gaps():
    store = EpochStore(2, "roc")
    store.append([np.array([0, 2]), np.array([1])], 0, 3)
    with pytest.raises(ValueError):
        store.append([np.zeros(0, np.int64)] * 2, 5, 2)   # hole in id space
    with pytest.raises(ValueError):
        store.append([np.zeros(0, np.int64)] * 2, 3, 0)   # empty universe


def test_epoch_store_resolve_across_epochs():
    store = EpochStore(2, "roc")
    store.append([np.array([0, 2]), np.array([1])], 0, 3)     # ids 0..2
    store.append([np.array([1]), np.array([0, 2])], 3, 3)     # ids 3..5
    cache = DecodedListCache()
    # cluster 0 holds [0, 2, 4]; cluster 1 holds [1, 3, 5]
    got = store.resolve(np.array([0, 0, 0, 1, 1, 1]),
                        np.array([0, 1, 2, 0, 1, 2]), cache)
    assert got.tolist() == [0, 2, 4, 1, 3, 5]


# ---------------------------------------------------------------------------
# sharded routed ingest
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", ["IVF8,ids=roc", "IVF8,ids=wt",
                                  "IVF8,PQ4x8,ids=roc,codes=polya"])
def test_sharded_ivf_ingest_bit_parity(data, spec):
    x, extra, q = data
    mono = index_factory(spec).build(x, seed=0)
    mono.add(extra[:20])                       # epochs exist before the split
    plan = plan_shards(mono, 3, by="range")
    with ShardedAnnService(plan, topk=10) as svc:
        mono.add(extra[20:50])
        mono.add(extra[50:])
        svc.add(extra[20:50])
        svc.add(extra[50:])
        ids_s, d_s = svc.search(q)
        d_m, ids_m, _ = mono.search(q, k=10)
        assert np.array_equal(ids_s, ids_m)
        assert np.array_equal(d_s, d_m)
        # every shard sealed every epoch with the global universe
        for w in svc._workers:
            assert w.index.ivf.n == mono.ivf.n
            assert w.index.ivf.n_epochs == mono.ivf.n_epochs
        assert svc.stats()["add_rows"] == 70


def test_sharded_hash_ingest_routes_all_rows(data):
    x, extra, q = data
    mono = index_factory("Flat").build(x, seed=0)
    plan = plan_shards(mono, 3, by="hash")
    ref = index_factory("Flat").build(np.concatenate([x, extra]))
    with ShardedAnnService(plan, topk=10) as svc:
        t = svc.add(extra)
        assert t.done and t.ids[0] == x.shape[0]
        assert sum(int(w.index.n) for w in svc._workers) == ref.n
        ids_s, d_s = svc.search(q)
        d_m, ids_m, _ = ref.search(q, k=10)
        assert np.array_equal(ids_s, ids_m)
        assert np.array_equal(d_s, d_m)


def test_sharded_ingest_needs_plan(data):
    x, _, _ = data
    mono = index_factory("IVF8,ids=roc").build(x, seed=0)
    shards = plan_shards(mono, 2, by="range").indexes
    with ShardedAnnService(shards, topk=5) as svc:   # plan-less construction
        with pytest.raises(ValueError):
            svc.submit_add(x[:3])


def test_planner_shard_add_still_guarded(data):
    x, _, _ = data
    mono = index_factory("Flat").build(x, seed=0)
    plan = plan_shards(mono, 2, by="hash")
    with pytest.raises(ValueError):
        plan.indexes[0].add(x[:2])           # direct add bypasses routing


def test_service_microbatched_ingest(data):
    x, extra, q = data
    idx = index_factory("IVF10,ids=roc").build(x, seed=0)
    from repro.serve.ann_service import AnnService, BatchPolicy

    svc = AnnService(idx, topk=10,
                     policy=BatchPolicy(max_batch=1 << 30,
                                        max_wait_s=float("inf")))
    t1 = svc.submit_add(extra[:10])
    t2 = svc.submit_add(extra[10:30])
    assert not t1.done and svc.pending_adds() == 30
    svc.flush_adds()
    assert t1.done and t2.done
    assert t1.ids[0] == x.shape[0] and t2.ids[-1] == x.shape[0] + 29
    assert idx.ivf.n_epochs == 2             # one epoch per flush, not per add
    # read-your-writes: a query flush applies pending adds first
    svc.submit_add(extra[30:40])
    ids, _ = svc.search(q)
    assert idx.n == x.shape[0] + 40
    assert svc.stats()["add_batches"] == 2
