"""Batched graph-scan parity: ``GraphIndex.search`` must be bit-identical
to the ``search_ref`` oracle — ids AND distances — for NSG and HNSW, every
graph id codec, both scoring engines, every kernel-gate setting, and
across edge cases (single query, ef=1, topk > n, duplicate vectors,
post-``add()`` indexes, RIDX-reloaded indexes).

Also: beam-state invariant property tests (hypothesis, with the
deterministic fallback) and the DecodedListCache exact-count test shared
by the IVF and graph paths.
"""

import numpy as np
import pytest

import jax

try:  # hypothesis is optional (tests/requirements-test.txt)
    from hypothesis import given, settings, strategies as st
except ImportError:  # properties run over deterministic seeded samples
    from _compat_hypothesis import given, settings, st

from repro.ann.graph import GraphIndex, build_hnsw, build_nsg
from repro.ann.graph_scan import GRAPH_BLOCK_N, batched_graph_search
from repro.ann.scan import DecodedListCache

jax.config.update("jax_platforms", "cpu")

ALL_CODECS = ["unc64", "unc32", "compact", "ef", "roc", "gap_ans"]
ENGINES = ["xla", "pallas"]


def _data(n=800, d=24, nq=33, seed=0):
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((n, d)).astype(np.float32)
    base[50] = base[51]          # duplicate vectors -> exact distance ties
    base[52] = base[51]
    queries = rng.standard_normal((nq, d)).astype(np.float32)
    queries[5] = queries[6]      # duplicate queries too
    return base, queries


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def graphs(data):
    base, _ = data
    return {"nsg": build_nsg(base, 12, seed=3),
            "hnsw": build_hnsw(base, 8, seed=3)}


def _assert_parity(idx, queries, ef=24, topk=10, engine="xla", **kw):
    ids_r, d_r, _ = idx.search_ref(queries, ef=ef, topk=topk)
    ids_b, d_b, st_b = idx.search(queries, ef=ef, topk=topk,
                                  engine=engine, **kw)
    np.testing.assert_array_equal(ids_b, ids_r)
    np.testing.assert_array_equal(d_b, d_r)       # exact, not allclose
    return st_b


# ---------------------------------------------------------------------------
# codec x builder x engine matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("codec", ALL_CODECS)
@pytest.mark.parametrize("kind", ["nsg", "hnsw"])
def test_parity_all_codecs(data, graphs, kind, codec):
    base, queries = data
    idx = GraphIndex(id_codec=codec).build(base, graphs[kind])
    # kernel_min forces the device-scorer branch on CPU too
    _assert_parity(idx, queries, kernel_min=GRAPH_BLOCK_N)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("kind", ["nsg", "hnsw"])
def test_parity_engines(data, graphs, kind, engine):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs[kind])
    _assert_parity(idx, queries, engine=engine, kernel_min=GRAPH_BLOCK_N)


@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("codec", ["compact", "gap_ans"])
def test_parity_codec_engine_cross(data, graphs, codec, engine):
    base, queries = data
    idx = GraphIndex(id_codec=codec).build(base, graphs["nsg"])
    _assert_parity(idx, queries, engine=engine, kernel_min=GRAPH_BLOCK_N)


def test_parity_kernel_gate_settings(data, graphs):
    """The kernel_min gate is a pure perf knob: results identical whether
    every step, some steps, or no step takes the device scorer."""
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    ids_r, d_r, _ = idx.search_ref(queries, ef=24, topk=10)
    for km in (None, 1, GRAPH_BLOCK_N, 10**9):
        ids_b, d_b, _ = idx.search(queries, ef=24, topk=10, kernel_min=km)
        np.testing.assert_array_equal(ids_b, ids_r)
        np.testing.assert_array_equal(d_b, d_r)


# ---------------------------------------------------------------------------
# device-side step select (select="device")
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ENGINES)
@pytest.mark.parametrize("kind", ["nsg", "hnsw"])
def test_parity_device_select(data, graphs, kind, engine):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs[kind])
    st = _assert_parity(idx, queries, engine=engine, kernel_min=1,
                        select="device")
    # every kernel-scored step gathered its distances on device, and only
    # the per-candidate vectors (not the step blocks) crossed to the host
    assert st.device_select > 0
    _, _, st_h = idx.search(queries, ef=24, topk=10, engine=engine,
                            kernel_min=1, select="host")
    assert st_h.device_select == 0
    assert 0 < st.host_block_bytes < st_h.host_block_bytes


@pytest.mark.parametrize("codec", ["compact", "gap_ans"])
def test_parity_device_select_codecs(data, graphs, codec):
    base, queries = data
    idx = GraphIndex(id_codec=codec).build(base, graphs["nsg"])
    _assert_parity(idx, queries, kernel_min=1, select="device")


def test_graph_select_unknown_mode_raises(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    with pytest.raises(ValueError, match="select"):
        idx.search(queries[:2], select="gpu")


# ---------------------------------------------------------------------------
# edge cases
# ---------------------------------------------------------------------------

def test_parity_single_query(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    _assert_parity(idx, queries[:1], kernel_min=GRAPH_BLOCK_N)


def test_parity_ef_one(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["hnsw"])
    _assert_parity(idx, queries, ef=1, topk=1)


def test_parity_topk_exceeds_n(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    _assert_parity(idx, queries, ef=4, topk=2 * base.shape[0])


def test_parity_small_query_block(data, graphs):
    """Batching contract: results independent of query_block."""
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    ref = idx.search(queries, ef=24, topk=10)
    for qb in (1, 7, 64):
        got = idx.search(queries, ef=24, topk=10, query_block=qb)
        np.testing.assert_array_equal(got[0], ref[0])
        np.testing.assert_array_equal(got[1], ref[1])


def test_parity_after_add(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="ef").build(base[:700],
                                          [a[a < 700] for a in
                                           graphs["nsg"][:700]])
    idx.add(base[700:], r=12)
    _assert_parity(idx, queries, kernel_min=GRAPH_BLOCK_N)


def test_parity_reloaded_ridx_index(data):
    from repro.api import index_factory, load_index, save_index

    base, queries = data
    idx = index_factory("NSG12,ids=roc").build(base, seed=1)
    idx2 = load_index(save_index(idx))
    ids_r, d_r, _ = idx.graph.search_ref(queries, ef=24, topk=10)
    ids_b, d_b, st = idx2.graph.search(queries, ef=24, topk=10,
                                       kernel_min=GRAPH_BLOCK_N)
    np.testing.assert_array_equal(ids_b, ids_r)
    np.testing.assert_array_equal(d_b, d_r)
    assert st.engine.startswith("graph-")


def test_batched_stats_counters(data, graphs):
    base, queries = data
    idx = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    _assert_parity(idx, queries)
    # the oracle pass above warmed the shared cache; clear the entries
    # (counters survive) so the batched pass's decode delta is visible
    idx.decoded_cache.clear()
    _, _, st = idx.search(queries, ef=24, topk=10)
    assert st.steps > 0
    # every step counts its active beams; at least one beam runs per step
    assert st.frontier_size >= st.steps
    assert st.visited > 0 and st.ndis >= st.visited
    assert st.dedup_hits >= 0
    # the per-block memo decodes each distinct expanded node at most once
    assert 0 < st.decodes <= st.visited - st.dedup_hits


# ---------------------------------------------------------------------------
# beam-state invariant properties
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31), st.integers(1, 48), st.integers(1, 12))
@settings(max_examples=10, deadline=None)
def test_property_results_well_formed(seed, ef, topk):
    """No id appears twice in a result row; distances sorted ascending;
    batched == reference for random (seed, ef, topk)."""
    rng = np.random.default_rng(seed)
    base = rng.standard_normal((300, 8)).astype(np.float32)
    queries = rng.standard_normal((9, 8)).astype(np.float32)
    idx = GraphIndex(id_codec="roc").build(base, build_nsg(base, 6, seed=1))
    ids_r, d_r, _ = idx.search_ref(queries, ef=ef, topk=topk)
    ids_b, d_b, _ = batched_graph_search(idx, queries, ef=ef, topk=topk)
    np.testing.assert_array_equal(ids_b, ids_r)
    np.testing.assert_array_equal(d_b, d_r)
    k = min(topk, ef)
    for row_ids, row_d in zip(ids_b[:, :k], d_b[:, :k]):
        finite = row_d < np.inf
        assert len(set(row_ids[finite].tolist())) == int(finite.sum())
        assert np.all(np.diff(row_d[finite]) >= 0)


@given(st.integers(0, 2**31), st.integers(2, 32))
@settings(max_examples=5, deadline=None)
def test_property_beam_state_invariants(seed, ef):
    """Step-level invariants of the array bookkeeping, checked at every
    pop: visited counts only grow, frontier slots past f_len stay +inf,
    beam lengths never exceed ef, and b_max matches the live beam max."""
    import repro.ann.graph_scan as gs

    rng = np.random.default_rng(seed)
    base = rng.standard_normal((300, 8)).astype(np.float32)
    queries = rng.standard_normal((8, 8)).astype(np.float32)
    idx = GraphIndex(id_codec="roc").build(base, build_nsg(base, 6, seed=1))

    seen = {"last_visited": -1, "checks": 0}
    orig = gs._BeamState.pop_all

    def checked_pop(self):
        v = int(self.visited.sum())
        assert v >= seen["last_visited"]          # monotone visited sets
        seen["last_visited"] = v
        cols = np.arange(self.f_d.shape[1])[None, :]
        pad = cols >= self.f_len[:, None]
        assert np.all(np.isinf(self.f_d[pad]))    # frontier pad invariant
        assert np.all(self.b_len <= self.ef)
        full = np.flatnonzero(self.b_len == self.ef)
        for i in full[:4]:                        # spot-check b_max cache
            assert self.b_max[i] == self.b_d[i, :self.ef].max()
        seen["checks"] += 1
        return orig(self)

    # plain patch (not the monkeypatch fixture: function-scoped fixtures
    # are rejected inside @given by hypothesis health checks)
    gs._BeamState.pop_all = checked_pop
    try:
        ids_b, d_b, _ = batched_graph_search(idx, queries, ef=ef, topk=5)
    finally:
        gs._BeamState.pop_all = orig
    assert seen["checks"] > 0
    ids_r, d_r, _ = idx.search_ref(queries, ef=ef, topk=5)
    np.testing.assert_array_equal(ids_b, ids_r)
    np.testing.assert_array_equal(d_b, d_r)


# ---------------------------------------------------------------------------
# DecodedListCache: exact hit/miss/eviction accounting
# ---------------------------------------------------------------------------

def test_decoded_cache_exact_counts():
    """Forced-eviction budget: every counter lands exactly where the LRU
    spec says, including the set_budget shrink path."""
    entry = np.arange(10, dtype=np.int64)         # 80 bytes each
    cache = DecodedListCache(max_bytes=160)       # room for two entries
    mk = lambda: entry.copy()
    cache.get(0, mk)                              # miss           [0]
    cache.get(1, mk)                              # miss           [0, 1]
    cache.get(0, mk)                              # hit            [1, 0]
    cache.get(2, mk)                              # miss, evict 1  [0, 2]
    cache.get(1, mk)                              # miss, evict 0  [2, 1]
    st = cache.stats()
    decode_s = st.pop("decode_s")                 # wall time of the 4 misses
    assert decode_s > 0
    assert st == {"entries": 2, "bytes": 160, "hits": 1,
                  "decodes": 4, "decode_ids": 40, "evictions": 2}
    cache.set_budget(100)                         # shrink: evict 2 -> [1]
    assert cache.stats() == {"entries": 1, "bytes": 80, "hits": 1,
                             "decodes": 4, "decode_s": decode_s,
                             "decode_ids": 40, "evictions": 3}


def test_decoded_cache_shared_by_both_paths(data, graphs):
    """IVF and graph searches account decode traffic through the same
    DecodedListCache class with the same counters."""
    from repro.ann.ivf import IVFIndex

    base, queries = data
    g = GraphIndex(id_codec="roc").build(base, graphs["nsg"])
    ivf = IVFIndex(nlist=8, id_codec="roc").build(base, seed=1)
    assert isinstance(g.decoded_cache, DecodedListCache)
    assert isinstance(ivf.decoded_cache, DecodedListCache)
    g.search(queries, ef=8, topk=4)
    ivf.search(queries, nprobe=2, topk=4)
    assert g.decoded_cache.stats()["decodes"] > 0
    assert ivf.decoded_cache.stats()["decodes"] > 0
