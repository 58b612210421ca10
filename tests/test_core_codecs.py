"""Tests for the set codecs (ROC, EF, gap-ANS), WT, RRR, REC, Polya, webgraph."""

import bisect

import numpy as np
import pytest

try:  # hypothesis is optional (tests/requirements-test.txt): without it the
    from hypothesis import given, settings, strategies as st
except ImportError:  # properties run over deterministic seeded samples
    from _compat_hypothesis import given, settings, st

from repro.core import (
    BigANS,
    EliasFano,
    WaveletTree,
    decode_gaps,
    encode_gaps,
    get_codec,
    polya_decode_clusters,
    polya_encode_clusters,
    rec_decode,
    rec_encode,
    roc_pop_set,
    roc_push_set,
    set_information_bits,
)
from repro.core.bitvec import BitVector, pack_lowbits, unpack_lowbits
from repro.core.roc import SPLIT_LEAF_IDS
from repro.core.rrr import RRRVector
from repro.core.webgraph_lite import webgraph_decode, webgraph_encode


def _random_set(rng, n, universe):
    return rng.choice(universe, size=n, replace=False)


# ---------------------------------------------------------------------------
# ROC
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,universe", [(1, 100), (50, 1000), (700, 10_000), (1000, 2**20)])
def test_roc_roundtrip(n, universe):
    rng = np.random.default_rng(42)
    ids = _random_set(rng, n, universe)
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    out = roc_pop_set(ans, n, universe)
    np.testing.assert_array_equal(out, np.sort(ids))
    assert ans.state == 0


def test_roc_rate_matches_set_bound():
    """The headline claim: ROC ~= log2 C(N, n) bits, i.e. n log N - log n!."""
    rng = np.random.default_rng(7)
    universe, n = 1_000_000, 1000
    ids = _random_set(rng, n, universe)
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    bound = set_information_bits(universe, n)
    assert bound <= ans.bits <= bound + 8  # exact coder: within a few bits


def test_roc_beats_compact_by_log_n_factorial():
    # paper Table 1: IVF1024-ish cluster, expect ~11.4 bpe vs compact 20
    rng = np.random.default_rng(8)
    universe, n = 1_000_000, 977
    ids = _random_set(rng, n, universe)
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    bpe = ans.bits / n
    assert 11.0 < bpe < 11.8


def test_roc_large_cluster_fenwick_path():
    rng = np.random.default_rng(9)
    universe, n = 100_000, 4000  # > 512 triggers the Fenwick path
    ids = _random_set(rng, n, universe)
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    out = roc_pop_set(ans, n, universe)
    np.testing.assert_array_equal(out, np.sort(ids))


def test_roc_rejects_duplicates():
    ans = BigANS()
    with pytest.raises(ValueError):
        roc_push_set(ans, np.array([1, 1, 2]), 10)


@given(st.integers(0, 2**31), st.integers(1, 300))
@settings(max_examples=25, deadline=None)
def test_roc_property(seed, n):
    rng = np.random.default_rng(seed)
    universe = int(rng.integers(n, n * 50 + 2))
    ids = _random_set(rng, n, universe)
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    np.testing.assert_array_equal(roc_pop_set(ans, n, universe), np.sort(ids))
    assert ans.state == 0


def _roc_pop_per_id(ans, n, universe):
    """The per-id ROC decode through ``BigANS`` (oracle of the split decode)."""
    out = []
    for i in range(1, n + 1):
        x = ans.pop_uniform(universe)
        j = bisect.bisect_left(out, x)
        out.insert(j, x)
        ans.push_uniform(j, i)
    return np.asarray(out, dtype=np.int64)


ROC_UNIVERSES = [1, 2, 17, 1000, 10**6, 2**40]
ROC_SIZES = [0, 1, SPLIT_LEAF_IDS - 1, SPLIT_LEAF_IDS, SPLIT_LEAF_IDS + 1,
             362, 909, 2406]
ROC_CASES = [(n, u) for u in ROC_UNIVERSES
             for n in sorted(set(ROC_SIZES) | {u}) if n <= min(u, 2406)]


def _random_ids(rng, n, universe):
    """``n`` distinct ids below ``universe`` (which may exceed int64 choice)."""
    if universe <= 10**7:
        return _random_set(rng, n, universe)
    return np.unique(rng.integers(0, universe, size=2 * n))[:n]


@pytest.mark.parametrize("n,universe", ROC_CASES)
def test_roc_split_decode_matches_per_id_loop(n, universe):
    rng = np.random.default_rng(n * 31 + universe % 1009)
    ids = _random_ids(rng, n, universe)
    assert ids.size == n
    ans = BigANS()
    roc_push_set(ans, ids, universe)
    oracle = BigANS(ans.state)
    want = _roc_pop_per_id(oracle, n, universe)
    got = roc_pop_set(ans, n, universe)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(got, np.sort(ids))
    assert ans.state == oracle.state == 0


@pytest.mark.parametrize("universe", ROC_UNIVERSES)
def test_roc_split_decode_joint_stream(universe):
    """Lists of one joint stream pop back to front: after each list the
    state is where the per-id loop leaves it, so the next list pops from
    the same place."""
    rng = np.random.default_rng(universe % 9973)
    sizes = [n for n in (909, 1, SPLIT_LEAF_IDS, 0, 2406, 17, 362)
             if n <= universe]
    lists = [_random_ids(rng, n, universe) for n in sizes]
    below = int(rng.integers(1, 2**62))           # stream below the lists
    ans = BigANS(below)
    for ids in lists:
        roc_push_set(ans, ids, universe)
    oracle = BigANS(ans.state)
    for n, ids in zip(reversed(sizes), reversed(lists)):
        want = _roc_pop_per_id(oracle, n, universe)
        got = roc_pop_set(ans, n, universe)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, np.sort(ids))
        assert ans.state == oracle.state
    assert ans.state == below


@pytest.mark.parametrize("n", [
    1, 16, 32, SPLIT_LEAF_IDS, SPLIT_LEAF_IDS + 1, 2 * SPLIT_LEAF_IDS,
    2 * SPLIT_LEAF_IDS + 1, 127, 128])
def test_roc_short_lists_on_joint_stream(n):
    """Many short lists (graph friend lists, small epochs) on one joint
    stream: each pops over a state far wider than itself, and must still
    leave the state exactly where the per-id loop does."""
    universe = 10**6
    rng = np.random.default_rng(n)
    lists = [_random_set(rng, n, universe) for _ in range(40)]
    ans = BigANS()
    for ids in lists:
        roc_push_set(ans, ids, universe)
    oracle = BigANS(ans.state)
    for ids in reversed(lists):
        want = _roc_pop_per_id(oracle, n, universe)
        got = roc_pop_set(ans, n, universe)
        np.testing.assert_array_equal(got, want)
        np.testing.assert_array_equal(got, np.sort(ids))
        assert ans.state == oracle.state
    assert ans.state == 0


# ---------------------------------------------------------------------------
# Elias-Fano
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,universe", [(10, 100), (977, 1_000_000), (5000, 2**20)])
def test_ef_roundtrip_and_rate(n, universe):
    rng = np.random.default_rng(10)
    ids = np.sort(_random_set(rng, n, universe))
    ef = EliasFano.encode(ids, universe)
    np.testing.assert_array_equal(ef.decode(), ids)
    # EF is within ~2.56 bits/id of the set bound (2 unary + ~0.56)
    bound = set_information_bits(universe, n) / n
    assert bound <= ef.size_bits / n <= bound + 2.6


def test_ef_random_access():
    rng = np.random.default_rng(11)
    ids = np.sort(_random_set(rng, 500, 10_000))
    ef = EliasFano.encode(ids, 10_000)
    for i in [0, 1, 250, 499]:
        assert ef.access(i) == ids[i]


# ---------------------------------------------------------------------------
# gap-ANS (TPU-path codec)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,universe,lanes", [
    (1, 100, 4), (64, 1000, 64), (977, 1_000_000, 64), (3000, 2**20, 128),
])
def test_gap_ans_roundtrip(n, universe, lanes):
    rng = np.random.default_rng(12)
    ids = _random_set(rng, n, universe)
    heads, words, k = encode_gaps(ids, universe, lanes)
    out = decode_gaps(heads, words, k, n, lanes)
    np.testing.assert_array_equal(out, np.sort(ids))


def test_gap_ans_rate_near_set_bound():
    rng = np.random.default_rng(13)
    universe, n = 1_000_000, 977
    ids = _random_set(rng, n, universe)
    from repro.core.gap_ans import GapAnsCodec
    gc = GapAnsCodec()
    blob = gc.encode(ids, universe)
    bits = gc.size_bits(blob)
    bound = set_information_bits(universe, n)
    # within ~2 bits/id of the set bound incl. 32-bit lane-head overhead
    assert bits <= bound + 2.0 * n


def test_gap_ans_dense_set():
    # dense regime: n close to universe (tiny gaps, k=0)
    rng = np.random.default_rng(14)
    ids = _random_set(rng, 900, 1000)
    heads, words, k = encode_gaps(ids, 1000, 16)
    out = decode_gaps(heads, words, k, 900, 16)
    np.testing.assert_array_equal(out, np.sort(ids))


@given(st.integers(0, 2**31), st.integers(1, 400))
@settings(max_examples=25, deadline=None)
def test_gap_ans_property(seed, n):
    rng = np.random.default_rng(seed)
    universe = int(rng.integers(n, n * 100 + 2))
    ids = _random_set(rng, n, universe)
    heads, words, k = encode_gaps(ids, universe, 32)
    np.testing.assert_array_equal(
        decode_gaps(heads, words, k, n, 32), np.sort(ids)
    )


# ---------------------------------------------------------------------------
# codec registry facade
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["unc64", "unc32", "compact", "ef", "roc", "gap_ans"])
def test_codec_registry_roundtrip(name):
    rng = np.random.default_rng(15)
    universe, n = 50_000, 333
    ids = _random_set(rng, n, universe)
    codec = get_codec(name)
    blob = codec.encode(ids, universe)
    np.testing.assert_array_equal(codec.decode(blob, universe), np.sort(ids))
    assert codec.size_bits(blob) > 0


# ---------------------------------------------------------------------------
# BitVector / RRR
# ---------------------------------------------------------------------------

def test_bitvector_rank_select():
    rng = np.random.default_rng(16)
    bits = (rng.random(10_000) < 0.3).astype(np.uint8)
    bv = BitVector.from_bits(bits)
    cum = np.concatenate([[0], np.cumsum(bits)])
    for pos in [0, 1, 7, 8, 511, 512, 9999, 10_000]:
        assert bv.rank1(pos) == cum[pos]
    ones = np.flatnonzero(bits)
    zeros = np.flatnonzero(1 - bits)
    for j in [0, 5, len(ones) - 1]:
        assert bv.select1(j) == ones[j]
    for j in [0, 5, len(zeros) - 1]:
        assert bv.select0(j) == zeros[j]


def test_pack_unpack_lowbits():
    rng = np.random.default_rng(17)
    vals = rng.integers(0, 1 << 9, size=100)
    packed = pack_lowbits(vals, 9)
    np.testing.assert_array_equal(unpack_lowbits(packed, 9, 100), vals)
    np.testing.assert_array_equal(unpack_lowbits(packed, 9, 100, 10, 5), vals[10:15])


@pytest.mark.parametrize("p", [0.02, 0.3, 0.5, 0.9])
def test_rrr_rank_select(p):
    rng = np.random.default_rng(18)
    bits = (rng.random(4000) < p).astype(np.uint8)
    rv = RRRVector.from_bits(bits)
    cum = np.concatenate([[0], np.cumsum(bits)])
    for pos in [0, 1, 30, 31, 32, 495, 496, 3999, 4000]:
        assert rv.rank1(pos) == cum[pos], pos
    ones = np.flatnonzero(bits)
    zeros = np.flatnonzero(1 - bits)
    for j in [0, len(ones) // 2, len(ones) - 1]:
        assert rv.select1(j) == ones[j]
    for j in [0, len(zeros) // 2, len(zeros) - 1]:
        assert rv.select0(j) == zeros[j]
    np.testing.assert_array_equal(rv.bits(), bits)


def test_rrr_compresses_skewed_bits():
    rng = np.random.default_rng(19)
    bits = (rng.random(100_000) < 0.05).astype(np.uint8)
    rv = RRRVector.from_bits(bits)
    assert rv.size_bits < 0.55 * len(bits)  # H(0.05)~0.29 + class overhead


# ---------------------------------------------------------------------------
# Wavelet tree
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("compressed", [False, True])
@pytest.mark.parametrize("K", [4, 7, 16])
def test_wavelet_tree_select_access(K, compressed):
    rng = np.random.default_rng(20)
    N = 2000
    s = rng.integers(0, K, size=N)
    wt = WaveletTree.build(s, K, compressed=compressed)
    for k in range(K):
        ids = np.flatnonzero(s == k)
        assert wt.cluster_size(k) == len(ids)
        for o in [0, len(ids) // 2, len(ids) - 1]:
            if o >= 0 and len(ids):
                assert wt.select(k, o) == ids[o]
    for i in [0, 1, N // 2, N - 1]:
        assert wt.access(i) == s[i]


def test_wavelet_tree_decode_cluster():
    rng = np.random.default_rng(21)
    s = rng.integers(0, 8, size=500)
    wt = WaveletTree.build(s, 8)
    for k in range(8):
        np.testing.assert_array_equal(wt.decode_cluster(k), np.flatnonzero(s == k))


def test_wavelet_tree_rate():
    # flat WT payload = N * ceil(log2 K) exactly
    rng = np.random.default_rng(22)
    s = rng.integers(0, 1024, size=5000)
    wt = WaveletTree.build(s, 1024)
    assert wt.size_bits == 5000 * 10


# ---------------------------------------------------------------------------
# REC
# ---------------------------------------------------------------------------

def _random_graph(rng, n, deg):
    edges = set()
    while len(edges) < n * deg:
        u = int(rng.integers(0, n))
        v = int(rng.integers(0, n))
        if u != v:
            edges.add((u, v))
    return np.array(sorted(edges), dtype=np.int64)


@pytest.mark.parametrize("model", ["polya", "degree"])
def test_rec_roundtrip(model):
    rng = np.random.default_rng(23)
    edges = _random_graph(rng, 60, 4)
    res = rec_encode(edges, 60, model=model)
    out = rec_decode(res, 60, edges.shape[0])
    np.testing.assert_array_equal(out, edges)


def test_rec_saves_edge_order_bits():
    """REC should land near 2E log N - log E! for a uniform-ish graph."""
    import math

    rng = np.random.default_rng(24)
    n, deg = 256, 8
    edges = _random_graph(rng, n, deg)
    E = edges.shape[0]
    res = rec_encode(edges, n, model="polya")
    naive = E * 2 * math.log2(n)
    saving = math.lgamma(E + 1) / math.log(2)
    # the urn model also pays for degree learning; allow slack
    assert res.payload_bits < naive - 0.5 * saving


# ---------------------------------------------------------------------------
# Polya PQ-code codec
# ---------------------------------------------------------------------------

def test_polya_roundtrip():
    rng = np.random.default_rng(25)
    sizes = [37, 100, 1, 64]
    m = 4
    clusters = [rng.integers(0, 256, size=(n, m)).astype(np.uint8) for n in sizes]
    heads, words, bits = polya_encode_clusters(clusters)
    out = polya_decode_clusters(heads, words, sizes, m)
    for a, b in zip(out, clusters):
        np.testing.assert_array_equal(a, b)


def test_polya_compresses_skewed_codes():
    rng = np.random.default_rng(26)
    # codes concentrated on few symbols within each cluster -> low entropy
    sizes = [512] * 8
    m = 8
    clusters = [
        (rng.integers(0, 8, size=(n, m)) * 3 + rng.integers(0, 3, size=(n, m)))
        .astype(np.uint8)
        for n in sizes
    ]
    _, _, bits = polya_encode_clusters(clusters)
    bpe = bits / (sum(sizes) * m)
    assert bpe < 6.0  # true entropy ~log2(24)=4.6 + adaptation cost


def test_polya_random_codes_near_8_bits():
    rng = np.random.default_rng(27)
    sizes = [1024] * 4
    clusters = [rng.integers(0, 256, size=(n, 4)).astype(np.uint8) for n in sizes]
    _, _, bits = polya_encode_clusters(clusters)
    bpe = bits / (sum(sizes) * 4)
    assert 7.9 < bpe < 8.6  # incompressible codes stay ~8 bits


# ---------------------------------------------------------------------------
# webgraph-lite (Zuckerli stand-in)
# ---------------------------------------------------------------------------

def test_webgraph_roundtrip():
    rng = np.random.default_rng(28)
    n = 80
    adj = [
        np.unique(rng.integers(0, n, size=rng.integers(1, 12)))
        for _ in range(n)
    ]
    ans = webgraph_encode(adj, n)
    out = webgraph_decode(ans, n, n)
    for a, b in zip(out, adj):
        np.testing.assert_array_equal(a, np.sort(b))


def test_webgraph_exploits_overlap():
    # identical consecutive lists should compress far below gap coding
    base = np.array([3, 17, 40, 41, 42, 99, 150, 151], dtype=np.int64)
    adj = [base for _ in range(50)]
    ans = webgraph_encode(adj, 200)
    bits_per_edge = ans.bits / (50 * len(base))
    assert bits_per_edge < 4.0
