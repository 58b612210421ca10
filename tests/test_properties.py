"""System-invariant property tests (hypothesis) across the stack."""

import numpy as np

try:  # hypothesis is optional (tests/requirements-test.txt): without it the
    from hypothesis import given, settings, strategies as st
except ImportError:  # properties run over deterministic seeded samples
    from _compat_hypothesis import given, settings, st

from repro.core.ans import StreamANS
from repro.core.elias_fano import EliasFano
from repro.core.polya import polya_decode_clusters, polya_encode_clusters
from repro.core.wavelet_tree import WaveletTree


# ---------------------------------------------------------------------------
# coders
# ---------------------------------------------------------------------------

@given(st.integers(0, 2**31), st.integers(1, 500))
@settings(max_examples=30, deadline=None)
def test_streamans_random_op_sequences(seed, n_ops):
    """Any pow2-total op sequence round-trips and restores the seed state."""
    rng = np.random.default_rng(seed)
    ops = []
    for _ in range(n_ops):
        r = int(rng.integers(1, 17))
        f = int(rng.integers(1, (1 << r) + 1))
        c = int(rng.integers(0, (1 << r) - f + 1))
        ops.append((c, f, r))
    ans = StreamANS()
    for c, f, r in ops:
        ans.push(c, f, r)
    for c, f, r in reversed(ops):
        if f == (1 << r):
            continue
        cf = ans.pop_cf(r)
        assert c <= cf < c + f
        ans.pop_advance(c, f, r)
    assert ans.head == 1 << 32 and not ans.tail


@given(st.integers(0, 2**31), st.integers(1, 200), st.integers(2, 12))
@settings(max_examples=25, deadline=None)
def test_ef_monotone_roundtrip_and_access(seed, n, logu):
    rng = np.random.default_rng(seed)
    universe = max(n + 1, 1 << logu)
    ids = np.sort(rng.choice(universe, size=min(n, universe - 1), replace=False))
    ef = EliasFano.encode(ids, universe)
    np.testing.assert_array_equal(ef.decode(), ids)
    i = int(rng.integers(0, len(ids)))
    assert ef.access(i) == ids[i]


@given(st.integers(0, 2**31), st.integers(2, 20), st.integers(10, 400))
@settings(max_examples=20, deadline=None)
def test_wavelet_tree_select_inverts_access(seed, K, N):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, K, size=N)
    wt = WaveletTree.build(s, K)
    k = int(rng.integers(0, K))
    occs = np.flatnonzero(s == k)
    for o in range(min(3, len(occs))):
        pos = wt.select(k, o)
        assert s[pos] == k and pos == occs[o]
        assert wt.access(pos) == k


@given(st.integers(0, 2**31))
@settings(max_examples=10, deadline=None)
def test_polya_arbitrary_cluster_shapes(seed):
    rng = np.random.default_rng(seed)
    C = int(rng.integers(1, 6))
    m = int(rng.integers(1, 5))
    sizes = [int(rng.integers(1, 80)) for _ in range(C)]
    clusters = [rng.integers(0, 256, size=(n, m)).astype(np.uint8)
                for n in sizes]
    heads, words, bits = polya_encode_clusters(clusters)
    out = polya_decode_clusters(heads, words, sizes, m)
    for a, b in zip(out, clusters):
        np.testing.assert_array_equal(a, b)
    assert bits > 0
