"""Retrieval through the ``repro.api`` factory, end to end."""

import numpy as np

from repro.api import index_factory, load_index, save_index
from repro.data.synthetic import make_dataset


def test_retrieval_index_end_to_end():
    base, _ = make_dataset("deep-like", 20_000, 64, seed=0)
    idx = index_factory("IVF64,ids=roc").build(base)
    compact_bits = float(np.ceil(np.log2(idx.n)))
    assert idx.ivf.bits_per_id() < compact_bits - 2
    _, ids, _ = idx.search(base[:32], k=5, nprobe=8)
    # self-retrieval: the query vector itself must come back first
    assert np.mean(ids[:, 0] == np.arange(32)) > 0.9


def test_retrieval_index_with_pq_codes():
    base, _ = make_dataset("sift-like", 20_000, 16, seed=0)
    idx = index_factory("IVF32,PQ8x8,ids=gap_ans,codes=polya").build(base)
    assert idx.ivf.code_bits_per_element() <= 8.2
    _, ids, _ = idx.search(base[:8], k=3, nprobe=8)
    assert ids.shape == (8, 3)


def test_retrieval_index_is_spec_thin():
    """Any spec serves through the factory, and the whole index persists
    as one RIDX artifact."""
    base, queries = make_dataset("deep-like", 3_000, 16, seed=0)
    idx = index_factory("IVF32,PQ8x8,ids=roc,codes=polya").build(base)
    assert idx.spec == "IVF32,PQ8x8,ids=roc,codes=polya"
    d0, ids0, _ = idx.search(queries, k=5, nprobe=8)
    idx2 = load_index(save_index(idx))
    d1, ids1, _ = idx2.search(queries, k=5, nprobe=8)
    np.testing.assert_array_equal(ids0, ids1)
    np.testing.assert_array_equal(d0, d1)
    # graph spec through the same front door
    g = index_factory("NSG8,ids=ef").build(base[:400])
    _, gids, gst = g.search(queries, k=5, ef=16)
    assert gids.shape == (16, 5) and gst.visited > 0
    assert g.graph.bits_per_edge() > 0


def test_ivf_container_roundtrip():
    """Offline whole-index blob (paper §4.3) round-trips and shrinks."""
    from repro.ann.ivf import IVFIndex
    from repro.ann.pq import ProductQuantizer
    from repro.core.container import pack_ivf, unpack_ivf

    base, _ = make_dataset("sift-like", 30_000, 8, seed=0)
    pq = ProductQuantizer(m=8, bits=8)
    idx = IVFIndex(nlist=64, id_codec="compact", pq=pq,
                   code_codec="polya").build(base)
    blob = pack_ivf(idx)
    manifest, lists, cents, codes = unpack_ivf(blob)
    assert manifest["n"] == 30_000
    for k in range(64):
        np.testing.assert_array_equal(lists[k], np.sort(idx._lists[k]))
    np.testing.assert_array_equal(codes, idx.codes)
    np.testing.assert_allclose(cents, idx.centroids, atol=0.5)
    # blob must beat the compact layout (ids at ceil(log2 n) + raw codes)
    compact_bytes = (np.ceil(np.log2(30_000)) / 8) * 30_000 + 30_000 * 8
    assert len(blob) < compact_bytes


def test_public_import_surface():
    """The documented package entry points all import."""
    import repro.core as core
    import repro.serve as serve
    from repro.api import (Index, index_factory, load_index,  # noqa: F401
                           parse_spec, save_index)
    from repro.core import CODEC_NAMES, get_codec

    assert set(CODEC_NAMES) >= {"unc64", "compact", "ef", "roc", "gap_ans"}
    for name in CODEC_NAMES:
        assert get_codec(name) is not None
