"""RIDX v2: every factory spec round-trips losslessly through save/load.

The acceptance bar of the api redesign: for the full IVF codec × payload
matrix and both graph kinds, ``load(save(index))`` returns bit-identical
search results (ids AND distances), the spec string survives, and the
``id_bits`` bookkeeping matches the pre-save index exactly (online blobs
are deterministic re-encodes of the decoded lists).
"""

import numpy as np
import pytest

import jax

from repro.ann.kmeans import kmeans
from repro.ann.pq import ProductQuantizer
from repro.api import index_factory, load_index, save_index
from repro.api.container import RIDX_MAGIC, unpack_index

jax.config.update("jax_platforms", "cpu")

ALL_ID_CODECS = ["unc64", "unc32", "compact", "ef", "roc", "gap_ans",
                 "wt", "wt1"]
NLIST = 12
D = 32


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    base = rng.standard_normal((900, D)).astype(np.float32)
    queries = rng.standard_normal((12, D)).astype(np.float32)
    return base, queries


@pytest.fixture(scope="module")
def centroids(data):
    return kmeans(data[0], NLIST, iters=4, seed=1)


@pytest.fixture(scope="module")
def pq(data):
    return ProductQuantizer(m=8, bits=8).train(data[0], iters=3)


@pytest.fixture(scope="module")
def graph_adjs(data):
    from repro.ann.graph import build_hnsw, build_nsg

    base = data[0][:350]
    return {"nsg": build_nsg(base, 8), "hnsw": build_hnsw(base, 8)}


def _roundtrip(idx, queries, search_kw):
    d0, i0, _ = idx.search(queries, **search_kw)
    blob = save_index(idx)
    assert blob[:4] == RIDX_MAGIC
    idx2 = load_index(blob)
    assert idx2.spec == idx.spec
    d1, i1, _ = idx2.search(queries, **search_kw)
    np.testing.assert_array_equal(i1, i0)
    np.testing.assert_array_equal(d1, d0)   # exact, not allclose
    return idx2


def _build_ivf(spec, data, centroids, pq):
    idx = index_factory(spec)
    if idx.ivf.pq is not None:
        idx.ivf.pq.codebooks = pq.codebooks  # shared training (test speed)
    return idx.build(data[0], seed=1, centroids=centroids)


@pytest.mark.parametrize("codec", ALL_ID_CODECS)
@pytest.mark.parametrize("payload", ["", ",PQ8x8", ",PQ8x8+polya"])
def test_ivf_matrix_roundtrip(data, centroids, pq, codec, payload):
    spec = (f"IVF{NLIST}"
            + payload.replace("+polya", "")
            + f",ids={codec}"
            + (",codes=polya" if payload.endswith("+polya") else ""))
    idx = _build_ivf(spec, data, centroids, pq)
    idx2 = _roundtrip(idx, data[1], dict(k=7, nprobe=5, engine="xla"))
    # size bookkeeping survives the reload bit-for-bit
    assert idx2.ivf.id_bits() == idx.ivf.id_bits()
    assert idx2.ivf.bits_per_id() == idx.ivf.bits_per_id()
    if payload.endswith("+polya"):
        assert (idx2.ivf.code_bits_per_element()
                == idx.ivf.code_bits_per_element())
    # the reloaded index still matches the per-query oracle
    ids_b, d_b, _ = idx2.ivf.search(data[1], nprobe=5, topk=7, engine="xla")
    ids_r, d_r, _ = idx2.ivf.search_ref(data[1], nprobe=5, topk=7)
    np.testing.assert_array_equal(ids_b, ids_r)
    np.testing.assert_array_equal(d_b, d_r)


@pytest.mark.parametrize("kind", ["nsg", "hnsw"])
@pytest.mark.parametrize("codec", ["roc", "ef"])
def test_graph_roundtrip(data, graph_adjs, kind, codec):
    base = data[0][:350]
    idx = index_factory(f"{kind.upper()}8,ids={codec}").build(
        base, adj=[a.copy() for a in graph_adjs[kind]])
    idx2 = _roundtrip(idx, data[1], dict(k=5, ef=16))
    assert idx2.graph.id_bits() == idx.graph.id_bits()
    assert idx2.graph.entry == idx.graph.entry
    for a, b in zip(idx.graph.adj_raw, idx2.graph.adj_raw):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("graph_codec", ["webgraph", "rec"])
def test_graph_offline_codecs(data, graph_adjs, graph_codec):
    base = data[0][:350]
    idx = index_factory("NSG8,ids=roc").build(
        base, adj=[a.copy() for a in graph_adjs["nsg"]])
    d0, i0, _ = idx.search(data[1], k=5, ef=16)
    blob = save_index(idx, graph_codec=graph_codec)
    idx2 = load_index(blob)
    d1, i1, _ = idx2.search(data[1], k=5, ef=16)
    np.testing.assert_array_equal(i1, i0)
    np.testing.assert_array_equal(d1, d0)


def test_flat_roundtrip(data):
    idx = index_factory("Flat").build(data[0])
    idx2 = _roundtrip(idx, data[1], dict(k=9))
    np.testing.assert_array_equal(idx2.vecs, idx.vecs)


def test_options_survive_roundtrip(data, centroids, pq):
    idx = _build_ivf(f"IVF{NLIST},ids=roc,cache_mb=2,engine=xla",
                     data, centroids, pq)
    blob = save_index(idx)
    idx2 = load_index(blob)
    assert idx2.spec == idx.spec
    assert idx2.ivf.decoded_cache.max_bytes == 2 << 20


def test_save_load_file_path(tmp_path, data, centroids, pq):
    idx = _build_ivf(f"IVF{NLIST},ids=ef", data, centroids, pq)
    p = tmp_path / "index.ridx"
    save_index(idx, p)
    idx2 = load_index(p)
    d0, i0, _ = idx.search(data[1], k=5)
    d1, i1, _ = idx2.search(data[1], k=5)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_roc_joint_streams_split_decode_roundtrip(data):
    """Joint ROC streams load to bit-identical search, and re-save to the
    same bytes, whether a list splits recursively (the built epoch, ~300
    ids a list) or once, as one leaf (an added epoch of ~30 a list)."""
    from repro.core.roc import SPLIT_LEAF_IDS

    base, queries = data
    cents = kmeans(base, 3, iters=4, seed=1)
    idx = index_factory("IVF3,ids=roc").build(base, seed=1, centroids=cents)
    idx.add(base[:90] + 0.01)
    sizes = [ep.sizes for ep in idx.ivf._ids.epochs]
    assert min(sizes[0]) > 2 * SPLIT_LEAF_IDS
    assert SPLIT_LEAF_IDS >= max(sizes[1]) and min(sizes[1]) > 0
    idx2 = _roundtrip(idx, queries, dict(k=10, nprobe=3))
    blob = save_index(idx)
    assert save_index(idx2) == blob
    assert save_index(load_index(blob)) == blob


def test_container_rejects_garbage():
    with pytest.raises(ValueError):
        unpack_index(b"NOPE" + b"\x00" * 64)


def test_v1_container_still_unpacks(data, centroids, pq):
    """The legacy RIVF v1 blob keeps working alongside RIDX v2."""
    from repro.core.container import pack_ivf, unpack_ivf

    idx = _build_ivf(f"IVF{NLIST},PQ8x8,ids=compact,codes=polya",
                     data, centroids, pq)
    manifest, lists, cents, codes = unpack_ivf(pack_ivf(idx.ivf))
    assert manifest["n"] == len(data[0])
    for k in range(NLIST):
        np.testing.assert_array_equal(lists[k], idx.ivf._lists[k])
    np.testing.assert_array_equal(codes, idx.ivf.payload.host())
