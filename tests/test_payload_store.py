"""The IVF payload is held once, on the device from the first search.

``IVFIndex.payload`` (:class:`repro.ann.payload.PayloadStore`) holds the
cluster-grouped rows: numpy after a build, an ``add`` or a load, a device
array from the first batched search on, never both.  The scan gathers
each block's arena from it on the device and pulls the short-lists' rows
back once a block.  These tests hold the results bit-identical to the
``search_ref`` oracle (ids, distances, and the merge keys against an
oracle of their own) for IVF-flat and IVF-PQ under both ``select``
paths, across ``add``, ``compact`` and a save/load round trip; check that
the placement is counted once; and walk the index's objects to check
that no second copy of the rows is kept.
"""

import sys
from pathlib import Path

import numpy as np
import pytest

import jax

from repro.ann import scan
from repro.ann.ivf import IVFIndex
from repro.ann.payload import PayloadStore
from repro.ann.pq import ProductQuantizer
from repro.api import index_factory, load_index, save_index
from repro.serve.ann_service import AnnService, BatchPolicy

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.footprint import held_bytes  # noqa: E402

NLIST, NPROBE, TOPK = 16, 4, 10


def _data(n=1500, d=32, nq=24, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((n, d)).astype(np.float32),
            rng.standard_normal((nq, d)).astype(np.float32))


@pytest.fixture(scope="module")
def data():
    return _data()


def _ivf(base, kind, codec="roc"):
    pq = ProductQuantizer(m=8, bits=8) if kind == "pq" else None
    return IVFIndex(nlist=NLIST, id_codec=codec, pq=pq).build(base, seed=1)


def _oracle_keys(idx, queries, ids):
    """Merge keys of ``ids`` from the index's lists: each id's probe rank
    for its query, shifted past its offset in its cluster's list."""
    probes = scan.coarse_probes(queries, idx.centroids, NPROBE)
    keys = np.full(ids.shape, scan.MERGE_KEY_PAD, np.uint64)
    for qi in range(ids.shape[0]):
        for j, i in enumerate(ids[qi]):
            k = int(idx.cluster_of[i])
            off = int(np.flatnonzero(idx._lists[k] == i)[0])
            rank = int(np.flatnonzero(probes[qi] == k)[0])
            keys[qi, j] = scan.pack_merge_keys(np.array([rank]),
                                               np.array([off]))[0]
    return keys


def _assert_parity(idx, queries, select):
    ids_r, d_r, _ = idx.search_ref(queries, nprobe=NPROBE, topk=TOPK)
    ids, d, st = idx.search(queries, nprobe=NPROBE, topk=TOPK,
                            select=select, query_block=8, with_keys=True)
    np.testing.assert_array_equal(ids, ids_r)
    np.testing.assert_array_equal(d, d_r)
    np.testing.assert_array_equal(st.merge_keys,
                                  _oracle_keys(idx, queries, ids_r))
    assert st.device_select == (st.batches if select == "device" else 0)
    return ids, d, st


@pytest.mark.parametrize("select", ["host", "device"])
@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_parity_across_add_and_compact(data, kind, select):
    base, queries = data
    idx = _ivf(base[:1000], kind)
    assert not idx.payload.on_device
    _, _, st = _assert_parity(idx, queries, select)
    assert idx.payload.on_device
    assert st.payload_put_bytes == idx.payload.nbytes == (
        1000 * (8 if kind == "pq" else 32 * 4))
    _, _, st = _assert_parity(idx, queries, select)
    assert st.payload_put_bytes == 0
    # an add regroups the rows on the host; the next search re-places them
    idx.add(base[1000:])
    assert not idx.payload.on_device
    idx.compact()
    _, _, st = _assert_parity(idx, queries, select)
    assert st.payload_put_bytes == idx.payload.nbytes == (
        1500 * (8 if kind == "pq" else 32 * 4))
    # a compaction re-codes ids and changes no row: the rows stay placed
    idx.compact()
    assert idx.payload.on_device
    _, _, st = _assert_parity(idx, queries, select)
    assert st.payload_put_bytes == 0


@pytest.mark.parametrize("select", ["host", "device"])
@pytest.mark.parametrize("spec", [f"IVF{NLIST},ids=roc",
                                  f"IVF{NLIST},PQ8x8,ids=wt"])
def test_parity_after_save_load(data, spec, select):
    base, queries = data
    idx = index_factory(spec).build(base, seed=1)
    d0, i0, st0 = idx.search(queries, k=TOPK, nprobe=NPROBE, select=select,
                             with_keys=True)
    assert idx.ivf.payload.on_device      # saved from the device copy
    idx2 = load_index(save_index(idx))
    assert not idx2.ivf.payload.on_device
    np.testing.assert_array_equal(idx2.ivf.payload.host(),
                                  idx.ivf.payload.host())
    d1, i1, st1 = idx2.search(queries, k=TOPK, nprobe=NPROBE, select=select,
                              with_keys=True)
    np.testing.assert_array_equal(i1, i0)
    np.testing.assert_array_equal(d1, d0)
    np.testing.assert_array_equal(st1.merge_keys, st0.merge_keys)
    assert st1.payload_put_bytes == idx2.ivf.payload.nbytes > 0
    _assert_parity(idx2.ivf, queries, select)


@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_payload_held_once(data, kind):
    base, queries = data
    # ids=ef resolves by random access and caches nothing, so the walk
    # sees only what the search itself may have added
    idx = _ivf(base, kind, codec="ef")
    rows = idx.payload.host().copy()
    before = held_bytes(idx)
    idx.search(queries, nprobe=NPROBE, topk=TOPK)
    assert held_bytes(idx) == before
    assert idx.payload.on_device and idx.payload._host is None
    seen, stack, host_rows = set(), [idx], []
    while stack:                  # no numpy copy of the rows anywhere
        o = stack.pop()
        if id(o) in seen:
            continue
        seen.add(id(o))
        if isinstance(o, np.ndarray):
            host_rows += [o] if o.shape == rows.shape else []
        elif isinstance(o, dict):
            stack.extend(o.values())
        elif isinstance(o, (list, tuple)):
            stack.extend(o)
        elif hasattr(o, "__dict__") and not isinstance(o, type):
            stack.append(vars(o))
    assert host_rows == []
    # host readers pull the same rows back
    np.testing.assert_array_equal(idx.payload.host(), rows)


@pytest.mark.parametrize("kind", ["flat", "pq"])
def test_wide_band_pulls_after_the_cut(kind):
    # 200 copies of one row tie with the query: the band outgrows the
    # shortlist rows that ride with the device select, so the block's
    # rows come back in one pull after the cut; results stay exact
    base, _ = _data(n=1200)
    base = np.concatenate([base, np.repeat(base[:1], 200, axis=0)])
    idx = _ivf(base, kind)
    _, _, st = _assert_parity(idx, base[:1], "device")
    assert st.rescore_rows > scan.SHORTLIST_ROWS


def test_store_pickles_as_host_rows():
    import pickle

    store = PayloadStore(np.arange(12, dtype=np.float32).reshape(4, 3))
    assert store.place() == 48 and store.place() == 0
    back = pickle.loads(pickle.dumps(store))
    assert not back.on_device and back.nbytes == 48
    np.testing.assert_array_equal(back.host(), store.host())
    with pytest.raises(RuntimeError):
        back.device


def test_flat_block_uploads_row_numbers_not_rows(data):
    base, queries = data
    idx = _ivf(base, "flat")
    qb = 8
    _, _, first = idx.search(queries[:qb], nprobe=NPROBE, topk=TOPK,
                             select="device")
    assert first.batches == 1 and first.payload_put_bytes == base.nbytes
    probes = scan.coarse_probes(queries[:qb], idx.centroids, NPROBE)
    u_pad = scan._bucket(int(idx.sizes[np.unique(probes)].sum()))
    qb_pad = scan._bucket(qb, floor=8)
    select_meta = (qb_pad * NPROBE + 2 * NLIST) * 4
    pulled = scan._bucket(first.rescore_rows, floor=256) * 4
    bound = u_pad * 4 + qb_pad * idx.d * 4 + select_meta + pulled
    assert first.upload_bytes <= bound < u_pad * idx.d * 4
    # counted once: repeated searches and a service place nothing more
    for _ in range(2):
        _, _, again = idx.search(queries[:qb], nprobe=NPROBE, topk=TOPK,
                                 select="device")
        assert again.payload_put_bytes == 0
        assert again.upload_bytes == first.upload_bytes
    svc = AnnService(_ivf(base, "flat"), topk=TOPK, nprobe=NPROBE,
                     policy=BatchPolicy(max_batch=8))
    for q in np.array_split(queries, 3):
        svc.submit(q)
        svc.flush()
    assert svc.stats()["payload_put_bytes"] == base.nbytes
