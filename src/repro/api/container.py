"""RIDX v3 — one versioned container for *any* factory-built index.

Generalizes the v1 ``RIVF`` IVF-only blob (``repro.core.container``) to a
manifest-of-sections format whose manifest records the index's canonical
factory spec.  ``load_index(save_index(idx))`` returns an index whose
search results are **bit-identical** to the original:

* centroids / vectors / PQ codebooks are stored as exact f32 (the v1
  container's f16 centroids would perturb coarse probes);
* IVF id lists ride in joint exact-ANS ROC streams (§4.3 offline
  setting, ``log n_k!`` collected per cluster) — **one per epoch** since
  v3: the manifest carries the epoch table (``[base, count]`` rows) and
  per-epoch ``ids{e}`` / ``esizes`` sections, so an index mid-ingest
  round-trips losslessly *including* its epoch structure and therefore
  its exact ``id_bits()`` accounting;
* PQ codes go through the Pólya coder when the index carries one — also
  one blob per epoch (``code{e}_*`` sections);
* graph edge lists go through the offline path — webgraph-lite by
  default, Random Edge Coding (``graph_codec="rec"``, static degree
  model + shipped degree table) on request; per-node encoding universes
  (the graph ingest analogue of epochs) ride as an RLE section;
* per-list online blobs (ROC/EF/...) and the wavelet tree are *not*
  stored: they are deterministic functions of (lists, universe) and are
  re-encoded per epoch on load, so ``id_bits()`` bookkeeping round-trips.

v2 containers (single implicit epoch, all graph universes = n) still
load; new blobs are always written as v3.
"""

from __future__ import annotations

import os
from typing import List, Optional, Union

import numpy as np

from ..ann.graph import GraphIndex
from ..ann.ivf import IVFIndex
from ..ann.payload import PayloadStore
from ..ann.pq import ProductQuantizer
from ..core.ans import StreamANS
from ..core.container import (SectionReader, SectionWriter, pack_joint_ids,
                              pack_polya_sections, unpack_joint_ids,
                              unpack_polya_sections)
from ..core.epoch import EpochStore, wt_sequence
from ..core.polya import PolyaCodec
from ..core.rec import RECResult, _degree_table, rec_decode, rec_encode
from ..core.webgraph_lite import webgraph_decode, webgraph_encode
from .indexes import FlatIndex, GraphApiIndex, IVFApiIndex, as_api_index
from .spec import IndexSpec, parse_spec

__all__ = ["pack_index", "unpack_index", "save_index", "load_index",
           "wt_sequence", "RIDX_MAGIC", "RIDX_VERSION"]

RIDX_MAGIC = b"RIDX"
RIDX_VERSION = 3


# ---------------------------------------------------------------------------
# pack
# ---------------------------------------------------------------------------

def pack_index(index, graph_codec: str = "webgraph") -> bytes:
    """Serialize any factory-built (or raw IVF/Graph) index to one blob."""
    index = as_api_index(index)
    spec = parse_spec(index.spec)
    meta = {"spec": str(spec), "kind": spec.kind}
    w = SectionWriter()
    if isinstance(index, FlatIndex):
        meta.update(n=int(index.n), d=int(index.d))
        w.add("vecs", index.vecs.astype(np.float32).tobytes())
        id_map = getattr(index, "id_map", None)
        if id_map is not None:
            meta["id_map"] = True
            w.add("id_map", np.asarray(id_map, np.int64).tobytes())
    elif isinstance(index, IVFApiIndex):
        _pack_ivf_sections(w, meta, index.ivf)
    elif isinstance(index, GraphApiIndex):
        _pack_graph_sections(w, meta, index.graph, graph_codec)
    else:  # pragma: no cover - as_api_index guarantees one of the above
        raise TypeError(f"cannot pack {type(index).__name__}")
    return w.finish(RIDX_MAGIC, RIDX_VERSION, meta)


def _pack_ivf_sections(w: SectionWriter, meta: dict, ivf: IVFIndex) -> None:
    meta.update(n=int(ivf.n), d=int(ivf.d), nlist=int(ivf.nlist))
    w.add("sizes", ivf.sizes.astype(np.int64).tobytes())
    w.add("centroids", ivf.centroids.astype(np.float32).tobytes())
    # epoch table + one joint ROC stream per epoch (relative ids, epoch
    # universe) — lossless for an index mid-ingest
    store: EpochStore = ivf._ids
    meta["epochs"] = [[int(ep.base), int(ep.count)] for ep in store.epochs]
    w.add("esizes", np.stack(
        [ep.sizes for ep in store.epochs]).astype(np.int64).tobytes())
    for e, ep in enumerate(store.epochs):
        rel = store.rel_lists(e, ivf._lists)
        w.add(f"ids{e}", pack_joint_ids(rel, ep.count))
    meta["pq"] = ({"m": int(ivf.pq.m), "bits": int(ivf.pq.bits)}
                  if ivf.pq is not None else None)
    if ivf.pq is not None:
        w.add("pq_codebooks", ivf.pq.codebooks.astype(np.float32).tobytes())
    if ivf._code_blobs is not None:
        meta["code"] = {
            "m": int(ivf._code_blobs[0]["m"]),
            "epochs": [pack_polya_sections(w, blob, prefix=f"code{e}")
                       for e, blob in enumerate(ivf._code_blobs)],
        }
    elif ivf.pq is not None:
        w.add("codes_raw", ivf.payload.host().tobytes())
        meta["code"] = {"m": int(ivf.payload.shape[1]), "raw": True}
    else:
        meta["code"] = None
        w.add("vecs", ivf.payload.host().astype(np.float32).tobytes())


def _rle(a: np.ndarray):
    """(values, run_lengths) run-length encoding of a 1-d array."""
    a = np.asarray(a, np.int64)
    if a.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    starts = np.concatenate([[0], np.flatnonzero(np.diff(a)) + 1])
    lens = np.diff(np.concatenate([starts, [a.size]]))
    return a[starts], lens.astype(np.int64)


def _pack_graph_sections(w: SectionWriter, meta: dict, g: GraphIndex,
                         graph_codec: str) -> None:
    meta.update(n=int(g.n), d=int(g.x.shape[1]), entry=int(g.entry),
                graph_codec=graph_codec)
    w.add("vecs", g.x.astype(np.float32).tobytes())
    id_map = getattr(g, "id_map", None)
    if id_map is not None:
        meta["id_map"] = True
        w.add("id_map", np.asarray(id_map, np.int64).tobytes())
    # per-node encoding universes: appends leave old nodes' blobs sealed at
    # the universe they were built with — RLE is tiny (one run per ingest
    # generation), and shipping it lets the loader re-encode each blob at
    # its original universe so id_bits round-trips mid-ingest
    universes = getattr(g, "_universes", None)
    if universes is None:
        universes = np.full(g.n, g.n, np.int64)
    vals, lens = _rle(universes)
    meta["universe_runs"] = int(vals.size)
    w.add("universes", np.concatenate([vals, lens]).tobytes())
    if graph_codec == "webgraph":
        ans = webgraph_encode(g.adj_raw, g.n)
        head, tail = ans.tobytes()
        w.add("graph_head", head)
        w.add("graph_tail", tail)
    elif graph_codec == "rec":
        edges = _edge_list(g.adj_raw)
        meta["n_edges"] = int(edges.shape[0])
        res = rec_encode(edges, g.n, model="degree")
        head, tail = res.state.tobytes()
        w.add("graph_head", head)
        w.add("graph_tail", tail)
        degrees = np.bincount(edges.reshape(-1), minlength=g.n)
        w.add("degrees", degrees.astype(np.int64).tobytes())
    else:
        raise ValueError(f"unknown graph_codec {graph_codec!r} "
                         "(options: webgraph, rec)")


def _edge_list(adj: List[np.ndarray]) -> np.ndarray:
    src = np.concatenate([np.full(len(a), i, np.int64)
                          for i, a in enumerate(adj)] or
                         [np.zeros(0, np.int64)])
    dst = (np.concatenate(adj) if any(len(a) for a in adj)
           else np.zeros(0, np.int64))
    return np.stack([src.astype(np.int64), dst.astype(np.int64)], axis=1)


# ---------------------------------------------------------------------------
# unpack
# ---------------------------------------------------------------------------

def unpack_index(raw: bytes):
    """Inverse of :func:`pack_index`: a ready-to-search api index."""
    r = SectionReader(raw, RIDX_MAGIC)
    if r.version not in (2, RIDX_VERSION):
        raise ValueError(f"unsupported RIDX version {r.version}")
    m = r.manifest
    spec = parse_spec(m["spec"])
    if spec.kind == "flat":
        idx = FlatIndex(spec)
        idx.n, idx.d = m["n"], m["d"]
        idx.vecs = _f32(r.section("vecs"), (m["n"], m["d"]))
        if m.get("id_map"):
            idx.id_map = np.frombuffer(r.section("id_map"), np.int64).copy()
        return idx
    if spec.kind == "ivf":
        return IVFApiIndex.from_built(_unpack_ivf(r, spec), spec)
    return GraphApiIndex.from_built(_unpack_graph(r, spec), spec)


def _f32(raw: bytes, shape) -> np.ndarray:
    return np.frombuffer(raw, np.float32).reshape(shape).copy()


def _cache_fields(spec: IndexSpec) -> dict:
    return dict(
        cache_bytes=(int(spec.cache_mb * (1 << 20))
                     if spec.cache_mb is not None else None),
        cache_policy=spec.cache_policy or "lru",
        max_epochs=spec.max_epochs,
    )


def _unpack_ivf(r: SectionReader, spec: IndexSpec) -> IVFIndex:
    m = r.manifest
    n, d, nlist = m["n"], m["d"], m["nlist"]
    pq = None
    if m["pq"]:
        pq = ProductQuantizer(m=m["pq"]["m"], bits=m["pq"]["bits"])
        pq.codebooks = _f32(r.section("pq_codebooks"),
                            (pq.m, pq.ksub, d // pq.m))
    ivf = IVFIndex(nlist=nlist, id_codec=spec.ids, pq=pq,
                   code_codec=spec.codes, **_cache_fields(spec))
    ivf.n, ivf.d = n, d
    ivf.sizes = np.frombuffer(r.section("sizes"), np.int64).copy()
    ivf.offsets = np.concatenate([[0], np.cumsum(ivf.sizes)]).astype(np.int64)
    ivf.centroids = _f32(r.section("centroids"), (nlist, d))
    # id lists + epoch structure; online blobs / the wavelet tree are
    # deterministic re-encodes from the decoded lists (per epoch), so
    # size_bits bookkeeping matches the pre-save index exactly
    ivf._ids = EpochStore(nlist, spec.ids)
    if r.version == 2:                     # v2: one implicit epoch [0, n)
        epochs = [[0, n]]
        esizes = ivf.sizes[None, :]
        rel_of = {0: unpack_joint_ids(r.section("ids"), ivf.sizes, n)}
    else:
        epochs = m["epochs"]
        esizes = np.frombuffer(r.section("esizes"), np.int64).reshape(
            len(epochs), nlist)
        rel_of = {
            e: unpack_joint_ids(r.section(f"ids{e}"), esizes[e], int(count))
            for e, (_, count) in enumerate(epochs)
        }
    per_epoch_abs = []
    for e, (base, count) in enumerate(epochs):
        ivf._ids.append(rel_of[e], int(base), int(count))
        per_epoch_abs.append([lst + int(base) for lst in rel_of[e]])
    ivf._lists = [
        np.concatenate([per_epoch_abs[e][k] for e in range(len(epochs))])
        for k in range(nlist)
    ]
    # assignment string (id -> cluster); also the storage permutation source
    ivf.cluster_of = np.zeros(n, np.int64)
    if n and int(ivf.sizes.sum()):
        ivf.cluster_of[np.concatenate(ivf._lists)] = np.repeat(
            np.arange(nlist, dtype=np.int64), ivf.sizes)
    # payload (cluster-grouped storage order)
    cm = m["code"]
    if cm is None:
        # shards store fewer rows than the global universe n
        ivf.payload = PayloadStore(
            _f32(r.section("vecs"), (int(ivf.sizes.sum()), d)))
        ivf._code_blobs = None
    elif cm.get("raw"):
        ivf.payload = PayloadStore(np.frombuffer(
            r.section("codes_raw"), np.uint8).reshape(-1, cm["m"]).copy())
        ivf._code_blobs = None
    else:
        ivf._polya = PolyaCodec()
        if r.version == 2:
            blob = unpack_polya_sections(r, [int(s) for s in ivf.sizes], cm)
            ivf._code_blobs = [blob]
            per_epoch_codes = [PolyaCodec().decode(blob)]
        else:
            ivf._code_blobs = []
            per_epoch_codes = []
            for e in range(len(epochs)):
                blob = unpack_polya_sections(
                    r, [int(s) for s in esizes[e]], cm["epochs"][e],
                    prefix=f"code{e}")
                ivf._code_blobs.append(blob)
                per_epoch_codes.append(PolyaCodec().decode(blob))
        # epoch-major per-cluster chunks -> global cluster-grouped rows
        ivf.payload = PayloadStore(np.concatenate(
            [chunk
             for k in range(nlist)
             for per in per_epoch_codes
             for chunk in [per[k]]], axis=0))
    ivf._decoded_cache = ivf._new_cache()
    return ivf


def _unpack_graph(r: SectionReader, spec: IndexSpec) -> GraphIndex:
    from ..core.codecs import get_codec

    m = r.manifest
    n, d = m["n"], m["d"]
    g = GraphIndex(id_codec=spec.ids, **_cache_fields(spec))
    g.n = n
    g.x = _f32(r.section("vecs"), (n, d))
    g.entry = int(m["entry"])
    if m.get("id_map"):
        g.id_map = np.frombuffer(r.section("id_map"), np.int64).copy()
    if m["graph_codec"] == "webgraph":
        ans = StreamANS.frombytes(r.section("graph_head"),
                                  r.section("graph_tail"))
        g.adj_raw = [a.astype(np.int64) for a in webgraph_decode(ans, n, n)]
    else:  # rec
        degrees = np.frombuffer(r.section("degrees"), np.int64)
        ans = StreamANS.frombytes(r.section("graph_head"),
                                  r.section("graph_tail"))
        res = RECResult(payload_bits=0, aux_bits=0, model="degree",
                        state=ans, aux=_degree_table(degrees))
        edges = rec_decode(res, n, m["n_edges"])
        g.adj_raw = _group_edges(edges, n)
    if r.version == 2 or "universes" not in r:
        g._universes = np.full(n, n, np.int64)
    else:
        runs = int(m["universe_runs"])
        flat = np.frombuffer(r.section("universes"), np.int64)
        g._universes = np.repeat(flat[:runs], flat[runs:])
    g._codec = get_codec(spec.ids)
    g._blobs = [g._codec.encode(a, int(u)) if len(a) else None
                for a, u in zip(g.adj_raw, g._universes)]
    g._decoded_cache = g._new_cache()
    return g


def _group_edges(edges: np.ndarray, n: int) -> List[np.ndarray]:
    """Lexicographically sorted (src, dst) rows -> per-node sorted adjacency."""
    counts = np.bincount(edges[:, 0], minlength=n) if edges.size else \
        np.zeros(n, np.int64)
    bounds = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
    return [edges[bounds[i]:bounds[i + 1], 1].astype(np.int64)
            for i in range(n)]


# ---------------------------------------------------------------------------
# file conveniences
# ---------------------------------------------------------------------------

def save_index(index, path: Optional[Union[str, os.PathLike]] = None,
               graph_codec: str = "webgraph") -> bytes:
    """Pack ``index``; also write the blob to ``path`` when given."""
    raw = pack_index(index, graph_codec=graph_codec)
    if path is not None:
        with open(path, "wb") as f:
            f.write(raw)
    return raw


def load_index(src: Union[bytes, str, os.PathLike]):
    """Load an index from a blob or a file path."""
    if isinstance(src, (bytes, bytearray)):
        return unpack_index(bytes(src))
    with open(src, "rb") as f:
        return unpack_index(f.read())
