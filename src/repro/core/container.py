"""Offline index containers: manifest-of-sections binary artifacts.

The paper's *offline* setting (§4.3) — the index is stored or transmitted
as a binary artifact and decompressed on load.  Two layers live here:

* :class:`SectionWriter` / :class:`SectionReader` — the generic
  manifest-of-sections framing every container version shares::

      magic | u32 version | u32 json_manifest_len | manifest |
      payload sections (offsets in the manifest["sections"] table)

  ``repro.api.container`` builds the RIDX-v2 any-index format on these.

* ``pack_ivf`` / ``unpack_ivf`` — the original v1 ``RIVF`` IVF-only blob
  (ids of all clusters share a single exact-ANS stream, PQ codes through
  the Pólya coder, centroids as f16), kept for backward compatibility and
  as the Table-4 "index" sizing unit.
"""

from __future__ import annotations

import io
import json
from typing import Dict

import numpy as np

from .ans import BigANS
from .polya import polya_decode_clusters
from .roc import roc_pop_set, roc_push_set

__all__ = [
    "pack_ivf", "unpack_ivf", "SectionWriter", "SectionReader",
    "pack_joint_ids", "unpack_joint_ids",
    "pack_polya_sections", "unpack_polya_sections",
]

_MAGIC = b"RIVF"
_VERSION = 1


class SectionWriter:
    """Accumulates named payload sections behind a JSON manifest.

    ``add(name, raw)`` appends bytes and records ``[offset, length]``;
    ``finish(magic, version, meta)`` frames the whole container.  The
    manifest is ``meta`` plus the ``sections`` table.
    """

    def __init__(self) -> None:
        self._payload = io.BytesIO()
        self._sections: Dict[str, list] = {}

    def add(self, name: str, raw: bytes) -> None:
        if name in self._sections:
            raise ValueError(f"duplicate section {name!r}")
        self._sections[name] = [self._payload.tell(), len(raw)]
        self._payload.write(raw)

    def finish(self, magic: bytes, version: int, meta: dict) -> bytes:
        manifest = dict(meta)
        manifest["sections"] = self._sections
        mraw = json.dumps(manifest).encode()
        out = io.BytesIO()
        out.write(magic)
        out.write(np.uint32(version).tobytes())
        out.write(np.uint32(len(mraw)).tobytes())
        out.write(mraw)
        out.write(self._payload.getvalue())
        return out.getvalue()


class SectionReader:
    """Parses a manifest-of-sections container produced by SectionWriter."""

    def __init__(self, raw: bytes, magic: bytes) -> None:
        if raw[: len(magic)] != magic:
            raise ValueError(f"not a {magic.decode(errors='replace')} container")
        p = len(magic)
        self.version = int(np.frombuffer(raw[p: p + 4], np.uint32)[0])
        mlen = int(np.frombuffer(raw[p + 4: p + 8], np.uint32)[0])
        self.manifest = json.loads(raw[p + 8: p + 8 + mlen].decode())
        self._base = p + 8 + mlen
        self._raw = raw

    def __contains__(self, name: str) -> bool:
        return name in self.manifest["sections"]

    def section(self, name: str) -> bytes:
        off, ln = self.manifest["sections"][name]
        return self._raw[self._base + off: self._base + off + ln]


def pack_joint_ids(lists, n: int) -> bytes:
    """Ids of all clusters as one joint exact-ANS stream (clusters in order)."""
    ans = BigANS()
    for ids in lists:
        if len(ids):
            roc_push_set(ans, ids, n)
    return ans.tobytes()


def unpack_joint_ids(raw: bytes, sizes, n: int):
    """Inverse of :func:`pack_joint_ids`: per-cluster sorted id arrays."""
    ans = BigANS.frombytes(raw)
    lists = [None] * len(sizes)
    for k in range(len(sizes) - 1, -1, -1):  # stack order: last pushed, first out
        lists[k] = (roc_pop_set(ans, int(sizes[k]), n) if sizes[k]
                    else np.zeros(0, np.int64))
    return lists


def pack_polya_sections(w: SectionWriter, blob, prefix: str = "code") -> dict:
    """Write a PolyaCodec blob's arrays as sections; returns its meta dict."""
    w.add(f"{prefix}_heads", blob["heads"].astype(np.uint64).tobytes())
    words = blob["words"]
    lens = np.array([len(x) for x in words], np.int64)
    w.add(f"{prefix}_word_lens", lens.tobytes())
    w.add(f"{prefix}_words", np.concatenate(
        [x for x in words] or [np.zeros(0, np.uint32)]).tobytes())
    return {"m": blob["m"], "bits": int(blob["bits"])}


def unpack_polya_sections(r: SectionReader, sizes, meta: dict,
                          prefix: str = "code"):
    """Inverse of :func:`pack_polya_sections`: the reconstructed blob dict."""
    heads = np.frombuffer(r.section(f"{prefix}_heads"), np.uint64)
    lens = np.frombuffer(r.section(f"{prefix}_word_lens"), np.int64)
    flat = np.frombuffer(r.section(f"{prefix}_words"), np.uint32)
    words, off = [], 0
    for ln in lens:
        words.append(flat[off:off + ln].copy())
        off += ln
    return {"heads": heads.copy(), "words": words, "bits": meta["bits"],
            "sizes": [int(s) for s in sizes], "m": meta["m"]}


def pack_ivf(index) -> bytes:
    """Serialize a built repro.ann.ivf.IVFIndex into one v1 RIVF blob."""
    sizes = [int(s) for s in index.sizes]
    w = SectionWriter()
    w.add("ids", pack_joint_ids(index._lists, index.n))
    w.add("centroids", index.centroids.astype(np.float16).tobytes())
    code_meta = None
    if getattr(index, "_code_blob", None) is not None:
        # v1 manifests carry only {"m"} for the polya payload
        code_meta = {"m": pack_polya_sections(w, index._code_blob)["m"]}
    elif index.pq is not None:
        w.add("codes_raw", index.payload.host().tobytes())
        code_meta = {"m": int(index.payload.shape[1]), "raw": True}
    return w.finish(_MAGIC, _VERSION, {
        "n": int(index.n), "d": int(index.d), "nlist": int(index.nlist),
        "sizes": sizes, "code": code_meta,
        "pq_m": int(index.pq.m) if index.pq else 0,
    })


def unpack_ivf(raw: bytes):
    """Returns (manifest, lists, centroids, codes|None)."""
    r = SectionReader(raw, _MAGIC)
    assert r.version == _VERSION
    manifest = r.manifest
    n, nlist = manifest["n"], manifest["nlist"]
    sizes = manifest["sizes"]
    lists = unpack_joint_ids(r.section("ids"), sizes, n)
    cents = np.frombuffer(r.section("centroids"), np.float16).reshape(
        nlist, manifest["d"]).astype(np.float32)
    codes = None
    cm = manifest["code"]
    if cm and cm.get("raw"):
        codes = np.frombuffer(r.section("codes_raw"), np.uint8).reshape(-1, cm["m"])
    elif cm:
        heads = np.frombuffer(r.section("code_heads"), np.uint64)
        lens = np.frombuffer(r.section("code_word_lens"), np.int64)
        flat = np.frombuffer(r.section("code_words"), np.uint32)
        words, off = [], 0
        for ln in lens:
            words.append(flat[off:off + ln])
            off += ln
        per = polya_decode_clusters(heads, words, sizes, cm["m"])
        codes = np.concatenate([c for c in per], axis=0)
    return manifest, lists, cents, codes
