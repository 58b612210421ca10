"""Operations and bytes the scoring stage needs, from the work itself.

Counted from the reference's probes and list sizes, never from padded
shapes, so a kernel rewrite cannot make a count stale:

* flat payload: each query scores every row of its probed lists,
  ``2*d`` operations a row; a flush must read each row of the distinct
  lists its queries probe once, ``4*d`` bytes a row;
* PQ payload (``m`` one-byte codes, ``ksub`` codewords): ``m`` table
  additions a candidate row; a flush reads each distinct probed row's
  ``m`` bytes once, plus the queries' look-up tables, ``m*ksub*4`` bytes
  a query.

The least time is the larger of operations over the peak rate and bytes
over the peak bandwidth.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

import numpy as np

__all__ = ["scoring_work", "least_seconds", "peak"]

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def scoring_work(flush_probes: Iterable[np.ndarray], sizes: np.ndarray,
                 d: int, pq_m: int = 0, ksub: int = 256) -> Dict[str, float]:
    """``flush_probes``: one ``(queries, nprobe)`` array of list ids per flush."""
    sizes = np.asarray(sizes, np.int64)
    ops = nbytes = 0
    for probes in flush_probes:
        probes = np.asarray(probes)
        if probes.size == 0:
            continue
        cand = int(sizes[probes].sum())
        distinct = int(sizes[np.unique(probes)].sum())
        if pq_m:
            ops += pq_m * cand
            nbytes += distinct * pq_m + probes.shape[0] * pq_m * ksub * 4
        else:
            ops += 2 * d * cand
            nbytes += distinct * d * 4
    return {"ops": float(ops), "bytes": float(nbytes)}


def peak(device_kind: str) -> Dict[str, float]:
    """The peak table's row for ``device_kind``; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return table[device_kind]


def least_seconds(work: Dict[str, float], pk: Dict[str, float]):
    """``(seconds, bound)``: the roofline time and which roof sets it."""
    t_ops = work["ops"] / pk["flops_per_s"]
    t_bytes = work["bytes"] / pk["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "hbm_bytes")
