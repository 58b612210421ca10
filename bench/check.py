"""The comparison that decides ``correct``.

Served answers are held against ``bench/reference.py`` query by query:

* ``wrong_ids``: the share of the reference's top-k slots whose id the
  served answer lacks (an id outside the reference's top-k, a missing
  result, or a duplicate);
* ``wrong_dists``: the share of served results whose distance differs
  from the reference's distance of the same id by more than
  ``DIST_TOL`` of it (at least of 1.0).  Float32 arithmetic in the
  program stays some thousand times inside that band; a payload or a
  table rounded to bfloat16 leaves it.

Each number has its limit in the configuration file (``correct``); the
run is correct when every number is at or under its limit and every
request was answered.
"""

from __future__ import annotations

import numpy as np

__all__ = ["DIST_TOL", "compare", "verdict"]

DIST_TOL = 1e-4


def compare(ref, queries: np.ndarray, ids: np.ndarray,
            dists: np.ndarray) -> dict:
    """Numbers of served ``(ids, dists)`` for ``queries`` against ``ref``."""
    ref_ids, _ = ref.search(queries)
    n = ref.x.shape[0]
    miss = slots = bad_d = served = 0
    for i in range(len(queries)):
        want = ref_ids[i][ref_ids[i] >= 0]
        ok = np.isfinite(dists[i])
        got = ids[i][ok]
        valid = (got >= 0) & (got < n)
        slots += len(want)
        miss += len(want) - len(np.intersect1d(np.unique(got[valid]), want))
        served += len(got)
        bad_d += int(np.count_nonzero(~valid))
        if valid.any():
            exact = ref.distance(queries[i], got[valid])
            gap = np.abs(dists[i][ok][valid].astype(np.float64) - exact)
            bad_d += int(np.count_nonzero(
                gap > DIST_TOL * np.maximum(np.abs(exact), 1.0)))
    return {"wrong_ids": miss / max(slots, 1),
            "wrong_dists": bad_d / max(served, 1),
            "compared_queries": len(queries)}


def verdict(numbers: dict, limits: dict, unanswered: int) -> bool:
    return unanswered == 0 and all(
        numbers[name] <= limit for name, limit in limits.items())
