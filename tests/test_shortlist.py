"""The re-score shortlist band (``repro.ann.scan._shortlist``), directly.

Each case is a block of kernel distances in candidate-column order, with
``+inf`` in the padding columns.  A fake select returns its rows sorted
ascending and cut to ``K`` columns, as the device select does, and logs
each ``K`` asked for: a second ``K`` means the band forced a retry.
"""

import numpy as np
import pytest

from repro.ann.scan import RESCORE_SLACK, _shortlist, rescore_eps

INF = np.inf


def _select(rows, ks=None, nvalid=None):
    """``select(K)`` over ``rows``: ``(vals, cols)`` ascending, ``K`` wide.

    ``ks=None`` gives the host form (whole sorted rows, ``K`` ignored).
    With ``nvalid``, equal distances among a row's real columns break to
    the higher column, a tie order the band must not depend on; padding
    columns still sort after the real ones, as the device select's
    segment lengths make them.
    """
    rows = np.asarray(rows, np.float32)
    cols = np.broadcast_to(np.arange(rows.shape[1]), rows.shape)
    tie = cols
    if nvalid is not None:
        tie = np.where(cols < nvalid[:, None], -cols, cols + rows.shape[1])
    order = np.lexsort((tie, rows), axis=-1)
    vals = np.take_along_axis(rows, order, axis=1)

    def select(K):
        if ks is None:
            return vals, order
        ks.append(K)
        return vals[:, :K], order[:, :K]

    return select


def _edge(bound, d, qn):
    """``bound`` plus its band: the largest distance the cut keeps."""
    return np.float32(bound + rescore_eps(d, bound, qn))


# one f32 ulp at 127 is 2^-17; with d = 1 the band at 127 is exactly
# 2^-12 for qn = 0 and 2^-11 for qn = 128, both f32-representable
_PQ_EDGE = 127.0 + 2.0**-12
_FLAT_EDGE = 127.0 + 2.0**-11
_ULP = 2.0**-17
_PERM = [31, 4, 17, 0, 22, 9, 38, 13, 26, 2, 35, 19, 7, 29, 11, 24, 36,
         1, 15, 33, 6, 20, 27, 10, 39, 3, 18, 30, 8, 25, 14, 37, 5, 21,
         34, 12, 28, 16, 23, 32]

CASES = {
    # values 0..39 scattered over the columns: take = 10, bound = 9
    "band_inside_k": dict(
        rows=[np.array(_PERM, float)], nvalid=[40], topk=2, qn=[0.0],
        width=64, ks=[16], bounds=[9.0],
        kept=[sorted(_PERM.index(v) for v in range(10))]),
    # twenty ties at the bound fill column K-1 = 15 of 40: K doubles once
    "row_k_minus_1_in_band_retries": dict(
        rows=[list(range(9)) + [9.0] * 20 + list(range(30, 41))],
        nvalid=[40], topk=2, qn=[0.0], width=64, ks=[16, 32],
        bounds=[9.0], kept=[list(range(29))]),
    # all sixteen candidates tie through column K-1, but nvalid <= K:
    # nothing lies past K
    "nvalid_at_most_k_never_retries": dict(
        rows=[[1.0] * 16 + [INF] * 48], nvalid=[16], topk=2, qn=[0.0],
        width=64, ks=[16], bounds=[1.0], kept=[list(range(16))]),
    # fewer candidates than topk + RESCORE_SLACK: take is nvalid
    "nvalid_below_topk_plus_slack": dict(
        rows=[[5.0, 3.0, 4.0, 1.0, 2.0] + [INF] * 11], nvalid=[5],
        topk=2, qn=[0.0], width=16, ks=[16], bounds=[5.0],
        kept=[list(range(5))]),
    # an empty row keeps nothing and never forces a retry
    "empty_row": dict(
        rows=[[INF] * 16, [2.0, 1.0, 3.0] + [INF] * 13], nvalid=[0, 3],
        topk=1, qn=[0.0, 0.0], width=16, ks=[16], bounds=[None, 3.0],
        kept=[[], [0, 1, 2]]),
    # a real +inf distance keeps its column; the +inf padding is dropped
    "padding_inf_past_nvalid": dict(
        rows=[[2.0, 1.0, INF, 0.5, 3.0, 4.0] + [INF] * 10], nvalid=[6],
        topk=1, qn=[0.0], width=16, ks=[16], bounds=[INF],
        kept=[list(range(6))]),
    # PQ (qn = 0): ties at the bound and a distance exactly on the band's
    # edge stay in; one ulp past the edge goes out
    "edge_ties_pq": dict(
        rows=[list(range(9)) + [127.0, _PQ_EDGE + _ULP, 127.0, _PQ_EDGE,
                                200.0]],
        nvalid=[14], topk=2, qn=[0.0], width=16, ks=[16], bounds=[127.0],
        kept=[list(range(9)) + [9, 11, 12]]),
    # flat (qn > 0 widens the band): the same edge at the wider band
    "edge_ties_flat": dict(
        rows=[list(range(9)) + [_FLAT_EDGE, 127.0, _FLAT_EDGE + _ULP,
                                127.0, 300.0]],
        nvalid=[14], topk=2, qn=[128.0], width=16, ks=[16],
        bounds=[127.0], kept=[list(range(9)) + [9, 10, 12]]),
    # ties reach past K twice: K doubles until the row fits
    "retry_doubles_until_row_fits": dict(
        rows=[[7.0] * 48 + [INF] * 16], nvalid=[48], topk=2, qn=[0.0],
        width=64, ks=[16, 32, 64], bounds=[7.0], kept=[list(range(48))]),
    # one row's band forces the retry for the whole block
    "one_row_retries_the_block": dict(
        rows=[list(range(20)) + [INF] * 12, [0.0] * 20 + [INF] * 12],
        nvalid=[20, 20], topk=2, qn=[0.0, 0.0], width=32, ks=[16, 32],
        bounds=[9.0, 0.0], kept=[list(range(10)), list(range(20))]),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_shortlist_band(name):
    case = CASES[name]
    rows = np.asarray(case["rows"], np.float32)
    nvalid = np.asarray(case["nvalid"])
    qn = np.asarray(case["qn"], np.float32)
    d = 1
    ks = []
    (vals, cols), thr, cut = _shortlist(
        _select(rows, ks), nvalid, case["topk"], d, qn, case["width"])
    assert ks == case["ks"]
    for i, bound in enumerate(case["bounds"]):
        if bound is None:
            assert thr[i] == -np.inf
        else:
            assert thr[i] == bound + rescore_eps(d, bound, float(qn[i]))
            assert vals[i, min(case["topk"] + RESCORE_SLACK,
                               nvalid[i]) - 1] == bound
        assert cols[i, cut(i)].tolist() == case["kept"][i]


def test_shortlist_edge_is_kept():
    """The edge cases above sit exactly on the band's edge."""
    assert _edge(127.0, 1, 0.0) == np.float32(_PQ_EDGE)
    assert _edge(127.0, 1, 128.0) == np.float32(_FLAT_EDGE)


@pytest.mark.parametrize("seed", range(4))
def test_shortlist_host_and_device_forms_agree(seed):
    rng = np.random.default_rng(seed)
    qb, width, d, topk = 24, 128, 16, int(rng.integers(1, 30))
    nvalid = rng.integers(0, width + 1, qb)
    nvalid[:3] = [0, 3, width]
    # few distinct values, near-duplicates a band apart, a real +inf
    rows = rng.integers(0, 12, (qb, width)).astype(np.float32)
    near = rng.random((qb, width)) < 0.3
    rows[near] += np.float32(rescore_eps(d, 12.0, 4.0)) * rng.choice(
        [0.5, 1.0, 2.0], near.sum()).astype(np.float32)
    rows[rng.random((qb, width)) < 0.02] = np.inf
    rows[np.arange(width)[None] >= nvalid[:, None]] = np.inf
    qn = rng.random(qb).astype(np.float32) * 4
    host = _shortlist(_select(rows), nvalid, topk, d, qn, width)
    ks = []
    dev = _shortlist(_select(rows, ks, nvalid), nvalid, topk,
                     d, qn, width)
    np.testing.assert_array_equal(host[1], dev[1])
    for i in range(qb):
        kept_host = host[0][1][i, host[2](i)]
        kept_dev = dev[0][1][i, dev[2](i)]
        np.testing.assert_array_equal(kept_host, kept_dev)
        assert (kept_host < nvalid[i]).all()
        assert (np.diff(kept_host) > 0).all()
