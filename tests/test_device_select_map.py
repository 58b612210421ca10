"""The device select's candidate->arena map against the host's.

``_device_selector().run`` rebuilds, on the device, the map from each
query's candidate columns to arena positions that the host builds as
``cand_pos`` from ``_spans_concat`` (probe order, zero-size probes
skipped).  These tests hold the device map to that independent host map
on awkward block shapes, and hold the traced program free of a search
loop.
"""

import functools

import jax
import numpy as np
import pytest

from repro.ann.scan import (SHORTLIST_ROWS, _bucket, _device_selector,
                            _spans_concat)

jax.config.update("jax_platforms", "cpu")


def _block(rng, P, qb, pad_rows, zero_mode, wide):
    """One select block as ``batched_search`` lays it out.

    Returns ``(dmat, probes, start_of, size_of, c_pad, lens, cand_pos)``;
    ``cand_pos`` is the host's map, -1 past each row's length.  Row 0 opens
    and closes on zero-size probes (where ``P`` allows) and its total is
    exactly ``c_pad`` (a bucket edge) unless ``wide`` doubles the edge;
    other rows draw zero-size probes too, first and last included.  The
    ``pad_rows`` rows past ``qb`` probe cluster 0 ``P`` times, as the
    scan's padding rows do; ``zero_mode`` makes cluster 0 probed and
    non-empty ("probed"), empty ("empty"), or non-empty but in no real
    row, so absent from the arena ("unprobed").
    """
    nlist = 2 * P + 40
    sizes = rng.integers(1, 12, nlist)
    sizes[rng.random(nlist) < 0.2] = 0
    sizes[0] = 0 if zero_mode == "empty" else 5
    edge_cl = nlist - 1                      # sized last; row 0's alone
    sizes[1:3] = 0                           # two empty clusters at least
    empty = np.flatnonzero(sizes == 0)
    others = np.arange(1 if zero_mode == "unprobed" else 0, nlist - 1)
    probes = np.stack([rng.choice(others, P, replace=False)
                       for _ in range(qb)])
    for row in probes[1:]:
        if rng.random() < 0.5 and empty[0] not in row:
            row[0] = empty[0]
        if rng.random() < 0.5 and empty[1] not in row:
            row[-1] = empty[1]
    if zero_mode == "probed" and 0 not in probes[1]:
        probes[1, P // 2] = 0
    full = np.flatnonzero(sizes[1:nlist - 1] > 0) + 1
    ends = [1, 2] if P >= 3 else ([1] if P == 2 else [])
    mid = rng.choice(full, P - 1 - len(ends), replace=False)
    row0 = [edge_cl] if P == 1 else (
        [1, edge_cl] if P == 2 else [1, *mid, edge_cl, 2])
    probes[0] = row0
    other_lens = sizes[probes[1:]].sum(axis=1)
    partial = int(sizes[mid].sum())
    edge = _bucket(max(int(other_lens.max(initial=0)), partial + 1),
                   floor=128)
    sizes[edge_cl] = edge - partial
    qb_pad = qb + pad_rows
    probes_pad = np.zeros((qb_pad, P), np.int64)
    probes_pad[:qb] = probes

    # arena: the block's distinct non-empty clusters, concatenated
    uniq = np.unique(probes)
    uniq = uniq[sizes[uniq] > 0]
    arena_start = np.cumsum(sizes[uniq]) - sizes[uniq]
    start_of = np.full(nlist, -1, np.int64)
    size_of = np.zeros(nlist, np.int64)
    start_of[uniq] = arena_start
    size_of[uniq] = sizes[uniq]
    u_pad = _bucket(int(sizes[uniq].sum()), floor=128)

    pp = size_of[probes_pad]
    lens = pp.sum(axis=1)
    # c_pad from the real rows, as the scan buckets it
    c_pad = _bucket(int(lens[:qb].max()), floor=128) * (2 if wide else 1)
    assert lens[0] == edge == c_pad // (2 if wide else 1)
    assert (lens[1:qb] <= edge).all()
    cand_pos = np.full((qb_pad, c_pad), -1, np.int64)
    for i in range(qb_pad):
        row = _spans_concat(start_of[probes_pad[i]], pp[i])[:c_pad]
        cand_pos[i, :row.size] = row
    dmat = rng.standard_normal((qb_pad, u_pad)).astype(np.float32)
    return (dmat, probes_pad.astype(np.int32),
            np.maximum(start_of, 0).astype(np.int32),
            size_of.astype(np.int32), c_pad, lens, cand_pos)


@pytest.mark.parametrize("case", [
    # (pad_rows, cluster 0, wide c_pad, k = c_pad)
    (0, "probed", False, False),
    (3, "empty", False, True),
    (5, "unprobed", True, False),
    (2, "probed", True, True),
])
@pytest.mark.parametrize("P", [1, 2, 16, 64])
def test_device_map_matches_host_spans(P, case):
    pad_rows, zero_mode, wide, k_all = case
    rng = np.random.default_rng(7 * P + pad_rows)
    dmat, probes, start_of, size_of, c_pad, lens, cand_pos = _block(
        rng, P, qb=6, pad_rows=pad_rows, zero_mode=zero_mode, wide=wide)
    k = c_pad if k_all else 32            # k = c_pad: every padding slot
    payload = rng.standard_normal((500, 4)).astype(np.float32)
    arena_rows = rng.integers(0, 500, dmat.shape[1]).astype(np.int32)
    vals, cols, pos_sel, rows_sel = (np.asarray(a) for a in _device_selector()(
        dmat, probes, start_of, size_of, payload, arena_rows, c_pad=c_pad,
        k=k, engine="xla", interpret=None))
    # the payload rows of each row's first columns ride along
    np.testing.assert_array_equal(
        rows_sel, payload[arena_rows[pos_sel[:, :min(k, SHORTLIST_ROWS)]]])

    # reference: gather through the host's map, then a stable ascending
    # (value, column) cut of each row
    rows = np.arange(dmat.shape[0])[:, None]
    valid = cand_pos >= 0
    d_ref = np.where(valid, dmat[rows, np.maximum(cand_pos, 0)], np.inf)
    cols_ref = np.argsort(d_ref, axis=1, kind="stable")[:, :k]
    np.testing.assert_array_equal(cols, cols_ref)
    np.testing.assert_array_equal(
        vals, np.take_along_axis(d_ref, cols_ref, axis=1))
    real = cols < lens[:, None]
    np.testing.assert_array_equal(real.sum(axis=1),
                                  np.minimum(k, np.minimum(lens, c_pad)))
    np.testing.assert_array_equal(
        pos_sel[real], np.take_along_axis(cand_pos, cols, axis=1)[real])


@pytest.mark.parametrize("engine", ["xla", "pallas"])
def test_device_map_traces_no_loop(engine):
    """The map is dense vector work: the traced select program holds no
    loop (``searchsorted``'s ``scan``) outside the top-k kernel, and one
    gather over the ``(qb, c_pad)`` block, the f32 distance gather."""
    qb, c_pad = 64, 32768
    run = functools.partial(_device_selector(), c_pad=c_pad, k=32,
                            engine=engine, interpret=True)
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(run)(sds((qb, 1 << 19), np.float32),
                                sds((qb, 16), np.int32),
                                sds((1024,), np.int32),
                                sds((1024,), np.int32),
                                sds((1 << 20, 128), np.float32),
                                sds((1 << 19,), np.int32))

    def eqns(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                continue        # the kernel's own loops are seg_topk's
            yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from eqns(sub)

    found = list(eqns(jaxpr.jaxpr))
    assert not {e.primitive.name for e in found} & {"while", "scan"}
    wide = [e.outvars[0].aval.dtype for e in found
            if e.primitive.name == "gather"
            and e.outvars[0].aval.size == qb * c_pad]
    assert wide == [np.float32]
