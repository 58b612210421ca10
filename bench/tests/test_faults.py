"""The comparison fails the control and a timed path broken underneath.

The control is the reference one precision below float32 (bfloat16) in
the program's place; the faults are planted in the program while a
rehearsal run drives it: an id altered where it is resolved, and half of
each flushed batch left unsearched.
"""

import json

import numpy as np
import pytest

from bench import run as bench_run
from bench.control import control_numbers

N = 6000
CELLS = ["sift1m-flat.batch", "sift1m-pq8.batch"]


def rehearse(capsys, cell, seed=11):
    bench_run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                    "1", "--trace", "0", "--rehearse-n", str(N)])
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    numbers = control_numbers(cell, seed=3, rehearse_n=N)
    assert numbers["correct"] is False
    assert numbers["wrong_dists"] > 0.5


@pytest.mark.parametrize("cell", CELLS)
def test_altered_id_is_caught(capsys, monkeypatch, cell):
    import repro.ann.scan as scan

    resolve = scan.resolve_ids_batch

    def off_by_one(index, clusters, offsets):
        return resolve(index, clusters, offsets) + 1

    monkeypatch.setattr(scan, "resolve_ids_batch", off_by_one)
    res = rehearse(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["wrong_ids"]["value"] > 0.5


@pytest.mark.parametrize("cell", CELLS)
def test_half_batch_left_out_is_caught(capsys, monkeypatch, cell):
    from repro.api.indexes import IVFApiIndex

    search = IVFApiIndex.search

    def first_half(self, queries, k=10, **kw):
        half = (len(queries) + 1) // 2
        d, i, st = search(self, queries[:half], k=k, **kw)
        dists = np.full((len(queries), k), np.inf, np.float32)
        ids = np.zeros((len(queries), k), np.int64)
        dists[:half], ids[:half] = d, i
        return dists, ids, st

    monkeypatch.setattr(IVFApiIndex, "search", first_half)
    res = rehearse(capsys, cell)
    assert res["correct"] is False
    assert res["checks"]["wrong_ids"]["value"] > 0.2
