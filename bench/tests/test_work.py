"""``bench/work.py`` against a hand count on a toy index."""

import numpy as np
import pytest

from bench import work


def test_flat_counts_by_hand():
    sizes = np.array([3, 0, 5, 2])
    # flush 1: q0 probes lists 0, 2; q1 probes 2, 3 -> candidates 8 + 7,
    # distinct rows 3 + 5 + 2; flush 2: q2 probes 1, 3 -> 0 + 2
    flushes = [np.array([[0, 2], [2, 3]]), np.array([[1, 3]])]
    w = work.scoring_work(flushes, sizes, d=4)
    assert w["ops"] == 2 * 4 * (8 + 7 + 2)
    assert w["bytes"] == (10 + 2) * 4 * 4


def test_pq_counts_by_hand():
    sizes = np.array([3, 0, 5, 2])
    flushes = [np.array([[0, 2], [2, 3]])]
    w = work.scoring_work(flushes, sizes, d=4, pq_m=2, ksub=8)
    assert w["ops"] == 2 * 15
    assert w["bytes"] == 10 * 2 + 2 * 2 * 8 * 4


def test_least_seconds_names_the_roof():
    pk = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"ops": 1000.0, "bytes": 1.0}, pk) == (10.0, "compute")
    assert work.least_seconds({"ops": 1.0, "bytes": 50.0}, pk) == (5.0, "hbm_bytes")


def test_unknown_device_kind_is_an_error():
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peak("cpu")
