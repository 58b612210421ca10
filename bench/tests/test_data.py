"""The generated base has the IVF shape of a SIFT-like set, at a size a
test run holds: 131,072 rows in 128 lists of ~1,024 (the full size's list
length), 2 probes (the full size's 1/64 of the lists).

* the k-means lists are balanced: imbalance factor
  ``nlist * sum(size^2) / n^2`` at most 1.3;
* a query's probes cover 0.8-1.4 times ``nprobe * n / nlist`` rows;
* both hold alike for two seeds of the rows: the seed changes the rows,
  not the shape.

A base of tight modes that k-means merges (the generator this benchmark
once had) fails this: its lists read an imbalance of 2.3 here, and its
probes cover 2.6 times the nominal rows (about 7 times at 1M rows).
"""

import numpy as np
import pytest

from bench import run as bench_run
from bench.data import make_base, query_pool
from bench.reference import nearest
from bench.train import kmeans, sample_rows

N, NLIST, NPROBE = 131072, 128, 2


def ivf_shape(seed):
    data = bench_run.load_cell("sift1m-flat.batch")["config"]["data"]
    base = make_base(data, N, seed)
    q = query_pool(data, 256, {"kind": "data"}, stream=0)
    c = kmeans(base[sample_rows(N, 256 * NLIST, seed)], NLIST, 20, seed)
    sizes = np.bincount(nearest(base, c), minlength=NLIST)
    imbalance = NLIST * float((sizes.astype(np.float64) ** 2).sum()) / N ** 2
    probes = np.argsort(((q[:, None] - c[None]) ** 2).sum(-1),
                        axis=1)[:, :NPROBE]
    cover = sizes[probes].sum(axis=1).mean() / (NPROBE * N / NLIST)
    return imbalance, cover


@pytest.mark.parametrize("seed", [5, 2**32 + 9])
def test_lists_balanced_and_probes_cover_nominal_rows(seed):
    imbalance, cover = ivf_shape(seed)
    assert imbalance <= 1.3
    assert 0.8 <= cover <= 1.4
