"""``bench/trace.py`` on a small trace recorded on a v5e.

``data/single.xplane.pb`` is a three-second traced window of the
single-client flat cell on one v5e, with the device top-k select.  The reduction's busy time is
checked against a plain microsecond timeline of the same events.
"""

from pathlib import Path

import numpy as np
import pytest

from bench import trace

XPLANE = Path(__file__).parent / "data" / "single.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return trace.reduce_trace(XPLANE)


def test_window_is_the_benchmark_span(tr):
    spans = [s for s in tr.spans if s[0] == trace.WINDOW_SPAN]
    assert len(spans) == 1
    assert tr.window == spans[0][1:]
    assert 1.5 < tr.window_s < 10


def test_busy_time_matches_a_timeline(tr):
    (plane, ops), = tr.ops.items()
    assert plane.startswith("/device:TPU")
    w0, w1 = tr.window
    line = np.zeros((w1 - w0) // 1000 + 1, bool)
    for o in ops:
        line[(o.start - w0) // 1000:(o.end - w0 + 999) // 1000] = True
    busy_us = line.sum()
    assert tr.busy_s() == pytest.approx(busy_us * 1e-6, rel=0.05, abs=2e-4)
    assert 0 < tr.busy_s() < tr.window_s


def test_kernels_are_found(tr):
    assert tr.kernel_s(("l2_dist",)) > 0
    assert tr.kernel_s(("seg_topk",)) > 0
    assert tr.kernel_s(("no-such-kernel",)) == 0


def test_idle_gaps_cover_the_idle_time(tr):
    idle = sum(s for _, s in tr.idle_gaps(n=1000))
    assert idle == pytest.approx(tr.window_s - tr.busy_s(), rel=1e-6, abs=1e-6)
    names = [n for n, _ in tr.idle_gaps()]
    assert "scan.search" in names
    assert len(tr.top_ops()) <= 10
