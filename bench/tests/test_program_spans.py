"""The program's own spans and stage counters, read by a traced rehearsal.

A traced rehearsal of each batch cell must report the per-layer metrics
that read the scan's stage counters, and its trace must hold the spans
the program emits inside ``scan.search`` and ``ids.resolve``.  At the
rehearsal's size the select runs on the host (``SELECT_MIN_CPU``), so
``scan.select_calls_per_block`` has nothing to read there.  A CPU trace
has no device plane, so idle gaps are not checked.
"""

import json

import pytest

from bench import run as bench_run
from bench.trace import find_xplane, reduce_trace

N = 6000
METRICS = ("scan.arena_ms_per_query", "scan.upload_ms_per_query",
           "scan.upload_mb_per_query", "scan.select_ms_per_query",
           "scan.rescore_ms_per_query", "ids.decode_ms_per_query",
           "scan.new_shapes")
SPANS = ("scan.arena", "scan.upload", "scan.select", "scan.rescore",
         "ids.decode")


@pytest.mark.parametrize("cell", ["sift1m-flat.batch", "sift1m-pq8.batch"])
def test_traced_rehearsal_reads_program_stages(capsys, cell):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 29),
                         "--seconds", "1", "--trace", "1",
                         "--rehearse-n", str(N)])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    for name in METRICS:
        assert res["metrics"][name]["value"] >= 0, name
    for name in METRICS[:-1]:
        assert res["metrics"][name]["value"] > 0, name
    assert "scan.select_calls_per_block" not in res["metrics"]
    names = {s[0] for s in reduce_trace(find_xplane(bench_run.TRACE_DIR))
             .spans}
    assert set(SPANS) <= names, sorted(names)
