"""Each cell, rehearsed on the CPU at a tiny base through ``--rehearse-n``.

Drives the whole run (data, training, build, warm-up, closed-loop window,
reference comparison, metric readers) and reads its last line.
"""

import json

import pytest

from bench import run as bench_run

CELLS = [w["name"] for w in json.loads(
    (bench_run.ROOT / "BENCHMARK.json").read_text())["workloads"]]
N = 6000


def rehearse(capsys, cell, seed, trace=0, seconds=1.0):
    rc = bench_run.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", str(seconds), "--trace", str(trace),
                         "--rehearse-n", str(N)])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearsal_is_correct(capsys, cell):
    info = bench_run.load_cell(cell)
    rc, res = rehearse(capsys, cell, seed=2**31 + 17)
    assert rc == 0
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {m["name"] for m in info["end_to_end"]}
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    assert res["checks"]["wrong_ids"]["value"] == 0.0


def test_traced_rehearsal_reports_program_metrics(capsys):
    rc, res = rehearse(capsys, CELLS[0], seed=5, trace=1)
    assert rc == 0 and res["correct"] is True
    # the CPU trace has no device plane: device metrics are left out
    for name in ("serve.batch_queries", "scan.ms_per_query",
                 "scan.ndis_per_query", "ids.resolve_share",
                 "ids.decodes_per_query"):
        assert res["metrics"][name]["value"] > 0
    assert res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs():
    from bench.data import make_base, order, query_pool

    data = bench_run.load_cell(CELLS[0])["config"]["data"]
    a = make_base(data, 2000, 2**32 + 5)
    assert (a == make_base(data, 2000, 2**32 + 5)).all()
    assert not (a == make_base(data, 2000, 2**32 + 6)).all()
    qa = query_pool(data, 64, {"kind": "data"}, stream=0)
    assert (qa == query_pool(data, 64, {"kind": "data"}, stream=0)).all()
    assert not (qa == query_pool(data, 64, {"kind": "data"}, stream=1)).all()
    assert (order(64, 2**32 + 5) == order(64, 2**32 + 5)).all()
    assert sorted(order(64, 7)) == list(range(64))
