"""``ids.decode_us_per_id``: the decode's time per id produced.

The reader must leave its metric out where the program does not count the
ids its decodes produce (a program without ``SearchStats.decode_ids``), and
a traced rehearsal of a batch cell must report it.
"""

import json
from types import SimpleNamespace

import pytest

from bench import run as bench_run


def _run_of(*stats):
    flushes = [SimpleNamespace(stats=SimpleNamespace(**st)) for st in stats]
    return SimpleNamespace(window=SimpleNamespace(flushes=flushes,
                                                  queries=64))


def test_decode_us_per_id_reads_nothing_without_the_field():
    read = bench_run.load_reader("ids.decode_us_per_id")
    # a program that times decodes but does not count their ids
    assert read(_run_of({"decode_s": 0.5}, {"decode_s": 0.25})) is None
    # a window that decoded nothing
    assert read(_run_of({"decode_s": 0.0, "decode_ids": 0})) is None
    assert read(_run_of({"decode_s": 0.002, "decode_ids": 900},
                        {"decode_s": 0.001, "decode_ids": 600})) == \
        pytest.approx(2.0)


def test_traced_rehearsal_reads_decode_us_per_id(capsys):
    rc = bench_run.main(["--workload", "sift1m-pq8.batch",
                         "--seed", str(2**31 + 31), "--seconds", "1",
                         "--trace", "1", "--rehearse-n", "6000"])
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and res["correct"] is True
    assert res["metrics"]["ids.decode_us_per_id"]["value"] > 0
