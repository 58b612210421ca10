#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload <cell> --seeds <a,b,c>

Puts the reference in the program's place computed one precision below
the configuration's float32: the base, centroids, codebooks and queries
rounded to bfloat16, with float32 arithmetic (``bench/reference.py``,
``precision="bfloat16"``).  For each seed it builds the cell's data and
quantizers as a run does, answers the first ``COMPARE_QUERIES`` queries
of the window's pool with the control, and holds those answers against
the float32 reference with ``bench/check.py``.  The control must come
out not correct; its numbers are the upper readings the configuration's
limits are set below.  The benchmark's own runs never run this.

Prints one line per seed and, last, one JSON object with every reading.
``--rehearse-n`` runs it off the chip at that many rows (self-tests).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def control_numbers(cell_name: str, seed: int, rehearse_n=None) -> dict:
    from bench import check
    from bench.data import make_base, order, query_pool
    from bench.reference import Reference
    from bench.run import COMPARE_QUERIES, load_cell, train

    info = load_cell(cell_name)
    config, traffic = info["config"], info["traffic"]
    data = config["data"]
    n = rehearse_n or data["n"]
    base = make_base(data, n, seed)
    pool = query_pool(data, traffic["pool_queries"], traffic["topics"],
                      stream=0)
    queries = pool[order(len(pool), seed)[:COMPARE_QUERIES]]
    centroids, codebooks = train(config, base, seed)
    srch = config["search"]
    exact = Reference(base, centroids, srch["nprobe"], srch["k"], codebooks)
    low = Reference(base, centroids, srch["nprobe"], srch["k"], codebooks,
                    precision="bfloat16")
    ids, dists = low.search(queries)
    numbers = check.compare(exact, queries, ids, dists.astype("float32"))
    numbers["correct"] = check.verdict(numbers, config["correct"], 0)
    return numbers


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse-n", type=int, default=None)
    args = ap.parse_args(argv)
    from bench.run import _import_path

    _import_path()
    import jax

    if args.rehearse_n is None and jax.devices()[0].platform != "tpu":
        raise SystemExit("control.py: no TPU; use --rehearse-n off the chip")
    out = {}
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        out[seed] = control_numbers(args.workload, seed, args.rehearse_n)
        print(f"control {args.workload} seed {seed}: {out[seed]} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    print(json.dumps({"workload": args.workload, "control": out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(ROOT))
    sys.exit(main())
