"""Benchmark orchestrator — one module per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines (the scaffold contract) and
writes structured JSON to experiments/results/.  ``--quick`` shrinks data
sizes for smoke use; default sizes reproduce the paper-comparable numbers.
A suite that raises is reported and the remaining suites still run, but
the command then exits non-zero.
"""

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: table1 table2 table3 table4 fig3 codec "
                         "graph spec shard ingest select")
    ap.add_argument("--spec", action="append", default=None,
                    help="factory spec string for the 'spec' suite "
                         "(repeatable); implies --only spec when --only is "
                         "not given")
    args = ap.parse_args()

    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    from . import (codec_speed, fig3_code_compression, graph_bench,
                   ingest_bench, select_bench, shard_bench, spec_bench,
                   table1_bpe, table2_search_time, table3_offline_graph,
                   table4_large_scale)

    suites = {
        "table1": table1_bpe.main,
        "table2": table2_search_time.main,
        "table3": table3_offline_graph.main,
        "table4": table4_large_scale.main,
        "fig3": fig3_code_compression.main,
        "codec": codec_speed.main,
        "graph": graph_bench.main,
        "shard": shard_bench.main,
        "ingest": ingest_bench.main,
        "select": select_bench.main,
        "spec": lambda quick=False: spec_bench.main(quick=quick,
                                                    specs=args.spec),
    }
    chosen = args.only or (["spec"] if args.spec else
                           [n for n in suites if n != "spec"])
    failed = []
    for name in chosen:
        t0 = time.time()
        print(f"# --- {name} ---", flush=True)
        try:
            suites[name](quick=args.quick)
        except Exception as e:  # noqa: BLE001 - reported, fails the command
            traceback.print_exc()
            print(f"{name}/FAILED,0.0,{type(e).__name__}:{e}", flush=True)
            failed.append(name)
            continue
        print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
    if failed:
        sys.exit(f"failed suites: {', '.join(failed)}")


if __name__ == "__main__":
    main()
