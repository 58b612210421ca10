#!/usr/bin/env python3
"""Run one benchmark cell of compressed-id ANN serving on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process does everything.  From ``--seed`` it draws the base rows
from the configuration's fixed SIFT-shaped distribution
(``bench/data.py``), trains the coarse centroids and PQ codebooks on
samples of them (``bench/train.py``), and orders the fixed query pool
and the request sizes.  It builds the index through
``repro.api.index_factory(spec).build`` and serves it through
``repro.serve.AnnService`` with closed-loop clients (``bench/loop.py``).
It warms up on a query stream of its own, first on blocks of 1, 2, 4, ...
distinct queries and on blocks holding far-off queries (so every padded
shape and shortlist width the window can meet is compiled), then until a
pass of its traffic loads nothing new,
then measures for ``--seconds``: the window opens at the first submit and
closes when the first flush that ends after ``--seconds`` completes.

After the window it reads the peak device memory, counts the bytes the
index holds (``bench/footprint.py``), frees the program's state, and
holds a sample of the
window's answers against the plain reference (``bench/reference.py``,
``bench/check.py``).  The cell's configuration, traffic mix and metrics
are found by name: ``BENCHMARK.json`` names the configuration file,
``bench/traffic/<traffic>.json`` the mix, ``bench/metrics/<metric>.py``
each metric's reader.  With ``--trace 0`` the end-to-end metrics are
reported; with ``--trace 1`` the window is traced with the JAX profiler
and the per-layer metrics are reported.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown``,
``checks``); the numbers compared are also the last lines of standard
error.  The run stops, with no result, unless JAX's devices are TPUs and
there are as many as the cell asks for.  ``--rehearse-n <rows>`` runs
the cell on whatever backend JAX has, at that many base rows: the
benchmark's own self-tests use it on the CPU.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE_DIR = BENCH / ".jax_cache"
TRACE_DIR = BENCH / ".trace"
COMPARE_QUERIES = 512        # answers held against the reference per run
FAR_QUERY = 300.0            # scale of the warm-up's far-off queries
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_MISS = "/jax/compilation_cache/cache_misses"


def log(msg: str) -> None:
    print(msg, flush=True)


def _import_path() -> None:
    # this file's directory must not shadow the standard library
    sys.path[:] = [p for p in sys.path
                   if Path(p or ".").resolve() != BENCH]
    for p in (ROOT / "src", ROOT):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))


def load_cell(name: str) -> dict:
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json; "
                         f"known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text())

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return dict(cell=cell, config=config, traffic=traffic,
                end_to_end=[m for m in spec["end_to_end"] if applies(m)],
                per_layer=[m for m in spec["per_layer"] if applies(m)])


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class CompileCounter:
    """Counts executables JAX loads (compiled or read from the cache)."""

    def __init__(self):
        import jax.monitoring as mon

        self.loads = 0
        self.misses = 0
        self.names = []
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._event)

    def _dur(self, event, duration, **kw):
        if event == BACKEND_COMPILE:
            self.loads += 1
            self.names.append(kw.get("fun_name", "?"))

    def _event(self, event, **kw):
        if event == CACHE_MISS:
            self.misses += 1

    def close(self):
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._dur)
        mon.unregister_event_listener(self._event)


class Spans:
    """Host spans in the profiler's trace, around calls into each layer.

    Only a traced run installs the wrappers; each names the layer whose
    call it wraps.  A name the program no longer has is skipped.
    """

    WRAP = [("repro.serve.ann_service", "AnnService", "flush", "serve.flush"),
            ("repro.api.indexes", "IVFApiIndex", "search", "scan.search"),
            ("repro.ann.scan", None, "coarse_probes", "scan.coarse_probes"),
            ("repro.ann.scan", None, "resolve_ids_batch", "ids.resolve"),
            ("repro.ann.pq", "ProductQuantizer", "adc_tables",
             "pq.adc_tables")]

    def __init__(self):
        import jax

        self.annotation = jax.profiler.TraceAnnotation
        self.undo = []

    def __call__(self, name):
        return self.annotation(name)

    def install(self):
        import functools
        import importlib

        for mod_name, cls_name, attr, span in self.WRAP:
            owner = importlib.import_module(mod_name)
            if cls_name is not None:
                owner = getattr(owner, cls_name, None)
            fn = getattr(owner, attr, None) if owner is not None else None
            if fn is None:
                log(f"spans: {mod_name}.{cls_name or ''}.{attr} not found; "
                    f"span {span} left out")
                continue

            def wrapped(*a, _fn=fn, _span=span, **kw):
                with self.annotation(_span):
                    return _fn(*a, **kw)

            functools.update_wrapper(wrapped, fn)
            if isinstance(owner, type) and isinstance(
                    owner.__dict__.get(attr), staticmethod):
                wrapped = staticmethod(wrapped)
            self.undo.append((owner, attr, owner.__dict__[attr]
                              if isinstance(owner, type) else fn))
            setattr(owner, attr, wrapped)

    def remove(self):
        for owner, attr, orig in reversed(self.undo):
            setattr(owner, attr, orig)
        self.undo = []


class RunRecord:
    """What a metric reader reads (see ``bench/metrics``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self._recall = {}
        self._flush_probes = None

    def recall_at(self, k: int) -> float:
        if k not in self._recall:
            import numpy as np
            from bench.reference import brute_force_topk

            answers = self.window.answers
            rows = np.concatenate([a.rows for a in answers])
            ids = np.concatenate([a.ids for a in answers])[:, :k]
            gt = brute_force_topk(self.base, self.pool[rows], k)
            hits = [len(np.intersect1d(a, b)) for a, b in zip(ids, gt)]
            self._recall[k] = float(np.mean(hits)) / k
        return self._recall[k]

    def flush_probes(self):
        if self._flush_probes is None:
            self._flush_probes = [self.ref.probes(self.pool[f.rows])
                                  for f in self.window.flushes]
        return self._flush_probes

    @property
    def list_sizes(self):
        return self.ref.sizes

    def peak(self):
        from bench.work import peak

        return peak(self.device_kind)


def build_index(config: dict, base, centroids, codebooks):
    import jax
    from repro.api import index_factory

    idx = index_factory(config["spec"])
    if codebooks is not None:
        idx.ivf.pq.codebooks = codebooks.copy()
    with jax.default_matmul_precision(config["build"]["matmul_precision"]):
        idx.build(base, centroids=centroids.copy())
    return idx


def train(config: dict, base, seed: int):
    from bench.train import kmeans, pq_codebooks, sample_rows

    nlist = int(config["spec"].split(",")[0][3:])
    tr = config["train"]
    rows = sample_rows(len(base), tr["coarse_points_per_centroid"] * nlist,
                       seed)
    centroids = kmeans(base[rows], nlist, tr["iters"], seed)
    codebooks = None
    m = pq_m(config)
    if m:
        rows = sample_rows(len(base), tr["pq_points_per_centroid"] * 256,
                           seed + 1)
        codebooks = pq_codebooks(base[rows], m, 256, tr["iters"], seed)
    return centroids, codebooks


def pq_m(config: dict) -> int:
    for tok in config["spec"].split(","):
        if tok.startswith("PQ"):
            return int(tok[2:].split("x")[0])
    return 0


def run(args) -> int:
    cell_info = load_cell(args.workload)
    cell, config, traffic = (cell_info["cell"], cell_info["config"],
                             cell_info["traffic"])
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"run.py: no program at {ROOT / 'src' / 'repro'}; "
                         "run from a checkout of the repository")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    from repro.jax_cache import enable_compile_cache

    cache = enable_compile_cache()
    import jax

    if args.rehearse_n is not None:
        # a rehearsal compiles for the host it runs on; keep it out of the
        # chip's cache
        jax.config.update("jax_enable_compilation_cache", False)
        cache = "off (rehearsal)"
    devices = jax.devices()
    dev = devices[0]
    if args.rehearse_n is None:
        if dev.platform != "tpu":
            raise SystemExit(f"run.py: no TPU (JAX's first device is "
                             f"{dev.platform!r}); use --rehearse-n for a "
                             "rehearsal off the chip")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"run.py: the cell asks for {cell['chips']} "
                             f"chips, JAX has {len(devices)}")
    log(f"device: {dev.platform} {dev.device_kind} x {len(devices)}, "
        f"jax {jax.__version__}, compile cache {cache}")
    counter = CompileCounter()
    try:
        return serve_and_check(args, cell_info, counter, dev, devices)
    finally:
        counter.close()


def serve_and_check(args, cell_info, counter, dev, devices) -> int:
    import jax
    import numpy as np

    cell, config, traffic = (cell_info["cell"], cell_info["config"],
                             cell_info["traffic"])

    from bench import check
    from bench.data import make_base, order, query_pool
    from bench.loop import ClosedLoop, request_sizes
    from bench.reference import Reference
    from repro.serve import AnnService, BatchPolicy

    seed = args.seed
    data = config["data"]
    n = args.rehearse_n or data["n"]
    t0 = time.perf_counter()
    base = make_base(data, n, seed)
    topics = traffic["topics"]
    pool = query_pool(data, traffic["pool_queries"], topics, stream=0)
    pool = pool[order(len(pool), seed)]
    warm_pool = query_pool(data, traffic["warmup"]["pool_queries"], topics,
                           stream=1)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    centroids, codebooks = train(config, base, seed)
    t_train = time.perf_counter() - t0
    t0 = time.perf_counter()
    idx = build_index(config, base, centroids, codebooks)
    t_build = time.perf_counter() - t0
    log(f"data: {n} x {base.shape[1]} float32 from seed {seed}, "
        f"{len(pool)} queries: {t_data:.3f} s; training {t_train:.3f} s; "
        f"build {config['spec']} {t_build:.3f} s")

    srch = config["search"]
    pol = config["policy"]
    svc = AnnService(idx, topk=srch["k"], nprobe=srch["nprobe"],
                     engine=srch["engine"], select=srch["select"],
                     policy=BatchPolicy(max_batch=pol["max_batch"],
                                        max_wait_s=pol["max_wait_s"]))
    rq = traffic["request_queries"]
    clients = traffic["clients"]
    warm = ClosedLoop(svc, warm_pool,
                      request_sizes(4096, rq["min"], rq["max"], seed, 1),
                      clients)
    t0 = time.perf_counter()
    loads_before = counter.loads
    w = traffic["warmup"]
    # every request size alone first, each from 1, 2, 4, ... distinct
    # queries: a block's probed lists, and so the padded shapes the
    # program compiles for, run from one query's 16 lists to the full
    # block's, and a random block rarely reaches the small ones.  Then
    # each size with one query in 16 (at least one) scaled FAR_QUERY times
    # off the data: its candidates crowd the program's re-score band, so
    # the device select widens its shortlist step by step (32, 64, 128,
    # ...) and compiles the widths a window's near-ties can ask for.
    reqs = []
    rng = np.random.default_rng([seed, 4099])
    sizes = range(rq["min"], rq["max"] + 1)
    for size in sizes:
        for distinct in sorted({min(1 << b, size)
                                for b in range(size.bit_length() + 1)}):
            for _ in range(w["size_reps"]):
                rows = rng.choice(len(warm_pool), size=distinct,
                                  replace=False)
                reqs.append(warm_pool[np.resize(rows, size)])
    for size in sizes:
        for _ in range(w["size_reps"]):
            q = warm_pool[rng.choice(len(warm_pool), size=size,
                                     replace=False)]
            q[:max(1, size // 16)] *= FAR_QUERY
            reqs.append(q)
    ClosedLoop(svc, np.concatenate(reqs), np.array([len(r) for r in reqs]),
               1).run(flushes=len(reqs))
    passes = 0
    while passes < w["max_passes"]:
        before = counter.loads
        warm.run(flushes=w["flushes_per_pass"])
        passes += 1
        if passes > 1 and counter.loads == before:
            break
    t_warm = time.perf_counter() - t0
    log(f"warm-up: {len(reqs)} flushes of 1, 2, 4, ... distinct queries "
        f"and of far-off queries, "
        f"{passes} passes of {w['flushes_per_pass']} flushes, "
        f"{counter.loads - loads_before} executables loaded "
        f"({counter.misses} compiled), {t_warm:.3f} s")
    svc.reset_stats()

    spans = None
    if args.trace:
        spans = Spans()
        spans.install()
    loop = ClosedLoop(svc, pool,
                      request_sizes(1 << 16, rq["min"], rq["max"], seed, 2),
                      clients, spans=spans)
    loads_before, misses_before = counter.loads, counter.misses
    setup_s = time.perf_counter() - T_PROCESS
    trace_path = None
    if args.trace:
        import shutil

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    with (spans("bench.window") if spans else contextlib.nullcontext()):
        window = loop.run(seconds=args.seconds)
    if args.trace:
        jax.profiler.stop_trace()
        spans.remove()
    window_loads = counter.loads - loads_before
    window_misses = counter.misses - misses_before
    st = svc.stats()
    engine = svc.last_stats.engine if svc.last_stats else "none"
    select = "device" if st["device_selects"] > 0 else "host"
    mem = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices[:cell["chips"]]), default=0)
    log(f"window: {window.end_s - window.start_s:.3f} s, "
        f"{len(window.answers)} requests, {window.queries} queries, "
        f"{len(window.flushes)} flushes; engine={engine} select={select}; "
        f"executables loaded in the window: {window_loads} "
        f"({window_misses} compiled) {counter.names[loads_before:]}")
    log(f"client loop: resubmit late by {window.late_submit_mean_s * 1e3:.3f}"
        f" ms mean, {window.late_submit_max_s * 1e3:.3f} ms max; "
        f"p95_ms is over {len(window.answers)} requests")
    log(f"service: {json.dumps(st)}")
    log(f"memory: peak_bytes_in_use {mem}")

    from bench.footprint import held_bytes

    t0 = time.perf_counter()
    index_bytes = held_bytes(idx)
    log(f"index: {index_bytes} bytes held ({time.perf_counter() - t0:.3f} s)")
    del svc, idx, warm, loop
    gc.collect()

    # --- the comparison that decides `correct` -----------------------------
    t0 = time.perf_counter()
    ref = Reference(base, centroids, srch["nprobe"], srch["k"], codebooks)
    answers = window.answers
    rng = np.random.default_rng([seed, 31337])
    q_rows = np.concatenate([a.rows for a in answers])
    ids = np.concatenate([a.ids for a in answers])
    dists = np.concatenate([a.dists for a in answers])
    pick = np.sort(rng.choice(len(q_rows), size=min(COMPARE_QUERIES,
                                                    len(q_rows)),
                              replace=False))
    numbers = check.compare(ref, pool[q_rows[pick]], ids[pick], dists[pick])
    unanswered = window.submitted - len(answers)
    limits = config["correct"]
    correct = check.verdict(numbers, limits, unanswered)
    log(f"reference: {numbers['compared_queries']} answers compared "
        f"({time.perf_counter() - t0:.3f} s), unanswered {unanswered}")

    record = RunRecord(window=window, setup_s=setup_s, base=base, pool=pool,
                       ref=ref, index_bytes=index_bytes, n=n,
                       d=base.shape[1], pq_m=pq_m(config),
                       device_kind=dev.device_kind, trace=None)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"], "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": int(window.submitted),
           "failed": int(unanswered)}
    breakdown = None
    if args.trace:
        from bench.trace import find_xplane, reduce_trace

        xplane = find_xplane(TRACE_DIR)
        record.trace = reduce_trace(xplane)
        log(f"trace: {xplane} ({xplane.stat().st_size} bytes)")
        device["busy_s"] = record.trace.busy_s()
        device["window_s"] = record.trace.window_s
        breakdown = {"device_ops": record.trace.top_ops(),
                     "idle_gaps": record.trace.idle_gaps()}
        chosen = cell_info["per_layer"]
    else:
        chosen = cell_info["end_to_end"]
    metrics = {}
    for m in chosen:
        val = load_reader(m["name"])(record)
        if isinstance(val, tuple):
            val, note = val
            log(f"{m['name']}: {note}")
        if val is None:
            log(f"{m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                     for k in limits}
    out["checks"]["unanswered"] = {"value": unanswered, "limit": 0}
    for k, v in out["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-n", type=int, default=None,
                    help="rows of a rehearsal off the chip (self-tests)")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    _import_path()
    return run(parse(argv))


if __name__ == "__main__":
    sys.exit(main())
