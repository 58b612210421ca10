import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) cell.

For each cell this produces (and persists to experiments/dryrun/*.json):
  * compiled.memory_analysis()  — per-device bytes (proves the cell fits),
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes accessed,
  * collective operand bytes parsed from the post-SPMD HLO text, by op kind,
  * lowering + compile wall time.

The single-pod (16,16) mesh feeds the roofline table; the (2,16,16) mesh
proves the "pod" axis shards.  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-72b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod-only|--single-pod-only]
"""

import argparse
import json
import re
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import SHAPES, get_config, shape_applicable, ARCH_IDS
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    param_shardings,
)
from repro.launch.mesh import make_production_mesh
from repro.models import build, decode_input_specs, input_specs, model_flops
from repro.train.optim import init_opt
from repro.train.step import make_prefill_step, make_serve_step, make_train_step

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "token": 0,
}
_HLO_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^=]*?\s("
    + "|".join(_COLLECTIVES) + r")(?:-start)?\("
)
_TUPLE_RE = re.compile(
    r"=\s+\(((?:[a-z0-9]+\[[0-9,]*\][^,)]*,?\s*)+)\)\s*("
    + "|".join(_COLLECTIVES) + r")(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def collective_bytes(hlo_text: str) -> dict:
    """Sum result-operand bytes of every collective op, by kind."""
    out = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        hit = None
        for kind in _COLLECTIVES:
            if f" {kind}(" in line or f" {kind}-start(" in line:
                hit = kind
                break
        if hit is None:
            continue
        # take the result shape(s) on the lhs of '='
        lhs = line.split("=", 1)[0] + "=" + line.split("=", 1)[1].split("(", 1)[0]
        total = 0
        for dt, dims in _SHAPE_RE.findall(lhs):
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            total += n * _DTYPE_BYTES[dt]
        out[hit] += total
        counts[hit] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _mem_stats(compiled) -> dict:
    m = compiled.memory_analysis()
    return {
        "argument_bytes": int(m.argument_size_in_bytes),
        "output_bytes": int(m.output_size_in_bytes),
        "temp_bytes": int(m.temp_size_in_bytes),
        "alias_bytes": int(m.alias_size_in_bytes),
        "code_bytes": int(m.generated_code_size_in_bytes),
    }


def _cost_analysis(compiled) -> dict:
    c = compiled.cost_analysis() or {}
    if isinstance(c, list):  # old jax returns one dict per computation
        c = c[0] if c else {}
    return c


def _cost_stats(compiled) -> dict:
    c = _cost_analysis(compiled)
    return {
        "flops": float(c.get("flops", -1.0)),
        "bytes_accessed": float(c.get("bytes accessed", -1.0)),
        "transcendentals": float(c.get("transcendentals", 0.0)),
    }


def lower_cell(arch: str, shape_name: str, multi_pod: bool, step_kind: str | None = None):
    """Build + lower + compile one cell; returns the record dict."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    kind = step_kind or shape.kind
    rec = {
        "arch": arch, "shape": shape_name, "kind": kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": int(np.prod(mesh.devices.shape)),
    }
    t0 = time.time()
    model = build(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = param_shardings(params_shapes, mesh, cfg.n_experts)

    with jax.set_mesh(mesh):
        if kind == "train":
            _, train_step = make_train_step(cfg)
            opt_shapes = jax.eval_shape(init_opt, params_shapes)
            o_shard = jax.tree.map(
                lambda s: s, jax.eval_shape(init_opt, params_shapes))
            o_shard = param_shardings(opt_shapes, mesh, cfg.n_experts)
            batch = input_specs(cfg, shape)
            b_shard = batch_shardings(batch, mesh)
            jf = jax.jit(
                train_step,
                in_shardings=(p_shard, o_shard, b_shard),
                out_shardings=(p_shard, o_shard, None),
                donate_argnums=(0, 1),
            )
            lowered = jf.lower(params_shapes, opt_shapes, batch)
        elif kind == "prefill":
            _, prefill_step = make_prefill_step(cfg)
            batch = input_specs(cfg, shape)
            batch.pop("labels", None)
            b_shard = batch_shardings(batch, mesh)
            jf = jax.jit(prefill_step, in_shardings=(p_shard, b_shard))
            lowered = jf.lower(params_shapes, batch)
        elif kind == "decode":
            _, serve_step = make_serve_step(cfg)
            mem_len = None
            cache_kwargs = {}
            if cfg.encoder_decoder:
                cache_kwargs["mem_len"] = shape.seq_len
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         dtype=jnp.bfloat16, **cache_kwargs))
            c_shard = cache_shardings(cache_shapes, mesh, shape.global_batch,
                                      cfg.n_kv_heads)
            inputs = decode_input_specs(cfg, shape)
            i_shard = batch_shardings(inputs, mesh)
            jf = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, i_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            )
            lowered = jf.lower(params_shapes, cache_shapes, inputs)
        else:
            raise ValueError(kind)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    rec["memory"] = _mem_stats(compiled)
    rec["cost"] = _cost_stats(compiled)
    hlo = compiled.as_text()
    rec["collectives"] = collective_bytes(hlo)
    rec["hlo_lines"] = hlo.count("\n")
    rec["model_flops_global"] = model_flops(cfg, shape)
    return rec


def cell_path(arch: str, shape_name: str, multi_pod: bool) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return OUT_DIR / f"{arch}__{shape_name}__{mesh}.json"


# ---------------------------------------------------------------------------
# cost probes: XLA cost_analysis counts while-loop (scan) bodies ONCE, so the
# scanned full-depth compiles under-count FLOPs/bytes/collectives by ~n_iters.
# Probes compile UNROLLED reduced-depth variants at two depths and the cell's
# true totals are the linear extrapolation (exact for homogeneous stacks):
#     cost(L) = base + per_layer * L
# ---------------------------------------------------------------------------

def probe_layer_pair(cfg):
    """Two reduced-depth configs + their n_layers, preserving structure."""
    import dataclasses as dc

    if cfg.local_global_ratio:          # gemma3: keep the remainder equal
        per = cfg.local_global_ratio + 1
        rem = cfg.n_layers % per
        l1, l2 = per + rem, 2 * per + rem
    elif cfg.hybrid_attn_every:
        per = cfg.hybrid_attn_every
        l1, l2 = per, 2 * per
    elif cfg.mlstm_slstm_pattern:
        per = cfg.mlstm_slstm_pattern + 1
        l1, l2 = per, 2 * per
    else:
        l1, l2 = 1, 2
    def mk(l):
        kw = {"n_layers": l}
        if cfg.encoder_decoder:
            kw["n_encoder_layers"] = l
        return dc.replace(cfg, **kw)
    return mk(l1), l1, mk(l2), l2


def _lower_probe(cfg, shape, kind, mesh):
    """Compile an unrolled reduced cfg; return (flops, bytes, coll_bytes)."""
    model = build(cfg)
    params_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_shard = param_shardings(params_shapes, mesh, cfg.n_experts)
    with jax.set_mesh(mesh):
        if kind == "train":
            _, step = make_train_step(cfg, unroll=True)
            opt_shapes = jax.eval_shape(init_opt, params_shapes)
            o_shard = param_shardings(opt_shapes, mesh, cfg.n_experts)
            batch = input_specs(cfg, shape)
            b_shard = batch_shardings(batch, mesh)
            jf = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         donate_argnums=(0, 1))
            compiled = jf.lower(params_shapes, opt_shapes, batch).compile()
        elif kind == "prefill":
            _, step = make_prefill_step(cfg, unroll=True)
            batch = input_specs(cfg, shape)
            batch.pop("labels", None)
            jf = jax.jit(step, in_shardings=(p_shard, batch_shardings(batch, mesh)))
            compiled = jf.lower(params_shapes, batch).compile()
        else:
            _, step = make_serve_step(cfg, unroll=True)
            kw = {"mem_len": shape.seq_len} if cfg.encoder_decoder else {}
            cache_shapes = jax.eval_shape(
                lambda: model.init_cache(shape.global_batch, shape.seq_len,
                                         dtype=jnp.bfloat16, **kw))
            c_shard = cache_shardings(cache_shapes, mesh, shape.global_batch,
                                      cfg.n_kv_heads)
            inputs = decode_input_specs(cfg, shape)
            jf = jax.jit(step, in_shardings=(p_shard, c_shard,
                                             batch_shardings(inputs, mesh)),
                         donate_argnums=(1,))
            compiled = jf.lower(params_shapes, cache_shapes, inputs).compile()
    c = _cost_analysis(compiled)
    coll = collective_bytes(compiled.as_text())
    return (float(c.get("flops", 0.0)), float(c.get("bytes accessed", 0.0)),
            float(coll["total_bytes"]))


def run_probe(arch: str, shape_name: str, force: bool = False):
    path = OUT_DIR.parent / "probes" / f"{arch}__{shape_name}.json"
    if path.exists() and not force:
        prev = json.loads(path.read_text())
        if prev.get("status") == "ok":
            print(f"[skip] probe {path.name}")
            return prev
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=False)
    print(f"[probe] {arch} x {shape_name} ...", flush=True)
    try:
        t0 = time.time()
        c1, c2, scale = None, None, None
        cfg1, l1, cfg2, l2 = probe_layer_pair(cfg)
        c1 = _lower_probe(cfg1, shape, shape.kind, mesh)
        c2 = _lower_probe(cfg2, shape, shape.kind, mesh)
        scale = (cfg.n_layers - l1) / (l2 - l1)
        total = [a + scale * (b - a) for a, b in zip(c1, c2)]
        rec = {
            "arch": arch, "shape": shape_name, "status": "ok",
            "probe_layers": [l1, l2], "scale": scale,
            "flops": total[0], "bytes_accessed": total[1],
            "collective_bytes": total[2],
            "probe1": c1, "probe2": c2,
            "probe_s": round(time.time() - t0, 1),
        }
        print(f"  probe ok: flops/dev={total[0]:.3g} "
              f"coll/dev={total[2]:.3g}B ({rec['probe_s']}s)", flush=True)
    except Exception as e:  # noqa: BLE001
        rec = {"arch": arch, "shape": shape_name, "status": "error",
               "error": f"{type(e).__name__}: {e}"}
        print(f"  probe ERROR: {rec['error']}", flush=True)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1))
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool, force: bool = False):
    path = cell_path(arch, shape_name, multi_pod)
    if path.exists() and not force:
        prev = json.loads(path.read_text())
        if prev.get("status") == "ok":   # error cells are retried
            print(f"[skip] {path.name} (ok)")
            return prev
    print(f"[dryrun] {arch} x {shape_name} x "
          f"{'2x16x16' if multi_pod else '16x16'} ...", flush=True)
    try:
        rec = lower_cell(arch, shape_name, multi_pod)
        rec["status"] = "ok"
    except Exception as e:  # noqa: BLE001 — failures are data here
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16",
               "status": "error", "error": f"{type(e).__name__}: {e}"}
        print(f"  ERROR: {rec['error']}", flush=True)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(rec, indent=1))
    if rec["status"] == "ok":
        print(f"  ok: compile={rec['compile_s']}s "
              f"flops/dev={rec['cost']['flops']:.3g} "
              f"coll={rec['collectives']['total_bytes']:.3g}B", flush=True)
    return rec


def all_cells():
    for arch in ARCH_IDS:
        for shape_name in SHAPES:
            if not shape_applicable(arch, shape_name):
                continue
            yield arch, shape_name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod-only", action="store_true")
    ap.add_argument("--single-pod-only", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--probe", action="store_true",
                    help="run the unrolled cost probes instead of full cells")
    args = ap.parse_args()

    meshes = [False, True]
    if args.multi_pod_only:
        meshes = [True]
    if args.single_pod_only:
        meshes = [False]

    if args.probe:
        cells = (all_cells() if args.all else [(args.arch, args.shape)])
        for arch, shape_name in cells:
            run_probe(arch, shape_name, force=args.force)
        return

    if args.all:
        for arch, shape_name in all_cells():
            for mp in meshes:
                run_cell(arch, shape_name, mp, force=args.force)
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        for mp in meshes:
            run_cell(args.arch, args.shape, mp, force=args.force)


if __name__ == "__main__":
    main()
