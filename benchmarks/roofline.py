"""§Roofline: three-term roofline per (arch x shape x mesh) from the dry-run.

    compute_s    = HLO_FLOPs_per_device / 197e12      (bf16 peak, TPU v5e)
    memory_s     = HLO_bytes_per_device / 819e9       (HBM bw)
    collective_s = collective_bytes_per_device / 50e9 (per-link ICI)

``cost_analysis()`` semantics (per-device vs global) are *calibrated* in a
CPU-only subprocess (8 virtual devices, ``JAX_PLATFORMS=cpu``, so it never
contends for an accelerator the parent holds) against a matmul of known
FLOPs before being trusted.  The
dominant term, MODEL_FLOPS=6ND (or 6·N_active·D) ratio, and a what-to-fix
hint are derived per cell; output feeds EXPERIMENTS.md §Roofline directly.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
from pathlib import Path

from .common import ROOT, emit, save_result

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9

DRYRUN = ROOT / "experiments" / "dryrun"

_CALIB_SRC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("d",))
xs = jax.ShapeDtypeStruct((1024, 512), jnp.float32)
ws = jax.ShapeDtypeStruct((512, 256), jnp.float32)
f = jax.jit(lambda x, w: x @ w,
            in_shardings=(NamedSharding(mesh, P("d", None)), NamedSharding(mesh, P())))
c = f.lower(xs, ws).compile()
flops = c.cost_analysis()["flops"]
global_flops = 2 * 1024 * 512 * 256
print(flops / global_flops)
"""


def calibrate() -> float:
    """Returns cost_analysis flops / global flops (≈1/n_dev ⇒ per-device)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", _CALIB_SRC], env=env,
                         capture_output=True, text=True, timeout=300,
                         check=True)
    ratio = float(out.stdout.strip().splitlines()[-1])
    return ratio


def analytic_memory_bytes(arch: str, shape_name: str, kind: str,
                          n_dev: int) -> float:
    """Analytic per-device HBM traffic model (fused-TPU assumption).

    XLA-CPU's ``bytes accessed`` counts every unfused op's operands — 10-100x
    above fused HBM reality — so the memory term comes from the exact tensor
    inventory instead (params/optimizer/grad passes + activation stream +
    KV-cache reads), all computed from the real configs and shardings:

      train:   32 B/param/dev (f32 master r+w, bf16 cast r x2, grad f32 r+w,
               m+v r+w) + activations ~12 B/token/layer/d_model x3 passes
               (fwd + remat-fwd + bwd) + logits f32.
      prefill: 2 B/param/dev + activation stream x1 + KV write.
      decode:  2 B/param/dev + full KV-cache read per token + state r/w.
    """
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config
    from repro.models import build, count_params
    from repro.models.encdec import dec_len_for

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n_params = count_params(cfg)
    p_dev = n_params / n_dev
    B, S = shape.global_batch, shape.seq_len
    d = cfg.d_model
    L = cfg.n_layers + (cfg.n_encoder_layers if cfg.encoder_decoder else 0)
    tokens_dev = B * S / n_dev
    if cfg.encoder_decoder and kind != "decode":
        tokens_dev = B * (S + dec_len_for(S)) / n_dev

    tp = 16  # model-axis width of the production mesh
    logits_traffic = 2 * 4.0 * tokens_dev * cfg.padded_vocab / tp  # f32 w+r
    if kind == "train":
        param_traffic = 32.0 * p_dev
        act = 12.0 * tokens_dev * d * 2 * L * 3
        return param_traffic + act + logits_traffic
    if kind == "prefill":
        return 2.0 * p_dev + 12.0 * tokens_dev * d * 2 * L + logits_traffic / 2
    # decode: params + cache read per token + writes
    model = build(cfg)
    kw = {"mem_len": S} if cfg.encoder_decoder else {}
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(B, S, dtype=jnp.bfloat16, **kw))
    cache_bytes = sum(
        int(np.prod(l.shape)) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(cache_shapes))
    return 2.0 * p_dev + 1.05 * cache_bytes / n_dev


def analyze(rec: dict, per_device_ratio: float, probe: dict | None = None) -> dict:
    n_dev = rec["n_devices"]
    # cost_analysis is per-device if ratio ~ 1/8 in the 8-dev calibration
    per_device = per_device_ratio < 0.5
    flops_dev = rec["cost"]["flops"] if per_device else rec["cost"]["flops"] / n_dev
    raw_bytes_dev = (rec["cost"]["bytes_accessed"] if per_device
                     else rec["cost"]["bytes_accessed"] / n_dev)
    coll_dev = rec["collectives"]["total_bytes"]  # HLO shapes are per-device
    if probe and probe.get("status") == "ok":
        # scans under-count (while bodies counted once): prefer the unrolled
        # probe extrapolation (see dryrun.run_probe) for flops/collectives
        flops_dev = probe["flops"]
        coll_dev = probe["collective_bytes"]
    bytes_dev = analytic_memory_bytes(rec["arch"], rec["shape"], rec["kind"],
                                      n_dev)
    compute_s = flops_dev / PEAK_FLOPS
    memory_s = bytes_dev / HBM_BW
    collective_s = coll_dev / LINK_BW
    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    step_s = max(terms.values())
    model_flops_dev = rec["model_flops_global"] / n_dev
    useful = model_flops_dev / flops_dev if flops_dev > 0 else 0.0
    mfu_bound = (model_flops_dev / PEAK_FLOPS) / step_s if step_s > 0 else 0.0
    hints = {
        "compute_s": "reduce recompute (remat policy) / keep MXU dims aligned",
        "memory_s": "fuse element-wise chains; widen per-step arithmetic intensity",
        "collective_s": "reshard to cut all-gathers; overlap collectives with compute",
    }
    return {
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant,
        "roofline_step_s": round(step_s, 6),
        "model_flops_ratio": round(useful, 4),
        "roofline_fraction": round(mfu_bound, 4),
        "hint": hints[dominant],
    }


def main(quick: bool = False):
    ratio = calibrate()
    probes_dir = ROOT / "experiments" / "probes"
    rows = {}
    for p in sorted(DRYRUN.glob("*.json")):
        rec = json.loads(p.read_text())
        if rec.get("status") != "ok":
            rows[p.stem] = {"status": rec.get("status", "missing"),
                            "error": rec.get("error", "")[:200]}
            continue
        if rec["mesh"] != "16x16":
            # the roofline table is single-pod only (the multi-pod compile is
            # the pod-axis shard proof); multi-pod cells have no cost probes
            continue
        probe = None
        pp = probes_dir / f"{rec['arch']}__{rec['shape']}.json"
        if pp.exists():
            probe = json.loads(pp.read_text())
        rows[p.stem] = {"status": "ok", **analyze(rec, ratio, probe),
                        "mesh": rec["mesh"], "kind": rec["kind"],
                        "probed": bool(probe and probe.get("status") == "ok")}
        emit(f"roofline/{p.stem}", rows[p.stem].get("roofline_step_s", 0) * 1e6,
             f"{rows[p.stem].get('dominant','-')},frac={rows[p.stem].get('roofline_fraction',0)}")
    save_result("roofline", {"calibration_ratio": ratio, "rows": rows})
    return rows


if __name__ == "__main__":
    main()
