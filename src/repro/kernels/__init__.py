"""repro.kernels — Pallas TPU kernels for the paper's compute hot-spots.

Each kernel package pairs a Pallas implementation (``kernel.py``,
written to TPU v5e tiling rules: 2-D, lane-dense blocks) with a jitted
wrapper (``ops.py``) and a pure-jnp oracle (``ref.py``).  The wrappers
take ``interpret=None`` by default, which compiles the kernel with Mosaic
on a TPU and runs the Pallas interpreter on the CPU backend; the
serving-path kernels (``l2_topk``, ``pq_adc``, ``seg_topk``) are also
AOT-compiled for v5e in ``tests/test_tpu_compile.py`` and run compiled
by ``chip_smoke.py``:

* ``l2_topk``   — blocked squared-L2 distance matrix / top-1 scan.
* ``pq_adc``    — PQ asymmetric-distance (ADC) lookup-table scoring.
* ``seg_topk``  — segmented top-k select: cuts per-query candidate rows
  to their k smallest ``(value, column)`` pairs on device, bit-identical
  between the Pallas kernel and the ``lax.top_k`` fallback, so the scan
  engines never pull a full distance block to the host.
* ``rans_decode`` — interleaved-stream rANS symbol decode.
* ``wt_rank``   — wavelet-tree bitvector rank over packed u32 words.

The scan engines (``repro.ann.scan`` / ``repro.ann.graph_scan``) pick
kernels vs the XLA fallback per call via ``engine=auto|xla|pallas``.
"""

from .l2_topk import l2_dist, l2_dist_ref, l2_top1, l2_top1_ref
from .pq_adc import pq_adc, pq_adc_ref
from .rans_decode import make_tables, rans_decode, rans_decode_ref
from .seg_topk import seg_topk, seg_topk_ref, seg_topk_xla
from .wt_rank import pack_bits_u32, wt_rank, wt_rank_ref

__all__ = [
    "l2_dist", "l2_dist_ref", "l2_top1", "l2_top1_ref",
    "pq_adc", "pq_adc_ref",
    "seg_topk", "seg_topk_xla", "seg_topk_ref",
    "rans_decode", "rans_decode_ref", "make_tables",
    "wt_rank", "wt_rank_ref", "pack_bits_u32",
]
