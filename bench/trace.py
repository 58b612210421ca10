"""Reduction of a JAX profiler trace (``*.xplane.pb``) to device numbers.

* device ops: the events of the ``XLA Ops`` line of each device plane
  (``/device:TPU:<i>``), one interval per operation that ran;
* busy time: the union of those intervals inside the traced window,
  averaged over the device planes;
* kernel time: the summed durations of the ops whose name (or HLO
  ``long_name``/``hlo_op`` stat) contains one of a metric's patterns;
* idle gaps: each stretch inside the window with no op running, charged
  to the innermost benchmark span (``TraceAnnotation`` on the host) that
  covers its middle.

The window is the host span named ``WINDOW_SPAN``.  Device and host
timestamps share one clock in the profile (the profiler aligns them).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from pathlib import Path
from typing import Dict, Iterable, List, Tuple

import numpy as np

__all__ = ["Trace", "reduce_trace", "find_xplane", "WINDOW_SPAN"]

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
HOST_PLANE = "/host:CPU"
SPAN_PREFIXES = ("bench.", "serve.", "scan.", "ids.", "pq.")


@dataclasses.dataclass
class Op:
    name: str
    text: str              # name plus the stats that name the kernel
    start: int             # ns
    end: int


@dataclasses.dataclass
class Trace:
    ops: Dict[str, List[Op]]          # device plane -> ops in the window
    spans: List[Tuple[str, int, int]]  # host benchmark spans
    window: Tuple[int, int]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds with an op running, averaged over the device planes."""
        if not self.ops:
            return 0.0
        return float(np.mean([_union(ops) for ops in self.ops.values()])) * 1e-9

    def kernel_s(self, patterns: Iterable[str]) -> float:
        """Device seconds of the ops matching any pattern (all planes)."""
        pats = tuple(patterns)
        return sum(o.end - o.start for ops in self.ops.values() for o in ops
                   if any(p in o.text for p in pats)) * 1e-9

    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, int] = defaultdict(int)
        for ops in self.ops.values():
            for o in ops:
                tot[_short(o.name)] += o.end - o.start
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle device time in the window by the host span covering it."""
        tot: Dict[str, int] = defaultdict(int)
        for ops in self.ops.values():
            for g0, g1 in _gaps(ops, self.window):
                tot[self._host_at((g0 + g1) // 2)] += g1 - g0
        if self.ops:
            for k in tot:
                tot[k] //= len(self.ops)
        best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in best]

    def _host_at(self, t: int) -> str:
        best, width = "outside benchmark spans", None
        for name, s, e in self.spans:
            if name != WINDOW_SPAN and s <= t < e and (width is None
                                                      or e - s < width):
                best, width = name, e - s
        return best


def _short(name: str) -> str:
    """``%fusion.27 = f32[8,131072]{...} fusion(...)`` -> ``%fusion.27 f32[8,131072]``."""
    head, _, rest = name.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0] if rest else ""
    return f"{head} {shape}".strip()[:120]


def _merged(ops: List[Op]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for o in sorted(ops, key=lambda o: o.start):
        if out and o.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], o.end)
        else:
            out.append([o.start, o.end])
    return [(a, b) for a, b in out]


def _union(ops: List[Op]) -> int:
    return sum(b - a for a, b in _merged(ops))


def _gaps(ops: List[Op], window: Tuple[int, int]):
    t = window[0]
    for a, b in _merged(ops):
        if a > t:
            yield t, a
        t = max(t, b)
    if window[1] > t:
        yield t, window[1]


def find_xplane(log_dir) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def _stat_text(ev) -> str:
    parts = [ev.name]
    for key, val in ev.stats:
        if key in ("long_name", "hlo_op", "tf_op", "kernel_details",
                   "name") and isinstance(val, str):
            parts.append(val)
    return " ".join(parts)


def reduce_trace(path) -> Trace:
    """Read one ``.xplane.pb`` and clip the device ops to the window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: List[Tuple[str, int, int]] = []
    raw_ops: Dict[str, List[Op]] = {}
    for plane in pd.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIXES):
                        s = int(ev.start_ns)
                        spans.append((ev.name, s, s + int(ev.duration_ns)))
        elif DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                ops = raw_ops.setdefault(plane.name, [])
                for ev in line.events:
                    s = int(ev.start_ns)
                    ops.append(Op(ev.name, _stat_text(ev), s,
                                  s + int(ev.duration_ns)))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if win:
        window = win[0]
    else:
        every = [o for ops in raw_ops.values() for o in ops]
        window = ((min(o.start for o in every), max(o.end for o in every))
                  if every else (0, 0))
    ops = {}
    for plane, lst in raw_ops.items():
        ops[plane] = [Op(o.name, o.text, max(o.start, window[0]),
                         min(o.end, window[1]))
                      for o in lst if o.end > window[0] and o.start < window[1]]
    return Trace(ops, spans, window)
