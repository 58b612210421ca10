"""Project-specific static analysis for the repro codebase.

Run with ``python -m repro.analysis [paths]``.  The pass enforces the
invariants behind byte-reproducible compression and the concurrent
serving path at the *source* level:

==========  ===============================================================
RPA001      codec-protocol conformance (full ``encode/decode/size_bits``
            surface on every ``IdCodec``; no ``hasattr`` duck-typing on
            the hot path)
RPA002      lock discipline in executor-backed services
RPA003      serialization determinism in container/blob writers
RPA004      overflow/width contracts on wide bit-pack shifts
RPA005      jit/Pallas purity in traced functions
RPA006      broad-except hygiene (name the concrete failure types)
==========  ===============================================================

Suppress one line with ``# repro: ignore[RPA001]`` (or a bare
``# repro: ignore``); grandfather whole findings in
``analysis_baseline.json`` (``--write-baseline``).
"""

from .core import (CHECKERS, Checker, Finding, ModuleContext, all_checkers,
                   analyze_file, analyze_paths, analyze_source,
                   load_baseline, module_path, split_baselined,
                   write_baseline)
from .cli import main

__all__ = [
    "CHECKERS", "Checker", "Finding", "ModuleContext", "all_checkers",
    "analyze_file", "analyze_paths", "analyze_source", "load_baseline",
    "module_path", "split_baselined", "write_baseline", "main",
]
