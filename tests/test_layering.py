"""Arrows point down: importing a package loads only the layers below it.

The layer map is the ``text layers`` block of ``docs/architecture.md``:
each package names the packages it sits on.  Each case imports one
package in a fresh interpreter and checks that every ``repro.*``
subpackage it loaded is that package or lies below it in the map.
"""

import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
PACKAGES = ["core", "kernels", "ann", "api", "serve", "shard", "analysis"]

_PROBE = (
    "import importlib, sys\n"
    "importlib.import_module(sys.argv[1])\n"
    "print(' '.join(sorted({m.split('.')[1] for m in sys.modules\n"
    "                       if m.startswith('repro.')})))\n"
)


def _layer_map() -> dict:
    text = (ROOT / "docs" / "architecture.md").read_text()
    block = re.search(r"```text layers\n(.*?)```", text, re.S).group(1)
    sits_on = {}
    for line in block.splitlines()[1:]:          # skip the header row
        package, below, _ = line.split(None, 2)
        sits_on[package] = [] if below == "-" else below.split(",")
    return sits_on


def _below(package: str, sits_on: dict) -> set:
    out = {package}
    for lower in sits_on[package]:
        out |= _below(lower, sits_on)
    return out


def _loaded(package: str) -> set:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE, f"repro.{package}"],
        capture_output=True, text=True, timeout=300, env=env, cwd=str(ROOT))
    assert proc.returncode == 0, proc.stderr
    return set(proc.stdout.split())


@pytest.mark.parametrize("package", PACKAGES)
def test_package_loads_only_layers_below(package):
    sits_on = _layer_map()
    assert package in sits_on, f"docs/architecture.md has no layer {package}"
    allowed = _below(package, sits_on)
    loaded = _loaded(package)
    assert package in loaded
    assert loaded <= allowed, (
        f"repro.{package} loads {sorted(loaded - allowed)}, which the layer "
        f"map in docs/architecture.md does not put below it")
