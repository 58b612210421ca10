"""Backend-derived default for the kernels' ``interpret`` flag."""

from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given; when ``None``, the Pallas interpreter on the
    CPU backend and the compiled Mosaic kernel on every other backend."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
