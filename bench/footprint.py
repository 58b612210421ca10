"""Bytes an index holds, measured on its objects rather than claimed.

Walks the object graph from the index: every array buffer is counted
once (a view counts its root buffer), device arrays by their bytes,
``bytes``/``str``/numbers and every other object by
``sys.getsizeof``.  Modules, classes and functions are not entered.
The number is what the served index keeps in memory, whatever layout a
later change gives it.
"""

from __future__ import annotations

import sys
import types

import numpy as np

__all__ = ["held_bytes"]

_SKIP = (types.ModuleType, type, types.FunctionType, types.BuiltinFunctionType,
         types.MethodType)


def held_bytes(obj) -> int:
    seen = set()
    total = 0
    stack = [obj]
    while stack:
        o = stack.pop()
        if id(o) in seen or isinstance(o, _SKIP):
            continue
        seen.add(id(o))
        if isinstance(o, np.ndarray):
            root = o
            while isinstance(root.base, np.ndarray):
                root = root.base
            if root is not o:
                stack.append(root)
            else:
                total += o.nbytes
            continue
        if hasattr(o, "nbytes") and hasattr(o, "shape") and hasattr(
                o, "dtype"):
            total += int(o.nbytes)      # device arrays
            continue
        total += sys.getsizeof(o)
        if isinstance(o, dict):
            stack.extend(o.keys())
            stack.extend(o.values())
        elif isinstance(o, (list, tuple, set, frozenset)):
            stack.extend(o)
        else:
            if hasattr(o, "__dict__"):
                stack.append(vars(o))
            for cls in type(o).__mro__:
                for slot in getattr(cls, "__slots__", ()):
                    if isinstance(slot, str) and hasattr(o, slot):
                        stack.append(getattr(o, slot))
    return total
