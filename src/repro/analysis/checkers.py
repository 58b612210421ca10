"""Project-specific checkers enforcing the repo's byte-parity invariants.

Each rule encodes an invariant that otherwise lives only in reviewers'
heads and after-the-fact parity tests:

* **RPA001 codec-protocol conformance** — every ``IdCodec`` subclass
  statically defines the full ``encode/decode/size_bits`` surface with
  the registry's signatures (``gather`` may inherit the random-access
  default), and hot-path modules never ``hasattr``-duck-type an index:
  the codec matrix and the service seam are *contracts*, checked at the
  source, not probed at runtime.
* **RPA002 lock discipline** — in executor-backed services, methods that
  run on the thread pool (statically: targets of ``self._pool.submit``)
  may only mutate ``self`` state or touch shard workers under the owning
  ``self._lock``/``self._locks[...]`` ``with`` block, and state they
  share with caller-thread methods must be locked on both sides.
* **RPA003 serialization determinism** — container writers (RIDX/RIVF
  modules and any ``pack_*``/``*_blobs``/``*_sections`` function) must
  not iterate sets or dict views unsorted, nor call wall-clock/random
  sources: the byte stream must be a pure function of the index.
* **RPA004 overflow/width contracts** — a ``<<`` by >= 32 bits on a
  non-literal operand (merge keys, ANS heads) needs an explicit bound
  check (``raise OverflowError`` / compare against ``1 << BITS``) or a
  uint64 cast in the same function, generalizing ``pack_merge_keys``.
* **RPA005 jit/scan purity** — functions handed to ``jax.jit`` or
  ``pl.pallas_call`` under ``repro/kernels/`` and the scan engines must
  stay traceable: no host prints, ``.item()``/``tolist()``, Python
  scalar coercions, host-``np`` calls (silent constant-folding),
  wall-clock reads, or Python-side mutation.
* **RPA006 broad-except hygiene** — no ``except Exception`` (or bare
  ``except``): the concrete failure types must be named.
* **RPA007 spec-grammar/docs drift** — the option keys
  ``repro.api.spec`` actually parses (``KNOWN_OPTION_KEYS``) and the
  keys documented in the ``spec-grammar`` block of
  ``docs/architecture.md`` must match exactly, both directions: the
  factory-string grammar is user-facing API and the docs page is its
  normative reference.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from .core import Checker, Finding, ModuleContext, register

__all__ = [
    "CodecProtocolChecker", "LockDisciplineChecker",
    "SerializationDeterminismChecker", "WidthContractChecker",
    "JitPurityChecker", "BroadExceptChecker", "SpecGrammarDriftChecker",
]


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def dotted(node: Optional[ast.AST]) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains (through subscripts); else None."""
    parts: List[str] = []
    while True:
        if isinstance(node, ast.Attribute):
            parts.append(node.attr)
            node = node.value
        elif isinstance(node, ast.Name):
            parts.append(node.id)
            return ".".join(reversed(parts))
        else:
            return None


def _self_attr(node: ast.AST) -> Optional[str]:
    """``self.A`` / ``self.A[...]`` -> ``A``."""
    if isinstance(node, ast.Subscript):
        node = node.value
    if (isinstance(node, ast.Attribute)
            and isinstance(node.value, ast.Name)
            and node.value.id == "self"):
        return node.attr
    return None


def _functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# ---------------------------------------------------------------------------
# RPA001 — codec-protocol conformance / no hasattr duck-typing
# ---------------------------------------------------------------------------

@register
class CodecProtocolChecker(Checker):
    rule = "RPA001"
    title = "codec-protocol conformance"

    #: method -> positional signature after ``self`` (extras need defaults)
    SURFACE = {
        "encode": ("ids", "universe"),
        "decode": ("blob", "universe"),
        "size_bits": ("blob",),
        "gather": ("blob", "offsets"),
    }
    #: must be statically defined on every registered codec class
    REQUIRED = ("encode", "decode", "size_bits")
    #: modules where hasattr duck-typing is a hot-path hazard
    HOT_PREFIXES = ("repro/ann/", "repro/api/", "repro/serve/",
                    "repro/shard/", "repro/core/")

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef):
                out.extend(self._check_class(ctx, node))
        if ctx.path.startswith(self.HOT_PREFIXES):
            for node in ast.walk(ctx.tree):
                if (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id == "hasattr"):
                    out.append(self.finding(
                        ctx, node,
                        "hasattr duck-typing on the hot path; use an "
                        "isinstance/protocol check from repro.api.protocol"))
        return out

    def _check_class(self, ctx: ModuleContext,
                     node: ast.ClassDef) -> List[Finding]:
        if not any((dotted(b) or "").split(".")[-1] == "IdCodec"
                   for b in node.bases):
            return []
        out: List[Finding] = []
        methods = {s.name: s for s in node.body
                   if isinstance(s, ast.FunctionDef)}
        for name in self.REQUIRED:
            if name not in methods:
                out.append(self.finding(
                    ctx, node,
                    f"codec class {node.name} must statically define "
                    f"{name}() (no runtime duck-typing on the decode path)"))
        for name, expected in self.SURFACE.items():
            fn = methods.get(name)
            if fn is None:
                continue
            bad = self._signature_mismatch(fn, expected)
            if bad:
                out.append(self.finding(
                    ctx, fn,
                    f"codec method {node.name}.{name}() signature "
                    f"incompatible with the IdCodec contract: {bad}"))
        return out

    @staticmethod
    def _signature_mismatch(fn: ast.FunctionDef,
                            expected: Tuple[str, ...]) -> Optional[str]:
        a = fn.args
        if a.vararg is not None or a.kwarg is not None:
            return None                      # pass-through signature: accept
        names = [arg.arg for arg in a.posonlyargs + a.args]
        if not names or names[0] != "self":
            return "first parameter must be self"
        names = names[1:]
        want = list(expected)
        if len(names) < len(want):
            return (f"expected parameters {tuple(want)}, got {tuple(names)}")
        if names[:len(want)] != want:
            return (f"expected parameters {tuple(want)}, got {tuple(names)}")
        extras = len(names) - len(want)
        if extras > len(a.defaults):
            return ("extra parameters beyond the contract must carry "
                    "defaults")
        return None


# ---------------------------------------------------------------------------
# RPA002 — lock discipline in executor-backed services
# ---------------------------------------------------------------------------

_MUTATORS = frozenset({
    "append", "appendleft", "extend", "insert", "add", "update",
    "setdefault", "pop", "popleft", "popitem", "remove", "discard",
    "clear", "sort",
})


@register
class LockDisciplineChecker(Checker):
    rule = "RPA002"
    title = "lock discipline / race detection"

    def applies(self, ctx: ModuleContext) -> bool:
        return "ThreadPoolExecutor" in ctx.source

    def check(self, ctx: ModuleContext) -> List[Finding]:
        out: List[Finding] = []
        for node in ctx.tree.body:
            if isinstance(node, ast.ClassDef):
                out.extend(self._check_class(ctx, node))
        return out

    def _check_class(self, ctx: ModuleContext,
                     cls: ast.ClassDef) -> List[Finding]:
        executor_methods = self._executor_methods(cls)
        if not executor_methods:
            return []
        # (method, attr, node, locked) for every self-attribute write, plus
        # worker touches (attr None) in executor methods
        writes: List[Tuple[str, Optional[str], ast.AST, bool]] = []
        for fn in cls.body:
            if isinstance(fn, ast.FunctionDef):
                self._scan(fn, fn.body, locked=False, writes=writes,
                           aliases=set(),
                           on_executor=fn.name in executor_methods)
        exec_attrs = {attr for m, attr, _, _ in writes
                      if m in executor_methods and attr is not None}
        out: List[Finding] = []
        for method, attr, node, locked in writes:
            if locked:
                continue
            if method in executor_methods:
                what = (f"self.{attr}" if attr is not None
                        else "a shard worker")
                out.append(self.finding(
                    ctx, node,
                    f"{cls.name}.{method} runs on the executor but mutates "
                    f"{what} outside a `with self._lock(s)` block"))
            elif attr in exec_attrs and method != "__init__":
                # __init__ runs before the object is published to the pool
                out.append(self.finding(
                    ctx, node,
                    f"self.{attr} is also mutated on the executor; this "
                    f"write in {cls.name}.{method} must hold the owning "
                    "self._lock(s)"))
        return out

    @staticmethod
    def _executor_methods(cls: ast.ClassDef) -> Set[str]:
        targets: Set[str] = set()
        for node in ast.walk(cls):
            if (isinstance(node, ast.Call)
                    and dotted(node.func) == "self._pool.submit"
                    and node.args):
                name = dotted(node.args[0])
                if name and name.startswith("self."):
                    targets.add(name.split(".", 1)[1])
        return targets

    @classmethod
    def _is_lock_ctx(cls, expr: ast.AST) -> bool:
        for node in ast.walk(expr):
            name = dotted(node)
            if name is not None and name.startswith("self._lock"):
                return True
        return False

    @classmethod
    def _scan(cls, fn: ast.FunctionDef, stmts, locked: bool,
              writes: List, aliases: Set[str], on_executor: bool) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.With):
                inner = locked or any(cls._is_lock_ctx(i.context_expr)
                                      for i in stmt.items)
                cls._scan(fn, stmt.body, inner, writes, aliases, on_executor)
                continue
            if isinstance(stmt, (ast.If, ast.For, ast.While)):
                cls._scan(fn, stmt.body, locked, writes, aliases, on_executor)
                cls._scan(fn, stmt.orelse, locked, writes, aliases,
                          on_executor)
                continue
            if isinstance(stmt, ast.Try):
                for blk in (stmt.body, stmt.orelse, stmt.finalbody):
                    cls._scan(fn, blk, locked, writes, aliases, on_executor)
                for h in stmt.handlers:
                    cls._scan(fn, h.body, locked, writes, aliases,
                              on_executor)
                continue
            cls._scan_stmt(fn, stmt, locked, writes, aliases, on_executor)

    @classmethod
    def _scan_stmt(cls, fn: ast.FunctionDef, stmt: ast.stmt, locked: bool,
                   writes: List, aliases: Set[str],
                   on_executor: bool) -> None:
        # worker aliasing: svc = self._workers[s]
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                and isinstance(stmt.targets[0], ast.Name) \
                and isinstance(stmt.value, ast.Subscript) \
                and dotted(stmt.value.value) == "self._workers":
            aliases.add(stmt.targets[0].id)
        targets: List[ast.AST] = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
            targets = [stmt.target]
        for t in targets:
            attr = _self_attr(t)
            if attr is not None:
                writes.append((fn.name, attr, t, locked))
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            if not isinstance(func, ast.Attribute):
                continue
            # mutating call on self.<attr> / self.<attr>[...]
            if func.attr in _MUTATORS:
                attr = _self_attr(func.value)
                if attr is not None:
                    writes.append((fn.name, attr, node, locked))
            # any call through a shard worker while on the executor
            if on_executor:
                base = func.value
                if (isinstance(base, ast.Subscript)
                        and dotted(base.value) == "self._workers") or (
                        isinstance(base, ast.Name) and base.id in aliases):
                    writes.append((fn.name, None, node, locked))


# ---------------------------------------------------------------------------
# RPA003 — serialization determinism in container writers
# ---------------------------------------------------------------------------

_NONDET_CALLS = frozenset({
    "time.time", "time.time_ns", "time.monotonic", "time.perf_counter",
    "datetime.now", "datetime.utcnow", "datetime.today",
    "datetime.datetime.now", "datetime.datetime.utcnow", "date.today",
    "os.urandom", "uuid.uuid1", "uuid.uuid4", "os.getpid",
})
_NONDET_PREFIXES = ("random.", "np.random.", "numpy.random.")
_UNORDERED_FS = frozenset({
    "os.listdir", "os.scandir", "glob.glob", "glob.iglob",
})


@register
class SerializationDeterminismChecker(Checker):
    rule = "RPA003"
    title = "bitstream determinism"

    MODULES = ("repro/core/container.py", "repro/api/container.py")

    def applies(self, ctx: ModuleContext) -> bool:
        return ctx.path in self.MODULES or any(
            self._is_writer_name(fn.name) for fn in _functions(ctx.tree))

    @staticmethod
    def _is_writer_name(name: str) -> bool:
        return ("pack_" in name or name.endswith("_blobs")
                or name.endswith("_sections"))

    def check(self, ctx: ModuleContext) -> List[Finding]:
        if ctx.path in self.MODULES:
            scopes: List[ast.AST] = [ctx.tree]
        else:
            scopes = [fn for fn in _functions(ctx.tree)
                      if self._is_writer_name(fn.name)]
        out: List[Finding] = []
        for scope in scopes:
            out.extend(self._check_scope(ctx, scope))
        return out

    def _check_scope(self, ctx: ModuleContext,
                     scope: ast.AST) -> List[Finding]:
        out: List[Finding] = []
        sorted_args = {
            id(arg)
            for node in ast.walk(scope)
            if isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name) and node.func.id == "sorted"
            for arg in node.args
        }
        iters = []
        for node in ast.walk(scope):
            if isinstance(node, ast.For):
                iters.append(node.iter)
            elif isinstance(node, (ast.ListComp, ast.SetComp, ast.DictComp,
                                   ast.GeneratorExp)):
                iters.extend(g.iter for g in node.generators)
        for it in iters:
            reason = self._unordered_iter(it)
            if reason and id(it) not in sorted_args:
                out.append(self.finding(
                    ctx, it,
                    f"unsorted iteration over {reason} in a serialization "
                    "path; ordering must be explicit or the byte stream "
                    "can drift"))
        for node in ast.walk(scope):
            if not isinstance(node, ast.Call):
                continue
            name = dotted(node.func)
            if name is None:
                continue
            if name in _NONDET_CALLS or name.startswith(_NONDET_PREFIXES):
                out.append(self.finding(
                    ctx, node,
                    f"nondeterministic call {name}() inside a serialization "
                    "path; the byte stream must be a pure function of the "
                    "index"))
            elif ((name in _UNORDERED_FS or name.endswith(".iterdir"))
                  and id(node) not in sorted_args):
                out.append(self.finding(
                    ctx, node,
                    f"{name}() returns OS-ordered entries; wrap in "
                    "sorted(...) inside serialization paths"))
        return out

    @staticmethod
    def _unordered_iter(it: ast.AST) -> Optional[str]:
        if isinstance(it, ast.Set):
            return "a set literal"
        if isinstance(it, ast.Call):
            if isinstance(it.func, ast.Name) and it.func.id in ("set",
                                                                "frozenset"):
                return f"{it.func.id}(...)"
            if isinstance(it.func, ast.Attribute) and it.func.attr in (
                    "keys", "values", "items"):
                return f".{it.func.attr}() of a dict"
        return None


# ---------------------------------------------------------------------------
# RPA004 — overflow / width contracts on wide shifts
# ---------------------------------------------------------------------------

@register
class WidthContractChecker(Checker):
    rule = "RPA004"
    title = "overflow/width contracts"

    WIDE_BITS = 32

    def check(self, ctx: ModuleContext) -> List[Finding]:
        consts = self._module_consts(ctx.tree)
        out: List[Finding] = []
        # map each wide shift to its nearest enclosing function (or module)
        scopes: List[ast.AST] = [ctx.tree] + list(_functions(ctx.tree))
        seen: Set[int] = set()
        for scope in reversed(scopes):        # innermost functions last
            for node in ast.walk(scope):
                if id(node) in seen or not self._is_wide_shift(node, consts):
                    continue
                seen.add(id(node))
                if scope is not ctx.tree and node is scope:
                    continue
                if not self._guarded(scope, node, consts):
                    amount = self._shift_amount(node.right, consts)
                    out.append(self.finding(
                        ctx, node,
                        f"<< {amount} bit-packing without an explicit bound "
                        "check (raise OverflowError / compare against "
                        "1 << BITS) or uint64 cast in the same scope; a "
                        "silent wrap corrupts packed keys"))
        return out

    @classmethod
    def _module_consts(cls, tree: ast.Module) -> Dict[str, int]:
        consts: Dict[str, int] = {}
        for stmt in tree.body:
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)):
                val = cls._fold(stmt.value, consts)
                if isinstance(val, int):
                    consts[stmt.targets[0].id] = val
        return consts

    @classmethod
    def _fold(cls, node: ast.AST, consts: Dict[str, int]) -> Optional[int]:
        if isinstance(node, ast.Constant) and isinstance(node.value, int) \
                and not isinstance(node.value, bool):
            return node.value
        if isinstance(node, ast.Name):
            return consts.get(node.id)
        if isinstance(node, ast.BinOp):
            left = cls._fold(node.left, consts)
            right = cls._fold(node.right, consts)
            if left is None or right is None:
                return None
            if isinstance(node.op, ast.Add):
                return left + right
            if isinstance(node.op, ast.Sub):
                return left - right
            if isinstance(node.op, ast.Mult):
                return left * right
            if isinstance(node.op, ast.LShift):
                return left << right
            if isinstance(node.op, ast.BitOr):
                return left | right
            return None
        if isinstance(node, ast.Call) and len(node.args) == 1:
            name = dotted(node.func) or ""
            if name.split(".")[-1] in ("uint64", "int64", "uint32", "int"):
                return cls._fold(node.args[0], consts)
        return None

    @classmethod
    def _shift_amount(cls, right: ast.AST,
                      consts: Dict[str, int]) -> Optional[int]:
        return cls._fold(right, consts)

    @classmethod
    def _is_wide_shift(cls, node: ast.AST, consts: Dict[str, int]) -> bool:
        if not (isinstance(node, ast.BinOp)
                and isinstance(node.op, ast.LShift)):
            return False
        if isinstance(node.left, ast.Constant):
            return False                      # python-int literal: no wrap
        amount = cls._shift_amount(node.right, consts)
        return amount is not None and amount >= cls.WIDE_BITS

    @classmethod
    def _guarded(cls, scope: ast.AST, shift: ast.BinOp,
                 consts: Dict[str, int]) -> bool:
        # the shifted operand itself carries a uint64 cast
        left_name = dotted(shift.left)
        if left_name is not None and left_name.split(".")[-1] == "uint64":
            return True
        if isinstance(shift.left, ast.Call):
            fname = (dotted(shift.left.func) or "").split(".")[-1]
            if fname in ("uint64", "int64"):
                return True
        for node in ast.walk(scope):
            if isinstance(node, ast.Raise):
                exc = node.exc
                name = dotted(exc.func) if isinstance(exc, ast.Call) \
                    else dotted(exc)
                if name and "OverflowError" in name:
                    return True
            if isinstance(node, ast.Compare):
                for part in [node.left] + list(node.comparators):
                    if any(isinstance(sub, ast.BinOp)
                           and isinstance(sub.op, ast.LShift)
                           for sub in ast.walk(part)):
                        return True
            if isinstance(node, ast.Call):
                name = (dotted(node.func) or "").split(".")[-1]
                if name == "uint64":
                    return True
                if name in ("asarray", "astype") and any(
                        (dotted(a) or "").split(".")[-1] == "uint64"
                        for a in node.args):
                    return True
        return False


# ---------------------------------------------------------------------------
# RPA005 — jit / pallas purity
# ---------------------------------------------------------------------------

@register
class JitPurityChecker(Checker):
    rule = "RPA005"
    title = "jit/scan purity"

    MODULES = ("repro/ann/scan.py", "repro/ann/graph_scan.py")

    def applies(self, ctx: ModuleContext) -> bool:
        return (ctx.path.startswith("repro/kernels/")
                or ctx.path in self.MODULES)

    def check(self, ctx: ModuleContext) -> List[Finding]:
        kernel_names = self._pallas_kernel_names(ctx.tree)
        out: List[Finding] = []
        self._visit(ctx, ctx.tree.body, kernel_names, restricted=False,
                    out=out)
        return out

    @staticmethod
    def _pallas_kernel_names(tree: ast.AST) -> Set[str]:
        names: Set[str] = set()
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call) and node.args
                    and (dotted(node.func) or "").split(".")[-1]
                    == "pallas_call"
                    and isinstance(node.args[0], ast.Name)):
                names.add(node.args[0].id)
        return names

    @staticmethod
    def _is_jitted(fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            name = dotted(dec)
            if name is not None and name.split(".")[-1] in ("jit", "vmap"):
                return True
            if isinstance(dec, ast.Call):
                fname = (dotted(dec.func) or "").split(".")[-1]
                if fname in ("jit", "vmap"):
                    return True
                if fname == "partial" and any(
                        (dotted(a) or "").split(".")[-1] in ("jit", "vmap")
                        for a in dec.args):
                    return True
        return False

    def _visit(self, ctx: ModuleContext, stmts, kernel_names: Set[str],
               restricted: bool, out: List[Finding]) -> None:
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                inner = (restricted or stmt.name in kernel_names
                         or self._is_jitted(stmt))
                if inner:
                    self._check_traced(ctx, stmt, out)
                else:
                    self._visit(ctx, stmt.body, kernel_names, False, out)
            elif isinstance(stmt, ast.ClassDef):
                self._visit(ctx, stmt.body, kernel_names, restricted, out)
            elif hasattr(stmt, "body"):
                self._visit(ctx, stmt.body, kernel_names, restricted, out)
                for blk in ("orelse", "finalbody"):
                    self._visit(ctx, getattr(stmt, blk, []), kernel_names,
                                restricted, out)
                for h in getattr(stmt, "handlers", []):
                    self._visit(ctx, h.body, kernel_names, restricted, out)

    def _check_traced(self, ctx: ModuleContext, fn: ast.FunctionDef,
                      out: List[Finding]) -> None:
        where = f"traced function {fn.name}()"
        for node in ast.walk(fn):
            if isinstance(node, (ast.Global, ast.Nonlocal)):
                out.append(self.finding(
                    ctx, node, f"global/nonlocal mutation inside {where}: "
                    "traced code must be pure"))
            elif isinstance(node, ast.Assign):
                for t in node.targets:
                    if isinstance(t, ast.Attribute):
                        out.append(self.finding(
                            ctx, node,
                            f"Python-side attribute mutation inside {where}: "
                            "side effects are silently dropped under jit"))
            elif isinstance(node, ast.Call):
                name = dotted(node.func)
                if isinstance(node.func, ast.Name) and node.func.id == \
                        "print":
                    out.append(self.finding(
                        ctx, node, f"host print() inside {where}: runs at "
                        "trace time only"))
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in ("float", "int", "bool") \
                        and node.args \
                        and not all(isinstance(a, ast.Constant)
                                    for a in node.args):
                    out.append(self.finding(
                        ctx, node,
                        f"{node.func.id}() scalar coercion inside {where}: "
                        "forces a host sync / fails under tracing"))
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("item", "tolist"):
                    out.append(self.finding(
                        ctx, node,
                        f".{node.func.attr}() inside {where}: forces a host "
                        "sync / fails under tracing"))
                elif name is not None and name.startswith(("np.",
                                                           "numpy.")):
                    out.append(self.finding(
                        ctx, node,
                        f"host-numpy call {name}() inside {where}: silently "
                        "constant-folds at trace time; use jnp"))
                elif name is not None and name.startswith("time."):
                    out.append(self.finding(
                        ctx, node,
                        f"wall-clock read {name}() inside {where}: traced "
                        "code must be pure"))


# ---------------------------------------------------------------------------
# RPA006 — broad-except hygiene
# ---------------------------------------------------------------------------

@register
class BroadExceptChecker(Checker):
    rule = "RPA006"
    title = "broad-except hygiene"

    def check(self, ctx: ModuleContext) -> List[Finding]:
        return [self.finding(
                    ctx, node,
                    "broad `except Exception`; catch the concrete failure "
                    "types (e.g. ShardTimeout/ShardDead/TimeoutError) and "
                    "record into stats")
                for node in ast.walk(ctx.tree)
                if isinstance(node, ast.ExceptHandler)
                and self._is_broad(node.type)]

    @staticmethod
    def _is_broad(type_node: Optional[ast.AST]) -> bool:
        if type_node is None:
            return True
        names = ([dotted(e) for e in type_node.elts]
                 if isinstance(type_node, ast.Tuple) else [dotted(type_node)])
        return any(n is not None
                   and n.split(".")[-1] in ("Exception", "BaseException")
                   for n in names)



# ---------------------------------------------------------------------------
# RPA007 — spec-grammar / docs drift
# ---------------------------------------------------------------------------

_GRAMMAR_FENCE_RE = re.compile(
    r"```[^\n`]*spec-grammar[^\n`]*\n(.*?)\n```", re.DOTALL)
_GRAMMAR_KEY_RE = re.compile(r"^\s*([a-z_]+)\s*=\s", re.MULTILINE)


@register
class SpecGrammarDriftChecker(Checker):
    rule = "RPA007"
    title = "spec-grammar/docs drift"

    MODULE = "repro/api/spec.py"
    DOC = os.path.join("docs", "architecture.md")
    KEYS_NAME = "KNOWN_OPTION_KEYS"

    def applies(self, ctx: ModuleContext) -> bool:
        return ctx.path == self.MODULE

    def check(self, ctx: ModuleContext) -> List[Finding]:
        code_keys = self._option_keys(ctx.tree)
        if code_keys is None:
            return [self.finding(
                ctx, 1,
                f"spec module must define {self.KEYS_NAME} as a "
                "module-level tuple of string literals (the grammar keys "
                "docs/architecture.md documents)")]
        line, keys = code_keys
        doc_path = self._locate_doc(ctx.fs_path)
        if doc_path is None:
            return [self.finding(
                ctx, line,
                f"cannot locate {self.DOC} above {ctx.fs_path}; the "
                "factory-string grammar must have a docs page")]
        with open(doc_path, encoding="utf-8") as fh:
            doc_keys = self._doc_keys(fh.read())
        if doc_keys is None:
            return [self.finding(
                ctx, line,
                f"{self.DOC} has no ```spec-grammar fenced block; the "
                "documented grammar is what RPA007 checks against")]
        out: List[Finding] = []
        for key in keys:
            if key not in doc_keys:
                out.append(self.finding(
                    ctx, line,
                    f"spec option {key!r} is parsed but missing from the "
                    f"spec-grammar block in {self.DOC}"))
        for key in doc_keys:
            if key not in keys:
                out.append(self.finding(
                    ctx, line,
                    f"spec option {key!r} is documented in the "
                    f"spec-grammar block of {self.DOC} but not parsed "
                    f"({self.KEYS_NAME})"))
        return out

    @classmethod
    def _option_keys(cls, tree: ast.Module
                     ) -> Optional[Tuple[int, Tuple[str, ...]]]:
        for stmt in tree.body:
            if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and stmt.targets[0].id == cls.KEYS_NAME):
                continue
            if not isinstance(stmt.value, (ast.Tuple, ast.List)):
                return None
            keys: List[str] = []
            for elt in stmt.value.elts:
                if not (isinstance(elt, ast.Constant)
                        and isinstance(elt.value, str)):
                    return None
                keys.append(elt.value)
            return stmt.lineno, tuple(keys)
        return None

    @classmethod
    def _locate_doc(cls, fs_path: str) -> Optional[str]:
        d = os.path.dirname(os.path.abspath(fs_path))
        while True:
            cand = os.path.join(d, cls.DOC)
            if os.path.isfile(cand):
                return cand
            parent = os.path.dirname(d)
            if parent == d:
                return None
            d = parent

    @staticmethod
    def _doc_keys(doc: str) -> Optional[Tuple[str, ...]]:
        m = _GRAMMAR_FENCE_RE.search(doc)
        if m is None:
            return None
        return tuple(_GRAMMAR_KEY_RE.findall(m.group(1)))
