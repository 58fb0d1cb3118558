"""recompile-hazard: jit/kernel construction that defeats the compile
cache, and traced-value Python branches inside jitted functions.

The serving paths stay fast because compilation happens once per shape:
jitted steps live at module level (``_jit_step = jax.jit(...)``) or
behind ``functools.lru_cache`` builders (``_mesh_pallas_step``). A
``jax.jit``/``pallas_call`` constructed inside a loop — or inside a plain
per-call function — builds a fresh callable each time, and one stray
recompile is a stall of seconds in the middle of a flush.

Rules:

- ``jax.jit(...)`` / ``functools.partial(jax.jit, ...)`` / ``pl.pallas_call``
  / ``.lower(...).compile()`` inside a ``for``/``while`` body: flagged.
- The same constructions inside a function body (not module level):
  flagged unless the enclosing function is cached
  (``functools.lru_cache``/``cache``) or is itself jit-decorated
  (``pallas_call`` under a jitted entry point traces once per shape
  through the jit cache).
- Inside a jit-decorated function, ``if``/``while`` tests that reference
  a NON-static parameter directly (not through ``.shape``/``.ndim``/
  ``.dtype``, which are static at trace time): flagged as
  shape-dependent Python branching on a traced value.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Optional, Set, Tuple

from tools.graftlint import config
from tools.graftlint.core import Finding, ModuleSource, scope_files
from tools.graftlint.passes.host_sync import _decorated_jit

_STATIC_ATTRS = ("shape", "ndim", "dtype", "size")


def _is_jit_ctor(node: ast.Call) -> Optional[str]:
    """'jax.jit' / 'pallas_call' / 'compile' when this call constructs a
    compiled callable."""
    f = node.func
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "jit"
        and isinstance(f.value, ast.Name)
        and f.value.id == "jax"
    ):
        return "jax.jit"
    if isinstance(f, ast.Attribute) and f.attr == "pallas_call":
        return "pallas_call"
    if isinstance(f, ast.Name) and f.id == "pallas_call":
        return "pallas_call"
    # functools.partial(jax.jit, ...) used as a value
    is_partial = (
        isinstance(f, ast.Name) and f.id == "partial"
    ) or (
        isinstance(f, ast.Attribute)
        and f.attr == "partial"
        and isinstance(f.value, ast.Name)
        and f.value.id == "functools"
    )
    if is_partial and node.args:
        a0 = node.args[0]
        if (
            isinstance(a0, ast.Attribute)
            and a0.attr == "jit"
            and isinstance(a0.value, ast.Name)
            and a0.value.id == "jax"
        ):
            return "functools.partial(jax.jit, ...)"
    # X.lower(...).compile()
    if (
        isinstance(f, ast.Attribute)
        and f.attr == "compile"
        and isinstance(f.value, ast.Call)
        and isinstance(f.value.func, ast.Attribute)
        and f.value.func.attr == "lower"
    ):
        return ".lower().compile()"
    return None


def _cached_def(fn: ast.AST) -> bool:
    """Decorated with functools.lru_cache / functools.cache (bare names
    included)."""
    for dec in getattr(fn, "decorator_list", []):
        target = dec.func if isinstance(dec, ast.Call) else dec
        name = None
        if isinstance(target, ast.Attribute):
            name = target.attr
        elif isinstance(target, ast.Name):
            name = target.id
        if name in ("lru_cache", "cache"):
            return True
    return False


def _static_params(fn: ast.AST) -> Tuple[Set[str], bool]:
    """(static parameter names, is_jitted) from @jax.jit /
    @functools.partial(jax.jit, static_argnums=..., static_argnames=...)."""
    if not _decorated_jit(fn):
        return set(), False
    args = fn.args
    ordered = [a.arg for a in args.posonlyargs + args.args]
    static: Set[str] = set()
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        for kw in dec.keywords:
            if kw.arg == "static_argnums":
                try:
                    nums = ast.literal_eval(kw.value)
                except ValueError:
                    continue
                if isinstance(nums, int):
                    nums = (nums,)
                for i in nums:
                    if 0 <= i < len(ordered):
                        static.add(ordered[i])
            elif kw.arg == "static_argnames":
                try:
                    names = ast.literal_eval(kw.value)
                except ValueError:
                    continue
                if isinstance(names, str):
                    names = (names,)
                static.update(names)
    # Keyword-only params without static_argnames are still traced, but
    # jit entry points here pass them statically (block_docs=, interpret=)
    # — jax itself errors otherwise, so treat kwonly as static.
    static.update(a.arg for a in args.kwonlyargs)
    return static, True


class RecompileHazardPass:
    id = "recompile-hazard"

    def scope(self, root: str) -> List[str]:
        return scope_files(root, config.DEVICE_PATH_SCOPE)

    def run(self, src: ModuleSource) -> Iterator[Tuple[Finding, ast.AST]]:
        yield from self._walk(src, src.tree.body, fn_stack=[], loop_depth=0)

    def _walk(
        self,
        src: ModuleSource,
        body: List[ast.stmt],
        fn_stack: List[ast.AST],
        loop_depth: int,
    ) -> Iterator[Tuple[Finding, ast.AST]]:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield from self._check_jit_branches(src, stmt)
                yield from self._walk(
                    src, stmt.body, fn_stack + [stmt], loop_depth=0
                )
                continue
            if isinstance(stmt, ast.ClassDef):
                yield from self._walk(src, stmt.body, fn_stack, loop_depth)
                continue
            in_loop = loop_depth > 0
            # Compound statements contribute only their header
            # expressions here; their bodies recurse below (walking the
            # whole subtree would double-count).
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                roots: List[ast.AST] = [stmt.iter]
            elif isinstance(stmt, (ast.If, ast.While)):
                roots = [stmt.test]
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                roots = [i.context_expr for i in stmt.items]
            elif isinstance(stmt, ast.Try):
                roots = []
            else:
                roots = [stmt]
            for node in (n for r in roots for n in ast.walk(r)):
                if not isinstance(node, ast.Call):
                    continue
                kind = _is_jit_ctor(node)
                if kind is None:
                    continue
                if in_loop:
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"{kind} constructed inside a loop builds a "
                            "fresh compiled callable per iteration — "
                            "hoist to module level or an lru_cache "
                            "builder",
                        ),
                        stmt,
                    )
                elif fn_stack and not any(
                    _cached_def(f) or _decorated_jit(f) for f in fn_stack
                ):
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"{kind} constructed per call (enclosing "
                            f"function {fn_stack[-1].name!r} is neither "
                            "cached nor jitted) — each call re-traces; "
                            "hoist to module level or wrap the builder "
                            "in functools.lru_cache",
                        ),
                        stmt,
                    )
            if isinstance(stmt, (ast.For, ast.AsyncFor, ast.While)):
                yield from self._walk(
                    src, stmt.body, fn_stack, loop_depth + 1
                )
                yield from self._walk(
                    src, stmt.orelse, fn_stack, loop_depth
                )
            elif isinstance(stmt, ast.If):
                yield from self._walk(src, stmt.body, fn_stack, loop_depth)
                yield from self._walk(src, stmt.orelse, fn_stack, loop_depth)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                yield from self._walk(src, stmt.body, fn_stack, loop_depth)
            elif isinstance(stmt, ast.Try):
                for blk in (stmt.body, stmt.orelse, stmt.finalbody):
                    yield from self._walk(src, blk, fn_stack, loop_depth)
                for h in stmt.handlers:
                    yield from self._walk(src, h.body, fn_stack, loop_depth)

    def _check_jit_branches(
        self, src: ModuleSource, fn: ast.AST
    ) -> Iterator[Tuple[Finding, ast.AST]]:
        static, jitted = _static_params(fn)
        if not jitted:
            return
        args = fn.args
        traced = {
            a.arg
            for a in args.posonlyargs + args.args
            if a.arg not in static
        }
        if not traced:
            return
        for stmt in ast.walk(fn):
            if not isinstance(stmt, (ast.If, ast.While)):
                continue
            hits = sorted(_traced_refs(stmt.test, traced))
            if hits:
                yield (
                    src.finding(
                        self.id,
                        stmt.test,
                        "Python branch on traced value(s) "
                        f"{', '.join(hits)} inside jitted "
                        f"{fn.name!r} — this is a shape/trace-time "
                        "decision at best and a TracerBoolConversionError "
                        "at worst; use lax.cond/jnp.where or mark the "
                        "argument static",
                    ),
                    stmt,
                )


def _traced_refs(test: ast.AST, traced: Set[str]) -> Set[str]:
    """Traced parameter names the test reads OUTSIDE static attribute
    contexts (.shape/.ndim/.dtype/.size are trace-time constants)."""
    hits: Set[str] = set()

    def visit(node: ast.AST) -> None:
        if (
            isinstance(node, ast.Attribute)
            and node.attr in _STATIC_ATTRS
        ):
            return  # x.shape[...] is static — don't descend into x
        if isinstance(node, ast.Name) and node.id in traced:
            hits.add(node.id)
            return
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(test)
    return hits
