"""host-sync: implicit device→host transfers in device-path modules.

The serving paths stage uploads and drain readbacks deliberately — every
transfer is part of a documented cost model (one readback per boxcar,
bytes crossing host↔device counted). An ``int(device_scalar)`` that creeps into a loop, or an
``np.asarray(pool.state.err)`` added for a quick stat, is a synchronous
device round-trip the profiles will blame on the kernels. This pass flags
them all; intentional ones carry ``# graftlint: readback(<reason>)``.

Detection is a single-forward-pass local taint analysis, not type
inference: an expression is *device-tainted* when it reaches through

- an attribute whose terminal name is a known device-state idiom
  (``config.DEVICE_ATTRS``: ``pool.state``, ``self.tables``, ...);
- a call to a jit-built function (module-level ``x = jax.jit(...)``,
  ``@jax.jit`` / ``@functools.partial(jax.jit, ...)`` defs);
- a call to anything imported from the kernel modules
  (``config.KERNEL_MODULE_PREFIXES``);
- a call into ``jnp.*`` / ``jax.device_put``;
- a local name last assigned from a tainted expression (loop targets over
  tainted iterables included).

``np.asarray``/``np.array`` over a tainted argument is the readback
boundary: the call is flagged and its RESULT is host (so downstream
``int()`` over it is clean). ``.item()`` and ``block_until_ready`` are
flagged unconditionally — in a device-path module there is no innocent
reading of either.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterator, List, Optional, Set, Tuple

from tools.graftlint import config
from tools.graftlint.core import Finding, ModuleSource, scope_files

_SCALARIZERS = ("int", "float", "bool")


def _is_np(func: ast.AST, names: Tuple[str, ...]) -> bool:
    """``np.asarray`` / ``numpy.array`` style attribute calls."""
    return (
        isinstance(func, ast.Attribute)
        and func.attr in names
        and isinstance(func.value, ast.Name)
        and func.value.id in ("np", "numpy")
    )


def _is_jnp_call(func: ast.AST) -> bool:
    if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
        if func.value.id == "jnp":
            return True
        if func.value.id == "jax" and func.attr == "device_put":
            return True
    return False


def _decorated_jit(fn: ast.AST) -> bool:
    """``@jax.jit`` or ``@functools.partial(jax.jit, ...)`` (also bare
    ``partial(jax.jit, ...)``)."""
    for dec in getattr(fn, "decorator_list", []):
        if (
            isinstance(dec, ast.Attribute)
            and dec.attr == "jit"
            and isinstance(dec.value, ast.Name)
            and dec.value.id == "jax"
        ):
            return True
        if isinstance(dec, ast.Call):
            f = dec.func
            if (
                isinstance(f, ast.Attribute)
                and f.attr == "jit"
                and isinstance(f.value, ast.Name)
                and f.value.id == "jax"
            ):
                return True
            is_partial = (
                isinstance(f, ast.Name) and f.id == "partial"
            ) or (
                isinstance(f, ast.Attribute)
                and f.attr == "partial"
                and isinstance(f.value, ast.Name)
                and f.value.id == "functools"
            )
            if is_partial and dec.args:
                a0 = dec.args[0]
                if (
                    isinstance(a0, ast.Attribute)
                    and a0.attr == "jit"
                    and isinstance(a0.value, ast.Name)
                    and a0.value.id == "jax"
                ):
                    return True
    return False


def device_fn_names(tree: ast.AST) -> Set[str]:
    """Module-level names whose CALL yields a device value: jit-built
    callables and kernel-module imports."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module:
            if node.module.startswith(config.KERNEL_MODULE_PREFIXES):
                for alias in node.names:
                    name = alias.asname or alias.name
                    # Functions only: CamelCase imports are container
                    # constructors (SegmentState) whose taint follows
                    # their arguments, ALL_CAPS are constants.
                    if name[:1].islower():
                        out.add(name)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if _decorated_jit(node):
                out.add(node.name)
        elif isinstance(node, ast.Assign):
            v = node.value
            if (
                isinstance(v, ast.Call)
                and isinstance(v.func, ast.Attribute)
                and v.func.attr == "jit"
                and isinstance(v.func.value, ast.Name)
                and v.func.value.id == "jax"
            ):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        out.add(t.id)
    return out


def _seed_params(taint: "_Taint", fn: ast.AST) -> None:
    """Device-param contract (``config.DEVICE_PARAM_FNS``): the off-loop
    transfer halves receive concrete device arrays by design — their
    parameters START tainted so the np.asarray inside is a verified
    readback, not an invisible one."""
    if getattr(fn, "name", None) not in config.DEVICE_PARAM_FNS:
        return
    a = fn.args
    for arg in (
        list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
    ):
        if arg.arg not in ("self", "cls"):
            taint.env[arg.arg] = True


def device_method_names(
    tree: ast.AST, device_fns: Set[str]
) -> Tuple[Set[str], Set[str]]:
    """(device-returning names, ALL local function names): same-module
    functions/methods whose return value is device-tainted, as a fixed
    point (a method returning another device method's result is itself
    a device source). The full name set makes the summaries
    authoritative — a local call NOT in the device set returns host."""
    fns = [
        n
        for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    local = {fn.name for fn in fns}
    methods: Set[str] = set()
    changed = True
    while changed:
        changed = False
        for fn in fns:
            if fn.name in methods:
                continue
            if _returns_tainted(fn, device_fns, methods, local):
                methods.add(fn.name)
                changed = True
    return methods, local


def _returns_tainted(
    fn: ast.AST, device_fns: Set[str], methods: Set[str], local: Set[str]
) -> bool:
    taint = _Taint(device_fns, methods, local)
    _seed_params(taint, fn)
    found = False

    def walk(body) -> None:
        nonlocal found
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if (
                isinstance(stmt, ast.Return)
                and stmt.value is not None
                and taint.tainted(stmt.value)
            ):
                found = True
            if isinstance(stmt, ast.Assign):
                v = taint.tainted(stmt.value)
                for t in stmt.targets:
                    taint.bind(t, v)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                taint.bind(stmt.target, taint.tainted(stmt.value))
            elif isinstance(stmt, ast.AugAssign):
                if taint.tainted(stmt.value):
                    taint.bind(stmt.target, True)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                taint.bind(stmt.target, taint.tainted(stmt.iter))
                walk(stmt.body)
                walk(stmt.orelse)
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                walk(stmt.body)
            elif isinstance(stmt, (ast.If, ast.While)):
                walk(stmt.body)
                walk(stmt.orelse)
            elif isinstance(stmt, ast.Try):
                for blk in (stmt.body, stmt.orelse, stmt.finalbody):
                    walk(blk)
                for h in stmt.handlers:
                    walk(h.body)

    walk(fn.body)
    return found


class _Taint:
    """Local device-taint evaluation for one function (or module) body.

    ``device_methods`` are same-module functions/methods whose RETURN is
    device-tainted (computed by :func:`device_method_names` as a fixed
    point) — ``self._telemetry_device()`` is as much a device source as
    a jitted call, and without the summary the readback pragma on its
    consumer would be unverifiable."""

    def __init__(
        self,
        device_fns: Set[str],
        device_methods: Set[str] = frozenset(),
        local_fns: Set[str] = frozenset(),
    ):
        self.device_fns = device_fns
        self.device_methods = device_methods
        # Every same-module function name: where a summary exists it is
        # AUTHORITATIVE — a local call not in device_methods returns
        # host, even over tainted args (the generic carries-taint rule
        # is for constructors/unknown callees only).
        self.local_fns = local_fns
        self.env: Dict[str, bool] = {}

    # -- expression taint ------------------------------------------------------

    def tainted(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return self.env.get(node.id, False)
        if isinstance(node, ast.Attribute):
            if node.attr in ("shape", "ndim", "dtype", "size"):
                return False  # array metadata lives on host
            if node.attr in config.DEVICE_ATTRS:
                return True
            return self.tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.tainted(node.value)
        if isinstance(node, ast.Call):
            f = node.func
            # The readback boundary: the result of np.asarray/np.array is
            # HOST regardless of the argument.
            if _is_np(f, ("asarray", "array")):
                return False
            if isinstance(f, ast.Name):
                if f.id in self.device_fns:
                    return True
                if f.id == "getattr" and node.args:
                    return self.tainted(node.args[0])
                if f.id in _SCALARIZERS + ("len", "str", "repr", "range"):
                    return False
            if _is_jnp_call(f):
                return True
            # Same-module functions/methods: the computed return-taint
            # summary decides, in either direction.
            if isinstance(f, ast.Name) and f.id in self.local_fns:
                return f.id in self.device_methods
            if (
                isinstance(f, ast.Attribute)
                and isinstance(f.value, ast.Name)
                and f.value.id in ("self", "cls")
                and f.attr in self.local_fns
            ):
                return f.attr in self.device_methods
            # Method call on a tainted receiver stays on device
            # (dev.sum(), state._replace(...), tainted[i].max()).
            if isinstance(f, ast.Attribute):
                if f.attr in ("tolist", "item"):
                    return False  # readback boundary (flagged separately)
                if self.tainted(f.value):
                    return True
            # A constructor over tainted elements carries the taint
            # (SegmentState(*[...]) of device lanes is still device).
            return any(
                self.tainted(a)
                for a in list(node.args)
                + [kw.value for kw in node.keywords]
            )
        if isinstance(node, ast.Starred):
            return self.tainted(node.value)
        if isinstance(node, (ast.BinOp,)):
            return self.tainted(node.left) or self.tainted(node.right)
        if isinstance(node, ast.UnaryOp):
            return self.tainted(node.operand)
        if isinstance(node, ast.IfExp):
            return self.tainted(node.body) or self.tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List)):
            return any(self.tainted(e) for e in node.elts)
        if isinstance(node, (ast.ListComp, ast.GeneratorExp, ast.SetComp)):
            sub = self._comp_scope(node.generators)
            return sub._eval_in(node.elt)
        return False

    def _comp_scope(self, generators) -> "_Taint":
        sub = _Taint(self.device_fns, self.device_methods, self.local_fns)
        sub.env = dict(self.env)
        for gen in generators:
            if sub.tainted(gen.iter):
                sub.bind(gen.target, True)
        return sub

    def _eval_in(self, node: ast.AST) -> bool:
        return self.tainted(node)

    # -- binding ---------------------------------------------------------------

    def bind(self, target: ast.AST, value: bool) -> None:
        if isinstance(target, ast.Name):
            self.env[target.id] = value
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self.bind(e, value)
        elif isinstance(target, ast.Starred):
            self.bind(target.value, value)
        # attribute/subscript targets: taint follows DEVICE_ATTRS, not env


class HostSyncPass:
    id = "host-sync"

    def scope(self, root: str) -> List[str]:
        return scope_files(root, config.DEVICE_PATH_SCOPE)

    def run(self, src: ModuleSource) -> Iterator[Tuple[Finding, ast.AST]]:
        device_fns = device_fn_names(src.tree)
        device_methods, local_fns = device_method_names(
            src.tree, device_fns
        )
        # Module body + every function body, each with a fresh local env.
        yield from self._walk_body(
            src,
            src.tree.body,
            _Taint(device_fns, device_methods, local_fns),
            (device_fns, device_methods, local_fns),
        )

    # -- statement walk --------------------------------------------------------

    def _walk_body(
        self,
        src: ModuleSource,
        body: List[ast.stmt],
        taint: _Taint,
        device_fns,
    ) -> Iterator[Tuple[Finding, ast.AST]]:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # Fresh local scope; parameters start untainted (callers
                # own their transfers) — EXCEPT the declared off-loop
                # transfer halves, whose params are device by contract.
                sub = _Taint(*device_fns)
                _seed_params(sub, stmt)
                yield from self._walk_body(src, stmt.body, sub, device_fns)
                continue
            if isinstance(stmt, ast.ClassDef):
                yield from self._walk_body(src, stmt.body, taint, device_fns)
                continue
            # Flag readbacks in this statement's own expressions (compound
            # statements contribute only their headers here — their bodies
            # re-enter _walk_body below so the env stays in order).
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
            for root in roots:
                yield from self._check_expr(src, root, stmt, taint)
            # Update bindings AFTER flagging (the RHS is evaluated with
            # the pre-assignment env).
            if isinstance(stmt, ast.Assign):
                v = taint.tainted(stmt.value)
                for t in stmt.targets:
                    taint.bind(t, v)
            elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                taint.bind(stmt.target, taint.tainted(stmt.value))
            elif isinstance(stmt, ast.AugAssign):
                if taint.tainted(stmt.value):
                    taint.bind(stmt.target, True)
            elif isinstance(stmt, (ast.For, ast.AsyncFor)):
                taint.bind(stmt.target, taint.tainted(stmt.iter))
                yield from self._walk_body(src, stmt.body, taint, device_fns)
                yield from self._walk_body(
                    src, stmt.orelse, taint, device_fns
                )
                continue
            elif isinstance(stmt, (ast.With, ast.AsyncWith)):
                yield from self._walk_body(src, stmt.body, taint, device_fns)
                continue
            elif isinstance(stmt, (ast.If, ast.While)):
                yield from self._walk_body(src, stmt.body, taint, device_fns)
                yield from self._walk_body(
                    src, stmt.orelse, taint, device_fns
                )
                continue
            elif isinstance(stmt, ast.Try):
                for blk in (stmt.body, stmt.orelse, stmt.finalbody):
                    yield from self._walk_body(src, blk, taint, device_fns)
                for h in stmt.handlers:
                    yield from self._walk_body(src, h.body, taint, device_fns)
                continue

    def _check_expr(
        self, src: ModuleSource, root: ast.AST, stmt: ast.stmt, taint: _Taint
    ) -> Iterator[Tuple[Finding, ast.AST]]:
        """Flag readbacks anywhere under one expression root, evaluating
        taint in the statement's current env (with comprehension-local
        bindings rebuilt for nodes inside comprehensions)."""
        for node in ast.walk(root):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested defs walk separately
            if not isinstance(node, (ast.Call, ast.Attribute)):
                continue
            env = taint
            # Rebuild comprehension-local taint for nodes inside
            # comprehensions (ast.walk loses that context, so find the
            # nearest comprehension ancestor by identity containment).
            comp = _enclosing_comp(root, node)
            if comp is not None:
                env = taint._comp_scope(comp.generators)
            if isinstance(node, ast.Attribute):
                if node.attr == "block_until_ready":
                    yield (
                        src.finding(
                            self.id,
                            node,
                            "block_until_ready is a host sync barrier — "
                            "annotate `# graftlint: readback(<reason>)` "
                            "if this device-path sync is intentional",
                        ),
                        stmt,
                    )
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "item":
                yield (
                    src.finding(
                        self.id,
                        node,
                        ".item() reads one scalar back per call — "
                        "batch the readback or annotate "
                        "`# graftlint: readback(<reason>)`",
                    ),
                    stmt,
                )
                continue
            if isinstance(f, ast.Attribute) and f.attr == "tolist":
                if env.tainted(f.value):
                    yield (
                        src.finding(
                            self.id,
                            node,
                            ".tolist() on a device value is an implicit "
                            "device→host transfer — annotate "
                            "`# graftlint: readback(<reason>)` or go "
                            "through one staged np.asarray",
                        ),
                        stmt,
                    )
                continue
            if _is_np(f, ("asarray", "array")):
                if node.args and env.tainted(node.args[0]):
                    name = ast.unparse(node.args[0])
                    yield (
                        src.finding(
                            self.id,
                            node,
                            f"np.{f.attr}({name}) is an implicit "
                            "device→host transfer — annotate "
                            "`# graftlint: readback(<reason>)` or keep "
                            "the value on device",
                        ),
                        stmt,
                    )
                continue
            if (
                isinstance(f, ast.Name)
                and f.id in _SCALARIZERS
                and len(node.args) == 1
                and env.tainted(node.args[0])
            ):
                name = ast.unparse(node.args[0])
                yield (
                    src.finding(
                        self.id,
                        node,
                        f"{f.id}({name}) scalarizes a device value "
                        "(one blocking transfer per call) — annotate "
                        "`# graftlint: readback(<reason>)` or batch via "
                        "one np.asarray",
                    ),
                    stmt,
                )


def _enclosing_comp(
    root: ast.AST, node: ast.AST
) -> Optional[ast.expr]:
    """Nearest comprehension in ``root`` that strictly contains ``node``
    (by identity walk)."""
    best = None
    for cand in ast.walk(root):
        if isinstance(
            cand, (ast.ListComp, ast.GeneratorExp, ast.SetComp, ast.DictComp)
        ):
            for sub in ast.walk(cand):
                if sub is node and cand is not node:
                    best = cand
                    break
    return best
