"""TRACE-PURITY: no host escapes inside trace-reachable functions.

The device runner compiles ONE program for all epochs
(``trace_count == 1`` in ``dist/runner.py``); that invariant dies the
moment a traced function forces a host sync -- ``.item()`` /
``int(tracer)`` / ``float(tracer)`` concretize an abstract value (a
TracerError at best, a silent retrace at worst), host IO and
``time.*`` run at TRACE time (once, not per step, a classic silent
bug), and ``threading`` primitives inside a traced region are never
what the author meant (DESIGN.md §8).

Reachability is computed per module, syntactically: a function is
TRACED when it is decorated with (or passed by name to) a jax tracing
wrapper -- ``jax.jit``, ``shard_map``, ``lax.scan`` and friends,
``pl.pallas_call``, ``custom_vjp``/``defvjp`` -- plus the transitive
closure over same-module calls. Casts of provably shape-static
expressions (``int(x.shape[0])``, ``len(...)``, constant arithmetic)
are exempt: shapes are static under trace.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set, Tuple

from repro.analysis.engine import (Finding, ModuleContext, Rule)

#: calls whose function-valued arguments become traced regions
TRACE_WRAPPERS = {
    "jax.jit", "jax.pmap", "jax.vmap", "jax.grad", "jax.value_and_grad",
    "jax.checkpoint", "jax.remat", "jax.custom_vjp", "jax.custom_jvp",
    "jax.linearize", "jax.linear_transpose", "jax.make_jaxpr",
    "jax.lax.scan", "jax.lax.while_loop", "jax.lax.fori_loop",
    "jax.lax.cond", "jax.lax.switch", "jax.lax.map",
    "jax.lax.associative_scan", "jax.lax.custom_root",
    "jax.shard_map",
    "jax.experimental.pallas.pallas_call",
}

#: method names that seed their args regardless of receiver
#: (``f.defvjp(fwd, bwd)`` on a custom_vjp object)
_SEEDING_METHODS = {"defvjp", "defjvp"}

_CASTS = {"int", "float", "bool"}
_HOST_IO = {"print", "open", "input", "breakpoint"}

#: call targets allowed inside a static (shape-arithmetic) expression
_STATIC_CALL_PREFIXES = ("math.",)
_STATIC_CALLS = {"len", "int", "float", "min", "max", "abs", "round",
                 "divmod"}

_FN_NODES = (ast.FunctionDef, ast.AsyncFunctionDef)


def _is_static(node: ast.AST, static_names: Set[str],
               ctx: ModuleContext) -> bool:
    """Conservatively: does this expression only depend on shapes /
    constants (static under jax tracing)?"""
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.Name):
        return node.id in static_names
    if isinstance(node, ast.Attribute):
        # .shape/.ndim/.dtype of ANYTHING is static under trace
        return node.attr in ("shape", "ndim", "dtype")
    if isinstance(node, ast.Subscript):
        return _is_static(node.value, static_names, ctx) and \
            _is_static(node.slice, static_names, ctx)
    if isinstance(node, ast.Index):        # py<3.9 compat slot
        return _is_static(node.value, static_names, ctx)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_static(e, static_names, ctx) for e in node.elts)
    if isinstance(node, ast.BinOp):
        return _is_static(node.left, static_names, ctx) and \
            _is_static(node.right, static_names, ctx)
    if isinstance(node, ast.UnaryOp):
        return _is_static(node.operand, static_names, ctx)
    if isinstance(node, ast.Compare):
        return _is_static(node.left, static_names, ctx) and \
            all(_is_static(c, static_names, ctx)
                for c in node.comparators)
    if isinstance(node, ast.IfExp):
        return all(_is_static(e, static_names, ctx)
                   for e in (node.test, node.body, node.orelse))
    if isinstance(node, ast.Call):
        canon = ctx.resolve(node.func)
        if canon is None:
            return False
        if canon in _STATIC_CALLS and canon != "len":
            return all(_is_static(a, static_names, ctx)
                       for a in node.args)
        if canon == "len":       # len() of a traced array is its shape
            return True
        if canon.startswith(_STATIC_CALL_PREFIXES):
            return all(_is_static(a, static_names, ctx)
                       for a in node.args)
        return False
    return False


def _iter_stmts(body: List[ast.stmt]):
    """Statements of a function body in source order, descending into
    compound statements but NOT into nested function/class defs."""
    for stmt in body:
        yield stmt
        for field in ("body", "orelse", "finalbody"):
            sub = getattr(stmt, field, None)
            if sub and not isinstance(stmt, _FN_NODES + (ast.ClassDef,)):
                yield from _iter_stmts(sub)
        for h in getattr(stmt, "handlers", ()):
            yield from _iter_stmts(h.body)


def _static_params(fn: ast.AST) -> Set[str]:
    """Parameters declared static via ``static_argnames`` /
    ``static_argnums`` in a jit-style decorator: plain Python values
    under trace, so casting them is fine."""
    out: Set[str] = set()
    posonly = getattr(fn.args, "posonlyargs", [])
    positional = [a.arg for a in list(posonly) + list(fn.args.args)]
    for dec in fn.decorator_list:
        if not isinstance(dec, ast.Call):
            continue
        for k in dec.keywords:
            v = k.value
            if k.arg == "static_argnames":
                if isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    out.add(v.value)
                elif isinstance(v, (ast.Tuple, ast.List)):
                    out.update(e.value for e in v.elts
                               if isinstance(e, ast.Constant)
                               and isinstance(e.value, str))
            elif k.arg == "static_argnums":
                nums = [v] if isinstance(v, ast.Constant) else \
                    list(getattr(v, "elts", []))
                for e in nums:
                    if isinstance(e, ast.Constant) and \
                            isinstance(e.value, int) and \
                            e.value < len(positional):
                        out.add(positional[e.value])
    # keyword-only static_argnames params also count
    return out


def _static_names(fn: ast.AST, ctx: ModuleContext) -> Set[str]:
    """Names assigned (in order) from static-only expressions inside
    ``fn``: a one-pass, loop-free dataflow good enough for the
    ``m = x.shape[0]; int(m // bm)`` idiom kernels live on. Seeded
    with the function's jit-static parameters."""
    static: Set[str] = set(_static_params(fn))
    for stmt in _iter_stmts(fn.body):
        targets: List[ast.expr] = []
        value: Optional[ast.expr] = None
        if isinstance(stmt, ast.Assign):
            targets, value = stmt.targets, stmt.value
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            targets, value = [stmt.target], stmt.value
        elif isinstance(stmt, ast.AugAssign):
            targets, value = [stmt.target], stmt.value
        if value is None:
            continue
        ok = _is_static(value, static, ctx)
        for t in targets:
            names = [t] if isinstance(t, ast.Name) else \
                [e for e in getattr(t, "elts", []) if isinstance(e, ast.Name)]
            for n in names:
                if ok and (not isinstance(stmt, ast.AugAssign)
                           or n.id in static):
                    static.add(n.id)
                else:
                    static.discard(n.id)
    return static


class _FnIndex:
    """All function defs in a module, with lexical-scope resolution of
    ``Name`` references to the innermost visible def."""

    def __init__(self, tree: ast.AST):
        self.defs: List[Tuple[ast.AST, Tuple[ast.AST, ...]]] = []
        self.lambdas: List[Tuple[ast.Lambda, Tuple[ast.AST, ...]]] = []
        self._walk(tree, ())

    def _walk(self, node: ast.AST, scope: Tuple[ast.AST, ...]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, _FN_NODES):
                self.defs.append((child, scope))
                self._walk(child, scope + (child,))
            elif isinstance(child, ast.Lambda):
                self.lambdas.append((child, scope))
                self._walk(child, scope)
            else:
                self._walk(child, scope)

    def resolve_ref(self, name: str,
                    from_scope: Tuple[ast.AST, ...]) -> Optional[ast.AST]:
        best, best_len = None, -1
        for fn, scope in self.defs:
            if fn.name != name:
                continue
            if len(scope) <= len(from_scope) and \
                    scope == from_scope[:len(scope)] and \
                    len(scope) > best_len:
                best, best_len = fn, len(scope)
        return best

    def scope_of(self, fn: ast.AST) -> Tuple[ast.AST, ...]:
        for f, scope in self.defs:
            if f is fn:
                return scope
        return ()


class TracePurityRule(Rule):
    rule_id = "TRACE-PURITY"
    description = ("no .item()/int()/float() on traced values, host "
                   "IO, time.* or threading inside jax.jit / "
                   "shard_map / lax.scan-reachable functions")

    def check_module(self, ctx: ModuleContext) -> Iterable[Finding]:
        idx = _FnIndex(ctx.tree)
        traced: Set[ast.AST] = set()
        traced_lambdas: Set[ast.Lambda] = set()

        def seed_arg(arg: ast.expr, scope: Tuple[ast.AST, ...]) -> None:
            if isinstance(arg, ast.Name):
                fn = idx.resolve_ref(arg.id, scope)
                if fn is not None:
                    traced.add(fn)
            elif isinstance(arg, ast.Lambda):
                traced_lambdas.add(arg)

        # -- seeds: decorators and wrapper-call arguments ------------
        scope_of_node: Dict[ast.AST, Tuple[ast.AST, ...]] = {}

        def index_scopes(node: ast.AST,
                         scope: Tuple[ast.AST, ...]) -> None:
            for child in ast.iter_child_nodes(node):
                scope_of_node[child] = scope
                index_scopes(child, scope + (child,)
                             if isinstance(child, _FN_NODES) else scope)

        index_scopes(ctx.tree, ())

        for fn, scope in idx.defs:
            for dec in fn.decorator_list:
                canon = ctx.resolve(dec)
                if canon in TRACE_WRAPPERS:
                    traced.add(fn)
                elif isinstance(dec, ast.Call):
                    if ctx.resolve(dec.func) in TRACE_WRAPPERS:
                        traced.add(fn)
                    elif ctx.resolve(dec.func) in ("functools.partial",
                                                   "partial"):
                        if any(ctx.resolve(a) in TRACE_WRAPPERS
                               for a in dec.args):
                            traced.add(fn)

        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            canon = ctx.resolve(node.func)
            scope = scope_of_node.get(node, ())
            is_wrapper = canon in TRACE_WRAPPERS
            is_seeding_method = (isinstance(node.func, ast.Attribute)
                                 and node.func.attr in _SEEDING_METHODS)
            if is_wrapper or is_seeding_method:
                for a in list(node.args) + [k.value for k in node.keywords]:
                    seed_arg(a, scope)

        # -- transitive closure over same-module calls ---------------
        changed = True
        while changed:
            changed = False
            for fn in list(traced):
                scope = idx.scope_of(fn) + (fn,)
                for node in ast.walk(fn):
                    if isinstance(node, ast.Call) and \
                            isinstance(node.func, ast.Name):
                        callee = idx.resolve_ref(node.func.id, scope)
                        if callee is not None and callee not in traced:
                            traced.add(callee)
                            changed = True

        # -- violations inside traced regions ------------------------
        def region_nodes(root_body: List[ast.AST]):
            """Every node under the region, NOT descending into nested
            defs/lambdas (those are audited as their own regions iff
            they are themselves traced)."""
            stack = list(root_body)
            while stack:
                node = stack.pop()
                yield node
                for child in ast.iter_child_nodes(node):
                    if not isinstance(child, _FN_NODES + (ast.Lambda,)):
                        stack.append(child)

        found: List[Finding] = []
        regions = [(fn, fn.name, fn.body, _static_names(fn, ctx))
                   for fn in traced] + \
                  [(lam, "<lambda>", [lam.body], set())
                   for lam in traced_lambdas]
        for _, where, body, names in regions:
            for node in region_nodes(body):
                if not isinstance(node, ast.Call):
                    continue
                f = self._check_call(node, names, ctx, where)
                if f is not None:
                    found.append(f)
        return found

    def _check_call(self, node: ast.Call, static_names: Set[str],
                    ctx: ModuleContext, where: str) -> Optional[Finding]:
        if isinstance(node.func, ast.Attribute) and \
                node.func.attr in ("item", "tolist") and not node.args:
            return ctx.finding(
                node, self.rule_id,
                f".{node.func.attr}() in traced '{where}' forces a "
                f"host sync (breaks trace_count == 1)")
        canon = ctx.resolve(node.func)
        if canon is None:
            return None
        if canon in _CASTS and len(node.args) == 1 and \
                not _is_static(node.args[0], static_names, ctx):
            return ctx.finding(
                node, self.rule_id,
                f"{canon}(...) on a non-shape value in traced "
                f"'{where}' concretizes a tracer; hoist to the host "
                f"or compute from .shape")
        if canon in _HOST_IO:
            return ctx.finding(
                node, self.rule_id,
                f"host IO {canon}(...) in traced '{where}' runs at "
                f"trace time, not per step")
        if canon.startswith("time."):
            return ctx.finding(
                node, self.rule_id,
                f"{canon}() in traced '{where}' measures trace time, "
                f"not step time")
        if canon == "threading" or canon.startswith("threading."):
            return ctx.finding(
                node, self.rule_id,
                f"{canon} in traced '{where}': thread primitives "
                f"cannot live inside a traced region")
        return None
