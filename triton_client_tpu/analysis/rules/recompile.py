"""TPL1xx — recompilation / retrace hazards inside jitted code.

XLA compiles one executable per (shape, dtype, static-arg) signature;
anything that makes the traced Python non-deterministic per call either
fails at trace time or silently retraces — and on the serving path a
retrace is a multi-second stall.
These rules find the three shapes of that bug this codebase has
actually grown:

  TPL101  Python ``if``/``while``/``for`` branching on a *traced* value
          inside a ``@jax.jit`` body or ``device_fn``. Branching on
          ``x.shape``/``x.ndim``/``x.dtype``/``len(x)`` is fine (those
          are static at trace time); branching on ``x`` itself raises a
          TracerBoolConversionError or bakes in one trace per branch.
  TPL102  ``static_argnums``/``static_argnames``/``donate_argnums``
          passed a *list* literal. Lists are unhashable, so the jit
          cache keys degrade (newer jax versions reject them outright);
          use a tuple.
  TPL103  f-string / ``str()``/``repr()``/``format()`` over a traced
          value inside a jitted body: concretizes the tracer (error) or
          leaks a trace-time constant into strings that then differ per
          trace.
"""

from __future__ import annotations

import ast
from typing import Iterator

from triton_client_tpu.analysis.engine import (
    Finding,
    Module,
    Package,
    Rule,
    call_name,
    context_of,
    dotted_name,
    qualname_contexts,
    register,
)

# attribute reads on a traced value that are static at trace time
_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "at"}
_STATIC_CALLS = {"len", "isinstance", "type", "hasattr", "getattr"}
_JIT_NAMES = {"jax.jit", "jit", "pjit", "jax.pjit"}

# Parameter-name convention for STATIC serving config threaded through
# jitted bodies (round 10): a precision policy (runtime/precision.py
# PrecisionPolicy — a frozen dataclass of python strings/floats) rides
# into launched programs by closure or argument, and branching on it
# (`if policy.name == "bf16"`, `policy.scale_for(k) is not None`)
# dispatches on serving CONFIG, not on a tracer — one executable per
# policy is exactly the intent. Names matching this convention are
# excluded from the traced-param set for every TPL1xx rule.
_STATIC_PARAM_SUFFIXES = ("policy", "precision")


def is_static_param_name(name: str) -> bool:
    """True for parameter names that carry static (python) serving
    config by convention: ``policy``, ``precision``, ``*_policy``,
    ``*_precision``."""
    n = name.lower()
    return any(
        n == s or n.endswith("_" + s) for s in _STATIC_PARAM_SUFFIXES
    )


def _is_jit_call(node: ast.Call) -> bool:
    name = call_name(node)
    if name in _JIT_NAMES:
        return True
    # functools.partial(jax.jit, ...) decorators
    if name.endswith("partial") and node.args:
        first = node.args[0]
        return isinstance(first, (ast.Name, ast.Attribute)) and (
            dotted_name(first) in _JIT_NAMES
        )
    return False


def jit_bodies(module: Module) -> Iterator[tuple[ast.AST, list[str], str]]:
    """Yield (function node, traced param names, context) for every
    jit-compiled function the module defines:

      * ``@jax.jit``-decorated defs (incl. ``partial(jax.jit, ...)``)
      * defs named ``device_fn`` (the repository's launch contract:
        TPUChannel wraps them in ``jax.jit(..., donate_argnums)``)
      * lambdas / local defs passed as the first argument of a
        ``jax.jit(...)`` call

    Static args named by ``static_argnums``/``static_argnames`` are
    excluded from the traced set, as are params matching the
    static-config naming convention (:func:`is_static_param_name`).
    """
    contexts = qualname_contexts(module.tree)

    def params(fn: ast.AST, static_nums=(), static_names=()) -> list[str]:
        args = fn.args
        names = [a.arg for a in args.posonlyargs + args.args + args.kwonlyargs]
        if names and names[0] in ("self", "cls"):
            names = names[1:]
        out = []
        for i, n in enumerate(names):
            if i in static_nums or n in static_names:
                continue
            if is_static_param_name(n):
                continue  # precision policy config — never a tracer
            out.append(n)
        return out

    def static_spec(call: ast.Call | None) -> tuple[tuple, tuple]:
        nums: tuple = ()
        names: tuple = ()
        if call is None:
            return nums, names
        for kw in call.keywords:
            if kw.arg == "static_argnums":
                try:
                    v = ast.literal_eval(kw.value)
                    nums = tuple(v) if isinstance(v, (list, tuple)) else (v,)
                except (ValueError, SyntaxError):
                    pass
            elif kw.arg == "static_argnames":
                try:
                    v = ast.literal_eval(kw.value)
                    names = tuple([v] if isinstance(v, str) else v)
                except (ValueError, SyntaxError):
                    pass
        return nums, names

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            jit_deco = None
            for deco in node.decorator_list:
                if isinstance(deco, ast.Call) and _is_jit_call(deco):
                    jit_deco = deco
                elif dotted_name(deco) in _JIT_NAMES:
                    jit_deco = ast.Call(func=deco, args=[], keywords=[])
            if jit_deco is not None or node.name == "device_fn":
                nums, names = static_spec(jit_deco)
                yield node, params(node, nums, names), contexts.get(
                    node, node.name
                )
        elif isinstance(node, ast.Call) and _is_jit_call(node) and node.args:
            fn = node.args[0]
            if isinstance(fn, ast.Lambda):
                nums, names = static_spec(node)
                yield fn, params(fn, nums, names), "<lambda>"


def _traced_uses(test: ast.AST, traced: set[str]) -> list[ast.Name]:
    """Name loads of traced params in ``test`` that are NOT shielded by
    a static attribute/call (``x.shape``, ``len(x)``, ...)."""
    hits: list[ast.Name] = []

    def walk(node: ast.AST) -> None:
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return  # x.shape / x.dtype — static, don't descend into x
        if isinstance(node, ast.Call):
            name = call_name(node)
            if name in _STATIC_CALLS:
                return
        if (
            isinstance(node, ast.Name)
            and isinstance(node.ctx, ast.Load)
            and node.id in traced
        ):
            hits.append(node)
        for child in ast.iter_child_nodes(node):
            walk(child)

    walk(test)
    return hits


@register
class TracedBranchRule(Rule):
    code = "TPL101"
    name = "traced-branch"
    doc = (
        "Python control flow (`if`/`while`/`for`) branches on a traced "
        "value inside a jit-compiled body; use `jnp.where`/"
        "`lax.cond`/`lax.fori_loop`, or mark the argument static."
    )

    def check(self, package: Package) -> Iterator[Finding]:
        for module in package.modules:
            for fn, traced_params, ctx in jit_bodies(module):
                traced = set(traced_params)
                body = fn.body if isinstance(fn.body, list) else [fn.body]
                for stmt in ast.walk(ast.Module(body=body, type_ignores=[])):
                    if isinstance(stmt, (ast.If, ast.While)):
                        for use in _traced_uses(stmt.test, traced):
                            yield self.finding(
                                module,
                                stmt,
                                f"`{type(stmt).__name__.lower()}` branches on "
                                f"traced value `{use.id}` inside a jitted "
                                "body (retrace/TracerBoolConversionError)",
                                context=ctx,
                            )
                    elif isinstance(stmt, ast.For):
                        for use in _traced_uses(stmt.iter, traced):
                            yield self.finding(
                                module,
                                stmt,
                                f"`for` iterates over traced value "
                                f"`{use.id}` inside a jitted body "
                                "(unrolls per trace; use lax.fori_loop/scan)",
                                context=ctx,
                            )


@register
class StaticArgListRule(Rule):
    code = "TPL102"
    name = "unhashable-static-args"
    doc = (
        "`static_argnums`/`static_argnames`/`donate_argnums` passed a "
        "list literal — lists are unhashable, degrading (or breaking) "
        "the jit cache key; use a tuple."
    )

    _KEYS = ("static_argnums", "static_argnames", "donate_argnums")

    def check(self, package: Package) -> Iterator[Finding]:
        for module in package.modules:
            for node in ast.walk(module.tree):
                if not (isinstance(node, ast.Call) and _is_jit_call(node)):
                    continue
                for kw in node.keywords:
                    if kw.arg in self._KEYS and isinstance(kw.value, ast.List):
                        yield self.finding(
                            module,
                            kw.value,
                            f"`{kw.arg}` is a list literal; use a tuple "
                            "(lists are unhashable jit-cache keys)",
                            context=context_of(module, node),
                        )


@register
class TracedStringRule(Rule):
    code = "TPL103"
    name = "traced-string-leak"
    doc = (
        "f-string/`str()`/`repr()`/`format()` over a traced value inside "
        "a jitted body — concretizes the tracer or bakes a trace-time "
        "constant into the string."
    )

    def check(self, package: Package) -> Iterator[Finding]:
        for module in package.modules:
            for fn, traced_params, ctx in jit_bodies(module):
                traced = set(traced_params)
                body = fn.body if isinstance(fn.body, list) else [fn.body]
                for node in ast.walk(ast.Module(body=body, type_ignores=[])):
                    if isinstance(node, ast.FormattedValue):
                        for use in _traced_uses(node.value, traced):
                            yield self.finding(
                                module,
                                node,
                                f"f-string formats traced value `{use.id}` "
                                "inside a jitted body",
                                context=ctx,
                            )
                    elif isinstance(node, ast.Call) and call_name(node) in (
                        "str",
                        "repr",
                        "format",
                    ):
                        for arg in node.args:
                            for use in _traced_uses(arg, traced):
                                yield self.finding(
                                    module,
                                    node,
                                    f"`{call_name(node)}()` over traced "
                                    f"value `{use.id}` inside a jitted body",
                                    context=ctx,
                                )
