"""Lightweight package call graph for reachability-scoped rules.

The TPL3xx host-sync family needs "is this function on the serving hot
path?" — i.e. reachable from ``TPUChannel.stage``/``launch``,
``ContinuousBatchingChannel``'s dispatch machinery, or ``_Servicer._issue``. A
full points-to analysis is overkill for a ~30-module package with a
conventional style, so resolution is name-based with three edges:

  * ``f(...)``          -> same-module function ``f``, else a
                           ``from m import f`` target in the package
  * ``self.m(...)``     -> method ``m`` of the lexically enclosing
                           class (plus any same-package base classes)
  * ``alias.f(...)``    -> function ``f`` of the package module that
                           ``import pkg.mod as alias`` / ``from pkg
                           import mod`` bound

Nested functions (closures like ``launch``'s ``resolve``) are treated
as reachable from their enclosing function — the serving pipeline leans
on closures for deferred work, and a deferred host sync is *exactly*
what TPL3xx exists to catch. Dynamic dispatch through variables is out
of scope; rules that need soundness must not rely on edges the graph
cannot see (unreachable = "not proven hot", never "proven cold").
"""

from __future__ import annotations

import ast
import collections
import dataclasses
from typing import Iterable

from triton_client_tpu.analysis.engine import Module, dotted_name


@dataclasses.dataclass
class FunctionInfo:
    """One function/method definition node in the package."""

    qualname: str  # "pkg.mod.Class.method" (module path dotted, no .py)
    module: Module
    node: ast.AST  # FunctionDef | AsyncFunctionDef | Lambda
    class_name: str = ""  # enclosing class simple name, "" for free funcs


def _module_dotted(relpath: str) -> str:
    name = relpath[:-3] if relpath.endswith(".py") else relpath
    name = name.replace("\\", "/").strip("/").replace("/", ".")
    if name.endswith(".__init__"):
        name = name[: -len(".__init__")]
    return name


class CallGraph:
    def __init__(self, modules: Iterable[Module]) -> None:
        self.functions: dict[str, FunctionInfo] = {}
        self.edges: dict[str, set[str]] = collections.defaultdict(set)
        self._modules = list(modules)
        self._mod_names = {m: _module_dotted(m.relpath) for m in self._modules}
        # simple method index: method name -> {qualnames} (fallback for
        # cross-class self-dispatch through base classes)
        self._methods: dict[str, set[str]] = collections.defaultdict(set)
        self._import_cache: dict[int, dict[str, str]] = {}
        for m in self._modules:
            self._collect_functions(m)
        for m in self._modules:
            self._collect_edges(m)

    # -- construction ------------------------------------------------------

    def _collect_functions(self, module: Module) -> None:
        mod_name = self._mod_names[module]

        def walk(node: ast.AST, prefix: str, class_name: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    qn = f"{prefix}.{child.name}"
                    self.functions[qn] = FunctionInfo(
                        qn, module, child, class_name
                    )
                    if class_name:
                        self._methods[child.name].add(qn)
                    walk(child, qn, "")  # nested defs: not methods
                elif isinstance(child, ast.ClassDef):
                    walk(child, f"{prefix}.{child.name}", child.name)
                else:
                    walk(child, prefix, class_name)

        walk(module.tree, mod_name, "")

    def _imports(self, module: Module) -> dict[str, str]:
        """local alias -> dotted target (module or module.attr)."""
        out: dict[str, str] = {}
        for node in ast.walk(module.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    out[a.asname or a.name.split(".")[0]] = (
                        a.name if a.asname else a.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and node.module:
                base = node.module
                if node.level:  # relative import: anchor to this package
                    pkg_parts = self._mod_names[module].split(".")
                    anchor = pkg_parts[: -node.level]
                    base = ".".join(anchor + [node.module])
                for a in node.names:
                    out[a.asname or a.name] = f"{base}.{a.name}"
        return out

    def imports_of(self, module: Module) -> dict[str, str]:
        """Cached alias map for ``module`` (threads.py resolves spawn
        targets with the same import model the edge builder uses)."""
        cached = self._import_cache.get(id(module))
        if cached is None:
            cached = self._imports(module)
            self._import_cache[id(module)] = cached
        return cached

    def resolve_call(
        self,
        module: Module,
        call: ast.Call,
        enclosing_class: str,
        owner: str | None = None,
    ) -> set[str]:
        """Package qualnames a call expression may target. Name-based,
        same three edges the module docstring describes; ``owner`` (the
        caller's qualname) additionally resolves bare names to nested
        defs in the caller — ``submit(run)``-style closures."""
        name = dotted_name(call.func)
        if not name:
            return set()
        mod_name = self._mod_names[module]
        imports = self.imports_of(module)
        targets: set[str] = set()
        parts = name.split(".")
        if parts[0] == "self" and len(parts) == 2:
            # self.m() -> enclosing class method, else any same-name
            # method in the package (base-class fallback)
            qn = f"{mod_name}.{enclosing_class}.{parts[1]}"
            if qn in self.functions:
                targets.add(qn)
            else:
                targets |= self._methods.get(parts[1], set())
            return targets
        # plain f() -> nested def in the caller, same module, from-imports
        if len(parts) == 1:
            if owner and f"{owner}.{parts[0]}" in self.functions:
                targets.add(f"{owner}.{parts[0]}")
            qn = f"{mod_name}.{parts[0]}"
            if qn in self.functions:
                targets.add(qn)
            imp = imports.get(parts[0])
            if imp and imp in self.functions:
                targets.add(imp)
            return targets
        # alias.f() / alias.sub.f() -> imported module function
        imp = imports.get(parts[0])
        if imp:
            qn = ".".join([imp] + parts[1:])
            if qn in self.functions:
                targets.add(qn)
        qn = ".".join([mod_name] + parts)  # e.g. Class.method refs
        if qn in self.functions:
            targets.add(qn)
        return targets

    def _collect_edges(self, module: Module) -> None:
        def resolve(call: ast.Call, enclosing_class: str) -> set[str]:
            return self.resolve_call(module, call, enclosing_class)

        def walk(node: ast.AST, owner: str | None, enclosing_class: str) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    if owner is None:
                        qn = None
                        for q, info in self.functions.items():
                            if info.node is child:
                                qn = q
                                break
                        child_owner = qn
                    else:
                        child_owner = f"{owner}.{child.name}"
                        # a nested def is reachable from its encloser:
                        # closures ARE the deferred hot path
                        self.edges[owner].add(child_owner)
                    walk(child, child_owner, enclosing_class)
                elif isinstance(child, ast.ClassDef):
                    walk(child, None, child.name)
                else:
                    if owner is not None and isinstance(child, ast.Call):
                        for t in resolve(child, enclosing_class):
                            self.edges[owner].add(t)
                    walk(child, owner, enclosing_class)

        walk(module.tree, None, "")

    # -- queries ----------------------------------------------------------

    def match(self, patterns: Iterable[str]) -> set[str]:
        """Qualnames whose dotted suffix matches any pattern; a pattern
        ending in '.*' matches every method of the named class/module."""
        out: set[str] = set()
        for pat in patterns:
            if pat.endswith(".*"):
                prefix = pat[:-1]  # keep the dot
                for qn in self.functions:
                    if qn.startswith(prefix) or f".{prefix}" in f".{qn}":
                        out.add(qn)
            else:
                for qn in self.functions:
                    if qn == pat or qn.endswith("." + pat):
                        out.add(qn)
        return out

    def reachable(self, roots: Iterable[str]) -> set[str]:
        """BFS closure over call edges from root patterns."""
        seen = set(self.match(roots))
        queue = collections.deque(seen)
        while queue:
            qn = queue.popleft()
            for nxt in self.edges.get(qn, ()):
                if nxt not in seen:
                    seen.add(nxt)
                    queue.append(nxt)
        return seen
