"""Package-wide thread & lock model for the TPL6xx concurrency family.

The runtime is a web of cooperating threads — the batch dispatcher, the
stall watchdog, executor workers, the router's probe loop and hedge
completion callbacks, the SIGTERM handler — all mutating shared objects
guarded by per-structure locks. TPL4xx checks guarded-vs-bare
discipline *inside one class*; this model answers the questions that
need the whole package:

  * which locks exist, unified across a class hierarchy (a
    ``TPUChannel`` method holding ``self._slot_cv`` holds the SAME
    lock a ``StagedChannel`` method acquires);
  * which locks are held on entry to every function, propagated
    interprocedurally along the call graph (so a ``*_locked`` helper
    called under ``with self._lock:`` is known to run locked);
  * in what ORDER locks nest — the lock-order digraph whose cycles are
    potential deadlocks (TPL601);
  * which functions run on which THREAD ROOTS — discovered from
    ``threading.Thread/Timer`` spawns, ``Executor.submit``,
    ``add_done_callback``, ``signal.signal``, plus the declared roots
    AST cannot see (gRPC handler threads, the caller's own thread) — so
    an attribute mutated lock-free from two roots is a race (TPL602).

Everything here is an over-approximation in the safe direction for a
linter: held sets union over callers and paths (suppressing, never
inventing, race findings), reachability includes subclass overrides
(``self._run_group()`` in the base dispatch loop may land on the
subclass's override at runtime), and dynamic dispatch the name-based
call graph cannot see simply contributes nothing. "Not flagged" never
means "proven safe"; it means "not provably hazardous".
"""

from __future__ import annotations

import ast
import collections
import dataclasses
from typing import Iterable, Iterator

from triton_client_tpu.analysis.engine import (
    Module,
    call_name,
    walk_held,
)

# factories whose self-attribute bindings make an attribute a lock
_LOCK_FACTORIES = {
    "threading.Lock",
    "threading.RLock",
    "threading.Condition",
    "Lock",
    "RLock",
    "Condition",
}
# factories safe to re-acquire on the same thread (Condition wraps an
# RLock by default); a plain Lock re-acquired while held self-deadlocks
_REENTRANT_FACTORIES = {
    "threading.RLock",
    "RLock",
    "threading.Condition",
    "Condition",
}
# object construction is single-threaded: mutations there never race
_EXEMPT_METHODS = {"__init__", "__new__", "__post_init__"}

#: Thread roots the AST cannot discover, declared as (suffix pattern,
#: group, why). The *group* is the distinctness key for TPL602 — all
#: "caller" entries are ONE logical root (a caller thread entering via
#: do_inference vs do_inference_async is the same foreign thread), and
#: the gRPC server's handler pool is one root no matter how many
#: servicer methods it enters through. Extend this tuple when a new
#: externally-threaded entry point appears (docs/LINTING.md shows the
#: workflow).
DECLARED_THREAD_ROOTS: tuple[tuple[str, str, str], ...] = (
    (
        "_Servicer.*",
        "rpc",
        "gRPC server handler threads invoke every servicer method",
    ),
    (
        "do_inference",
        "caller",
        "public inference entry point: runs on the caller's thread",
    ),
    (
        "do_inference_async",
        "caller",
        "async issue side of the public entry point",
    ),
    # ISSUE 15 streaming sessions: the frame bracket spans threads —
    # advance runs on the issuing request thread (inside launch),
    # release on the readback executor inside the resolve closure — so
    # the lock-carrying SessionManager races across these two groups
    # unless every mutation holds the pool lock
    (
        "SessionManager.advance",
        "caller",
        "session frame bracket: runs on the issuing request thread",
    ),
    (
        "SessionManager.release",
        "executor",
        "resolve side of the frame bracket: readback executor threads",
    ),
)

# spawn shapes: call-name -> (kind, how to find the target expression)
_THREAD_CTORS = {"threading.Thread", "Thread"}
_TIMER_CTORS = {"threading.Timer", "Timer"}


@dataclasses.dataclass(frozen=True)
class ThreadRoot:
    """One discovered or declared source of a distinct thread of
    execution. ``group`` is the TPL602 distinctness key; ``pattern`` is
    what reachability is seeded from (an exact qualname for discovered
    roots, a suffix pattern for declared ones)."""

    group: str
    kind: str  # thread | timer | executor | callback | signal | declared
    pattern: str
    where: str  # "path.py:line" of the spawn site, or "declared"


@dataclasses.dataclass
class LockSite:
    """One lock acquisition: ``with self.<attr>:`` at ``node`` inside
    ``function``, with ``local_held`` locks already held lexically
    (entry-held locks are added by the model after the fixpoint)."""

    lock: str
    local_held: frozenset
    module: Module
    node: ast.AST
    function: str


@dataclasses.dataclass
class MutationSite:
    """One self-attribute mutation, with its lexically-held lock set."""

    family: str
    attr: str
    local_held: frozenset
    module: Module
    node: ast.AST
    function: str
    method: str  # simple method name (for __init__-style exemptions)


class ThreadModel:
    """The lock graph + thread-root model over one analyzed Package."""

    def __init__(self, package) -> None:
        self.package = package
        self.graph = package.callgraph
        # class hierarchy ----------------------------------------------------
        self._parents: dict[str, str] = {}
        self._class_names: set[str] = set()
        # family root -> {attr -> factory ("" when usage-discovered)}
        self.lock_attrs: dict[str, dict[str, str]] = collections.defaultdict(dict)
        self._collect_classes()
        self._overrides = self._build_overrides()
        # per-function local facts -------------------------------------------
        self.acquisitions: list[LockSite] = []
        self.mutations: dict[tuple[str, str], list[MutationSite]] = (
            collections.defaultdict(list)
        )
        self._call_sites: dict[str, list[tuple[frozenset, tuple[str, ...]]]] = {}
        self._spawns: list[ThreadRoot] = []
        for qn, info in self.graph.functions.items():
            self._analyze_function(qn, info)
        # interprocedural entry-held fixpoint --------------------------------
        self.entry_held: dict[str, frozenset] = {}
        self._fixpoint()
        # lock-order digraph -------------------------------------------------
        # (held_lock -> acquired_lock) -> first witness LockSite
        self.lock_order: dict[tuple[str, str], LockSite] = {}
        self.reacquisitions: list[LockSite] = []
        self._build_lock_order()
        # thread roots + reachability ----------------------------------------
        self.roots: list[ThreadRoot] = self._assemble_roots()
        self.function_roots: dict[str, set[str]] = self._build_root_reach()

    # -- class hierarchy ----------------------------------------------------

    def _collect_classes(self) -> None:
        for module in self.package.modules:
            for cls in ast.walk(module.tree):
                if not isinstance(cls, ast.ClassDef):
                    continue
                self._class_names.add(cls.name)
                for base in cls.bases:
                    name = base.attr if isinstance(base, ast.Attribute) else (
                        base.id if isinstance(base, ast.Name) else ""
                    )
                    if name:
                        self._parents.setdefault(cls.name, name)
        # second pass: lock attributes, keyed by FAMILY root so base and
        # subclass methods agree on lock identity
        for module in self.package.modules:
            for cls in ast.walk(module.tree):
                if not isinstance(cls, ast.ClassDef):
                    continue
                fam = self.family(cls.name)
                for node in ast.walk(cls):
                    if (
                        isinstance(node, ast.Assign)
                        and isinstance(node.value, ast.Call)
                        and call_name(node.value) in _LOCK_FACTORIES
                    ):
                        for tgt in node.targets:
                            if (
                                isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self"
                            ):
                                self.lock_attrs[fam][tgt.attr] = call_name(
                                    node.value
                                )
                    elif isinstance(node, ast.With):
                        for item in node.items:
                            ctx = item.context_expr
                            if (
                                isinstance(ctx, ast.Attribute)
                                and isinstance(ctx.value, ast.Name)
                                and ctx.value.id == "self"
                                and (
                                    "lock" in ctx.attr.lower()
                                    or ctx.attr.endswith("_cv")
                                )
                            ):
                                self.lock_attrs[fam].setdefault(ctx.attr, "")

    def family(self, class_name: str) -> str:
        """Root of the (package-local, name-based) base-class chain —
        the scope locks are identified under."""
        seen = set()
        cur = class_name
        while cur in self._parents and cur not in seen:
            seen.add(cur)
            parent = self._parents[cur]
            if parent not in self._class_names:
                break
            cur = parent
        return cur

    def _build_overrides(self) -> dict[str, set[str]]:
        """base-method qualname -> subclass override qualnames. Used to
        widen reachability: a base-class ``self._run_group()`` call may
        dispatch to the subclass override at runtime."""
        # class -> {method name -> qualname}
        by_class: dict[str, dict[str, str]] = collections.defaultdict(dict)
        for qn, info in self.graph.functions.items():
            if info.class_name:
                by_class[info.class_name][info.node.name] = qn
        out: dict[str, set[str]] = collections.defaultdict(set)
        for cls, methods in by_class.items():
            ancestor = self._parents.get(cls)
            seen = set()
            while ancestor and ancestor not in seen:
                seen.add(ancestor)
                for name, qn in methods.items():
                    base_qn = by_class.get(ancestor, {}).get(name)
                    if base_qn and base_qn != qn:
                        out[base_qn].add(qn)
                ancestor = self._parents.get(ancestor)
        return dict(out)

    # -- per-function local analysis ----------------------------------------

    def _class_of(self, qualname: str, info) -> str:
        """Owning class of a function, including closures nested in
        methods (their ``self`` is the method's) — the callgraph only
        records class_name for direct methods."""
        if info.class_name:
            return info.class_name
        for part in reversed(qualname.split(".")):
            if part in self._class_names:
                return part
        return ""

    def lock_id(self, class_name: str, attr: str) -> str | None:
        """Lock identity of ``self.<attr>`` seen from ``class_name``, or
        None when the attribute is not a known lock."""
        if not class_name:
            return None
        fam = self.family(class_name)
        if attr in self.lock_attrs.get(fam, {}) or (
            "lock" in attr.lower() or attr.endswith("_cv")
        ):
            return f"{fam}.{attr}"
        return None

    def reentrant(self, lock: str) -> bool:
        fam, _, attr = lock.rpartition(".")
        return self.lock_attrs.get(fam, {}).get(attr, "") in _REENTRANT_FACTORIES

    def _analyze_function(self, qn: str, info) -> None:
        cls = self._class_of(qn, info)
        module = info.module

        def lock_of(expr: ast.AST) -> str | None:
            if (
                isinstance(expr, ast.Attribute)
                and isinstance(expr.value, ast.Name)
                and expr.value.id == "self"
            ):
                return self.lock_id(cls, expr.attr)
            return None

        method = info.node.name
        exempt = method in _EXEMPT_METHODS
        fam = self.family(cls) if cls else ""
        sites: list[tuple[frozenset, tuple[str, ...]]] = []
        for node, held in walk_held(info.node, lock_of):
            if isinstance(node, ast.With):
                for item in node.items:
                    lid = lock_of(item.context_expr)
                    if lid:
                        self.acquisitions.append(
                            LockSite(lid, held, module, node, qn)
                        )
            elif isinstance(node, ast.Call):
                targets = self.graph.resolve_call(
                    module, node, info.class_name or cls, owner=qn
                )
                if targets:
                    sites.append((held, tuple(sorted(targets))))
                self._spawn_of(node, module, qn, cls)
            if fam and not exempt:
                for attr, site in _mutations(node):
                    if attr in self.lock_attrs.get(fam, {}):
                        continue
                    self.mutations[(fam, attr)].append(
                        MutationSite(fam, attr, held, module, site, qn, method)
                    )
        if sites:
            self._call_sites[qn] = sites

    def _spawn_of(
        self, call: ast.Call, module: Module, owner: str, cls: str
    ) -> None:
        """Record a thread root if ``call`` hands a package function to
        another thread of execution."""
        name = call_name(call)
        kind = None
        target: ast.AST | None = None
        if name in _THREAD_CTORS:
            kind = "thread"
            for kw in call.keywords:
                if kw.arg == "target":
                    target = kw.value
        elif name in _TIMER_CTORS:
            kind = "timer"
            if len(call.args) >= 2:
                target = call.args[1]
            for kw in call.keywords:
                if kw.arg == "function":
                    target = kw.value
        elif name == "signal.signal":
            kind = "signal"
            if len(call.args) >= 2:
                target = call.args[1]
        elif isinstance(call.func, ast.Attribute):
            if call.func.attr == "submit" and call.args:
                kind = "executor"
                target = call.args[0]
            elif call.func.attr == "add_done_callback" and call.args:
                kind = "callback"
                target = call.args[0]
        if kind is None or target is None:
            return
        for qn in self._resolve_target(target, module, owner, cls):
            self._spawns.append(
                ThreadRoot(
                    group=qn,
                    kind=kind,
                    pattern=qn,
                    where=f"{module.relpath}:{getattr(call, 'lineno', 0)}",
                )
            )

    def _resolve_target(
        self, expr: ast.AST, module: Module, owner: str, cls: str
    ) -> set[str]:
        """Qualnames a spawn-target expression may name: ``self._loop``,
        a nested closure, a module function, an import."""
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
        ):
            fake = ast.Call(func=expr, args=[], keywords=[])
            return self.graph.resolve_call(module, fake, cls, owner=owner)
        if isinstance(expr, ast.Name):
            fake = ast.Call(
                func=ast.Name(id=expr.id, ctx=ast.Load()), args=[], keywords=[]
            )
            # walk the owner chain so a closure two defs deep resolves
            targets: set[str] = set()
            parts = owner.split(".")
            for i in range(len(parts), 0, -1):
                cand = ".".join(parts[:i] + [expr.id])
                if cand in self.graph.functions:
                    targets.add(cand)
                    break
            targets |= self.graph.resolve_call(module, fake, cls, owner=owner)
            return targets
        return set()

    # -- interprocedural propagation ----------------------------------------

    def _fixpoint(self) -> None:
        """Union-over-callers entry-held sets. Monotone (sets only
        grow), so iterate to fixpoint; the union direction means "some
        caller holds L here", which SUPPRESSES race findings (an access
        might be protected) and ADDS lock-order edges (a path exists on
        which L is held) — both the safe over-approximation for a
        linter that must not invent races and must not miss cycles."""
        changed = True
        while changed:
            changed = False
            for fn, sites in self._call_sites.items():
                base = self.entry_held.get(fn, frozenset())
                for local_held, targets in sites:
                    h = base | local_held
                    if not h:
                        continue
                    for t in targets:
                        # a call resolved to a base method may execute a
                        # subclass override at runtime: the override's
                        # callers hold the same locks
                        for callee in (t, *self._overrides.get(t, ())):
                            cur = self.entry_held.get(callee, frozenset())
                            if not h <= cur:
                                self.entry_held[callee] = cur | h
                                changed = True

    def held_at(self, site) -> frozenset:
        """Full held set at a LockSite/MutationSite: lexical plus
        propagated entry-held locks of the enclosing function."""
        return site.local_held | self.entry_held.get(site.function, frozenset())

    def _build_lock_order(self) -> None:
        for acq in self.acquisitions:
            held = self.held_at(acq)
            for h in held:
                if h == acq.lock:
                    if not self.reentrant(acq.lock):
                        self.reacquisitions.append(acq)
                else:
                    self.lock_order.setdefault((h, acq.lock), acq)

    def lock_cycles(self) -> list[tuple[tuple[str, ...], list[LockSite]]]:
        """Strongly-connected components of the lock-order digraph with
        more than one lock: each is a potential deadlock. Returns
        (sorted lock cycle, witness acquisition sites) pairs, sorted for
        deterministic output."""
        succ: dict[str, set[str]] = collections.defaultdict(set)
        for (a, b) in self.lock_order:
            succ[a].add(b)
        sccs = _tarjan(succ)
        out: list[tuple[tuple[str, ...], list[LockSite]]] = []
        for scc in sccs:
            if len(scc) < 2:
                continue
            cyc = tuple(sorted(scc))
            members = set(scc)
            witnesses = [
                site
                for (a, b), site in sorted(
                    self.lock_order.items(),
                    key=lambda kv: (kv[0][0], kv[0][1]),
                )
                if a in members and b in members
            ]
            out.append((cyc, witnesses))
        out.sort(key=lambda c: c[0])
        return out

    # -- thread roots -------------------------------------------------------

    def _assemble_roots(self) -> list[ThreadRoot]:
        roots: dict[tuple[str, str], ThreadRoot] = {}
        for pattern, group, why in DECLARED_THREAD_ROOTS:
            roots[(group, pattern)] = ThreadRoot(
                group=group, kind="declared", pattern=pattern, where="declared"
            )
        for spawn in self._spawns:
            roots.setdefault((spawn.group, spawn.pattern), spawn)
        return sorted(
            roots.values(), key=lambda r: (r.group, r.pattern, r.where)
        )

    def _reach(self, patterns: Iterable[str]) -> set[str]:
        """BFS closure over call edges PLUS subclass-override edges —
        the dispatcher calling ``self._run_group()`` on the base class
        reaches every override a subclass instance would run."""
        seen = set(self.graph.match(patterns))
        extra = set()
        for qn in seen:
            extra |= self._overrides.get(qn, set())
        seen |= extra
        queue = collections.deque(seen)
        while queue:
            qn = queue.popleft()
            nxt = self.graph.edges.get(qn, set()) | self._overrides.get(
                qn, set()
            )
            for t in nxt:
                if t not in seen:
                    seen.add(t)
                    queue.append(t)
        return seen

    def _build_root_reach(self) -> dict[str, set[str]]:
        by_group: dict[str, set[str]] = collections.defaultdict(set)
        for root in self.roots:
            by_group[root.group].add(root.pattern)
        out: dict[str, set[str]] = collections.defaultdict(set)
        for group, patterns in by_group.items():
            for qn in self._reach(patterns):
                out[qn].add(group)
        return dict(out)

    def roots_reaching(self, qualname: str) -> set[str]:
        """Distinct thread-root groups that can execute ``qualname``."""
        return self.function_roots.get(qualname, set())


# -- shared AST helpers ------------------------------------------------------


_MUTATING_METHODS = {
    "append",
    "appendleft",
    "extend",
    "extendleft",
    "pop",
    "popleft",
    "popitem",
    "add",
    "insert",
    "remove",
    "discard",
    "clear",
    "update",
    "setdefault",
    "put",
    "put_nowait",
}


def _self_attr_of_target(tgt: ast.AST) -> str | None:
    """`self.x = ...` -> x; `self.x[k] = / += ...` -> x (subscript
    stores mutate the container the attribute holds)."""
    node = tgt
    while isinstance(node, ast.Subscript):
        node = node.value
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _mutations(node: ast.AST) -> Iterator[tuple[str, ast.AST]]:
    """(attr, site) for every self-attribute mutation AT ``node`` (not
    recursing — callers drive this from a flow walk that visits every
    node exactly once)."""
    if isinstance(node, ast.Assign):
        for tgt in node.targets:
            attr = _self_attr_of_target(tgt)
            if attr:
                yield attr, node
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        attr = _self_attr_of_target(node.target)
        if attr:
            yield attr, node
    elif isinstance(node, ast.Call):
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in _MUTATING_METHODS:
            attr = _self_attr_of_target(f.value)
            if attr:
                yield attr, node


def _tarjan(succ: dict[str, set[str]]) -> list[list[str]]:
    """Tarjan SCC, iterative (the lock graph is tiny, but recursion
    depth should not depend on analyzed code shape)."""
    index: dict[str, int] = {}
    low: dict[str, int] = {}
    on_stack: set[str] = set()
    stack: list[str] = []
    counter = [0]
    sccs: list[list[str]] = []
    nodes = set(succ)
    for targets in succ.values():
        nodes |= targets

    for start in sorted(nodes):
        if start in index:
            continue
        work: list[tuple[str, Iterator[str]]] = [
            (start, iter(sorted(succ.get(start, ()))))
        ]
        index[start] = low[start] = counter[0]
        counter[0] += 1
        stack.append(start)
        on_stack.add(start)
        while work:
            node, it = work[-1]
            advanced = False
            for nxt in it:
                if nxt not in index:
                    index[nxt] = low[nxt] = counter[0]
                    counter[0] += 1
                    stack.append(nxt)
                    on_stack.add(nxt)
                    work.append((nxt, iter(sorted(succ.get(nxt, ())))))
                    advanced = True
                    break
                if nxt in on_stack:
                    low[node] = min(low[node], index[nxt])
            if advanced:
                continue
            work.pop()
            if work:
                parent = work[-1][0]
                low[parent] = min(low[parent], low[node])
            if low[node] == index[node]:
                scc: list[str] = []
                while True:
                    w = stack.pop()
                    on_stack.discard(w)
                    scc.append(w)
                    if w == node:
                        break
                sccs.append(scc)
    return sccs
