"""thread-map: which functions execute on which thread ROLES (v5).

The control plane that keeps elastic training alive under churn spawns
~30 threads across dispatcher, rendezvous, pod manager, liveness beats,
checkpoint watchers and the micro-batcher — and every review round since
r6 has hand-found check-and-set races on the shared state they touch.
The lock-discipline/lock-order passes only judge state someone already
*annotated*; this module infers the concurrency structure itself, so the
shared-state pass (analysis/shared_state.py) can flag UNANNOTATED state
crossing thread boundaries.

A *role* is a named concurrency domain.  Entry points seed roles:

- ``threading.Thread(target=T, name="x")``   -> ``thread:x`` (or the
  target's name when ``name=`` is absent/dynamic);
- ``threading.Timer(delay, T)``              -> ``timer:<T>``;
- ``<pool>.submit(T, ...)``                  -> ``pool:<T>`` (executor
  worker threads — ThreadPoolExecutor and the repo's IngestPool share
  the ``submit`` shape);
- ``<future>.add_done_callback(T)``          -> ``callback:<T>`` (done
  callbacks run on executor threads, or inline on the completing one);
- gRPC servicer handler tables               -> ``grpc:<Class>`` — a
  ``method_table`` method's string constants naming methods of its own
  class (master/servicer.py), or a dict literal mapping string constants
  to ``self.<method>`` inside a class that wires grpc handlers
  (ps/service.py, serving/server.py);
- a module-level ``def main(...)``           -> ``main`` (the task loop);
- ``functools.partial(T, ...)`` (or a bare ``partial`` import) in any of
  the spawn shapes above unwraps to ``T`` (v6 — previously a documented
  blind spot: partial-wrapped targets got no role, muting shared-state
  checks on everything they touch);
- ``# thread-role: <role>`` on a ``def`` line (or the comment-only line
  above) — the explicit seed for hand-offs the resolver cannot see
  (e.g. a worker handed to the beat thread through a holder dict).

Roles then propagate over call edges: the resolved edges of
analysis/callgraph.py PLUS a constructor-type layer local to this map —
``v = ClassName(...)`` types local ``v`` (lexically visible to nested
closures), ``self._x = ClassName(...)`` types the instance attribute,
and ``v.meth(...)`` / ``self._x.meth(...)`` then edge into the class's
method.  These typed edges exist for ROLE propagation only: lock-order
and blocking-propagation keep the conservative resolved-edge set.
Nested ``def``/``lambda`` scopes inherit the enclosing function's roles
unless they are themselves a spawn target (a closure handed to a thread
runs ONLY there).

Blind spots (docs/static_analysis.md v5; the runtime twin
``common/racesan.py`` covers them from the other side): dynamic targets
(``target=self._table[k]``), ``getattr`` dispatch, callables stored in
containers, roles of code only tests invoke, and same-role concurrency
(two threads of one role racing each other — the role model treats a
role as one domain).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from elasticdl_tpu.analysis.callgraph import CallGraph, partial_target, shared_graph
from elasticdl_tpu.analysis.core import Finding, SourceFile, attr_chain as _attr_chain
from elasticdl_tpu.analysis.import_hygiene import _module_name

MAIN_ROLE = "main"

_ROLE_ANNOTATION = re.compile(r"#\s*thread-role\s*:\s*(?P<role>[^#]*)")
_ROLE_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.:\-]*$")
_ANON = re.compile(r"^(?P<enc>.+)\.<(?P<name>[^@>]+)@\d+>$")

#: Receivers/spellings that mark a class as wiring grpc handlers — the
#: dict-literal handler-table detector only fires inside such classes, so
#: an ordinary dispatch table does not become a thread entry by accident.
_GRPC_MARKERS = (
    "grpc.server",
    "add_generic_rpc_handlers",
    "make_generic_handler",
    "unary_unary_rpc_method_handler",
    "method_handlers_generic_handler",
)


class ThreadEntry:
    """One inferred (or declared) thread entry point."""

    __slots__ = ("role", "kind", "target", "path", "line")

    def __init__(self, role: str, kind: str, target: str, path: str, line: int):
        self.role = role
        self.kind = kind  # thread|timer|pool|callback|grpc|main|annotation
        self.target = target  # qualname of the entry function
        self.path = path
        self.line = line

    def as_dict(self) -> dict:
        return {
            "role": self.role, "kind": self.kind, "target": self.target,
            "site": f"{self.path}:{self.line}",
        }


def _short_name(node: ast.expr) -> str:
    """Display name of a spawn target expression."""
    inner = partial_target(node)
    if inner is not None:
        return _short_name(inner)  # partial(T, ...): T names the role
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Lambda):
        return f"lambda@{node.lineno}"
    return "?"


def _const_str(node: Optional[ast.expr]) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class ThreadMap:
    """Role assignment over a CallGraph's functions."""

    def __init__(self, graph: CallGraph):
        self.graph = graph
        self.entries: List[ThreadEntry] = []
        #: Malformed/unknown '# thread-role:' annotations — reported by the
        #: shared-state pass (the map itself is not a pass).
        self.errors: List[Finding] = []
        #: qualname -> roles.  Functions absent here have UNKNOWN role and
        #: do not participate in cross-role judgements.
        self.roles: Dict[str, Set[str]] = {}
        #: (module:Class) -> {attr: "module:Class"} constructor types.
        self._attr_types: Dict[str, Dict[str, str]] = {}
        #: qualname -> extra role-propagation edges (typed receivers).
        self._typed_edges: Dict[str, Set[str]] = {}
        #: anon qualname -> enclosing qualname (from the callgraph naming).
        self._enclosing: Dict[str, str] = {}
        for q in graph.functions:
            m = _ANON.match(q)
            if m is not None:
                self._enclosing[q] = m.group("enc")
        #: (enclosing qualname, local def name) -> anon qualnames.
        self._nested: Dict[Tuple[str, str], List[str]] = {}
        for q, enc in self._enclosing.items():
            name = _ANON.match(q).group("name")
            self._nested.setdefault((enc, name), []).append(q)
        self._collect_attr_types()
        self._collect_entries_and_edges()
        self._propagate()

    # -- phase 1: constructor types of instance attributes --

    def _collect_attr_types(self) -> None:
        for path, src in self.graph.sources.items():
            mod = _module_name(path) or path
            for node in src.tree.body:
                if not isinstance(node, ast.ClassDef):
                    continue
                types: Dict[str, str] = {}
                for sub in ast.walk(node):
                    if not (
                        isinstance(sub, ast.Assign)
                        and len(sub.targets) == 1
                        and isinstance(sub.value, ast.Call)
                    ):
                        continue
                    t = sub.targets[0]
                    if not (
                        isinstance(t, ast.Attribute)
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"
                    ):
                        continue
                    cls_q = self.graph.resolve_class(mod, sub.value.func)
                    if cls_q is not None:
                        types[t.attr] = cls_q
                if types:
                    self._attr_types[f"{mod}:{node.name}"] = types

    # -- phase 2: entries + typed edges, per lexical scope --

    def _collect_entries_and_edges(self) -> None:
        for path, src in self.graph.sources.items():
            mod = _module_name(path) or path
            for node in src.tree.body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    q = f"{mod}:{node.name}"
                    if node.name == "main":
                        self._add_entry(MAIN_ROLE, "main", q, path, node.lineno)
                    self._scan_annotation(src, mod, node, q)
                    self._scan_scope(src, mod, None, node, q, {})
                elif isinstance(node, ast.ClassDef):
                    self._scan_grpc_tables(src, mod, node)
                    for meth in node.body:
                        if isinstance(
                            meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                        ):
                            q = f"{mod}:{node.name}.{meth.name}"
                            self._scan_annotation(src, mod, meth, q)
                            self._scan_scope(src, mod, node, meth, q, {})

    def _scan_annotation(self, src: SourceFile, mod, node, q: str) -> None:
        """``# thread-role: <role>`` on the def line or anywhere in the
        contiguous comment-only block above it (the ``# hot-path``
        placement convention) seeds an explicit role."""
        cands = [node.lineno]
        above = node.lineno - 1
        while above in src.comment_only_lines:
            cands.append(above)
            above -= 1
        for cand in cands:
            comment = src.comments.get(cand)
            if comment is None:
                continue
            m = _ROLE_ANNOTATION.search(comment)
            if m is None:
                continue
            # First token only: trailing prose on the annotation line is
            # the author's rationale, not part of the role name.
            tokens = m.group("role").split()
            role = tokens[0] if tokens else ""
            if not role or not _ROLE_NAME.match(role):
                self.errors.append(Finding(
                    "shared-state", src.path, cand,
                    f"malformed thread-role annotation {role!r}: expected "
                    "'# thread-role: <role>' naming one role "
                    "(e.g. main, thread:heartbeat, grpc:MasterServicer)",
                ))
                return
            self._add_entry(role, "annotation", q, src.path, node.lineno)
            return

    def _scan_grpc_tables(self, src: SourceFile, mod, cls: ast.ClassDef):
        """gRPC handler entry points: the ``method_table`` string-constant
        form, and dict literals {str: self.<meth>} in grpc-wiring classes."""
        role = f"grpc:{cls.name}"
        methods = {
            m.name for m in cls.body
            if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        wires_grpc = False
        for sub in ast.walk(cls):
            if isinstance(sub, ast.Attribute):
                chain = _attr_chain(sub)
                if any(chain.endswith(mk) for mk in _GRPC_MARKERS):
                    wires_grpc = True
            elif isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name):
                if sub.func.id in _GRPC_MARKERS:
                    wires_grpc = True
        for meth in cls.body:
            if not isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            if meth.name == "method_table":
                for sub in ast.walk(meth):
                    name = _const_str(sub) if isinstance(sub, ast.Constant) else None
                    if name in methods:
                        self._add_entry(
                            role, "grpc", f"{mod}:{cls.name}.{name}",
                            src.path, meth.lineno,
                        )
            elif wires_grpc:
                for sub in ast.walk(meth):
                    if not isinstance(sub, ast.Dict):
                        continue
                    for key, value in zip(sub.keys, sub.values):
                        if _const_str(key) is None:
                            continue
                        if (
                            isinstance(value, ast.Attribute)
                            and isinstance(value.value, ast.Name)
                            and value.value.id == "self"
                            and value.attr in methods
                        ):
                            self._add_entry(
                                role, "grpc",
                                f"{mod}:{cls.name}.{value.attr}",
                                src.path, sub.lineno,
                            )

    def _scan_scope(self, src, mod, cls, node, q: str, outer_types: dict):
        """One lexical scope: collect local constructor types (closures see
        the enclosing scope's), spawn entries, and typed call edges.
        Recurses into nested defs under their callgraph anon names."""
        local_types = dict(outer_types)
        body = node.body if isinstance(node.body, list) else [node.body]
        stack = list(body)
        nested: List[ast.AST] = []
        # First sweep: local constructor types of THIS scope (hoisted, so a
        # spawn above the assignment still resolves — lexical, not flow).
        seen: List[ast.AST] = list(stack)
        while seen:
            n = seen.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            if (
                isinstance(n, ast.Assign)
                and len(n.targets) == 1
                and isinstance(n.targets[0], ast.Name)
                and isinstance(n.value, ast.Call)
            ):
                cls_q = self.graph.resolve_class(mod, n.value.func)
                if cls_q is not None:
                    local_types[n.targets[0].id] = cls_q
            seen.extend(ast.iter_child_nodes(n))
        while stack:
            n = stack.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                nested.append(n)
                continue
            if isinstance(n, ast.Call):
                self._scan_call(src, mod, cls, q, n, local_types)
            stack.extend(ast.iter_child_nodes(n))
        for sub in nested:
            name = getattr(sub, "name", "lambda")
            anon_q = f"{q}.<{name}@{sub.lineno}>"
            self._scan_scope(src, mod, cls, sub, anon_q, local_types)

    def _scan_call(self, src, mod, cls, q, node: ast.Call, local_types):
        chain = _attr_chain(node.func)
        tail = chain.split(".")[-1] if chain else ""
        # Spawn shapes.
        if tail == "Thread" or (
            isinstance(node.func, ast.Name) and node.func.id == "Thread"
        ):
            target = next(
                (kw.value for kw in node.keywords if kw.arg == "target"), None
            )
            if target is not None:
                tq = self._resolve_target(mod, cls, q, target, local_types)
                name = next(
                    (_const_str(kw.value) for kw in node.keywords
                     if kw.arg == "name"), None,
                )
                role = f"thread:{name or _short_name(target)}"
                if tq is not None:
                    self._add_entry(role, "thread", tq, src.path, node.lineno)
            return
        if tail == "Timer" or (
            isinstance(node.func, ast.Name) and node.func.id == "Timer"
        ):
            target = node.args[1] if len(node.args) > 1 else next(
                (kw.value for kw in node.keywords if kw.arg == "function"),
                None,
            )
            if target is not None:
                tq = self._resolve_target(mod, cls, q, target, local_types)
                if tq is not None:
                    self._add_entry(
                        f"timer:{_short_name(target)}", "timer", tq,
                        src.path, node.lineno,
                    )
            return
        if isinstance(node.func, ast.Attribute) and node.func.attr == "submit":
            if node.args:
                tq = self._resolve_target(
                    mod, cls, q, node.args[0], local_types
                )
                if tq is not None:
                    self._add_entry(
                        f"pool:{_short_name(node.args[0])}", "pool", tq,
                        src.path, node.lineno,
                    )
            # fall through: the submit receiver may also be a typed call
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "add_done_callback"
            and node.args
        ):
            tq = self._resolve_target(mod, cls, q, node.args[0], local_types)
            if tq is not None:
                self._add_entry(
                    f"callback:{_short_name(node.args[0])}", "callback", tq,
                    src.path, node.lineno,
                )
            return
        # Typed call edges: v.meth(...) / self._x.meth(...).
        if isinstance(node.func, ast.Attribute):
            recv = node.func.value
            cls_q: Optional[str] = None
            if isinstance(recv, ast.Name):
                cls_q = local_types.get(recv.id)
            elif (
                isinstance(recv, ast.Attribute)
                and isinstance(recv.value, ast.Name)
                and recv.value.id == "self"
                and cls is not None
            ):
                cls_q = self._attr_types.get(
                    f"{mod}:{cls.name}", {}
                ).get(recv.attr)
            if cls_q is not None:
                callee = self.graph.class_method(cls_q, node.func.attr)
                if callee is not None:
                    self._typed_edges.setdefault(q, set()).add(callee)

    def _resolve_target(
        self, mod, cls, q, node: ast.expr, local_types
    ) -> Optional[str]:
        """A spawn-target expression -> qualname, or None (dynamic)."""
        inner = partial_target(node)
        if inner is not None:
            # functools.partial(T, ...): the spawned thread runs T —
            # resolve the wrapped callable (v6; previously a documented
            # blind spot that muted shared-state checks on T).
            return self._resolve_target(mod, cls, q, inner, local_types)
        if isinstance(node, ast.Lambda):
            return f"{q}.<lambda@{node.lineno}>"
        if isinstance(node, ast.Name):
            # Nested def of this scope chain first (lexical shadowing).
            scope = q
            while scope:
                anons = self._nested.get((scope, node.id))
                if anons:
                    return anons[0]
                m = _ANON.match(scope)
                scope = m.group("enc") if m else ""
            cand = f"{mod}:{node.id}"
            if cand in self.graph.functions:
                return cand
            tgt = self.graph._from_imports.get(mod, {}).get(node.id)
            if tgt is not None:
                base, leaf = tgt
                cand = f"{base}:{leaf}"
                if cand in self.graph.functions:
                    return cand
            return None
        if isinstance(node, ast.Attribute):
            if isinstance(node.value, ast.Name):
                if node.value.id == "self" and cls is not None:
                    cand = f"{mod}:{cls.name}.{node.attr}"
                    return cand if cand in self.graph.functions else None
                recv_cls = local_types.get(node.value.id)
                if recv_cls is not None:
                    return self.graph.class_method(recv_cls, node.attr)
            chain = _attr_chain(node)
            if chain and "." in chain:
                prefix, leaf = chain.rsplit(".", 1)
                target_mod = self.graph._resolve_module(mod, prefix)
                if target_mod is not None:
                    cand = f"{target_mod}:{leaf}"
                    if cand in self.graph.functions:
                        return cand
        return None

    def _add_entry(self, role, kind, target, path, line) -> None:
        self.entries.append(ThreadEntry(role, kind, target, path, line))

    # -- phase 3: propagation --

    def _propagate(self) -> None:
        entry_targets = {e.target for e in self.entries if e.kind != "main"}
        for e in self.entries:
            if e.target in self.graph.functions:
                self.roles.setdefault(e.target, set()).add(e.role)
        changed = True
        while changed:
            changed = False
            for q, fn in self.graph.functions.items():
                r = self.roles.get(q)
                if not r:
                    continue
                callees = {c.callee for c in fn.calls}
                callees |= self._typed_edges.get(q, set())
                for callee in callees:
                    if callee not in self.graph.functions:
                        continue
                    have = self.roles.setdefault(callee, set())
                    if not r <= have:
                        have |= r
                        changed = True
            # Nested scopes inherit the enclosing function's roles unless
            # they are spawn targets themselves (a closure handed to a
            # thread runs ONLY on that thread).
            for anon_q, enc_q in self._enclosing.items():
                if anon_q in entry_targets:
                    continue
                r = self.roles.get(enc_q)
                if not r:
                    continue
                have = self.roles.setdefault(anon_q, set())
                if not r <= have:
                    have |= r
                    changed = True

    # -- API --

    def roles_of(self, qualname: str) -> frozenset:
        return frozenset(self.roles.get(qualname, ()))

    def attr_types(self) -> Dict[str, Dict[str, str]]:
        """``"module:Class"`` -> {attr: constructed ``"module:Class"``} —
        the constructor-type layer, shared with the v6 transfer-discipline
        pass (it resolves ``self.trainer.train_step(...)``-shaped calls
        through the same typed receivers the role propagation uses)."""
        return self._attr_types

    def known_roles(self) -> Set[str]:
        return {e.role for e in self.entries}

    def dump(self) -> dict:
        """Machine-readable map: role -> functions, plus the entry list —
        the ``--threadmap`` CLI payload and the LINT artifact's stats."""
        by_role: Dict[str, List[str]] = {}
        for q, roles in self.roles.items():
            for r in roles:
                by_role.setdefault(r, []).append(q)
        return {
            "roles": {r: sorted(qs) for r, qs in sorted(by_role.items())},
            "entries": [e.as_dict() for e in self.entries],
            "functions_with_role": len(self.roles),
            "functions_total": len(self.graph.functions),
        }


#: One-entry memo, keyed on the (memoized) CallGraph identity — the
#: shared-state pass and the CLI's --threadmap view reuse
#: one map per run, like shared_graph.
_MAP_MEMO: dict = {}


def shared_thread_map(files: Sequence[SourceFile]) -> ThreadMap:
    graph = shared_graph(files)
    hit = _MAP_MEMO.get(id(graph))
    if hit is not None and hit[0] is graph:
        return hit[1]
    tmap = ThreadMap(graph)
    _MAP_MEMO.clear()
    _MAP_MEMO[id(graph)] = (graph, tmap)
    return tmap
