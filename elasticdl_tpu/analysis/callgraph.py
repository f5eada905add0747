"""Whole-repo call graph: the interprocedural layer under graftlint v2.

r7's passes were strictly intra-procedural — a blocking call or a lock
acquisition hidden ONE call deep was invisible (`hot-path-sync` could not
see a one-line helper wrapping ``block_until_ready``; lock nesting through
a ``self._helper()`` call was not an edge).  This module builds the shared
function index + call-edge resolution both v2 passes (blocking-propagation,
lock-order) consume:

Resolved edges (deliberately conservative — every edge is real):

- ``self.method(...)``      -> a method of the lexically enclosing class;
- ``func(...)``             -> a module-level function of the same module,
                               or one bound by ``from mod import func``;
- ``mod.func(...)``         -> a module-level function of an imported repo
                               module (``import mod`` / ``import pkg.mod`` /
                               ``from pkg import mod`` / aliases).

Known blind spots (documented in docs/static_analysis.md and covered by
the runtime sanitizer instead): dynamic dispatch through object attributes
(``self.dispatcher.get_task(...)`` — the receiver's type is not tracked),
``getattr`` / method tables, callbacks/lambdas handed across objects,
class constructors, and ``super()``.

Per function the graph also records the facts the v2 passes need at each
site:

- *call sites* with the blocking-exemption context (inside a
  ``phases.phase(...)`` boundary / an ``except`` handler) and the set of
  locks lexically held;
- *blocking primitives* (shared detector with hot-path-sync) with the same
  context plus whether the line carries a ``hot-path-sync`` waiver — a
  reasoned waiver covers the transitive concern too, so waived blocking
  does not propagate to callers;
- *lock acquisitions* (``with self.<lock>:`` / ``with <module_lock>:`` of
  a lock DECLARED in scope) with the locks already held.

Nested ``def``/``lambda`` bodies are separate anonymous scopes: their
execution is deferred (background threads own their own time and their own
lock stacks), so their blocking never propagates to the enclosing function
and their acquisitions start from an empty held set.
"""

from __future__ import annotations

import ast
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from elasticdl_tpu.analysis.core import SourceFile
from elasticdl_tpu.analysis.hot_path import blocking_reason, is_phase_context
from elasticdl_tpu.analysis.import_hygiene import _module_name

#: Constructors that declare a lock attribute (the runtime wrapper spellings
#: come first: common/locksan.py is the sanitizer the declarations feed).
_LOCK_CTOR_CHAINS = {
    "threading.Lock": False,
    "threading.RLock": True,
    # Condition() defaults to wrapping an RLock: same-thread nested entry
    # is legal, so it must not produce self-deadlock findings.
    "threading.Condition": True,
    "locksan.lock": False,
    "locksan.rlock": True,
}


@dataclasses.dataclass
class CallSite:
    callee: str  # qualified "module:Class.method" / "module:func"
    line: int
    exempt: bool  # inside a phase boundary or except handler
    held: Tuple[str, ...]  # lock ids lexically held at the site


@dataclasses.dataclass
class BlockingCall:
    line: int
    reason: str
    exempt: bool
    waived: bool  # carries a hot-path-sync waiver: accounted by a human


@dataclasses.dataclass
class LockAcquire:
    lock: str  # qualified lock id "module:Class.attr" / "module:attr"
    line: int
    held: Tuple[str, ...]


@dataclasses.dataclass
class AttrAccess:
    """One ``self.<attr>`` touch inside a class-scoped function, with the
    locks lexically held at the site — the raw material of the v5
    shared-state pass (analysis/shared_state.py)."""

    attr: str
    line: int
    write: bool
    rmw: bool  # read-modify-write in ONE site (augmented assignment)
    held: Tuple[str, ...]


@dataclasses.dataclass
class FunctionInfo:
    qualname: str  # "module:Class.method" / "module:func" / anon scopes
    path: str
    line: int
    hot_path: bool
    resolvable: bool  # False for nested/anonymous scopes
    cls_name: str = ""  # lexically enclosing class ("" for module funcs)
    calls: List[CallSite] = dataclasses.field(default_factory=list)
    blocking: List[BlockingCall] = dataclasses.field(default_factory=list)
    acquires: List[LockAcquire] = dataclasses.field(default_factory=list)
    attr_accesses: List[AttrAccess] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class LockDecl:
    lock_id: str  # "module:Class.attr" / "module:attr"
    attr: str
    cls: str  # "" for module-level locks
    module: str
    path: str
    line: int
    reentrant: bool
    is_locksan: bool
    rt_name: Optional[str]  # locksan.lock("<name>") first argument
    rt_leaf: bool  # locksan leaf= kwarg
    rt_before: Tuple[str, ...]  # locksan before= kwarg (attr names)


def _self_attr(node: ast.AST) -> Optional[str]:
    if (
        isinstance(node, ast.Attribute)
        and isinstance(node.value, ast.Name)
        and node.value.id == "self"
    ):
        return node.attr
    return None


def _chain(node: ast.AST) -> str:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def partial_target(node: ast.AST) -> Optional[ast.expr]:
    """``functools.partial(T, ...)`` / ``partial(T, ...)`` -> the wrapped
    callable expression ``T``, else None.  Shared by the thread map (v6):
    a partial handed to ``Thread(target=...)`` / ``pool.submit(...)``
    executes its wrapped callable on the spawned thread, so the role
    resolver must see through it — before v6, partial-wrapped targets got
    no role, silently muting shared-state checks on everything they
    touch.  Only the two canonical spellings match (``functools.partial``
    and a bare ``partial`` import); an arbitrary ``obj.partial(...)``
    method stays dynamic."""
    if not isinstance(node, ast.Call):
        return None
    chain = _chain(node.func)
    if chain in ("partial", "functools.partial") and node.args:
        return node.args[0]
    return None


def _lock_ctor(node: ast.AST) -> Optional[Tuple[bool, bool]]:
    """(is_lock, reentrant) when ``node`` is a lock-constructor call."""
    if not isinstance(node, ast.Call):
        return None
    chain = _chain(node.func)
    tail = ".".join(chain.split(".")[-2:]) if "." in chain else chain
    if tail in _LOCK_CTOR_CHAINS:
        return True, _LOCK_CTOR_CHAINS[tail]
    return None


def _locksan_meta(node: ast.Call) -> Tuple[Optional[str], bool, Tuple[str, ...]]:
    """(rt_name, leaf, before) from a ``locksan.lock(...)`` call."""
    rt_name: Optional[str] = None
    if node.args and isinstance(node.args[0], ast.Constant) and isinstance(
        node.args[0].value, str
    ):
        rt_name = node.args[0].value
    leaf = False
    before: Tuple[str, ...] = ()
    for kw in node.keywords:
        if kw.arg == "name" and isinstance(kw.value, ast.Constant):
            rt_name = str(kw.value.value)
        elif kw.arg == "leaf" and isinstance(kw.value, ast.Constant):
            leaf = kw.value.value is True
        elif kw.arg == "before" and isinstance(kw.value, (ast.Tuple, ast.List)):
            before = tuple(
                e.value
                for e in kw.value.elts
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            )
    return rt_name, leaf, before


#: One-entry memo for :func:`shared_graph`: both v2 passes (and the CLI's
#: --callgraph view) consume the SAME parsed file set within a
#: run; rebuilding the graph per consumer tripled the pre-commit cost.
#: Keyed by the identity of every SourceFile (the cached entry keeps a
#: strong reference to them, so the ids stay valid while it lives).
_GRAPH_MEMO: dict = {}


def shared_graph(files: Sequence[SourceFile]) -> "CallGraph":
    """The CallGraph for ``files``, built at most once per file set."""
    key = tuple(id(s) for s in files)
    hit = _GRAPH_MEMO.get(key)
    if hit is not None:
        return hit[1]
    graph = CallGraph(files)
    _GRAPH_MEMO.clear()  # one entry: the current run's file set
    _GRAPH_MEMO[key] = (list(files), graph)
    return graph


class CallGraph:
    """Function index + resolved call edges over a set of SourceFiles."""

    def __init__(self, files: Sequence[SourceFile]):
        self.functions: Dict[str, FunctionInfo] = {}
        self.locks: Dict[str, LockDecl] = {}
        self.sources: Dict[str, SourceFile] = {s.path: s for s in files}
        self._blocking_memo: Optional[Dict[str, List[str]]] = None
        self._edges_memo: Optional[Dict[Tuple[str, str], List[str]]] = None
        #: module -> {local name -> qualified target}; filled in two passes
        #: (the index must be complete before edges resolve).
        self._modules: Dict[str, SourceFile] = {}
        self._mod_funcs: Dict[str, set] = {}
        self._mod_classes: Dict[str, Dict[str, set]] = {}
        self._imports: Dict[str, Dict[str, str]] = {}  # alias -> module
        self._from_imports: Dict[str, Dict[str, Tuple[str, str]]] = {}
        for src in files:
            mod = _module_name(src.path) or src.path
            self._modules[mod] = src
        for mod, src in self._modules.items():
            self._index_module(mod, src)
        for mod, src in self._modules.items():
            self._extract_module(mod, src)

    # -- pass 1: symbol + import index --

    def _index_module(self, mod: str, src: SourceFile) -> None:
        funcs: set = set()
        classes: Dict[str, set] = {}
        imports: Dict[str, str] = {}
        from_imports: Dict[str, Tuple[str, str]] = {}
        for node in src.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                funcs.add(node.name)
            elif isinstance(node, ast.ClassDef):
                classes[node.name] = {
                    n.name
                    for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                }
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    bound = alias.asname or alias.name.split(".")[0]
                    # ``import a.b`` binds ``a``; ``import a.b as ab`` binds
                    # ``ab`` to a.b directly.
                    imports[bound] = alias.name if alias.asname else (
                        alias.name.split(".")[0]
                    )
            elif isinstance(node, ast.ImportFrom) and not node.level:
                base = node.module or ""
                for alias in node.names:
                    bound = alias.asname or alias.name
                    from_imports[bound] = (base, alias.name)
        self._mod_funcs[mod] = funcs
        self._mod_classes[mod] = classes
        self._imports[mod] = imports
        self._from_imports[mod] = from_imports

    # -- pass 2: per-function extraction --

    def _extract_module(self, mod: str, src: SourceFile) -> None:
        # Module-level lock declarations: ``_lib_lock = threading.Lock()``.
        for node in src.tree.body:
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and (
                isinstance(node.targets[0], ast.Name)
            ):
                self._maybe_declare_lock(
                    mod, src, "", node.targets[0].id, node.value, node.lineno
                )
        for node in src.tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._extract_function(mod, src, None, node, f"{mod}:{node.name}")
            elif isinstance(node, ast.ClassDef):
                # Class-scoped lock declarations live in ANY method (almost
                # always __init__) as ``self.<attr> = threading.Lock()``.
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Assign) and len(sub.targets) == 1:
                        attr = _self_attr(sub.targets[0])
                        if attr is not None:
                            self._maybe_declare_lock(
                                mod, src, node.name, attr, sub.value, sub.lineno
                            )
                for meth in node.body:
                    if isinstance(meth, (ast.FunctionDef, ast.AsyncFunctionDef)):
                        self._extract_function(
                            mod, src, node, meth,
                            f"{mod}:{node.name}.{meth.name}",
                        )

    def _maybe_declare_lock(
        self, mod, src, cls: str, attr: str, value: ast.AST, line: int
    ) -> None:
        ctor = _lock_ctor(value)
        if ctor is None:
            return
        lock_id = f"{mod}:{cls}.{attr}" if cls else f"{mod}:{attr}"
        chain = _chain(value.func)
        is_locksan = chain.split(".")[-2:-1] == ["locksan"] or chain.startswith(
            "locksan."
        )
        rt_name, rt_leaf, rt_before = (
            _locksan_meta(value) if is_locksan else (None, False, ())
        )
        self.locks[lock_id] = LockDecl(
            lock_id=lock_id, attr=attr, cls=cls, module=mod,
            path=src.path, line=line, reentrant=ctor[1],
            is_locksan=is_locksan, rt_name=rt_name, rt_leaf=rt_leaf,
            rt_before=rt_before,
        )

    def _extract_function(self, mod, src, cls, node, qualname) -> None:
        info = FunctionInfo(
            qualname=qualname,
            path=src.path,
            line=node.lineno,
            hot_path=src.is_hot_path(node.lineno),
            resolvable=True,
            cls_name=cls.name if cls is not None else "",
        )
        self.functions[qualname] = info
        self._walk(mod, src, cls, info, node.body, exempt=False, held=())

    def _walk(self, mod, src, cls, info, body, exempt, held) -> None:
        for node in body:
            self._visit(mod, src, cls, info, node, exempt, held)

    def _visit(self, mod, src, cls, info, node, exempt, held) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            # Deferred scope: fresh anonymous FunctionInfo, empty held set,
            # not resolvable as a call target.  Its lock nesting still
            # counts (a closure IS eventually some thread's code).
            anon = FunctionInfo(
                qualname=f"{info.qualname}.<{getattr(node, 'name', 'lambda')}"
                f"@{node.lineno}>",
                path=src.path, line=node.lineno, hot_path=False,
                resolvable=False, cls_name=info.cls_name,
            )
            self.functions[anon.qualname] = anon
            body = node.body if isinstance(node.body, list) else [node.body]
            self._walk(mod, src, cls, anon, body, exempt=False, held=())
            return
        if isinstance(node, ast.With):
            new_held = held
            new_exempt = exempt
            for item in node.items:
                ctx = item.context_expr
                if is_phase_context(ctx):
                    new_exempt = True
                    continue
                lock = self._lock_of_ctx(mod, cls, ctx)
                if lock is not None:
                    info.acquires.append(
                        LockAcquire(lock=lock, line=node.lineno, held=new_held)
                    )
                    new_held = new_held + (lock,)
                else:
                    self._visit(mod, src, cls, info, ctx, exempt, held)
            self._walk(mod, src, cls, info, node.body, new_exempt, new_held)
            return
        if isinstance(node, ast.Try):
            self._walk(mod, src, cls, info, node.body, exempt, held)
            self._walk(mod, src, cls, info, node.orelse, exempt, held)
            self._walk(mod, src, cls, info, node.finalbody, exempt, held)
            for h in node.handlers:
                # Error path: exempt for blocking, NOT for locks (a lock
                # taken while recovering still nests for real).
                self._walk(mod, src, cls, info, h.body, True, held)
            return
        if isinstance(node, ast.AugAssign):
            # ``self.x += 1`` is a read AND a write at one site — the
            # check-and-set shape the shared-state pass must see as a
            # read-modify-write (never legal under '# gil-atomic').
            # ``self.d[k] += 1`` mutates the SHARED CONTAINER through the
            # attribute: same read-modify-write judgement on the attr.
            attr = _self_attr(node.target)
            if attr is None and isinstance(node.target, ast.Subscript):
                attr = _self_attr(node.target.value)
                if attr is not None and cls is not None:
                    info.attr_accesses.append(AttrAccess(
                        attr=attr, line=node.lineno, write=True, rmw=True,
                        held=held,
                    ))
                    self._visit(
                        mod, src, cls, info, node.target.slice, exempt, held
                    )
                    self._visit(mod, src, cls, info, node.value, exempt, held)
                    return
                self._visit(mod, src, cls, info, node.target, exempt, held)
            elif attr is not None and cls is not None:
                info.attr_accesses.append(AttrAccess(
                    attr=attr, line=node.lineno, write=True, rmw=True,
                    held=held,
                ))
            self._visit(mod, src, cls, info, node.value, exempt, held)
            return
        if isinstance(node, ast.Subscript) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            # ``self.d[k] = v`` / ``del self.d[k]`` mutate the shared
            # container: a WRITE of the attribute (single-op, not rmw —
            # dict/list item set is one GIL-atomic op).  The generic
            # recursion below still records the receiver's Load, which is
            # harmless (same line, same held set).
            attr = _self_attr(node.value)
            if attr is not None and cls is not None:
                info.attr_accesses.append(AttrAccess(
                    attr=attr, line=node.lineno, write=True, rmw=False,
                    held=held,
                ))
        if isinstance(node, ast.Attribute):
            # Innermost ``self.<attr>`` only: for ``self.a.b`` the chain
            # recurses down to the ``self.a`` load (the shared slot) —
            # ``b`` lives on another object.
            attr = _self_attr(node)
            if attr is not None and cls is not None:
                info.attr_accesses.append(AttrAccess(
                    attr=attr, line=node.lineno,
                    write=isinstance(node.ctx, (ast.Store, ast.Del)),
                    rmw=False, held=held,
                ))
        if isinstance(node, ast.Call):
            reason = blocking_reason(node)
            if reason is not None:
                info.blocking.append(BlockingCall(
                    line=node.lineno, reason=reason, exempt=exempt,
                    waived=self._line_waives(src, node.lineno, "hot-path-sync"),
                ))
            callee = self._resolve_call(mod, cls, node.func)
            if callee is not None:
                info.calls.append(CallSite(
                    callee=callee, line=node.lineno, exempt=exempt, held=held,
                ))
        for child in ast.iter_child_nodes(node):
            self._visit(mod, src, cls, info, child, exempt, held)

    @staticmethod
    def _line_waives(src: SourceFile, line: int, rule: str) -> bool:
        for cand in (line, line - 1):
            w = src.waivers.get(cand)
            if w is not None and w.rule == rule and (
                cand == line or cand in src.comment_only_lines
            ):
                # A waiver consumed HERE is load-bearing even when its
                # function is not hot-path-marked (it stops the primitive
                # from propagating to hot callers) — record usage or the
                # stale-waiver pass would tell the user to delete it.
                src.used_waiver_lines.add(cand)
                return True
        return False

    def _lock_of_ctx(self, mod, cls, ctx: ast.expr) -> Optional[str]:
        attr = _self_attr(ctx)
        if attr is not None and cls is not None:
            lock_id = f"{mod}:{cls.name}.{attr}"
            return lock_id if lock_id in self.locks else None
        if isinstance(ctx, ast.Name):
            lock_id = f"{mod}:{ctx.id}"
            return lock_id if lock_id in self.locks else None
        return None

    def _resolve_call(self, mod, cls, func: ast.expr) -> Optional[str]:
        if isinstance(func, ast.Name):
            name = func.id
            if name in self._mod_funcs.get(mod, ()):
                return f"{mod}:{name}"
            tgt = self._from_imports.get(mod, {}).get(name)
            if tgt is not None:
                base, leaf = tgt
                if leaf in self._mod_funcs.get(base, ()):
                    return f"{base}:{leaf}"
            return None
        if isinstance(func, ast.Attribute):
            attr = _self_attr(func)
            if attr is not None:
                if cls is not None and attr in self._mod_classes.get(mod, {}).get(
                    cls.name, ()
                ):
                    return f"{mod}:{cls.name}.{attr}"
                return None
            chain = _chain(func)
            if not chain or "." not in chain:
                return None
            prefix, leaf = chain.rsplit(".", 1)
            target_mod = self._resolve_module(mod, prefix)
            if target_mod is not None and leaf in self._mod_funcs.get(
                target_mod, ()
            ):
                return f"{target_mod}:{leaf}"
        return None

    def _resolve_module(self, mod: str, prefix: str) -> Optional[str]:
        """Dotted receiver prefix -> repo module name, via this module's
        import bindings (``import a.b`` binds ``a``; dotted access walks
        down from there)."""
        head, _, rest = prefix.partition(".")
        from_tgt = self._from_imports.get(mod, {}).get(head)
        if from_tgt is not None:
            base, leaf = from_tgt
            cand = f"{base}.{leaf}" if base else leaf
            cand = f"{cand}.{rest}" if rest else cand
            return cand if cand in self._modules else None
        bound = self._imports.get(mod, {}).get(head)
        if bound is None:
            return None
        cand = bound if bound.split(".")[0] != head or bound == head else head
        cand = f"{cand}.{rest}" if rest else cand
        if cand in self._modules:
            return cand
        # ``import a.b`` bound ``a``: the chain ``a.b.f`` walks a.b.
        cand2 = f"{head}.{rest}" if rest else head
        return cand2 if cand2 in self._modules else None

    # -- class resolution (the thread-map's constructor-type layer) --

    def resolve_class(self, mod: str, func: ast.expr) -> Optional[str]:
        """``ClassName(...)``'s class as ``"module:Class"`` when it is a
        repo class visible from ``mod`` (local, ``from m import Class``,
        or ``m.Class``); None otherwise."""
        if isinstance(func, ast.Name):
            name = func.id
            if name in self._mod_classes.get(mod, {}):
                return f"{mod}:{name}"
            tgt = self._from_imports.get(mod, {}).get(name)
            if tgt is not None:
                base, leaf = tgt
                if leaf in self._mod_classes.get(base, {}):
                    return f"{base}:{leaf}"
            return None
        if isinstance(func, ast.Attribute):
            chain = _chain(func)
            if not chain or "." not in chain:
                return None
            prefix, leaf = chain.rsplit(".", 1)
            target_mod = self._resolve_module(mod, prefix)
            if target_mod is not None and leaf in self._mod_classes.get(
                target_mod, {}
            ):
                return f"{target_mod}:{leaf}"
        return None

    def class_method(self, cls_q: str, meth: str) -> Optional[str]:
        """``("module:Class", "meth")`` -> the method's qualname when the
        class declares it."""
        mod, _, cls = cls_q.partition(":")
        if meth in self._mod_classes.get(mod, {}).get(cls, ()):
            return f"{mod}:{cls}.{meth}"
        return None

    # -- derived: transitive blocking --

    def blocking_witnesses(self) -> Dict[str, List[str]]:
        """qualname -> witness chain (site strings down to a primitive) for
        every function that may block at steady state.  Waived primitives
        and phase-boundary/except-handler sites do not count."""
        if self._blocking_memo is not None:
            return self._blocking_memo
        wit: Dict[str, List[str]] = {}
        changed = True
        while changed:
            changed = False
            for q, fn in self.functions.items():
                if q in wit or not fn.resolvable:
                    continue
                w: Optional[List[str]] = None
                for b in fn.blocking:
                    if not b.exempt and not b.waived:
                        w = [f"{fn.path}:{b.line} {b.reason}"]
                        break
                if w is None:
                    for c in fn.calls:
                        if c.exempt:
                            continue
                        sub = wit.get(c.callee)
                        if sub is not None:
                            w = [
                                f"{fn.path}:{c.line} calls "
                                f"{c.callee.split(':')[-1]}"
                            ] + sub
                            break
                if w is not None:
                    wit[q] = w
                    changed = True
        self._blocking_memo = wit
        return wit

    def blocking_roots(self) -> List[str]:
        """Functions that DIRECTLY block (non-exempt, non-waived primitive)
        — the propagation roots the artifact counts."""
        return sorted(
            q for q, fn in self.functions.items()
            if fn.resolvable
            and any(not b.exempt and not b.waived for b in fn.blocking)
        )

    # -- derived: lock acquisition graph --

    def lock_closures(self) -> Dict[str, Dict[str, List[str]]]:
        """qualname -> {lock_id: witness chain of sites acquiring it},
        including locks acquired by transitive callees."""
        clo: Dict[str, Dict[str, List[str]]] = {
            q: {} for q in self.functions
        }
        for q, fn in self.functions.items():
            for a in fn.acquires:
                clo[q].setdefault(
                    a.lock, [f"{fn.path}:{a.line} acquires {a.lock}"]
                )
        changed = True
        while changed:
            changed = False
            for q, fn in self.functions.items():
                for c in fn.calls:
                    sub = clo.get(c.callee)
                    if not sub:
                        continue
                    for lock, chain in sub.items():
                        if lock not in clo[q]:
                            clo[q][lock] = [
                                f"{fn.path}:{c.line} calls "
                                f"{c.callee.split(':')[-1]}"
                            ] + chain
                            changed = True
        return clo

    def lock_edges(self) -> Dict[Tuple[str, str], List[str]]:
        """(held, acquired) -> first witness chain observed.  Direct
        acquisitions under a held lock, plus call sites whose callee's
        closure acquires locks."""
        if self._edges_memo is not None:
            return self._edges_memo
        clo = self.lock_closures()
        edges: Dict[Tuple[str, str], List[str]] = {}
        for q, fn in self.functions.items():
            for a in fn.acquires:
                for h in a.held:
                    edges.setdefault(
                        (h, a.lock),
                        [f"{fn.path}:{a.line} {q.split(':')[-1]} acquires "
                         f"{a.lock} while holding {h}"],
                    )
            for c in fn.calls:
                sub = clo.get(c.callee)
                if not sub:
                    continue
                for h in c.held:
                    for lock, chain in sub.items():
                        edges.setdefault(
                            (h, lock),
                            [f"{fn.path}:{c.line} {q.split(':')[-1]} calls "
                             f"{c.callee.split(':')[-1]} while holding {h}"]
                            + chain,
                        )
        self._edges_memo = edges
        return edges
