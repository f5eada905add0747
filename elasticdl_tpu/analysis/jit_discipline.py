"""jit-discipline (v6): compile & transfer discipline over the jit boundary.

Every perf number of record rides an unenforced contract: the jitted step
compiles ONCE per declared variant and its outputs stay on device until a
deliberate, accounted fetch.  r15 proved mask flips recompile-free and the
r11 donation story assumes stable jit identity — but nothing gated either,
and one shape drift or accidental ``np.asarray`` on a hot path quietly
halves throughput.  Three rules, in the established static-pass +
runtime-sanitizer pattern (lock-order/locksan, shared-state/racesan; the
runtime twin here is ``common/jitsan.py``):

- ``jit-shim``       raw ``jax.jit`` / ``jax.pjit`` (attribute use and
                     ``from jax import jit`` aliases) only inside
                     ``common/jax_compat.py``; every other site routes
                     through ``jax_compat.jit_compiled`` /
                     ``jit_donating`` — and those call sites must declare
                     ``name=`` (the jitsan registry, the
                     ``edl_jit_compiles_total{fn=}`` gauge label, and the
                     LINT artifact's budget table all key on it).

- ``jit-stability``  a jit created inside a per-call function body (or
                     loop) builds a FRESH compile cache on every
                     invocation — every prior compile is thrown away and
                     paid again.  Flagged shapes: the jit result invoked
                     directly (``jit_compiled(f, ...)(x)``) or through a
                     local that the same function then calls.  Clean
                     shapes: bound at module level, memoized onto
                     ``self.<attr>``, stored into a cache subscript, or
                     returned/handed out (builder pattern — the caller
                     owns the binding; the trainer's ``_structured``
                     memo is exactly this).

- ``transfer-discipline``
                     a device->host materialization — ``.item()``,
                     ``.tolist()``, ``jax.device_get``, ``np.asarray`` /
                     ``np.array``, ``int()`` / ``float()`` — applied to a
                     value flowing from a jit boundary must not be
                     reachable from a ``# hot-path`` function outside a
                     ``phases.phase(...)`` boundary.  "Flowing from a jit
                     boundary": assigned from a call to a function whose
                     ``def`` line carries ``# jit-boundary`` (or that
                     provably returns such a value — inferred as a
                     fixpoint over return statements), or from calling a
                     local bound to a jit.  Call targets resolve over the
                     v2 call graph PLUS the v5 constructor-type layer
                     (``self.trainer.train_step(...)`` edges into
                     Trainer), and materializing helpers propagate to
                     their hot callers with a witness chain, exactly like
                     ``blocking-propagation``.  Direct ``.item()`` /
                     ``device_get`` in the hot body stay
                     ``hot-path-sync`` findings too (one rule per failure
                     shape); this rule adds the dataflow- and
                     callee-chain-scoped half r7 could not express.

Blind spots (covered by the runtime twin: jitsan's per-site lowering
budget and the optional ``jax.transfer_guard`` window around worker
dispatch): values materialized through function PARAMETERS (the
jit-flow tracking is per-function lexical), dynamic dispatch, containers
of device values, and shape drift itself — the static passes prove the
binding discipline, the sanitizer proves the compile count.

Waive with ``# graftlint: allow[<rule>] <reason>`` on the finding's line;
a waived materialization does not propagate (the reason covers the call
however deep the caller sits — the blocking-propagation stance).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from elasticdl_tpu.analysis.callgraph import shared_graph
from elasticdl_tpu.analysis.core import Finding, LintPass, SourceFile, attr_chain
from elasticdl_tpu.analysis.hot_path import is_phase_context
from elasticdl_tpu.analysis.import_hygiene import _module_name
from elasticdl_tpu.analysis.thread_map import shared_thread_map

#: The one module allowed to spell raw jax.jit.
SHIM_MODULE_SUFFIX = "common/jax_compat.py"

#: Shim spellings whose call sites carry the name=/expected_variants=
#: declaration (the jitsan registry contract).
JIT_FAMILY = ("jit_compiled", "jit_donating")

_RAW_JIT_CHAINS = {"jax.jit", "jax.pjit"}

_JIT_BOUNDARY = re.compile(r"#\s*jit-boundary\b")

_TRANSFER_CASTS = {"int", "float"}
_TRANSFER_ARRAY_CHAINS = {
    "np.asarray", "numpy.asarray", "np.array", "numpy.array",
}


def _is_jit_boundary_annotated(src: SourceFile, line: int) -> bool:
    """``# jit-boundary`` on the def line or the contiguous comment-only
    block above it (the ``# hot-path`` placement convention)."""
    comment = src.comments.get(line)
    if comment is not None and _JIT_BOUNDARY.search(comment):
        return True
    cand = line - 1
    while cand in src.comment_only_lines:
        if _JIT_BOUNDARY.search(src.comments[cand]):
            return True
        cand -= 1
    return False


def _scope_nodes(fn) -> Iterable[ast.AST]:
    """Every node of ``fn``'s own body, PRUNING nested def/lambda scopes
    — deferred execution owns its own judgement (the repo-wide traversal
    stance; ast.walk would leak nested returns/calls into the enclosing
    function's model)."""
    stack: List[ast.AST] = list(fn.body)
    while stack:
        n = stack.pop()
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            continue
        yield n
        stack.extend(ast.iter_child_nodes(n))


def _jit_call_kind(node: ast.Call) -> Optional[str]:
    """``"raw"`` for jax.jit/jax.pjit spellings, ``"shim"`` for the
    jax_compat family, else None."""
    f = node.func
    chain = attr_chain(f)
    if chain in _RAW_JIT_CHAINS:
        return "raw"
    tail = chain.split(".")[-1] if chain else ""
    if tail in JIT_FAMILY or (
        isinstance(f, ast.Name) and f.id in JIT_FAMILY
    ):
        return "shim"
    # ``from jax import jit`` smuggles the raw spelling past the chain
    # check; the import itself is flagged by JitShimPass, and the bare
    # ``jit(...)`` call still counts for stability judgement.
    if isinstance(f, ast.Name) and f.id in ("jit", "pjit"):
        return "raw"
    return None


def _kwarg(node: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _param_defaults(fn) -> Dict[str, int]:
    """Int defaults of a function's parameters — the resolution table for
    a ``expected_variants=<param>`` spelling (the trainer's builders pass
    their ``variant_budget: int = 1`` through)."""
    args = fn.args
    out: Dict[str, int] = {}
    pos = args.posonlyargs + args.args
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, int):
            out[a.arg] = int(d.value)
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if isinstance(d, ast.Constant) and isinstance(d.value, int):
            out[a.arg] = int(d.value)
    return out


def declared_sites(sources: Sequence[SourceFile]) -> Dict[str, dict]:
    """Static harvest of the jit_compiled/jit_donating declarations:
    ``name`` -> {"budget": <int>, "sites": [...], "dynamic": bool}.
    A non-constant ``expected_variants`` resolves through the enclosing
    function's parameter default when the spelling is a plain parameter
    name (``expected_variants=variant_budget`` with ``variant_budget:
    int = 1`` — the trainer's builder shape; recorded with
    ``dynamic: true`` since a caller may override upward, e.g. the
    serving bucket count), and falls back to ``None`` only when truly
    unresolvable.  Stamped into the LINT artifact next to the jitsan
    runtime stats so the declared contract and the measured compile
    counts live in one place (tests/test_jitsan.py holds the two
    against each other on the live tree)."""
    out: Dict[str, dict] = {}

    def visit_calls(body_owner, defaults: Dict[str, int]) -> None:
        for node in _scope_nodes(body_owner):
            if not (
                isinstance(node, ast.Call)
                and _jit_call_kind(node) == "shim"
            ):
                continue
            name_kw = _kwarg(node, "name")
            if not (
                isinstance(name_kw, ast.Constant)
                and isinstance(name_kw.value, str)
            ):
                continue
            budget_kw = _kwarg(node, "expected_variants")
            dynamic = False
            if budget_kw is None:
                budget: Optional[int] = 1  # the wrapper's own default
            elif isinstance(budget_kw, ast.Constant) and isinstance(
                budget_kw.value, int
            ):
                budget = int(budget_kw.value)
            elif isinstance(budget_kw, ast.Name) and (
                budget_kw.id in defaults
            ):
                budget = defaults[budget_kw.id]
                dynamic = True
            else:
                budget = None
                dynamic = True
            rec = out.setdefault(
                name_kw.value, {"budget": 0, "sites": [], "dynamic": False}
            )
            rec["sites"].append(f"{src.path}:{node.lineno}")
            rec["dynamic"] = rec["dynamic"] or dynamic
            if budget is None:
                rec["budget"] = None  # unresolvable expression
            elif rec["budget"] is not None:
                rec["budget"] = max(rec["budget"], budget)

    for src in sources:
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_calls(node, _param_defaults(node))
        # Module-level binds (no enclosing parameters to resolve against).
        mod_scope = ast.Module(body=src.tree.body, type_ignores=[])
        visit_calls(mod_scope, {})
    return {k: out[k] for k in sorted(out)}


class JitShimPass(LintPass):
    name = "jit-shim"
    description = (
        "raw jax.jit/jax.pjit only inside common/jax_compat.py; "
        "jit_compiled/jit_donating call sites declare name="
    )

    def run(self, src: SourceFile) -> Iterable[Finding]:
        in_shim = src.path.replace("\\", "/").endswith(SHIM_MODULE_SUFFIX)
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if isinstance(node, ast.ImportFrom) and not in_shim:
                mod = node.module or ""
                if mod == "jax":
                    for alias in node.names:
                        if alias.name in ("jit", "pjit"):
                            findings.append(Finding(
                                self.name, src.path, node.lineno,
                                f"raw 'from jax import {alias.name}' "
                                "bypasses the compile shim — use "
                                "elasticdl_tpu.common.jax_compat."
                                "jit_compiled/jit_donating (jitsan "
                                "accounting and the declared variant "
                                "budget live there)",
                            ))
                elif mod.startswith("jax.experimental.pjit"):
                    findings.append(Finding(
                        self.name, src.path, node.lineno,
                        "raw pjit import bypasses the compile shim — use "
                        "elasticdl_tpu.common.jax_compat.jit_compiled",
                    ))
            elif isinstance(node, ast.Attribute) and not in_shim:
                chain = attr_chain(node)
                if chain in _RAW_JIT_CHAINS:
                    findings.append(Finding(
                        self.name, src.path, node.lineno,
                        f"raw {chain} bypasses the compile shim — use "
                        "elasticdl_tpu.common.jax_compat.jit_compiled/"
                        "jit_donating so the compile is named, budgeted, "
                        "and jitsan-accounted",
                    ))
            elif isinstance(node, ast.Call) and _jit_call_kind(node) == "shim":
                name_kw = _kwarg(node, "name")
                if name_kw is None:
                    findings.append(Finding(
                        self.name, src.path, node.lineno,
                        "jit_compiled/jit_donating call declares no name= "
                        "— the jitsan registry, the edl_jit_compiles_total "
                        "gauge label, and the LINT artifact's budget table "
                        "all key on it",
                    ))
        return findings


class JitStabilityPass(LintPass):
    name = "jit-stability"
    description = (
        "a jit created inside a per-call function body (or loop) and "
        "invoked there builds a fresh compile cache every invocation — "
        "bind it module-level, memoize on self.<attr>, or return it"
    )

    def run(self, src: SourceFile) -> Iterable[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(src.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._check_scope(src, node, findings)
        return findings

    def _check_scope(self, src, fn, findings: List[Finding]) -> None:
        """One function scope (nested defs are their own scopes via the
        outer ast.walk).  Module scope is exempt by construction: a
        module-level bind runs once per process."""
        jit_locals: Dict[str, int] = {}  # local name -> jit creation line
        for node in _scope_nodes(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _jit_call_kind(node.value) is not None:
                    if len(node.targets) == 1 and isinstance(
                        node.targets[0], ast.Name
                    ):
                        jit_locals[node.targets[0].id] = node.value.lineno
                    # self.<attr> / cache[key] targets: ownership escapes
                    # the call frame (memo/bucket patterns) — clean.
            elif isinstance(node, ast.Call):
                inner = node.func
                if isinstance(inner, ast.Call) and _jit_call_kind(inner):
                    findings.append(Finding(
                        self.name, src.path, inner.lineno,
                        f"jit created and invoked in one expression inside "
                        f"{fn.name}(): every call of {fn.name} pays a "
                        "fresh trace+compile — bind the jit module-level, "
                        "memoize it on self.<attr>, or waive with a reason",
                    ))
        # Second sweep: locals bound to a jit and then CALLED in this same
        # scope — the fresh-cache-per-invocation shape one step removed.
        if not jit_locals:
            return
        reported: Set[str] = set()
        for node in _scope_nodes(fn):
            if (
                isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in jit_locals
                and node.func.id not in reported
            ):
                reported.add(node.func.id)
                findings.append(Finding(
                    self.name, src.path, jit_locals[node.func.id],
                    f"jit bound to local {node.func.id!r} and invoked "
                    f"inside {fn.name}() (line {node.lineno}): every call "
                    f"of {fn.name} rebuilds the compile cache — bind it "
                    "module-level, memoize it on self.<attr>, or waive "
                    "with a reason",
                ))


class _FnTransferModel:
    """Per-function raw material for transfer-discipline: jit-flow locals,
    materialization sites (with exemption context), and whether the
    function's return value is jit-flow."""

    __slots__ = ("qualname", "path", "transfers", "returns_jit_flow",
                 "boundary_return_callees")

    def __init__(self, qualname: str, path: str):
        self.qualname = qualname
        self.path = path
        #: (line, reason) — non-exempt, non-waived materializations only.
        self.transfers: List[Tuple[int, str]] = []
        self.returns_jit_flow = False
        #: resolved callees whose boundary-ness makes this fn a boundary.
        self.boundary_return_callees: Set[str] = set()


class TransferDisciplinePass(LintPass):
    name = "transfer-discipline"
    description = (
        "device->host materializations of jit-boundary values must not be "
        "reachable from '# hot-path' functions outside a phases.phase(...) "
        "boundary (resolved over the v2/v5 call graph)"
    )

    def run_project(self, files: Sequence[SourceFile]) -> Iterable[Finding]:
        graph = shared_graph(files)
        attr_types = shared_thread_map(files).attr_types()
        models: Dict[str, _FnTransferModel] = {}
        # Annotation pre-scan: the declared '# jit-boundary' set must be
        # complete before any jit-flow judgement (extraction order across
        # files must not matter).
        boundary: Set[str] = {
            q for q, fn in graph.functions.items()
            if fn.resolvable
            and _is_jit_boundary_annotated(graph.sources[fn.path], fn.line)
        }

        for path, src in graph.sources.items():
            mod = _module_name(path) or path
            for node in src.tree.body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._extract(
                        graph, attr_types, src, mod, None, node,
                        f"{mod}:{node.name}", models, boundary,
                    )
                elif isinstance(node, ast.ClassDef):
                    for meth in node.body:
                        if isinstance(
                            meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                        ):
                            self._extract(
                                graph, attr_types, src, mod, node, meth,
                                f"{mod}:{node.name}.{meth.name}",
                                models, boundary,
                            )

        # Boundary inference fixpoint: a function returning a jit-flow
        # value, or a call into a boundary function, is itself a boundary
        # (Trainer.run_predict_step returns self.predict_step(...)).
        changed = True
        while changed:
            changed = False
            for q, m in models.items():
                if q in boundary:
                    continue
                if m.returns_jit_flow or (
                    m.boundary_return_callees & boundary
                ):
                    boundary.add(q)
                    changed = True
        # Second extraction pass: jit-flow depends on the final boundary
        # set, so transfers are re-derived once it settles (two passes
        # suffice — boundary-ness never depends on transfer sites).
        models = {}
        for path, src in graph.sources.items():
            mod = _module_name(path) or path
            for node in src.tree.body:
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    self._extract(
                        graph, attr_types, src, mod, None, node,
                        f"{mod}:{node.name}", models, set(boundary),
                        final_boundary=boundary,
                    )
                elif isinstance(node, ast.ClassDef):
                    for meth in node.body:
                        if isinstance(
                            meth, (ast.FunctionDef, ast.AsyncFunctionDef)
                        ):
                            self._extract(
                                graph, attr_types, src, mod, node, meth,
                                f"{mod}:{node.name}.{meth.name}",
                                models, set(boundary),
                                final_boundary=boundary,
                            )

        # Witness fixpoint over the conservative v2 call edges, the
        # blocking-propagation shape: wit[q] = chain down to the
        # materializing primitive.
        wit: Dict[str, List[str]] = {}
        changed = True
        while changed:
            changed = False
            for q, fn in graph.functions.items():
                if q in wit or not fn.resolvable:
                    continue
                m = models.get(q)
                w: Optional[List[str]] = None
                if m is not None and m.transfers:
                    line, reason = m.transfers[0]
                    w = [f"{fn.path}:{line} {reason}"]
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

        findings: List[Finding] = []
        for q, fn in graph.functions.items():
            if not fn.hot_path:
                continue
            short = q.split(":")[-1]
            m = models.get(q)
            if m is not None:
                for line, reason in m.transfers:
                    findings.append(Finding(
                        self.name, fn.path, line,
                        f"hot-path {short}: {reason} — keep step outputs "
                        "on device, move the fetch behind a "
                        "phases.phase(...) boundary, or waive with a "
                        "reason",
                    ))
            for c in fn.calls:
                if c.exempt:
                    continue
                chain = wit.get(c.callee)
                if chain is None:
                    continue
                findings.append(Finding(
                    self.name, fn.path, c.line,
                    f"hot-path {short} calls {c.callee.split(':')[-1]}, "
                    "whose callee chain materializes a jit-boundary value "
                    "on the host: " + " -> ".join(chain)
                    + " — move the fetch behind a phases.phase(...) "
                    "boundary, off the hot path, or waive with a reason",
                ))
        return findings

    # -- per-function extraction --

    def _extract(
        self, graph, attr_types, src, mod, cls, fn, qualname, models,
        boundary: Set[str], final_boundary: Optional[Set[str]] = None,
    ) -> None:
        m = _FnTransferModel(qualname, src.path)
        models[qualname] = m
        if _is_jit_boundary_annotated(src, fn.lineno):
            boundary.add(qualname)
            if final_boundary is not None:
                final_boundary.add(qualname)
        resolved_boundary = (
            final_boundary if final_boundary is not None else boundary
        )

        # Lexically hoisted jit-flow locals (order-insensitive, the
        # thread-map local_types stance): names assigned from a call to a
        # boundary function or from invoking a jit-bound local.
        jit_bound: Set[str] = set()
        jit_flow: Set[str] = set()
        for _ in range(2):  # two sweeps: step = jit(...); out = step(x)
            for n in _scope_nodes(fn):
                if isinstance(n, ast.Assign) and isinstance(n.value, ast.Call):
                    names = self._target_names(n.targets)
                    if names:
                        if _jit_call_kind(n.value) is not None:
                            jit_bound |= names
                        elif self._call_is_boundary(
                            graph, attr_types, mod, cls, n.value,
                            resolved_boundary, jit_bound,
                        ):
                            jit_flow |= names

        # Return judgement (for the inference fixpoint).
        for n in _scope_nodes(fn):
            if not isinstance(n, ast.Return) or n.value is None:
                continue
            if any(
                isinstance(s, ast.Name) and s.id in jit_flow
                for s in ast.walk(n.value)
            ):
                m.returns_jit_flow = True
            if isinstance(n.value, ast.Call):
                callee = self._resolve(
                    graph, attr_types, mod, cls, n.value.func
                )
                if callee is not None:
                    m.boundary_return_callees.add(callee)
                if self._call_is_boundary(
                    graph, attr_types, mod, cls, n.value,
                    resolved_boundary, jit_bound,
                ):
                    m.returns_jit_flow = True

        # Materialization sites, with the blocking-style exemptions.
        self._walk_transfers(src, fn.body, m, jit_flow, exempt=False)

    @staticmethod
    def _target_names(targets) -> Set[str]:
        names: Set[str] = set()
        for t in targets:
            if isinstance(t, ast.Name):
                names.add(t.id)
            elif isinstance(t, (ast.Tuple, ast.List)):
                for el in t.elts:
                    if isinstance(el, ast.Name):
                        names.add(el.id)
        return names

    def _call_is_boundary(
        self, graph, attr_types, mod, cls, call: ast.Call,
        boundary: Set[str], jit_bound: Set[str],
    ) -> bool:
        f = call.func
        if isinstance(f, ast.Name) and f.id in jit_bound:
            return True  # out = step(x) where step = jit_compiled(...)
        callee = self._resolve(graph, attr_types, mod, cls, f)
        return callee is not None and callee in boundary

    def _resolve(self, graph, attr_types, mod, cls, f) -> Optional[str]:
        """v2 resolution plus the v5 typed-receiver layer
        (``self.<attr>.<meth>`` through constructor types)."""
        callee = graph._resolve_call(mod, cls, f)
        if callee is not None:
            return callee
        if (
            isinstance(f, ast.Attribute)
            and isinstance(f.value, ast.Attribute)
            and isinstance(f.value.value, ast.Name)
            and f.value.value.id == "self"
            and cls is not None
        ):
            cls_q = attr_types.get(f"{mod}:{cls.name}", {}).get(f.value.attr)
            if cls_q is not None:
                return graph.class_method(cls_q, f.attr)
        return None

    def _walk_transfers(self, src, body, m, jit_flow, exempt: bool) -> None:
        for node in body:
            self._visit_transfer(src, node, m, jit_flow, exempt)

    def _visit_transfer(self, src, node, m, jit_flow, exempt: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return  # deferred execution: its own scope, its own judgement
        if isinstance(node, ast.With):
            new_exempt = exempt or any(
                is_phase_context(i.context_expr) for i in node.items
            )
            self._walk_transfers(src, node.body, m, jit_flow, new_exempt)
            return
        if isinstance(node, ast.Try):
            self._walk_transfers(src, node.body, m, jit_flow, exempt)
            self._walk_transfers(src, node.orelse, m, jit_flow, exempt)
            self._walk_transfers(src, node.finalbody, m, jit_flow, exempt)
            for h in node.handlers:
                self._walk_transfers(src, h.body, m, jit_flow, True)
            return
        if isinstance(node, ast.Call):
            reason = self._transfer_reason(node, jit_flow)
            if reason is not None and not exempt and not self._waived(
                src, node.lineno
            ):
                m.transfers.append((node.lineno, reason))
        for child in ast.iter_child_nodes(node):
            self._visit_transfer(src, child, m, jit_flow, exempt)

    @staticmethod
    def _refs_flow(node: ast.AST, jit_flow) -> bool:
        return any(
            isinstance(s, ast.Name) and s.id in jit_flow
            for s in ast.walk(node)
        )

    def _transfer_reason(self, node: ast.Call, jit_flow) -> Optional[str]:
        f = node.func
        chain = attr_chain(f)
        if isinstance(f, ast.Attribute):
            if f.attr in ("item", "tolist") and not node.args:
                if self._refs_flow(f.value, jit_flow):
                    return (
                        f".{f.attr}() materializes a jit-boundary value "
                        "on the host (a blocking device->host transfer)"
                    )
            if chain == "jax.device_get" and any(
                self._refs_flow(a, jit_flow) for a in node.args
            ):
                return (
                    "jax.device_get of a jit-boundary value blocks on "
                    "the device->host transfer"
                )
            if chain in _TRANSFER_ARRAY_CHAINS and any(
                self._refs_flow(a, jit_flow) for a in node.args
            ):
                return (
                    f"{chain} over a jit-boundary value forces a "
                    "device->host copy"
                )
        elif isinstance(f, ast.Name) and f.id in _TRANSFER_CASTS:
            if any(self._refs_flow(a, jit_flow) for a in node.args):
                return (
                    f"{f.id}() over a jit-boundary value is a blocking "
                    "device read"
                )
        return None

    @staticmethod
    def _waived(src: SourceFile, line: int) -> bool:
        """A transfer-discipline waiver on the primitive's line stops it
        from propagating to callers (the blocking-propagation stance) —
        and is recorded as used so stale-waiver stays honest."""
        for cand in (line, line - 1):
            w = src.waivers.get(cand)
            if w is not None and w.rule == "transfer-discipline" and (
                cand == line or cand in src.comment_only_lines
            ):
                src.used_waiver_lines.add(cand)
                return True
        return False
