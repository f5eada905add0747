"""import-hygiene: control-plane modules stay jax-free at import time.

The master, fleet drivers, and test harness processes deliberately never
import jax: a jax import can open the TPU plugin and fight for the chip,
and it was about half of the relaunch path on the CPU harness
(docs/perf.md).  r6 hoisted ``free_port`` into the jax-free
``common/platform.py`` for exactly this reason; this pass locks the
property in *transitively*: for each root module below, walk module-level
imports (function-local imports are deferred by definition and do not
count) across the repo's own modules — importing a module also executes
its ancestor packages' ``__init__`` — and flag any path that reaches a
top-level ``import jax``.

The finding is reported at the root's offending import line with the full
chain, so the fix site is obvious.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from elasticdl_tpu.analysis.core import Finding, LintPass, SourceFile

#: Modules that must import without pulling jax into the process.  Keyed by
#: dotted module name (derived from repo-relative paths).
DEFAULT_JAX_FREE_ROOTS = (
    "elasticdl_tpu.common.platform",
    "elasticdl_tpu.common.config",
    "elasticdl_tpu.common.log_utils",
    "elasticdl_tpu.common.metrics",
    "elasticdl_tpu.common.rpc",
    # r13: the fault injector rides in the master control plane (rpc.py
    # imports it) and in the jax-free bench tools — its own root keeps the
    # contract explicit even if the rpc edge ever moves.
    "elasticdl_tpu.chaos.inject",
    "elasticdl_tpu.master.main",
    "elasticdl_tpu.master.servicer",
    "elasticdl_tpu.master.pod_manager",
    "elasticdl_tpu.master.task_dispatcher",
    "elasticdl_tpu.master.rendezvous",
    "elasticdl_tpu.master.evaluation_service",
    "elasticdl_tpu.analysis",
    "tools.artifact",
    "tools.graftlint",
)

_BANNED_TOP = "jax"

#: common/platform.py helpers that import jax INSIDE their body: a deferred
#: import the graph walk cannot see — unless the module CALLS one at module
#: level, which executes the import right there.  (This is exactly how
#: master/main.py once leaked jax into the control plane: a module-level
#: platform-helper call, found by the runtime twin test.)
JAX_IMPORTING_CALLS = frozenset(
    {
        "enable_compile_cache", "compile_cache_stats", "device_summary",
        "count_compiles", "device_peak_bytes", "device_bytes_in_use",
    }
)


def _module_name(path: str) -> Optional[str]:
    """Repo-relative ``a/b/c.py`` -> ``a.b.c``; ``a/b/__init__.py`` ->
    ``a.b``.  Absolute/outside paths return None."""
    p = path.replace("\\", "/")
    if not p.endswith(".py") or p.startswith("/"):
        return None
    parts = p[:-3].split("/")
    if parts[-1] == "__init__":
        parts = parts[:-1]
    if not parts or any(not seg.isidentifier() for seg in parts):
        return None
    return ".".join(parts)


def _top_level_imports(tree: ast.Module) -> List[Tuple[str, int]]:
    """(dotted module, line) pairs imported when the module is imported:
    module-body imports, including those under top-level ``if``/``try``
    (conditional top-level imports still execute at import time on some
    path, so they count).  A module-level CALL to a known jax-importing
    helper (``JAX_IMPORTING_CALLS``) records a direct jax edge."""
    out: List[Tuple[str, int]] = []

    def scan_calls(node):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                f = sub.func
                name = (
                    f.id if isinstance(f, ast.Name)
                    else f.attr if isinstance(f, ast.Attribute)
                    else ""
                )
                if name in JAX_IMPORTING_CALLS:
                    out.append((_BANNED_TOP, sub.lineno))

    def visit(body):
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # deferred: bodies run later, not at import
            if isinstance(node, ast.ClassDef):
                visit(node.body)  # class bodies DO execute at import
                continue
            if isinstance(node, (ast.Expr, ast.Assign, ast.AnnAssign)):
                scan_calls(node)
            if isinstance(node, ast.Import):
                for alias in node.names:
                    out.append((alias.name, node.lineno))
            elif isinstance(node, ast.ImportFrom):
                if node.level:  # relative import — not used in this repo
                    continue
                mod = node.module or ""
                if mod:
                    out.append((mod, node.lineno))
                    for alias in node.names:
                        # ``from pkg import submodule`` imports pkg.submodule
                        # when it is a module; recorded speculatively — the
                        # graph only keeps edges that resolve to real files.
                        out.append((f"{mod}.{alias.name}", node.lineno))
            elif isinstance(node, ast.If):
                visit(node.body)
                visit(node.orelse)
            elif isinstance(node, ast.Try):
                visit(node.body)
                for h in node.handlers:
                    visit(h.body)
                visit(node.orelse)
                visit(node.finalbody)
            elif isinstance(node, (ast.With, ast.For, ast.While)):
                # Any compound statement at module level runs at import
                # time — a loop body can smuggle an import just as an
                # if-branch can.
                visit(node.body)
                visit(getattr(node, "orelse", []) or [])

    visit(tree.body)
    return out


def module_dependents(
    files: Sequence[SourceFile], changed_paths: set
) -> set:
    """Display paths of modules that (transitively) import any CHANGED
    module at module level — the re-lint scope ``--changed`` adds so the
    project-wide passes (import-hygiene, lock-order) judge every root a
    change can affect, not just the changed files themselves.  Importing
    ``a.b.c`` executes ``a`` and ``a.b`` too, so a changed package
    ``__init__`` pulls in every importer underneath it."""
    mod_path: Dict[str, str] = {}
    for src in files:
        name = _module_name(src.path)
        if name is not None:
            mod_path[name] = src.path
    rev: Dict[str, set] = {}
    for src in files:
        name = _module_name(src.path)
        if name is None:
            continue
        for target, _line in _top_level_imports(src.tree):
            parts = target.split(".")
            for i in range(1, len(parts) + 1):
                cand = ".".join(parts[:i])
                if cand in mod_path:
                    rev.setdefault(cand, set()).add(name)
    changed_mods = [m for m, p in mod_path.items() if p in changed_paths]
    seen = set(changed_mods)
    queue = list(changed_mods)
    while queue:
        cur = queue.pop()
        for dep in rev.get(cur, ()):
            if dep not in seen:
                seen.add(dep)
                queue.append(dep)
    return {mod_path[m] for m in seen}


class ImportHygienePass(LintPass):
    name = "import-hygiene"
    description = (
        "designated control-plane modules must not transitively import jax "
        "at module level"
    )

    def __init__(self, roots: Sequence[str] = DEFAULT_JAX_FREE_ROOTS):
        self.roots = tuple(roots)

    def run_project(self, files: Sequence[SourceFile]) -> Iterable[Finding]:
        modules: Dict[str, SourceFile] = {}
        for src in files:
            name = _module_name(src.path)
            if name is not None:
                modules[name] = src
        imports: Dict[str, List[Tuple[str, int]]] = {
            name: _top_level_imports(src.tree)
            for name, src in modules.items()
        }
        findings: List[Finding] = []
        for root in self.roots:
            if root not in modules:
                continue
            chain = self._find_jax_chain(root, modules, imports)
            if chain is not None:
                path_str, line = chain
                findings.append(Finding(
                    self.name, modules[root].path, line,
                    f"{root} must stay jax-free but reaches a module-level "
                    f"'import jax' via: {path_str} — defer the import into "
                    "the function that needs it",
                ))
        return findings

    def _ancestors(self, name: str) -> List[str]:
        parts = name.split(".")
        return [".".join(parts[:i]) for i in range(1, len(parts))]

    def _find_jax_chain(self, root, modules, imports):
        """BFS from ``root``; returns (chain string, root's offending import
        line) on the first path to jax, else None."""
        seen = set()
        # queue entries: (module, chain-so-far, root_line)
        queue: List[Tuple[str, List[str], Optional[int]]] = [(root, [root], None)]
        while queue:
            mod, chain, root_line = queue.pop(0)
            if mod in seen:
                continue
            seen.add(mod)
            for target, line in imports.get(mod, ()):
                at_root = mod == root
                eff_line = line if at_root else root_line
                if target == _BANNED_TOP or target.startswith(_BANNED_TOP + "."):
                    return (
                        " -> ".join(chain + ["jax"]),
                        eff_line if eff_line is not None else 1,
                    )
                # An import of a.b.c executes packages a and a.b too.
                for cand in self._ancestors(target) + [target]:
                    if cand in modules and cand not in seen:
                        queue.append((cand, chain + [cand], eff_line))
        return None
