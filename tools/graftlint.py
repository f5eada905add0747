"""graftlint CLI — the repo's static-analysis gate.

Usage:
    python tools/graftlint.py [paths...]         # default: elasticdl_tpu tools
    python tools/graftlint.py --changed          # git-diff-scoped fast mode
    python tools/graftlint.py --json             # findings + waiver inventory
    python tools/graftlint.py --callgraph        # dump the v2 call/lock graph
    python tools/graftlint.py --threadmap        # dump the v5 role map
    python tools/graftlint.py --durables         # dump the v7 durable inventory
    python tools/graftlint.py --wire             # dump the v8 wire inventory
    python tools/graftlint.py --update-wire-lock # regenerate the schema lock
    python tools/graftlint.py --list-rules

Exit code 0 = clean, 1 = findings, 2 = usage/internal error.  Pure stdlib
and jax-free by design (the import-hygiene pass guards this file too): the
pre-commit path must cost milliseconds, never a backend init.

``--changed`` scopes reporting to files changed vs HEAD (plus untracked)
AND their module-level DEPENDENTS: the project-wide passes (import-hygiene,
lock-order, blocking-propagation) judge whole-graph properties, so a change
to a helper module must re-lint every module that imports it.  Install as a
pre-commit hook with tools/precommit.sh (see docs/static_analysis.md).

Waiver syntax (inline, same line as the finding or the comment-only line
above): ``# graftlint: allow[<rule>] <reason>`` — reason mandatory; a
waiver that suppresses nothing is itself a finding (``stale-waiver``); see
docs/static_analysis.md for the invariant catalogue.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

DEFAULT_PATHS = ("elasticdl_tpu", "tools")


def _changed_files(repo: str) -> Optional[List[str]]:
    """Repo-relative .py files touched vs HEAD (worktree + index) plus
    untracked — the pre-commit scope.  None when git itself failed: the
    caller must fail LOUD (exit 2), because 'git broke' reported as
    'nothing changed' would let a violating commit through the gate."""
    out: List[str] = []
    for args in (
        ["git", "diff", "--name-only", "HEAD", "--"],
        ["git", "ls-files", "--others", "--exclude-standard"],
    ):
        try:
            r = subprocess.run(
                args, cwd=repo, capture_output=True, text=True, timeout=20
            )
        except Exception:
            return None
        if r.returncode != 0:
            return None
        out.extend(line.strip() for line in r.stdout.splitlines())
    return sorted({p for p in out if p.endswith(".py")})


def _callgraph_dump(sources) -> dict:
    """The v2 interprocedural model, machine-readable: function/edge
    counts, blocking roots, and the lock graph with its annotations."""
    from elasticdl_tpu.analysis.callgraph import shared_graph

    g = shared_graph(sources)
    edges = g.lock_edges()
    return {
        "functions": sum(1 for f in g.functions.values() if f.resolvable),
        "call_edges": sum(
            len(f.calls) for f in g.functions.values() if f.resolvable
        ),
        "hot_path_functions": sorted(
            q for q, f in g.functions.items() if f.hot_path
        ),
        "blocking_roots": g.blocking_roots(),
        "locks": {
            lock_id: {
                "declared_at": f"{d.path}:{d.line}",
                "locksan": d.is_locksan,
                "leaf": d.rt_leaf,
                "before": list(d.rt_before),
                "reentrant": d.reentrant,
            }
            for lock_id, d in sorted(g.locks.items())
        },
        "lock_edges": [
            {"held": a, "acquired": b, "witness": w}
            for (a, b), w in sorted(edges.items())
        ],
    }


def _threadmap_dump(sources) -> dict:
    """The v5 role model, machine-readable: role -> functions plus the
    inferred entry points (``--threadmap``, mirroring ``--callgraph``)."""
    from elasticdl_tpu.analysis.thread_map import shared_thread_map

    return shared_thread_map(sources).dump()


def _durables_dump(sources) -> dict:
    from elasticdl_tpu.analysis.durability import durables_inventory

    return durables_inventory(sources)


def _wire_dump(sources) -> dict:
    from elasticdl_tpu.analysis.wire_discipline import wire_inventory

    return wire_inventory(sources)


#: The dump modes: each a VIEW of the sources one analysis loaded, so a
#: caller that holds ``run_lint_full``'s result (tests/test_graftlint.py
#: analyses the tree once a process) reads any of them without another.
VIEWS = {
    "callgraph": _callgraph_dump,
    "threadmap": _threadmap_dump,
    "durables": _durables_dump,
    "wire": _wire_dump,
}


def findings_json(findings, waivers) -> dict:
    """What ``--json`` prints: the findings and the waiver inventory."""
    return {"findings": [f.__dict__ for f in findings], "waivers": waivers}


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="graftlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "paths", nargs="*", default=list(DEFAULT_PATHS),
        help="files/directories to lint (default: elasticdl_tpu tools)",
    )
    parser.add_argument(
        "--changed", action="store_true",
        help="lint only files changed vs HEAD (plus untracked) under the "
        "given paths, PLUS modules that import them — pre-commit fast "
        "mode; project-wide passes still see the full file set",
    )
    parser.add_argument(
        "--json", action="store_true", dest="as_json",
        help="emit {findings: [...], waivers: [...]} as JSON",
    )
    parser.add_argument(
        "--callgraph", action="store_true",
        help="dump the interprocedural model (functions, blocking roots, "
        "lock graph) as JSON and exit",
    )
    parser.add_argument(
        "--threadmap", action="store_true",
        help="dump the v5 thread-role map (role -> functions, entry "
        "points) as JSON and exit",
    )
    parser.add_argument(
        "--durables", action="store_true",
        help="dump the v7 durable-file inventory (constant -> writers -> "
        "recovery readers) as JSON and exit",
    )
    parser.add_argument(
        "--wire", action="store_true",
        help="dump the v8 wire inventory (method -> request/response "
        "schema -> sender/receiver sites) as JSON and exit",
    )
    parser.add_argument(
        "--update-wire-lock", action="store_true",
        help="regenerate artifacts/wire_schema.lock.json from the current "
        "MessageSchema tables (the wire-evolution baseline) and exit — "
        "run it in the SAME diff as any schema change",
    )
    parser.add_argument(
        "--list-rules", action="store_true", help="list rules and exit"
    )
    args = parser.parse_args(argv)

    from elasticdl_tpu.analysis import all_passes, collect_waivers
    from elasticdl_tpu.analysis.core import iter_file_paths, run_lint_full

    passes = all_passes()
    if args.list_rules:
        for p in passes:
            print(f"{p.name:20s} {p.description}")
        print(f"{'stale-waiver':20s} a waiver that suppresses no finding is "
              "itself a finding")
        print(f"{'waiver-syntax':20s} waivers must be "
              "'# graftlint: allow[<rule>] <reason>' with a known rule")
        return 0

    # Resolve paths relative to the repo root so display paths (and the
    # import-hygiene module names derived from them) are stable no matter
    # where the tool is invoked from.
    roots = [
        p if os.path.isabs(p) else os.path.join(_REPO_ROOT, p)
        for p in args.paths
    ]
    missing = [p for p in roots if not os.path.exists(p)]
    if missing:
        print(f"graftlint: no such path: {', '.join(missing)}", file=sys.stderr)
        return 2

    all_files = iter_file_paths(roots)
    only_paths = None
    preloaded = None
    n_changed = n_dependents = 0
    if args.changed:
        changed = _changed_files(_REPO_ROOT)
        if changed is None:
            print(
                "graftlint: --changed could not read the git state; "
                "refusing to report a clean pass (run without --changed)",
                file=sys.stderr,
            )
            return 2
        changed_set = set(changed)
        only_paths = {
            os.path.relpath(fp, _REPO_ROOT)
            for fp in all_files
            if os.path.relpath(fp, _REPO_ROOT) in changed_set
        }
        n_changed = len(only_paths)
        # Project-wide passes judge whole-graph properties: re-lint every
        # module that imports a changed one, or a helper edit could break
        # an unchanged root silently (import-hygiene chains, lock-order
        # edges, blocking propagation — and since v5, thread-role
        # propagation and shared-state judgements, whose typed call edges
        # ride the same import graph — all cross module boundaries).
        from elasticdl_tpu.analysis.core import load_sources
        from elasticdl_tpu.analysis.import_hygiene import module_dependents

        preloaded = load_sources(all_files, rel_to=_REPO_ROOT)
        deps = module_dependents(preloaded[0], only_paths)
        n_dependents = len(deps - only_paths)
        only_paths |= deps

    if args.update_wire_lock:
        # A pure regenerator: findings must not block it — the whole point
        # is to clear a wire-evolution finding in the same diff.
        from elasticdl_tpu.analysis.core import load_sources
        from elasticdl_tpu.analysis.wire_discipline import (
            WIRE_LOCK_PATH, wire_fingerprint,
        )
        from elasticdl_tpu.common import durable

        srcs = (preloaded or load_sources(all_files, rel_to=_REPO_ROOT))[0]
        lock_path = os.path.join(_REPO_ROOT, WIRE_LOCK_PATH)
        durable.atomic_publish_json(
            lock_path, wire_fingerprint(srcs), indent=1
        )
        print(f"wire-schema lock written to {lock_path}", file=sys.stderr)
        return 0

    findings, sources = run_lint_full(
        roots, passes, rel_to=_REPO_ROOT, only_paths=only_paths,
        preloaded=preloaded,
    )
    waivers = collect_waivers(sources, only_paths=only_paths)

    view = next((name for name in VIEWS if getattr(args, name)), None)
    if view is not None:
        # Findings still gate the exit code — render them (stderr, so the
        # stdout JSON stays parseable) or a failing dump is undiagnosable.
        for f in findings:
            print(f.render(), file=sys.stderr)
        print(json.dumps(VIEWS[view](sources), indent=1, sort_keys=True))
        return 1 if findings else 0

    if args.as_json:
        print(json.dumps(findings_json(findings, waivers), indent=1, sort_keys=True))
    else:
        for f in findings:
            print(f.render())
        scope = (
            f"{n_changed} changed (+{n_dependents} dependent)"
            if only_paths is not None else str(len(all_files))
        )
        print(
            f"graftlint: {len(findings)} finding(s) across {scope} file(s)",
            file=sys.stderr,
        )

    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
