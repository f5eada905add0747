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
    python tools/graftlint.py --artifact [PATH]  # stamp LINT artifact
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
from collections import Counter
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

DEFAULT_PATHS = ("elasticdl_tpu", "tools")
ARTIFACT_NAME = "LINT_r22.json"

#: jitsan runtime stats (common/jitsan.py dump, GRAFT_JITSAN_DUMP) merged
#: into the artifact when present: the static tool stays jax-free, so the
#: measured compile counts come from a jitsan-armed run's dump file.
JITSAN_STATS_DEFAULT = os.path.join("artifacts", "jitsan_stats.json")

#: crashsan matrix summary (tools/crashsan_matrix.py) merged into the
#: artifact when present — same stance as the jitsan dump: the static tool
#: proves the write routing, the matrix proves the crash states recover.
CRASHSAN_MATRIX_DEFAULT = os.path.join("artifacts", "crashsan_matrix.json")

#: version-skew roundtrip verdict (tools/wire_skew.py) merged into the
#: artifact when present — same stance again: the static wire rules prove
#: the field-access grammar, the skew run proves a v1-masked worker
#: completes a real gRPC job against a current master with zero wire
#: violations and zero double-trains.
WIRE_SKEW_DEFAULT = os.path.join("artifacts", "wire_skew.json")


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
        "--artifact", nargs="?", const="", default=None, metavar="PATH",
        help="write a LINT artifact (findings + per-rule counts + waiver "
        "inventory + lock-graph/blocking-root stats + code_rev) via "
        f"tools/artifact.py; optional explicit path, else "
        f"artifacts/{ARTIFACT_NAME} (env override LINT_OUT)",
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

    if args.callgraph or args.threadmap or args.durables or args.wire:
        # Findings still gate the exit code — render them (stderr, so the
        # stdout JSON stays parseable) or a failing dump is undiagnosable.
        for f in findings:
            print(f.render(), file=sys.stderr)
        if args.callgraph:
            dump = _callgraph_dump(sources)
        elif args.threadmap:
            dump = _threadmap_dump(sources)
        elif args.wire:
            from elasticdl_tpu.analysis.wire_discipline import wire_inventory

            dump = wire_inventory(sources)
        else:
            from elasticdl_tpu.analysis.durability import durables_inventory

            dump = durables_inventory(sources)
        print(json.dumps(dump, indent=1, sort_keys=True))
        return 1 if findings else 0

    if args.as_json:
        print(json.dumps(
            {
                "findings": [f.__dict__ for f in findings],
                "waivers": waivers,
            },
            indent=1, sort_keys=True,
        ))
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

    if args.artifact is not None:
        from elasticdl_tpu.analysis.jit_discipline import declared_sites
        from tools.artifact import code_rev, write_artifact

        by_rule = Counter(f.rule for f in findings)
        waivers_by_rule = Counter(w["rule"] for w in waivers)
        cg = _callgraph_dump(sources)
        tm = _threadmap_dump(sources)
        # v6 jitsan section: the statically declared name/budget table,
        # plus the runtime lowering counts when a jitsan-armed run left a
        # dump (env JITSAN_STATS overrides the default path).  The
        # budget itself is held live by tests/test_jitsan.py: a compile
        # count past its declared budget fails there.
        stats_path = os.environ.get(
            "JITSAN_STATS", os.path.join(_REPO_ROOT, JITSAN_STATS_DEFAULT)
        )
        jitsan_runtime = None
        jitsan_meta: dict = {}
        if os.path.exists(stats_path):
            try:
                with open(stats_path, encoding="utf-8") as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    meta = loaded.pop("_meta", None)
                    jitsan_runtime = loaded
                    if isinstance(meta, dict):
                        jitsan_meta = dict(meta)
            except (OSError, ValueError):
                pass  # a torn dump must not fail the lint artifact
        if jitsan_runtime is not None:
            # Staleness flag: a dump written before the last CODE commit
            # measured different code — stamp the mismatch rather than
            # silently certifying old counts as this revision's (the
            # consumer decides; the honest default is to re-run the
            # armed suite with GRAFT_JITSAN_DUMP and re-stamp).  The
            # reference excludes artifacts/-only commits: the stamp
            # workflow (commit code, refresh dump, commit artifacts)
            # must not mark its own dump stale — committing artifacts
            # changes no measured code.
            dumped_s = jitsan_meta.get("utc_s") or os.path.getmtime(stats_path)
            try:
                r = subprocess.run(
                    ["git", "log", "-1", "--format=%ct", "--",
                     ".", ":(exclude)artifacts"],
                    cwd=_REPO_ROOT, capture_output=True, text=True,
                    timeout=10,
                )
                code_s = int(r.stdout.strip()) if r.returncode == 0 else None
            except Exception:
                code_s = None
            jitsan_meta["stale_vs_code"] = (
                bool(code_s is not None and dumped_s < code_s)
            )
        # v7 crashsan section: the matrix driver's summary (crash points
        # injected / recovered / contract class per scenario) when a run
        # left one (env CRASHSAN_MATRIX overrides the default path).
        # tests/test_crashsan.py holds unrecovered at zero, in-process.
        matrix_path = os.environ.get(
            "CRASHSAN_MATRIX",
            os.path.join(_REPO_ROOT, CRASHSAN_MATRIX_DEFAULT),
        )
        crashsan_summary = None
        if os.path.exists(matrix_path):
            try:
                with open(matrix_path, encoding="utf-8") as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    crashsan_summary = loaded.get("summary", loaded)
            except (OSError, ValueError):
                pass  # a torn matrix file must not fail the lint artifact
        # v8 wire section: the static inventory (methods, schemas,
        # resolved sender/receiver sites) plus the version-skew roundtrip
        # verdict when a tools/wire_skew.py run left one (env WIRE_SKEW
        # overrides the default path).  tests/test_wiresan.py's
        # run_skew holds unknown fields at zero, and the repo-clean run
        # in tests/test_graftlint.py the finding counts.
        from elasticdl_tpu.analysis.wire_discipline import wire_inventory

        skew_path = os.environ.get(
            "WIRE_SKEW", os.path.join(_REPO_ROOT, WIRE_SKEW_DEFAULT)
        )
        skew_verdict = None
        if os.path.exists(skew_path):
            try:
                with open(skew_path, encoding="utf-8") as f:
                    loaded = json.load(f)
                if isinstance(loaded, dict):
                    skew_verdict = loaded
            except (OSError, ValueError):
                pass  # a torn skew dump must not fail the lint artifact
        wire_inv = wire_inventory(sources)
        unknown_fields = (
            (skew_verdict.get("wiresan") or {}).get("unknown_fields") or {}
            if skew_verdict else {}
        )
        from elasticdl_tpu.analysis.durability import durables_inventory

        write_artifact(
            {
                # A stamp for a reader of the tree; what holds the count
                # at zero is tests/test_graftlint.py's repo-clean run.
                "metric": "lint_findings",
                "findings": len(findings),
                "by_rule": dict(sorted(by_rule.items())),
                "waivers": len(waivers),
                "waivers_by_rule": dict(sorted(waivers_by_rule.items())),
                "files_scanned": len(all_files),
                "changed_only": bool(args.changed),
                "rules": sorted(p.name for p in passes),
                "blocking_roots": {
                    "count": len(cg["blocking_roots"]),
                    "functions": cg["blocking_roots"],
                },
                "lock_graph": {
                    "locks": len(cg["locks"]),
                    "locksan_wrapped": sum(
                        1 for d in cg["locks"].values() if d["locksan"]
                    ),
                    "leaf": sorted(
                        k for k, d in cg["locks"].items() if d["leaf"]
                    ),
                    "edges": [
                        [e["held"], e["acquired"]] for e in cg["lock_edges"]
                    ],
                },
                "hot_path_functions": len(cg["hot_path_functions"]),
                "jitsan": {
                    "declared": declared_sites(sources),
                    "runtime": jitsan_runtime,
                    "runtime_meta": jitsan_meta,
                    "stats_file": (
                        os.path.relpath(stats_path, _REPO_ROOT)
                        if jitsan_runtime is not None else None
                    ),
                },
                "durables": durables_inventory(sources),
                "wire": {
                    "protocol_version": wire_inv["protocol_version"],
                    "methods": len(wire_inv["methods"]),
                    "lock_file": "artifacts/wire_schema.lock.json",
                    "unknown_total": sum(unknown_fields.values()),
                    "skew": skew_verdict,
                    "skew_file": (
                        os.path.relpath(skew_path, _REPO_ROOT)
                        if skew_verdict is not None else None
                    ),
                },
                "crashsan": {
                    "summary": crashsan_summary,
                    "matrix_file": (
                        os.path.relpath(matrix_path, _REPO_ROOT)
                        if crashsan_summary is not None else None
                    ),
                },
                "thread_map": {
                    "roles": len(tm["roles"]),
                    "entries": len(tm["entries"]),
                    "functions_with_role": tm["functions_with_role"],
                    "functions_total": tm["functions_total"],
                    "entries_by_kind": dict(sorted(Counter(
                        e["kind"] for e in tm["entries"]
                    ).items())),
                },
                "code_rev": code_rev(),
            },
            ARTIFACT_NAME,
            env_var="LINT_OUT",
            path=args.artifact or None,
        )

    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())
