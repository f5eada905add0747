"""crashsan matrix — every injectable crash point, every mode, recovered.

The runtime half of the r21 durability work: graftlint v7 proves every
durable write ROUTES through ``common/durable.py``; this driver proves the
routed writes RECOVER.  Three scenarios — the master journal, the pod
reattach registry, the checkpoint manifest — each run once under
``crashsan.record()`` to enumerate their durable-op crossings, then re-run
in a fresh directory for every (op, crash mode) pair with
``crashsan.crash_at`` armed.  The crossing produces ON DISK the exact
state a real process death at that point leaves (torn final append, temp
complete but rename never landed, rename-before-fsync tear) and the
scenario's REAL recovery reader (``journal.read_journal``,
``PodManager.scan_registry``, ``checkpoint.read_manifest``) then runs
against it.  Each outcome must land in the scenario's documented contract
class (docs/robustness.md "Durability contracts"):

- ``exact-prefix``       append crashes: replay returns exactly the
                         records of every COMPLETED op; the torn tail
                         (never acknowledged to anyone) is dropped.
- ``previous-version``   publish crashes before the rename landed: the
                         reader sees the previous complete version.
- ``watermark-fallback``  the journal is absent or has no usable base:
                         ``JournalError`` — the master falls back to the
                         coarse watermark loudly (at-least-once).
- ``fallback-empty``     registry/manifest absent or torn by a simulated
                         NON-compliant writer (``published_torn``): the
                         tolerant reader reports "nothing published".

Anything else — records that are not a prefix, an unexpected exception,
silent acceptance of mid-file garbage — is an UNRECOVERED crash point and
fails the row (exit 1 here; ``tests/test_crashsan.py`` asserts the same
matrix in-process).

Usage:
    python tools/crashsan_matrix.py            # print summary, exit 1 on
                                               # any unrecovered point

tests/test_crashsan.py drives the same scenario functions in-process, so
this tool and the tier-1 gate exercise one definition.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# The sanitizer must be armed before any scenario runs (crash_at refuses
# to arm otherwise — a sweep that never crashes proves nothing).
os.environ.setdefault("GRAFT_CRASHSAN", "1")

#: pids beyond any live process (default pid_max) — the registry scan's
#: liveness probe must classify them dead deterministically.
_DEAD_PID_BASE = 4_194_304 + 7


# -- journal scenario ------------------------------------------------------

def _journal_events() -> List[Tuple[str, dict]]:
    """The workload script: (durable-op kind, logical record).  Rotation
    publishes a fresh base; appends extend the WAL.  Op 3 is the r18
    regression's membership record — the crash-at-rotation rows prove it
    can no longer land in NEITHER file."""
    base1 = {"kind": "base", "dispatcher": {"doing": 0, "done": []}}
    base2 = {"kind": "base", "dispatcher": {"doing": 0, "done": [1, 2]}}
    return [
        ("publish", base1),
        ("append", {"kind": "handout", "worker": "w0", "tasks": [{"id": 1}]}),
        ("append", {"kind": "report", "task_id": 1, "success": True,
                    "worker": "w0", "requeue": False}),
        ("append", {"kind": "membership", "version": 7}),
        ("publish", base2),
        ("append", {"kind": "handout", "worker": "w1", "tasks": [{"id": 3}]}),
        ("append", {"kind": "stop"}),
    ]


def journal_expected(completed: int) -> List[dict]:
    """The record list read_journal must see after ``completed`` ops
    landed fully: the latest completed rotation's base plus every append
    after it."""
    events = _journal_events()[:completed]
    out: List[dict] = []
    for kind, rec in events:
        if kind == "publish":
            out = [dict(rec, kind="base")]
        else:
            out.append(rec)
    return out


def run_journal(directory: str, crash: Optional[Tuple[int, str]] = None):
    """Run the journal workload, optionally crashing at op ``crash[0]``
    with mode ``crash[1]``; returns the recovery view ``(records, torn)``
    or the string ``"watermark-fallback"`` when the journal is unusable
    (absent / no base) — the master's documented fallback."""
    from elasticdl_tpu.common import crashsan
    from elasticdl_tpu.master import journal as journal_mod

    path = os.path.join(directory, journal_mod.JOURNAL_FILENAME)
    j = journal_mod.MasterJournal(path)
    try:
        if crash is not None:
            crashsan.arm(crash[0], crash[1])
        try:
            for kind, rec in _journal_events():
                if kind == "publish":
                    j.rotate(rec)
                else:
                    j.record(rec)
        except crashsan.CrashPoint:
            pass  # the simulated death; recovery runs below
        else:
            if crash is not None:
                raise AssertionError(
                    f"armed crash {crash} never fired in the journal "
                    "workload"
                )
    finally:
        if crash is not None:
            crashsan.disarm()
        j.close()
    if not os.path.exists(path):
        return "watermark-fallback"
    try:
        base, events, torn = journal_mod.read_journal(path)
    except journal_mod.JournalError:
        return "watermark-fallback"
    return [base] + events, torn


# -- registry scenario -----------------------------------------------------

def _registry_versions() -> List[dict]:
    """Three successive registry publishes, i+1 slots each — distinct
    sizes so which VERSION a recovery scan sees is unambiguous."""
    out = []
    for v in range(1, 4):
        out.append({
            "slots": {
                str(s): {
                    "name": f"w{s}", "pid": _DEAD_PID_BASE + s,
                    "relaunches": 0, "gen": v, "cmdline": None,
                }
                for s in range(v)
            }
        })
    return out


def run_registry(directory: str, crash: Optional[Tuple[int, str]] = None):
    """Publish three registry generations through the durable shape the
    pod manager uses, optionally crashing; recovery is the REAL
    ``PodManager.scan_registry``.  Returns its dict."""
    from elasticdl_tpu.common import crashsan, durable
    from elasticdl_tpu.master.pod_manager import PodManager

    path = os.path.join(directory, PodManager.REGISTRY_FILENAME)
    if crash is not None:
        crashsan.arm(crash[0], crash[1])
    try:
        for payload in _registry_versions():
            durable.atomic_publish_json(path, payload, sort_keys=True)
    except crashsan.CrashPoint:
        pass
    else:
        if crash is not None:
            raise AssertionError(
                f"armed crash {crash} never fired in the registry workload"
            )
    finally:
        if crash is not None:
            crashsan.disarm()
    return PodManager.scan_registry(path)


# -- manifest scenario -----------------------------------------------------

def run_manifest(directory: str, crash: Optional[Tuple[int, str]] = None):
    """Publish checkpoint manifests for steps 100 then 200, optionally
    crashing; recovery is the REAL ``checkpoint.read_manifest``.  Returns
    its dict (or None)."""
    from elasticdl_tpu.common import checkpoint, crashsan

    if crash is not None:
        crashsan.arm(crash[0], crash[1])
    try:
        for step in (100, 200):
            checkpoint.publish_manifest(directory, step, code_rev="matrix")
    except crashsan.CrashPoint:
        pass
    else:
        if crash is not None:
            raise AssertionError(
                f"armed crash {crash} never fired in the manifest workload"
            )
    finally:
        if crash is not None:
            crashsan.disarm()
    return checkpoint.read_manifest(directory)


# -- sweep + contract classification ---------------------------------------

def _enumerate_ops(scenario: Callable) -> List[dict]:
    from elasticdl_tpu.common import crashsan

    with tempfile.TemporaryDirectory() as d:
        with crashsan.record() as ops:
            scenario(d)
    return list(ops)


def _judge_journal(op_index: int, kind: str, mode: str, result) -> Tuple[bool, str]:
    if result == "watermark-fallback":
        # Legal only when no completed rotation's base can be on disk:
        # crashes at/around the FIRST publish, or a published_torn tear of
        # a later rotation (the non-compliant-writer mode tears the base).
        legal = op_index == 0 or (kind == "publish" and mode == "published_torn")
        return legal, "watermark-fallback"
    records, torn = result
    if records == journal_expected(op_index):
        if kind == "publish" and op_index > 0:
            return True, "previous-version"
        return True, "exact-prefix"
    return False, f"unexpected records: {json.dumps(records)[:200]}"


def _judge_registry(op_index: int, kind: str, mode: str, scan) -> Tuple[bool, str]:
    recorded = scan.get("recorded")
    if scan.get("alive"):
        return False, f"dead pids scanned alive: {scan}"
    if recorded == op_index and op_index > 0:
        return True, "previous-version"
    if recorded == 0:
        legal = op_index == 0 or mode == "published_torn"
        return legal, "fallback-empty"
    return False, f"unexpected scan: {scan}"


def _judge_manifest(op_index: int, kind: str, mode: str, m) -> Tuple[bool, str]:
    steps = (100, 200)
    if m is None:
        legal = op_index == 0 or mode == "published_torn"
        return legal, "fallback-empty"
    if isinstance(m, dict) and m.get("step") == steps[op_index - 1]:
        return True, "previous-version"
    return False, f"unexpected manifest: {m}"


SCENARIOS = (
    ("journal", run_journal, _judge_journal),
    ("registry", run_registry, _judge_registry),
    ("manifest", run_manifest, _judge_manifest),
)


def run_matrix() -> dict:
    """The full sweep: every scenario x every durable op x every crash
    mode its kind admits.  Returns ``{"rows": [...], "summary": {...}}``."""
    from elasticdl_tpu.common import crashsan

    rows: List[dict] = []
    crash_points = 0
    for name, scenario, judge in SCENARIOS:
        ops = _enumerate_ops(scenario)
        crash_points += len(ops)
        for op in ops:
            modes = (
                crashsan.APPEND_MODES if op["kind"] == "append"
                else crashsan.PUBLISH_MODES
            )
            for mode in modes:
                with tempfile.TemporaryDirectory() as d:
                    result = scenario(d, crash=(op["index"], mode))
                ok, contract = judge(op["index"], op["kind"], mode, result)
                rows.append({
                    "scenario": name,
                    "op": op["index"],
                    "kind": op["kind"],
                    "file": op["file"],
                    "mode": mode,
                    "recovered": bool(ok),
                    "contract": contract,
                })
    by_contract: Dict[str, int] = {}
    for r in rows:
        if r["recovered"]:
            by_contract[r["contract"]] = by_contract.get(r["contract"], 0) + 1
    summary = {
        "crash_points": crash_points,
        "injected": len(rows),
        "recovered": sum(1 for r in rows if r["recovered"]),
        "unrecovered": sum(1 for r in rows if not r["recovered"]),
        "by_contract": dict(sorted(by_contract.items())),
        "by_scenario": {
            name: sum(1 for r in rows if r["scenario"] == name)
            for name, _s, _j in SCENARIOS
        },
    }
    return {"rows": rows, "summary": summary}


def main() -> int:
    out = run_matrix()
    s = out["summary"]
    for r in out["rows"]:
        if not r["recovered"]:
            print(
                f"UNRECOVERED {r['scenario']} op={r['op']} "
                f"({r['kind']} {r['file']}) mode={r['mode']}: "
                f"{r['contract']}",
                file=sys.stderr,
            )
    print(json.dumps(s, indent=1, sort_keys=True))
    return 1 if s["unrecovered"] else 0


if __name__ == "__main__":
    sys.exit(main())
