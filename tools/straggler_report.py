"""straggler_report — per-rank gang-boundary wait skew and per-phase
p50/p99 from a merged grafttrace.

ROADMAP item 3 (tail tolerance) needs stragglers as a RECORDED number
before anything can sacrifice or route around them: OptiReduce-style
timeout-bounded collectives and hot-spare splicing both key off per-rank
timing visibility.  This tool turns the merged cross-process trace
(tools/trace_dump.py) into exactly that:

- **gang-boundary wait skew**: every rank's ``gang_boundary`` spans
  (worker/_next_lease — the lockstep hand-out each rank crosses at the
  same seq) summed per rank; the max-min spread is the skew a straggler
  imposes on its peers.
- **per-phase p50/p99 (+ shared histogram buckets)**: every ``phase``-
  category span's duration distribution per process — prep_wait/dispatch/
  step_wait/... as distributions, not just the cumulative sums PhaseTimers
  already ships.

Modes:
    python tools/straggler_report.py --trace merged.json
    python tools/straggler_report.py --raw dump.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)


def analyze(merged: dict) -> dict:
    """Per-process straggler analytics over a merged Chrome trace."""
    from tools.artifact import latency_stats

    events = merged.get("traceEvents") or []
    proc_names: Dict[int, str] = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            proc_names[e.get("pid")] = e["args"]["name"]

    per_proc: Dict[str, dict] = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        proc = proc_names.get(e.get("pid"), str(e.get("pid")))
        d = per_proc.setdefault(
            proc,
            {"phases": {}, "gang_wait_ms": 0.0, "gang_crossings": 0,
             "first_us": None, "last_us": None},
        )
        ts = float(e.get("ts", 0.0))
        dur_ms = float(e.get("dur", 0.0)) / 1e3
        d["first_us"] = ts if d["first_us"] is None else min(d["first_us"], ts)
        d["last_us"] = (
            ts + dur_ms * 1e3 if d["last_us"] is None
            else max(d["last_us"], ts + dur_ms * 1e3)
        )
        if e.get("cat") == "phase":
            d["phases"].setdefault(e["name"], []).append(dur_ms)
        elif e.get("cat") == "gang" and e.get("name") == "gang_boundary":
            d["gang_wait_ms"] += dur_ms
            d["gang_crossings"] += 1

    report: dict = {"processes": {}}
    for proc, d in sorted(per_proc.items()):
        phases = {
            name: {
                "count": len(durs),
                "total_ms": round(sum(durs), 2),
                # The shared bucket grid (tools/artifact.py): tail SHAPE
                # per phase, comparable across artifacts and rounds.
                **latency_stats(durs, buckets=True),
            }
            for name, durs in sorted(d["phases"].items())
        }
        entry: dict = {"phases": phases}
        if d["first_us"] is not None:
            entry["span_wall_s"] = round((d["last_us"] - d["first_us"]) / 1e6, 3)
        if d["gang_crossings"]:
            entry["gang_boundary_wait_ms"] = round(d["gang_wait_ms"], 2)
            entry["gang_crossings"] = d["gang_crossings"]
        report["processes"][proc] = entry

    # Per-rank gang wait = lockstep hand-out wait (gang_boundary spans)
    # plus the collective drain (step_wait phase): in this gang a fast
    # rank's surplus shows up BLOCKED IN THE COLLECTIVE on its slow peer,
    # so the drain is where peer-waiting actually lands — the boundary RPC
    # alone would understate it.
    waits = {}
    for p, e in report["processes"].items():
        if "gang_boundary_wait_ms" not in e:
            continue
        drain = e["phases"].get("step_wait", {}).get("total_ms", 0.0)
        waits[p] = {
            "boundary_ms": e["gang_boundary_wait_ms"],
            "collective_drain_ms": drain,
            "total_ms": round(e["gang_boundary_wait_ms"] + drain, 2),
        }
    if waits:
        totals = {p: w["total_ms"] for p, w in waits.items()}
        slowest = min(totals, key=totals.get)
        report["gang_boundary_skew"] = {
            "per_rank": waits,
            # The straggler is the rank that waits LEAST — its wall went
            # into its own work (prep/decode/compute) while every peer's
            # surplus wait absorbed the difference.
            "skew_ms": round(max(totals.values()) - min(totals.values()), 2),
            "straggler": slowest,
            "note": "per-rank gang_boundary span walls + step_wait "
                    "(collective drain) totals; the rank with the SMALLEST "
                    "total wait is the straggler its peers wait for",
        }
    return report


def _merged_from_args(args) -> dict:
    from tools.trace_dump import merge

    if args.trace:
        with open(args.trace) as f:
            return json.load(f)
    with open(args.raw) as f:
        return merge(json.load(f))


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="straggler_report", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--trace", default="", help="merged Chrome-trace JSON")
    ap.add_argument("--raw", default="", help="raw DumpTrace response JSON")
    args = ap.parse_args(argv)
    if bool(args.trace) == bool(args.raw):
        print("straggler_report: exactly one of --trace/--raw", file=sys.stderr)
        return 2
    report = analyze(_merged_from_args(args))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
