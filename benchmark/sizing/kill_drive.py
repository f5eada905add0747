#!/usr/bin/env python3
"""Untimed sizing drive for the planned ``gpt2m_kill`` cell (PERF.md, Open
questions): one worker SIGKILL-equivalent (``--chaos kill``, exit code 9)
after a completed checkpoint, once with a cold relaunch and once with
``--warm_worker_standby``.  Not a benchmark cell and not run by the driver.

    python3 benchmark/sizing/kill_drive.py --out <dir> [--standby 0|1]
        [--checkpoint_steps N] [--kill_step M] [--after_reports K]

It launches the ``gpt2_medium`` x ``job_seq1k`` job through the benchmark's
own launcher with a checkpoint directory, waits for the kill and for
``K`` task reports after it, stops the job and writes a timeline read
from the logs' own timestamps: seconds from the killed worker's last
report to the successor's first log line, to its boot (device) line, to
the checkpoint restored, to the first task report, and the seconds one
background save took (``checkpoint_bg`` in the worker's PhaseTimers).
"""

from __future__ import annotations

import argparse
import datetime
import glob
import json
import os
import re
import shutil
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import datagen  # noqa: E402
from job import Job, JobFailed, job_argv  # noqa: E402
from resolve import ROOT, Bench  # noqa: E402

STAMP = re.compile(r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\]")


def stamp_s(line: str) -> float | None:
    m = STAMP.match(line)
    if not m:
        return None
    t = datetime.datetime.strptime(m.group(1), "%Y-%m-%d %H:%M:%S")
    return t.replace(tzinfo=datetime.timezone.utc).timestamp() + int(m.group(2)) / 1e3


def first_line(path: str, pattern: str):
    with open(path, errors="replace") as f:
        for line in f:
            if re.search(pattern, line) and stamp_s(line) is not None:
                return stamp_s(line), line.strip()[:200]
    return None, None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--standby", type=int, default=0)
    ap.add_argument("--checkpoint_steps", type=int, default=120)
    ap.add_argument("--kill_step", type=int, default=220)
    ap.add_argument("--after_reports", type=int, default=10)
    ap.add_argument("--seed", type=int, default=23)
    ap.add_argument("--timeout", type=float, default=700.0)
    args = ap.parse_args()

    bench = Bench(ROOT)
    config, traffic = bench.config("gpt2_medium"), bench.traffic("job_seq1k")
    work = os.path.join(BENCH_DIR, ".state", "runs", f"kill_drive_{args.standby}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    data_dir = os.path.join(work, "data")
    datagen.generate(data_dir, traffic, args.seed)
    job_name = "bench-kill"
    victim = f"{job_name}-worker-0"
    extra = {
        "job_name": job_name,
        "max_worker_relaunch": 3,
        "checkpoint_dir": os.path.join(work, "ckpt"),
        "checkpoint_steps": args.checkpoint_steps,
        "chaos": f"kill:worker={victim},step={args.kill_step}",
        "warm_worker_standby": bool(args.standby),
    }
    cache = os.path.join(BENCH_DIR, ".state", "jax_cache")
    os.makedirs(cache, exist_ok=True)
    t_launch = time.time()
    job = Job(job_argv(config, traffic, data_dir, work, extra), work, "tpu", cache)
    steps_per_task = int(traffic["minibatches_per_task"])
    try:
        def done():
            train = [r for r in job.records() if r["kind"] == "train"]
            after = [r for r in train if r["step"] > args.kill_step + steps_per_task]
            logs = glob.glob(os.path.join(work, "pods", "*.log"))
            return train if len(logs) >= 2 and len(after) >= args.after_reports else None

        train = job.wait_for(done, args.timeout, "the kill and the reports after it", poll_s=0.5)
        status = job.job_status()
    except JobFailed as e:
        print(f"[kill-drive] failed: {e}", file=sys.stderr)
        train, status = [r for r in job.records() if r["kind"] == "train"], {}
    finally:
        records = job.records()
        job.stop()

    os.makedirs(args.out, exist_ok=True)
    for path in glob.glob(os.path.join(work, "pods", "*.log")) + [job.master_log, job.metrics_path]:
        if os.path.exists(path):
            shutil.copy(path, args.out)
    gaps = [(b["ts"] - a["ts"], a["ts"], b["ts"], a["step"], b["step"]) for a, b in zip(train, train[1:])]
    steady = sorted(g[0] for g in gaps)[len(gaps) // 2] if gaps else None
    worst = max(gaps) if gaps else None
    timeline = {"launch": t_launch, "standby": args.standby, "steady_gap_s": steady}
    if worst:
        gap, t_before, t_after, step_before, step_after = worst
        timeline.update(
            kill_gap_s=gap, recover_s=gap - steady, last_report_before=t_before,
            first_report_after=t_after, step_before=step_before, step_after=step_after,
        )
        pods = sorted(glob.glob(os.path.join(work, "pods", "*.log")), key=os.path.getmtime)
        marks = {}
        for path in pods:
            name = os.path.basename(path)
            for key, pattern in (
                ("first_line", r"."), ("registered", r"registered \(membership"),
                ("boot_device_line", r"worker \S+ device: "), ("restored", r"restored checkpoint step|joined from checkpoint step"),
                ("adopted", r"standby|adopt"),
            ):
                ts, line = first_line(path, pattern)
                if ts is not None:
                    marks[f"{name}:{key}"] = {"since_last_report_s": ts - t_before, "line": line}
        for key, pattern in (("master_saw_failure", r"FAILED|exit code 9|rc=9|relaunch"), ("master_relaunch", r"relaunch")):
            ts = None
            with open(job.master_log, errors="replace") as f:
                for line in f:
                    s = stamp_s(line)
                    if s is not None and s >= t_before and re.search(pattern, line):
                        ts, text = s, line.strip()[:200]
                        break
            if ts is not None:
                marks[f"master:{key}"] = {"since_last_report_s": ts - t_before, "line": text}
        timeline["marks"] = marks
    phases = [r for r in records if r["kind"] == "phase"]
    timeline["checkpoint_bg_s_by_step"] = [
        [r["step"], r.get("checkpoint_bg"), r.get("checkpoint")] for r in phases
        if r.get("checkpoint_bg") is not None
    ][-40:]
    timeline["status"] = {k: status.get(k) for k in ("done", "abandoned", "duplicate_done")}
    ckpt_dir = os.path.join(work, "ckpt")
    sizes = {}
    for step_dir in sorted(glob.glob(os.path.join(ckpt_dir, "*"))):
        total = 0
        for base, _, files in os.walk(step_dir):
            total += sum(os.path.getsize(os.path.join(base, f)) for f in files)
        sizes[os.path.basename(step_dir)] = total
    timeline["checkpoint_bytes"] = sizes
    with open(os.path.join(args.out, "timeline.json"), "w") as f:
        json.dump(timeline, f, indent=1)
    print(json.dumps(timeline)[:6000])
    return 0


if __name__ == "__main__":
    sys.exit(main())
