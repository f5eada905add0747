#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics are
found by name (``resolve.py``); there is no table of cells, models or
metrics in this file.  A run launches a real ``elasticdl train --local``
job on the machine's TPU, waits for the traffic's warm-up tasks (set-up),
measures for ``--seconds`` from the master's own task reports, stops the
job, checks the outputs and prints one JSON object as the last line of
standard output.  Anything that stops a measurement (no TPU, too few
chips, a dead worker) exits non-zero and prints no result.

This process never imports jax: the job's worker holds the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

T_START = time.time()

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import clock  # noqa: E402
import datagen  # noqa: E402
import xplane  # noqa: E402
from job import Job, JobFailed, job_argv  # noqa: E402
from resolve import ROOT, Bench, ResolveError  # noqa: E402

#: Everything a run writes lives here (listed in .gitignore): the compile
#: cache at a FIXED path (the path is part of the cache key), the data and
#: the job's logs of the last run of each cell.
STATE_DIR = os.path.join(BENCH_DIR, ".state")
CACHE_DIR = os.path.join(STATE_DIR, "jax_cache")


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def boot_line(log: str) -> dict | None:
    found = re.findall(r"worker \S+ device: (\{.*\})", log)
    return json.loads(found[-1]) if found else None


def last_words(path: str) -> str:
    """The last line of a log that names an error (jax ends a traceback
    with a note about its frames, not with the exception), else its last
    line."""
    try:
        with open(path, errors="replace") as f:
            lines = [line.strip() for line in f.read().strip().splitlines()]
    except OSError:
        return ""
    errors = [line for line in lines if re.match(r"[\w.]*(Error|Exception)\b", line)]
    return (errors or lines or [""])[-1][-300:]


def run_reference(bench: Bench, config: dict, traffic: dict, first_file: str, work: str, platform: str) -> dict:
    """The plain float32 reference, in a child that runs once the job has
    released the chip (``Job.wait_for_chips``): the mean loss of the first
    task from the same initial weights on the same records and, where the
    configuration names ``checks``, a reading for each."""
    out = os.path.join(work, "reference.json")
    env = dict(os.environ, JAX_PLATFORMS=platform, JAX_COMPILATION_CACHE_DIR=CACHE_DIR)
    env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    argv = [
        sys.executable, bench.reference_path(config["name"]),
        "--config", json.dumps(config), "--traffic", json.dumps(traffic),
        "--data", first_file, "--out", out,
    ]
    t0 = time.time()
    with open(os.path.join(work, "reference.log"), "w") as log:
        rc = subprocess.run(argv, cwd=ROOT, env=env, stdout=log, stderr=subprocess.STDOUT, timeout=600).returncode
    if rc != 0:
        said = last_words(os.path.join(work, "reference.log"))
        return {"error": f"reference child exited {rc}: {said}", "seconds": time.time() - t0}
    with open(out) as f:
        result = json.load(f)
    result["seconds"] = time.time() - t0
    return result


def reference_problems(reference: dict, first_loss, tolerance: float, checks: dict) -> list:
    """What the reference child's report holds against the run: the first
    task's mean loss beside the reference's (``relative_difference`` is
    written into ``reference``), then every check the CONFIGURATION names
    (``checks``: ``{name: {"limit": l, ...}}``) beside the child's bare
    reading of it (``reference["checks"]``: ``{name: value}``).  The judging
    is here and nowhere else: a reading that is missing, not a finite number
    or over its limit is a problem, whatever the child thinks of it;
    ``reference["checks"]`` becomes ``{name: {"value", "limit", "ok"}}``.  A
    configuration without ``checks`` is judged on the loss alone."""
    if "loss" not in reference:
        return [f"reference: {reference.get('error')}"]
    problems = []
    rel = abs(first_loss - reference["loss"]) / abs(reference["loss"])
    reference["relative_difference"] = rel
    if rel > tolerance:
        problems.append(
            f"first task's loss {first_loss} differs from the float32 reference "
            f"{reference['loss']} by {rel:.2e} (tolerance {tolerance})"
        )
    readings, judged = reference.get("checks") or {}, {}
    for name, check in checks.items():
        value, limit = readings.get(name), check["limit"]
        ok = isinstance(value, (int, float)) and not isinstance(value, bool) and math.isfinite(value) and value <= limit
        judged[name] = {"value": value, "limit": limit, "ok": ok}
        if not ok:
            problems.append(f"check {name}: no reading (limit {limit})" if value is None else f"check {name}: {value} over {limit}")
    if checks:
        reference["checks"] = judged
    return problems


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # Rehearsal on the CPU at toy sizes (control flow only): never prints a
    # result line, always exits non-zero.
    ap.add_argument("--rehearsal", default="", help=argparse.SUPPRESS)
    args = ap.parse_args()

    try:
        bench = Bench(ROOT)
        cell = bench.cell(args.workload)
        config = bench.config(cell["config"])
        traffic = bench.traffic(cell["traffic"])
        if args.rehearsal:
            with open(args.rehearsal) as f:
                override = json.load(f)
            config["model_params"].update(override.get("model_params", {}))
            traffic.update(override.get("traffic", {}))
            config["expect"] = override.get("expect", config["expect"])
        costs = bench.costs(config["costs"]).compute(config, traffic)
    except (ResolveError, OSError, KeyError) as e:
        say(f"cannot resolve the cell: {e!r}")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "elasticdl_tpu")):
        say("the system under test (elasticdl_tpu/) is not in this checkout")
        return 2
    platform = "cpu" if args.rehearsal else "tpu"
    chips = int(cell["chips"])

    work = os.path.join(STATE_DIR, "runs", cell["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(CACHE_DIR, exist_ok=True)
    data_dir = os.path.join(work, "data")
    shape = datagen.generate(data_dir, traffic, args.seed)
    t_data = time.time()

    extra = {}
    profile_dir = os.path.join(work, "profile")
    if args.trace:
        extra["profile_dir"] = profile_dir
    job_env = {**config.get("job_env", {}), **traffic.get("job_env", {})}
    job = Job(job_argv(config, traffic, data_dir, work, extra), work, platform, CACHE_DIR, job_env=job_env)
    warmup = int(traffic["warmup_tasks"])
    try:
        # -- set-up: everything up to the warm-up tasks' last report --
        def warm():
            train = [r for r in job.records() if r["kind"] == "train"]
            return train if len(train) >= warmup else None

        train = job.wait_for(warm, 1100.0, f"{warmup} warm-up task reports", poll_s=0.2)
        t0 = train[warmup - 1]["ts"]
        setup_s = t0 - T_START
        log = job.worker_log()
        boot = boot_line(log)
        if boot is None:
            raise JobFailed("the worker never logged its device line")
        if boot["platform"] != platform or boot["count"] != chips:
            raise JobFailed(
                f"the worker ran on {boot['count']} x {boot['platform']} "
                f"({boot['device_kind']}); the cell needs {chips} x {platform}"
            )
        peaks = bench.peaks("TPU v5 lite" if args.rehearsal else boot["device_kind"])
        probe_start = job.ask_probe()
        # -- the window --
        t1 = t0 + args.seconds
        job.wait_for(lambda: time.time() >= t1 + 0.05, args.seconds + 60.0, "the window's end", poll_s=0.2)
        probe_end = job.ask_probe()
        status = job.job_status()
        records = job.records()
        log = job.worker_log()
    except (JobFailed, ResolveError) as e:
        say(f"no measurement: {e}")
        say("worker log tail:\n" + job.worker_log()[-3000:])
        return 3
    finally:
        job.stop()

    # -- the report clock --
    units_per_step = int(traffic["minibatch_size"]) * int(traffic["units_per_record"])
    train = clock.window_records(records, "train", t0, t1)
    window = clock.report_rate(train, units_per_step)
    phase_records = clock.window_records(records, "phase", t0, t1)
    phases = clock.phase_delta(phase_records)
    phases_span = phase_records[-1]["ts"] - phase_records[0]["ts"] if len(phase_records) > 1 else 0.0
    rate_per_chip = None if window["rate"] is None else window["rate"] / chips
    all_train = [r for r in records if r["kind"] == "train"]
    # The first task is the record with the lowest model version: in a
    # traced run the profiled (synchronous) second task reports before the
    # pipelined first one.
    first_loss = min(all_train, key=lambda r: r["step"]).get("loss")

    # -- correct --
    problems = []
    if not boot.get("native_lib"):
        problems.append("the worker ran without the native library")
    for key, pattern in (("embedding_route", r"embedding lookup route: (\w+)"), ("attention_path", r"attention path: ([\w-]+)")):
        want = config["expect"].get(key)
        got = sorted(set(re.findall(pattern, log)))
        if want is not None and got != [want]:
            problems.append(f"{key} {got}, expected {want!r}")
    abandoned, duplicate = int(status.get("abandoned", 0)), int(status.get("duplicate_done", 0))
    if abandoned or duplicate:
        problems.append(f"abandoned={abandoned} duplicate_done={duplicate}")
    nonfinite = clock.nonfinite_losses(train)
    if nonfinite:
        problems.append(f"{nonfinite} task(s) with a non-finite loss")
    if window["steps"] != (window["reports"] - 1) * int(traffic["minibatches_per_task"]) and window["rate"] is not None:
        problems.append(f"the window's {window['reports']} reports span {window['steps']} steps: not whole tasks in order")
    if window["rate"] is None:
        problems.append(f"only {window['reports']} task report(s) inside the window")
    compiles = None
    if probe_start and probe_end and probe_start["pid"] == probe_end["pid"]:
        compiles = probe_end["compiles"]["count"] - probe_start["compiles"]["count"]
        if compiles:
            problems.append(f"{compiles} XLA compile(s) inside the window")
    else:
        problems.append("the in-worker probe did not answer (no compile count, no memory peak)")
    band = config["first_task_loss_band"]
    if first_loss is None or not (band[0] <= first_loss <= band[1]):
        problems.append(f"first task's loss {first_loss} outside the band {band}")
    # Peak on the fullest chip.  The TPU runtime keeps a program's
    # temporaries in a region it reserves at the bottom of memory, outside
    # ``bytes_in_use``: the peak is the two peaks together.
    memory_peak = 0
    if probe_end:
        memory_peak = max(
            (s or {}).get("peak_bytes_in_use", 0) + (s or {}).get("peak_bytes_reserved", 0)
            for s in probe_end["memory_stats"]
        )

    reference = None
    trace = None
    if args.trace:
        path = xplane.find_xplane(profile_dir)
        trace = xplane.summarize(path) if path else {"devices": 0}
        if not args.rehearsal and not trace.get("devices"):
            problems.append("the profile holds no TPU device plane")
        job.wait_for_chips()  # the reference child opens the TPU next
        reference = run_reference(bench, config, traffic, shape["first_file"], work, platform)
        problems += reference_problems(reference, first_loss, config["reference_tolerance"], config.get("checks", {}))

    # -- metrics --
    metrics = {}
    if args.trace:
        ctx = {
            "config": config, "traffic": traffic, "chips": chips, "costs": costs,
            "peaks": peaks, "window": window, "phases": phases, "phases_span_s": phases_span,
            "records_per_task": shape["records_per_task"], "status": status,
            "trace": trace, "trace_steps": int(traffic["minibatches_per_task"]),
            "rate_per_chip": rate_per_chip, "memory_peak_bytes": memory_peak,
        }
        for entry in bench.metrics_of(cell["name"], "per_layer"):
            spec = bench.metric_file(entry["name"])
            value = bench.reader(spec["reader"]).read(ctx, spec.get("params", {}))
            if value is not None and math.isfinite(value):
                metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        for entry in bench.metrics_of(cell["name"], "end_to_end"):
            if entry["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": entry["unit"]}
            elif entry["name"] == traffic["rate_metric"] and rate_per_chip is not None:
                metrics[entry["name"]] = {"value": rate_per_chip, "unit": entry["unit"]}

    device = {
        "platform": boot["platform"], "kind": boot["device_kind"], "count": boot["count"],
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": not problems,
        "attempted": window["reports"],
        "failed": nonfinite + abandoned + duplicate,
        "metrics": metrics,
        "device": device,
    }
    if args.trace and trace and trace.get("devices"):
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["span_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}

    # -- the earlier line: what explains an odd run --
    info = {
        "cell": cell["name"], "seed": args.seed, "trace": args.trace,
        "problems": problems,
        "setup_s": setup_s, "setup_data_s": t_data - T_START,
        "window": {k: window[k] for k in ("reports", "steps", "span_s", "rate", "gap_max_s", "gap_median_s")},
        "ts_minus_t0": [round(ts - t0, 4) for ts in window["ts"]],
        "warmup_ts_minus_t0": [round(r["ts"] - t0, 3) for r in all_train[:warmup]],
        "first_task_loss": first_loss,
        "last_task_loss": train[-1].get("loss") if train else None,
        "phases_window_s": {k: round(v, 4) for k, v in phases.items()},
        "phases_span_s": phases_span,
        "status": {k: status.get(k) for k in ("done", "doing", "todo", "abandoned", "duplicate_done", "epoch", "model_version", "stale_reports")},
        "compiles_in_window": compiles,
        "compiles_before_window": probe_start["compiles"] if probe_start else None,
        "memory_stats_end": probe_end["memory_stats"] if probe_end else None,
        "boot": boot,
        "data": shape,
        "costs": costs,
        "reference": reference,
        "chips_wait_s": job.chips_wait_s,
        "chips_waited_for": job.chips_waited_for,
        "trace_in_task": None if not (trace and trace.get("devices")) else {
            "busy_s": trace["busy_s"], "span_s": trace["span_s"], "events": trace["events"],
            "idle_pct_inside_the_traced_task": 100.0 * (1 - trace["busy_s"] / trace["span_s"]) if trace["span_s"] else None,
        },
        "wall_s": time.time() - T_START,
    }
    print("[bench-info] " + json.dumps(info), flush=True)
    # each number compared beside its limit, last on standard error
    say(f"compared: first task's loss {first_loss} in {band}; compiles in the window {compiles} (limit 0); "
        f"abandoned + duplicate_done {abandoned + duplicate} (limit 0); non-finite losses {nonfinite} (limit 0)")
    if reference and "loss" in reference:
        say(f"compared: loss against the reference {reference['relative_difference']:.3e} (limit {config['reference_tolerance']})")
        for name, check in (reference["checks"] if config.get("checks") else {}).items():
            say(f"compared: check {name} {check['value']} (limit {check['limit']})")
    say(f"correct {not problems}: {problems}")
    if args.rehearsal:
        print("[bench-rehearsal] " + json.dumps(result), file=sys.stderr, flush=True)
        return 4
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
