"""Tests of what PR 35 added to the yardstick: ten ``setup_*`` metrics that
read the program's ``setup`` records (reader ``setup_spans``) in all six
cells, and ``prep_ms_task.ex`` on the ``prep`` spans of the traced run's
host plane (reader ``host_span_ms_task``).  New files and entries only.
CPU only; the trace in ``data/`` was recorded on a v5e."""

from __future__ import annotations

import json
import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402
import runfiles  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "deepfm_toy_job_host_spans.xplane.pb")

CELLS = ["deepfm_job", "gpt2m_job", "deepfm_x4_job", "deepfm_job_zipf", "olmoe_job", "kanana2_job", "evabyte_job"]
#: metric -> (the reader's quantity, unit, better, layer)
SETUP = {
    "setup_master_s": ("master_s", "s", "lower", "master"),
    "setup_index_scan_s": ("index_scan_s", "s", "lower", "ingest"),
    "setup_worker_imports_s": ("worker_imports_s", "s", "lower", "worker loop"),
    "setup_device_open_s": ("device_open_s", "s", "lower", "device"),
    "setup_init_state_s": ("init_state_s", "s", "lower", "trainer"),
    "setup_worker_build_s": ("worker_build_s", "s", "lower", "worker loop"),
    "setup_compile_s": ("compile_s", "s", "lower", "trainer"),
    "setup_cache_served_pct": ("cache_served_pct", "%", "higher", "trainer"),
    "setup_warmup_s": ("warmup_s", "s", "lower", "worker loop"),
    "setup_unattributed_s": ("unattributed_s", "s", "lower", "worker loop"),
}
DURATIONS = [n for n in SETUP if n not in ("setup_cache_served_pct", "setup_unattributed_s")]


def _read(name, ctx):
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file(name)
    return bench.reader(spec["reader"]).read(ctx, spec.get("params", {}))


# -------------------------------------------- files and entries, by name


@pytest.mark.parametrize("name", [*SETUP, "prep_ms_task.ex"])
def test_new_metric_files_say_what_benchmark_json_says(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [e for e in bench.spec["per_layer"] if e["name"] == name]
    spec = bench.metric_file(name)
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    assert "cells" not in spec and entry["source"] == "program_span"
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    if name == "prep_ms_task.ex":
        assert (spec["reader"], spec["params"]) == ("host_span_ms_task", {"span": "prep", "lines": "edl-prep_"})
        assert (entry["moves"], entry["layer"], entry["unit"], entry["better"]) == ("examples_per_s_chip", "ingest", "ms", "lower")
        assert entry["workloads"][:2] == ["deepfm_job", "deepfm_job_zipf"]  # a later cell appends itself
        return
    what, unit, better, layer = SETUP[name]
    assert (spec["reader"], spec["params"]) == ("setup_spans", {"what": what})
    assert (entry["moves"], entry["unit"], entry["better"], entry["layer"]) == ("setup_s", unit, better, layer)
    # every cell the benchmark had at PR 39, in the order they joined (deepfm_x4_job joined setup_init_state_s
    # in PR 39, when init_state_s.ex4 was retired); a later cell appends itself
    joined = [*(c for c in CELLS[:6] if c != "deepfm_x4_job"), "deepfm_x4_job", "evabyte_job"] if name == "setup_init_state_s" else CELLS
    assert entry["workloads"][: len(CELLS)] == joined


def test_every_cell_reads_the_set_up_and_the_layers_are_the_benchmarks_own():
    bench = resolve.Bench(ROOT)
    reader = bench.reader("setup_spans")
    assert sorted(reader.QUANTITIES) == sorted(v[0] for v in SETUP.values())
    layers = {e["layer"] for e in bench.spec["per_layer"] if not e["name"].startswith(("setup_", "prep_ms_task"))}
    for cell in CELLS:
        names = {m["name"] for m in bench.metrics_of(cell, "per_layer")}
        assert set(SETUP) <= names and "init_state_s.ex4" not in names, cell
    assert {SETUP[n][3] for n in SETUP} <= layers


# ------------------------------------------------ the reader, by hand


def _spans(**named) -> dict:
    out = {}
    for name, (t0, t1) in named.items():
        key = name if name == "init_state" else "setup:" + name
        out[key + "_t0"], out[key + "_t1"] = float(t0), float(t1)
    return out


#: A job on a clock that starts at 1000: the launcher's first stamp at
#: 1000, the fleet spawned at 1007, the first report at 1040, t0 at 1052.
MASTER = {"kind": "setup", "ts": 1007.001, "step": 0, "pid": 11.0,
          **_spans(launch=(1000, 1001), shards=(1001, 1005), serve=(1005, 1006.5), spawn=(1006.5, 1007))}
WORKER = {"kind": "setup", "ts": 1040.001, "step": 8, "pid": 12.0, "cache_hits": 3.0, "cache_misses": 1.0,
          **_spans(interp=(1007, 1007.5), imports=(1007.5, 1020), register=(1020, 1020.5),
                   device_open=(1020.5, 1028), build=(1028, 1031), shards=(1029, 1030.5),
                   init_state=(1031, 1032), first_prep=(1032, 1033), first_dispatch=(1033, 1039),
                   first_step=(1039, 1040))}
BY_HAND = {
    "setup_master_s": 7.0 - 4.0, "setup_index_scan_s": 4.0 + 1.5, "setup_worker_imports_s": 0.5 + 12.5,
    "setup_device_open_s": 7.5, "setup_init_state_s": 1.0, "setup_worker_build_s": 0.5 + 3.0 - 1.5 + 1.0,
    "setup_compile_s": 6.0, "setup_warmup_s": 1052.0 - 1039.0, "setup_unattributed_s": 0.0,
    "setup_cache_served_pct": 75.0,
}


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """A checkout's worth of what the reader looks for: BENCHMARK.json and
    the last run of ``gpt2m_job`` (its metrics.jsonl); ``write(records)``
    replaces the file."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    work = tmp_path / "benchmark" / ".state" / "runs" / "gpt2m_job" / "metrics"
    work.mkdir(parents=True)
    monkeypatch.setattr(runfiles, "ROOT", str(tmp_path))

    def write(records):
        with open(work / "metrics.jsonl", "w") as f:
            f.write("".join(json.dumps(r) + "\n" for r in records) + '{"kind": "set')

    ctx = {"config": {"name": "gpt2_medium"}, "traffic": {"name": "job_seq1k"}, "chips": 1,
           "window": {"ts": [1052.0, 1053.0, 1054.0]}}
    return ctx, write


def _train(ts, step):
    return {"kind": "train", "ts": ts, "step": step, "loss": 1.0}


def test_each_quantity_is_what_the_spans_say_by_hand(run):
    ctx, write = run
    write([MASTER, _train(1040.0, 8), WORKER, _train(1046.0, 16), _train(1052.0, 24)])
    values = {name: _read(name, ctx) for name in SETUP}
    assert values == pytest.approx(BY_HAND)
    # the eight durations and what no span covers are the whole of t0 - the launcher's first stamp
    assert sum(values[n] for n in DURATIONS) + values["setup_unattributed_s"] == pytest.approx(1052.0 - 1000.0)


def test_time_no_span_covers_is_a_number_of_its_own(run):
    """A gap between two spans (the worker's first stamp 2 s after the
    spawn's end with no ``interp`` to cover it is NOT one: that is read as
    the interpreter's start) and a span the reader has no name for (a
    relaunched worker's walk over the checkpoints) both land in
    ``setup_unattributed_s``; nothing is lost from the sum."""
    ctx, write = run
    late = {k: v for k, v in WORKER.items() if not k.startswith("setup:interp")}
    write([MASTER, late, _train(1052.0, 24)])
    assert _read("setup_worker_imports_s", ctx) == pytest.approx(0.5 + 12.5)
    restore = dict(WORKER, **_spans(init_state=(1031, 1031.5), restore=(1031.5, 1032)))
    gap = dict(restore, **_spans(first_prep=(1032.25, 1033)))
    write([MASTER, gap, _train(1052.0, 24)])
    values = {name: _read(name, ctx) for name in SETUP}
    assert values["setup_init_state_s"] == pytest.approx(0.5) and values["setup_worker_build_s"] == pytest.approx(3.0 - 0.25)
    assert values["setup_unattributed_s"] == pytest.approx(0.5 + 0.25)
    assert sum(values[n] for n in DURATIONS) + values["setup_unattributed_s"] == pytest.approx(52.0)


def test_a_relaunched_workers_chain_is_not_counted_twice(run):
    """The reader takes the incarnation whose first report precedes ``t0``:
    the last worker record stamped no later than it."""
    ctx, write = run
    shift = 100.0
    second = {k: (v + shift if k.endswith(("_t0", "_t1")) or k == "ts" else v) for k, v in WORKER.items()}
    second.update(cache_hits=4.0, cache_misses=0.0)
    records = [MASTER, _train(1040.0, 8), WORKER, _train(1052.0, 16), _train(1140.0, 24), second, _train(1152.0, 32)]
    write(records)
    assert {name: _read(name, ctx) for name in SETUP} == pytest.approx(BY_HAND)  # the window of the first incarnation
    after = dict(ctx, window={"ts": [1152.0, 1153.0]})
    values = {name: _read(name, after) for name in SETUP}
    for name in ("setup_device_open_s", "setup_init_state_s", "setup_compile_s", "setup_worker_build_s", "setup_index_scan_s"):
        assert values[name] == pytest.approx(BY_HAND[name]), name
    assert values["setup_cache_served_pct"] == 100.0 and values["setup_warmup_s"] == pytest.approx(13.0)
    # from the master's spawn to the relaunch there is no span of set-up: it is the first incarnation's life
    assert values["setup_unattributed_s"] == pytest.approx(shift)
    assert sum(values[n] for n in DURATIONS) + values["setup_unattributed_s"] == pytest.approx(1152.0 - 1000.0)


@pytest.mark.parametrize("records", [
    [], [_train(1040.0, 8), _train(1052.0, 16)], [MASTER, _train(1052.0, 16)], [WORKER, _train(1052.0, 16)],
    [dict(MASTER, ts=1060.0), dict(WORKER, ts=1061.0), _train(1052.0, 16)],
], ids=["no_file_content", "an_older_program", "no_worker_record", "no_master_record", "both_after_t0"])
def test_without_a_setup_record_the_reader_reports_nothing(run, records):
    ctx, write = run
    write(records)
    for name in SETUP:
        assert _read(name, ctx) is None, name


@pytest.mark.parametrize("ctx", [
    {}, {"window": {"ts": [1.0, 2.0]}},
    {"config": {"name": "gpt2_medium"}, "traffic": {"name": "job_seq1k"}, "chips": 1, "window": {"ts": []}},
    {"config": {"name": "olmoe_1b_7b_l1"}, "traffic": {"name": "job_seq4k"}, "chips": 1, "window": {"ts": [1052.0, 1053.0]}},
], ids=["bare", "no_cell", "no_window", "cell_without_a_run"])
def test_new_readers_report_nothing_when_there_is_nothing_to_read(run, ctx):
    _, write = run
    write([MASTER, WORKER, _train(1052.0, 24)])
    for name in [*SETUP, "prep_ms_task.ex"]:
        assert _read(name, ctx) is None, name


def test_no_cache_request_gives_no_share(run):
    ctx, write = run
    write([MASTER, dict(WORKER, cache_hits=0.0, cache_misses=0.0), _train(1052.0, 24)])
    assert _read("setup_cache_served_pct", ctx) is None
    assert _read("setup_compile_s", ctx) == pytest.approx(6.0)


# ------------------------------------------------------ the prep spans


def test_prep_ms_task_is_the_prep_threads_span_time_over_their_tasks(monkeypatch):
    reader = resolve.Bench(ROOT).reader("host_span_ms_task")
    lines = [
        ("edl-prep_0", [(0.0, 90e6, "prep", {"task": 2}), (100e6, 130e6, "prep", {"task": 4}), (0.0, 50e6, "rpc:GetTask", {})]),
        ("edl-prep_1", [(10e6, 70e6, "prep", {"task": 3})]),
        ("python3", [(0.0, 500e6, "prep", {"task": 9}), (10.0, 20.0, "dispatch", {"task": 1})]),
        ("edl-ingest_1", [(0.0, 50e6, "decode_parallel", {"task": 2})]),
    ]
    monkeypatch.setattr(reader.runfiles, "trace_path", lambda ctx: "a.xplane.pb")
    monkeypatch.setattr(reader.runfiles, "host_lines", lambda path: lines)
    params = {"span": "prep", "lines": "edl-prep_"}
    assert reader.read({}, params) == pytest.approx((90 + 30 + 60) / 3)
    # a task whose host half was cut in two counts once; spans without the stat count themselves
    lines[1] = ("edl-prep_1", [(10e6, 70e6, "prep", {"task": 2})])
    assert reader.read({}, params) == pytest.approx((90 + 30 + 60) / 2)
    lines[:2] = [("edl-prep_0", [(0.0, 8e6, "prep", {}), (9e6, 13e6, "prep", {})])]
    assert reader.read({}, params) == pytest.approx(6.0)
    assert reader.read({}, {"span": "prep", "lines": "edl-other_"}) is None
    monkeypatch.setattr(reader.runfiles, "trace_path", lambda ctx: None)
    assert reader.read({}, params) is None


def test_prep_ms_task_on_the_recorded_chip_trace(tmp_path, monkeypatch):
    if not os.path.exists(TRACE):
        pytest.skip("the recorded trace is not in this checkout")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    profile = tmp_path / "benchmark" / ".state" / "runs" / "deepfm_job" / "profile" / "plugins" / "profile" / "2026_01_01"
    profile.mkdir(parents=True)
    shutil.copy(TRACE, profile / "vm.xplane.pb")
    monkeypatch.setattr(runfiles, "ROOT", str(tmp_path))
    ctx = {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 1}
    spans = [e for line, events in runfiles.host_lines(TRACE) if line.startswith("edl-prep_") for e in events if e[2] == "prep"]
    tasks = {e[3]["task"] for e in spans}
    assert len(spans) >= 3 and len(tasks) == len(spans)
    value = _read("prep_ms_task.ex", ctx)
    assert value == pytest.approx(sum(e[1] - e[0] for e in spans) / 1e6 / len(tasks)) and 0 < value < 1000
    # a trace without the program's spans (a program older than PR 24)
    shutil.copy(os.path.join(HERE, "data", "deepfm_two_steps.xplane.pb"), profile / "vm.xplane.pb")
    assert _read("prep_ms_task.ex", ctx) is None


# ---------------------------------------------------- a whole rehearsal


def test_rehearsal_reads_every_setup_metric_and_they_add_up(tmp_path):
    """The whole of run.py for ``deepfm_job`` at toy sizes with ``--trace
    1``: all ten ``setup_*`` metrics and ``prep_ms_task.ex`` in the report,
    finite; the eight durations and what no span covers add up to ``t0`` -
    the launcher's first stamp, which with the harness's own share
    (``setup_data_s`` and the launcher's start) is the run's ``setup_s``."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(
            ".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache", "parent_tree", "final_tree",
        ),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "deepfm_job", "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/deepfm_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    metrics = result["metrics"]
    values = {name: metrics[name]["value"] for name in SETUP}
    assert all(math.isfinite(v) for v in values.values()) and metrics["prep_ms_task.ex"]["value"] > 0
    assert all(values[n] >= 0 for n in DURATIONS) and 0 <= values["setup_cache_served_pct"] <= 100
    records = runfiles.read_records(str(scratch / "benchmark" / ".state" / "runs" / "deepfm_job" / "metrics" / "metrics.jsonl"))
    setups = [r for r in records if r["kind"] == "setup"]
    train = [r for r in records if r["kind"] == "train"]
    assert len(setups) == 2
    t0 = train[len(info["warmup_ts_minus_t0"]) - 1]["ts"]
    first_stamp = setups[0]["setup:launch_t0"]
    assert sum(values[n] for n in DURATIONS) + values["setup_unattributed_s"] == pytest.approx(t0 - first_stamp, abs=1e-3)
    assert abs(values["setup_unattributed_s"]) < 1.0
    # setup_s = the harness's share (its data, its Popen, the interpreter's start) + the chain
    harness = info["setup_s"] - (t0 - first_stamp)
    assert info["setup_data_s"] <= harness < info["setup_data_s"] + 2.0
    # imports and the first compile are seconds in any process; nothing else is asserted of a toy job's sizes
    assert values["setup_worker_imports_s"] > 0.5 and values["setup_compile_s"] > 0.05
