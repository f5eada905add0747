"""Tests of what PR 24 added to the yardstick: device-idle time put down to
the host's spans (``benchmark/runfiles.py``, reader ``idle_under_spans``)
and the readers of the worker's own counters (``counter_delta``,
``counter_last``).  CPU only; the trace in ``data/`` was recorded on a v5e.
"""

from __future__ import annotations

import json
import os
import shutil
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
EXPECTED = os.path.join(HERE, "data", "deepfm_toy_job_host_spans.expected.json")


# ------------------------------------------------- attribution, by hand


def test_a_gap_split_over_two_spans_goes_to_each_by_its_share():
    spans = [(0, 100, "prep_wait"), (100, 250, "dispatch")]
    assert runfiles.attribute([(80, 60)], spans) == {None: 0.0, "prep_wait": 20.0, "dispatch": 40.0}


def test_a_gap_under_no_span_is_unattributed():
    spans = [(0, 100, "prep_wait"), (300, 400, "dispatch")]
    under = runfiles.attribute([(150, 100), (90, 20)], spans)
    assert under == {None: 110.0, "prep_wait": 10.0}


def test_nested_spans_give_each_nanosecond_to_the_innermost():
    # control [0,1000] holds lease_wait [200,500], which holds an RPC span
    # the caller did not list (it is filtered before), so control keeps
    # only what lease_wait does not cover.
    spans = [(0, 1000, "control"), (200, 500, "lease_wait")]
    assert runfiles.innermost_segments(spans) == [
        (0, 200, "control"), (200, 500, "lease_wait"), (500, 1000, "control"),
    ]
    under = runfiles.attribute([(100, 200), (450, 100), (900, 300)], spans)
    assert under == {None: 200.0, "control": 100.0 + 50.0 + 100.0, "lease_wait": 100.0 + 50.0}
    assert sum(under.values()) == 200 + 100 + 300


@pytest.mark.parametrize("gaps", [[], [(5, 1)], [(0, 10), (10, 10), (40, 5), (1000, 7)]])
def test_attributed_time_adds_up_to_the_gaps(gaps):
    spans = [(0, 12, "a"), (3, 9, "b"), (4, 5, "c"), (30, 42, "a"), (41, 42, "c")]
    under = runfiles.attribute(gaps, spans)
    assert sum(under.values()) == pytest.approx(sum(length for _, length in gaps))


def test_the_task_loop_is_the_line_that_dispatches_other_threads_are_ignored():
    lines = [
        ("edl-prep_0", [(0.0, 90.0, "prep", {"task": 2})]),
        ("python3", [(10.0, 20.0, "dispatch", {"task": 1, "seq": 1}), (20.0, 80.0, "step_wait", {"task": 0})]),
        ("edl-ingest_1", [(0.0, 50.0, "decode_parallel", {"task": 2})]),
    ]
    loop = runfiles.loop_line(lines)
    assert [e[2] for e in loop] == ["dispatch", "step_wait"]
    assert runfiles.loop_line([lines[0], lines[2]]) is None
    # a gap wholly under another thread's span is under nothing of the loop's
    spans = [(s, e, n) for s, e, n, _ in loop if n in ("dispatch",)]
    assert runfiles.attribute([(30, 40)], spans) == {None: 40.0}


def test_gaps_are_clipped_to_the_step_programs():
    assert runfiles.clip([(0, 10), (95, 10), (50, 5), (200, 5)], 5, 100) == [(5, 5), (95, 5), (50, 5)]


# ------------------------------------------------ the recorded chip trace


@pytest.fixture(scope="module")
def expected():
    if not os.path.exists(TRACE):
        pytest.skip("the recorded trace is not in this checkout")
    return json.load(open(EXPECTED))


def test_recorded_trace_holds_the_loops_spans_with_task_and_seq(expected):
    lines = runfiles.host_lines(TRACE)
    loop = runfiles.loop_line(lines)
    dispatches = [stats for _, _, name, stats in loop if name == "dispatch"]
    assert [d["seq"] for d in dispatches] == expected["dispatch_seqs"]
    assert [d["task"] for d in dispatches] == expected["dispatch_tasks"]
    assert sorted({name for _, events in lines for _, _, name, _ in events}) == expected["span_names"]
    assert sorted(name for name, _ in lines) == expected["line_names"]


def test_recorded_trace_host_and_device_are_on_one_clock(expected):
    pairs = runfiles.clock_check(TRACE, "jit_local_scan")
    assert len(pairs) == expected["clock_pairs"]
    assert all(p["dispatch_before_start"] for p in pairs)
    assert [p["settled_after_end"] for p in pairs] == expected["settled_after_end"]
    assert [p["seq"] for p in pairs] == expected["dispatch_seqs"][: len(pairs)]


def test_recorded_trace_idle_time_is_put_down_to_spans_and_adds_up(expected):
    # ProfileData hands out whole nanoseconds; the expected numbers come
    # from the file's picoseconds: a thousand events' truncation is 1e-4
    known = tuple(expected["known"])
    found = runfiles.idle_by_span(TRACE, "jit_local_scan", known)
    assert found["tasks"] == expected["tasks"]
    assert found["idle_ns"] == pytest.approx(expected["idle_ns"], rel=2e-4)
    assert found["window_ns"] == pytest.approx(expected["window_ns"], rel=1e-6)
    assert set(found["under"]) == set(expected["under_ns"])
    for name, ns in expected["under_ns"].items():
        assert found["under"][name] == pytest.approx(ns, rel=2e-4), name
    assert found["rest"] == pytest.approx(expected["rest_ns"], rel=2e-4)
    assert sum(found["under"].values()) + found["rest"] == pytest.approx(found["idle_ns"], rel=1e-12)
    # a trace without the program's spans (a program older than PR 24)
    old = os.path.join(HERE, "data", "deepfm_two_steps.xplane.pb")
    assert runfiles.idle_by_span(old, "jit_local_scan", known) is None
    assert runfiles.clock_check(old, "jit_local_scan") == []


# ------------------------------------------ the readers, through resolve


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """A checkout's worth of what the readers look for: BENCHMARK.json and
    the last run of ``deepfm_job`` (counter records, the recorded trace)."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    work = tmp_path / "benchmark" / ".state" / "runs" / "deepfm_job"
    (work / "metrics").mkdir(parents=True)
    records = []
    for i in range(10):
        ts = 100.0 + i
        records.append({"kind": "phase", "ts": ts, "step": 8 * i, "prep_wait": 0.1 * i})
        records.append({
            "kind": "counter", "ts": ts + 0.001, "step": 8 * i, "compiles": 5.0 + (i == 9),
            "compile_s": 40.0, "hbm_peak_bytes": 3.0 * 2**30 + (2**29 if i >= 6 else 0),
            "dispatches": 2.0 + i, "dispatches_device_idle": float(i // 4),
        })
        records.append({"kind": "train", "ts": ts + 0.002, "step": 8 * i, "loss": 0.5})
    with open(work / "metrics" / "metrics.jsonl", "w") as f:
        f.write("".join(json.dumps(r) + "\n" for r in records) + '{"kind": "coun')
    monkeypatch.setattr(runfiles, "ROOT", str(tmp_path))
    # the window: train reports 2..8 (their counter records 3..8 lie inside)
    train_ts = [100.0 + i + 0.002 for i in range(2, 9)]
    ctx = {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 1,
           "window": {"ts": train_ts}}
    return ctx, work


#: The ten metrics PR 24 added on those three readers, by name: other cells'
#: metrics name the same readers since, and are none of this list's business.
PR24 = [
    "idle_under_ingest_ms_task.ex", "idle_under_master_ms_task.ex", "idle_under_loop_ms_task.ex",
    "idle_unattributed_ms_task.ex", "starved_dispatch_pct.ex", "starved_dispatch_pct.tok",
    "compiles_in_window.ex", "compiles_in_window.tok", "hbm_peak_reported_gib.ex", "hbm_peak_reported_gib.tok",
]


def _read(name, ctx):
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file(name)
    return bench.reader(spec["reader"]).read(ctx, spec.get("params", {}))


def test_counter_readers_on_hand_made_records(run):
    ctx, _ = run
    assert [r["step"] for r in runfiles.counter_records(ctx)] == [24, 32, 40, 48, 56, 64]
    # idle 0 -> 2 while dispatches 5 -> 10
    assert _read("starved_dispatch_pct.ex", ctx) == pytest.approx(100 * 2 / 5)
    assert _read("compiles_in_window.ex", ctx) == 0.0  # the sixth compile came after the window
    assert _read("hbm_peak_reported_gib.ex", ctx) == pytest.approx(3.5)
    wider = dict(ctx, window={"ts": [100.0, 110.0]})
    assert _read("compiles_in_window.ex", wider) == 1.0


@pytest.mark.parametrize("ctx", [
    {},
    {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 4, "window": {"ts": [0.0, 1e12]}},
    {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "nope"}, "chips": 1, "window": {"ts": [0.0, 1e12]}},
    {"config": {"name": "gpt2_medium"}, "traffic": {"name": "job_seq1k"}, "chips": 1, "window": {"ts": [0.0, 1e12]}},
    {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 1, "window": {"ts": [100.0]}},
    {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 1, "window": {"ts": [500.0, 600.0]}},
], ids=["bare", "no_such_cell", "no_such_traffic", "cell_without_a_run", "one_report", "no_records_in_window"])
def test_new_readers_report_nothing_when_there_is_nothing_to_read(run, ctx):
    bench = resolve.Bench(ROOT)
    using = [e["name"] for e in bench.spec["per_layer"] if bench.metric_file(e["name"])["reader"]
             in ("idle_under_spans", "counter_delta", "counter_last")]
    assert set(PR24) <= set(using)
    for name in using:  # PR 24's ten, and every metric a later PR pointed at those readers
        assert _read(name, ctx) is None, name


def test_a_counter_that_did_not_grow_gives_no_ratio(run):
    ctx, _ = run
    narrow = dict(ctx, window={"ts": [100.0, 100.5]})  # one counter record
    assert _read("starved_dispatch_pct.ex", narrow) is None
    assert _read("hbm_peak_reported_gib.ex", narrow) == pytest.approx(3.0)


def test_idle_metrics_of_a_cell_add_up_to_the_traces_idle_time(run, expected):
    ctx, work = run
    profile = work / "profile" / "plugins" / "profile" / "2026_01_01"
    profile.mkdir(parents=True)
    shutil.copy(TRACE, profile / "vm.xplane.pb")
    names = ["idle_under_ingest_ms_task.ex", "idle_under_master_ms_task.ex",
             "idle_under_loop_ms_task.ex", "idle_unattributed_ms_task.ex"]
    values = {name: _read(name, ctx) for name in names}
    assert all(v is not None and v >= 0 for v in values.values())
    assert sum(values.values()) == pytest.approx(expected["idle_ns"] / 1e6 / expected["tasks"], rel=2e-4)
    assert values["idle_unattributed_ms_task.ex"] == pytest.approx(expected["rest_ns"] / 1e6 / expected["tasks"], rel=2e-4)
    assert values["idle_under_ingest_ms_task.ex"] == pytest.approx(expected["under_ns"]["prep_wait"] / 1e6 / expected["tasks"], rel=2e-4)


def test_new_metric_files_say_what_benchmark_json_says():
    """PR 24's four idle metrics (a later cell's twins of them are its own
    test's business)."""
    bench = resolve.Bench(ROOT)
    known, claimed = None, []
    for name in PR24[:4]:
        (entry,) = [e for e in bench.spec["per_layer"] if e["name"] == name]
        spec = bench.metric_file(name)
        assert spec["reader"] == "idle_under_spans"
        assert entry["workloads"] == ["deepfm_job"] and entry["source"] == "program_span"
        # one universe of names for the innermost rule, or the four would not add up
        known = known or spec["params"]["known"]
        assert spec["params"]["known"] == known
        assert spec["params"].get("rest") or set(spec["params"]["spans"]) <= set(known)
        claimed += spec["params"].get("spans", [])
    assert sorted(claimed) == sorted(known)
