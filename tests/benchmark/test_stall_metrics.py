"""The six stall metrics PR 54 added (``stalls_in_window``, ``stall_ms_dispatch``,
``stall_unnamed_ms_dispatch``, each ``.ex`` and ``.tok``): files and entries only,
on the reader that was there (``counter_delta``), read from the ``counter``
records of the whole report window.  They read 0, never nothing, in a window
without a stall; nothing on the records of a program that lacks the counters
(the parent's).  CPU only, hand-made records."""

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

EX = ["deepfm_job", "deepfm_x4_job", "deepfm_job_zipf"]
TOK = ["gpt2m_job", "olmoe_job", "kanana2_job", "evabyte_job"]
#: joined since, at the end: ``trinity_mini_job`` (PR 56), ``keye_vl2_job`` (PR 58) with their cells; ``nemotron3_job`` and
#: ``kimi_linear_job`` in PR 63 (the recorder runs in every worker loop; their tests' ``JOINED`` waited for a ``benchmark`` PR)
LATER_TOK = ["trinity_mini_job", "keye_vl2_job", "nemotron3_job", "kimi_linear_job"]
#: metric -> (unit, the reader's parameters)
STALL_METRICS = {
    "stalls_in_window": ("count", {"counter": "stalls", "scale": 1}),
    "stall_ms_dispatch": ("ms", {"counter": "stall_s", "over": "dispatches", "scale": 1000}),
    "stall_unnamed_ms_dispatch": ("ms", {"counter": "stall_unnamed_s", "over": "dispatches", "scale": 1000}),
}
NAMES = [base + suffix for base in STALL_METRICS for suffix in (".ex", ".tok")]


def _records(stalled_at=None, excess_s=0.0, unnamed=False, with_keys=True):
    """Ten reports a second apart; the stall's excess is counted by the
    report that ends its gap (``stalled_at``) and by every later one."""
    records = []
    for i in range(10):
        ts = 100.0 + i
        counter = {"kind": "counter", "ts": ts + 0.001, "step": 8 * i, "compiles": 5.0, "dispatches": 2.0 + i}
        if with_keys:
            hit = stalled_at is not None and i >= stalled_at
            counter.update(stalls=float(hit), stall_s=excess_s * hit, stall_unnamed_s=excess_s * hit * unnamed)
        records.append(counter)
        records.append({"kind": "train", "ts": ts + 0.002, "step": 8 * i, "loss": 0.5})
    return records


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """The last run of ``deepfm_job`` and of ``gpt2m_job`` in a checkout's
    place; ``write(records)`` puts the same records into both."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    monkeypatch.setattr(runfiles, "ROOT", str(tmp_path))
    works = []
    for cell in ("deepfm_job", "gpt2m_job"):
        work = tmp_path / "benchmark" / ".state" / "runs" / cell
        (work / "metrics").mkdir(parents=True)
        works.append(work)

    def write(records):
        for work in works:
            with open(work / "metrics" / "metrics.jsonl", "w") as f:
                f.write("".join(json.dumps(r) + "\n" for r in records))

    window = {"ts": [100.0 + i + 0.002 for i in range(1, 10)]}  # reports 1..9: counter records 2..9
    ctxs = {
        ".ex": {"config": {"name": "deepfm_criteo"}, "traffic": {"name": "job_uniform_8k"}, "chips": 1, "window": window},
        ".tok": {"config": {"name": "gpt2_medium"}, "traffic": {"name": "job_seq1k"}, "chips": 1, "window": window},
    }
    return write, ctxs


def _read(name, ctxs):
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file(name)
    return bench.reader(spec["reader"]).read(ctxs[name[name.rindex("."):]], spec.get("params", {}))


@pytest.mark.parametrize("name", NAMES)
def test_a_window_without_a_stall_reads_zero_not_nothing(run, name):
    write, ctxs = run
    write(_records())
    value = _read(name, ctxs)
    assert value == 0.0 and isinstance(value, float)


@pytest.mark.parametrize("name", NAMES)
def test_a_stall_inside_the_window_is_counted_and_spread_over_the_windows_dispatches(run, name):
    write, ctxs = run
    write(_records(stalled_at=5, excess_s=1.4, unnamed=name.startswith("stall_unnamed")))
    # counter records 2..9 lie inside: dispatches grow 4.0 -> 11.0
    want = {"stalls_in_window": 1.0, "stall_ms_dispatch": 1400.0 / 7, "stall_unnamed_ms_dispatch": 1400.0 / 7}
    assert _read(name, ctxs) == pytest.approx(want[name[:name.rindex(".")]])
    # the metric times dispatches is the seconds lost: what the report clock sees as the long gap's excess
    if name.startswith("stall_ms"):
        assert _read(name, ctxs) * 7 / 1e3 == pytest.approx(1.4)


def test_a_named_stall_leaves_the_closure_at_zero_and_one_before_the_window_is_not_the_windows(run):
    write, ctxs = run
    write(_records(stalled_at=5, excess_s=1.4, unnamed=False))
    assert _read("stall_unnamed_ms_dispatch.ex", ctxs) == 0.0 and _read("stall_ms_dispatch.ex", ctxs) > 0
    write(_records(stalled_at=2, excess_s=3.0, unnamed=True))  # counted by the window's FIRST record already
    assert [_read(name, ctxs) for name in NAMES] == [0.0] * 6


@pytest.mark.parametrize("name", NAMES)
def test_records_of_a_program_without_the_counters_read_nothing_and_raise_nothing(run, name):
    write, ctxs = run
    write(_records(with_keys=False))  # the parent commit's counter records
    assert _read(name, ctxs) is None
    write([])
    assert _read(name, ctxs) is None


@pytest.mark.parametrize("name", NAMES)
def test_the_entries_say_what_their_files_say(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    spec = bench.metric_file(name)
    base, suffix = name[:name.rindex(".")], name[name.rindex("."):]
    unit, params = STALL_METRICS[base]
    assert spec["reader"] == "counter_delta" and spec["params"] == params and spec["name"] == name
    assert (spec["unit"], spec["better"], spec["source"], spec["layer"]) == (unit, "lower", "program_counter", "worker loop")
    for key in ("unit", "layer", "moves", "better", "source"):
        assert entry[key] == spec[key], key
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    moves, cells = {".ex": ("examples_per_s_chip", EX), ".tok": ("tokens_per_s_chip", TOK)}[suffix]
    assert entry["moves"] == moves and entry["workloads"][:len(cells)] == cells  # a later cell joins at the end
    if suffix == ".tok":
        assert entry["workloads"][len(cells):len(cells) + len(LATER_TOK)] == LATER_TOK
    # every listed cell reports the end-to-end metric the entry moves, and resolves the entry
    for cell in cells:
        assert moves in [m["name"] for m in bench.metrics_of(cell, "end_to_end")]
        assert name in [m["name"] for m in bench.metrics_of(cell, "per_layer")]


def test_the_six_were_appended_together_and_the_counters_are_the_workers_own():
    bench = resolve.Bench(ROOT)
    names = [m["name"] for m in bench.spec["per_layer"]]
    first = names.index(NAMES[0])
    # after what PR 53 left (98 entries then; PR 63's fold took fifteen from before them) and before what later PRs brought
    assert names[first:first + 6] == NAMES and names.index("kda_chunked_pct.kda") < first < names.index("window_attn_ms_step.swa")
    assert len(names) <= 128
    from elasticdl_tpu.worker.worker import COUNTER_GAUGES

    for _, params in STALL_METRICS.values():
        assert params["counter"] in COUNTER_GAUGES and params.get("over", "dispatches") in COUNTER_GAUGES
