"""Tests of the benchmark's yardstick (``benchmark/``): CPU only, seconds
long.  They hold what a later PR may not change: the report-to-report
rate, the trace reduction, the resolver that finds a cell's files by name,
the traffic generator's wire formats and ``BENCHMARK.json``'s contract.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import clock  # noqa: E402
import datagen  # noqa: E402
import resolve  # noqa: E402
import xplane  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "deepfm_two_steps.xplane.pb")


# ---------------------------------------------------------------- clock


def _records(ts_list, steps_per_task=8, start_step=8):
    return [
        {"kind": "train", "ts": ts, "step": start_step + i * steps_per_task, "loss": 0.5}
        for i, ts in enumerate(ts_list)
    ]


def test_rate_is_work_after_the_first_report_over_the_report_span():
    train = _records([100.0 + 0.25 * i for i in range(41)])
    out = clock.report_rate(train, units_per_step=8192)
    assert out["reports"] == 41 and out["steps"] == 320
    assert out["span_s"] == pytest.approx(10.0)
    assert out["rate"] == pytest.approx(320 * 8192 / 10.0)
    assert out["gap_max_s"] == pytest.approx(0.25)


@pytest.mark.parametrize("n_reports", [40, 41, 42])
def test_a_task_more_or_less_in_the_window_does_not_move_the_rate(n_reports):
    # PR 22's rate had a one-task quantum (1 % of a 32 s window); this one
    # has none: numerator and denominator move together.
    train = _records([7.0 + 0.317 * i for i in range(n_reports)])
    out = clock.report_rate(train, units_per_step=8192)
    assert out["rate"] == pytest.approx(8 * 8192 / 0.317, rel=1e-9)


def test_a_task_length_stall_shows_in_the_gap_and_costs_its_own_time_only():
    ts = [0.317 * i for i in range(50)]
    stalled = ts[:25] + [t + 0.317 for t in ts[25:]]
    steady = clock.report_rate(_records(ts), 8192)
    out = clock.report_rate(_records(stalled), 8192)
    assert out["gap_max_s"] == pytest.approx(2 * 0.317)
    assert out["gap_median_s"] == pytest.approx(0.317)
    assert out["rate"] / steady["rate"] == pytest.approx(49 / 50, rel=1e-9)


@pytest.mark.parametrize("n", [0, 1])
def test_fewer_than_two_reports_give_no_rate(n):
    out = clock.report_rate(_records([5.0] * n), 8192)
    assert out["rate"] is None and out["reports"] == n


def test_window_selection_and_phase_delta(tmp_path):
    records = []
    for i in range(10):
        records.append({"kind": "phase", "ts": 10.0 + i, "step": 8 * i, "prep_wait": 0.1 * i, "dispatch": 1.0 + 0.5 * i})
        records.append({"kind": "train", "ts": 10.0 + i + 0.001, "step": 8 * i, "loss": float("nan") if i == 4 else 0.6})
    train = clock.window_records(records, "train", 12.0, 17.5)
    assert [r["step"] for r in train] == [16, 24, 32, 40, 48, 56]
    assert clock.nonfinite_losses(train) == 1
    delta = clock.phase_delta(clock.window_records(records, "phase", 12.0, 17.5))
    assert delta["prep_wait"] == pytest.approx(0.5) and delta["dispatch"] == pytest.approx(2.5)
    assert clock.phase_delta([]) == {}


# ------------------------------------------------------- trace reduction


def _brute_union_ns(events):
    """Independent of xplane.busy_and_gaps: sweep over the sorted end
    points counting open intervals."""
    points = sorted([(s, 1) for s, _, _ in events] + [(e, -1) for _, e, _ in events], key=lambda p: (p[0], -p[1]))
    busy, depth, last = 0.0, 0, None
    for t, d in points:
        if depth > 0:
            busy += t - last
        depth += d
        last = t
    return busy


def test_busy_union_and_self_times_on_a_hand_made_line():
    events = [(0, 100, "while.1"), (0, 10, "a.1"), (10, 30, "fusion.2"), (40, 60, "fusion.3"), (120, 130, "b")]
    reduced = xplane.busy_and_gaps(events)
    assert reduced["busy_ns"] == 110 and reduced["span_ns"] == 130
    assert reduced["gaps"] == [(100, 20)]
    assert xplane.self_times(events) == {"a": 10, "fusion": 40, "while": 50, "b": 10}
    assert xplane.kernel_events(events, r"^fusion") == [20, 20]
    assert xplane.busy_and_gaps([]) == {"busy_ns": 0.0, "span_ns": 0.0, "gaps": []}


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(TRACE):
        pytest.skip("the recorded trace is not in this checkout")
    return xplane.load_op_events(TRACE)


def test_recorded_trace_has_one_tpu_plane_with_op_events(recorded):
    assert list(recorded) == ["/device:TPU:0"]
    events = recorded["/device:TPU:0"]
    expected = json.load(open(os.path.join(HERE, "data", "deepfm_two_steps.expected.json")))
    assert len(events) == expected["events"]
    assert all(end >= start for start, end, _ in events)


def test_recorded_trace_busy_union_matches_an_independent_sweep(recorded):
    events = recorded["/device:TPU:0"]
    reduced = xplane.busy_and_gaps(events)
    assert reduced["busy_ns"] == pytest.approx(_brute_union_ns(events), rel=1e-12)
    gap_total = sum(g for _, g in reduced["gaps"])
    assert reduced["busy_ns"] + gap_total == pytest.approx(reduced["span_ns"], rel=1e-12)


def test_recorded_trace_summary_numbers(recorded):
    expected = json.load(open(os.path.join(HERE, "data", "deepfm_two_steps.expected.json")))
    summary = xplane.summarize(TRACE)
    assert summary["devices"] == 1
    assert summary["busy_s"] == pytest.approx(expected["busy_s"], rel=1e-9)
    assert summary["span_s"] == pytest.approx(expected["span_s"], rel=1e-9)
    # ProfileData hands out whole nanoseconds; the file holds picoseconds
    assert summary["busy_s"] == pytest.approx(expected["busy_s_from_proto_picoseconds"], rel=1e-5)
    assert [round(s, 6) for _, s in summary["modules"]] == [0.219831, 0.274833]
    assert xplane.step_seconds(summary, "jit_local_scan", 8) == pytest.approx(0.274833238 / 8)
    idle = 1 - summary["busy_s"] / summary["span_s"]
    assert idle == pytest.approx(expected["idle_share"], abs=1e-9)
    assert all(where in ("inside_program", "between_programs") for where, _ in summary["idle_gaps"])
    ops = dict(summary["device_ops"])
    for name, seconds in expected["device_ops"].items():
        assert ops[name] == pytest.approx(seconds, rel=1e-9)
    # own times partition busy time: nothing counted twice under the while
    total_self = sum(xplane.self_times(recorded["/device:TPU:0"]).values())
    assert total_self / 1e9 == pytest.approx(summary["busy_s"], rel=1e-9)


def test_recorded_trace_named_kernel_time(recorded):
    expected = json.load(open(os.path.join(HERE, "data", "deepfm_two_steps.expected.json")))
    durations = xplane.kernel_events(recorded["/device:TPU:0"], expected["kernel"]["pattern"])
    assert len(durations) == expected["kernel"]["count"]
    assert sum(durations) / 1e9 == pytest.approx(expected["kernel"]["seconds"], rel=1e-9)


# -------------------------------------------------------------- resolver


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    shutil.copytree(
        BENCH_DIR, tmp_path / "benchmark",
        ignore=shutil.ignore_patterns(".state", "__pycache__"),
    )
    return tmp_path


def test_resolver_finds_every_cell_of_the_committed_benchmark():
    bench = resolve.Bench(ROOT)
    for cell in bench.spec["workloads"]:
        config = bench.config(cell["config"])
        traffic = bench.traffic(cell["traffic"])
        assert os.path.isfile(bench.reference_path(cell["config"]))
        costs = bench.costs(config["costs"]).compute(config, traffic)
        assert all(v >= 0 for v in costs.values())
        for entry in bench.metrics_of(cell["name"], "per_layer"):
            spec = bench.metric_file(entry["name"])
            assert callable(bench.reader(spec["reader"]).read)
            for key in ("unit", "layer", "moves", "better", "source"):
                assert spec[key] == entry[key], (entry["name"], key)
            # a metric's cells are its entry's `workloads` alone since PR 39 (test_renamed_metrics.py)


def test_new_config_traffic_metric_and_cell_are_added_as_files_only(copy):
    """A later PR adds a configuration, a traffic mix, a per-layer metric
    (with a reader of its own) and a cell without editing a file that is
    there: new files, new BENCHMARK.json entries."""
    bdir = copy / "benchmark"
    before = _contents(bdir)
    config = json.load(open(bdir / "configs" / "deepfm_criteo.json"))
    config["model_params"]["buckets_per_feature"] = 2097152
    json.dump(config, open(bdir / "configs" / "deepfm_criteo_x4.json", "w"))
    shutil.copy(bdir / "configs" / "deepfm_criteo_reference.py", bdir / "configs" / "deepfm_criteo_x4_reference.py")
    traffic = json.load(open(bdir / "traffic" / "job_uniform_8k.json"))
    traffic["generator"]["ids"] = {"kind": "zipf", "support": 786432, "exponent": 1.05}
    json.dump(traffic, open(bdir / "traffic" / "job_zipf_8k.json", "w"))
    (bdir / "readers" / "reports_in_window.py").write_text(
        "def read(ctx, params):\n    return float(ctx['window']['reports']) * params['scale']\n"
    )
    json.dump(
        {"name": "reports.zipf", "unit": "tasks", "better": "higher", "source": "program_counter",
         "layer": "master", "moves": "examples_per_s_chip",
         "reader": "reports_in_window", "params": {"scale": 2}},
        open(bdir / "metrics" / "reports.zipf.json", "w"),
    )
    spec = json.load(open(copy / "BENCHMARK.json"))
    spec["configs"].append({"name": "deepfm_criteo_x4", "source": "test", "file": "benchmark/configs/deepfm_criteo_x4.json", "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "deepfm_x4_zipf", "config": "deepfm_criteo_x4", "traffic": "job_zipf_8k", "chips": 4, "why": "test"})
    spec["per_layer"].append({"name": "reports.zipf", "unit": "tasks", "better": "higher", "source": "program_counter", "layer": "master", "moves": "examples_per_s_chip", "workloads": ["deepfm_x4_zipf"]})
    next(m for m in spec["end_to_end"] if m["name"] == "examples_per_s_chip")["workloads"].append("deepfm_x4_zipf")
    json.dump(spec, open(copy / "BENCHMARK.json", "w"))

    bench = resolve.Bench(str(copy))
    cell = bench.cell("deepfm_x4_zipf")
    assert bench.config(cell["config"])["model_params"]["buckets_per_feature"] == 2097152
    assert bench.traffic(cell["traffic"])["generator"]["ids"]["kind"] == "zipf"
    assert bench.reference_path(cell["config"]).endswith("deepfm_criteo_x4_reference.py")
    names = [m["name"] for m in bench.metrics_of("deepfm_x4_zipf", "per_layer")]
    assert names == ["reports.zipf"]
    metric = bench.metric_file("reports.zipf")
    value = bench.reader(metric["reader"]).read({"window": {"reports": 21}}, metric["params"])
    assert value == 42.0
    assert [m["name"] for m in bench.metrics_of("deepfm_x4_zipf", "end_to_end")] == ["examples_per_s_chip", "setup_s"]
    # nothing that was there has changed
    after = _contents(bdir)
    assert [rel for rel in before if after[rel] != before[rel]] == []


# ------------------------------------------- the benchmark grows by files

#: Set in the environment of the test runs the rehearsal below starts, so
#: that the copy's own rehearsal does not start them again.
GROWING = "EDL_BENCH_GROWTH_REHEARSAL"

#: What later ``model_config`` PRs do to whatever tree this is: (how many
#: cells an earlier PR has already added to it, the cells added then).  An
#: added cell is (its name, the cell it is made like, chips, whether it
#: brings a configuration of its own); a four-chip cell that the quota of
#: the grown benchmark has no room for is added on one chip, like
#: ``deepfm_job_zipf``, instead.  Nothing here counts today's cells, and a
#: cell is added by PR 39's rule, as every accepted PR since has added one:
#: ONE ``per_layer`` entry and one file a METRIC, its cells in the entry's
#: ``workloads``, joined by appending; a (metric, cell) pair is never an
#: entry of its own.
ONE_MORE = [("added_lm_job", "gpt2m_job", 1, True)]
GROWTH = {
    "one_more": (0, ONE_MORE),
    "three_more_of_which_a_four_chip_cell": (0, ONE_MORE + [("added_ctr_job", "deepfm_job", 1, False), ("added_x4_job", "deepfm_x4_job", 4, True)]),
    "one_more_on_a_tree_that_has_grown": (1, ONE_MORE),
}
EARLIER = [("earlier_moe_job", "olmoe_job", 1, True)]
#: The ``per_layer`` entries, each with its file, that a cell with a
#: configuration of its own brings for what no cell had: the most any
#: accepted ``model_config`` PR has brought or planned (PR 33 eight ``.mla``,
#: PR 37 six ``.eva``, PR 40 eight ``.ssm`` planned).  A cell on a
#: configuration the benchmark has brings none.
OWN = 8


def quota(n_cells: int) -> int:
    return max(1, n_cells // 4)


def placed(added: list, n0: int, four0: int) -> list:
    """``added`` as a tree of ``n0`` cells, ``four0`` of them on four chips,
    takes it: a four-chip cell the grown quota has no room for is made like
    ``deepfm_job_zipf`` on one chip instead."""
    fits = four0 + 1 <= quota(n0 + len(added))
    return [(name, like, chips, own) if chips == 1 or fits else (name, "deepfm_job_zipf", 1, own) for name, like, chips, own in added]


def add_cell_like(root, name: str, like: str, chips: int, own_config: bool, tag: str) -> dict:
    """Add the cell ``name`` to the checkout at ``root`` by NEW files and
    ``BENCHMARK.json`` entries alone: a traffic file (whose ``job_flags``
    use the run's ``{work}`` directory), where ``own_config`` a
    configuration with its reference.  Per-layer metrics by PR 39's rule,
    one entry and one file a METRIC: the cell JOINS every entry ``like``
    reports, generic or not, by appending its name to the entry's
    ``workloads`` (it is made like that cell, so the reader and the
    parameters are the same), and to its rate metric's.  A cell with a
    configuration of its own also BRINGS ``OWN`` entries with their files
    under names no entry has, as a new architecture brings what no cell had:
    ``<metric>.<tag>`` for the entries of ``like`` that only some cells
    report, made up to ``OWN`` under further names (``<metric>_2.<tag>``)
    where ``like`` has fewer.  No file that is there is written.  Returns
    the cell's entry."""
    root = str(root)
    spec_path = os.path.join(root, "BENCHMARK.json")
    spec = json.load(open(spec_path))
    bdir = os.path.join(root, spec["paths"][0])
    (old,) = [w for w in spec["workloads"] if w["name"] == like]
    traffic = json.load(open(os.path.join(bdir, "traffic", old["traffic"] + ".json")))
    traffic["job_flags"] = {"checkpoint_dir": "{work}/ckpt", "checkpoint_steps": 64}
    json.dump(traffic, open(os.path.join(bdir, "traffic", f"job_{tag}.json"), "x"))
    config = old["config"]
    if own_config:
        (entry,) = [c for c in spec["configs"] if c["name"] == old["config"]]
        config, stem = f"config_{tag}", entry["file"][: -len(".json")]
        new_stem = os.path.join(os.path.dirname(entry["file"]), config)
        shutil.copy(os.path.join(root, entry["file"]), os.path.join(root, new_stem + ".json"))
        shutil.copy(os.path.join(root, stem + "_reference.py"), os.path.join(root, new_stem + "_reference.py"))
        spec["configs"].append(dict(entry, name=config, file=new_stem + ".json", source="test: " + entry["source"][:150]))
    moved = {m["name"]: m.get("workloads", [w["name"] for w in spec["workloads"]]) for m in spec["end_to_end"]}
    cell = {"name": name, "config": config, "traffic": f"job_{tag}", "chips": chips, "why": "test: a cell a later PR adds"}
    spec["workloads"].append(cell)
    reported = [m for m in spec["per_layer"] if like in m["workloads"]]
    # what only some cells report is what a new architecture has its own of; a metric every cell judged on its rate reports is never copied
    models = [m for m in reported if not set(moved[m["moves"]]) <= set(m["workloads"])] or reported
    for metric in reported:
        metric["workloads"].append(name)
    for i in range(OWN if own_config else 0):
        metric, again = models[i % len(models)], i // len(models)
        twin = metric["name"].rsplit(".", 1)[0] + (f"_{again + 1}" if again else "") + f".{tag}"
        described = json.load(open(os.path.join(bdir, "metrics", metric["name"] + ".json")))
        json.dump(dict(described, name=twin), open(os.path.join(bdir, "metrics", twin + ".json"), "x"))
        spec["per_layer"].append(dict(metric, name=twin, workloads=[name]))
    next(m for m in spec["end_to_end"] if m["name"] == traffic["rate_metric"])["workloads"].append(name)
    json.dump(spec, open(spec_path, "w"), indent=1)
    return cell


def _contents(root) -> dict:
    out = {}
    for base, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d not in (".state", "__pycache__")]
        for f in files:
            path = os.path.join(base, f)
            out[os.path.relpath(path, root)] = open(path, "rb").read()
    return out


@pytest.mark.skipif(GROWING in os.environ, reason="this IS the grown copy's run")
@pytest.mark.parametrize("stage", sorted(GROWTH, reverse=True))
def test_every_benchmark_test_stays_green_when_later_prs_add_cells(tmp_path, stage):
    """The benchmark's own tests, ALL modules of ``tests/benchmark/``, run
    against a copy of the checkout to which later PRs have added cells as
    new files + ``BENCHMARK.json`` entries: one more one-chip cell with a
    configuration of its own; three more, of which one takes four chips
    where the grown benchmark's quota admits it; and one more on a tree an
    earlier PR has already grown.  Every count is taken from the tree the
    copy was made of, so this test holds on the trees those PRs leave.  A
    test that pins the number of cells, the list of four-chip cells, the
    metrics that name a reader or the metrics of a cell it does not own goes
    red here, before it stops a PR that may not edit it."""
    copy = tmp_path / "checkout"
    (copy / "tests").mkdir(parents=True)
    for name in ("BENCHMARK.json", "pyproject.toml"):
        shutil.copy(os.path.join(ROOT, name), copy / name)
    ignore = shutil.ignore_patterns(".state", "__pycache__")
    shutil.copytree(BENCH_DIR, copy / "benchmark", ignore=ignore)
    shutil.copytree(HERE, copy / "tests" / "benchmark", ignore=ignore)
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), copy / "tests" / "conftest.py")
    os.symlink(os.path.join(ROOT, "elasticdl_tpu"), copy / "elasticdl_tpu")  # the system under test: not copied, not changed
    earlier, added = GROWTH[stage]
    for i, (name, like, chips, own_config) in enumerate(EARLIER[:earlier]):
        add_cell_like(copy, name, like, chips, own_config, tag=f"was{i}")
    tree = json.load(open(copy / "BENCHMARK.json"))["workloads"]
    n0, four0 = len(tree), sum(w["chips"] == 4 for w in tree)
    before = _contents(copy)
    for i, (name, like, chips, own_config) in enumerate(placed(added, n0, four0)):
        add_cell_like(copy, name, like, chips, own_config, tag=f"add{i}")
    after = _contents(copy)
    changed = sorted(rel for rel in before if after[rel] != before[rel])
    assert changed == ["BENCHMARK.json"] and len(after) > len(before)

    # the grown benchmark resolves, keeps the quota, and a run's flags hold the run's own directory
    import job

    bench = resolve.Bench(str(copy))
    cells = bench.spec["workloads"]
    assert len(cells) == n0 + len(added) and [w["name"] for w in cells[:n0]] == [w["name"] for w in tree]
    assert four0 <= sum(w["chips"] == 4 for w in cells) <= quota(len(cells))
    cell = bench.cell("added_lm_job")
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    argv = job.job_argv(config, traffic, "/data", "/runs/added_lm_job", {})
    assert argv[argv.index("--checkpoint_dir") + 1] == "/runs/added_lm_job/ckpt" and "{work}" not in " ".join(argv)
    assert argv[argv.index("--checkpoint_steps") + 1] == "64"
    assert len(bench.metrics_of("added_lm_job", "per_layer")) == len(bench.metrics_of("gpt2m_job", "per_layer")) + OWN
    # it joined every entry its model cell reports and brought its own, and the contract's 128 entries still hold them all
    reported = bench.metrics_of("added_lm_job", "per_layer")
    joined = {m["name"] for m in reported if len(m["workloads"]) > 1}
    assert {"step_ms.tok", "mfu_pct.tok", "setup_compile_s", "flash_roofline_pct.tok"} <= joined
    assert "flash_roofline_pct.add0" in {m["name"] for m in reported} - joined
    assert len(bench.spec["per_layer"]) <= 128

    # ... and every module under tests/benchmark/ is green on it.  The whole-job rehearsals (45 s
    # each) run in the first stage only: they read nothing of how many cells there are.
    argv = [sys.executable, "-m", "pytest", "tests/benchmark", "-q", "-m", "not slow", "-p", "no:cacheprovider", "-p", "no:randomly"]
    if stage != "one_more":
        argv += ["-k", "not test_rehearsal_"]
    env = dict(os.environ, **{GROWING: stage})
    env.pop("PYTEST_XDIST_WORKER", None)
    done = subprocess.run(argv, cwd=copy, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]


@pytest.mark.parametrize("stage", sorted(GROWTH))
def test_an_added_cell_joins_what_its_model_cell_reports_and_brings_own_entries_only_with_a_configuration(copy, stage):
    """The rehearsal's count, held apart from the run above: a cell with a
    configuration of its own brings exactly ``OWN`` entries with their
    files, a cell on a configuration the benchmark has brings none, either
    reports what its model cell reports by being appended to those entries'
    ``workloads``, and nothing that was there but ``BENCHMARK.json`` is
    written.  (Names and tags of its own: on a grown copy ``add0`` is taken.)"""
    earlier, added = GROWTH[stage]
    for i, (name, like, chips, own_config) in enumerate(EARLIER[:earlier]):
        add_cell_like(copy, "counted_" + name, like, chips, own_config, tag=f"had{i}")
    spec0 = json.load(open(copy / "BENCHMARK.json"))
    n0, four0 = len(spec0["workloads"]), sum(w["chips"] == 4 for w in spec0["workloads"])
    before = _contents(copy)
    cells = [("counted_" + name, like, chips, own_config) for name, like, chips, own_config in placed(added, n0, four0)]
    for i, (name, like, chips, own_config) in enumerate(cells):
        add_cell_like(copy, name, like, chips, own_config, tag=f"cnt{i}")
    after = _contents(copy)
    assert sorted(rel for rel in before if after[rel] != before[rel]) == ["BENCHMARK.json"]
    bench = resolve.Bench(str(copy))
    names0 = [m["name"] for m in spec0["per_layer"]]
    brought = [m for m in bench.spec["per_layer"] if m["name"] not in names0]
    assert [m["name"] for m in bench.spec["per_layer"][: len(names0)]] == names0  # appended: no entry went or moved
    assert len(brought) == OWN * sum(own_config for _, _, _, own_config in cells)
    assert sum(rel.startswith(os.path.join("benchmark", "metrics")) for rel in after if rel not in before) == len(brought)
    for name, like, chips, own_config in cells:
        own = [m for m in brought if m["workloads"] == [name]]  # what a cell brings is its own alone until a later cell joins it
        assert len(own) == len({m["name"] for m in own}) == (OWN if own_config else 0), (name, own)
        assert bench.metrics_of(name, "per_layer") == bench.metrics_of(like, "per_layer") + own
        for entry in own:  # an entry of its own says what its file says, as every entry does
            described = bench.metric_file(entry["name"])
            assert all(described[key] == entry[key] for key in ("unit", "layer", "moves", "better", "source")) and "cells" not in described


@pytest.mark.parametrize("what,name", [("cell", "nope"), ("traffic", "nope"), ("metric_file", "nope"), ("peaks", "TPU v9")])
def test_unknown_names_are_errors_not_defaults(what, name):
    with pytest.raises(resolve.ResolveError):
        getattr(resolve.Bench(ROOT), what)(name)


# ------------------------------------------------------- BENCHMARK.json


def _spec():
    return json.load(open(os.path.join(ROOT, "BENCHMARK.json")))


def test_benchmark_json_has_exactly_the_contract_keys():
    spec = _spec()
    assert sorted(spec) == sorted(["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(spec["run_seconds"], int) and 1 <= spec["run_seconds"] <= 51
    assert 1 <= len(spec["paths"]) <= 16 and len(spec["command"]) <= 32
    # a full check with the full 24 cells fits the driver's budget
    cells = 24
    assert (2 + 14 * cells) * (spec["run_seconds"] + 60) + cells * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines_keep_to_the_allowed_characters():
    spec = _spec()
    names = []
    for group, keys in (
        ("configs", {"name", "source", "file", "reduced", "why"}),
        ("workloads", {"name", "config", "traffic", "chips", "why"}),
    ):
        for entry in spec[group]:
            assert set(entry) == keys, entry
            names.append(entry["name"])
            assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"] and "\t" not in entry["why"]
    for entry in spec["configs"]:
        assert 1 <= len(entry["source"]) <= 200
        assert entry["file"].startswith(tuple(p + "/" for p in spec["paths"]))
        assert len(entry["reduced"]) <= 16 and all(resolve.NAME_RE.match(k) for k in entry["reduced"])
    for entry in spec["workloads"]:
        assert resolve.NAME_RE.match(entry["config"]) and resolve.NAME_RE.match(entry["traffic"])
        assert entry["chips"] in (1, 4)
    for entry in spec["end_to_end"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert entry["source"] in ("host_clock", "device_trace")
        assert 0.01 <= entry["bound"] <= 0.1
    for entry in spec["per_layer"]:
        assert set(entry) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert 1 <= len(entry["layer"]) <= 200
    for entry in spec["end_to_end"] + spec["per_layer"]:
        names.append(entry["name"])
        assert resolve.UNIT_RE.match(entry["unit"]), entry
        assert entry["better"] in ("lower", "higher")
    for name in names:
        assert resolve.NAME_RE.match(name), name
    metric_names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len({w["name"] for w in spec["workloads"]}) == len(spec["workloads"])
    assert len({(w["config"], w["traffic"]) for w in spec["workloads"]}) == len(spec["workloads"])
    four = sum(1 for w in spec["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(spec["workloads"]) // 4)


def test_every_cell_reports_setup_another_end_to_end_and_a_per_layer_metric():
    bench = resolve.Bench(ROOT)
    e2e_names = {m["name"] for m in bench.spec["end_to_end"]}
    assert "setup_s" in e2e_names
    used = {w["config"] for w in bench.spec["workloads"]}
    assert used == {c["name"] for c in bench.spec["configs"]}
    for cell in bench.spec["workloads"]:
        e2e = [m["name"] for m in bench.metrics_of(cell["name"], "end_to_end")]
        assert "setup_s" in e2e and len(e2e) >= 2
        per_layer = bench.metrics_of(cell["name"], "per_layer")
        assert per_layer
        for metric in per_layer:
            assert metric["moves"] in e2e, (cell["name"], metric["name"])
        assert bench.traffic(cell["traffic"])["rate_metric"] in e2e


def test_files_under_paths_are_named_from_name_characters():
    for base, dirs, files in os.walk(BENCH_DIR):
        dirs[:] = [d for d in dirs if d not in (".state", "__pycache__")]
        for name in files + dirs:
            assert resolve.NAME_RE.match(name), os.path.join(base, name)


# ----------------------------------------------------- traffic generator

CRITEO = {"minibatch_size": 32, "minibatches_per_task": 2,
          "generator": {"kind": "criteo_tsv", "container": "text", "ids": {"kind": "uniform"},
                        "tasks_per_file": 6, "distinct_tasks": 2}}
LM = {"minibatch_size": 4, "minibatches_per_task": 2,
      "generator": {"kind": "lm_tokens", "container": "recordio", "vocab": 50257, "seq_len": 64,
                    "tasks_per_file": 4, "distinct_tasks": 2}}


@pytest.mark.parametrize("traffic", [CRITEO, LM], ids=["criteo_tsv", "lm_tokens"])
def test_same_seed_same_bytes_other_seed_other_bytes(tmp_path, traffic):
    big = 3000000019  # more than 32 signed bits hold
    a = datagen.generate(str(tmp_path / "a"), traffic, big)
    datagen.generate(str(tmp_path / "b"), traffic, big)
    datagen.generate(str(tmp_path / "c"), traffic, big + 1)
    read = lambda d: open(os.path.join(tmp_path, d, os.path.basename(a["first_file"])), "rb").read()  # noqa: E731
    assert read("a") == read("b") != read("c")
    assert os.listdir(tmp_path / "a") == [os.path.basename(a["first_file"])]
    assert a["tasks_per_epoch"] == traffic["generator"]["tasks_per_file"]


def test_criteo_records_are_what_the_program_decodes(tmp_path):
    """The generator writes the program's wire format from its description;
    the program's own reader and decoder must read back what was drawn."""
    from elasticdl_tpu.data.codecs import criteo_feed
    from elasticdl_tpu.data.reader import create_data_reader

    datagen.generate(str(tmp_path / "d"), CRITEO, 7)
    reader = create_data_reader(str(tmp_path / "d"))
    shards = reader.create_shards(64)
    assert len(shards) == 6
    # the two generated tasks' records, written three times over
    assert list(reader.read_records(shards[0])) == list(reader.read_records(shards[2]))
    assert list(reader.read_records(shards[1])) == list(reader.read_records(shards[5]))
    batch = criteo_feed(list(reader.read_records(shards[0])))
    rng = np.random.default_rng(np.random.SeedSequence([7, 0x6EDB]))
    dense = rng.integers(0, 1000, (128, 13))
    cats = rng.integers(0, 1 << 32, (128, 26), dtype=np.uint64).astype(np.uint32)
    assert np.array_equal(batch["dense"], dense[:64].astype(np.float32))
    assert np.array_equal(batch["cat"].astype(np.uint32), cats[:64])
    assert set(np.unique(batch["labels"])) <= {0, 1}


def test_lm_records_are_what_the_program_decodes(tmp_path):
    from elasticdl_tpu.data.codecs import lm_feed
    from elasticdl_tpu.data.reader import create_data_reader

    datagen.generate(str(tmp_path / "d"), LM, 9)
    reader = create_data_reader(str(tmp_path / "d"))
    batch = lm_feed(list(reader.read_records(reader.create_shards(8)[0])))
    assert batch["tokens"].shape == (8, 64) and batch["tokens"].max() < 50257 and batch["tokens"].min() >= 0
    assert np.array_equal(batch["tokens"][:, 1:], batch["labels"][:, :-1])


def test_zipf_ids_are_skewed_and_bounded():
    rng = np.random.default_rng(0)
    ids = datagen._draw_ids(rng, (20000, 3), {"kind": "zipf", "support": 1 << 20, "exponent": 1.05})
    assert ids.dtype == np.uint32
    for column in ids.T:
        _, counts = np.unique(column, return_counts=True)
        assert counts.max() > 0.03 * len(column)  # the hottest id takes a few percent
        assert len(counts) < 0.8 * len(column)  # and many draws repeat
    uniform = datagen._draw_ids(rng, (20000, 3), {"kind": "uniform"})
    assert len(np.unique(uniform[:, 0])) > 0.999 * 20000


# ------------------------------------------------------------ arithmetic


def test_cost_models_count_what_their_docstrings_say():
    bench = resolve.Bench(ROOT)
    gpt = bench.config("gpt2_medium")
    costs = bench.costs(gpt["costs"]).compute(gpt, {"minibatch_size": 16})
    assert costs["matmul_params"] == 24 * 12 * 1024 * 1024 + 50257 * 1024 == 353453056
    assert costs["attention_flops_per_token"] == 6 * 1024 * 1024 * 24
    assert costs["train_flops_per_token"] == 6 * 353453056 + 150994944
    assert costs["flash_unit_flops"] == 16 * 16 * 1024 * 1024 * 64
    deepfm = bench.config("deepfm_criteo")
    costs = bench.costs(deepfm["costs"]).compute(deepfm, {"minibatch_size": 8192})
    assert costs["table_rows"] == 26 * deepfm["model_params"]["buckets_per_feature"]
    assert costs["rows_touched_per_step"] == 212992 and costs["row_bytes"] == 44
    assert costs["dense_params"] == (273 * 400 + 400) + 2 * (400 * 400 + 400) + 401 + 14
    assert costs["step_bytes"] == 9 * (212992 * 44 + costs["dense_params"] * 4)


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    bench = resolve.Bench(ROOT)
    v5e = bench.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "TPU v5e" in resolve.load_json(os.path.join(BENCH_DIR, "peaks.json"))["_source"]


def test_readers_return_nothing_when_there_is_nothing_to_read():
    bench = resolve.Bench(ROOT)
    empty = {
        "trace": None, "trace_steps": 8, "phases": {}, "phases_span_s": 0.0,
        "window": {"reports": 0, "gap_max_s": None, "span_s": 0.0, "steps": 0},
        "records_per_task": 65536, "rate_per_chip": None, "memory_peak_bytes": 0,
        "costs": {}, "peaks": bench.peaks("TPU v5 lite"),
    }
    for entry in bench.spec["per_layer"]:
        spec = bench.metric_file(entry["name"])
        assert bench.reader(spec["reader"]).read(empty, spec.get("params", {})) is None, entry["name"]


def test_step_time_is_the_median_execution_so_a_cut_one_does_not_count():
    trace = {"devices": 1, "modules": [("jit_local_scan(1)", 0.1), ("jit_local_scan(1)", 0.8), ("jit_local_scan(1)", 0.8)]}
    assert xplane.step_seconds(trace, "jit_local_scan", 8) == pytest.approx(0.1)
    assert xplane.step_seconds(trace, "no_such_module", 8) is None
    assert xplane.step_seconds({"devices": 0}, "jit_local_scan", 8) is None


def test_op_names_of_tpu_events_are_short_rows():
    hlo = '%fusion.5340 = (f32[1024]{0:T(1024)}, f32[16,1024]{1,0}) fusion(f32[2]{0} %copy-done.1), kind=kOutput'
    assert xplane.op_name(hlo) == "fusion"
    kernel = '%checkpoint.526 = (bf16[256,1024,128]{2,1,0}) custom-call(bf16[2]{0} %pad.1), custom_call_target="tpu_custom_call"'
    assert xplane.op_name(kernel) == "tpu_custom_call:checkpoint"
    assert xplane.op_name("multiply_add_fusion.12") == "multiply_add_fusion"


def test_readers_on_hand_made_readings():
    bench = resolve.Bench(ROOT)
    events = [(0.0, 8e6, "while.1")] + [(i * 1e6, i * 1e6 + 9e5, f"fusion.{i}") for i in range(8)]
    ctx = {
        "trace": {"devices": 1, "busy_s": 8e-3, "span_s": 8e-3, "planes": {"/device:TPU:0": events},
                  "modules": [("jit_local_scan(123)", 3e-3), ("jit_local_scan(123)", 8e-3),
                              ("jit_local_scan(123)", 8e-3), ("jit_convert(9)", 5.0)]},
        "trace_steps": 8,
        "phases": {"prep_wait": 0.5, "dispatch": 1.0, "lease_wait": 0.02, "control": 0.03, "decode_parallel": 6.5536},
        "phases_span_s": 10.0,
        "window": {"reports": 21, "gap_max_s": 0.7, "span_s": 10.0, "steps": 160},
        "records_per_task": 65536, "rate_per_chip": 30000.0, "memory_peak_bytes": 8 * 2**30,
        "costs": {"step_bytes": 819e9 * 1e-5, "train_flops_per_token": 197e12 / 30000.0 * 0.5},
        "peaks": bench.peaks("TPU v5 lite"),
    }
    got = {}
    for name in ("host_loop_pct.ex", "prep_wait_pct.ex", "lease_ms_task.ex", "decode_us_record.ex",
                 "task_gap_max_ms.ex", "step_ms.ex", "device_idle_pct.ex", "step_roofline_pct.ex",
                 "mfu_pct.tok", "lease_ms_task.tok"):
        spec = bench.metric_file(name)
        got[name] = bench.reader(spec["reader"]).read(ctx, spec.get("params", {}))
    assert got["host_loop_pct.ex"] == pytest.approx(100 * 1.55 / 10)
    assert got["prep_wait_pct.ex"] == pytest.approx(5.0)
    assert got["lease_ms_task.ex"] == pytest.approx(1000 * 0.05 / 20)
    assert got["decode_us_record.ex"] == pytest.approx((6.5536e6 + 0.5e6) / (20 * 65536))
    assert got["task_gap_max_ms.ex"] == pytest.approx(700.0)
    assert got["step_ms.ex"] == pytest.approx(1.0)
    assert got["device_idle_pct.ex"] == pytest.approx(100 * (1 - 160 * 1e-3 / 10.0))
    assert got["step_roofline_pct.ex"] == pytest.approx(1.0)
    assert got["mfu_pct.tok"] == pytest.approx(50.0)
    assert got["lease_ms_task.tok"] == pytest.approx(1000 * 0.05 / 20)
