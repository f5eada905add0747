"""What PR 27 added to the benchmark, as data files only: the one-chip cell
``deepfm_job_zipf`` (``deepfm_criteo`` under Zipf ids), its ``.exz`` metrics
(twins of files that were there) and the ``table_grad_*`` metrics that read
the merge sweep's scope and counters.  CPU only; lives outside
``tests/benchmark/`` because a PR that adds a cell adds no code there."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402

CELL = "deepfm_job_zipf"
ONE_CHIP = ["deepfm_job", CELL]
#: What the cell reports of its own (PR 27), and the table gradient's and update's metrics (PR 27, PR 29) that the
#: three DeepFM cells report — by STEM, the name before the first dot.  No entry, suffix or file is named here or
#: below (D25): a ``benchmark`` PR that folds two entries of a stem into one, or renames a suffix, edits neither file.
OWN = ("step_ms", "step_roofline_pct", "device_idle_pct", "host_loop_pct", "prep_wait_pct", "starved_dispatch_pct",
       "compiles_in_window", "hbm_peak_reported_gib")
TABLE = ("table_grad_ms_step", "table_grad_sweep_pct", "table_apply_ms_step", "table_apply_fused_pct")
REPORTED = [(stem, CELL) for stem in OWN] + [(stem, cell) for stem in TABLE for cell in ONE_CHIP + ["deepfm_x4_job"]]
RATE = "examples_per_s_chip"


def _stem(name: str) -> str:
    return name.partition(".")[0]


def _entry_of(bench, stem: str, cell: str) -> dict:
    """The ONE ``per_layer`` entry that reports ``stem`` in ``cell`` and moves the DeepFM cells' rate."""
    (entry,) = [m for m in bench.spec["per_layer"] if _stem(m["name"]) == stem and m["moves"] == RATE and cell in m["workloads"]]
    return entry


def _data(spec: dict) -> dict:
    """A metric's file less what names it (a file's own list of cells, where it still has one, is not read)."""
    return {k: v for k, v in spec.items() if k not in ("name", "cells")}


def _the_cell_reports_the_stem(bench, stem: str, cell: str) -> None:
    entry = _entry_of(bench, stem, cell)
    spec = bench.metric_file(entry["name"])
    assert spec["name"] == entry["name"]
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert callable(bench.reader(spec["reader"]).read)
    assert entry["name"] in [m["name"] for m in bench.metrics_of(cell, "per_layer")]
    # a one-chip DeepFM cell reads the stem as ``deepfm_job`` does: the same file but for its name, the same entry but
    # for its name and cells (one entry, or a twin of it)
    if cell in ONE_CHIP:
        original = _entry_of(bench, stem, "deepfm_job")
        assert _data(spec) == _data(bench.metric_file(original["name"]))
        assert {**original, "name": None, "workloads": None} == {**entry, "name": None, "workloads": None}
    if stem not in TABLE:
        return
    params = spec["params"]
    if stem.endswith("_ms_step"):
        # the scope as the compiled step spells it (tests/test_chip_lowering.py), and no other kernel's
        scope = stem[: -len("_ms_step")]
        assert params == {"module": "jit_local_scan", "on": "scope", "pattern": rf"\b{scope}\b"}
        assert entry["layer"] == {"table_grad": "ops", "table_apply": "trainer"}[scope]
        spelt = {
            "table_grad": "jit(local_scan)/while/body/closed_call/transpose(jvp(table_grad))/pallas_call",
            "table_apply": "jit(local_scan)/while/body/closed_call/table_apply/pallas_call",
        }
        others = [spelling for name, spelling in spelt.items() if name != scope] + ["jit(local_scan)/table_grad_rows/add"]
        assert re.search(params["pattern"], spelt.pop(scope)) and not any(re.search(params["pattern"], other) for other in others)
    else:
        from elasticdl_tpu.worker.worker import COUNTER_GAUGES, STEP_COUNTERS

        assert entry["layer"] == "ops" and params["scale"] == 100
        for counter in (params["counter"], params["over"]):
            assert counter in STEP_COUNTERS and counter in COUNTER_GAUGES
        if stem == "table_apply_fused_pct":
            assert (params["counter"], params["over"]) == ("table_grad_rows_fused", "table_grad_rows")
            assert COUNTER_GAUGES[params["counter"]][0] == "edl_table_grad_rows_fused_total"
        # a program without the counters (the parent commit) reports nothing
        old = {"window": {"ts": [0.0, 1e12]}, "config": {"name": "x"}, "traffic": {"name": "y"}, "chips": 1}
        assert bench.reader(spec["reader"]).read(old, params) is None


def test_the_cell_resolves_and_its_traffic_differs_in_the_ids_alone():
    bench = resolve.Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("deepfm_criteo", "job_zipf105_8k", 1)
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["examples_per_s_chip", "setup_s"]
    ours, theirs = bench.traffic("job_zipf105_8k"), bench.traffic("job_uniform_8k")
    assert ours["generator"].pop("ids") == {"kind": "zipf", "exponent": 1.05, "support": 786432}
    assert theirs["generator"].pop("ids") == {"kind": "uniform"}
    for key in ("name", "why", "generator_why"):
        assert ours.pop(key) != theirs.pop(key)
    assert ours == theirs
    # the cell's own, and the table's; PR 35's set-up metrics and `prep` span metric list every cell they are read in
    assert {_stem(m["name"]) for m in bench.metrics_of(CELL, "per_layer")} >= {*OWN, *TABLE}


@pytest.mark.parametrize("stem,cell", REPORTED, ids=[f"{stem}-{cell}" for stem, cell in REPORTED])
def test_a_cell_reports_a_stem_through_one_entry_whose_file_agrees_with_it(stem, cell):
    _the_cell_reports_the_stem(resolve.Bench(ROOT), stem, cell)


def test_the_same_holds_where_the_cells_own_entries_are_folded_onto_deepfm_jobs(tmp_path):
    """What D25 exists for: a ``benchmark`` PR that needs room folds each of the cell's own entries onto the entry
    ``deepfm_job`` reports the stem through (one more cell in its ``workloads``, the twin's entry and file gone).
    On such a copy every case above holds as it stands."""
    shutil.copytree(BENCH_DIR, tmp_path / "benchmark", ignore=shutil.ignore_patterns(".state", "__pycache__"))
    spec = resolve.Bench(ROOT).spec
    before = len(spec["per_layer"])
    for stem in OWN:
        bench = resolve.Bench(ROOT)
        twin, original = _entry_of(bench, stem, CELL), _entry_of(bench, stem, "deepfm_job")
        if twin["name"] == original["name"]:  # folded already in the tree itself
            continue
        spec["per_layer"] = [m for m in spec["per_layer"] if m["name"] != twin["name"]]
        next(m for m in spec["per_layer"] if m["name"] == original["name"])["workloads"].append(CELL)
        os.remove(tmp_path / "benchmark" / "metrics" / (twin["name"] + ".json"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    folded = resolve.Bench(str(tmp_path))
    assert len(folded.spec["per_layer"]) <= before and all(_entry_of(folded, stem, CELL) is _entry_of(folded, stem, "deepfm_job") for stem in OWN)
    for stem, cell in REPORTED:
        _the_cell_reports_the_stem(folded, stem, cell)


def test_rehearsal_runs_the_cells_control_flow_on_the_cpu(tmp_path):
    """The whole of run.py for the new cell at toy sizes, Zipf ids drawn by
    the generator that was there: exit code 4, ``correct`` but for the toy
    model's loss band, and the table gradient's counters in the report
    (none of it swept: the table is small and this is no TPU)."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(
            ".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache",
            "parent_tree", "final_tree",
        ),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/deepfm_job_zipf.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert info["reference"]["relative_difference"] < 4e-3
    metrics = result["metrics"]
    by_stem = {_stem(name): metric for name, metric in metrics.items()}
    assert by_stem["table_grad_sweep_pct"]["value"] == 0.0
    for stem in ("host_loop_pct", "prep_wait_pct", "starved_dispatch_pct", "compiles_in_window", "hbm_peak_reported_gib"):
        assert stem in by_stem, stem
    assert "examples_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
