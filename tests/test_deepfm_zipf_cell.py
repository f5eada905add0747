"""What PR 27 added to the benchmark, as data files only: the one-chip cell
``deepfm_job_zipf`` (``deepfm_criteo`` under Zipf ids), its ``.exz`` metrics
(twins of files that were there) and the ``table_grad_*`` metrics that read
the merge sweep's scope and counters.  CPU only; lives outside
``tests/benchmark/`` because a PR that adds a cell adds no code there."""

from __future__ import annotations

import json
import os
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
# the new metric -> the file it copies in every field but name and cells
TWINS = {
    "step_ms.exz": "step_ms.ex",
    "step_roofline_pct.exz": "step_roofline_pct.ex",
    "device_idle_pct.exz": "device_idle_pct.ex",
    "host_loop_pct.exz": "host_loop_pct.ex",
    "prep_wait_pct.exz": "prep_wait_pct.ex",
    "starved_dispatch_pct.exz": "starved_dispatch_pct.ex4",
    "compiles_in_window.exz": "compiles_in_window.ex4",
    "hbm_peak_reported_gib.exz": "hbm_peak_reported_gib.ex4",
}
ONE_CHIP = ["deepfm_job", CELL]
TABLE_GRAD = {
    "table_grad_ms_step.ex": ONE_CHIP, "table_grad_ms_step.ex4": ["deepfm_x4_job"],
    "table_grad_sweep_pct.ex": ONE_CHIP, "table_grad_sweep_pct.ex4": ["deepfm_x4_job"],
}


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
    # the cell's own; PR 35's set-up metrics and `prep` span metric list every cell they are read in
    reported = [m["name"] for m in bench.metrics_of(CELL, "per_layer") if not m["name"].startswith(("setup_", "prep_ms_task"))]
    assert set(reported) >= {
        *TWINS, "table_grad_ms_step.ex", "table_grad_sweep_pct.ex",
        "table_apply_ms_step.ex", "table_apply_fused_pct.ex",  # PR 29
    }


@pytest.mark.parametrize("name", sorted(TWINS))
def test_an_exz_metric_is_its_original_under_another_name(name):
    bench = resolve.Bench(ROOT)
    ours, theirs = bench.metric_file(name), bench.metric_file(TWINS[name])
    # (a file's own list of cells, where it still has one, is not read: BENCHMARK.json's entry is)
    assert ours.pop("name") == name and theirs.pop("name") == TWINS[name]
    ours.pop("cells", None), theirs.pop("cells", None)
    assert ours == theirs
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    (original,) = [m for m in bench.spec["per_layer"] if m["name"] == TWINS[name]]
    assert CELL in entry.pop("workloads")
    assert {**original, "name": name, "workloads": None} == {**entry, "workloads": None}


@pytest.mark.parametrize("name", sorted(TABLE_GRAD))
def test_a_table_grad_metric_reads_the_sweeps_scope_or_counters(name):
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file(name)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert all(cell in entry["workloads"] for cell in TABLE_GRAD[name])
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert (entry["layer"], entry["moves"]) == ("ops", "examples_per_s_chip")
    assert callable(bench.reader(spec["reader"]).read)
    if name.startswith("table_grad_ms_step"):
        assert spec["params"] == {"module": "jit_local_scan", "on": "scope", "pattern": r"\btable_grad\b"}
        # the scope as the compiled step spells it (tests/test_chip_lowering.py)
        import re
        assert re.search(spec["params"]["pattern"], "jit(local_scan)/while/body/closed_call/transpose(jvp(table_grad))/pallas_call")
        assert not re.search(spec["params"]["pattern"], "jit(local_scan)/table_grad_rows/add")
    else:
        from elasticdl_tpu.worker.worker import COUNTER_GAUGES, STEP_COUNTERS
        params = spec["params"]
        assert params["scale"] == 100
        for counter in (params["counter"], params["over"]):
            assert counter in STEP_COUNTERS and counter in COUNTER_GAUGES
        # a program without the counters (the parent commit) reports nothing
        old = {"window": {"ts": [0.0, 1e12]}, "config": {"name": "x"}, "traffic": {"name": "y"}, "chips": 1}
        assert bench.reader(spec["reader"]).read(old, params) is None


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
    assert metrics["table_grad_sweep_pct.ex"]["value"] == 0.0
    for name in ("host_loop_pct.exz", "prep_wait_pct.exz", "starved_dispatch_pct.exz", "compiles_in_window.exz", "hbm_peak_reported_gib.exz"):
        assert name in metrics, name
    assert "examples_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
