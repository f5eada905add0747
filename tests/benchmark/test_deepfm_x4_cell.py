"""What PR 26 added to the benchmark: the configuration
``deepfm_criteo_tb_x4``, the four-chip cell ``deepfm_x4_job``, its cost
model, the reader ``op_ms_step`` and the ``.ex4`` metrics (what only the
sharded table has; what the cell reads as the one-chip cells do is under
their ``.ex`` names since PR 63).  CPU only."""

from __future__ import annotations

import gzip
import json
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
import xplane  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "deepfm_two_steps.xplane.pb")
CELL = "deepfm_x4_job"
EX4 = [
    "step_ms.ex", "step_roofline_pct.ex4", "device_idle_pct.ex", "host_loop_pct.ex",
    "prep_wait_pct.ex", "starved_dispatch_pct.ex", "compiles_in_window.ex",
    "hbm_peak_reported_gib.ex", "task_gap_max_ms.ex", "setup_init_state_s",
    "collective_ms_step.ex4", "route_ms_step.ex4", "optimizer_ms_step.ex4",
    "route_recv_max_pct_mean.ex4",
    # the table's gradient and update, under the names the one-chip cells read them by since PR 63 (the same scope and counters)
    "table_grad_ms_step.ex", "table_grad_sweep_pct.ex", "table_apply_ms_step.ex", "table_apply_fused_pct.ex",
]


def _reader():
    return resolve.Bench(ROOT).reader("op_ms_step")


def test_the_cell_its_configuration_and_its_traffic_resolve_by_name():
    bench = resolve.Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("deepfm_criteo_tb_x4", "job_uniform_8k", 4)
    config = bench.config(cell["config"])
    assert config["chips"] == 4 and config["reduced"] == [] and config["model_def"] == "deepfm.model_spec"
    old = bench.config("deepfm_criteo")
    ours, theirs = dict(config["model_params"]), dict(old["model_params"])
    assert ours.pop("buckets_per_feature") == 3 * 2**21 and theirs.pop("buckets_per_feature")
    assert ours == theirs and ours["host_tier"] is False  # no width differs
    assert config["expect"]["embedding_route"] in ("ragged", "dense")
    assert os.path.isfile(bench.reference_path(cell["config"]))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["examples_per_s_chip", "setup_s"]
    # a four-chip cell, inside the quota (a quarter of the cells, one always)
    four = [w["name"] for w in bench.spec["workloads"] if w["chips"] == 4]
    assert CELL in four and len(four) <= max(1, len(bench.spec["workloads"]) // 4)


@pytest.mark.parametrize("name", EX4)
def test_every_ex4_metric_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"]
    spec = bench.metric_file(name)
    assert callable(bench.reader(spec["reader"]).read)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert entry["moves"] == ("setup_s" if name == "setup_init_state_s" else "examples_per_s_chip")
    assert name in [m["name"] for m in bench.metrics_of(CELL, "per_layer")]


def test_cost_model_counts_what_its_docstring_says():
    bench = resolve.Bench(ROOT)
    config = bench.config("deepfm_criteo_tb_x4")
    costs = bench.costs(config["costs"]).compute(config, bench.traffic("job_uniform_8k"))
    assert costs["table_rows"] == 163577856 and costs["table_rows_chip"] == 40894464
    assert costs["rows_served_per_step_chip"] == 8192 * 26 // 4 == 53248
    dense = (273 * 400 + 400) + 2 * (400 * 400 + 400) + 401 + 14
    assert costs["dense_params"] == dense
    assert costs["step_bytes_chip"] == 9 * (53248 * 44 + dense * 4)
    # three quarters of a chip's lookups live elsewhere: id out and vector
    # back, and both again for the gradient
    assert costs["lookups_remote_per_step_chip"] == 39936
    assert costs["cross_chip_bytes_step"] == 39936 * 2 * (4 + 44) == 3833856
    assert costs["dense_allreduce_bytes_chip"] == 2 * 3 * dense * 4 // 4
    # with one chip nothing crosses
    alone = bench.costs(config["costs"]).compute(dict(config, chips=1), bench.traffic("job_uniform_8k"))
    assert alone["cross_chip_bytes_step"] == 0 == alone["dense_allreduce_bytes_chip"]
    assert alone["step_bytes_chip"] == bench.costs("deepfm_step_bytes").compute(config, bench.traffic("job_uniform_8k"))["step_bytes"]


def test_own_times_take_nested_events_out_of_their_parent():
    reader = _reader()
    events = [(0.0, 100.0, "%while.1 = while()"), (10.0, 30.0, "%a.1 = add()"), (30.0, 70.0, "%b.2 = fusion()"),
              (40.0, 50.0, "%c.3 = inner()"), (200.0, 210.0, "%d.4 = add()")]
    own = {name.split(" ")[0]: ns for _, ns, name in reader.own_times(events)}
    assert own == {"%while.1": 40.0, "%a.1": 20.0, "%b.2": 30.0, "%c.3": 10.0, "%d.4": 10.0}


def test_op_ms_step_on_the_recorded_trace():
    """The recorded two steps lie inside the first (cut) of two executions
    of the step program: with the cut one admitted, the Adam sweep's events
    give the independent script's seconds over two executions; under the
    default rule the cut execution is left out and nothing matches."""
    reader = _reader()
    expected = json.load(open(os.path.join(HERE, "data", "deepfm_two_steps.expected.json")))
    got = reader.per_step_ms(TRACE, "jit_local_scan", expected["kernel"]["pattern"], 1, whole_share=0.5)
    assert got == pytest.approx(expected["kernel"]["seconds"] * 1e3 / 2, rel=1e-9)
    assert reader.per_step_ms(TRACE, "jit_local_scan", expected["kernel"]["pattern"], 1) is None
    assert reader.per_step_ms(TRACE, "jit_local_scan", "no_such_op", 1, whole_share=0.5) is None
    assert reader.per_step_ms(TRACE, "no_such_program", expected["kernel"]["pattern"], 1) is None
    # every op, by own time, is the device's busy time: nothing counted twice
    everything = reader.per_step_ms(TRACE, "jit_local_scan", ".", 1, whole_share=0.5)
    assert everything == pytest.approx(expected["busy_s"] * 1e3 / 2, rel=1e-9)


def test_op_ms_step_matches_scopes_through_the_profilers_json(tmp_path):
    """Scopes are not in the xplane's events; the ``trace.json.gz`` beside
    it maps instruction names to them.  Without that file: no metric."""
    reader = _reader()
    trace = tmp_path / "t.xplane.pb"
    shutil.copy(TRACE, trace)
    pattern = r"\broute_(plan|bwd_scatter)\b"
    assert reader.per_step_ms(str(trace), "jit_local_scan", pattern, 1, on="scope", whole_share=0.5) is None
    events = xplane.load_op_events(TRACE)["/device:TPU:0"]
    names = sorted({name.split(" = ")[0].lstrip("%") for _, _, name in events})
    sweeps = [n for n in names if n.startswith("multiply_add_fusion")]
    scopes = {n: "jit(local_scan)/while/body/transpose(jvp(route_bwd_scatter))/scatter-add" for n in sweeps}
    scopes.update({n: "jit(local_scan)/while/body/adam/mul" for n in names if n not in scopes})
    with gzip.open(tmp_path / "t.trace.json.gz", "wt") as f:
        json.dump({"traceEvents": [{"ph": "M", "name": "process_name"}] + [
            {"ph": "X", "name": n, "args": {"tf_op": scope}} for n, scope in scopes.items()
        ]}, f)
    by_scope = reader.per_step_ms(str(trace), "jit_local_scan", pattern, 1, on="scope", whole_share=0.5)
    by_name = reader.per_step_ms(str(trace), "jit_local_scan", r"^%?multiply_add_fusion", 1, whole_share=0.5)
    assert by_scope == pytest.approx(by_name)
    excluded = reader.per_step_ms(
        str(trace), "jit_local_scan", pattern, 1, on="scope", exclude=r"^%?multiply_add_fusion", whole_share=0.5)
    assert excluded is None


def test_the_collective_pattern_names_collectives_only():
    import re

    pattern = resolve.Bench(ROOT).metric_file("collective_ms_step.ex4")["params"]["pattern"]
    assert pattern == resolve.Bench(ROOT).metric_file("route_ms_step.ex4")["params"]["exclude"]
    yes = [
        "%ragged_all_to_all.45 = s32[53248,1,128]{2,1,0} ragged-all-to-all(s32[53248,1,128]{2,1,0} %copy.90, s32[1]{0} %x)",
        "%all-reduce-start.3 = f32[400,400]{1,0} all-reduce-start(f32[400,400]{1,0} %p), replica_groups={}",
        "%all-reduce-done.3 = f32[400,400]{1,0} all-reduce-done(f32[400,400]{1,0} %all-reduce-start.3)",
        "%all-gather.1 = s32[4,4]{1,0} all-gather(s32[1,4]{1,0} %c), dimensions={0}",
        "%reduce-scatter.2 = f32[8]{0} reduce-scatter(f32[32]{0} %c)",
    ]
    no = [
        "%slice_reduce_fusion.4 = s32[53248]{0} fusion(s32[53248,1,128]{2,1,0} %ragged_all_to_all.45), kind=kLoop",
        "%multiply_add_fusion.29 = (f32[5111808,128]{1,0}) fusion(f32[5111808,128]{1,0} %all-reduce-done.3), kind=kLoop",
        "%sort.35 = (s32[53248]{0}, s32[53248]{0}) sort(s32[53248]{0} %a, s32[53248]{0} %iota.82)",
    ]
    assert all(re.search(pattern, text) for text in yes)
    assert not any(re.search(pattern, text) for text in no)


def test_rehearsal_runs_the_cells_control_flow_on_four_cpu_devices(tmp_path):
    """The whole of run.py for the new cell at toy sizes: four CPU devices,
    the ragged route's own sort / offsets / unsort code (the collective
    emulated), the reference child over the sharded init, and the metrics
    that read counters.  Never a result line; exit code 4."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache", "parent_tree"),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/deepfm_x4_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 4 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band; nothing else is wrong
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert info["reference"]["devices"] == 4 and info["reference"]["rows_touched"] <= 2 * 64 * 26
    assert info["reference"]["relative_difference"] < 2e-3
    metrics = result["metrics"]
    assert metrics["setup_init_state_s"]["value"] > 0
    assert 100.0 <= metrics["route_recv_max_pct_mean.ex4"]["value"] <= 400.0
    for name in ("host_loop_pct.ex", "prep_wait_pct.ex", "starved_dispatch_pct.ex", "compiles_in_window.ex", "task_gap_max_ms.ex",
                 "lease_ms_task.ex"):  # the last joined the cell in PR 39
        assert name in metrics, name
    assert "examples_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
