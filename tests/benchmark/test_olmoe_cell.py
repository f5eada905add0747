"""What PR 30 added to the benchmark: the configuration ``olmoe_1b_7b_l1``
(OLMoE-1B-7B at its published widths, one layer), the traffic mix
``job_seq4k``, the cell ``olmoe_job``, the cost model ``olmoe_flops``, the
reader ``scope_roofline`` and the ``.moe`` metrics.  CPU only."""

from __future__ import annotations

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

CELL = "olmoe_job"
MOE = [
    "step_ms.tok", "mfu_pct.tok", "flash_roofline_pct.tok", "device_idle_pct.tok", "host_loop_pct.tok",
    "prep_wait_pct.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok",
    "lm_head_ms_step.tok", "optimizer_ms_step.moe", "expert_load_max_pct_mean.moe", "moe_slots_computed_pct.moe",
    "expert_mxu_pct.moe",
]
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name OLMoE-1B-7B-0125-Instruct), copied: the guide is not in the checkout.
CATALOG = {
    "attention_bias": False, "clip_qkv": None, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 1024, "max_position_embeddings": 4096, "model_type": "olmoe",
    "norm_topk_prob": False, "num_attention_heads": 16, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 16, "num_key_value_heads": 16, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": False, "vocab_size": 50304,
}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def _catalog_rows(name: str, path: str = "") -> list:
    """The catalog's rows of that name: none without the file (it is outside
    the checkout), none when the catalog has moved on from the model."""
    try:
        with open(path or CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []
    return [r for r in rows if r.get("name") == name]


def test_the_cell_its_configuration_traffic_rehearsal_and_reference_resolve_by_name():
    bench = resolve.Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("olmoe_1b_7b_l1", "job_seq4k", 1)
    assert len(cell["why"]) <= 200
    config, traffic = bench.config(cell["config"]), bench.traffic(cell["traffic"])
    assert config["model_def"] == "moe_lm.model_spec" and config["distribution_strategy"] == "AllReduce"
    assert config["expect"] == {"embedding_route": None, "attention_path": "pallas-compiled"}
    assert os.path.isfile(bench.reference_path(cell["config"]))
    assert os.path.isfile(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 50304, 4096, "recordio")
    assert traffic["units_per_record"] == 4096 and traffic["minibatches_per_task"] == 2
    assert traffic["minibatch_size"] in (2, 4) and traffic["rate_metric"] == "tokens_per_s_chip"
    assert traffic["job_flags"] == {} and traffic["warmup_tasks"] == 4
    # how many cells the benchmark has, and which take four chips, is not this cell's
    # business: test_benchmark_yardstick.py holds the quota once, for all


def test_the_configuration_keeps_every_published_width_and_cuts_the_depth_only():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == "olmoe_1b_7b_l1"]
    config = bench.config("olmoe_1b_7b_l1")
    assert entry["reduced"] == config["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == config["source"] == "https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct/blob/main/config.json"
    assert config["published"] == CATALOG  # the pin: the copy above
    # the catalog itself, where this machine has the file AND the file still has the row
    for row in _catalog_rows("OLMoE-1B-7B-0125-Instruct"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the file holds every key of the published config under the same name, as it is run
    for key, value in CATALOG.items():
        assert config[key] == (1 if key == "num_hidden_layers" else value), key
    # ... and the program is given the same numbers
    p = config["model_params"]
    for key in ("hidden_size", "intermediate_size", "num_attention_heads", "num_experts", "num_experts_per_tok",
                "rms_norm_eps", "rope_theta", "tie_word_embeddings", "vocab_size"):
        assert p[key] == CATALOG[key], key
    assert p["num_hidden_layers"] == 1 and p["seq_len"] == CATALOG["max_position_embeddings"]
    assert set(config["assumed"]) >= {"router_losses", "precision", "init", "optimizer", "weights", "remat", "depth"}
    assert config["first_task_loss_band"][0] >= 10.8 and config["reference_tolerance"] <= 1e-3


@pytest.mark.parametrize("catalog", ["absent", "without_the_row", "with_the_row"])
def test_the_catalog_is_compared_only_where_the_file_and_the_row_exist(tmp_path, catalog):
    path = tmp_path / "architectures.jsonl"
    rows = [{"name": "some-other-model", "config": {}, "source_url": "x"}]
    if catalog == "with_the_row":
        rows.append({"name": "OLMoE-1B-7B-0125-Instruct", "config": CATALOG, "source_url": "y"})
    if catalog != "absent":
        path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    found = _catalog_rows("OLMoE-1B-7B-0125-Instruct", str(path))
    assert [r["config"] for r in found] == ([CATALOG] if catalog == "with_the_row" else [])


@pytest.mark.parametrize("name", MOE)
def test_every_moe_metric_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s_chip"
    spec = bench.metric_file(name)
    assert callable(bench.reader(spec["reader"]).read)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert name in [m["name"] for m in bench.metrics_of(CELL, "per_layer")]


def test_the_moe_metrics_are_all_there():
    """The eighteen PR 30 named, every one reported in ``olmoe_job``; a later
    PR may give the cell more (the list is a floor, not a fence)."""
    bench = resolve.Bench(ROOT)
    assert set(MOE) <= {m["name"] for m in bench.metrics_of(CELL, "per_layer")}
    # the flash kernels keep the operand signatures gpt2m_job's metric reads: one entry, one file, both cells (PR 39)
    (flash,) = [m for m in bench.spec["per_layer"] if m["name"] == "flash_roofline_pct.tok"]
    assert {"gpt2m_job", CELL} <= set(flash["workloads"])


def test_olmoe_flops_counts_what_its_docstring_says():
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config("olmoe_1b_7b_l1"), bench.traffic("job_seq4k")
    costs = bench.costs(config["costs"]).compute(config, dict(traffic, minibatch_size=2))
    assert costs["active_matmul_params"] == 16777216 + 131072 + 8 * 6291456 + 103022592 == 170262528
    assert costs["attention_flops_per_token"] == 6 * 4096 * 2048 == 50331648
    assert costs["train_flops_per_token"] == 6 * 170262528 + 50331648 == 1071906816
    assert costs["flash_unit_flops"] == 2 * 16 * 4096**2 * 128
    assert (costs["flash_fwd_units"], costs["flash_bwd_units"], costs["flash_bwd_second_units"]) == (2, 5, 0)
    assert costs["moe_slots_per_step"] == 65536
    assert costs["expert_flops_per_step"] == 3 * 3 * 2 * 65536 * 2048 * 1024 == 2473901162496
    # the published depth counts sixteen layers and one head
    deep = dict(config, model_params=dict(config["model_params"], num_hidden_layers=16))
    full = bench.costs(config["costs"]).compute(deep, dict(traffic, minibatch_size=2))
    assert full["active_matmul_params"] == 16 * (16777216 + 131072 + 8 * 6291456) + 103022592
    assert full["expert_flops_per_step"] == 16 * costs["expert_flops_per_step"]


def test_scope_roofline_is_flops_at_the_peak_over_the_scopes_time(monkeypatch):
    bench = resolve.Bench(ROOT)
    reader = bench.reader("scope_roofline")
    params = bench.metric_file("expert_mxu_pct.moe")["params"]
    ctx = {"costs": {"expert_flops_per_step": 2473901162496}, "peaks": {"bf16_flops_per_s": 197e12}}
    asked = []

    class OpMs:
        @staticmethod
        def read(ctx, p):
            asked.append(p)
            return 25.0

    monkeypatch.setattr(resolve, "load_module", lambda path: OpMs)
    assert reader.read(ctx, params) == pytest.approx(100.0 * 2473901162496 / 197e12 / 0.025)
    assert asked == [{"module": "jit_local_scan", "pattern": r"\bmoe_experts\b", "on": "scope"}]
    OpMs.read = staticmethod(lambda ctx, p: None)
    assert reader.read(ctx, params) is None
    monkeypatch.undo()
    # no trace: nothing to read, and no error (what the parent's program gives)
    assert reader.read({"trace": None, "costs": ctx["costs"], "peaks": ctx["peaks"]}, params) is None


def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` on the CPU, the float32 reference child on the
    first task's records, the counters' metrics.  Never a result line; exit
    code 4."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache", "parent_tree"),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3000000019", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/olmoe_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band; nothing else is wrong
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    # ln 256 + 0.01 x LB (2 when balanced) + 0.001 x Z
    assert 5.5 < info["first_task_loss"] < 5.7
    assert info["reference"]["relative_difference"] < 1e-3
    terms = info["reference"]["step_terms"][0]
    assert 1.9 < terms["lb_loss"] < 2.3 and terms["z_loss"] > 0
    # the configuration's checks ran in the same child, after the loss (PR 32)
    checks = info["reference"]["checks"]
    assert sorted(checks) == ["router_choices_differing", "router_logits"] and all(c["ok"] for c in checks.values()), checks
    assert info["chips_wait_s"] == 0.0  # no chip to wait for on the CPU
    assert "compared: check router_logits" in done.stderr
    metrics = result["metrics"]
    assert metrics["moe_slots_computed_pct.moe"]["value"] == 100.0
    assert 100.0 <= metrics["expert_load_max_pct_mean.moe"]["value"] <= 250.0
    for name in ("host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok", "compiles_in_window.tok",
                 "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok"):
        assert name in metrics, name
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
