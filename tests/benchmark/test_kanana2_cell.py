"""What PR 33 added to the benchmark: the configuration
``kanana2_30b_a3b_ep8_l5`` (kanana-2-30b-a3b at its published widths: one
chip's share of an 8-way expert-parallel layer, the dense layer and four
expert layers), the traffic mix ``job_seq8k``, the cell ``kanana2_job``, the
cost model ``kanana2_flops``, the reader ``scope_roofline_counted`` and the
``.mla`` metrics.  CPU only."""

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

CELL, CONFIG, TRAFFIC = "kanana2_job", "kanana2_30b_a3b_ep8_l5", "job_seq8k"
MLA = [
    "step_ms.tok", "mfu_pct.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok",
    "starved_dispatch_pct.tok", "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok",
    "lease_ms_task.tok", "flash_attn_ms_step.tok", "flash_roofline_pct.mla", "mla_proj_ms_step.mla",
    "moe_shared_ms_step.mla", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "lm_head_ms_step.tok",
    "optimizer_ms_step.mla", "expert_mxu_pct.mla", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla",
]
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name kanana-2-30b-a3b-instruct-2601), copied: the guide is not in the checkout.
CATALOG = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 128, "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256,
}
SOURCE = "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "n_routed_experts": 16, "vocab_size": 16032}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


def _catalog_rows(name: str) -> list:
    """The catalog's rows of that name: none without the file (it is outside
    the checkout), none when the catalog has moved on from the model."""
    try:
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []
    return [r for r in rows if r.get("name") == name]


def test_the_cell_its_configuration_traffic_rehearsal_and_reference_resolve_by_name():
    bench = resolve.Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1)
    assert len(cell["why"]) <= 200
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    assert config["model_def"] == "moe_lm.model_spec" and config["distribution_strategy"] == "AllReduce"
    assert config["expect"] == {"embedding_route": None, "attention_path": "pallas-compiled"}
    assert os.path.isfile(bench.reference_path(CONFIG))
    assert os.path.isfile(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 16032, 8192, "recordio")
    # no task repeats inside warm-up + the window: nothing is memorised
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64
    assert traffic["units_per_record"] == 8192 and traffic["minibatch_size"] in (2, 4)
    assert traffic["minibatches_per_task"] in (1, 2) and traffic["rate_metric"] == "tokens_per_s_chip"
    assert traffic["job_flags"] == {} and traffic["warmup_tasks"] == 4


def test_the_configuration_keeps_every_published_width_and_states_its_three_cuts():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == sorted(CUT, key=list(CUT).index)
    assert entry["source"] == config["source"] == SOURCE
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("kanana-2-30b-a3b-instruct-2601"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the file holds every key of the published config under the same name, as it is run:
    # the three cuts beside their published values, nothing else moved
    for key, value in CATALOG.items():
        assert config[key] == CUT.get(key, value), key
    assert "8 chips share each layer" in config["deployment"] and "8 chips share each layer" in config["reduced_why"]
    for published in ("48 layers", "128 routed experts", "128,256"):
        assert published in config["reduced_why"], published
    # floors: four expert layers after the dense one, 8 experts or more, an eighth of the vocabulary
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8 and 8 * config["vocab_size"] >= CATALOG["vocab_size"]
    # ... and the program is given the same numbers: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_experts_per_tok",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_shared_experts",
            "first_k_dense_replace", "rms_norm_eps", "rope_theta", "rope_interleave", "routed_scaling_factor",
            "scoring_func", "norm_topk_prob", "topk_method", "n_group", "topk_group", "tie_word_embeddings")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["num_experts"] == CATALOG["n_routed_experts"]  # the ROUTER keeps its published width
    assert (p["experts_held"], p["first_expert_held"]) == (config["n_routed_experts"], 0)
    assert p["num_hidden_layers"] == 5 and p["vocab_size"] == 16032 and p["seq_len"] == 8192
    assert p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0 and p["bias_update_speed"] == 0.001
    assert set(config["assumed"]) >= {"loss", "correction_bias", "precision", "init", "optimizer", "weights", "remat", "depth"}
    assert sorted(config["checks"]) == ["router_choices_differing", "router_logits"]
    assert config["first_task_loss_band"][0] >= math.log(16032) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"]


def test_the_share_is_the_arithmetic_the_file_states():
    """575.96 M parameters: the model's own init at the configuration's
    keys, counted (shapes only)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert sorted(blocks) == ["b00", "b01", "b02", "b03", "b04"]
    assert "router" not in blocks["b00"] and all("router" in blocks[name] for name in ("b01", "b02", "b03", "b04"))
    assert round(count(blocks["b00"]) / 1e6, 2) == 64.10
    assert round(count(blocks["b01"]) / 1e6, 2) == 111.55
    experts = sum(math.prod(blocks["b01"][name].shape) for name in ("w_gate", "w_up", "w_down"))
    assert experts == 16 * 3 * 2048 * 768 and round((count(blocks["b01"]) - experts) / 1e6, 2) == 36.05
    assert blocks["b01"]["router"].shape == (2048, 128) and blocks["b01"]["router_bias"].shape == (128,)
    assert blocks["b01"]["wq"].shape == (2048, 32 * 192) and blocks["b01"]["wkv_a"].shape == (2048, 576)
    assert blocks["b01"]["wkv_b"].shape == (512, 32 * 256) and blocks["b01"]["wo"].shape == (4096, 2048)
    assert round(count(shapes) / 1e6, 2) == 575.96
    assert "575.96 M" in config["reduced_why"] and "575.96 M" in config["deployment"]


@pytest.mark.parametrize("name", MLA)
def test_every_mla_metric_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s_chip"
    spec = bench.metric_file(name)
    assert callable(bench.reader(spec["reader"]).read)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert name in [m["name"] for m in bench.metrics_of(CELL, "per_layer")]


def test_the_new_flash_patterns_read_the_new_operand_lists_and_only_those():
    import re

    bench = resolve.Bench(ROOT)
    new = [k["pattern"] for k in bench.metric_file("flash_roofline_pct.mla")["params"]["kernels"]]
    old = [k["pattern"] for k in bench.metric_file("flash_roofline_pct.tok")["params"]["kernels"]]
    operand = lambda dtype, i: f"{dtype}[2,8192,4096]{{2,1,0:T(8,128)(2,1)}} %fusion.{i}"  # noqa: E731
    event = lambda n_bf16, n_f32: (  # noqa: E731
        "%custom-call.7 = bf16[2,8192,4096]{2,1,0} custom-call("
        + ", ".join([operand("bf16", i) for i in range(n_bf16)] + [operand("f32", 9 + i) for i in range(n_f32)])
        + '), custom_call_target="tpu_custom_call"'
    )
    lists = [(5, 0), (6, 1), (6, 2)]
    for pattern, own in zip(new, lists):
        for other in lists + [(3, 0), (4, 1), (4, 2)]:
            assert bool(re.search(pattern, event(*other))) == (other == own), (own, other)
    for pattern in old:
        assert not any(re.search(pattern, event(*own)) for own in lists)


def test_kanana2_flops_counts_what_its_docstring_says():
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    costs = bench.costs(config["costs"]).compute(config, dict(traffic, minibatch_size=2))
    attention = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 4096 * 2048
    assert attention == 26345472
    per_expert_layer = 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    assert costs["active_matmul_params"] == 5 * attention + 3 * 2048 * 6144 + 4 * per_expert_layer + 2048 * 16032
    # forward FLOPs a token, as ISSUE 33 reckons them: 930 M
    forward = 2 * costs["active_matmul_params"] + 5 * 32 * 4096 * 640
    assert round(forward / 1e6) == 930
    assert (costs["flash_fwd_units"], costs["flash_bwd_units"], costs["flash_bwd_second_units"]) == (640, 1664, 0)
    assert costs["flash_unit_flops"] == 2 * 32 * 8192 * 8192 // 2
    assert costs["attention_flops_per_token"] == 5 * 32 * 4096 * 3 * 640
    assert costs["train_flops_per_token"] == 6 * costs["active_matmul_params"] + costs["attention_flops_per_token"]
    assert costs["moe_slots_per_step"] == 2 * 8192 * 6 * 4
    assert costs["expert_flops_per_slot"] == 3 * 3 * 2 * 2048 * 768
    assert costs["expert_flops_per_step"] == costs["moe_slots_per_step"] / 8 * costs["expert_flops_per_slot"]
    # a step of 16,384 tokens: 45.7 TFLOP
    assert round(costs["train_flops_per_token"] * 16384 / 1e12, 1) == 45.7


def test_scope_roofline_counted_takes_its_flops_from_the_counters(tmp_path, monkeypatch):
    """The slots the steps really computed (growth of ``moe_slots_computed``
    over ``moe_slots`` across the reports up to the window's first), not the
    expectation: 10 % held here against the expected 12.5 %."""
    import runfiles

    bench = resolve.Bench(ROOT)
    reader = bench.reader("scope_roofline_counted")
    params = bench.metric_file("expert_mxu_pct.mla")["params"]
    run = tmp_path / "run"
    (run / "metrics").mkdir(parents=True)
    records = [
        {"kind": "counter", "ts": 10.0 + i, "moe_slots": 1000.0 * (i + 1), "moe_slots_computed": 100.0 * (i + 1)}
        for i in range(4)
    ] + [{"kind": "counter", "ts": 20.0, "moe_slots": 6000.0, "moe_slots_computed": 1000.0}]
    (run / "metrics" / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in records))
    monkeypatch.setattr(runfiles, "run_dir", lambda ctx: str(run))
    costs = {"expert_flops_per_slot": 28311552, "moe_slots_per_step": 393216}
    ctx = {"costs": costs, "peaks": {"bf16_flops_per_s": 197e12}, "window": {"ts": [13.0, 20.0]}}

    class OpMs:
        @staticmethod
        def read(ctx, p):
            return 20.0

    real = resolve.load_module
    monkeypatch.setattr(resolve, "load_module", lambda path: OpMs if path.endswith("op_ms_step.py") else real(path))
    assert reader.read(ctx, params) == pytest.approx(100.0 * 28311552 * 393216 * 0.1 / 197e12 / 0.020)
    # fewer than two reports before the window: the window's own
    ctx["window"] = {"ts": [10.0, 20.0]}
    assert reader.read(ctx, params) == pytest.approx(100.0 * 28311552 * 393216 * (900 / 5000) / 197e12 / 0.020)
    # a program without the counters (the parent): no metric, and no error
    (run / "metrics" / "metrics.jsonl").write_text(json.dumps({"kind": "counter", "ts": 11.0, "compiles": 3}) + "\n")
    assert reader.read(ctx, params) is None
    OpMs.read = staticmethod(lambda ctx, p: None)
    assert reader.read(ctx, params) is None


def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under kanana-2's keys on the CPU, the float32
    reference child on the first task's records with the two router checks,
    the counters' metrics.  Never a result line; exit code 4."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache",
                                      "parent_tree", "final_tree"),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000029", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/kanana2_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band; nothing else is wrong
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.65  # ln 256 + the head's variance: CE alone, no router loss
    assert info["reference"]["relative_difference"] < 1e-3
    assert len(info["reference"]["held_share"]) == 2 and 0.15 < info["reference"]["held_share"][0] < 0.35
    checks = info["reference"]["checks"]
    assert sorted(checks) == ["router_choices_differing", "router_logits"] and all(c["ok"] for c in checks.values()), checks
    assert "compared: check router_logits" in done.stderr
    metrics = result["metrics"]
    assert metrics["moe_slots_computed_pct.mla"]["value"] == 100.0
    assert 15.0 < metrics["moe_slots_held_pct.mla"]["value"] < 35.0  # 4 of 16 experts held: 25 when balanced
    for name in ("host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok", "compiles_in_window.tok",
                 "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok"):
        assert name in metrics, name
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
