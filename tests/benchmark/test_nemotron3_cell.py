"""What PR 40 added to the benchmark: the configuration
``nemotron3_super_tp4_ep64_l11`` (Nemotron 3 Super at its published widths:
one chip's share of a 4-way head-parallel, 64-way expert-parallel stage, one
period of 11 layers), the traffic mix ``job_seq8k_x1``, the cell
``nemotron3_job``, the cost model ``nemotron_h_flops``, the reader
``scope_roofline_larger`` and the ``.ssm`` metrics.  CPU only."""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402

CELL, CONFIG, TRAFFIC = "nemotron3_job", "nemotron3_super_tp4_ep64_l11", "job_seq8k_x1"
#: every per-layer metric the cell reports: the ones it JOINED (appended to their ``workloads``) and its own ``.ssm``
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",  # PR 63: the recorder runs in every worker loop (PR 54)
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "flash_attn_ms_step.tok",
    "flash_roofline_pct.tok", "remat_kept_pct.tok", "moe_shared_ms_step.mla", "moe_slots_computed_pct.mla",
    "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla", "expert_load_max_pct_mean.moe",
]
#: the scope entries PR 46 landed (they had waited for room in ``per_layer`` since PR 40): entry -> the scopes
#: of the step it reads; every other scope of the step is a neighbour it must not read
SCOPE_ENTRIES = {
    "ssm_proj_ms_step.ssm": {"ssm_proj"},
    "ssm_glue_ms_step.ssm": {"ssm_conv", "ssm_norm"},
    "moe_latent_ms_step.ssm": {"moe_latent"},
    "attn_proj_ms_step.ssm": {"attn_proj"},
}
#: its own: PR 40's three, and since PR 46 the four above and the kernels' share of the scans' positions.  ``attn_proj_ms_step.ssm``
#: stays here (the cell brought it) though ``trinity_mini_job`` reports it too since PR 63: a suffix that names a family, as
#: ``moe_shared_ms_step.mla`` is for four cells; an own entry's list starts with the cell and later cells join it
OWN = ["ssm_scan_roofline_pct.ssm", "ssm_scan_ms_step.ssm", "ssm_glue_hbm_pct.ssm", *SCOPE_ENTRIES, "ssm_scan_kernel_pct.ssm"]
CHECKS = sorted([
    "ssm_output", "ssm_decay", "router_logits", "router_choices_differing", "head_logits", "logits", "adamw_update",
    "grad_ssm", "grad_attention", "grad_experts", "grad_latent", "grad_shared", "grad_router", "grad_head",
    "grad_embedding", "grad_norms",
])
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name NVIDIA-Nemotron-3-Super-120B-A12B-BF16), copied: the guide is not in the checkout.
CATALOG = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128, "hidden_size": 4096,
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 2688, "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64, "mamba_hidden_act": "silu",
    "mamba_num_heads": 128, "mamba_proj_bias": False, "max_position_embeddings": 262144, "mlp_bias": False,
    "mlp_hidden_act": "relu2", "model_type": "nemotron_h", "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "moe_shared_expert_overlap": False, "mtp_hybrid_override_pattern": "*E",
    "n_group": 1, "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 22, "num_hidden_layers": 88, "num_key_value_heads": 2,
    "num_logits_to_keep": 1, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000, "routed_scaling_factor": 5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False, "time_step_floor": 0.0001, "time_step_max": 0.1,
    "time_step_min": 0.001, "topk_group": 1, "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072,
}
SOURCE = "https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json"
CUT = {
    "num_hidden_layers": 11, "mamba_num_heads": 32, "n_groups": 2, "num_attention_heads": 8, "num_key_value_heads": 1,
    "n_routed_experts": 8, "vocab_size": 16384,
}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
#: The growth rehearsal (test_benchmark_yardstick.py) runs this module again on grown copies of the tree; the tests
#: marked so compile models and read nothing of how many cells there are: they run on the tree itself only.
on_the_tree_itself = pytest.mark.skipif("EDL_BENCH_GROWTH_REHEARSAL" in os.environ, reason="reads nothing of the cells a later PR adds")


def _catalog_rows(name: str) -> list:
    """The catalog's rows of that name: none without the file (it is outside
    the checkout), none when the catalog has moved on from the model."""
    try:
        with open(CATALOG_FILE) as f:
            rows = [json.loads(line) for line in f if line.strip()]
    except (OSError, ValueError):
        return []
    return [r for r in rows if r.get("name") == name]


def _costs():
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    return bench.costs(config["costs"]).compute(config, traffic)


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
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "nemotron_h_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED + OWN)
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 16384, 8192, "recordio")
    # no task repeats inside warm-up + the window: nothing is memorised
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64
    assert traffic["units_per_record"] == 8192 and traffic["minibatch_size"] == 1
    # ISSUE 40's parameters, as the other LM cells': two steps a task, four warm-up tasks
    assert traffic["minibatches_per_task"] == 2 and traffic["rate_metric"] == "tokens_per_s_chip"
    assert traffic["warmup_tasks"] == 4
    # a traced run's profile is two tasks, written by the task loop before the last warm-up task reports (warmup_why)
    assert traffic["job_flags"] == {"profile_tasks": 2, "profile_inline": True}
    for key in ("why", "minibatch_why", "generator_why", "warmup_why"):
        assert len(traffic[key]) > 80, key


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_seven_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
    elif key == "hybrid_override_pattern":  # a string: the period that stands at layers 26 to 36 of the 88
        assert config[key] == "EMEMEMEMEM*" == CATALOG[key][26:37]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == list(CUT)  # exactly the seven keys
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("NVIDIA-Nemotron-3-Super-120B-A12B-BF16"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: a whole period, 8 routed experts a layer, an eighth of the vocabulary
    pattern = CATALOG["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
    assert config["n_routed_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    for said in ("64 chips a stage", "4 ways inside a host", "64 ways", "8 ways", "NOT run"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths: no width is cut
    p = config["model_params"]
    widths = {
        "hidden_size": 4096, "mamba_head_dim": 64, "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 128,
        "head_dim": 128, "moe_latent_size": 1024, "moe_intermediate_size": 2688,
        "moe_shared_expert_intermediate_size": 5376, "num_experts_per_tok": 22, "routed_scaling_factor": 5.0,
        "mlp_hidden_act": "relu2", "norm_topk_prob": True, "time_step_min": 0.001, "time_step_max": 0.1,
    }
    for key, value in widths.items():
        assert p[key] == value == CATALOG.get(key, value), key
    assert p["num_experts"] == CATALOG["n_routed_experts"] == 512 and p["experts_held"] == config["n_routed_experts"] == 8
    assert p["mamba_num_heads"] == 128 and p["n_groups"] == 8 and p["mamba_heads_held"] == config["mamba_num_heads"] == 32
    assert p["mamba_heads_held"] * p["n_groups"] // p["mamba_num_heads"] == config["n_groups"] == 2  # whole groups of 16
    assert p["num_attention_heads"] == 32 and p["heads_held"] == config["num_attention_heads"] == 8
    assert p["num_key_value_heads"] == 2 and p["kv_heads_held"] == config["num_key_value_heads"] == 1
    assert p["hybrid_override_pattern"] == config["hybrid_override_pattern"] and p["num_hidden_layers"] == 11
    assert p["residual_layers"] == 88 and p["seq_len"] == 8192 and p["remat"] is True
    # the routers stay where the init put them for a run: the first step's rate is 0 and the rates of a run's 110
    # steps sum to under 1e-4 (the configuration's ``assumed`` says why), the biases move by under 0.01 in all
    assert p["lr_warmup_steps"] >= 1 and 110 * p["learning_rate"] < 1e-4 and 110 * p["bias_update_speed"] < 0.01
    assert p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {
        "layers", "mamba", "rotary", "attention", "latent_moe", "correction_bias", "left_out", "init", "optimizer",
        "precision", "weights", "remat", "depth", "data"}
    for key in ("mamba", "rotary", "latent_moe", "init"):
        assert "from memory" in config["assumed"][key], key
    assert "rope_theta" in config["assumed"]["rotary"] and "multi-token-prediction" in config["assumed"]["left_out"]
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room (the least where a flipped expert
        # choice is the reading: grad_experts, grad_latent, grad_router, checks_why)
        assert 1.3 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.4 * config["checks"][check]["limit"], (name, check)
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"ssm_decay", "router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(16384) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]


def test_the_share_is_the_arithmetic_the_file_states():
    """773.6 M parameters: the model's own init at the configuration's keys,
    counted (shapes only), against the cost model's count and the hand
    counts (27.41 / 9.44 / 98.57 M a layer)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(11)]
    e, m, a = blocks["b00"], blocks["b01"], blocks["b10"]
    assert m["ssm_in"].shape == (4096, 4640) and m["ssm_out"].shape == (2048, 4096) and m["conv_w"].shape == (4, 2560)
    assert a["wq"].shape == (4096, 1024) and a["wk"].shape == a["wv"].shape == (4096, 128) and a["wo"].shape == (1024, 4096)
    assert e["router"].shape == (4096, 512) and e["w_up"].shape == (8, 1024, 2688) and e["w_down"].shape == (8, 2688, 1024)
    assert e["ws_up"].shape == (4096, 5376) and e["w_lat_down"].shape == (4096, 1024) and shapes["head"].shape == (4096, 16384)
    per_layer = {"M": count(m), "*": count(a), "E": count(e)}
    assert per_layer == {"M": costs["params_m_layer"], "*": costs["params_attention_layer"], "E": costs["params_e_layer"]}
    assert {k: round(v / 1e6, 2) for k, v in per_layer.items()} == {"M": 27.41, "*": 9.44, "E": 98.57}
    assert count(shapes) == costs["params_total"] and round(count(shapes) / 1e6, 1) == 773.6
    assert "773.6 M" in config["reduced_why"] and "773.6 M" in config["deployment"]


@pytest.mark.parametrize("name", JOINED + OWN)
def test_every_metric_the_cell_reports_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    # a JOINED name is another cell's entry too; an OWN name is this cell's, its list STARTS with the cell and a later cell may join it
    assert CELL in entry["workloads"] and (entry["workloads"] != [CELL] if name in JOINED else entry["workloads"][0] == CELL)
    spec = bench.metric_file(name)
    assert callable(bench.reader(spec["reader"]).read)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    # a parameter that names a cost-model key names one this cell's cost model has
    for key in ("flops_per_unit", "units_per_step", "unit_flops", "flops", "bytes"):
        if key in spec.get("params", {}):
            assert spec["params"][key] in _costs(), (name, key)
    for kernel in spec.get("params", {}).get("kernels", []):
        assert kernel["units_key"] in _costs(), name


@pytest.fixture(scope="module")
def step_op_names():
    """The ``op_name`` of every instruction of the model's compiled forward
    and backward at the rehearsal's sizes: the ``jax.named_scope`` path as
    the compiled step spells it (``jvp(ssm_proj)``, ``checkpoint/ssm_proj``,
    ``rematted_computation/ssm_proj``), which is what the profiler's
    ``trace.json.gz`` carries as ``tf_op`` and ``op_ms_step`` matches."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        p = {**config["model_params"], **json.load(f)["model_params"], "seq_len": 64}
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    loss = lambda w, batch: spec.loss(spec.apply(w, batch, train=True), batch)  # noqa: E731
    compiled = jax.jit(jax.value_and_grad(loss)).lower(jax.eval_shape(spec.init, jax.random.key(0)), spec.example_batch(2)).compile()
    # an instruction XLA merged out of two carries both paths, ";" between them: each is a spelling
    return sorted({path for name in re.findall(r'op_name="([^"]+)"', compiled.as_text()) for path in name.split(";")})


#: every scope the hybrid's step is traced under (models/mamba.py, models/moe_lm.py, models/attentions.py, ops/ssm.py, ops/moe.py)
SCOPES = ("ssm_proj", "ssm_conv", "ssm_norm", "ssm_scan", "moe_latent", "moe_shared", "moe_router", "moe_dispatch",
          "moe_experts", "moe_combine", "attn_proj", "flash_attn", "lm_head")


def _scopes_of(op_name: str) -> set:
    return {scope for scope in SCOPES if re.search(rf"\b{scope}\b", op_name)}


@on_the_tree_itself
@pytest.mark.parametrize("name", sorted(SCOPE_ENTRIES))
def test_a_scope_entry_reads_its_scope_as_the_compiled_step_spells_it_and_not_its_neighbours(step_op_names, name):
    params = resolve.Bench(ROOT).metric_file(name)["params"]
    assert (params["module"], params["on"]) == ("jit_local_scan", "scope") and "exclude" not in params
    wanted = SCOPE_ENTRIES[name]
    matched = [op for op in step_op_names if re.search(params["pattern"], op)]
    # every scope it names is there, forward and backward, and everything under them is read ...
    for scope in wanted:
        assert any(scope in _scopes_of(op) and "transpose(" not in op for op in matched), scope
        assert any(scope in _scopes_of(op) and "transpose(" in op for op in matched), scope
    assert matched == [op for op in step_op_names if _scopes_of(op) & wanted]
    # ... and nothing under a neighbour: ``ssm_proj`` is not ``ssm_conv``, ``attn_proj`` not ``flash_attn``
    assert all(_scopes_of(op) <= wanted for op in matched)
    for neighbour in set(SCOPES) - wanted:
        assert not re.search(params["pattern"], f"jit(local_scan)/jvp({neighbour})/dot_general"), neighbour


def test_the_glue_in_ms_is_the_selection_its_share_of_the_bandwidth_has():
    bench = resolve.Bench(ROOT)
    ms, share = bench.metric_file("ssm_glue_ms_step.ssm"), bench.metric_file("ssm_glue_hbm_pct.ssm")
    assert (ms["params"]["module"], ms["params"]["pattern"]) == (share["params"]["module"], share["params"]["pattern"])
    # scope_hbm_roofline asks op_ms_step for exactly this selection: the two move together
    assert ms["reader"] == "op_ms_step" and share["reader"] == "scope_hbm_roofline" and ms["layer"] == share["layer"] == "ops"


def test_the_kernels_share_reads_the_scans_two_counters_and_nothing_where_a_program_has_none(monkeypatch):
    """``ssm_scan_kernel_pct.ssm``: the growth of ``ssm_positions_kernel``
    over that of ``ssm_positions`` (PR 44's counters), x 100: 100 while
    every scan call is on the kernels, so a later PR that falls off their
    path in silence is seen.  The pair is among the step counters the
    worker sums and publishes (``ModelSpec.step_counters``: the worker's
    running sums and its ``edl_<name>_total`` gauges are keyed by it)."""
    import runfiles

    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker import worker

    bench = resolve.Bench(ROOT)
    spec = bench.metric_file("ssm_scan_kernel_pct.ssm")
    assert spec["reader"] == "counter_delta" and (spec["better"], spec["source"]) == ("higher", "program_counter")
    assert spec["params"] == {"counter": "ssm_positions_kernel", "over": "ssm_positions", "scale": 100}
    pair = {spec["params"]["counter"], spec["params"]["over"]}
    counters = lambda config: load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"]).step_counters  # noqa: E731
    ours = counters(bench.config(CONFIG))
    assert pair <= set(ours) and all(ours[name] for name in pair)  # each with its gauge's help text
    assert not pair & set(worker.STEP_COUNTERS) and not pair & set(worker.COUNTER_GAUGES)  # the model's own, not the trainer's
    assert not pair & set(counters(bench.config("olmoe_1b_7b_l1")))  # a model without a state-space layer counts neither

    def read(records):
        monkeypatch.setattr(runfiles, "counter_records", lambda ctx: records)
        return bench.reader("counter_delta").read({}, spec["params"])

    positions = 8192 * 32 * 5 * 2  # a task's (head, position) pairs: 8192 tokens, 32 heads held, five M layers, two steps
    on_kernels = [{"ssm_positions": float(i * positions), "ssm_positions_kernel": float(i * positions), "moe_slots": 3.0 * i} for i in range(1, 5)]
    assert read(on_kernels) == 100.0
    fell_off = [dict(r, ssm_positions_kernel=r["ssm_positions_kernel"] if i < 2 else 2.0 * positions) for i, r in enumerate(on_kernels)]
    assert read(fell_off) == pytest.approx(100.0 / 3)
    assert read([dict(r, ssm_positions_kernel=0.0) for r in on_kernels]) == 0.0  # a share of the work, not of a peak: nought is a reading
    without = [{"moe_slots": 3.0 * i, "compiles": 5.0} for i in range(1, 5)]  # a program older than PR 44, or another model's
    assert read(without) is None and read(on_kernels[:1]) is None and read([]) is None
    assert read([{"ssm_positions_kernel": 0.0}, {"ssm_positions_kernel": 0.0}]) is None  # no scan advanced: no ratio


def test_nemotron_h_flops_counts_what_its_docstring_says():
    costs = _costs()
    m = 4096 * (2048 + 2560 + 32) + 2048 * 4096
    a = 2 * 4096 * 8 * 128 + 2 * 4096 * 128
    e = 4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 22 * 8 / 512 * 2 * 1024 * 2688
    assert costs["active_matmul_params"] == 5 * m + a + 5 * e + 4096 * 16384
    assert round(costs["active_matmul_params"] / 1e6) == 496
    # the scan's needed FLOPs a position and layer: C B^T, the masked product, the end state, the read-out
    assert costs["ssm_scan_flops_per_position"] == 2 * (128 * 128 * 2 + 128 * 64 * 32 + 2 * 64 * 128 * 32) == 1638400
    assert costs["attention_flops_per_token"] == 8 * 4096 * 3 * 512
    assert costs["train_flops_per_token"] == 6 * costs["active_matmul_params"] + costs["attention_flops_per_token"] + 3 * 5 * 1638400
    assert round(costs["train_flops_per_token"] / 1e9, 2) == 3.05
    # by needed FLOPs a token: M 28 %, E 55 %, the head 13 %, the attention layer 3.5 % (the cell's why)
    share = lambda flops: round(100 * flops / costs["train_flops_per_token"], 1)  # noqa: E731
    assert share(5 * (6 * m + 3 * 1638400)) == 27.8 and share(5 * 6 * e) == 55.5
    assert share(6 * 4096 * 16384) == 13.2 and share(6 * a + costs["attention_flops_per_token"]) == 3.5
    assert costs["ssm_scan_flops_per_step"] == 8192 * 5 * 3 * 1638400
    assert costs["ssm_scan_bytes_per_step"] == 8192 * 5 * 3 * (2 * (2048 + 512) + 4 * 32 + 2 * 2048)
    assert costs["ssm_glue_bytes_per_step"] == 8192 * 5 * 2 * (5 * 2560 + 8 * 2048)
    assert (costs["flash_unit_flops"], costs["flash_fwd_units"], costs["flash_bwd_units"], costs["flash_bwd_second_units"]) == (
        8 * 8192 * 8192 // 2, 512, 1280, 0)
    assert costs["moe_slots_per_step"] == 8192 * 22 * 5 and costs["expert_flops_per_slot"] == 2 * 3 * 2 * 1024 * 2688
    # the scan's own count, from the op's shapes, is the cost model's
    from elasticdl_tpu.ops import ssm

    assert ssm.scan_flops(1, 8192, 32, 64, 2, 128, 128) == 8192 * costs["ssm_scan_flops_per_position"]


def test_scope_roofline_larger_takes_the_larger_of_the_two_times(monkeypatch):
    reader = resolve.Bench(ROOT).reader("scope_roofline_larger")
    monkeypatch.setattr(resolve, "load_module", lambda path: type("R", (), {"read": staticmethod(lambda ctx, params: ctx["ms"])}))
    ctx = {"costs": {"f": 197e12 * 1e-3, "b": 819e9 * 2e-3}, "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "ms": 4.0}
    params = {"module": "m", "pattern": "p", "flops": "f", "bytes": "b"}
    assert reader.read(ctx, params) == pytest.approx(50.0)  # the bytes' 2 ms over 4 ms
    ctx["costs"]["f"] *= 3
    assert reader.read(ctx, params) == pytest.approx(75.0)  # now the FLOPs' 3 ms
    ctx["ms"] = None
    assert reader.read(ctx, params) is None  # no scope in the trace: no metric


def test_the_references_recurrence_is_the_closed_form_on_a_constant_decay():
    """``S_t = a S_{t-1} + dt x_t B^T`` with a constant decay, input and B, C:
    ``y_t = dt (B . C) x (1 - a^(t + 1)) / (1 - a)``; and the segmented
    gradient is the unsegmented one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    length, heads, width, state = 24, 2, 3, 4
    x = jnp.ones((1, length, heads, width)) * jnp.array([1.0, -2.0, 0.5])
    dt = jnp.full((1, length, heads), 0.1)
    a = jnp.array([-1.0, -3.0])
    b = jnp.ones((1, length, 1, state)) * jnp.arange(1.0, 5.0)
    c = jnp.ones((1, length, 1, state)) * 0.25
    y, last = reference.recurrence(x, dt, a, b, c)
    decay = np.exp(0.1 * np.asarray(a))  # a head
    t = np.arange(1, length + 1)
    geometric = (1 - decay[None, :] ** t[:, None]) / (1 - decay[None, :])  # [L, H]
    want = 0.1 * float(jnp.sum(b[0, 0, 0] * c[0, 0, 0])) * geometric[:, :, None] * np.asarray(x[0, 0])[None]
    np.testing.assert_allclose(np.asarray(y[0]), want, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(last[0, :, :, 0]), 0.1 * geometric[-1][:, None] * np.asarray(x[0, 0]), rtol=1e-5)
    loss = lambda segment: (lambda x, dt: jnp.sum(reference.recurrence(x, dt, a, b, c, segment=segment)[0] ** 2))  # noqa: E731
    for got, ref in zip(jax.grad(loss(8), (0, 1))(x, dt), jax.grad(loss(24), (0, 1))(x, dt)):
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


@on_the_tree_itself
def test_the_layerwise_reference_program_is_value_and_grad_of_the_plain_model():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from elasticdl_tpu.models.spec import load_model_spec

    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        p = {**config["model_params"], **json.load(f)["model_params"], "seq_len": 64}
    reference = resolve.load_module(bench.reference_path(CONFIG))
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    weights = reference.check_weights(jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0))))
    toks = np.random.default_rng(0).integers(0, p["vocab_size"], (2, 65)).astype(np.int32)
    tokens, labels = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    forward = reference.build(p)

    def loss(w):
        z, slots = forward(w, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(z, labels).mean(), (z, slots)

    (want, (want_z, want_slots)), want_grads = jax.value_and_grad(loss, has_aux=True)(weights)
    (got, (z, slots)), grads = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(z, want_z, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7, err_msg=str(path))
    _, none = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels, gradient=False)
    assert none is None


def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under nemotron_h's keys on the CPU, the float32
    reference child on the first task's records with the configuration's
    checks.  Never a result line; exit code 4."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache",
                                      "parent_tree", "final_tree"),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000029", "--seconds", "3",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/nemotron3_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and at the toy's 4 of 16 experts a flipped
    # choice weighs more in the experts' gradients than the limits drawn at 8 of 512 allow; nothing else is wrong
    flips = ("grad_experts", "grad_latent", "grad_router")
    assert [p for p in info["problems"] if "outside the band" not in p and not any(f"check {name}:" in p for name in flips)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.65  # ln 256 + the toy head's variance
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in flips), checks
    assert all(checks[name]["value"] < 0.15 for name in flips), checks
    assert "compared: check ssm_output" in done.stderr and "compared: check adamw_update" in done.stderr
    metrics = result["metrics"]
    for name in ("host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok", "compiles_in_window.tok",
                 "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok", "hbm_peak_reported_gib.tok",
                 "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla",
                 "expert_load_max_pct_mean.moe", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    assert metrics["moe_slots_computed_pct.mla"]["value"] == 100.0
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only


@on_the_tree_itself
def test_rehearsal_of_the_checks_a_sound_system_reads_every_one_and_every_control_is_caught():
    """The sizing tool's table (what the reference child reads, sound and
    under every control, judged by run.py's ``reference_problems`` against
    the configuration's limits) on one seeded minibatch at the rehearsal's
    sizes, ONE table for all.  A control with a train step costs 20 s here,
    so only the two that nothing but the step can catch (``adamw_update``)
    run theirs; the other three of the tool's ``OWN_STEP`` are caught by a
    forward check the file names for them, as on the chip.
    A control is over the limit of a check the configuration's file names
    for it; the forgetful scan, whose limits are drawn where a sequence is
    64 chunks long and not 4, is told by its reading against the sound one."""

    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        override = json.load(f)
    config["model_params"].update(override["model_params"])
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "nemotron_h_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    table = sizing.check_table(config, reference, 2, [3300000031], reference.CONTROLS, own_step=("no_weight_decay", "state_unchanged"))
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    # at the toy's 4 of 16 experts the flipped choices weigh more than at 8 of 512: those three apart
    assert all("grad_experts" in p or "grad_latent" in p or "grad_router" in p for p in sound["problems"]), sound["problems"]
    # the train step's own loss is the reference's: the step ran on the checks' weights
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    assert sorted(table) == sorted(("sound",) + reference.CONTROLS)
    for control in reference.CONTROLS:
        (row,) = table[control]
        if control == "no_carried_state":
            assert row["readings"]["ssm_output"] > 2 * sound["readings"]["ssm_output"], row["readings"]
            continue
        named = config["controls"][control]["caught_by"]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert not row["correct"] and set(over) & set(named), (control, over, named)
