"""What PR 47 added to the benchmark: the configuration
``kimi_linear_48b_a3b_ep32_l5`` (Kimi Linear 48B-A3B at its published widths:
one chip's share of a 32-way expert-parallel stage, 5 of 27 layers), the
traffic mix ``job_seq8k_x1_v20480``, the cell ``kimi_linear_job``, the cost
model ``kimi_linear_flops`` and the ``.kda`` metrics.  CPU only."""

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

CELL, CONFIG, TRAFFIC = "kimi_linear_job", "kimi_linear_48b_a3b_ep32_l5", "job_seq8k_x1_v20480"
#: every per-layer metric the cell reports: the ones it JOINED (appended to their ``workloads``) and its own ``.kda``
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",  # PR 63: the recorder runs in every worker loop (PR 54)
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "flash_attn_ms_step.tok",
    "flash_roofline_pct.mla", "mla_proj_ms_step.mla", "remat_kept_pct.tok", "moe_shared_ms_step.mla",
    "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla",
    "expert_load_max_pct_mean.moe",
]
#: entry -> the scopes of the step it reads; every other scope of the step is a neighbour it must not read
SCOPE_ENTRIES = {
    "kda_proj_ms_step.kda": {"kda_proj"},
    "kda_glue_ms_step.kda": {"kda_glue"},
    "kda_scan_ms_step.kda": {"kda_scan"},
}
#: a share of the part's (head, position) pairs by a step counter over ``kda_positions``: entry -> the counter.  The second and
#: the third waited for room in ``per_layer`` since PR 49 and PR 53 and landed in PR 63
COUNTER_ENTRIES = {
    "kda_chunked_pct.kda": "kda_positions_chunked",
    "kda_mask_kernel_pct.kda": "kda_positions_mask_kernel",
    "kda_conv_kernel_pct.kda": "kda_positions_conv_kernel",
}
#: the scope ``kda_mask`` nests under ``kda_scan`` (PR 49; the entry PR 63): a part of ``kda_scan_ms_step.kda``, held by a case of its own
MASK_ENTRY = "kda_mask_ms_step.kda"
OWN = [*SCOPE_ENTRIES, "kda_scan_roofline_pct.kda", "kda_glue_hbm_pct.kda", *COUNTER_ENTRIES, MASK_ENTRY]
CHECKS = sorted([
    "kda_output", "kda_decay", "router_logits", "router_choices_differing", "head_logits", "logits", "adamw_update",
    "grad_kda", "grad_attention", "grad_dense", "grad_experts", "grad_shared", "grad_router", "grad_head",
    "grad_embedding", "grad_norms",
])
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name Kimi-Linear-48B-A3B-Instruct), copied: the guide is not in the checkout.
CATALOG = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu", "hidden_size": 2304, "intermediate_size": 9216,
    "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19, 21, 22, 23, 25, 26],
        "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576, "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True, "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8, "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1, "q_lora_rank": None, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000, "routed_scaling_factor": 2.446,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128, "vocab_size": 163840,
}
SOURCE = "https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "num_experts": 8, "vocab_size": 20480}
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


def _rehearsal_params(**more) -> dict:
    config = resolve.Bench(ROOT).config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        return {**config["model_params"], **json.load(f)["model_params"], **more}


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
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "kimi_linear_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED + OWN)
    # a cell with a configuration of its own may BRING eight (PERF.md section 7: this one brought six, PR 47); a later
    # ``benchmark`` PR may add what waited for room (PR 63: the three of PR 49 and PR 53)
    assert len(OWN) == 9
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 20480, 8192, "recordio")
    assert gen["vocab"] == config["model_params"]["vocab_size"] == config["vocab_size"]
    # no task repeats inside warm-up + the window: nothing is memorised
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64
    assert traffic["units_per_record"] == 8192 and traffic["minibatch_size"] == 1
    # ISSUE 47's parameters, as the other LM cells': two steps a task, four warm-up tasks
    assert traffic["minibatches_per_task"] == 2 and traffic["rate_metric"] == "tokens_per_s_chip"
    assert traffic["warmup_tasks"] == 4
    # a traced run's profile is two tasks, written by the task loop before the last warm-up task reports (warmup_why)
    assert traffic["job_flags"] == {"profile_tasks": 2, "profile_inline": True}
    for key in ("why", "minibatch_why", "generator_why", "warmup_why"):
        assert len(traffic[key]) > 80, key
    # the traffic is job_seq8k_x1's with another vocabulary slice, and nothing else
    other = bench.traffic("job_seq8k_x1")
    same = lambda t: {k: v for k, v in t.items() if not k.endswith("why") and k not in ("name", "generator")}  # noqa: E731
    assert same(traffic) == same(other) and {**other["generator"], "vocab": 20480} == gen


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_stated_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
    elif key == "linear_attn_config":  # the group is named; its two LISTS are cut to the layers kept, its widths are not
        assert key in config["reduced"]
        assert config[key] == {**CATALOG[key], "kda_layers": [1, 2, 3, 5], "full_attn_layers": [4]}
        assert all(n in CATALOG[key]["kda_layers"] for n in config[key]["kda_layers"]) and 4 in CATALOG[key]["full_attn_layers"]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == [*CUT, "linear_attn_config"]  # exactly the depth, the experts, the vocabulary (and the lists)
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("Kimi-Linear-48B-A3B-Instruct"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: a whole period and four layers after the dense one, 8 routed experts a layer, an eighth of the vocabulary
    kda = config["linear_attn_config"]
    assert len(kda["kda_layers"]) == 4 and len(kda["full_attn_layers"]) == 1 and config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert (len(CATALOG["linear_attn_config"]["kda_layers"]), len(CATALOG["linear_attn_config"]["full_attn_layers"])) == (20, 7)
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    for said in ("32-way expert-parallel", "8 of 256 routed experts a chip", "20,480 of 163,840", "5 of 27 layers", "NOT run"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths, under the published spelling of the keys: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "num_attention_heads", "intermediate_size", "moe_intermediate_size", "kv_lora_rank", "q_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "mla_use_nope", "rope_theta", "rms_norm_eps", "num_experts_per_token",
            "num_shared_experts", "moe_router_activation_func", "moe_renormalize", "routed_scaling_factor", "use_grouped_topk",
            "num_expert_group", "topk_group", "first_k_dense_replace", "moe_layer_freq", "tie_word_embeddings")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["linear_attn_config"] == kda and p["num_hidden_layers"] == 5 and p["vocab_size"] == 20480
    assert p["num_experts"] == CATALOG["num_experts"] == 256 and p["experts_held"] == config["num_experts"] == 8
    # the published keys no layer reads are not handed to the program (it would refuse them) and the file says why
    unread = sorted(set(CATALOG) - set(p))
    assert unread == ["head_dim", "hidden_act", "model_max_length", "model_type", "num_key_value_heads", "num_nextn_predict_layers", "rope_scaling"]
    assert all(key in config["assumed"]["head_dim"] for key in unread if key != "model_type")
    assert p["seq_len"] == 8192 and p["remat"] is True and p["decay_matrices_only"] is True
    # the routers stay where the init put them for a run: the published-style warm-up (kanana2's assumed says why)
    assert p["lr_warmup_steps"] == 2000 and p["learning_rate"] == 2.2e-4 and p["bias_update_speed"] == 0.001
    assert p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {
        "layers", "kda", "head_dim", "mla", "router", "correction_bias", "loss", "init", "optimizer", "precision", "weights",
        "remat", "depth", "data"}
    for key in ("kda", "correction_bias", "init", "optimizer"):
        assert "from memory" in config["assumed"][key], key
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.3 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    assert {"bfloat16_decay", "no_carried_state", "no_delta_correction"} <= set(reference.CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.4 * config["checks"][check]["limit"], (name, check)
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"kda_decay", "router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(20480) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]


def test_the_share_is_the_arithmetic_the_file_states():
    """602.4 M parameters = 8.98 GiB at 16 bytes: the model's own init at the
    configuration's keys, counted (shapes only), against the cost model's
    count and the hand counts (39.52 / 29.12 / 63.70 / 64.29 M a part)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(5)]
    dense, kda, mla = blocks["b00"], blocks["b01"], blocks["b03"]
    assert ["kda_wq" in blocks[name] for name in sorted(blocks)] == [True, True, True, False, True]  # layers 1, 2, 3, 5 of 1..5
    assert kda["kda_wq"].shape == (2304, 4096) and kda["kda_wo"].shape == (4096, 2304) and kda["kda_conv_k"].shape == (4, 4096)
    assert kda["kda_wf_a"].shape == (2304, 128) and kda["kda_wg_b"].shape == (128, 4096) and kda["kda_wb"].shape == (2304, 32)
    assert kda["A_log"].shape == (32,) and kda["dt_bias"].shape == (4096,) and kda["kda_norm"].shape == (128,)
    assert mla["wq"].shape == (2304, 32 * 192) and mla["wkv_a"].shape == (2304, 576) and mla["wkv_b"].shape == (512, 32 * 256) and mla["wo"].shape == (4096, 2304)
    assert kda["router"].shape == (2304, 256) and kda["w_up"].shape == (8, 2304, 1024) and kda["ws_up"].shape == (2304, 1024)
    assert dense["w_up"].shape == (2304, 9216) and "router" not in dense and shapes["head"].shape == (2304, 20480)
    part = lambda blk, names: count({k: v for k, v in blk.items() if k.startswith(names)})  # noqa: E731
    mixers = ("kda_", "A_log", "dt_bias", "attn_norm", "wq", "wkv", "kv_norm", "wo")
    counted = {"kda_mixer": part(kda, mixers), "latent_mixer": part(mla, mixers), "dense_ffn": count(dense) - part(dense, mixers),
               "expert_ffn": count(kda) - part(kda, mixers)}
    assert counted == {key: costs["params_" + key] for key in counted}
    assert {k: round(v / 1e6, 2) for k, v in counted.items()} == {"kda_mixer": 39.52, "latent_mixer": 29.12, "dense_ffn": 63.7, "expert_ffn": 64.29}
    assert count(shapes) == costs["params_total"] and round(count(shapes) / 1e6, 1) == 602.4
    assert round(16 * count(shapes) / 2**30, 2) == 8.98 and round(16 * count(shapes) / 1e9, 2) == 9.64
    for said in ("602.4 M", "8.98 GiB"):
        assert said in config["reduced_why"] and said in config["deployment"], said


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
    the compiled step spells it (``jvp(kda_proj)``, ``checkpoint/kda_proj``,
    ``rematted_computation/kda_proj``), which is what the profiler's
    ``trace.json.gz`` carries as ``tf_op`` and ``op_ms_step`` matches."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **_rehearsal_params(seq_len=64))
    loss = lambda w, batch: spec.loss(spec.apply(w, batch, train=True), batch)  # noqa: E731
    compiled = jax.jit(jax.value_and_grad(loss)).lower(jax.eval_shape(spec.init, jax.random.key(0)), spec.example_batch(2)).compile()
    # an instruction XLA merged out of two carries both paths, ";" between them: each is a spelling
    return sorted({path for name in re.findall(r'op_name="([^"]+)"', compiled.as_text()) for path in name.split(";")})


#: every scope the model's step is traced under (models/linear_attention.py, models/attentions.py, models/moe_lm.py,
#: ops/delta_rule.py, ops/ssm.py, ops/moe.py); ``ssm_conv`` (ops/ssm.causal_conv's own) nests under ``kda_glue``
#: here, and ``ssm_proj`` / ``ssm_norm`` / ``ssm_scan`` are another family's neighbours by name
SCOPES = ("kda_proj", "kda_glue", "kda_scan", "mla_proj", "flash_attn", "moe_shared", "moe_router", "moe_dispatch",
          "moe_experts", "moe_combine", "mlp", "lm_head")
OTHER_FAMILIES = ("ssm_proj", "ssm_conv", "ssm_norm", "ssm_scan", "attn_proj", "eva_proj", "moe_latent")


def _scopes_of(op_name: str) -> set:
    return {scope for scope in SCOPES if re.search(rf"\b{scope}\b", op_name)}


@on_the_tree_itself
def test_a_scope_entry_reads_its_scope_as_the_compiled_step_spells_it_and_not_its_neighbours(step_op_names):
    # ONE test over the three entries: the compiled step is made once (a case an entry would make it on three workers)
    for name in sorted(SCOPE_ENTRIES):
        _reads_its_scope_alone(step_op_names, name)


def _reads_its_scope_alone(step_op_names, name):
    params = resolve.Bench(ROOT).metric_file(name)["params"]
    assert (params["module"], params["on"]) == ("jit_local_scan", "scope") and "exclude" not in params
    wanted = SCOPE_ENTRIES[name]
    matched = [op for op in step_op_names if re.search(params["pattern"], op)]
    # every scope it names is there, forward and backward, and everything under them is read ...
    for scope in wanted:
        assert any(scope in _scopes_of(op) and "transpose(" not in op for op in matched), scope
        assert any(scope in _scopes_of(op) and "transpose(" in op for op in matched), scope
    assert matched == [op for op in step_op_names if _scopes_of(op) & wanted]
    # ... and nothing under a neighbour: ``kda_proj`` is not ``mla_proj`` or ``ssm_proj``, ``kda_scan`` not ``ssm_scan``
    assert all(_scopes_of(op) <= wanted for op in matched)
    for neighbour in (set(SCOPES) - wanted) | set(OTHER_FAMILIES):
        assert not re.search(params["pattern"], f"jit(local_scan)/jvp({neighbour})/dot_general"), neighbour
    # the convolution's own scope nests under the glue's, and the state-space family's glue entries would read it
    # there too: the cell does not join them (BENCHMARK.json), so nothing is read twice
    nested = [op for op in step_op_names if re.search(r"\bssm_conv\b", op)]
    assert nested and all("kda_glue" in op for op in nested)
    bench = resolve.Bench(ROOT)
    assert not any(CELL in m["workloads"] for m in bench.spec["per_layer"] if m["name"].endswith(".ssm"))


@on_the_tree_itself
def test_the_mask_entry_reads_a_proper_part_of_the_scans_scope_in_both_passes_and_no_neighbour(step_op_names):
    """``kda_mask_ms_step.kda``: everything ``ops/delta_rule._masks_of`` emits,
    under the scope ``kda_mask`` INSIDE ``kda_scan`` in both passes (the two
    Mosaic calls on the chip, the cross-sub-block products, the add that
    joins them): a part of ``kda_scan_ms_step.kda``, never beside it."""
    bench = resolve.Bench(ROOT)
    params = bench.metric_file(MASK_ENTRY)["params"]
    assert params == {"module": "jit_local_scan", "on": "scope", "pattern": r"\bkda_mask\b"}
    whole = [op for op in step_op_names if op.startswith("jit(")]  # a whole path; XLA's merged instructions also leave tails of paths
    mask = [op for op in whole if re.search(params["pattern"], op)]
    scan = [op for op in whole if re.search(bench.metric_file("kda_scan_ms_step.kda")["params"]["pattern"], op)]
    assert mask and set(mask) < set(scan) and all(_scopes_of(op) == {"kda_scan"} for op in mask)
    # as the COMPILED step spells it: the forward scan, the backward scan's own forward (jvp inside the transpose) and its
    # transpose, under the layer's checkpoint and inside the rematerialised repeat
    forward, backward = [op for op in mask if "transpose(" not in op], [op for op in mask if "transpose(" in op]
    assert any("jvp(kda_scan)" in op and "checkpoint/kda_mask/" in op for op in forward)
    assert any("jvp(kda_mask)" in op for op in backward) and any("rematted_computation/kda_mask/" in op for op in backward)
    assert any(re.search(r"transpose\(jvp\(jvp\(\)\)\)/checkpoint/kda_mask/", op) for op in backward)
    for spelt in ("jit(local_scan)/jvp(kda_scan)/while/body/checkpoint/kda_mask/pallas_call", "jit(local_scan)/transpose(jvp(kda_mask))/pallas_call"):
        assert re.search(params["pattern"], spelt)
    # ... and nothing of a neighbour's, by name: not a plural, not a bare ``mask``, no other scope of the step or of another family
    for neighbour in ("kda_masks", "mask", "kda_mask_grads", "dsa_mask", *SCOPES, *OTHER_FAMILIES):
        assert not re.search(params["pattern"], f"jit(local_scan)/jvp({neighbour})/dot_general"), neighbour


def test_the_glue_in_ms_is_the_selection_its_share_of_the_bandwidth_has_and_so_for_the_scan():
    bench = resolve.Bench(ROOT)
    for ms_name, share_name, reader in (("kda_glue_ms_step.kda", "kda_glue_hbm_pct.kda", "scope_hbm_roofline"),
                                        ("kda_scan_ms_step.kda", "kda_scan_roofline_pct.kda", "scope_roofline_larger")):
        ms, share = bench.metric_file(ms_name), bench.metric_file(share_name)
        assert (ms["params"]["module"], ms["params"]["pattern"]) == (share["params"]["module"], share["params"]["pattern"])
        # the share's reader asks op_ms_step for exactly this selection: the two move together
        assert ms["reader"] == "op_ms_step" and share["reader"] == reader and ms["layer"] == share["layer"] == "ops"
    # the roofline share is computed from shapes by functions kept under benchmark/: the same work whatever implements the op
    params = bench.metric_file("kda_scan_roofline_pct.kda")["params"]
    assert {params["flops"], params["bytes"]} <= set(_costs()) and "kernel" not in json.dumps(params["pattern"])


@pytest.mark.parametrize("name", sorted(COUNTER_ENTRIES))
def test_a_share_reads_its_counter_over_the_parts_positions_and_nothing_where_a_program_has_none(monkeypatch, name):
    """``kda_chunked_pct.kda``: the growth of ``kda_positions_chunked`` over
    that of ``kda_positions``, x 100: 100 while every call of the op is on
    its chunked form; ``kda_mask_kernel_pct.kda`` and
    ``kda_conv_kernel_pct.kda`` likewise for the positions whose
    same-sub-block masks and whose three convolution chains a Pallas kernel
    computed (100 on the chip, 0 off a TPU: a silent fall-back to XLA's
    fusions reads under 100).  Each pair is among the step counters the
    worker sums and publishes (``ModelSpec.step_counters``)."""
    import runfiles

    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker import worker

    bench = resolve.Bench(ROOT)
    spec, counter = bench.metric_file(name), COUNTER_ENTRIES[name]
    assert spec["reader"] == "counter_delta" and (spec["unit"], spec["better"], spec["source"], spec["layer"]) == ("%", "higher", "program_counter", "ops")
    assert spec["params"] == {"counter": counter, "over": "kda_positions", "scale": 100}
    pair = {spec["params"]["counter"], spec["params"]["over"]}
    counters = lambda config: load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"]).step_counters  # noqa: E731
    ours = counters(bench.config(CONFIG))
    assert pair <= set(ours) and all(ours[name] for name in pair)  # each with its gauge's help text
    assert not pair & set(worker.STEP_COUNTERS) and not pair & set(worker.COUNTER_GAUGES)  # the model's own, not the trainer's
    assert not pair & set(counters(bench.config("kanana2_30b_a3b_ep8_l5")))  # a model without a linear-attention layer counts neither

    def read(records):
        monkeypatch.setattr(runfiles, "counter_records", lambda ctx: records)
        return bench.reader("counter_delta").read({}, spec["params"])

    positions = 8192 * 32 * 4 * 2  # a task's (head, position) pairs: 8192 tokens, 32 heads, four KDA layers, two steps
    every = [{"kda_positions": float(i * positions), counter: float(i * positions), "moe_slots": 3.0 * i} for i in range(1, 5)]
    assert read(every) == 100.0
    assert read([dict(r, **{counter: r[counter] / 4}) for r in every]) == 25.0  # one layer of four on the path: a share
    assert read([dict(r, **{counter: 0.0}) for r in every]) == 0.0  # every call off the path: nought is a reading
    without = [{"moe_slots": 3.0 * i, "compiles": 5.0} for i in range(1, 5)]  # the parent's program, or another model's
    assert read(without) is None and read(every[:1]) is None and read([]) is None


def test_kimi_linear_flops_counts_what_its_docstring_says():
    costs = _costs()
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 4096 * 2304
    expert = 3 * 2304 * 1024
    moe = 2304 * 256 + expert + 8 * 8 / 256 * expert
    assert costs["active_matmul_params"] == 4 * kda + mla + 3 * 2304 * 9216 + 4 * moe + 2304 * 20480
    assert round(costs["active_matmul_params"] / 1e6, 1) == 335.6
    # the op's needed FLOPs a position and layer IN ITS CHUNKED FORM AT CHUNK 64: the two masks, the solve's triangle, P U, three dk x dv products, a head
    assert costs["kda_scan_flops_per_position"] == 32 * (4 * 64 * 128 + 64 * 256 + 2 * 64 * 128 + 6 * 128 * 128) == 5242880
    assert costs["attention_flops_per_token"] == 32 * 4096 * 3 * 640
    assert costs["train_flops_per_token"] == 6 * costs["active_matmul_params"] + costs["attention_flops_per_token"] + 3 * 4 * 5242880
    assert round(costs["train_flops_per_token"] / 1e9, 3) == 2.328
    # by needed FLOPs a token: the KDA layers 43 % (ISSUE 47: 44), the dense MLP 16 %, latent attention 18 %, experts + shared + router 10 %, the head 12 %
    share = lambda flops: round(100 * flops / costs["train_flops_per_token"], 1)  # noqa: E731
    assert share(4 * (6 * kda + 3 * 5242880)) == 43.4 and share(6 * 3 * 2304 * 9216) == 16.4
    assert share(6 * mla + costs["attention_flops_per_token"]) == 18.3 and share(4 * 6 * moe) == 9.7 and share(6 * 2304 * 20480) == 12.2
    assert costs["kda_scan_flops_per_step"] == 8192 * 4 * 3 * 5242880
    assert costs["kda_scan_bytes_per_step"] == 8192 * 4 * 3 * (3 * 2 * 4096 + 4 * 4096 + 4 * 32 + 2 * 4096)
    assert costs["kda_glue_bytes_per_step"] == 8192 * 4 * (3 * 2 * 5 + 2 * 2 * 5 + 2 * 6 + 2 * 8) * 4096
    assert (costs["flash_unit_flops"], costs["flash_fwd_units"], costs["flash_bwd_units"], costs["flash_bwd_second_units"]) == (
        32 * 8192 * 8192 // 2, 640, 1664, 0)
    assert costs["moe_slots_per_step"] == 8192 * 8 * 4 and costs["expert_flops_per_slot"] == 3 * 3 * 2 * 2304 * 1024
    # the op's own count, from its shapes, is the cost model's; and the chunk is the part's
    from elasticdl_tpu.models.linear_attention import KimiDeltaAttention
    from elasticdl_tpu.ops import delta_rule

    assert delta_rule.rule_flops(1, 8192, 32, 128, 128, 64) == 8192 * costs["kda_scan_flops_per_position"]
    cost_model = resolve.Bench(ROOT).costs("kimi_linear_flops")
    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    assert KimiDeltaAttention(32, 128, 4, 1e-5).chunk == cost_model.KDA_CHUNK == reference.KDA_CHUNK == 64


def test_the_references_recurrence_overwrites_a_key_and_forgets_by_the_decay():
    """The delta rule with beta 1 WRITES: after ``(k, v)`` the state answers
    ``k`` with exactly ``v`` whatever it held for ``k`` before; a key
    orthogonal to it keeps its value, times the decay of every step since;
    and the segmented gradient is the unsegmented one."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    length, dk, dv = 24, 4, 3
    e0, e1 = jnp.eye(dk)[0], jnp.eye(dk)[1]
    k = jnp.stack([e0 if t % 2 == 0 else e1 for t in range(length)])[None, :, None, :]  # [1, L, 1, dk]
    v = jax.random.normal(jax.random.key(0), (1, length, 1, dv))
    g = jnp.full((1, length, 1, dk), -0.1)
    beta = jnp.ones((1, length, 1))
    o_same, _ = reference.recurrence(k, k, v, g, beta)  # q = k: what was just written
    np.testing.assert_allclose(o_same, v, rtol=1e-5, atol=1e-6)
    q_other = jnp.stack([e1 if t % 2 == 0 else e0 for t in range(length)])[None, :, None, :]  # the key written one step before
    o_other, last = reference.recurrence(q_other, k, v, g, beta)
    np.testing.assert_allclose(o_other[:, 1:], np.exp(-0.1) * np.asarray(v[:, :-1]), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(last[0, 0, 1], v[0, -1, 0], rtol=1e-5)  # the last write (t = 23: e1) stands
    beta = jax.nn.sigmoid(jax.random.normal(jax.random.key(1), (1, length, 1)))
    loss = lambda segment: (lambda k, v, g, beta: jnp.sum(reference.recurrence(q_other, k, v, g, beta, segment=segment)[0] ** 2))  # noqa: E731
    for got, ref in zip(jax.grad(loss(8), (0, 1, 2, 3))(k, v, g, beta), jax.grad(loss(24), (0, 1, 2, 3))(k, v, g, beta)):
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
    p = _rehearsal_params(seq_len=64)
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
        # float32 sums in another order (a layer at a time): 1e-4 of an entry, or 1e-5 of the leaf's largest
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(b).max()), err_msg=str(path))
    _, none = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels, gradient=False)
    assert none is None
    # the leading dense layer's gated MLP is read apart from the experts' (the leaves are named alike)
    groups = {reference.group_of(path, grads) for path, _ in jax.tree_util.tree_leaves_with_path(grads)}
    assert groups == {"kda", "attention", "dense", "experts", "shared", "router", "head", "embedding", "norms"}
    assert {f"grad_{g}" for g in groups} | {"kda_output", "kda_decay", "router_logits", "router_choices_differing", "head_logits",
                                            "logits", "adamw_update"} == set(CHECKS)


@on_the_tree_itself  # (three minutes of a job; the growth rehearsal runs kanana2's and nemotron3's jobs on its grown copies)
def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under kimi_linear's keys on the CPU, the float32
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
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000029", "--seconds", "8",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/kimi_linear_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and a toy of 64-wide layers reads more of the
    # bfloat16 compute's noise in its logits and gradients than the limits drawn at 2304 allow; nothing else is wrong
    noisy = ("logits",) + tuple(name for name in CHECKS if name.startswith("grad_"))
    # (nor is a window that held a single report on a loaded test machine)
    excused = lambda p: "outside the band" in p or "inside the window" in p or any(f"check {name}:" in p for name in noisy)  # noqa: E731
    assert [p for p in info["problems"] if not excused(p)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.65  # ln 256 + the toy head's variance
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in noisy), checks
    assert all(checks[name]["value"] < 0.25 for name in noisy), checks
    assert "compared: check kda_output" in done.stderr and "compared: check adamw_update" in done.stderr
    metrics = result["metrics"]
    for name in ("hbm_peak_reported_gib.tok", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    # what is read off the window's reports (the counters' growth between its first and last) needs reports in it:
    # asked where the window held three (four KDA layers of a toy are slow on a test machine that runs six suites)
    counted = ("compiles_in_window.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla",
               "expert_load_max_pct_mean.moe", "kda_chunked_pct.kda")
    if info["window"]["reports"] >= 3:
        assert all(name in metrics for name in counted), sorted(metrics)
    assert all(metrics[name]["value"] == 100.0 for name in ("moe_slots_computed_pct.mla", "kda_chunked_pct.kda") if name in metrics)
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
    # the two counters are in the worker's reports
    assert re.search(r'"kda_positions": [\d.e+]+', done.stdout + open(scratch / "benchmark" / ".state" / "runs" / CELL / "metrics" / "metrics.jsonl").read())


@on_the_tree_itself
def test_rehearsal_of_the_checks_a_sound_system_reads_every_one_and_every_control_is_caught():
    """The sizing tool's table (what the reference child reads, sound and
    under the controls, judged by run.py's ``reference_problems`` against
    the configuration's limits) on one seeded minibatch at the rehearsal's
    sizes, ONE table for all.  Only the two controls that nothing but the
    step can catch (``adamw_update``) run their own train step; the others
    are caught by a forward check the file names for them, as on the chip.
    A control is over the limit of a check the configuration's file names
    for it."""
    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        override = json.load(f)
    config["model_params"].update(override["model_params"])
    # three layers of the five (the dense one, a latent-attention one, a KDA one with experts): a control costs a compile
    config["model_params"].update(num_hidden_layers=3, linear_attn_config={
        **override["model_params"]["linear_attn_config"], "kda_layers": [1, 3], "full_attn_layers": [2]})
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "kimi_linear_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    # the two controls that all_bfloat16 holds together and the second of the train step's own are read on the chip
    # alone (the configuration's controls_read): a control costs this test a compile
    controls = tuple(c for c in reference.CONTROLS if c not in ("bfloat16_router", "bfloat16_logits", "no_weight_decay"))
    assert {"bfloat16_decay", "no_carried_state", "no_delta_correction", "all_bfloat16", "state_unchanged"} == set(controls)
    table = sizing.check_table(config, reference, 2, [3300000031], controls, own_step=sizing.OWN_STEP)
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    # at the toy's widths the bfloat16 noise in logits and gradients is over the limits drawn at 2304: those apart
    assert all(re.match(r"check (logits|grad_\w+):", p) for p in sound["problems"]), sound["problems"]
    # the train step's own loss is the reference's: the step ran on the checks' weights
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    assert sorted(table) == sorted(("sound",) + controls)
    for control in controls:
        (row,) = table[control]
        # a control without a train step of its own reads the forward checks alone: those of the checks the file names for it
        named = [check for check in config["controls"][control]["caught_by"] if check in row["readings"]]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert named and not row["correct"] and set(over) & set(named), (control, over, named)
        for check in named:  # and by more than the sound system's own reading
            assert not row["readings"][check] <= 2 * sound["readings"][check], (control, check)
