"""What PR 56 added to the benchmark: the configuration
``trinity_mini_26b_a3b_ep8_l5`` (Trinity-Mini 26B-A3B, ``afmoe``, at its
published widths: one chip's share of an 8-way expert-parallel stage, 5 of 32
layers), the traffic mix ``job_seq8k_x1_v25024``, the cell
``trinity_mini_job``, the cost model ``afmoe_flops`` and the ``.swa`` metrics.
CPU only."""

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

CELL, CONFIG, TRAFFIC = "trinity_mini_job", "trinity_mini_26b_a3b_ep8_l5", "job_seq8k_x1_v25024"
#: every per-layer metric the cell reports: the ones it JOINED (appended to their ``workloads``) and its own ``.swa``
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "flash_attn_ms_step.tok", "flash_roofline_pct.tok",
    "remat_kept_pct.tok", "moe_shared_ms_step.mla", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla",
    "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla", "expert_load_max_pct_mean.moe",
    "attn_proj_ms_step.ssm",  # the part's five projections under the scope ``nemotron3_job``'s four have: ONE entry since PR 63
]
#: entry -> the scopes of the step it reads; every other scope of the step is a neighbour it must not read
SCOPE_ENTRIES = {
    "window_attn_ms_step.swa": {"window_attn"},
    "attn_glue_ms_step.swa": {"attn_glue"},
    "flash_attn_ms_step.tok": {"flash_attn"},
    "attn_proj_ms_step.ssm": {"attn_proj"},
}
OWN = ["window_attn_ms_step.swa", "window_roofline_pct.swa", "window_pairs_needed_pct.swa", "attn_glue_ms_step.swa", "attn_glue_hbm_pct.swa"]
CHECKS = sorted([
    "window_output", "full_output", "router_logits", "router_choices_differing", "head_logits", "logits", "adamw_update",
    "grad_attention", "grad_dense", "grad_experts", "grad_shared", "grad_router", "grad_head", "grad_embedding", "grad_norms",
])
SLIDING, FULL = "sliding_attention", "full_attention"
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name Trinity-Mini), copied: the guide is not in the checkout.
CATALOG = {
    "global_attn_every_n_layers": 4, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 8, "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
    "model_type": "afmoe", "moe_intermediate_size": 1024, "mup_enabled": True, "n_group": 1, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_expert_groups": 1, "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 32,
    "num_key_value_heads": 4, "num_limited_groups": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid", "sliding_window": 2048,
    "tie_word_embeddings": False, "topk_group": 1, "use_grouped_mm": True, "vocab_size": 200192,
}
SOURCE = "https://huggingface.co/arcee-ai/Trinity-Mini/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 16, "vocab_size": 25024, "layer_types": [SLIDING, SLIDING, SLIDING, FULL, SLIDING]}
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
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "trinity_mini_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED + OWN)
    assert len(OWN) <= 8  # what a cell with a configuration of its own may bring (PERF.md section 7)
    # NOT joined, each for its reason (PERF.md section 4): the scope ``mlp`` nests under ``moe_shared`` here, and the
    # optimizer entry's pattern leaves out kanana2's head by its shape, which is not this cell's
    for name in ("mlp_ms_step.eva", "optimizer_ms_step.mla", "flash_roofline_pct.mla"):
        (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"], name
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 25024, 8192, "recordio")
    assert gen["vocab"] == config["model_params"]["vocab_size"] == config["vocab_size"]
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64  # no task repeats inside warm-up + the window
    assert traffic["units_per_record"] == 8192 and traffic["minibatch_size"] == 1
    assert traffic["minibatches_per_task"] == 2 and traffic["rate_metric"] == "tokens_per_s_chip" and traffic["warmup_tasks"] == 4
    assert traffic["job_flags"] == {"profile_tasks": 2, "profile_inline": True}
    for key in ("why", "minibatch_why", "generator_why", "warmup_why"):
        assert len(traffic[key]) > 80, key
    # the traffic is job_seq8k_x1_v20480's with another vocabulary slice, and nothing else
    other = bench.traffic("job_seq8k_x1_v20480")
    same = lambda t: {k: v for k, v in t.items() if not k.endswith("why") and k not in ("name", "generator")}  # noqa: E731
    assert same(traffic) == same(other) and {**other["generator"], "vocab": 25024} == gen
    assert gen["seq_len"] == 4 * config["sliding_window"]  # four windows long


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_stated_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
        if key == "layer_types":  # the published list's own first five: three sliding, a full, a sliding
            assert config[key] == CATALOG[key][:5]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == list(CUT)  # exactly the depth (twice), the experts, the vocabulary, the list
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("Trinity-Mini"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: four layers after the dense one in the published 3 : 1, 16 routed experts a layer, an eighth of the vocabulary
    kinds = config["layer_types"]
    assert config["num_hidden_layers"] - config["num_dense_layers"] == 4 and kinds[1:].count(SLIDING) == 3 and kinds[1:].count(FULL) == 1
    assert CATALOG["layer_types"].count(SLIDING) == 24 and CATALOG["layer_types"].count(FULL) == 8
    assert config["num_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    for said in ("8-way expert-parallel", "16 of 128 routed experts a chip", "25,024 of 200,192", "5 of 32 layers", "NOT run"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths, under the published spelling of the keys: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "moe_intermediate_size",
            "sliding_window", "rope_theta", "rms_norm_eps", "num_experts_per_tok", "num_shared_experts", "score_func", "route_norm",
            "route_scale", "n_group", "topk_group", "load_balance_coeff", "mup_enabled", "tie_word_embeddings")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["layer_types"] == kinds and p["num_hidden_layers"] == 5 and p["num_dense_layers"] == 1 and p["vocab_size"] == 25024
    assert p["num_experts"] == CATALOG["num_experts"] == 128 and p["experts_held"] == config["num_experts"] == 16
    # the published keys no layer reads are not handed to the program (it would refuse them) and the file says why
    unread = sorted(set(CATALOG) - set(p))
    assert unread == ["global_attn_every_n_layers", "hidden_act", "max_position_embeddings", "model_type", "num_expert_groups",
                      "num_limited_groups", "rope_scaling", "use_grouped_mm"]
    assert all(key in config["assumed"]["unread"] for key in unread)
    assert p["seq_len"] == 8192 and p["remat"] is True and p["decay_matrices_only"] is True
    assert p["lr_warmup_steps"] == 2000 and p["learning_rate"] == 2.2e-4 and p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {
        "layers", "attention", "embedding", "router", "correction_bias", "loss", "unread", "init", "optimizer", "precision", "weights",
        "remat", "depth", "data"}
    for key in ("layers", "attention", "embedding", "router", "correction_bias", "loss", "init", "optimizer"):
        assert "from memory" in config["assumed"][key], key
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.3 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    assert {"full_for_window", "window_off_by_one", "rotary_on_full_layers"} <= set(reference.CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.4 * config["checks"][check]["limit"], (name, check)
    assert "window_output" in config["controls"]["full_for_window"]["caught_by"]
    assert "window_output" in config["controls"]["window_off_by_one"]["caught_by"]
    assert "logits" in config["controls"]["rotary_on_full_layers"]["caught_by"]
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(25024) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]
    assert "@" not in json.dumps({k: v for k, v in config.items() if k != "source"})  # no reading left to fill in


def test_the_share_is_the_arithmetic_the_file_states():
    """705.5 M parameters = 10.51 GiB at 16 bytes: the model's own init at
    the configuration's keys, counted (shapes only), against the cost model's
    count and the hand counts (27.27 / 37.75 / 107.22 M a part)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(5)]
    dense, expert = blocks["b00"], blocks["b01"]
    assert ["router" in blocks[name] for name in sorted(blocks)] == [False, True, True, True, True]
    assert expert["wq"].shape == expert["wz"].shape == (2048, 4096) and expert["wk"].shape == expert["wv"].shape == (2048, 512)
    assert expert["wo"].shape == (4096, 2048) and expert["q_norm"].shape == expert["k_norm"].shape == (128,)
    assert expert["router"].shape == (2048, 128) and expert["w_up"].shape == (16, 2048, 1024) and expert["ws_up"].shape == (2048, 1024)
    assert dense["w_up"].shape == (2048, 6144) and shapes["head"].shape == (2048, 25024) and shapes["tok_emb"].shape == (25024, 2048)
    attention = ("wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm", "attn_norm", "post_attn_norm")
    part = lambda blk, names: count({k: v for k, v in blk.items() if k in names})  # noqa: E731
    counted = {"attention": part(expert, attention), "dense_ffn": count(dense) - part(dense, attention),
               "expert_ffn": count(expert) - part(expert, attention)}
    assert counted == {key: costs["params_" + key] for key in counted}
    assert {k: round(v / 1e6, 2) for k, v in counted.items()} == {"attention": 27.27, "dense_ffn": 37.75, "expert_ffn": 107.22}
    assert count(shapes) == costs["params_total"] == 705474304 and round(count(shapes) / 1e6, 1) == 705.5
    assert round(16 * count(shapes) / 2**30, 2) == 10.51 and round(16 * count(shapes) / 1e9, 2) == 11.29
    for said in ("705.5 M", "10.51 GiB"):
        assert said in config["reduced_why"] and said in config["deployment"], said
    # the layers are what the published list's first five say: the full one is the fourth
    windows = [layer[0][1].window for layer in spec.init.keywords["layers"]]
    assert windows == [2048, 2048, 2048, 0, 2048]


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
    the compiled step spells it, which is what the profiler's
    ``trace.json.gz`` carries as ``tf_op`` and ``op_ms_step`` matches."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    params = _rehearsal_params(seq_len=64)  # the rehearsal's three layers hold every scope: the dense one, a full and a sliding one with experts
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **params)
    loss = lambda w, batch: spec.loss(spec.apply(w, batch, train=True), batch)  # noqa: E731
    compiled = jax.jit(jax.value_and_grad(loss)).lower(jax.eval_shape(spec.init, jax.random.key(0)), spec.example_batch(2)).compile()
    return sorted({path for name in re.findall(r'op_name="([^"]+)"', compiled.as_text()) for path in name.split(";")})


#: every scope the model's step is traced under (models/attentions.py, models/moe_lm.py, ops/moe.py, ops/flash_attention.py:
#: ``window_attn`` / ``flash_attn`` are the kernels' own and are not in a step compiled for the CPU, where the attention is XLA's)
SCOPES = ("attn_proj", "attn_glue", "window_attn", "flash_attn", "moe_shared", "moe_router", "moe_dispatch", "moe_experts",
          "moe_combine", "mlp", "lm_head")
OTHER_FAMILIES = ("ssm_proj", "ssm_conv", "ssm_norm", "ssm_scan", "mla_proj", "eva_proj", "eva_attn", "moe_latent", "kda_proj", "kda_glue", "kda_scan")


def _scopes_of(op_name: str) -> set:
    return {scope for scope in SCOPES if re.search(rf"\b{scope}\b", op_name)}


@on_the_tree_itself
def test_a_scope_entry_reads_its_scope_as_the_compiled_step_spells_it_and_not_its_neighbours(step_op_names):
    bench = resolve.Bench(ROOT)
    for name, wanted in sorted(SCOPE_ENTRIES.items()):
        params = bench.metric_file(name)["params"]
        assert (params["module"], params["on"]) == ("jit_local_scan", "scope") and "exclude" not in params
        matched = [op for op in step_op_names if re.search(params["pattern"], op)]
        assert matched == [op for op in step_op_names if _scopes_of(op) & wanted] and all(_scopes_of(op) <= wanted for op in matched)
        if not wanted & {"window_attn", "flash_attn"}:  # the model's own scopes: there, forward and backward
            for scope in wanted:
                assert any(scope in _scopes_of(op) and "transpose(" not in op for op in matched), scope
                assert any(scope in _scopes_of(op) and "transpose(" in op for op in matched), scope
        for neighbour in (set(SCOPES) - wanted) | set(OTHER_FAMILIES):
            assert not re.search(params["pattern"], f"jit(local_scan)/jvp({neighbour})/dot_general"), (name, neighbour)
        for scope in wanted:
            assert re.search(params["pattern"], f"jit(local_scan)/transpose(jvp({scope}))/pallas_call")
    # the full calls' entry does not read a window call and the other way round: by scope ...
    assert not re.search(bench.metric_file("flash_attn_ms_step.tok")["params"]["pattern"], "jit(local_scan)/jvp(window_attn)/pallas_call")
    # ... and the shared expert's ``mlp`` nests under ``moe_shared``: mlp_ms_step.eva would read the dense layer AND the
    # four shared experts together here, which is why the cell does not join it
    nested = [op for op in step_op_names if re.search(r"\bmoe_shared\b", op)]
    assert nested and all(re.search(r"\bmlp\b", op) for op in nested if "dot_general" in op)


def _event(operands: list) -> str:
    """A Mosaic call's trace event name, as XLA:TPU spells the instruction."""
    listed = ", ".join(f"{dtype}[{shape}]{{{layout}}} %{name}" for dtype, shape, layout, name in operands)
    return f'%x.1 = (bf16[1,8192,4096]{{2,1,0:T(8,128)(2,1)}}) custom-call({listed}), custom_call_target="tpu_custom_call", operand_layout_constraints={{}}'


def test_the_window_calls_and_the_full_calls_are_told_apart_by_the_scalar_ahead_of_their_operands():
    """``window_roofline_pct.swa`` reads the three kernels under a window,
    ``flash_roofline_pct.tok`` the three of the full layer: the same operand
    lists, with the window's int32 [1] ahead of them or not.  Neither reads
    the other's calls, nor the experts' grouped matmuls (several scalars)."""
    bench = resolve.Bench(ROOT)
    window = [k["pattern"] for k in bench.metric_file("window_roofline_pct.swa")["params"]["kernels"]]
    full = [k["pattern"] for k in bench.metric_file("flash_roofline_pct.tok")["params"]["kernels"]]
    mat = ("bf16", "1,8192,4096", "2,1,0:T(8,128)(2,1)", "reshape.9")
    vec = lambda rows: ("f32", f"32,{rows},8192", "2,1,0:T(1,128)", "fusion.3")  # noqa: E731
    scalar = ("s32", "1", "0:T(128)S(6)", "constant.9")
    lists = {"fwd": [mat] * 3, "dq": [mat] * 4 + [vec(2)], "dkv": [mat] * 4 + [vec(1), vec(2)]}
    for i, ops in enumerate(lists.values()):
        for j, pattern in enumerate(window):
            assert bool(re.search(pattern, _event([scalar] + ops))) == (i == j) and not re.search(pattern, _event(ops))
        for j, pattern in enumerate(full):
            assert bool(re.search(pattern, _event(ops))) == (i == j) and not re.search(pattern, _event([scalar] + ops))
    gmm = [("s32", "17", "0", "a")] * 5 + [mat, mat]
    assert not any(re.search(pattern, _event(gmm)) for pattern in window + full)
    costs = _costs()
    # ONE FLOP a pair INSIDE the windows a unit, whatever the kernels visit; the full layer's over the causal half
    assert costs["window_unit_flops"] == 32 * (2048 * 2049 // 2 + 6144 * 2048) and costs["flash_unit_flops"] == 32 * 8192 * 8192 // 2
    assert (costs["window_fwd_units"], costs["window_bwd_units"], costs["window_bwd_second_units"]) == (512, 1280, 0)
    assert (costs["flash_fwd_units"], costs["flash_bwd_units"], costs["flash_bwd_second_units"]) == (512, 1280, 0)


def test_the_glue_in_ms_is_the_selection_its_share_of_the_bandwidth_has():
    bench = resolve.Bench(ROOT)
    ms, share = bench.metric_file("attn_glue_ms_step.swa"), bench.metric_file("attn_glue_hbm_pct.swa")
    assert (ms["params"]["module"], ms["params"]["pattern"]) == (share["params"]["module"], share["params"]["pattern"])
    assert ms["reader"] == "op_ms_step" and share["reader"] == "scope_hbm_roofline" and ms["layer"] == share["layer"] == "models"
    assert share["params"]["bytes"] in _costs()


def test_the_needed_share_reads_the_parts_two_counters_and_nothing_where_a_program_has_none(monkeypatch):
    """``window_pairs_needed_pct.swa``: the growth of ``attn_pairs_window``
    over that of ``attn_pairs_window_computed``, x 100: how much of what the
    window kernels' sub-tiles multiply the mask keeps (100 for a kernel that
    multiplies nothing it then hides)."""
    import runfiles

    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker import worker

    bench = resolve.Bench(ROOT)
    spec = bench.metric_file("window_pairs_needed_pct.swa")
    assert spec["reader"] == "counter_delta" and (spec["better"], spec["source"]) == ("higher", "program_counter")
    assert spec["params"] == {"counter": "attn_pairs_window", "over": "attn_pairs_window_computed", "scale": 100}
    pair = {spec["params"]["counter"], spec["params"]["over"]}
    counters = lambda config: load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"]).step_counters  # noqa: E731
    ours = counters(bench.config(CONFIG))
    assert pair | {"attn_pairs_full"} <= set(ours) and all(ours[name] for name in pair)  # each with its gauge's help text
    assert not pair & set(worker.STEP_COUNTERS) and not pair & set(worker.COUNTER_GAUGES)  # the model's own, not the trainer's
    assert not pair & set(counters(bench.config("kanana2_30b_a3b_ep8_l5")))  # a model without a window counts neither

    def read(records):
        monkeypatch.setattr(runfiles, "counter_records", lambda ctx: records)
        return bench.reader("counter_delta").read({}, spec["params"])

    # a task at the cell's shape: two steps, four sliding layers of 32 heads; by the plan's sub-tiles 16.33 blocks' worth a pass
    from elasticdl_tpu.ops import flash_attention as fa

    needed, computed = 2 * 4 * 32 * (2048 * 2049 // 2 + 6144 * 2048), 2 * 4 * 32 * fa.window_pairs_computed(8192, 2048)
    task = [{"attn_pairs_window": float(i * needed), "attn_pairs_window_computed": float(i * computed), "moe_slots": 3.0 * i} for i in range(1, 5)]
    assert read(task) == pytest.approx(100 * 14.0005 / (49 / 3), rel=1e-4)  # 85.7 %
    without = [{"moe_slots": 3.0 * i, "compiles": 5.0} for i in range(1, 5)]  # the parent's program, or another model's
    assert read(without) is None and read(task[:1]) is None and read([]) is None


def test_afmoe_flops_counts_what_its_docstring_says():
    costs = _costs()
    attention = 3 * 2048 * 4096 + 2 * 2048 * 512
    expert = 3 * 2048 * 1024
    moe = 2048 * 128 + expert + 8 * 16 / 128 * expert
    assert costs["active_matmul_params"] == 5 * attention + 3 * 2048 * 6144 + 4 * moe + 2048 * 25024
    assert round(costs["active_matmul_params"] / 1e6, 1) == 276.7  # ISSUE 56's count
    assert costs["pairs_window"] == 2048 * 2049 // 2 + 6144 * 2048 and costs["pairs_full"] == 8192 * 8192 // 2
    assert costs["attention_flops_per_token"] == 3 * 512 * 32 * (4 * costs["pairs_window"] + costs["pairs_full"]) // 8192
    assert costs["train_flops_per_token"] == 6 * costs["active_matmul_params"] + costs["attention_flops_per_token"]
    assert round(costs["train_flops_per_token"] / 1e9, 3) == 2.214
    # by needed FLOPs a token: the five attentions' matmuls 37 %, their scores 25 % (17 under the windows, 9 full), the dense MLP 10 %,
    # experts + shared + router 14 %, the head 14 %
    share = lambda flops: round(100 * flops / costs["train_flops_per_token"], 1)  # noqa: E731
    assert share(5 * 6 * attention) == 36.9 and share(costs["attention_flops_per_token"]) == 25.0
    assert share(3 * 512 * 32 * 4 * costs["pairs_window"] // 8192) == 15.9 and share(3 * 512 * 32 * costs["pairs_full"] // 8192) == 9.1
    assert share(6 * 3 * 2048 * 6144) == 10.2 and share(4 * 6 * moe) == 13.9 and share(6 * 2048 * 25024) == 13.9
    assert costs["attn_glue_bytes_per_step"] == 8192 * 5 * 2 * (17 * 4096 + 9 * 512)
    assert costs["moe_slots_per_step"] == 8192 * 8 * 4 and costs["expert_flops_per_slot"] == 3 * 3 * 2 * 2048 * 1024


def test_the_references_mask_is_the_window_and_its_key_value_heads_are_read_by_index():
    """``masked_attention``: under a window of W a query sees exactly W keys
    (fewer at the start), its own the last; without one all before it; a
    query head reads key/value head ``h // group`` (the repeated form gives
    the same); in blocks of queries or whole."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    l, heads, kv, hd, w = 64, 4, 2, 8, 16
    q, k, v = (jax.random.normal(key, (1, l, n, hd)) for key, n in zip(jax.random.split(jax.random.key(0), 3), (heads, kv, kv)))
    got = reference.masked_attention(q, k, v, w)
    repeated = reference.masked_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2), w)
    np.testing.assert_allclose(got, repeated, rtol=1e-5, atol=1e-6)
    # by hand, a head and a position at a time
    for h, p in ((0, 3), (1, 15), (2, 16), (3, 63)):
        first = max(0, p - w + 1)
        scores = np.asarray(q[0, p, h] @ k[0, first:p + 1, h // 2].T) / np.sqrt(hd)
        probs = np.exp(scores - scores.max())
        np.testing.assert_allclose(got[0, p, h], (probs / probs.sum()) @ np.asarray(v[0, first:p + 1, h // 2]), rtol=1e-4, atol=1e-5)
    # a value one key BEFORE the window changes nothing, the window's first key does
    moved = lambda at: reference.masked_attention(q, k, v.at[0, at].add(1.0), w)[0, 40]  # noqa: E731
    assert float(jnp.max(jnp.abs(moved(40 - w) - got[0, 40]))) == 0.0 and float(jnp.max(jnp.abs(moved(40 - w + 1) - got[0, 40]))) > 1e-3
    full = reference.masked_attention(q, k, v, 0)
    assert float(jnp.max(jnp.abs(full[0, :w] - got[0, :w]))) < 1e-6 and float(jnp.max(jnp.abs(full[0, w:] - got[0, w:]))) > 1e-3


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

    (want, (want_z, want_slots)), want_grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(weights)
    (got, (z, slots)), grads = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(z, want_z, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(b).max()), err_msg=str(path))
    groups = {reference.group_of(path, grads) for path, _ in jax.tree_util.tree_leaves_with_path(grads)}
    assert groups == {"attention", "dense", "experts", "shared", "router", "head", "embedding", "norms"}
    assert {f"grad_{g}" for g in groups} | {"window_output", "full_output", "router_logits", "router_choices_differing", "head_logits",
                                            "logits", "adamw_update"} == set(CHECKS)


@on_the_tree_itself
def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under afmoe's keys on the CPU, the float32
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
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/trinity_mini_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and a toy of 64-wide layers reads more of the
    # bfloat16 compute's noise than the limits drawn at 2048 allow (its attention outputs too: 16-wide heads); nothing else is wrong
    noisy = ("logits", "window_output", "full_output") + tuple(name for name in CHECKS if name.startswith("grad_"))
    excused = lambda p: "outside the band" in p or "inside the window" in p or any(f"check {name}:" in p for name in noisy)  # noqa: E731
    assert [p for p in info["problems"] if not excused(p)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.7  # ln 256 + the toy head's variance
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in noisy), checks
    assert all(checks[name]["value"] < 0.25 for name in noisy), checks
    assert "compared: check window_output" in done.stderr and "compared: check adamw_update" in done.stderr
    metrics = result["metrics"]
    for name in ("hbm_peak_reported_gib.tok", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    counted = ("compiles_in_window.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla",
               "expert_load_max_pct_mean.moe", "window_pairs_needed_pct.swa")
    if info["window"]["reports"] >= 3:
        assert all(name in metrics for name in counted), sorted(metrics)
    # off the TPU a window is the XLA path's: every pair multiplied, 32 x 33 / 2 + 96 x 32 of 128 x 128 kept
    if "window_pairs_needed_pct.swa" in metrics:
        assert metrics["window_pairs_needed_pct.swa"]["value"] == pytest.approx(100 * (32 * 33 // 2 + 96 * 32) / 128**2, rel=1e-4)
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
    assert re.search(r'"attn_pairs_window": [\d.e+]+', done.stdout + open(scratch / "benchmark" / ".state" / "runs" / CELL / "metrics" / "metrics.jsonl").read())


@on_the_tree_itself
def test_rehearsal_of_the_checks_a_sound_system_reads_every_one_and_every_control_is_caught():
    """The sizing tool's table (what the reference child reads, sound and
    under the controls, judged by run.py's ``reference_problems`` against
    the configuration's limits) on one seeded minibatch at the rehearsal's
    sizes, ONE table for all.  Only the control that nothing but the step
    can catch (``adamw_update``) runs its own train step; the others are
    caught by a forward check the file names for them, as on the chip."""
    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        override = json.load(f)
    config["model_params"].update(override["model_params"])  # three layers: the dense one, a full one and a sliding one with experts
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "trinity_mini_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    # the two controls that all_bfloat16 holds together and the second of the train step's own are read on the chip
    # alone (the configuration's controls_read): a control costs this test a compile
    controls = tuple(c for c in reference.CONTROLS if c not in ("bfloat16_router", "bfloat16_logits", "no_weight_decay"))
    assert {"full_for_window", "window_off_by_one", "rotary_on_full_layers", "all_bfloat16", "state_unchanged"} == set(controls)
    table = sizing.check_table(config, reference, 2, [3300000031], controls, own_step=sizing.OWN_STEP)
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    # at the toy's widths the bfloat16 noise is over the limits drawn at 2048 (16-wide heads, 64-wide layers): those apart
    assert all(re.match(r"check (logits|window_output|full_output|grad_\w+):", p) for p in sound["problems"]), sound["problems"]
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    assert sorted(table) == sorted(("sound",) + controls)
    for control in controls:
        (row,) = table[control]
        named = [check for check in config["controls"][control]["caught_by"] if check in row["readings"]]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert named and not row["correct"] and set(over) & set(named), (control, over, named)
        for check in named:  # and by more than the sound system's own reading
            assert not row["readings"][check] <= 2 * sound["readings"][check], (control, check)


# ---- the cell's whole step, compiled ahead of time for a described v5e ----
# ISSUE 56 asked for this case in ``tests/test_chip_lowering.py``.  It is HERE because that file's cases run one after
# another on ONE xdist worker under the driver's command and are the tail of the whole run (my runs, PR 56: five workers
# done at 1,030 to 1,060 s, the sixth busy with that file until 1,550): 90 s more there are 90 s more of the run, here a sixth.


@pytest.fixture(scope="module")
def v5e_device():
    """A device of a described (not attached) v5e 2x2 host: libtpu compiles
    for it ahead of time.  Skips where the installed libtpu cannot."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu").devices[0]
    except Exception as e:  # noqa: BLE001 — any plugin failure means "cannot"
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")


@pytest.fixture
def as_on_the_chip(monkeypatch):
    """The backend reads ``tpu``: the flash kernels and the grouped matmuls
    are picked and compiled, not interpreted; the devices are described."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def path_lines(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    return lines


def _mosaic_operand_lists(text: str):
    """(int32 scalars, bf16 operands, f32 operands) of every Mosaic call of a
    compiled program's lines, sorted: a window call of the flash kernels has
    ONE int32 [1] ahead of the full call's list."""
    calls = re.findall(
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes",
        text,
    )
    return sorted((ops.count("s32["), ops.count("bf16["), ops.count("f32[")) for ops in calls)


def _as_a_trace_event(call: str) -> str:
    """A compiled Mosaic call's operand list as a device trace's event name
    spells it: each operand's shape and layout ahead of its name."""
    ops = re.search(r"operand_layout_constraints=\{(.*?)\}, frontend", call).group(1)
    shapes = re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", ops)
    return "custom-call(" + ", ".join(f"{shape} %operand.{i}" for i, shape in enumerate(shapes)) + '), custom_call_target="tpu_custom_call"'


@on_the_tree_itself
def test_the_cells_whole_step_compiles_for_v5e_inside_the_line_with_the_window_calls_told_apart(
    v5e_device, as_on_the_chip, path_lines, monkeypatch
):
    """``trinity_mini_job``'s real step (Trinity-Mini's widths: the dense layer
    and four expert layers of 16 held experts, 32 query heads over 4
    key/value heads, a window of 2048 keys on layers 1, 2, 3 and 5 and full
    causal attention on the fourth; ONE sequence of 8192 tokens, the
    traffic's two steps a dispatch, per-layer rematerialisation) compiled for
    a described v5e with the byte budget the trainer resolves from a v5e's
    memory: 705.5 M parameters and their moments are 7.88 GiB of arguments,
    the layers keep every save site and the step stays between 10.5 GiB and
    the trainer's line of 14.25 AT THE FIRST COMPILE; the device scopes the
    ``.swa`` / ``.tok`` / ``.mla`` / ``.ssm`` metrics read are there; each
    sliding layer is the three flash kernels UNDER ``window_attn`` with the
    window's int32 [1] AHEAD of the operand lists (1 + 3 / 1 + 4 + 1 / 1 + 4
    + 2: what ``window_roofline_pct.swa`` reads), the full layer the three
    under ``flash_attn`` at the lists every older cell has (3 / 4 + 1 / 4 +
    2: ``flash_roofline_pct.tok``), each forward ONCE (its output is kept);
    the two sets of patterns tell exactly their own apart; the experts are
    grouped matmuls; and no [*, 8192, 8192] score matrix exists."""
    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel import trainer as trainer_lib
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_chip_lowering import V5E_BYTES_LIMIT, _abstract_scan_step  # the other cells' AOT cases live there

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    root = ROOT
    with open(os.path.join(root, "benchmark", "configs", "trinity_mini_26b_a3b_ep8_l5.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq8k_x1_v25024.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"], steps=traffic["minibatches_per_task"])
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total, plan = trainer_lib.compiled_bytes(compiled), trainer.keep_plan
    assert 10.5 * 2**30 < total < plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM, total / 2**30
    assert abs(compiled.memory_analysis().argument_size_in_bytes - 12 * 705474304) < 2**20  # parameters and two moments
    assert plan.kept == plan.tagged <= plan.budget and plan.tagged > 2**30
    text = compiled.as_text()
    scopes = ("attn_proj", "attn_glue", "window_attn", "flash_attn", "moe_shared", "moe_router", "moe_dispatch", "moe_experts",
              "moe_combine", "mlp", "lm_head")
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    assert not re.search(r'op_name="[^"]*\b(mla_proj|ssm_\w+|kda_\w+|eva_\w+)\b', text)  # the other families' are not this model's
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    mosaic = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    under = lambda scope: [c for c in mosaic if re.search(rf'op_name="[^"]*\b{scope}\b', c)]  # noqa: E731
    # ONE full layer and FOUR sliding layers, the forward ONCE each; a window call is never under ``flash_attn``
    assert _mosaic_operand_lists("\n".join(under("flash_attn"))) == [(0, 3, 0), (0, 4, 1), (0, 4, 2)]
    assert _mosaic_operand_lists("\n".join(under("window_attn"))) == sorted([(1, 3, 0), (1, 4, 1), (1, 4, 2)] * 4)
    assert not set(under("flash_attn")) & set(under("window_attn"))
    assert all("bf16[1,8192,4096]" in c and "s32[1]{0}" in c for c in under("window_attn"))  # 32 heads of 128; K and V repeated to as many
    assert under("moe_experts") and len(under("moe_experts")) % 4 == 0
    lines = [line for line in path_lines if "attention path: pallas-compiled" in line]
    assert any(line.endswith("heads_per_block=1 window=2048)") and "key_tiles=70/256 fwd, 252/1024 bwd" in line for line in lines), path_lines
    assert any(line.endswith("heads_per_block=1)") and "key_tiles=136/256 fwd" in line for line in lines), path_lines
    # the two roofline entries' patterns, on operand lists as a trace event spells them (the shapes with their
    # layouts ahead of each name): each set reads its own three and none of the other's
    for metric, own, other in (("window_roofline_pct.swa", "window_attn", "flash_attn"), ("flash_roofline_pct.tok", "flash_attn", "window_attn")):
        with open(os.path.join(root, "benchmark", "metrics", metric + ".json")) as f:
            patterns = [k["pattern"] for k in json.load(f)["params"]["kernels"]]
        assert len(patterns) == 3
        for pattern in patterns:
            assert sum(bool(re.search(pattern, _as_a_trace_event(c))) for c in under(own)) == len(under(own)) // 3, (metric, pattern)
            assert not any(re.search(pattern, _as_a_trace_event(c)) for c in under(other) + under("moe_experts")), (metric, pattern)
