"""What PR 58 added to the benchmark: the configuration
``keye_vl2_30b_a3b_ep8_l5`` (Keye-VL-2.0-30B-A3B's language model, ``KeyeVL2``,
at its published widths: one chip's share of an 8-way expert-parallel stage, 5
of 48 layers), the traffic mix ``job_seq16k_x1_v18992``, the cell
``keye_vl2_job``, the cost model ``keye_vl2_flops`` and the two ``.dsa``
metrics.  CPU only."""

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

CELL, CONFIG, TRAFFIC = "keye_vl2_job", "keye_vl2_30b_a3b_ep8_l5", "job_seq16k_x1_v18992"
#: every per-layer metric the cell reports: the ones it JOINED (appended to their ``workloads``) and its own two ``.dsa``
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok",
    "remat_kept_pct.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla",
    "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla", "expert_load_max_pct_mean.moe",
    # PR 63: the part's four projections and its glue (the norm a head, the rotary turn, the 4 -> 32 repeat) are traced under the
    # scopes ``trinity_mini_job``'s part has; an own entry may be joined since, and a sixth of the step had no entry
    "attn_proj_ms_step.ssm", "attn_glue_ms_step.swa",
]
#: the two shares of a roofline the cell brought (ISSUE 58, "Room": exactly two were free) ...
ROOFLINES = {"dsa_attn_roofline_pct.dsa": {"dsa_attn"}, "dsa_index_roofline_pct.dsa": {"dsa_index", "dsa_select"}}
#: ... and the five that waited for room since PR 58 and landed in PR 63: the four scopes of the mechanism in ms, one entry a
#: scope (``dsa_attn_ms_step.dsa`` is the selection its roofline share has; ``dsa_index`` + ``dsa_select`` are the other's, apart), and
#: how much of what the masked kernels multiply the selection keeps
MS_ENTRIES = {"dsa_index_ms_step.dsa": {"dsa_index"}, "dsa_select_ms_step.dsa": {"dsa_select"}, "dsa_attn_ms_step.dsa": {"dsa_attn"},
              "dsa_loss_ms_step.dsa": {"dsa_loss"}}
PAIRS_ENTRY = "dsa_pairs_computed_pct.dsa"
OWN = [*ROOFLINES, *MS_ENTRIES, PAIRS_ENTRY]
#: entry -> the scopes of the step it reads; every other scope of the step is a neighbour it must not read
SCOPE_ENTRIES = {**ROOFLINES, **MS_ENTRIES, "attn_proj_ms_step.ssm": {"attn_proj"}, "attn_glue_ms_step.swa": {"attn_glue"}}
GROUPS = ("attention", "attention_out", "indexer", "experts", "experts_out", "router", "head", "embedding", "norms")
CHECKS = sorted([
    "dsa_index_scores", "dsa_selected_differing", "dsa_output", "indexer_loss", "indexer_input_detached", "router_logits", "router_choices_differing",
    "head_logits", "logits", "adamw_update"] + [f"grad_{g}" for g in GROUPS])
SA = {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048}
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name Keye-VL-2.0-30B-A3B), copied: the guide is not in the checkout.
CATALOG = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 6144, "max_position_embeddings": 262144, "max_window_layers": 48, "mlp_only_layers": [],
    "model_type": "KeyeVL2", "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4, "num_local_experts": 128,
    "rms_norm_eps": 1e-06, "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
    "rope_theta": 10000000, "sa_config": SA, "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
SOURCE = "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "num_experts": 16, "vocab_size": 18992}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
#: The growth rehearsal (test_benchmark_yardstick.py) runs this module again on grown copies of the tree; the tests
#: marked so compile models and read nothing of how many cells there are: they run on the tree itself only.
on_the_tree_itself = pytest.mark.skipif("EDL_BENCH_GROWTH_REHEARSAL" in os.environ, reason="reads nothing of the cells a later PR adds")


def _catalog_rows(name: str) -> list:
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
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "keye_vl2_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED + OWN)
    assert len(OWN) == 7  # two the cell brought (PR 58), five a ``benchmark`` PR added when there was room (PR 63)
    # NOT joined, each for its reason (PERF.md section 4): no full flash call and no window call runs in this cell, there is no
    # shared expert, and the glue's share of the bandwidth counts ``trinity_mini_job``'s bytes (its cost model's, a gate among them)
    for name in ("flash_attn_ms_step.tok", "flash_roofline_pct.tok", "moe_shared_ms_step.mla", "window_attn_ms_step.swa", "attn_glue_hbm_pct.swa"):
        (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"], name
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 18992, 16384, "recordio")
    assert gen["vocab"] == config["model_params"]["vocab_size"] == config["vocab_size"]
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64  # no task repeats inside warm-up + the window
    assert traffic["units_per_record"] == 16384 and traffic["minibatch_size"] == 1
    assert traffic["minibatches_per_task"] == 1 and traffic["rate_metric"] == "tokens_per_s_chip" and traffic["warmup_tasks"] == 4
    assert traffic["job_flags"] == {"profile_tasks": 2, "profile_inline": True}
    for key in ("why", "minibatch_why", "generator_why", "warmup_why"):
        assert len(traffic[key]) > 80, key
    assert gen["seq_len"] == 8 * config["sa_config"]["topk"]  # 87.5 % of the queries select


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_stated_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == list(CUT)  # exactly the depth, the experts, the vocabulary
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("Keye-VL-2.0-30B-A3B"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: five layers (all 48 are alike: no dense layer, no period), 16 routed experts a layer, an eighth of the vocabulary
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    assert config["num_local_experts"] == 128  # the router's width stays
    for said in ("8-way expert-parallel", "16 of 128 routed experts a chip", "18,992 of 151,936", "5 of 48 layers", "NOT run"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths, under the published spelling of the keys: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "intermediate_size", "moe_intermediate_size",
            "rope_theta", "rms_norm_eps", "num_experts_per_tok", "norm_topk_prob", "tie_word_embeddings", "sa_config")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["num_hidden_layers"] == 5 and p["vocab_size"] == 18992
    # the stand-in init and the second loss's weight are constants of the family, not keys a configuration sets
    assert "residual_init_scale" not in p and "indexer_loss_coef" not in p
    assert "KEYE_VL2_INTO_STREAM" in config["assumed"]["init"] and "12.1 to 12.9 %" in config["assumed"]["init"]
    assert p["num_experts"] == CATALOG["num_experts"] == 128 and p["experts_held"] == config["num_experts"] == 16
    assert p["seq_len"] == 16384 and p["remat"] is True and p["decay_matrices_only"] is True
    assert p["lr_warmup_steps"] == 2000 and p["learning_rate"] == 2.2e-4 and p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {"layers", "attention", "positions", "indexer", "indexer_loss", "router", "init", "optimizer", "data", "sizes"}
    assert "all 64 values" in config["assumed"]["indexer"] and "stop-gradient" in config["assumed"]["indexer"]
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.3 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 2, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    assert {"dense_for_sparse", "topk_off_by_one", "bfloat16_index_scores", "no_indexer_loss", "lm_loss_reaches_indexer"} <= set(reference.CONTROLS)
    assert config["controls"]["dkv_ignores_selection"]["caught_by"] == ["grad_attention"]   # the masked BACKWARD alone
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.4 * config["checks"][check]["limit"], (name, check)
    assert "dsa_output" in config["controls"]["dense_for_sparse"]["caught_by"]
    assert "dsa_selected_differing" in config["controls"]["topk_off_by_one"]["caught_by"]
    assert "dsa_index_scores" in config["controls"]["bfloat16_index_scores"]["caught_by"]
    assert config["controls"]["no_indexer_loss"]["caught_by"] == ["grad_indexer"]
    assert config["controls"]["lm_loss_reaches_indexer"]["caught_by"] == ["indexer_input_detached"]
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"dsa_index_scores", "router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(18992) and config["reference_tolerance"] <= 5e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]
    assert "@" not in json.dumps({k: v for k, v in config.items() if k != "source"})  # no reading left to fill in


def test_the_share_is_the_arithmetic_the_file_states():
    """562.3 M parameters = 8.38 GiB at 16 bytes: the model's own init at the
    configuration's keys, counted (shapes only), against the cost model's
    count and the hand counts of ``reduced_why``."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(5)]
    blk = blocks["b03"]
    assert blk["wq"].shape == (2048, 4096) and blk["wk"].shape == blk["wv"].shape == (2048, 512) and blk["wo"].shape == (4096, 2048)
    assert blk["q_norm"].shape == blk["k_norm"].shape == (128,)
    assert blk["idx_wq"].shape == (2048, 16 * 64) and blk["idx_wk"].shape == (2048, 64) and blk["idx_ww"].shape == (2048, 16)
    assert blk["idx_norm"].shape == blk["idx_norm_bias"].shape == (64,)
    assert blk["router"].shape == (2048, 128) and blk["w_up"].shape == (16, 2048, 768)
    assert shapes["head"].shape == (2048, 18992) and shapes["tok_emb"].shape == (18992, 2048)
    assert all(count(blocks[name]) == costs["params_layer"] == 96899456 for name in blocks)
    part = lambda names: count({k: v for k, v in blk.items() if k in names})  # noqa: E731
    assert part(("wq", "wk", "wv", "wo")) == 18874368 and part(("idx_wq", "idx_wk", "idx_ww")) == 2260992
    assert part(("w_gate", "w_up", "w_down")) == 16 * 4718592 and part(("router",)) == 262144
    assert count(shapes) == costs["params_total"] == 562290560 and round(count(shapes) / 1e6, 1) == 562.3
    assert round(16 * count(shapes) / 2**30, 2) == 8.38 and round(16 * count(shapes) / 1e9, 1) == 9.0
    for said in ("562.3 M", "8.38 GiB"):
        assert said in config["reduced_why"] and said in config["deployment"], said
    assert "562,290,560" in config["reduced_why"] and "96,899,456" in config["reduced_why"]


def test_keye_vl2_flops_counts_what_its_docstring_says():
    costs = _costs()
    assert costs["pairs_causal"] == 134225920 and costs["pairs_selected"] == 31458304
    assert round(100 * costs["pairs_selected"] / costs["pairs_causal"], 1) == 23.4
    assert costs["dsa_attn_flops_per_step"] == 5 * 32 * 31458304 * (512 + 1280)
    assert costs["dsa_index_flops_per_step"] == 5 * 134225920 * 6 * 16 * 64
    active = 5 * (18874368 + 2260992 + 262144 + 4718592) + 2048 * 18992
    assert costs["active_matmul_params"] == active
    per_token = (costs["dsa_attn_flops_per_step"] + costs["dsa_index_flops_per_step"]) // 16384
    assert costs["train_flops_per_token"] == 6 * active + per_token
    assert costs["moe_slots_per_step"] == 16384 * 8 * 5 and costs["expert_flops_per_slot"] == 18 * 2048 * 768
    assert costs["expert_flops_per_step"] == costs["moe_slots_per_step"] / 8 * costs["expert_flops_per_slot"]
    # the needed attention work follows the SELECTION's size, not L alone: at a topk of L it is the causal half
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    config["model_params"]["sa_config"] = {**SA, "topk": 16384}
    dense = bench.costs(config["costs"]).compute(config, traffic)
    assert dense["pairs_selected"] == dense["pairs_causal"] and dense["dsa_index_flops_per_step"] == costs["dsa_index_flops_per_step"]


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
    for key in ("flops_per_unit", "units_per_step", "unit_flops", "flops", "bytes"):
        if key in spec.get("params", {}):
            assert spec["params"][key] in _costs(), (name, key)
    if name in OWN:
        assert (entry["layer"], entry["moves"]) == ("ops", "tokens_per_s_chip")
    if name in ROOFLINES:
        assert spec["reader"] == "scope_roofline" and spec["unit"] == "%" and name.split(".")[0].endswith("_roofline_pct")
        assert (entry["source"], entry["better"]) == ("device_trace", "higher")
    if name in MS_ENTRIES:
        (scope,) = MS_ENTRIES[name]
        assert name == f"{scope}_ms_step.dsa" and (spec["reader"], spec["unit"], entry["source"], entry["better"]) == ("op_ms_step", "ms", "device_trace", "lower")
        assert spec["params"] == {"module": "jit_local_scan", "on": "scope", "pattern": rf"\b{scope}\b"}


def test_the_cells_own_entries_are_the_cells_alone_and_the_rate_lists_the_cell():
    """Membership, by name: nothing here counts the benchmark's entries or
    says where in a list one stands, so a later PR's cell, configuration or
    entry (and the fold of the entries) leaves this file as it is."""
    spec = resolve.Bench(ROOT).spec
    for name in OWN:
        (entry,) = [m for m in spec["per_layer"] if m["name"] == name]
        assert entry["workloads"] == [CELL], name
    (rate,) = [m for m in spec["end_to_end"] if m["name"] == "tokens_per_s_chip"]
    assert CELL in rate["workloads"]
    assert [c["name"] for c in spec["configs"]].count(CONFIG) == 1 and [w["name"] for w in spec["workloads"]].count(CELL) == 1


def test_the_kept_share_reads_the_selections_two_counters_and_nothing_where_a_program_has_none(monkeypatch):
    """``dsa_pairs_computed_pct.dsa``: the growth of ``dsa_pairs_needed`` (the
    pairs the selection keeps, from shapes) over that of
    ``dsa_pairs_computed`` (the pairs of the (block, block) steps the masked
    kernels really multiply, from the selection the step made), x 100: how
    much of what the kernels multiply the selection keeps.  22.06 while
    every causal block of 1024 x 1024 holds a selected key (31,458,304 of
    136 x 1,048,576 a head: the grid's fold of PR 62 dropped steps that
    multiplied nothing and leaves it there); it rises only when a kernel
    skips a block or gathers.  The pair is among the model's step counters."""
    import runfiles

    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker import worker

    bench = resolve.Bench(ROOT)
    spec = bench.metric_file(PAIRS_ENTRY)
    assert spec["reader"] == "counter_delta" and (spec["unit"], spec["better"], spec["source"], spec["layer"]) == ("%", "higher", "program_counter", "ops")
    assert spec["params"] == {"counter": "dsa_pairs_needed", "over": "dsa_pairs_computed", "scale": 100}
    pair = {spec["params"]["counter"], spec["params"]["over"]}
    counters = lambda config: load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"]).step_counters  # noqa: E731
    ours = counters(bench.config(CONFIG))
    assert pair <= set(ours) and all(ours[name] for name in pair)  # each with its gauge's help text
    assert not pair & set(worker.STEP_COUNTERS) and not pair & set(worker.COUNTER_GAUGES)  # the model's own, not the trainer's
    assert not pair & set(counters(bench.config("trinity_mini_26b_a3b_ep8_l5")))  # a model that selects no keys counts neither

    def read(records):
        monkeypatch.setattr(runfiles, "counter_records", lambda ctx: records)
        return bench.reader("counter_delta").read({}, spec["params"])

    costs = _costs()
    needed, blocks = 32 * 5 * costs["pairs_selected"], 32 * 5 * 136 * 1024 * 1024  # a step's: 32 heads, five layers; 136 causal blocks a head
    assert costs["pairs_selected"] == 31458304
    dense_walk = [{"dsa_pairs_needed": float(i * needed), "dsa_pairs_computed": float(i * blocks), "moe_slots": 3.0 * i} for i in range(1, 5)]
    assert round(read(dense_walk), 2) == 22.06
    assert read([dict(r, dsa_pairs_computed=r["dsa_pairs_needed"]) for r in dense_walk]) == 100.0  # a kernel that multiplies the kept pairs alone
    assert read([dict(r, dsa_pairs_needed=0.0) for r in dense_walk]) == 0.0  # nought is a reading
    without = [{"moe_slots": 3.0 * i, "compiles": 5.0} for i in range(1, 5)]  # another model's records, or a program before PR 58
    assert read(without) is None and read(dense_walk[:1]) is None and read([]) is None
    assert read([dict(r, dsa_pairs_computed=7.0) for r in dense_walk]) is None  # a denominator that did not grow: nothing, not a division


@pytest.fixture(scope="module")
def step_op_names():
    """The ``op_name`` of every instruction of the model's compiled forward
    and backward at the rehearsal's sizes: the ``jax.named_scope`` path as
    the compiled step spells it, which is what the profiler's
    ``trace.json.gz`` carries as ``tf_op`` and ``op_ms_step`` matches."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **_rehearsal_params(seq_len=64))
    loss = lambda w, batch: spec.loss(spec.apply(w, batch, train=True), batch)  # noqa: E731
    compiled = jax.jit(jax.value_and_grad(loss)).lower(jax.eval_shape(spec.init, jax.random.key(0)), spec.example_batch(2)).compile()
    return sorted({path for name in re.findall(r'op_name="([^"]+)"', compiled.as_text()) for path in name.split(";")})


#: every scope the model's step is traced under (models/attentions.py, models/moe_lm.py, ops/moe.py, ops/sparse_select.py)
SCOPES = ("attn_proj", "attn_glue", "dsa_index", "dsa_select", "dsa_attn", "dsa_loss", "moe_router", "moe_dispatch", "moe_experts",
          "moe_combine", "lm_head")
OTHER_FAMILIES = ("ssm_proj", "ssm_scan", "mla_proj", "eva_attn", "moe_latent", "kda_scan", "window_attn", "flash_attn", "moe_shared", "mlp")


def _scopes_of(op_name: str) -> set:
    return {scope for scope in SCOPES if re.search(rf"\b{scope}\b", op_name)}


@on_the_tree_itself
def test_a_scope_entry_reads_its_scopes_as_the_compiled_step_spells_them_and_not_their_neighbours(step_op_names):
    bench = resolve.Bench(ROOT)
    for scope in ("dsa_index", "dsa_select", "dsa_attn", "dsa_loss"):  # the four the issue names are all in the step
        assert any(scope in _scopes_of(op) for op in step_op_names), scope
    for name, wanted in sorted(SCOPE_ENTRIES.items()):
        params = bench.metric_file(name)["params"]
        assert params["module"] == "jit_local_scan" and "exclude" not in params
        matched = [op for op in step_op_names if re.search(params["pattern"], op)]
        assert matched and matched == [op for op in step_op_names if _scopes_of(op) & wanted]
        for neighbour in (set(SCOPES) - wanted) | set(OTHER_FAMILIES):
            assert not re.search(params["pattern"], f"jit(local_scan)/jvp({neighbour})/dot_general"), (name, neighbour)
        for scope in wanted:
            assert re.search(params["pattern"], f"jit(local_scan)/transpose(jvp({scope}))/pallas_call")
    # one entry a scope in ms, and no instruction under two of the four: their sum is the mechanism's time, counted once.
    # ``dsa_attn_ms_step.dsa`` counts what the scope holds in EVERY pass (on the chip the forward's ``tpu_custom_call`` and the
    # backward's ``jvp_dsa_attn_`` fusions of a breakdown both): the selection of its roofline share; the other share's is two entries
    mechanism = set().union(*MS_ENTRIES.values())
    assert all(len(_scopes_of(op) & mechanism) <= 1 for op in step_op_names)
    pattern = lambda name: bench.metric_file(name)["params"]["pattern"]  # noqa: E731
    assert pattern("dsa_attn_ms_step.dsa") == pattern("dsa_attn_roofline_pct.dsa")
    both = [op for op in step_op_names if re.search(pattern("dsa_index_roofline_pct.dsa"), op)]
    assert sorted(both) == sorted(op for name in ("dsa_index_ms_step.dsa", "dsa_select_ms_step.dsa") for op in step_op_names if re.search(pattern(name), op))
    for scope in ("dsa_attn", "dsa_loss"):  # forward, the rematerialised block's repeat and the backward, as the compiled step spells them
        under = [op for op in step_op_names if scope in _scopes_of(op)]
        assert any("transpose(" not in op for op in under) and any("rematted_computation" in op for op in under), scope
    assert any("transpose(jvp(dsa_loss))" in op for op in step_op_names) and any("jvp(dsa_attn)" in op for op in step_op_names)
    for stranger in ("dsa_attns", "dsa_attn_roofline", "dsa_mask", "dsa_index_grads", "attn", "dsa"):  # a save site's or a longer name is not a scope's
        assert not any(re.search(pattern(name), f"jit(local_scan)/jvp({stranger})/mul") for name in MS_ENTRIES), stranger
    # the indexer's score products run forward AND in the backward of its loss
    index = [op for op in step_op_names if "dsa_index" in _scopes_of(op)]
    assert any("transpose(" in op for op in index) and any("transpose(" not in op for op in index)
    # the selection has no backward of its own: in a backward it is only ever the rematerialised block's repeat of it
    assert all("rematted_computation" in op for op in step_op_names if "dsa_select" in _scopes_of(op) and "transpose(" in op)


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
    weights = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32) * 3.0, spec.init(jax.random.key(0)))
    toks = np.random.default_rng(0).integers(0, p["vocab_size"], (2, 65)).astype(np.int32)
    tokens, labels = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    forward = reference.build(p)

    def loss(w):
        z, slots, loss_i = forward(w, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(z, labels).mean() + loss_i, (z, slots, loss_i)

    (want, (want_z, want_slots, want_i)), want_grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(weights)
    (got, (z, slots, loss_i)), grads = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels)
    assert float(got) == pytest.approx(float(want), rel=1e-6) and float(loss_i) == pytest.approx(float(want_i), rel=1e-5) and float(want_i) > 0
    np.testing.assert_allclose(z, want_z, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(b).max()), err_msg=str(path))
    groups = {reference.group_of(path, grads) for path, _ in jax.tree_util.tree_leaves_with_path(grads)}
    assert groups == set(GROUPS) == set(reference.GROUPS)
    indexer = [float(np.abs(a).max()) for path, a in jax.tree_util.tree_leaves_with_path(grads) if reference.group_of(path, grads) == "indexer"]
    assert len(indexer) == 2 * 5 and min(indexer) > 0   # the indexer trains: by its own loss
    # a group's leaves take gradients of one scale: what writes into the stream is apart from what feeds the branch
    assert reference.GROUPS["attention_out"] == ("wo",) and reference.GROUPS["experts_out"] == ("w_down",)


@on_the_tree_itself
def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under KeyeVL2's keys on the CPU, the float32
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
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000059", "--seconds", "6",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/keye_vl2_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and a toy of 64-wide layers reads more of the
    # bfloat16 compute's noise than the limits drawn at 2048 allow; nothing else is wrong
    noisy = ("logits", "dsa_output", "indexer_loss") + tuple(name for name in CHECKS if name.startswith("grad_"))
    excused = lambda p: "outside the band" in p or "inside the window" in p or any(f"check {name}:" in p for name in noisy)  # noqa: E731
    assert [p for p in info["problems"] if not excused(p)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.6 < info["first_task_loss"] < 6.1  # ln 256 + the toy head's variance + two layers' indexer loss
    assert info["reference"]["relative_difference"] < 5e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in noisy), checks
    assert all(checks[name]["value"] < 0.4 for name in noisy), checks
    assert checks["dsa_selected_differing"]["value"] == 0.0
    assert "compared: check dsa_selected_differing" in done.stderr and "compared: check grad_indexer" in done.stderr
    metrics = result["metrics"]
    for name in ("hbm_peak_reported_gib.tok", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    counted = ("compiles_in_window.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla", "expert_load_max_pct_mean.moe")
    if info["window"]["reports"] >= 3:
        assert all(name in metrics for name in counted), sorted(metrics)
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only
    records = open(scratch / "benchmark" / ".state" / "runs" / CELL / "metrics" / "metrics.jsonl").read()
    for counter in ("dsa_pairs_causal", "dsa_pairs_needed", "dsa_pairs_computed", "dsa_rows_selecting"):
        assert re.search(rf'"{counter}": [\d.e+]+', done.stdout + records), counter
    assert re.search(r'"indexer_loss": 0\.\d+', records)


@on_the_tree_itself
def test_rehearsal_of_the_checks_a_sound_system_reads_every_one_and_every_control_is_caught():
    """The sizing tool's table (what the reference child reads, sound and
    under the controls, judged by run.py's ``reference_problems`` against the
    configuration's limits) on one seeded minibatch at the rehearsal's sizes,
    ONE table for all.  The controls that nothing but the step can catch run
    their own train step; the others are caught by a forward check the file
    names for them, as on the chip."""
    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        override = json.load(f)
    config["model_params"].update(override["model_params"])
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "keye_vl2_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    # the three controls that all_bfloat16 holds together and the second of AdamW's own are read on the chip alone
    # (the configuration's controls_read): a control costs this test a compile
    # ... and so is the fault inside the masked dK/dV KERNEL: off the TPU the attention is XLA's (tests/test_keye_vl2.py
    # holds that fault against the sound kernels in the interpreter)
    chip_alone = ("bfloat16_index_scores", "bfloat16_router", "bfloat16_logits", "no_weight_decay", "dkv_ignores_selection")
    controls = tuple(c for c in reference.CONTROLS if c not in chip_alone)
    assert {"dense_for_sparse", "topk_off_by_one", "no_indexer_loss", "lm_loss_reaches_indexer", "all_bfloat16", "state_unchanged"} == set(controls)
    table = sizing.check_table(config, reference, 2, [3300000061], controls, own_step=sizing.OWN_STEP)
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    # at the toy's widths the bfloat16 noise is over the limits drawn at 2048 (16-wide heads, 64-wide layers): those apart
    assert all(re.match(r"check (logits|dsa_output|indexer_loss|grad_\w+):", p) for p in sound["problems"]), sound["problems"]
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    assert sound["readings"]["dsa_selected_differing"] == 0.0 and sound["readings"]["dsa_index_scores"] < 1e-6
    assert sorted(table) == sorted(("sound",) + controls)
    for control in controls:
        (row,) = table[control]
        named = [check for check in config["controls"][control]["caught_by"] if check in row["readings"]]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert named and not row["correct"] and set(over) & set(named), (control, over, named)
        for check in named:  # and by more than the sound system's own reading
            assert not row["readings"][check] <= 2 * sound["readings"][check], (control, check)
    # without its loss the indexer's gradient is all missing (1), and with its input tied into the stream
    # the loss's gradient shows on the parameters below it
    assert table["no_indexer_loss"][0]["readings"]["grad_indexer"] == pytest.approx(1.0, abs=1e-6)
    assert sound["readings"]["indexer_input_detached"] == 0.0 and table["lm_loss_reaches_indexer"][0]["readings"]["indexer_input_detached"] > 1e-9


# ---- the cell's shapes, compiled ahead of time for a described v5e ----
# ISSUE 58 asked for this beside the cases of ``tests/test_chip_lowering.py``; it is HERE for PR 56's reason
# (.claude/skills/verify: that file is the tail of the driver's run, and a new cell's compile lives with its cell).


@pytest.fixture(scope="module")
def v5e_device():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(topology_name="v5e:2x2", platform="tpu").devices[0]
    except Exception as e:  # noqa: BLE001 — any plugin failure means "cannot"
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")


@pytest.fixture
def as_on_the_chip(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture
def path_lines(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    return lines


def _operand_lists(calls: list) -> list:
    """(int32, int8, bf16, f32 operands) of each Mosaic call, sorted."""
    out = []
    for call in calls:
        ops = re.search(r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", call).group(1)
        out.append((ops.count("s32["), ops.count("s8["), ops.count("bf16["), ops.count("f32[")))
    return sorted(out)


@on_the_tree_itself
def test_the_cells_whole_step_compiles_for_v5e_inside_the_line_with_the_masked_calls_told_apart(
    v5e_device, as_on_the_chip, path_lines, monkeypatch
):
    """``keye_vl2_job``'s real step (Keye-VL-2.0's widths: five layers of 16
    held experts, 32 query heads over 4 key/value heads, the indexer 16 x 64,
    topk 2048; ONE sequence of 16,384 tokens, per-layer rematerialisation)
    compiled for a described v5e with the byte budget the trainer resolves
    from a v5e's memory: 562.3 M parameters and their moments are 6.28 GiB of
    arguments, every layer keeps its selection and its attention output and
    the step stays under the trainer's line of 14.25 GiB AT THE FIRST
    COMPILE; the four ``dsa_*`` scopes are there; each layer's attention is
    the three MASKED flash kernels under ``dsa_attn`` at operand lists no
    older call has (the block summary's int32 scalars and the int8 mask: 1 +
    3 + 1 / 1 + 4 + 1 + 1 for dQ and dK/dV alike), the forward ONCE a layer (its
    output is kept); the score kernels under ``dsa_index``, the head-summed
    probabilities under ``dsa_loss``; no call of the full or window kernels;
    and no [16384, 16384] array wider than the int8 mask."""
    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel import trainer as trainer_lib
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    sys.path.insert(0, os.path.join(ROOT, "tests"))
    from test_chip_lowering import V5E_BYTES_LIMIT, _abstract_scan_step  # the other cells' AOT cases live there

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    bench = resolve.Bench(ROOT)
    params, traffic = bench.config(CONFIG)["model_params"], bench.traffic(TRAFFIC)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    step, args = _abstract_scan_step(trainer, mesh, minibatch=traffic["minibatch_size"], steps=traffic["minibatches_per_task"])
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total, plan = trainer_lib.compiled_bytes(compiled), trainer.keep_plan
    assert 12 * 2**30 < total < plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM, total / 2**30
    assert abs(compiled.memory_analysis().argument_size_in_bytes - 12 * 562290560) < 2**20  # parameters and two moments
    assert plan.kept == plan.tagged <= plan.budget and plan.tagged > 1.8 * 2**30   # five selections, five outputs
    text = compiled.as_text()
    for scope in SCOPES:
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    assert not re.search(r'op_name="[^"]*\b(mla_proj|ssm_\w+|kda_\w+|eva_\w+|window_attn|flash_attn)\b', text)
    assert not re.search(r"(f32|s32|bf16|u32)\[(\d+,)*16384,16384\]", text)   # the mask is int8, and nothing of its shape is wider
    assert re.search(r"s8\[(\d+,)*16384\]", text)
    mosaic = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    under = lambda scope: [c for c in mosaic if re.search(rf'op_name="[^"]*\b{scope}\b', c)]  # noqa: E731
    # five layers: forward (kept: once), dQ, dK/dV, each with the summary's scalars and the int8 mask
    assert _operand_lists(under("dsa_attn")) == sorted([(1, 1, 3, 0), (1, 1, 4, 1), (1, 1, 4, 1)] * 5)
    # ... lists no older flash call has: theirs hold no int8 operand (tests/test_chip_lowering.py, test_trinity_mini_cell.py)
    older = {(0, 0, 3, 0), (0, 0, 4, 1), (0, 0, 4, 2), (1, 0, 3, 0), (1, 0, 4, 1), (1, 0, 4, 2), (0, 0, 5, 0), (0, 0, 6, 1), (0, 0, 6, 2)}
    assert not set(_operand_lists(under("dsa_attn"))) & older
    assert under("dsa_index") and under("dsa_loss") and under("moe_experts") and not under("dsa_select")
    assert not set(under("dsa_attn")) & (set(under("dsa_index")) | set(under("dsa_loss")))
    # the attention AND the indexer's score and head-sum announce the kernels: run.py's ``expect`` reads every such line
    lines = [line for line in path_lines if "attention path:" in line]
    assert lines and all("attention path: pallas-compiled" in line for line in lines), path_lines
    for said in ("mask=int8[16384,16384] blocks=16x16 of 1024 rows", "dsa_index keys=16384", "dsa_loss keys=16384"):
        assert any(said in line for line in lines), (said, path_lines)


def test_the_masked_calls_operand_signature_is_pinned_and_differs_from_every_one_that_exists(monkeypatch):
    """The three masked calls lowered for the TPU at a small shape: one int32
    scalar-prefetch operand, the bf16 operands, ONE int8 operand (the mask),
    the ONE f32 operand of the backward's two — 1 + 3 + 1 / 1 + 4 + 1 + 1 /
    1 + 4 + 1 + 1 — and the full and window calls at the same shape hold no int8 operand."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    q = jax.ShapeDtypeStruct((1, 2048, 2, 128), jnp.bfloat16)      # two blocks of 1024 rows: a window of one is in contract
    mask = jax.ShapeDtypeStruct((1, 2048, 2048), jnp.int8)

    def calls(fn, *args):
        text = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",)).as_text()
        found = []
        for line in text.splitlines():
            if "tpu_custom_call" in line and "stablehlo.custom_call" in line:
                types = re.search(r":\s*\((.*?)\)\s*->", line).group(1)
                found.append((types.count("xi32>"), types.count("xi8>"), types.count("xbf16>"), types.count("xf32>")))
        return sorted(found)

    masked = lambda q, k, v, m: jnp.sum(fa.masked_flash_attention(q, k, v, m)[0].astype(jnp.float32) ** 2)  # noqa: E731
    got = calls(jax.grad(masked, (0, 1, 2)), q, q, q, mask)
    assert got == [(1, 1, 3, 0), (1, 1, 4, 1), (1, 1, 4, 1)]
    for window in (None, 1024):
        full = lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, True, window=window).astype(jnp.float32) ** 2)  # noqa: E731
        older = calls(jax.grad(full, (0, 1, 2)), q, q, q)
        assert len(older) == 3 and all(ops[1] == 0 for ops in older) and not set(older) & set(got)
