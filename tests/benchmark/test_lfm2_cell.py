"""What PR 64 added to the benchmark: the configuration ``lfm2_8b_a1b_ep4_l5``
(LFM2-8B-A1B, ``lfm2_moe``, at its published widths: one chip's share of a
4-way expert-parallel stage, 5 of 24 layers), the traffic mix
``job_seq8k_x4_v16384``, the cell ``lfm2_job``, the cost model ``lfm2_flops``
and the ``.gsc`` metrics.  CPU only."""

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

CELL, CONFIG, TRAFFIC = "lfm2_job", "lfm2_8b_a1b_ep4_l5", "job_seq8k_x4_v16384"
#: every per-layer metric the cell reports: the ones it JOINED (appended to their ``workloads``) and its own ``.gsc``
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "flash_attn_ms_step.tok", "flash_roofline_pct.tok",
    "remat_kept_pct.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla",
    "expert_load_max_pct_mean.moe", "attn_proj_ms_step.ssm",
]
OWN = ["gconv_ms_step.gsc", "gconv_hbm_pct.gsc", "gconv_kernel_pct.gsc", "gconv_proj_ms_step.gsc"]
#: entry -> the scope of the step it reads; every other scope of the step is a neighbour it must not read
SCOPE_ENTRIES = {"gconv_ms_step.gsc": "gated_conv", "gconv_hbm_pct.gsc": "gated_conv", "gconv_proj_ms_step.gsc": "gconv_proj",
                 "attn_proj_ms_step.ssm": "attn_proj", "flash_attn_ms_step.tok": "flash_attn"}
SCOPES = ("gconv_proj", "gated_conv", "ssm_conv", "attn_proj", "attn_glue", "flash_attn", "moe_router", "moe_dispatch", "moe_experts",
          "moe_combine", "mlp", "lm_head", "kda_conv", "kda_glue")
GROUPS = ("conv", "attention", "dense", "experts", "router", "embedding", "norms")
CHECKS = sorted(["gconv_output", "attention_output", "router_logits", "router_choices_differing", "head_logits", "logits", "adamw_update"]
                + [f"grad_{group}" for group in GROUPS])
CONV, FULL = "conv", "full_attention"
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name LFM2-8B-A1B), copied: the guide is not in the checkout.
CATALOG = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 7168,
    "layer_types": [CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV, CONV, FULL, CONV, CONV,
                    FULL, CONV, CONV],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1792, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "num_hidden_layers": 24,
    "num_key_value_heads": 8, "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
}
SOURCE = "https://huggingface.co/LiquidAI/LFM2-8B-A1B/blob/main/config.json"
CUT = {"num_hidden_layers": 5, "num_dense_layers": 1, "layer_types": [CONV, FULL, CONV, CONV, CONV], "num_experts": 8, "vocab_size": 16384}
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


def _costs() -> dict:
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
    assert "job_env" not in config and "job_env" not in traffic  # no environment variable of its own
    assert os.path.isfile(bench.reference_path(CONFIG))
    assert os.path.isfile(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json"))
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "lfm2_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED + OWN)
    assert 4 <= len(OWN) <= 8  # what a cell with a configuration of its own may bring (PERF.md section 7)
    # NOT joined, each for its reason (PERF.md section 4): no shared expert; the other flash entry's operand lists and unit FLOPs are
    # latent attention's; the optimizer entries' patterns leave out other cells' heads by their shapes
    for name in ("moe_shared_ms_step.mla", "flash_roofline_pct.mla", "optimizer_ms_step.mla", "optimizer_ms_step.moe", "mlp_ms_step.eva"):
        (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
        assert CELL not in entry["workloads"], name
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 16384, 8192, "recordio")
    assert gen["vocab"] == config["model_params"]["vocab_size"] == config["vocab_size"]
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64  # no task repeats inside warm-up + the window
    assert traffic["units_per_record"] == 8192 and traffic["minibatch_size"] == 4
    assert traffic["minibatches_per_task"] == 2 and traffic["rate_metric"] == "tokens_per_s_chip" and traffic["warmup_tasks"] == 4
    assert traffic["job_flags"] == {"profile_tasks": 2, "profile_inline": True}
    for key in ("why", "minibatch_why", "generator_why", "warmup_why"):
        assert len(traffic[key]) > 80, key
    # the traffic is job_seq8k_x1_v25024's with four sequences a step and another vocabulary slice, and nothing else
    other = bench.traffic("job_seq8k_x1_v25024")
    same = lambda t: {k: v for k, v in t.items() if not k.endswith("why") and k not in ("name", "generator", "minibatch_size")}  # noqa: E731
    assert same(traffic) == same(other) and {**other["generator"], "vocab": 16384} == gen
    # a held expert is sent what a 4-chip deployment at one 8k sequence a chip sends it
    p = config["model_params"]
    slots_a_held_expert = traffic["minibatch_size"] * 8192 * p["num_experts_per_tok"] / p["num_experts"]
    assert slots_a_held_expert == 4096 == 4 * (8192 * p["num_experts_per_tok"]) / p["num_experts"]


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_stated_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
        if key == "layer_types":  # the published list's own layers 1..5: the dense conv layer, then one whole period
            assert config[key] == CATALOG[key][1:6]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] and sorted(config["reduced"]) == sorted(CUT)
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("LFM2-8B-A1B"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: four expert layers after the dense one, ONE whole period of the published list, 8 routed experts a layer, a quarter of the vocabulary
    kinds = config["layer_types"]
    assert config["num_hidden_layers"] - config["num_dense_layers"] == 4 and kinds[1:] == [FULL, CONV, CONV, CONV] and kinds[0] == CONV
    assert CATALOG["layer_types"].count(CONV) == 18 and CATALOG["layer_types"].count(FULL) == 6
    assert config["num_experts"] >= 8 and config["vocab_size"] * 4 == CATALOG["vocab_size"]
    for said in ("4-way expert-parallel", "8 of 32 routed experts a chip", "16,384 of 65,536", "5 of 24 layers", "NOT run"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths, under the published spelling of the keys: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size", "moe_intermediate_size", "conv_L_cache",
            "conv_bias", "rope_theta", "norm_eps", "num_experts_per_tok", "use_expert_bias", "norm_topk_prob", "routed_scaling_factor")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["layer_types"] == kinds and p["num_hidden_layers"] == 5 and p["num_dense_layers"] == 1 and p["vocab_size"] == 16384
    assert p["num_experts"] == CATALOG["num_experts"] == 32 and p["experts_held"] == config["num_experts"] == 8
    assert p["tie_word_embeddings"] is True and "tie_word_embeddings" not in CATALOG and "transformers' default" in config["assumed"]["head"]
    # the published keys no layer reads are not handed to the program (it would refuse them) and the file says why
    unread = sorted(set(CATALOG) - set(p))
    assert unread == ["max_position_embeddings", "model_type"] and all(key in config["assumed"]["unread"] for key in unread)
    assert p["seq_len"] == 8192 and p["remat"] is True and p["decay_matrices_only"] is True
    assert p["lr_warmup_steps"] == 2000 and p["learning_rate"] == 2.2e-4 and p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {"layers", "conv", "attention", "router", "correction_bias", "head", "unread", "init", "optimizer",
                                      "precision", "weights", "remat", "depth", "data"}
    for key in ("layers", "conv", "attention", "router", "init"):
        assert "from memory" in config["assumed"][key], key
    assert "1e-20" in config["assumed"]["router"] and "1e-6" in config["assumed"]["router"]  # the divisor's departure, with its size
    assert "NOT published" in config["assumed"]["correction_bias"]
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.25 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.25 * config["checks"][check]["limit"], (name, check)
    assert config["controls"]["bfloat16_conv"]["caught_by"] == ["gconv_output"]
    assert "logits" in config["controls"]["no_rotary"]["caught_by"]
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"gconv_output", "router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(16384) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]
    assert "@" not in json.dumps({k: v for k, v in config.items() if k != "source"})  # no reading left to fill in


def test_the_share_is_the_arithmetic_the_file_states():
    """507.8 M parameters = 7.57 GiB at 16 bytes: the model's own init at
    the configuration's keys, counted (shapes only), against the cost model's
    count and the hand counts; the operator's bytes from shapes."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(5)] and "head" not in shapes and shapes["tok_emb"].shape == (16384, 2048)
    assert ["router" in blocks[name] for name in sorted(blocks)] == [False, True, True, True, True]
    assert ["gconv_in" in blocks[name] for name in sorted(blocks)] == [True, False, True, True, True]
    dense, attention, conv = blocks["b00"], blocks["b01"], blocks["b02"]
    assert conv["gconv_in"].shape == (2048, 6144) and conv["gconv_taps"].shape == (3, 2048) and conv["gconv_out"].shape == (2048, 2048)
    assert attention["wq"].shape == attention["wo"].shape == (2048, 2048) and attention["wk"].shape == attention["wv"].shape == (2048, 512)
    assert attention["q_norm"].shape == attention["k_norm"].shape == (64,) and "wz" not in attention
    assert conv["router"].shape == (2048, 32) and conv["router_bias"].shape == (32,) and conv["w_up"].shape == (8, 2048, 1792)
    assert dense["w_up"].shape == (2048, 7168)
    operator = ("gconv_in", "gconv_taps", "gconv_out", "wq", "wk", "wv", "wo", "q_norm", "k_norm", "operator_norm")
    part = lambda blk, names: count({k: v for k, v in blk.items() if k in names})  # noqa: E731
    counted = {"conv": part(conv, operator), "attention": part(attention, operator), "dense_ffn": count(dense) - part(dense, operator),
               "expert_ffn": count(conv) - part(conv, operator)}
    assert counted == {key: costs["params_" + key] for key in counted}
    assert (count(dense), count(attention), count(conv)) == (60827648, 98635936, 104933408)  # ISSUE 64's hand counts
    assert count(shapes) == costs["params_total"] == 507820288 == 60827648 + 98635936 + 3 * 104933408 + 33554432 + 2048
    assert round(16 * count(shapes) / 2**30, 2) == 7.57 and round(16 * count(shapes) / 1e9, 2) == 8.13
    for said in ("507,820,288", "7.57 GiB"):
        assert said in config["reduced_why"], said
    assert "507.8 M" in config["deployment"] and "7.57 GiB" in config["deployment"]
    # 22 bytes an element (three reads and a write forward, four reads and three writes backward, bfloat16), four conv layers
    assert costs["gated_conv_bytes_per_step"] == 4 * (4 * 8192 * 2048) * 22 == 5905580032
    assert costs["flash_unit_flops"] == 4 * 32 * 8192 * 8192 // 2 and (costs["flash_fwd_units"], costs["flash_bwd_units"]) == (256, 640)
    assert costs["moe_slots_per_step"] == 4 * 8192 * 4 * 4 and costs["expert_flops_per_slot"] == 18 * 2048 * 1792
    # 6 x the matrices a token meets (a quarter of its 4 slots' worth of experts is held: ONE expert's) + the attention layer's causal pairs
    active = 4 * 16777216 + 10485760 + 44040192 + 4 * (65536 + 11010048) + 33554432
    assert costs["active_matmul_params"] == active and costs["train_flops_per_token"] == 6 * active + 3 * 256 * 32 * 8192 // 2


def test_lfm2_flops_follows_its_keys():
    """The cost model from shapes: an untied head counts the table twice in the
    parameters and once in the FLOPs; no conv layer, no operator bytes."""
    costs = resolve.Bench(ROOT).costs("lfm2_flops")
    config = resolve.Bench(ROOT).config(CONFIG)
    p = config["model_params"]
    base = costs.compute({"model_params": p}, {"minibatch_size": 4})
    untied = costs.compute({"model_params": dict(p, tie_word_embeddings=False)}, {"minibatch_size": 4})
    assert untied["params_total"] - base["params_total"] == 16384 * 2048 and untied["train_flops_per_token"] == base["train_flops_per_token"]
    attention_only = costs.compute({"model_params": dict(p, layer_types=[FULL] * 5)}, {"minibatch_size": 2})
    assert attention_only["gated_conv_bytes_per_step"] == 0 and attention_only["attention_flops_per_token"] == 5 * base["attention_flops_per_token"]
    assert costs.compute({"model_params": p}, {"minibatch_size": 2})["gated_conv_bytes_per_step"] * 2 == base["gated_conv_bytes_per_step"]


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
    if name in OWN:  # NO new reader: the three that were there
        assert spec["reader"] in ("op_ms_step", "scope_hbm_roofline", "counter_delta")


@pytest.mark.parametrize("name", sorted(SCOPE_ENTRIES))
def test_a_scope_entry_reads_its_scope_and_not_its_neighbours(name):
    """``gated_conv`` is not ``gconv_proj`` nor the XLA chain's own nested
    ``ssm_conv`` (which lies UNDER ``gated_conv`` and is read with it), and
    KDA's ``kda_conv`` is another op's."""
    params = resolve.Bench(ROOT).metric_file(name)["params"]
    wanted = SCOPE_ENTRIES[name]
    assert params["module"] == "jit_local_scan" and params.get("on", "scope") == "scope"
    for how in ("jvp({})", "transpose(jvp({}))", "checkpoint/{}"):
        assert re.search(params["pattern"], f"jit(local_scan)/{how.format(wanted)}/pallas_call")
        for neighbour in set(SCOPES) - {wanted}:
            assert not re.search(params["pattern"], f"jit(local_scan)/{how.format(neighbour)}/dot_general"), neighbour
    if wanted == "gated_conv":
        assert re.search(params["pattern"], "jit(local_scan)/jvp(gated_conv)/ssm_conv/mul")


def test_the_two_counters_give_the_kernels_share_and_nothing_where_a_program_has_none(monkeypatch):
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file("gconv_kernel_pct.gsc")
    assert spec["params"] == {"counter": "gconv_positions_kernel", "over": "gconv_positions", "scale": 100}
    import runfiles

    reader = bench.reader(spec["reader"])
    records = [{"gconv_positions": 0.0, "gconv_positions_kernel": 0.0}, {"gconv_positions": 4 * 4 * 8192 * 10.0, "gconv_positions_kernel": 4 * 4 * 8192 * 10.0}]
    monkeypatch.setattr(runfiles, "counter_records", lambda ctx: records)
    assert reader.read({}, spec["params"]) == 100.0
    monkeypatch.setattr(runfiles, "counter_records", lambda ctx: [{"moe_slots": 1.0}, {"moe_slots": 2.0}])  # the parent: no such counter
    assert reader.read({}, spec["params"]) is None
    # ... and the part's counters are what the model's spec hands the worker's gauges
    from elasticdl_tpu.models.spec import load_model_spec

    counters = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **_rehearsal_params()).step_counters
    assert {"gconv_positions", "gconv_positions_kernel"} <= set(counters)


def test_the_operators_share_of_the_bandwidth_is_the_bytes_over_the_scopes_time():
    bench = resolve.Bench(ROOT)
    spec = bench.metric_file("gconv_hbm_pct.gsc")
    assert spec["params"]["bytes"] == "gated_conv_bytes_per_step" and spec["params"]["pattern"] == bench.metric_file("gconv_ms_step.gsc")["params"]["pattern"]
    # at the peak 819 GB/s the needed 5.9 GB take 7.2 ms a step; the rematerialised forward caps the pair's share at 22 / 30
    needed_ms = 1e3 * _costs()["gated_conv_bytes_per_step"] / bench.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    assert 7.1 < needed_ms < 7.3 and round(100 * 22 / 30) == 73
    assert bench.reader(spec["reader"]).read({"trace": None, "costs": _costs(), "peaks": bench.peaks("TPU v5 lite")}, spec["params"]) is None


def test_the_references_convolution_is_causal_with_three_taps_and_its_key_value_heads_are_read_by_index():
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    keys = jax.random.split(jax.random.key(0), 4)
    b, c, z = (jax.random.normal(k, (1, 12, 4)) for k in keys[:3])
    taps = jax.random.normal(keys[3], (3, 4))
    got = np.asarray(reference.gated_convolution(b, c, z, taps))
    p = np.asarray(b * z)
    for t in range(12):  # position by position: taps[2] on the position itself, taps[0] two back, nothing before the start
        want = sum(np.asarray(taps)[j] * p[0, t - 2 + j] for j in range(3) if t - 2 + j >= 0)
        np.testing.assert_allclose(got[0, t], np.asarray(c)[0, t] * want, rtol=1e-5, atol=1e-6)
    q, k, v = jax.random.normal(keys[0], (1, 8, 4, 2)), jax.random.normal(keys[1], (1, 8, 2, 2)), jax.random.normal(keys[2], (1, 8, 2, 2))
    o = reference.masked_attention(q, k, v)
    repeated = reference.masked_attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2))
    np.testing.assert_allclose(o, repeated, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o[0, 0], np.broadcast_to(np.asarray(jnp.repeat(v, 2, axis=2))[0, 0], (4, 2)), rtol=1e-6)  # the first query sees itself alone
    # it imports nothing of the program's models or ops outside the functions that run the SYSTEM's side of the checks
    with open(resolve.Bench(ROOT).reference_path(CONFIG)) as f:
        lines = f.read().splitlines()
    top_level = [line for line in lines if re.match(r"(from|import) ", line)]
    assert not any("elasticdl_tpu" in line for line in top_level)
    assert any('jax.config.update("jax_default_matmul_precision", "highest")' in line for line in lines)


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
    (got, (z, slots)), grads = reference._reference_program(json.dumps(p, sort_keys=True))(weights, tokens, labels)  # a sequence at a time
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(z, want_z, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(want_slots))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5 * float(np.abs(b).max()), err_msg=str(path))
    groups = {reference.group_of(path, grads) for path, _ in jax.tree_util.tree_leaves_with_path(grads)}
    assert groups == set(GROUPS)
    assert {f"grad_{g}" for g in groups} | {"gconv_output", "attention_output", "router_logits", "router_choices_differing", "head_logits",
                                            "logits", "adamw_update"} == set(CHECKS)


@on_the_tree_itself
def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under lfm2_moe's keys on the CPU, the float32
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
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000029", "--seconds", "6",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/lfm2_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and a toy of 128-wide layers reads more of the
    # bfloat16 compute's noise than the limits drawn at 2048 allow; nothing else is wrong
    noisy = ("logits", "attention_output", "gconv_output") + tuple(name for name in CHECKS if name.startswith("grad_"))
    excused = lambda p: "outside the band" in p or "inside the window" in p or any(f"check {name}:" in p for name in noisy)  # noqa: E731
    assert [p for p in info["problems"] if not excused(p)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.7  # ln 256 + the toy head's variance
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in noisy), checks
    assert all(checks[name]["value"] < 0.25 for name in noisy), checks
    assert "compared: check gconv_output" in done.stderr and "compared: check adamw_update" in done.stderr
    metrics = result["metrics"]
    for name in ("hbm_peak_reported_gib.tok", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    counted = ("compiles_in_window.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla",
               "expert_load_max_pct_mean.moe", "gconv_kernel_pct.gsc")
    if info["window"]["reports"] >= 3:
        assert all(name in metrics for name in counted), sorted(metrics)
    # off the TPU the op takes the XLA chain: no position by the kernels
    if "gconv_kernel_pct.gsc" in metrics:
        assert metrics["gconv_kernel_pct.gsc"]["value"] == 0.0
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only


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
    config["model_params"].update(override["model_params"])  # three layers: conv + dense, attention + experts, conv + experts
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "lfm2_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    # the two controls that all_bfloat16 holds together with the operator's, and the second of the train step's own,
    # are read on the chip alone (the configuration's controls_read): a control costs this test a compile
    controls = tuple(c for c in reference.CONTROLS if c not in ("bfloat16_router", "bfloat16_logits", "no_weight_decay"))
    assert {"bfloat16_conv", "all_bfloat16", "no_rotary", "state_unchanged"} == set(controls)
    table = sizing.check_table(config, reference, 2, [3300000031], controls, own_step=sizing.OWN_STEP)
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    # at the toy's widths the bfloat16 noise may be over the limits drawn at 2048: those apart
    assert all(re.match(r"check (logits|attention_output|gconv_output|grad_\w+):", p) for p in sound["problems"]), sound["problems"]
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    assert sorted(table) == sorted(("sound",) + controls)
    for control in controls:
        (row,) = table[control]
        named = [check for check in config["controls"][control]["caught_by"] if check in row["readings"]]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert named and not row["correct"] and set(over) & set(named), (control, over, named)
        for check in named:  # and by more than the sound system's own reading
            assert not row["readings"][check] <= 1.5 * sound["readings"][check], (control, check)
