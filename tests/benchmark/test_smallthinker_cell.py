"""What PR 69 added to the benchmark: the configuration ``smallthinker_21b_a3b_ep8_l8`` (SmallThinker-21BA3B-Instruct at
its published widths: one chip's share of an 8-way expert-parallel stage, 8 of 52 layers), the cell ``smallthinker_job`` on
the traffic ``job_seq16k_x1_v18992`` (which was there), the cost model ``smallthinker_flops`` — and NO per-layer entry of
its own: the cell joins entries that exist.  CPU only."""

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

CELL, CONFIG, TRAFFIC = "smallthinker_job", "smallthinker_21b_a3b_ep8_l8", "job_seq16k_x1_v18992"
#: every per-layer metric the cell reports: all JOINED (the cell's name appended to their ``workloads``), none its own
JOINED = [
    "step_ms.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok",
    "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok",
    "setup_master_s", "setup_index_scan_s", "setup_worker_imports_s", "setup_device_open_s", "setup_init_state_s",
    "setup_worker_build_s", "setup_compile_s", "setup_cache_served_pct", "setup_warmup_s", "setup_unattributed_s",
    "stalls_in_window.tok", "stall_ms_dispatch.tok", "stall_unnamed_ms_dispatch.tok",
    "lm_head_ms_step.tok", "moe_experts_ms_step.tok", "moe_glue_ms_step.tok", "flash_attn_ms_step.tok", "flash_roofline_pct.tok",
    "remat_kept_pct.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla", "expert_mxu_pct.mla",
    "expert_load_max_pct_mean.moe", "attn_proj_ms_step.ssm",
    "window_attn_ms_step.swa", "window_roofline_pct.swa", "window_pairs_needed_pct.swa", "attn_glue_ms_step.swa", "attn_glue_hbm_pct.swa",
]
GROUPS = ("attention", "experts", "router", "head", "embedding", "norms")
CHECKS = sorted(["window_output", "full_output", "router_logits", "router_choices_differing", "expert_output", "head_logits", "logits",
                 "adamw_update"] + [f"grad_{group}" for group in GROUPS])
CONTROLS = ("router_reads_v", "silu_for_relu", "full_for_window", "window_off_by_one", "rotary_on_full_layers", "rotary_off_sliding_layers",
            "bfloat16_router", "bfloat16_logits", "all_bfloat16", "no_weight_decay", "state_unchanged")
LAYOUT = [0, 1, 1, 1]
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``, name SmallThinker-21BA3B-Instruct), copied:
#: the guide is not in the checkout.
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384, "model_name": "smallthinker_21b_instruct",
    "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True, "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_layout": LAYOUT * 13, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT * 13, "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
}
SOURCE = "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
CUT = {"num_hidden_layers": 8, "moe_num_primary_experts": 8, "vocab_size": 18992, "sliding_window_layout": LAYOUT * 2, "rope_layout": LAYOUT * 2}
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"
PARAMETERS = 643852800
#: The growth rehearsal (test_benchmark_yardstick.py) runs this module again on grown copies of the tree; the tests
#: marked so compile models and read nothing of how many cells there are: they run on the tree itself only.
on_the_tree_itself = pytest.mark.skipif("EDL_BENCH_GROWTH_REHEARSAL" in os.environ, reason="reads nothing of the cells a later PR adds")


def _costs():
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    return bench.costs(config["costs"]).compute(config, traffic)


def test_the_cell_its_configuration_traffic_rehearsal_and_reference_resolve_by_name_and_it_brings_no_entry_of_its_own():
    bench = resolve.Bench(ROOT)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, TRAFFIC, 1) and len(cell["why"]) <= 200
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    assert config["model_def"] == "moe_lm.model_spec" and config["distribution_strategy"] == "AllReduce"
    assert config["expect"] == {"embedding_route": None, "attention_path": "pallas-compiled"}
    assert os.path.isfile(bench.reference_path(CONFIG))
    assert os.path.isfile(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json"))
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "smallthinker_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    assert sorted(m["name"] for m in bench.metrics_of(CELL, "per_layer")) == sorted(JOINED)
    # joined, never brought: no entry's list STARTS with the cell (on the tree itself the count is the 112 the parent had:
    # the growth rehearsal's 16 more still fit the contract's 128; a grown copy has its own count)
    assert not [m["name"] for m in bench.spec["per_layer"] if m.get("workloads", [None])[0] == CELL]
    if "EDL_BENCH_GROWTH_REHEARSAL" not in os.environ:
        assert len(bench.spec["per_layer"]) == 112
    # what ``trinity_mini_job`` reports and this cell does not: the shared expert's scope (this model has none)
    theirs = {m["name"] for m in bench.metrics_of("trinity_mini_job", "per_layer")}
    assert theirs - set(JOINED) == {"moe_shared_ms_step.mla"} and set(JOINED) <= theirs
    # the traffic is ``keye_vl2_job``'s file, as it was: ONE sequence of the model's published context from the slice
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 18992, 16384, "recordio")
    assert gen["vocab"] == config["model_params"]["vocab_size"] == config["vocab_size"]
    assert gen["seq_len"] == config["max_position_embeddings"] == config["model_params"]["seq_len"] == 4 * config["sliding_window_size"]
    assert traffic["minibatch_size"] == traffic["minibatches_per_task"] == 1 and traffic["rate_metric"] == "tokens_per_s_chip"
    assert bench.cell("keye_vl2_job")["traffic"] == TRAFFIC


@pytest.mark.parametrize("key", sorted(CATALOG))
def test_every_published_key_is_in_the_file_and_only_the_stated_cuts_differ(key):
    config = resolve.Bench(ROOT).config(CONFIG)
    assert config["published"][key] == CATALOG[key]
    if key in CUT:
        assert key in config["reduced"] and config[key] == CUT[key] != CATALOG[key]
        if key.endswith("_layout"):  # the published list's own first eight: two whole periods
            assert config[key] == CATALOG[key][:8]
    else:
        assert key not in config["reduced"] and config[key] == CATALOG[key]


def test_the_configuration_keeps_every_published_width_and_states_its_cuts_checks_and_controls():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == list(CUT)  # exactly the depth, the experts held, the vocabulary and the two layouts
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    try:
        with open(CATALOG_FILE) as f:
            rows = [row for row in map(json.loads, filter(str.strip, f)) if row.get("name") == "SmallThinker-21BA3B-Instruct"]
    except (OSError, ValueError):
        rows = []  # the guide is outside the checkout
    for row in rows:
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the floors: two whole periods (no leading dense layer), 8 experts held a layer, an eighth of the vocabulary
    assert config["num_hidden_layers"] == 8 and config["sliding_window_layout"] == config["rope_layout"] == LAYOUT * 2
    assert config["moe_num_primary_experts"] >= 8 and config["vocab_size"] * 8 >= CATALOG["vocab_size"]
    for said in ("8-way expert-parallel", "8 of 64 experts a chip", "18,992 of 151,936", "8 of 52 layers", "NOT run", "1,536 a held expert"):
        assert said in config["deployment"], said
    # ... and the program is given the published widths, under the published spelling of the keys: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim", "moe_ffn_hidden_size", "sliding_window_size",
            "rope_theta", "rms_norm_eps", "moe_num_active_primary_experts", "moe_primary_router_apply_softmax", "norm_topk_prob",
            "tie_word_embeddings")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["sliding_window_layout"] == p["rope_layout"] == LAYOUT * 2 and p["num_hidden_layers"] == 8 and p["vocab_size"] == 18992
    assert p["moe_num_primary_experts"] == CATALOG["moe_num_primary_experts"] == 64 and p["experts_held"] == config["moe_num_primary_experts"] == 8
    unread = sorted(set(CATALOG) - set(p))
    assert unread == ["max_position_embeddings", "model_name", "rope_scaling"] and all(key in config["assumed"]["unread"] for key in unread)
    assert p["seq_len"] == 16384 and p["remat"] is True and p["decay_matrices_only"] is True
    assert p["lr_warmup_steps"] == 2000 and p["learning_rate"] == 2.2e-4 and p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {"router_input", "router", "attention", "experts", "layer", "loss", "optimizer", "init", "unread",
                                      "precision", "weights", "remat", "depth", "data"}
    for key in ("router_input", "router", "attention", "experts", "layer"):
        assert "from memory" in config["assumed"][key], key
    assert "CE alone" in config["assumed"]["loss"] and "STAND-IN" in config["assumed"]["init"]
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.3 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert tuple(reference.CONTROLS) == CONTROLS and sorted(config["controls"]) == sorted(CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"] and control["caught_by"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 1.4 * config["checks"][check]["limit"], (name, check)
    # the PR's own mechanisms, each by the check ISSUE 69 names for it
    assert {"router_logits", "router_choices_differing"} <= set(config["controls"]["router_reads_v"]["caught_by"])
    assert "expert_output" in config["controls"]["silu_for_relu"]["caught_by"]
    assert "window_output" in config["controls"]["full_for_window"]["caught_by"] and "window_output" in config["controls"]["window_off_by_one"]["caught_by"]
    assert "full_output" in config["controls"]["rotary_on_full_layers"]["caught_by"]
    assert config["controls"]["rotary_off_sliding_layers"]["caught_by"]
    # the nearest precision below the configuration's comes out not correct, by the float32 islands' limits
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"router_logits", "head_logits"}
    assert config["first_task_loss_band"][0] >= math.log(18992) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"] and config["reduced_why"]
    assert "@" not in json.dumps({k: v for k, v in config.items() if k != "source"})  # no reading left to fill in


def test_the_share_is_the_arithmetic_the_file_states():
    """643,852,800 parameters = 9.59 GiB at 16 bytes: the model's own init at the configuration's keys, counted (shapes
    only), against the cost model's count and the hand counts of ISSUE 69 (68,326,400 a layer)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks, costs = shapes["blocks"], _costs()
    assert sorted(blocks) == [f"b{i:02d}" for i in range(8)]
    blk = blocks["b03"]
    assert sorted(blk) == sorted(["attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down"])  # no norm a head, no gate, no bias
    assert blk["wq"].shape == (2560, 3584) and blk["wk"].shape == blk["wv"].shape == (2560, 512) and blk["wo"].shape == (3584, 2560)
    assert blk["router"].shape == (2560, 64) and blk["w_up"].shape == blk["w_gate"].shape == (8, 2560, 768) and blk["w_down"].shape == (8, 768, 2560)
    assert shapes["head"].shape == (2560, 18992) and shapes["tok_emb"].shape == (18992, 2560)
    attention = 2560 * (3584 + 512 + 512) + 3584 * 2560
    assert (attention, 2560 * 64, 8 * 3 * 2560 * 768) == (20971520, 163840, 47185920)
    assert all(count(b) == costs["params_layer"] == attention + 163840 + 5120 + 47185920 == 68326400 for b in blocks.values())
    assert count(shapes) == costs["params_total"] == PARAMETERS == 8 * 68326400 + 2 * 18992 * 2560 + 2560
    assert round(16 * PARAMETERS / 2**30, 2) == 9.59 and round(16 * PARAMETERS / 1e9, 2) == 10.30
    for said in ("643,852,800", "9.59 GiB", "68,326,400"):
        assert said in config["reduced_why"], said
    # the layers are what the published lists' first eight say: layers 0 and 4 full WITHOUT the turn, the others slide WITH it
    parts = [layer[0][1] for layer in spec.init.keywords["layers"]]
    assert [(part.window, part.rotary) for part in parts] == [(0, False), (4096, True), (4096, True), (4096, True)] * 2
    assert all(layer[1][1].routes_on == "attn_norm" and layer[1][1].activation == "relu" for layer in spec.init.keywords["layers"])


@pytest.mark.parametrize("name", JOINED)
def test_every_metric_the_cell_reports_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["workloads"][0] != CELL  # another cell's entry, joined
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


def test_smallthinker_flops_counts_what_its_docstring_says():
    """ISSUE 69's figures: projections 8 x 41.9 M, scores and values 2 x 117.4 M + 6 x 51.4 M, held experts 8 x 8.8 M, the
    head 97 M: 1.05 GFLOP a token forward, 51.6 TFLOP a step."""
    c = _costs()
    assert (c["pairs_window"], c["pairs_full"]) == (4096 * 4097 // 2 + 12288 * 4096, 16384 * 16384 // 2) == (58722304, 134217728)
    assert c["active_matmul_params"] == 8 * (20971520 + 163840 + 0.75 * 3 * 2560 * 768) + 2560 * 18992
    assert c["attention_flops_per_token"] == 3 * 512 * 28 * (6 * 58722304 + 2 * 134217728) // 16384
    assert c["train_flops_per_token"] == 6 * c["active_matmul_params"] + c["attention_flops_per_token"]
    forward = c["train_flops_per_token"] / 3
    assert round(forward / 1e9, 2) == 1.05 and round(c["train_flops_per_token"] * 16384 / 1e12, 1) == 51.6
    assert round(c["attention_flops_per_token"] / c["train_flops_per_token"], 2) == 0.52
    assert round(2 * 134217728 * 28 * 512 / 16384 / 1e6, 1) == 234.9 and round(6 * 58722304 * 28 * 512 / 16384 / 1e6, 1) == 308.3  # 2 x 117.4, 6 x 51.4
    assert c["moe_slots_per_step"] == 16384 * 6 * 8 and c["expert_flops_per_slot"] == 3 * 3 * 2 * 2560 * 768
    assert c["expert_flops_per_step"] == c["moe_slots_per_step"] / 8 * c["expert_flops_per_slot"]
    assert (c["window_unit_flops"], c["flash_unit_flops"]) == (28 * 58722304, 28 * 134217728)
    assert (c["window_fwd_units"], c["window_bwd_units"], c["window_bwd_second_units"]) == (512, 1280, 0) == (c["flash_fwd_units"], c["flash_bwd_units"], c["flash_bwd_second_units"])
    # the glue: six layers turn q and k (2 C_q + 2 C_k each way), all eight repeat k and v and sum the repeats back
    c_q, c_k = 28 * 128, 4 * 128
    assert c["attn_glue_bytes_per_step"] == 16384 * 2 * (6 + 8) * 2 * (2 * c_q + 2 * c_k)


def test_the_references_experts_are_a_loop_over_the_held_range_and_its_router_softmaxes_the_chosen():
    """``held_experts``: a slot on an absent expert adds nothing; ``build``'s routing: the weights of a token's chosen
    experts are the softmax over the chosen logits alone and sum to one, the slots count every choice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    reference = resolve.load_module(resolve.Bench(ROOT).reference_path(CONFIG))
    rng = np.random.default_rng(0)
    t, w_gate, w_up, w_down = (jnp.asarray(rng.standard_normal(shape), jnp.float32) for shape in ((10, 8), (3, 8, 6), (3, 8, 6), (3, 6, 8)))
    m = jnp.asarray(rng.uniform(0, 1, (10, 12)), jnp.float32)
    got = reference.held_experts(t, m, w_gate, w_up, w_down, 4, jax.nn.relu)
    want = sum(((np.maximum(t @ w_gate[e], 0) * (t @ w_up[e])) @ w_down[e]) * m[:, 4 + e, None] for e in range(3))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    elsewhere = m.at[:, :4].set(7.0).at[:, 7:].set(-3.0)  # the absent experts' weights change nothing
    np.testing.assert_array_equal(np.asarray(reference.held_experts(t, elsewhere, w_gate, w_up, w_down, 4, jax.nn.relu)), np.asarray(got))
    assert reference.kinds_of({"sliding_window_layout": [0, 1], "rope_layout": [0, 1], "num_hidden_layers": 2}) == ((0, 0), (1, 1))
    assert set(reference.GROUPS) == set(GROUPS) and tuple(reference.CONTROLS) == CONTROLS


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
        p = {**config["model_params"], **json.load(f)["model_params"], "seq_len": 64}  # two layers: a full one, a sliding one
    reference = resolve.load_module(bench.reference_path(CONFIG))
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    weights = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32) * (5.0 if a.ndim > 1 else 1.0), spec.init(jax.random.key(0)))
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
    assert {reference.group_of(path, grads) for path, _ in jax.tree_util.tree_leaves_with_path(grads)} == set(GROUPS)


@on_the_tree_itself
def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape (a minute: a whole job and its reference child, two
    layers; nothing smaller is the normal path): a real ``elasticdl train --local`` job (client, master, worker loop, Trainer) of
    ``moe_lm.model_spec`` under smallthinker's keys on the CPU, the float32 reference child on the first task's records
    with the configuration's checks.  Never a result line; exit code 4."""
    scratch = tmp_path / "checkout"
    shutil.copytree(
        ROOT, scratch, symlinks=True,
        ignore=shutil.ignore_patterns(".git", ".state", "__pycache__", "chiprun_out", "scratch_chip", ".jax_cache", "parent_tree", "final_tree"),
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed", "3300000069", "--seconds", "6",
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/smallthinker_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=900,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band, and a toy of 64-wide layers and 16-wide heads reads
    # another share of the bfloat16 compute's noise than the limits drawn at 2560 allow; nothing else is wrong
    noisy = ("logits", "window_output", "full_output", "expert_output") + tuple(name for name in CHECKS if name.startswith("grad_"))
    excused = lambda p: "outside the band" in p or "inside the window" in p or any(f"check {name}:" in p for name in noisy)  # noqa: E731
    assert [p for p in info["problems"] if not excused(p)] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.5 < info["first_task_loss"] < 5.7  # ln 256 + the toy head's variance
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS
    assert all(check["ok"] for name, check in checks.items() if name not in noisy), checks
    assert all(checks[name]["value"] < 0.25 for name in noisy), checks
    assert "compared: check router_logits" in done.stderr and "compared: check expert_output" in done.stderr
    metrics = result["metrics"]
    for name in ("hbm_peak_reported_gib.tok", "setup_master_s", "setup_init_state_s", "setup_compile_s"):
        assert name in metrics, name
    counted = ("compiles_in_window.tok", "moe_slots_computed_pct.mla", "moe_slots_held_pct.mla", "moe_slots_overflow_pct.mla",
               "expert_load_max_pct_mean.moe", "window_pairs_needed_pct.swa")
    if info["window"]["reports"] >= 3:
        assert all(name in metrics for name in counted), sorted(metrics)
        assert metrics["moe_slots_computed_pct.mla"]["value"] == 100.0  # dropless
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only


@pytest.mark.slow  # a minute of compiles that repeat on the CPU what the sizing tool read on the chip (the file's controls_read): on demand
@on_the_tree_itself
def test_rehearsal_of_the_checks_a_sound_system_reads_every_one_and_this_prs_controls_are_caught():
    """The sizing tool's table (what the reference child reads, sound and under controls, judged by run.py's
    ``reference_problems`` against the configuration's limits) on one seeded minibatch at the rehearsal's sizes (a
    minute: the tapped forward and the train step of the toy are two compiles, a control a third).  The two controls of
    the mechanisms this family brought — a router fed the experts' rows, silu for relu — and the window's are read here;
    the others on the chip alone (the configuration's ``controls_read``): a control costs this test a compile."""
    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        config["model_params"].update(json.load(f)["model_params"])
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "smallthinker_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    controls = ("router_reads_v", "silu_for_relu", "full_for_window")
    table = sizing.check_table(config, reference, 2, [3300000071], controls, own_step=sizing.OWN_STEP)
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS
    assert all(re.match(r"check (logits|window_output|full_output|expert_output|grad_\w+):", p) for p in sound["problems"]), sound["problems"]
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-3)
    for control in controls:
        (row,) = table[control]
        named = [check for check in config["controls"][control]["caught_by"] if check in row["readings"]]
        over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
        assert named and not row["correct"] and set(over) & set(named), (control, over, named)
    assert table["router_reads_v"][0]["readings"]["router_logits"] > 1e3 * sound["readings"]["router_logits"]
    assert table["silu_for_relu"][0]["readings"]["expert_output"] > 10 * sound["readings"]["expert_output"]


# ---- the cell's whole step for a described v5e: LOWERED in seconds; the compile (100 s) is ``slow`` (tests/README.md) ----


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


def _the_cells_step(v5e_device, monkeypatch):
    """(the trainer, the scanned step, its abstract arguments, the line) of the cell's real step on a described v5e."""
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
    return trainer, step, args, V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM


def _as_a_trace_event(operands: str) -> str:
    """A lowered Mosaic call's operand types as a device trace's event name spells them: each operand's shape with its
    (row-major) layout ahead of its name."""
    named = []
    for i, (shape, dtype) in enumerate(re.findall(r"tensor<([\dx]*?)x?(i32|bf16|f32)>", operands)):
        dims = [d for d in shape.split("x") if d]
        layout = ",".join(str(k) for k in reversed(range(len(dims))))
        kind = "s32" if dtype == "i32" else dtype
        named.append(f"{kind}[{','.join(dims)}]{{{layout}}} %operand.{i}")
    return "custom-call(" + ", ".join(named) + '), custom_call_target="tpu_custom_call"'


SCOPES = ("attn_proj", "attn_glue", "window_attn", "flash_attn", "moe_router", "moe_dispatch", "moe_experts", "moe_combine", "lm_head")


@on_the_tree_itself
def test_the_cells_step_lowers_for_v5e_with_the_full_and_window_calls_at_sixteen_thousand_rows_told_apart(
        v5e_device, as_on_the_chip, path_lines, monkeypatch):
    """``smallthinker_job``'s real step (the published widths: eight layers of 8 held experts, 28 query heads over 4
    key/value heads of 128; ONE sequence of 16,384 tokens, per-layer rematerialisation) LOWERED for a described v5e with
    the byte budget the trainer resolves from a v5e's memory: the scopes the joined entries read are there, the EARLY
    routing under ``moe_router``; every layer's attention is the three flash kernels on ``[1, 16384, 3584]`` operands —
    six layers with the window's int32 [1] AHEAD of the lists (what ``window_roofline_pct.swa``'s patterns read), two
    without (``flash_roofline_pct.tok``'s), each set reading its own and none of the other's nor a grouped matmul; no
    ``[16384, 16384]`` array exists (no layer took the XLA path); the keep plan is made at lowering: the attention's
    outputs alone are tagged, and kept."""
    trainer, step, args, line = _the_cells_step(v5e_device, monkeypatch)
    text = step.trace(*args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    stacks = " ".join(re.findall(r'^#loc\d+ = loc\("([^"]*)"', text, re.M))
    for scope in SCOPES:
        assert re.search(rf"\b{scope}\b", stacks), scope
    assert not re.search(r"\b(mla_proj|ssm_\w+|kda_\w+|eva_\w+|bd_attn|dsa_\w+|moe_shared|mlp)\b", stacks)
    assert not re.search(r"tensor<(\d+x)*16384x16384x", text)
    calls = {"window": [], "full": [], "other": []}
    for call in [line_ for line_ in text.splitlines() if "stablehlo.custom_call @tpu_custom_call" in line_]:
        name = re.search(r'kernel_name = "([^"]*)"', call).group(1)
        operands = call[call.rindex(" : (") + 4:call.rindex(") -> ")]
        flash = name in ("_fwd_kernel", "_dq_kernel", "_dkv_kernel")
        calls["other" if not flash else "window" if operands.startswith("tensor<1xi32>") else "full"].append((name, operands))
    lists = lambda kind: sorted((o.count("xi32>"), o.count("xbf16>"), o.count("xf32>")) for _, o in calls[kind])  # noqa: E731
    # a lowered text holds a layer's function once a distinct (block, keep-set): two kinds of layer here, each kept alike
    assert lists("full") and set(lists("full")) == {(0, 3, 0), (0, 4, 1), (0, 4, 2)}
    assert lists("window") and set(lists("window")) == {(1, 3, 0), (1, 4, 1), (1, 4, 2)}
    assert all("tensor<1x16384x3584xbf16>" in o for kind in ("full", "window") for _, o in calls[kind])  # 28 heads of 128; K, V repeated
    assert {name for name, _ in calls["other"]} >= {"kernel"}  # megablox's grouped matmuls
    for metric, own, other in (("window_roofline_pct.swa", "window", "full"), ("flash_roofline_pct.tok", "full", "window")):
        with open(os.path.join(BENCH_DIR, "metrics", metric + ".json")) as f:
            patterns = [k["pattern"] for k in json.load(f)["params"]["kernels"]]
        for pattern in patterns:
            assert sum(bool(re.search(pattern, _as_a_trace_event(o))) for _, o in calls[own]) == len(calls[own]) // 3, (metric, pattern)
            assert not any(re.search(pattern, _as_a_trace_event(o)) for _, o in calls[other] + calls["other"]), (metric, pattern)
    plan = trainer.keep_plan
    assert plan.line == line and plan.kept == plan.tagged <= plan.budget and 0.85 * 2**30 < plan.tagged < 0.95 * 2**30  # eight o's and their logsumexps
    lines = [said for said in path_lines if "attention path:" in said]
    assert lines and all("attention path: pallas-compiled" in said for said in lines), path_lines
    assert any("steps=80/70 key_tiles=252/1024 fwd, 952/4096 bwd" in said and said.endswith("window=4096)") for said in lines), path_lines
    assert any("steps=136/136 key_tiles=528/1024 fwd" in said and said.endswith("heads_per_block=1)") for said in lines), path_lines


@on_the_tree_itself
@pytest.mark.parametrize("window", [None, 4096], ids=["full_causal", "window_of_four_blocks"])
def test_the_flash_calls_compile_for_v5e_at_sixteen_thousand_rows(v5e_device, path_lines, monkeypatch, window):
    """Mosaic's verdict on the contract PR 69 opened, at the cell's shape ``[1, 16384, 28, 128]`` (5 to 9 s a call: the
    three kernels, forward and backward): the folded triangle over 16 blocks and the window's rows of five steps fit the
    16 MiB of scoped VMEM as the 8192-row calls do, at the operand lists every older call has."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_use_interpret", lambda: False)
    arg = jax.ShapeDtypeStruct((1, 16384, 28, 128), jnp.bfloat16, sharding=jax.sharding.SingleDeviceSharding(v5e_device))
    loss = lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, True, window=window).astype(jnp.float32) ** 2)  # noqa: E731
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(arg, arg, arg).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\", operand_layout_constraints=\{(.*?)\}, frontend_attributes", text)
    scalar = int(window is not None)
    assert sorted((ops.count("s32["), ops.count("bf16["), ops.count("f32[")) for ops in calls) == [(scalar, 3, 0), (scalar, 4, 1), (scalar, 4, 2)]
    assert all("bf16[1,16384,3584]" in ops for ops in calls) and not re.search(r"\[(\d+,)*16384,16384\]", text)
    said = "steps=80/70 key_tiles=252/1024 fwd, 952/4096 bwd" if window else "steps=136/136 key_tiles=528/1024 fwd, 2080/4096 bwd"
    assert any("attention path: pallas-compiled" in line and said in line for line in path_lines), path_lines


@pytest.mark.slow
@on_the_tree_itself
def test_the_cells_whole_step_compiles_for_v5e_inside_the_line(v5e_device, as_on_the_chip, monkeypatch):
    """The same step COMPILED (100 s alone: ``slow``; its lowered twin above runs): 643.9 M parameters and their moments are
    7.20 GiB of arguments and the step stays under the trainer's line of 14.25 GiB AT THE FIRST COMPILE (13.932 GiB as
    landed, 0.889 of 0.889 GiB tagged kept: the q, k, v products are no save sites in this family because the trainer's
    estimate reads this step 3 GiB under the compiler's account), each layer's attention three kernels under its scope."""
    from elasticdl_tpu.parallel import trainer as trainer_lib

    trainer, step, args, line = _the_cells_step(v5e_device, monkeypatch)
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total = trainer_lib.compiled_bytes(compiled)
    assert 13 * 2**30 < total < line, total / 2**30
    assert abs(compiled.memory_analysis().argument_size_in_bytes - 12 * PARAMETERS) < 2**20  # parameters and two moments
    text = compiled.as_text()
    mosaic = [call for call in text.splitlines() if 'custom_call_target="tpu_custom_call"' in call]
    under = lambda scope: [c for c in mosaic if re.search(rf'op_name="[^"]*\b{scope}\b', c)]  # noqa: E731
    assert len(under("flash_attn")) == 3 * 2 and len(under("window_attn")) == 3 * 6  # each forward ONCE: its output is kept
    assert all("s32[1]{0}" in c for c in under("window_attn")) and not any("s32[1]{0}" in c for c in under("flash_attn"))
    assert not re.search(r"\[(\d+,)*16384,16384\]", text)
