"""What PR 37 added to the benchmark: the configuration
``evabyte_6b5_tp2_l4`` (EvaByte at its published widths: one chip's share of
a 2-way head-parallel layer, four layers), the traffic mix ``job_seq16k``,
the cell ``evabyte_job``, the cost model ``evabyte_flops`` and the ``.eva``
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

CELL, CONFIG, TRAFFIC = "evabyte_job", "evabyte_6b5_tp2_l4", "job_seq16k"
EVA = [
    "step_ms.tok", "mfu_pct.tok", "device_idle_pct.tok", "host_loop_pct.tok", "prep_wait_pct.tok",
    "starved_dispatch_pct.tok", "compiles_in_window.tok", "hbm_peak_reported_gib.tok", "task_gap_max_ms.tok",
    "lease_ms_task.tok", "eva_attn_ms_step.eva", "eva_roofline_pct.eva", "eva_pool_ms_step.eva",
    "eva_pool_hbm_pct.eva", "eva_proj_ms_step.eva", "mlp_ms_step.eva", "lm_head_ms_step.tok",
]
CHECKS = sorted([
    "eva_output", "eva_lse", "head_logits", "logits", "adamw_update",
    "grad_attention", "grad_eva_vectors", "grad_mlp", "grad_head", "grad_embedding", "grad_norms",
])
#: The catalog row's ``config`` (model-configs guide, ``architectures.jsonl``,
#: name EvaByte), copied: the guide is not in the checkout.
CATALOG = {
    "attention_bias": False, "attention_class": "eva", "chunk_size": 16, "fp32_ln": False, "fp32_logits": True,
    "fp32_skip_add": True, "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None, "init_fn": "v2",
    "init_std": 0.01275, "intermediate_size": 11008, "lazy_init": True, "max_position_embeddings": 32768,
    "max_seq_length": 32768, "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
    "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32, "num_key_value_heads": 32,
    "num_pred_heads": 8, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 100000,
    "tie_word_embeddings": False, "vocab_size": 320, "window_size": 2048,
}
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
CUT = {"num_hidden_layers": 4, "num_attention_heads": 16, "num_key_value_heads": 16}
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
    assert os.path.isfile(os.path.join(BENCH_DIR, "sizing", "evabyte_against_reference.py"))
    assert [m["name"] for m in bench.metrics_of(CELL, "end_to_end")] == ["tokens_per_s_chip", "setup_s"]
    gen = traffic["generator"]
    assert (gen["kind"], gen["vocab"], gen["seq_len"], gen["container"]) == ("lm_tokens", 320, 16384, "recordio")
    # no task repeats inside warm-up + the window: nothing is memorised
    assert gen["tasks_per_file"] == gen["distinct_tasks"] == 64
    assert traffic["units_per_record"] == 16384 and traffic["minibatch_size"] == 1
    assert traffic["minibatches_per_task"] == 1 and traffic["rate_metric"] == "tokens_per_s_chip"
    assert traffic["job_flags"] == {} and traffic["warmup_tasks"] == 4
    for key in ("why", "minibatch_why", "generator_why"):
        assert len(traffic[key]) > 80, key


def test_the_configuration_keeps_every_published_width_and_states_its_three_cuts():
    bench = resolve.Bench(ROOT)
    (entry,) = [c for c in bench.spec["configs"] if c["name"] == CONFIG]
    config = bench.config(CONFIG)
    assert entry["reduced"] == config["reduced"] == list(CUT)  # exactly the three keys
    assert entry["source"] == config["source"] == SOURCE and len(entry["why"]) <= 200
    assert config["published"] == CATALOG  # the pin: the copy above
    for row in _catalog_rows("EvaByte"):
        assert row["config"] == config["published"] and row["source_url"] == config["source"]
    # the file holds every key of the published config under the same name, as it is run:
    # the three cuts beside their published values, nothing else moved
    for key, value in CATALOG.items():
        assert config[key] == CUT.get(key, value), key
    assert "2 chips share each layer" in config["deployment"] and "2 chips share each layer" in config["reduced_why"]
    for said in ("32 layers", "32 heads", "16 HELD", "(A)", "(B)"):
        assert said in config["reduced_why"], said
    assert config["num_hidden_layers"] >= 4  # the floor: four layers of the one kind there is
    # ... and the program is given the same numbers: no width is cut
    p = config["model_params"]
    same = ("hidden_size", "intermediate_size", "num_attention_heads", "vocab_size", "attention_class", "window_size",
            "chunk_size", "rope_theta", "rms_norm_eps", "norm_add_unit_offset", "fp32_skip_add", "num_pred_heads",
            "init_std", "tie_word_embeddings")
    for key in same:
        assert p[key] == CATALOG[key], key
    assert p["heads_held"] == config["num_attention_heads"] == 16  # the layer keeps its 32 heads' width of 128
    assert p["hidden_size"] // p["num_attention_heads"] == 128
    assert p["num_hidden_layers"] == 4 and p["layer_types"] == ["dense"] * 4 and p["seq_len"] == 16384
    assert p["remat"] is True and p["router_aux_loss_coef"] == p["router_z_loss_coef"] == 0.0
    assert set(config["assumed"]) >= {
        "eva_vectors", "windows", "rotary", "gains", "loss", "init", "optimizer", "precision", "weights", "remat",
        "depth", "data"}
    for key in ("eva_vectors", "windows", "rotary", "gains", "loss"):
        assert "from memory" in config["assumed"][key], key
    assert sorted(config["checks"]) == CHECKS
    for name, check in config["checks"].items():
        # every limit stands over every sound reading, with room
        assert 1.5 * check["system_reads"]["largest"] < check["limit"] and check["system_reads"]["seeds"] >= 3, name
    # every control is caught by a check it names, with room, or the file says why no limit can
    reference = resolve.load_module(bench.reference_path(CONFIG))
    assert sorted(config["controls"]) == sorted(reference.CONTROLS)
    for name, control in config["controls"].items():
        assert control["what"], name
        for check in control["caught_by"]:
            assert config["checks"][check]["controls_read"][name]["smallest"] > 2 * config["checks"][check]["limit"], (name, check)
        assert control["caught_by"] or control["why_not"], name
    # the nearest precision below the configuration's comes out not correct, by the limits of
    # the float32 islands that can be read apart: the softmax's and the logits'
    assert set(config["controls"]["all_bfloat16"]["caught_by"]) >= {"eva_lse", "head_logits"}
    assert not config["controls"]["bfloat16_residual"]["caught_by"]
    assert config["first_task_loss_band"][0] >= math.log(320) and config["reference_tolerance"] <= 1e-3
    assert config["correct_does_not_cover"] and config["checks_why"]


def test_the_share_is_the_arithmetic_the_file_states():
    """687.1 M parameters: the model's own init at the configuration's
    keys, counted (shapes only)."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    config = resolve.Bench(ROOT).config(CONFIG)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **config["model_params"])
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    count = lambda tree: sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(tree))  # noqa: E731
    blocks = shapes["blocks"]
    assert sorted(blocks) == ["b00", "b01", "b02", "b03"] and all("router" not in b for b in blocks.values())
    blk = blocks["b00"]
    assert blk["wq"].shape == blk["wk"].shape == blk["wv"].shape == (4096, 16 * 128) and blk["wo"].shape == (16 * 128, 4096)
    assert blk["eva_phi"].shape == blk["eva_mu"].shape == (16, 128)
    assert blk["w_gate"].shape == (4096, 11008) and shapes["head"].shape == (4096, 8 * 320)
    assert round(count(blk) / 1e6, 2) == 168.83
    assert round(count(shapes) / 1e6, 1) == 687.1
    assert "687.1 M" in config["reduced_why"] and "687.1 M" in config["deployment"]


@pytest.mark.parametrize("name", EVA)
def test_every_eva_metric_resolves_to_a_file_and_a_reader(name):
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert CELL in entry["workloads"] and entry["moves"] == "tokens_per_s_chip"
    spec = bench.metric_file(name)
    assert callable(bench.reader(spec["reader"]).read)
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert name in [m["name"] for m in bench.metrics_of(CELL, "per_layer")]


def test_the_eva_patterns_read_the_eva_kernels_operand_lists():
    bench = resolve.Bench(ROOT)
    patterns = [k["pattern"] for k in bench.metric_file("eva_roofline_pct.eva")["params"]["kernels"]]
    operand = lambda dtype, i: f"{dtype}[1,16384,2048]{{2,1,0:T(8,128)(2,1)}} %fusion.{i}"  # noqa: E731
    event = lambda n_bf16, n_f32: (  # noqa: E731
        "%custom-call.7 = bf16[1,16384,2048]{2,1,0} custom-call("
        + ", ".join([operand("bf16", i) for i in range(n_bf16)] + [operand("f32", 9 + i) for i in range(n_f32)])
        + '), custom_call_target="tpu_custom_call"'
    )
    lists = [(5, 0), (6, 1), (6, 2)]
    for pattern, own in zip(patterns, lists):
        for other in lists + [(3, 0), (4, 1), (4, 2), (6, 0), (5, 1)]:
            assert bool(re.search(pattern, event(*other))) == (other == own), (own, other)


def test_evabyte_flops_counts_what_its_docstring_says():
    bench = resolve.Bench(ROOT)
    config, traffic = bench.config(CONFIG), bench.traffic(TRAFFIC)
    costs = bench.costs(config["costs"]).compute(config, traffic)
    assert costs["active_matmul_params"] == 4 * (4 * 4096 * 2048 + 3 * 4096 * 11008) + 4096 * 2560
    # NEEDED pairs a head and sequence: 24.13 M, not the full causal 134.2 M
    assert (costs["eva_pairs_exact"], costs["eva_pairs_summary"]) == (16785408, 7340032)
    assert costs["eva_pairs_exact"] == 8 * 2048 * 2049 // 2 and costs["eva_pairs_summary"] == 2048 * 128 * 28
    assert (costs["eva_fwd_units"], costs["eva_bwd_units"], costs["eva_bwd_second_units"]) == (512, 1280, 0)
    assert costs["eva_unit_flops"] == 16 * 24125440
    assert costs["attention_flops_per_token"] == 4 * 16 * 24125440 * 3 * 512 / 16384
    assert costs["train_flops_per_token"] == 6 * costs["active_matmul_params"] + costs["attention_flops_per_token"]
    # a step of 16,384 tokens: 69.8 TFLOP needed, 3.4 % of it in the attention's pairs; no rematerialised forward
    assert round(costs["train_flops_per_token"] * 16384 / 1e12, 1) == 69.8
    assert round(100 * costs["attention_flops_per_token"] / costs["train_flops_per_token"], 1) == 3.4
    # the counters' share, from the same shapes: 30.4 % of the scored pairs are summaries
    assert round(100 * costs["eva_pairs_summary"] / 24125440, 1) == 30.4
    n = 16384 * 16 * 128
    assert costs["eva_pool_bytes_per_step"] == 4 * 2 * (6 * n + 4 * n // 16)
    # the cost model counts pairs as the op does
    from elasticdl_tpu.ops import eva_attention as eva_ops

    for shape in ((16384, 2048, 16), (5000, 2048, 16), (200, 64, 8)):
        assert bench.costs(config["costs"]).pairs(*shape) == eva_ops.pairs(*shape)


def test_rehearsal_trains_the_model_through_the_normal_path(tmp_path):
    """The whole of run.py for the new cell at the rehearsal shape: a real
    ``elasticdl train --local`` job (client, master, worker loop, Trainer)
    of ``moe_lm.model_spec`` under EvaByte's keys on the CPU, the float32
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
         "--trace", "1", "--rehearsal", "benchmark/rehearsal/evabyte_job.json"],
        cwd=scratch, env=env, capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 4, done.stderr[-3000:]
    info = json.loads([line for line in done.stdout.splitlines() if line.startswith("[bench-info] ")][-1][len("[bench-info] "):])
    result = json.loads([line for line in done.stderr.splitlines() if line.startswith("[bench-rehearsal] ")][-1][len("[bench-rehearsal] "):])
    assert info["boot"]["count"] == 1 and info["boot"]["platform"] == "cpu"
    # the toy model's first loss is not the configuration's band; nothing else is wrong
    assert [p for p in info["problems"] if "outside the band" not in p] == []
    assert info["compiles_in_window"] == 0 and info["status"]["abandoned"] == 0
    assert 5.75 < info["first_task_loss"] < 5.80  # ln 320 + the toy head's variance: CE over the eight heads
    assert info["reference"]["relative_difference"] < 1e-4
    checks = info["reference"]["checks"]
    assert sorted(checks) == CHECKS and all(check["ok"] for check in checks.values()), checks
    assert "compared: check eva_output" in done.stderr and "compared: check adamw_update" in done.stderr
    assert "attention path: xla-reference" in open(
        next(os.path.join(base, f) for base, _, files in os.walk(scratch / "benchmark" / ".state" / "runs" / CELL / "pods")
             for f in files if f.endswith(".log"))).read()
    metrics = result["metrics"]
    for name in ("host_loop_pct.tok", "prep_wait_pct.tok", "starved_dispatch_pct.tok", "compiles_in_window.tok",
                 "task_gap_max_ms.tok", "lease_ms_task.tok", "mfu_pct.tok", "hbm_peak_reported_gib.tok",
                 "setup_master_s", "setup_init_state_s", "setup_compile_s", "setup_unattributed_s"):  # the setup_* joined in PR 39
        assert name in metrics, name
    assert "tokens_per_s_chip" not in metrics  # a traced run reports per-layer metrics only


@pytest.fixture(scope="module")
def checks_at_the_rehearsal_size():
    """The sizing tool's table (what the reference child reads, sound and
    under every control, judged by run.py's ``reference_problems`` against
    the configuration's limits) on one seeded minibatch at the rehearsal's
    sizes."""
    bench = resolve.Bench(ROOT)
    config = bench.config(CONFIG)
    with open(os.path.join(BENCH_DIR, "rehearsal", CELL + ".json")) as f:
        override = json.load(f)
    config["model_params"].update(override["model_params"])
    sizing = resolve.load_module(os.path.join(BENCH_DIR, "sizing", "evabyte_against_reference.py"))
    reference = resolve.load_module(bench.reference_path(CONFIG))
    return config, sizing.check_table(config, reference, 2, [3300000031], reference.CONTROLS)


def test_a_sound_system_passes_every_check(checks_at_the_rehearsal_size):
    _, table = checks_at_the_rehearsal_size
    (sound,) = table["sound"]
    assert sorted(sound["readings"]) == CHECKS and sound["correct"], sound["problems"]
    # the train step's own loss is the reference's: the step ran on the checks' weights
    assert sound["losses"]["train_step"] == pytest.approx(sound["losses"]["reference"], rel=1e-4)


@pytest.mark.parametrize("control", [
    "no_summaries", "one_window_early", "bfloat16_softmax", "bfloat16_logits", "bfloat16_residual", "all_bfloat16",
    "no_summary_gradients", "no_weight_decay", "state_unchanged"])
def test_a_control_reads_not_correct_by_the_checks_the_file_names(checks_at_the_rehearsal_size, control):
    """Through the same judge as a run (``reference_problems``): a control is
    not correct by exactly the kind of check the configuration's file says
    catches it; the one the file says no limit can catch (a bfloat16
    residual stream, four layers from the initial weights) passes here too."""
    config, table = checks_at_the_rehearsal_size
    (row,) = table[control]
    named = config["controls"][control]["caught_by"]
    over = sorted(re.match(r"check (\w+):", problem).group(1) for problem in row["problems"])
    assert row["correct"] == (not named), (row["problems"], named)
    assert set(over) <= set(named) and (not named or over), (over, named)
