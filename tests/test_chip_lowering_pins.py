"""The other half of ``tests/test_chip_lowering.py`` (its docstring says how
the two are parted and why): the cases that only LOWER for platform ``tpu``
and never compile (the sha256 pins of the cells' lowered steps, all lowered in
one child process; four cells' steps read as lowered text), and ``gpt2m_job``'s
whole step compiled for a described v5e, twice.  Every fixture, helper and pin
is that module's: nothing is defined twice.
"""

from __future__ import annotations

import collections
import json
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from test_chip_lowering import (  # noqa: F401  (the fixtures are asked for by name)
    FLASH_SHAPES,
    LOWERED_STEP_SHA256,
    V5E_BYTES_LIMIT,
    _abstract_scan_step,
    _flash_calls,
    _flash_loss,
    as_on_the_chip,
    compiled_kernel,
    olmoe_as_on_the_chip,
    path_lines,
    v5e_device,
    v5e_host,
)

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer, build_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_fwd_bwd_lowers_to_three_mosaic_calls(
    compiled_kernel, path_lines, shape
):
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lowered = (
        jax.jit(jax.value_and_grad(_flash_loss, argnums=(0, 1, 2)))
        .trace(arg, arg, arg)
        .lower(lowering_platforms=("tpu",))
    )
    # fwd, dq, dkv — compiled kernels, not the interpreter's XLA expansion.
    assert lowered.as_text().count("tpu_custom_call") == 3
    # ... announced as such, in the line benchmark/run.py and chip_smoke.py
    # match, with fewer key tiles visited than there are.
    (line,) = set(path_lines)
    assert re.search(r"attention path: ([\w-]+)", line).group(1) == "pallas-compiled"
    visited, total = map(int, re.search(r"key_tiles=(\d+)/(\d+)", line).groups())
    assert visited < total
    assert f"heads_per_block={128 // shape[3]})" in line
    # the grid holds the pairs the causal rule can hold and no step besides (PR 65): a head of one block its one
    # step, eight blocks a side the triangle's 36 pairs in 4 x 9 steps, four blocks 10 in 2 x 5
    blocks = fa._blocks(shape[1])[0]
    assert f" steps={blocks * (blocks + 1) // 2}/{blocks * (blocks + 1) // 2} key_tiles=" in line


def test_deepfm_ragged_step_lowers_with_ragged_all_to_all(devices):
    """The 4-device DeepFM step on the explicit ragged route lowers for TPU
    with the real collective (XLA:CPU refuses the op outright, so tier-1
    otherwise only ever sees ``ragged_emulated``)."""
    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=256, embedding_dim=8, hidden=(16,),
        compute_dtype="float32",
    )
    trainer = Trainer(
        spec,
        JobConfig(
            distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
            embedding_lookup_impl="ragged",
        ),
        create_mesh(devices, num_devices=4),
    )
    assert trainer.ctx.embedding_impl == "ragged"
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(spec.example_batch(32))
    step = trainer._structured(
        trainer._train_steps, build_train_step, batch,
        host_keys=(), variant_budget=1, **trainer._train_build_kwargs(),
    )
    text = (
        step.trace(state, batch, trainer._active_device())
        .lower(lowering_platforms=("tpu",))
        .as_text()
    )
    # vectors back, cotangents out; the ids go by all_gather (PR 55).
    assert text.count("ragged_all_to_all") == 2


def test_gpt2_medium_step_has_no_table_update_in_it(as_on_the_chip):
    """``gpt2_medium`` declares no table and never calls
    ``embedding_lookup``: its step, lowered for the chip at the real size,
    holds no Mosaic call (its attention takes the XLA reference path off the
    TPU, so any would be a sweep's), where DeepFM's, the control, holds the
    one under ``table_apply``.  (Scope names are not asked of the lowered
    text: inner jits cached by earlier tests of the process carry theirs.)"""
    def lowered_text(model_def, strategy, minibatch, **params):
        spec = load_model_spec("elasticdl_tpu.models", model_def, **params)
        mesh = create_mesh(jax.devices()[:1], num_devices=1)
        trainer = Trainer(spec, JobConfig(distribution_strategy=strategy), mesh)
        step, args = _abstract_scan_step(trainer, mesh, minibatch=minibatch, steps=2)
        return step.trace(*args).lower(lowering_platforms=("tpu",)).as_text()

    text = lowered_text(
        "transformer_lm.model_spec", DistributionStrategy.ALLREDUCE, 16,
        vocab=50257, dim=1024, n_heads=16, n_layers=24, seq_len=1024,
        max_seq=1024, remat=True, parallelism="sequence",
    )
    assert "tpu_custom_call" not in text
    control = lowered_text(
        "deepfm.model_spec", DistributionStrategy.PARAMETER_SERVER, 64,
        buckets_per_feature=786432, embedding_dim=10, hidden=(400, 400, 400),
        host_tier=False,
    )
    assert control.count("tpu_custom_call") == 1


@pytest.mark.parametrize("bytes_limit", V5E_BYTES_LIMIT, ids=["v5e_budget", "budget_0"])
def test_gpt2_medium_step_compiles_for_v5e_with_no_layout_glue_at_flash(
    v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch, bytes_limit
):
    """``gpt2m_job``'s real step (24 layers, 16 sequences of 1024, remat)
    compiled for a described v5e: the flash kernels' operands ARE the
    model's ``[B, L, H*D]`` arrays, two 64-wide heads to a 128-lane block.
    Nothing pads a head to 128 lanes, and no transpose, copy or relayout of
    an array the size of q stands between the projections and a kernel (the
    parent had some thirty such passes a layer: PERF.md, PR 31).  With the
    budget the trainer resolves from a v5e's memory every layer keeps its
    flash output and logsumexp (the forward ONCE a layer) and the step stays
    under the trainer's line; with none (budget 0) it is the program it
    was: the forward twice a layer."""
    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: bytes_limit)
    layers = 24
    spec = load_model_spec(
        "elasticdl_tpu.models", "transformer_lm.model_spec", vocab=50257,
        dim=1024, n_heads=16, n_layers=layers, seq_len=1024, max_seq=1024,
        remat=True, parallelism="sequence",
    )
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(
        spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh
    )
    step, args = _abstract_scan_step(trainer, mesh, minibatch=16, steps=2)
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    text = compiled.as_text()
    (line,) = set(path_lines)
    assert "attention path: pallas-compiled" in line
    assert line.endswith("heads_per_block=2)")
    # forward, its re-run under remat unless the layer keeps its output, dQ,
    # dK + dV, every layer; each over the model's own [B, L, H * D]
    calls = _flash_calls(text)
    plan = trainer.keep_plan
    if bytes_limit is None:
        assert plan is None and len(calls) == 4 * layers
    else:
        assert len(calls) == 3 * layers
        assert 0.5 * plan.tagged < plan.kept <= plan.budget < plan.tagged
        assert 12.5 * 2**30 < trainer_lib.compiled_bytes(compiled) < plan.line == bytes_limit - trainer_lib.REMAT_HEADROOM
        # the estimate of the step with nothing kept (the compiler's own account: 9.795 GiB)
        assert abs(plan.estimate - 9.795 * 2**30) < 0.15 * 2**30
    assert all("bf16[16,1024,1024]" in c and "bf16[256," not in c for c in calls)
    # q is 16 x 1024 x 16 x 64 elements; padded to 128 lanes, twice that
    q_sized = {16 * 1024 * 16 * 64, 16 * 1024 * 16 * 128}
    glue = [
        found.group(0) for found in re.finditer(
            r"= bf16\[([\d,]+)\]\S* (pad|transpose|copy|reshape)\(", text
        )
        if math.prod(map(int, found.group(1).split(","))) in q_sized
    ]
    assert not glue, glue[:5]


# ------------------------------------------------ four steps read as lowered text
# The whole-step compiles of these four cells are ``slow`` (``tests/test_chip_lowering.py``: 60 to 184 s each under the
# driver's command), and the driver compiles each on a real v5e in every PR's check of its cell.  What those cases read
# off the COMPILED program beyond "it compiles and fits" is read here off the step LOWERED for the chip (StableHLO with
# its locations; seconds, not minutes): the scopes the metrics read, the Mosaic kernels by name with the operand lists
# the roofline entries tell them apart by, no score matrix, no scatter of token rows, the keep plan, the state's bytes.
# A lowered text names an op by the stack INSIDE the function that holds it and holds a layer's function once however
# often it is called: "this kernel under that scope" and counts a layer are the compiled cases'.


def _lowered_for_v5e(trainer, mesh, **shape):
    """(the step lowered for the chip as text with its locations, the abstract state's bytes)."""
    step, args = _abstract_scan_step(trainer, mesh, **shape)
    state_bytes = sum(math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in jax.tree.leaves(args[0]))
    return step.trace(*args).lower(lowering_platforms=("tpu",)).as_text(debug_info=True), state_bytes


def _mosaic_kernels(text: str):
    """``{(a Mosaic call's kernel, (its bfloat16 operands, its float32 operands)): calls}`` of a lowered text."""
    kernels = collections.Counter()
    for line in text.splitlines():
        if "stablehlo.custom_call @tpu_custom_call" in line:
            operands = line[line.rindex(" : (") + 4:line.rindex(") -> ")]
            kernels[re.search(r'kernel_name = "([^"]*)"', line).group(1), (operands.count("xbf16>"), operands.count("xf32>"))] += 1
    return kernels


def _name_stacks(text: str) -> dict:
    """``{#locN: the name stack jax gave the ops at that location}`` of a lowered text."""
    return dict(re.findall(r'^(#loc\d+) = loc\("([^"]*)"', text, re.M))


def _scopes(text: str) -> set:
    """Every word of every name stack of a lowered text's locations."""
    return {word for stack in _name_stacks(text).values() for word in re.findall(r"\w+", stack)}


def _row_scatters(text: str):
    """``{result type: scatters}`` of the scatters whose result has more than one dimension."""
    results = re.findall(r'"stablehlo\.scatter"\(.*?\n\s*\}\) : \([^\n]*?\) -> (tensor<[^>]*>)', text, re.S)
    return collections.Counter(result for result in results if result.count("x") > 1)


def _moe_lm_cell_lowered(config: str, traffic: str, device):
    """(model parameters, traffic, the trainer, the lowered text, the state's bytes) of a ``moe_lm`` cell's step."""
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(ROOT, "benchmark", "traffic", traffic + ".json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    text, state_bytes = _lowered_for_v5e(trainer, mesh, minibatch=traffic["minibatch_size"], steps=traffic["minibatches_per_task"])
    return params, traffic, trainer, text, state_bytes


#: the grouped matmuls (megablox ``gmm`` / ``tgmm``: "kernel") and the token sums' sweeps, every ``moe_lm`` cell's: since PR
#: 68 both read the rows three times over (a tile's first two chunks as blocks, the rest from HBM) in the experts' bfloat16,
#: behind the prefetched int32 offsets and each time behind the chunk's int32 ids — "_merge_tile" the weighted sum into the
#: tokens (the weights' bits ride under the ids; float32 out), "_merge_tile_rounded" the gather's transpose (bfloat16 out).
#: No float32 operand: ``(0, 3)`` was the one call they both were.  Neither STARTS with a bfloat16 operand, which is what the
#: flash rooflines' patterns (``benchmark/metrics/flash_roofline_pct.*.json``) tell the attention kernels by.
GROUPED = {("kernel", (2, 0)), ("_merge_tile", (3, 0)), ("_merge_tile_rounded", (3, 0))}
#: the three flash kernels at the operand lists ``flash_roofline_pct.tok`` / ``.mla`` (a rotary part: two more bfloat16 each) read
FLASH_TOK = {("_fwd_kernel", (3, 0)), ("_dq_kernel", (4, 1)), ("_dkv_kernel", (4, 2))}
FLASH_MLA = {("_fwd_kernel", (5, 0)), ("_dq_kernel", (6, 1)), ("_dkv_kernel", (6, 2))}


def _loss_and_embedding_scatters(batch: int, length: int, vocab: int, width: int):
    """The row-shaped scatters every LM step lowers to: the label's pick in the cross-entropy (forward and its
    transpose) and the token embedding's gradient, the ONE scatter of rows a compiled step keeps."""
    return {f"tensor<{batch}x{length}x1xf32>": 1, f"tensor<{batch}x{length}x{vocab}xf32>": 1, f"tensor<{vocab}x{width}xf32>": 1}


def test_deepfm_job_step_lowers_for_v5e_with_the_sweep_applying_the_table_update(v5e_device, as_on_the_chip):
    """``deepfm_criteo`` at its real size, lowered for one described v5e chip (its compile, ``slow``, weighs the memory):
    NO scatter at all (the table gradient is the merge sweep's), ONE Mosaic call, under ``table_apply``, whose three
    table-shaped results are aliased onto three of its operands (table, mu, nu, in place: no gradient buffer), and
    the sort under ``table_grad``."""
    from elasticdl_tpu.ops import embedding

    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec", buckets_per_feature=786432, embedding_dim=10, hidden=(400, 400, 400), host_tier=False,
    )
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER), mesh)
    text, _ = _lowered_for_v5e(trainer, mesh)
    rows = 26 * 786432 // 8
    assert rows == 2555904 >= embedding.SWEEP_MIN_ROWS
    assert "stablehlo.scatter" not in text
    (call,) = [line for line in text.splitlines() if "stablehlo.custom_call @tpu_custom_call" in line]
    table = f"tensor<{rows}x128xf32>"
    assert call.endswith(f"-> ({table}, {table}, {table}) " + call[call.rindex("loc("):]) and 'kernel_name = "_apply_kernel"' in call
    aliased = re.findall(r"output_tuple_indices = \[(\d)\], operand_index = (\d+)", call)
    operands = call[call.rindex(" : (") + 4:call.rindex(") -> ")].split(", ")
    assert [out for out, _ in aliased] == ["0", "1", "2"] and len({at for _, at in aliased}) == 3
    assert all(operands[int(at)] == table for _, at in aliased) and operands.count(table) == 3
    stacks = _name_stacks(text)
    under = stacks[call[call.rindex("loc(") + 4:-1]]
    assert re.search(r"\btable_apply\b", under) and "route_" not in under, under
    assert any(re.search(r"\btable_grad\b.*\bsort\b", stack) for stack in stacks.values())


def test_kanana2_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix(v5e_device, olmoe_as_on_the_chip, path_lines):
    """``kanana2_job``'s real step lowered for a described v5e (its compile is ``slow``): the eight device scopes the
    ``.mla`` metrics read; the flash kernels with a rotary part at the operand lists ``flash_roofline_pct.mla`` reads
    and the experts' grouped matmuls, and no other Mosaic kernel; the grouped matmuls read ``C`` = 18,432 rows and
    the 16 held experts' weights and no array of all the slots, or of the other 79,872, exists; no [*, 8192, 8192]
    array; and the only scatters of rows are the loss's label pick and the token embedding's gradient."""
    from elasticdl_tpu.ops import moe

    params, traffic, _, text, _ = _moe_lm_cell_lowered("kanana2_30b_a3b_ep8_l5", "job_seq8k", v5e_device)
    assert {"mla_proj", "flash_attn", "moe_router", "moe_dispatch", "moe_experts", "moe_shared", "moe_combine", "lm_head"} <= _scopes(text)
    assert set(_mosaic_kernels(text)) == FLASH_MLA | GROUPED
    assert any(line.endswith("heads_per_block=1 rotary=64)") for line in path_lines), path_lines
    assert "8192x8192" not in text
    slots = traffic["minibatch_size"] * params["seq_len"] * params["num_experts_per_tok"]
    bound = moe.held_rows_bound(slots, params["experts_held"], params["num_experts"])
    assert (slots, bound) == (98304, 18432)
    grouped = [line for line in text.splitlines() if 'kernel_name = "kernel"' in line]
    assert grouped and all(f"tensor<{bound}x" in line and "tensor<16x" in line for line in grouped), grouped[:1]
    for rows in (f"<{slots}x2048x", f"<{slots}x768x", f"<{slots - bound}x", "<16384x6x2048x"):
        assert rows not in text, rows
    # the token sums read the experts' bfloat16 rows themselves (PR 68, in place of a counter): no float32 copy of the
    # row buffer is permuted into token order (a gather's operand) or handed to a kernel, and each sweep's first operands
    # are its int32 offsets and ids
    moved = [line for line in text.splitlines() if '"stablehlo.gather"' in line or "stablehlo.custom_call @tpu_custom_call" in line]
    operands = [line[line.rindex(" : (") + 4:line.rindex(") -> ")] for line in moved]
    assert len(operands) > 12 and not any(f"tensor<{bound}x2048xf32>" in given for given in operands)
    sweeps = [given for line, given in zip(moved, operands) if 'kernel_name = "_merge_tile' in line]
    assert sweeps and all(re.match(rf"tensor<\d+xi32>, tensor<\d+x[12]x128xi32>, tensor<{bound}x2048xbf16>, ", given) for given in sweeps), sweeps
    assert _row_scatters(text) == _loss_and_embedding_scatters(traffic["minibatch_size"], params["seq_len"], params["vocab_size"], params["hidden_size"])


def test_nemotron3_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix(v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch):
    """``nemotron3_job``'s real step lowered for a described v5e with the byte budget the trainer resolves from a
    v5e's memory (its compile is ``slow``): 773.6 M parameters and their moments are the state, the layers keep every
    save site under the trainer's line; the device scopes the ``.ssm`` / ``.tok`` / ``.mla`` metrics read; the flash
    kernels at the operand lists ``flash_roofline_pct.tok`` reads, the three kernels of ``ops/ssm_kernels.py``, the
    grouped matmuls, and no other Mosaic kernel; no [*, 8192, 8192] score matrix; no scatter of token rows."""
    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    params, traffic, trainer, text, state_bytes = _moe_lm_cell_lowered("nemotron3_super_tp4_ep64_l11", "job_seq8k_x1", v5e_device)
    plan = trainer.keep_plan
    assert plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM and plan.kept == plan.tagged <= plan.budget and plan.tagged > 2**30
    assert abs(state_bytes - 12 * 773582304) < 2**20 and params["remat"]  # parameters and two moments
    scopes = {"ssm_proj", "ssm_conv", "ssm_scan", "ssm_norm", "attn_proj", "moe_latent", "moe_shared", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "mlp", "flash_attn", "lm_head"}
    assert scopes <= _scopes(text)
    kernels = _mosaic_kernels(text)
    assert set(kernels) == FLASH_TOK | GROUPED | {("ssm_chunk_states", (2, 1)), ("ssm_chunk_outputs", (3, 4)), ("ssm_chunk_grads", (4, 5))}
    # a layer: the chunks' end states and what y asks of the start states; the outputs; the gradients
    m_layers = params["hybrid_override_pattern"].count("M")
    assert [kernels[name, lists] for name, lists in (("ssm_chunk_states", (2, 1)), ("ssm_chunk_outputs", (3, 4)), ("ssm_chunk_grads", (4, 5)))] == [2 * m_layers, m_layers, m_layers]
    flash = [line for line in text.splitlines() if re.search(r'kernel_name = "_(fwd|dq|dkv)_kernel"', line)]
    assert len(flash) == 3 and all("tensor<1x8192x1024xbf16>" in line for line in flash)  # 8 query heads of 128; K and V repeated to as many
    assert "8192x8192" not in text
    assert any("attention path: pallas-compiled" in line and "ssm_scan groups=2 state=128 chunk=128" in line for line in path_lines), path_lines
    assert any("attention path: pallas-compiled" in line and "heads_per_block=1" in line for line in path_lines), path_lines
    assert _row_scatters(text) == _loss_and_embedding_scatters(traffic["minibatch_size"], params["seq_len"], params["vocab_size"], params["hidden_size"])


def test_kimi_linear_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix(v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch):
    """``kimi_linear_job``'s real step lowered for a described v5e with the byte budget the trainer resolves from a
    v5e's memory (its compile is ``slow``): 602.4 M parameters and their moments are the state, the layers keep every
    save site under the trainer's line; the device scopes the ``.kda`` / ``.tok`` / ``.mla`` metrics read and none of
    the state-space family's; the flash kernels at the operand lists ``flash_roofline_pct.mla`` reads, the
    same-sub-block pair of ``ops/delta_rule_kernels.py``, the convolution chains' pair of
    ``ops/short_conv_kernels.py`` (a chain's operand and result bfloat16 [1, 8192, 4096]), the grouped matmuls, and
    no other Mosaic kernel and no custom call that is not Mosaic's; no triangular solve of XLA's; a GROUP of 8
    chunks at a time and no [.., 16, 16, 128] array of a sub-block's differences; no [*, 8192, 8192] score matrix;
    and the only scatters of rows besides the loss's and the embedding's are the masks' own under ``kda_mask``."""
    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    params, traffic, trainer, text, state_bytes = _moe_lm_cell_lowered("kimi_linear_48b_a3b_ep32_l5", "job_seq8k_x1_v20480", v5e_device)
    plan = trainer.keep_plan
    assert plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM and plan.kept == plan.tagged <= plan.budget and plan.tagged > 2 * 2**30
    assert abs(state_bytes - 12 * 602434432) < 2**20 and params["remat"]  # parameters and two moments
    scopes = {"kda_proj", "kda_glue", "kda_conv", "kda_scan", "kda_mask", "mla_proj", "flash_attn", "moe_shared", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "mlp", "lm_head"}
    assert scopes <= _scopes(text) and not {"ssm_proj", "ssm_scan", "ssm_norm", "ssm_conv"} & _scopes(text)
    kernels = _mosaic_kernels(text)
    assert set(kernels) == FLASH_MLA | GROUPED | {
        ("kda_sub_block_masks", (2, 1)), ("kda_sub_block_mask_grads", (2, 3)), ("kda_conv_chain", (2, 1)), ("kda_conv_chain_grads", (5, 1))}
    # the three chains of each of the four KDA layers: the forward in the layer's forward and in its rematerialised repeat, the gradient once
    assert (kernels["kda_conv_chain", (2, 1)], kernels["kda_conv_chain_grads", (5, 1)]) == (3 * 4 * 2, 3 * 4)
    chains = [line for line in text.splitlines() if 'kernel_name = "kda_conv_chain"' in line]
    assert all(line[line.rindex(") -> "):].startswith(") -> tensor<1x8192x4096xbf16>") for line in chains)
    assert set(re.findall(r"custom_call @(\w+)", text)) == {"tpu_custom_call"} and "triangular_solve" not in text
    assert "8192x8192" not in text and "8195x4096x" not in text
    assert re.search(r"tensor<1x8x32x4x64x128xf32>", text) and not re.search(r"x16x16x128xf32>|<1x128x32x4x(16x16|64)x128xf32>", text)
    assert any("attention path: pallas-compiled" in line and "kda_mask chunk=64" in line for line in path_lines), path_lines
    for norm in (128, None):
        assert any("attention path: pallas-compiled" in line and f"kda_conv taps=4 norm={norm})" in line for line in path_lines), path_lines
    assert any("attention path: pallas-compiled" in line and "rotary=64" in line for line in path_lines), path_lines
    rows = _loss_and_embedding_scatters(traffic["minibatch_size"], params["seq_len"], params["vocab_size"], params["hidden_size"])
    assert _row_scatters(text) == {**rows, "tensor<1x8x32x64x128xf32>": 2}


_LOWERED_STEPS = """
import hashlib, json, sys, jax
sys.path.insert(0, 'tests')
from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer
import test_chip_lowering as T
shas = {}
for config, traffic in CELLS:
    c = json.load(open(f'benchmark/configs/{config}.json'))
    t = json.load(open(f'benchmark/traffic/{traffic}.json'))
    spec = load_model_spec('elasticdl_tpu.models', c['model_def'], **c['model_params'])
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    step, args = T._abstract_scan_step(trainer, mesh, minibatch=t['minibatch_size'], steps=t['minibatches_per_task'])
    text = step.trace(*args).lower(lowering_platforms=('tpu',)).as_text()
    shas[config + ' ' + traffic] = hashlib.sha256(text.encode()).hexdigest()
    jax.clear_caches()  # the next cell's text must not carry the names of inner jits this one traced
print('SHAS', json.dumps(shas))
"""


@pytest.fixture(scope="module")
def lowered_step_shas():
    """Every pinned cell's step lowered in ONE fresh process (inner jits cached by earlier tests of this one would carry
    other names into the text), the caches dropped between cells: the eight digests are the ones eight processes gave,
    and the process starts once (PR 66: 94 s of children in the parent's sitting, some 30 s of lowering in them)."""
    import subprocess
    import sys

    script = f"CELLS = {sorted(LOWERED_STEP_SHA256)!r}\n" + _LOWERED_STEPS
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, PYTHONPATH=ROOT),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    (shas,) = re.findall(r"^SHAS (.*)$", done.stdout, re.M)
    return json.loads(shas)


@pytest.mark.parametrize("config,traffic", sorted(LOWERED_STEP_SHA256))
def test_the_moe_lm_cells_lowered_steps_are_the_pinned_programs(lowered_step_shas, config, traffic):
    import hashlib

    sha = lowered_step_shas[f"{config} {traffic}"]
    assert len(hashlib.sha256(b"").hexdigest()) == len(sha)
    assert sha == LOWERED_STEP_SHA256[config, traffic]
