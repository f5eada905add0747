"""What the CPU can say about the chip-only code paths: they LOWER for
platform ``tpu`` as the real ops (``tpu_custom_call``, ``ragged_all_to_all``),
and — where libtpu can describe a v5e topology without a chip — they COMPILE
for it, Mosaic included.  Nothing here runs on a TPU; ``chip_smoke.py`` does.

ONE of two files (PR 66): a file's cases run one after another on one xdist
worker, and all of them together were the run's longest serial block.  HERE:
the fixtures, helpers and pins both files read (the pins' child script and two
cells' cases under ``tests/benchmark/`` import this module by name) and the
cases that COMPILE: one kernel pair or a small program, and a cell's whole
step.  In ``tests/test_chip_lowering_pins.py``: the cases that only lower
(the pins and the four steps read as lowered text among them) and
``gpt2m_job``'s two compiles, which weigh as much as the rest of this file.
``tests/conftest.py`` (``_COLLECTED_FIRST``) opens a different worker's first
deal with each.  The four whole-step compiles over a minute each under the
driver's command (``kimi_linear`` 184 s, ``kanana2`` 127, ``nemotron3`` 119,
``deepfm_job`` 60: PR 66's sitting) are marked ``slow``: the driver compiles
each of those steps on a real v5e in every PR's check of its cell, and what
their cases assert beyond "it compiles and fits" is read off the LOWERED step
by ``test_*_step_lowers_for_v5e_*`` in the other file.
"""

from __future__ import annotations

import os
import re

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import embedding, table_grad
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer, build_train_step

# The MFU-bench shape, the kernel's documented bound (_MAX_L), and
# ``olmoe_job``'s (one 128-wide head a lane group, four blocks a head).
FLASH_SHAPES = [(16, 1024, 12, 64), (1, 8192, 12, 64), (4, 4096, 16, 128)]


def _flash_loss(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, True).astype(jnp.float32) ** 2)


@pytest.fixture
def path_lines(monkeypatch):
    """The ``attention path:`` lines a trace announces (benchmark/run.py
    and chip_smoke.py match them in the worker's log)."""
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    return lines


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Interpret mode off, as on the chip (off-TPU the kernel otherwise
    silently lowers to the Pallas interpreter)."""
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)




@pytest.fixture(scope="module")
def v5e_host():
    """The four devices of a described (not attached) v5e 2x2 host: libtpu
    compiles for them ahead of time.  Skips where the installed libtpu
    cannot."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu"
        )
    except Exception as e:  # noqa: BLE001 — any plugin failure means "cannot"
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    return list(topo.devices)


@pytest.fixture(scope="module")
def v5e_device(v5e_host):
    return v5e_host[0]


def test_the_harness_compile_level_does_not_reach_a_tpu_compile(v5e_device):
    """``tests/conftest.py`` has XLA:CPU generate its code at level 1
    (``--xla_backend_optimization_level``, in ``XLA_FLAGS``), and that option
    rides EVERY compile's debug options, a described TPU's too.  libtpu does
    not read it: asked for the default 3, it gives the same program with the
    same memory, so this file's cases hold the steps the chip runs."""
    assert "--xla_backend_optimization_level=1" in os.environ["XLA_FLAGS"]

    def step(x, w_in, w_out):
        def loss(w_in, w_out):
            return jnp.mean((jax.nn.softmax(jax.nn.gelu(x @ w_in), axis=-1) @ w_out - x) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))(w_in, w_out)

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=jax.sharding.SingleDeviceSharding(v5e_device))

    lowered = jax.jit(step).trace(arg(1024, 512), arg(512, 2048), arg(2048, 512)).lower(lowering_platforms=("tpu",))
    the_runs, the_defaults = lowered.compile(), lowered.compile(compiler_options={"xla_backend_optimization_level": 3})
    assert "fusion" in the_runs.as_text() and the_runs.as_text() == the_defaults.as_text()
    assert the_runs.memory_analysis().temp_size_in_bytes == the_defaults.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_fwd_bwd_compiles_for_v5e(compiled_kernel, v5e_device, shape):
    """Mosaic accepts the kernels at both bounds under the default 16 MiB
    scoped-VMEM limit (no compiler_params are set)."""
    arg = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16,
        sharding=jax.sharding.SingleDeviceSharding(v5e_device),
    )
    compiled = (
        jax.jit(jax.value_and_grad(_flash_loss, argnums=(0, 1, 2)))
        .trace(arg, arg, arg)
        .lower(lowering_platforms=("tpu",))
        .compile()
    )
    # The kernels are unnamed, so `flash_roofline_pct.tok` tells their trace
    # events apart by operand list: forward 3 bf16, dQ 4 bf16 + 1 f32,
    # dK/dV 4 bf16 + 2 f32.  A kernel edit that adds or drops an operand
    # would blind the metric without failing anything else.
    calls = re.findall(
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes",
        compiled.as_text(),
    )
    signatures = sorted((ops.count("bf16["), ops.count("f32[")) for ops in calls)
    assert signatures == [(3, 0), (4, 1), (4, 2)]


def test_the_gated_convolution_pair_compiles_for_v5e_at_the_cells_shape_and_no_flash_entry_reads_it(v5e_device, monkeypatch):
    """``lfm2_job``'s operator (``ops/short_conv.gated_conv``) at the cell's
    shape [4, 8192, 2048], 3 taps, forward and gradient: Mosaic accepts the
    kernel pair (the scratch of a 512 x 512 block with its halo under the
    kernels' 32 MiB limit), each ONE call under the scope ``gated_conv``; and
    their operand lists (5 bfloat16 + the taps; 8 bfloat16 + the taps) are no
    flash kernel's, so neither ``flash_roofline_pct`` entry counts them."""
    import json

    from elasticdl_tpu.ops import short_conv

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    here = jax.sharding.SingleDeviceSharding(v5e_device)
    arg = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16, sharding=here)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32, sharding=here)
    assert short_conv.gated_path(arg, arg, arg, taps) == ("pallas-compiled", "")

    def loss(b, c, z, w):
        y, by_kernels = short_conv.gated_conv(b, c, z, w)
        assert by_kernels
        return jnp.sum(y.astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3))).trace(arg, arg, arg, taps).lower(lowering_platforms=("tpu",)).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all(re.search(r'op_name="[^"]*\bgated_conv\b', call) for call in calls)
    operands = [re.search(r"operand_layout_constraints=\{(.*?)\}, frontend_attributes", call).group(1) for call in calls]
    assert sorted((ops.count("bf16["), ops.count("f32[")) for ops in operands) == [(5, 1), (8, 1)]
    # a trace event names a call by its operands' shapes with their layouts, each ahead of its name
    events = [
        "custom-call(" + ", ".join(f"{shape} %p.{i}" for i, shape in enumerate(re.findall(r"\w+\[[\d,]*\]\{[^}]*\}", ops)))
        + '), custom_call_target="tpu_custom_call"' for ops in operands
    ]
    assert all(event.count(" %p.") in (6, 9) for event in events)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for metric in ("flash_roofline_pct.tok", "flash_roofline_pct.mla"):
        with open(os.path.join(root, "benchmark", "metrics", metric + ".json")) as f:
            for kernel in json.load(f)["params"]["kernels"]:
                assert not any(re.search(kernel["pattern"], event) for event in events), (metric, kernel["what"])




@pytest.fixture
def as_on_the_chip(monkeypatch):
    """What ``ops/embedding.py`` reads from the backend, set as the chip
    sets it (the devices here are described, the backend is the CPU): the
    platform is a TPU, and the sweep is compiled, not interpreted."""
    monkeypatch.setattr(embedding, "_on_tpu", lambda: True)
    monkeypatch.setattr(table_grad, "_use_interpret", lambda: False)


def _abstract_scan_step(trainer, mesh, minibatch=8192, steps=8):
    """(the scanned step, its abstract (state, batches, active)) on ``mesh``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    replicated = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=replicated)
    state = jax.tree.map(
        lambda leaf, spec_: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, spec_)
        ),
        jax.eval_shape(trainer._init_program(key), key), trainer.state_specs(),
    )
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            (steps,) + x.shape, x.dtype,
            sharding=NamedSharding(mesh, P(None, *trainer._batch_spec_for(x))),
        ),
        trainer.spec.example_batch(minibatch),
    )
    step = trainer._scanned(
        trainer._train_steps, build_train_step, stacked, host_keys=(),
        variant_budget=1, keep_plan=trainer._new_keep_plan(), **trainer._train_build_kwargs(),
    )
    active = jax.ShapeDtypeStruct(
        (trainer.num_contributors(),), jnp.float32, sharding=replicated
    )
    return step, (state, stacked, active)


def _assert_the_sweep_applies_the_table_update(text: str, rows: int):
    """In a compiled step: no scatter into a table-shaped buffer, and no
    gradient buffer either — ONE Mosaic call, under the scope ``table_apply``
    and outside every ``route_*`` one, whose three table-shaped outputs are
    aliased onto three of its operands (table, mu, nu, in place); no
    table-shaped copy around it (XLA honours the aliases inside the scan's
    carry); the sort still under ``table_grad``; and what is left of
    ``multiply_add_fusion`` (the name ``optimizer_ms_step.ex4`` reads) is
    the other leaves' Adam, with no table-shaped operand."""
    table = rf"f32\[{rows},128\]\S*"
    assert not re.findall(rf"{table} scatter\(", text)
    assert not re.findall(rf"{table} copy\(", text)
    mosaic = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(mosaic) == 1, mosaic
    (call,) = mosaic
    assert re.search(rf"= \(({table}, ){{2}}{table}\) custom-call\(", call), call[:300]
    aliases = re.search(
        r"output_to_operand_aliasing=\{\{0\}: \((\d+), \{\}\), "
        r"\{1\}: \((\d+), \{\}\), \{2\}: \((\d+), \{\}\)\}", call,
    )
    assert aliases and len(set(aliases.groups())) == 3, call[-600:]
    op_name = re.search(r'op_name="([^"]*)"', call).group(1)
    assert re.search(r"\btable_apply\b", op_name) and "route_" not in op_name, op_name
    assert re.search(r'op_name="[^"]*\btable_grad\b[^"]*sort', text)
    fusions = re.findall(r"%multiply_add_fusion[.\d]* = .* calls=(%[\w.]+)", text)
    assert fusions
    for computation in fusions:
        (header,) = re.findall(rf"^{re.escape(computation)} \(.*$", text, re.M)
        assert f"f32[{rows},128]" not in header, header[:300]


@pytest.mark.slow  # 60 s under the driver's command (PR 66); its lowered twin, which runs: ``test_deepfm_job_step_lowers_for_v5e_with_the_sweep_applying_the_table_update``
def test_deepfm_job_step_builds_its_table_gradient_by_the_sweep(
    v5e_device, as_on_the_chip
):
    """``deepfm_criteo`` at its real size on one described v5e chip: what
    ``table_grad_ms_step.ex`` and ``table_apply_ms_step.ex`` match is there,
    the scatter-add is not, and the step has no table-sized temporary: the
    gradient buffer (1.31 GB, PR 27) is never made."""
    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=786432, embedding_dim=10,
        hidden=(400, 400, 400), host_tier=False,
    )
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(
        spec,
        JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER),
        mesh,
    )
    step, args = _abstract_scan_step(trainer, mesh)
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    rows = 26 * 786432 // 8
    assert rows == 2555904 >= embedding.SWEEP_MIN_ROWS
    buffer = rows * 128 * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= 3 * buffer
    assert memory.temp_size_in_bytes < 0.15 * buffer
    _assert_the_sweep_applies_the_table_update(compiled.as_text(), rows)


# deepfm_criteo_tb_x4 (benchmark/configs): 163.6 M rows over four chips.
X4_BUCKETS = 6291456
X4_ROUTE_SCOPES = (
    "route_plan", "route_ids", "route_gather", "route_vectors", "route_unsort",
    "route_bwd_sort", "route_bwd_vectors", "route_bwd_scatter",
)


def test_x4_init_and_step_compile_for_a_v5e_host(v5e_host, as_on_the_chip):
    """The configuration that only four chips can hold, at its real size,
    through the chip's own compiler: the jitted init bears every output
    sharded with a per-device temporary far under a shard (the eager init
    needed 8 x the table on device 0), the step holds the real
    ragged-all-to-all twice (vectors back, cotangents out: the ids reach
    their owners by an all-gather under ``route_ids`` and no id is laid out
    as a 512-byte row, PR 55) and fits a chip, and what the
    ``*_ms_step.ex4`` metrics match in a device trace is there: the route's
    named scopes, and the shard's dense Adam update applied by the merge
    sweep itself under ``table_apply``, in place: no gradient buffer."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=X4_BUCKETS, embedding_dim=10,
        hidden=(400, 400, 400), host_tier=False,
    )
    mesh = create_mesh(v5e_host, num_devices=4)
    trainer = Trainer(
        spec,
        JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER),
        mesh,
    )
    assert trainer.ctx.embedding_impl == "ragged"  # what ``auto`` means on 4 TPU chips
    replicated = NamedSharding(mesh, P())
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=replicated)
    init = trainer._init_program(key)
    compiled = init.trace(key).lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    rows, width = 26 * X4_BUCKETS // 8, 128
    shard = rows * width * 4 // 4
    assert shard == 2496 * 2**20  # 2.44 GiB of rows a chip
    assert 3 * shard <= memory.output_size_in_bytes < 3 * shard + 2**24
    assert memory.temp_size_in_bytes < 1.5 * shard
    assert memory.output_size_in_bytes + memory.temp_size_in_bytes < 12 * 2**30

    step, args = _abstract_scan_step(trainer, mesh)
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    memory = compiled.memory_analysis()
    # state (aliased in and out) + the step's temporaries, none of them a
    # shard's size: three shards and a tenth of one.
    assert memory.alias_size_in_bytes >= 3 * shard
    assert memory.temp_size_in_bytes < 0.15 * shard
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 8 * 2**30
    text = compiled.as_text()
    assert len(re.findall(r" ragged-all-to-all\(", text)) == 2
    assert "s32[212992,1,128]" not in text
    assert re.search(r" all-gather(-start)?\([^\n]*op_name=\"[^\"]*\broute_ids\b", text)
    for scope in X4_ROUTE_SCOPES:
        assert re.search(rf"op_name=\"[^\"]*\b{scope}\b", text), scope
    _assert_the_sweep_applies_the_table_update(text, rows // 4)




# --------------------------------------------------------------- olmoe_job


@pytest.fixture
def olmoe_as_on_the_chip(monkeypatch):
    """The backend reads ``tpu`` (ops/ring_attention.py picks the flash
    kernels by it, ops/moe.py and ops/flash_attention.py compile their
    kernels instead of interpreting them); the devices are described."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _flash_calls(text: str):
    return [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and re.search(r'op_name="[^"]*\bflash_attn\b', line)
    ]


#: A v5e chip's memory as the trainer reads it (``memory_stats()["bytes_limit"]``
#: reads 15.748 GiB on the chip: my chip run, PR 38), or None: a backend that
#: reports none (every CPU program), where rematerialised blocks keep nothing.
V5E_BYTES_LIMIT = [int(15.75 * 2**30), None]




def test_olmoe_step_compiles_for_v5e_with_its_scopes_and_no_row_scatter(
    v5e_device, olmoe_as_on_the_chip, path_lines
):
    """``olmoe_job``'s real step (OLMoE's widths, one layer, 4 sequences of
    4096, two steps a dispatch) compiled for a described v5e: it fits the
    chip; the five device scopes the ``.moe`` metrics read are there; the
    attention is the three flash kernels at L = 4096, D = 128 and the
    experts are nine grouped matmuls; under ``moe_dispatch`` and
    ``moe_combine`` nothing scatters rows (the row movement is gathers,
    both ways, forward and backward)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "olmoe_1b_7b_l1.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq4k.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(
        spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh
    )
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"],
        steps=traffic["minibatches_per_task"],
    )
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    )
    # mostly full, and inside the chip's 15.75 GiB
    assert 11 * 2**30 < total < 15.5 * 2**30, total / 2**30
    text = compiled.as_text()
    for scope in ("moe_router", "moe_dispatch", "moe_experts", "moe_combine", "lm_head"):
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    calls = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    flash = [c for c in calls if "moe_experts" not in c]
    assert flash == _flash_calls(text)
    assert any(line.endswith("heads_per_block=1)") for line in path_lines), path_lines
    grouped = [c for c in calls if re.search(r'op_name="[^"]*\bmoe_experts\b', c)]
    # forward, dQ, dK+dV: every one over the model's own [B, 4096, H * 128]
    assert len(flash) == 3 and all("bf16[4,4096,2048]" in c for c in flash), flash
    # three projections x (forward, dx: gmm; dw: tgmm)
    assert len(grouped) == 9
    assert sum("jit(tgmm)" in c for c in grouped) == 3
    assert all("bf16[65536," in c or "bf16[64," in c for c in grouped)
    scatters = [line for line in text.splitlines() if re.search(r" scatter\(", line)]
    for line in scatters:
        shape = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
        under = re.search(r'op_name="([^"]*)"', line)
        rows = "," in shape  # two dimensions or more: rows
        if under and re.search(r"\bmoe_(dispatch|combine|router)\b", under.group(1)):
            assert not rows and int(shape) < 1024, line[:300]
    # XLA fuses the head's dW matmul into the head's AdamW update: that ONE
    # ``multiply_add_fusion`` carries ``lm_head`` (``lm_head_ms_step.moe``
    # counts it), and ``optimizer_ms_step.moe`` excludes exactly it.
    with open(os.path.join(root, "benchmark", "metrics", "optimizer_ms_step.moe.json")) as f:
        sweeps = json.load(f)["params"]
    named = [
        line.strip() for line in text.splitlines()
        if re.search(sweeps["pattern"], re.sub(r"^ROOT ", "", line.strip()))
        and " fusion(" in line
    ]
    under_head = [line for line in named if re.search(r'op_name="[^"]*\blm_head\b', line)]
    left_out = [line for line in named if re.search(sweeps["exclude"], line)]
    assert len(named) > 10 and len(left_out) == 1 and left_out == under_head
    # the one row scatter of the step is the token embedding's gradient
    row_scatters = [
        line for line in scatters
        if "," in re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
    ]
    assert len(row_scatters) == 1 and "f32[50304,2048]" in row_scatters[0]


# ------------------------------------------------------------- kanana2_job


def _signatures(text: str):
    """(bf16 operands, f32 operands) of every Mosaic call in a compiled
    program, sorted: what the ``flash_roofline_pct.*`` patterns tell the
    three flash kernels apart by."""
    calls = re.findall(
        r"custom_call_target=\"tpu_custom_call\", "
        r"operand_layout_constraints=\{(.*?)\}, frontend_attributes",
        text,
    )
    return sorted((ops.count("bf16["), ops.count("f32[")) for ops in calls)


def test_flash_with_a_rotary_part_compiles_for_v5e(compiled_kernel, v5e_device, path_lines):
    """Latent attention's call at ``kanana2_job``'s shape, [2, 8192, 32,
    128 + 64 / 128] with ONE shared rotary key: Mosaic accepts the three
    kernels under the default scoped-VMEM limit, at two more bf16 operands
    each than the plain calls (``flash_roofline_pct.mla`` reads 5 / 6 + 1 /
    6 + 2), over the model's own [B, L, H * width] arrays and the key tiled
    to one block of lanes."""
    def arg(*shape):
        return jax.ShapeDtypeStruct(
            shape, jnp.bfloat16, sharding=jax.sharding.SingleDeviceSharding(v5e_device)
        )

    def loss(q, k, v, q_rot, k_rot):
        return jnp.sum(fa.flash_attention(q, k, v, True, q_rot, k_rot).astype(jnp.float32) ** 2)

    wide = arg(2, 8192, 32, 128)
    text = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
        .trace(wide, wide, wide, arg(2, 8192, 32, 64), arg(2, 8192, 64))
        .lower(lowering_platforms=("tpu",))
        .compile()
        .as_text()
    )
    assert _signatures(text) == [(5, 0), (6, 1), (6, 2)]
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert all("bf16[2,8192,4096]" in c and "bf16[2,8192,2048]" in c and "bf16[2,8192,128]" in c for c in calls)
    (line,) = set(path_lines)
    assert "attention path: pallas-compiled" in line and line.endswith("heads_per_block=1 rotary=64)")
    assert " steps=36/36 key_tiles=136/256 fwd" in line      # the folded grid, a query row's two heads together (``_folded_heads``)


def test_flash_under_a_window_and_over_an_odd_number_of_blocks_compiles_for_v5e(compiled_kernel, v5e_device, path_lines):
    """Mosaic accepts the grids that hold idle steps: a window call at ``trinity_mini_job``'s shape (``w + 1`` = 3
    steps a row block, the three a head that rows 0 and 1 leave idle pinned to the row's first pair), the window's
    int32 [1] still AHEAD of the operand lists every call has; and a causal call over THREE blocks a head, whose
    folded grid's middle row runs once and idles twice."""
    def compiled(shape, **window):
        arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=jax.sharding.SingleDeviceSharding(v5e_device))
        loss = lambda q, k, v: jnp.sum(fa.flash_attention(q, k, v, True, **window).astype(jnp.float32) ** 2)  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).trace(arg, arg, arg).lower(lowering_platforms=("tpu",)).compile().as_text()

    text = compiled((1, 8192, 32, 128), window=2048)
    assert _signatures(text) == [(3, 0), (4, 1), (4, 2)]
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 3 and all(re.search(r"operand_layout_constraints=\{s32\[1\]\{0\}, bf16\[", c) for c in calls), calls
    assert any(" steps=24/21 key_tiles=70/256 fwd, 252/1024 bwd" in line and line.endswith("window=2048)") for line in path_lines)
    assert _signatures(compiled((2, 3072, 4, 128))) == [(3, 0), (4, 1), (4, 2)]
    assert any(" steps=8/6 key_tiles=" in line for line in path_lines)


@pytest.mark.slow  # 127 s under the driver's command (PR 66); its lowered twin, which runs: ``test_kanana2_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix``
def test_kanana2_step_compiles_for_v5e_with_its_scopes_and_no_score_matrix(
    v5e_device, olmoe_as_on_the_chip, path_lines
):
    """``kanana2_job``'s real step (kanana-2's widths, the dense layer and
    four expert layers of 16 held experts, 2 sequences of 8192, two steps a
    dispatch) compiled for a described v5e: it fits the chip; the eight
    device scopes the ``.mla`` metrics read are there; the attention is the
    three flash kernels with a rotary part at the operand lists
    ``flash_roofline_pct.mla`` reads; the experts' grouped matmuls read
    ``C`` = 18,432 rows (``ops/moe.held_rows_bound``: 1.5 x the 16 held
    experts' even share of the 98,304 slots) and the 16 held experts'
    weights, the always-run tier's and — inside its loops' bodies — the
    second tier's alike; no array of all the slots, or of the other
    79,872, exists anywhere in the step; the step's memory is under the
    14.76 GiB it took with row buffers of ``T * k`` (PR 33);
    nothing scatters rows under a ``moe_*`` scope or under ``mla_proj``;
    and no array of [*, 8192, 8192] exists anywhere (the XLA attention path
    would write 32 of them a sequence)."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "kanana2_30b_a3b_ep8_l5.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq8k.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(
        spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh
    )
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"],
        steps=traffic["minibatches_per_task"],
    )
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    ma = compiled.memory_analysis()
    total = (
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    )
    # over a quarter of the chip by a wide margin, and under what the step
    # took while every row buffer held all T * k slots (14.76 GiB, PR 33)
    assert 8 * 2**30 < total < 14.76 * 2**30, total / 2**30
    text = compiled.as_text()
    for scope in ("mla_proj", "flash_attn", "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
                  "moe_combine", "lm_head"):
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    assert any(line.endswith("heads_per_block=1 rotary=64)") for line in path_lines), path_lines
    layers, expert_layers = params["num_hidden_layers"], params["num_hidden_layers"] - params["first_k_dense_replace"]
    runs = 2 if params["remat"] else 1  # a rematerialised block runs its forward twice
    flash = _flash_calls(text)
    assert len(flash) == (runs + 2) * layers
    lists = re.findall(
        r"custom_call_target=\"tpu_custom_call\", operand_layout_constraints=\{(.*?)\}, frontend_attributes",
        "\n".join(flash),
    )
    assert sorted({(ops.count("bf16["), ops.count("f32[")) for ops in lists}) == [(5, 0), (6, 1), (6, 2)]
    assert all("bf16[2,8192,4096]" in c and "bf16[2,8192,128]" in c for c in flash), flash[:1]
    grouped = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line and re.search(r'op_name="[^"]*\bmoe_experts\b', line)
    ]
    from elasticdl_tpu.ops import moe

    slots = traffic["minibatch_size"] * params["seq_len"] * params["num_experts_per_tok"]
    bound = moe.held_rows_bound(slots, params["experts_held"], params["num_experts"])
    assert (slots, bound) == (98304, 18432)
    # the step is a scan: an op of the second tier's loop sits in a while body inside the scan's
    in_loop = lambda line: re.search(r'op_name="([^"]*)"', line).group(1).count("while/body") > 1  # noqa: E731
    always = [c for c in grouped if not in_loop(c)]
    second = [c for c in grouped if in_loop(c)]
    # the first tier: three projections x (forward, its re-run, dx: gmm; dw: tgmm), every
    # expert layer, over C rows and the 16 held experts' weights
    assert len(always) == 3 * (runs + 2) * expert_layers
    assert sum("jit(tgmm)" in c for c in always) == 3 * expert_layers
    # the second tier, inside its loops' bodies (no trip while the held run fits the first
    # tier): its forward, and in the backward's loop the forward again, dx and dw — the SAME
    # window of C rows, so the same kernels: no array of the other T * k - C rows anywhere
    assert len(second) == 3 * 4 * expert_layers
    assert all(f"bf16[{bound}," in c and "bf16[16," in c for c in grouped), grouped[:1]
    for rows in (f"[{slots},2048]", f"[{slots},768]", f"[{slots - bound},", "[16384,6,2048]"):
        assert rows not in text, rows  # the slots' ints (the sort, its inverse) are all there is of T * k
    # two token sums a layer always run (the combine, and the dispatch's transpose), each
    # ONE sweep kernel into [T, D] float32
    sums = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and re.search(r'op_name="[^"]*\bmoe_(dispatch|combine)\b', line) and not in_loop(line)
    ]
    assert len(sums) == 2 * expert_layers and all("= f32[16384,2048]" in c for c in sums), sums[:1]
    scatters = [line for line in text.splitlines() if re.search(r" scatter\(", line)]
    for line in scatters:
        shape = re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
        under = re.search(r'op_name="([^"]*)"', line)
        if under and re.search(r"\b(moe_(dispatch|combine|router|shared|experts)|mla_proj)\b", under.group(1)):
            assert "," not in shape and int(shape) < 1024, line[:300]
    # the one row scatter of the step is the token embedding's gradient
    row_scatters = [
        line for line in scatters
        if "," in re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1)
    ]
    assert len(row_scatters) == 1 and "f32[16032,2048]" in row_scatters[0]
    # the correction biases are parameters of the step that no optimizer sweep writes a
    # gradient into: their update is the model's own rule (sign of mean - count)
    assert re.search(r'op_name="[^"]*sign', text)


def test_eva_kernels_compile_for_v5e_at_sixteen_thousand(olmoe_as_on_the_chip, v5e_device, path_lines):
    """EVA attention's three kernels at ``evabyte_job``'s shape, [1, 16384,
    16, 128] (eight windows of 2048, 1024 summaries a head: past the flash
    kernels' ``_MAX_L``), forward and backward: Mosaic accepts them inside
    the kernels' scoped-VMEM limit, at the operand lists
    ``eva_roofline_pct.eva`` tells them apart by (forward 5 bf16, dQ 6 + 1
    f32, dK/dV with the summaries' gradients 6 + 2), over the model's own
    [B, L, H * D] arrays and [B, L / 16, H * D] summaries."""
    from elasticdl_tpu.ops import eva_attention as eva_ops

    def arg(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=jax.sharding.SingleDeviceSharding(v5e_device))

    def loss(q, k, v, phi, mu):
        return jnp.sum(eva_ops.eva_attention(q, k, v, phi, mu, window=2048, chunk=16).astype(jnp.float32) ** 2)

    wide, vector = arg(1, 16384, 16, 128), arg(16, 128, dtype=jnp.float32)
    text = (
        jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))
        .trace(wide, wide, wide, vector, vector)
        .lower(lowering_platforms=("tpu",))
        .compile()
        .as_text()
    )
    assert _signatures(text) == [(5, 0), (6, 1), (6, 2)]
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert all("bf16[1,16384,2048]" in c and "bf16[1,1024,2048]" in c for c in calls)
    assert all(re.search(r'op_name="[^"]*\beva_attn\b', c) for c in calls)
    (line,) = set(path_lines)
    assert "attention path: pallas-compiled" in line
    assert "eva window=2048 summaries_per_window=128 pairs_a_head=16785408+7340032" in line


@pytest.mark.parametrize("bytes_limit", V5E_BYTES_LIMIT, ids=["v5e_budget", "budget_0"])
def test_evabyte_step_compiles_for_v5e_with_its_scopes_and_no_score_matrix(
    v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch, bytes_limit
):
    """``evabyte_job``'s real step (EvaByte's widths, four layers of 16 held
    heads, ONE sequence of 16,384 bytes, one step a dispatch, per-block
    rematerialisation) compiled for a described v5e, with the byte budget
    the trainer resolves from a v5e's memory: the blocks keep every save
    site (gate, up, q, k, v, the EVA output with its logsumexp, the
    summaries) and the step stays between 12.5 GiB and the trainer's line
    of 14.25; the five device scopes the ``.eva`` metrics read are there;
    the attention is the three EVA kernels at the operand lists
    ``eva_roofline_pct.eva`` reads, the forward ONCE a layer; and no score
    tensor exists anywhere: nothing of [*, 16384, 16384], [*, 16384, 1024],
    [*, 2048, 2048] or [*, 2048, 3072] (what the XLA path would write,
    3.2 GB a sequence and layer).  With no memory to read (budget 0, every
    CPU program) it is the step it was: 10.8 GiB, the forward twice a layer
    (the rematerialised repeat)."""
    import json
    import os

    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: bytes_limit)

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "evabyte_6b5_tp2_l4.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq16k.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(
        spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh
    )
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"],
        steps=traffic["minibatches_per_task"],
    )
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total, plan = trainer_lib.compiled_bytes(compiled), trainer.keep_plan
    if bytes_limit is None:
        assert plan is None and 10.5 * 2**30 < total < 11.0 * 2**30, total / 2**30
    else:
        assert 12.5 * 2**30 < total < plan.line == bytes_limit - trainer_lib.REMAT_HEADROOM, total / 2**30
        assert plan.kept == plan.tagged <= plan.budget
        # the estimate of the step with nothing kept (the compiler's own account: 10.805 GiB)
        assert abs(plan.estimate - 10.805 * 2**30) < 0.15 * 2**30
    forwards = 2 if bytes_limit is None else 1
    text = compiled.as_text()
    for scope in ("eva_proj", "eva_pool", "eva_attn", "mlp", "lm_head"):
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    for scores in (r"16384,16384", r"16384,1024", r"2048,2048", r"2048,3072", r"2048,1024"):
        assert not re.search(rf"\[(\d+,)*{scores}\]", text), scores
    layers = params["num_hidden_layers"]
    calls = [
        line for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line and re.search(r'op_name="[^"]*\beva_attn\b', line)
    ]
    assert len(calls) == (forwards + 2) * layers and params["remat"]
    assert _signatures("\n".join(calls)) == sorted([(5, 0)] * forwards * layers + [(6, 1)] * layers + [(6, 2)] * layers)
    assert all("bf16[1,16384,2048]" in c and "bf16[1,1024,2048]" in c for c in calls), calls[:1]
    assert any("attention path: pallas-compiled" in line and "eva window=2048" in line for line in path_lines), path_lines


@pytest.mark.slow  # 119 s under the driver's command (PR 66); its lowered twin, which runs: ``test_nemotron3_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix``
def test_nemotron3_step_compiles_for_v5e_with_its_scopes_and_the_flash_kernels_lists(
    v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch
):
    """``nemotron3_job``'s real step (Nemotron 3 Super's widths, one period
    of 11 layers: 5 LatentMoE, 5 Mamba-2, 1 attention; ONE sequence of 8192
    tokens, the traffic's two steps a dispatch, per-layer rematerialisation) compiled for
    a described v5e with the byte budget the trainer resolves from a v5e's
    memory: 773.6 M parameters and their moments are 8.65 GiB of arguments,
    the layers keep every save site and the step stays between 11.5 GiB and
    the trainer's line of 14.25; the device scopes the ``.ssm`` / ``.tok`` /
    ``.mla`` metrics read are there; the attention layer is the three flash
    kernels at the operand lists ``flash_roofline_pct.tok`` reads (3 / 4 + 1
    / 4 + 2: the key/value head is repeated AHEAD of them), the forward
    once; the chunked scan's chunk arithmetic is the Mosaic calls of
    ``ops/ssm_kernels.py`` under ``ssm_scan`` (a layer: the chunks' end
    states and their outputs, the forward ONCE because the site is kept,
    what ``y`` asks of the start states and the chunks' gradients), within
    the scoped VMEM they ask for, and no [.., 128, 128] float32 decay mask
    or masked weight is left among the step's buffers under the scope; the
    experts are grouped matmuls; and no [*, 8192, 8192] score matrix exists."""
    import json
    import os

    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "nemotron3_super_tp4_ep64_l11.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq8k_x1.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"], steps=traffic["minibatches_per_task"])
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total, plan = trainer_lib.compiled_bytes(compiled), trainer.keep_plan
    assert 11.5 * 2**30 < total < plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM, total / 2**30
    assert abs(compiled.memory_analysis().argument_size_in_bytes - 12 * 773582304) < 2**20  # parameters and two moments
    assert plan.kept == plan.tagged <= plan.budget and plan.tagged > 2**30
    text = compiled.as_text()
    scopes = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_norm", "attn_proj", "moe_latent", "moe_shared", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "mlp", "flash_attn", "lm_head")
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    mosaic = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    under = lambda scope: [c for c in mosaic if re.search(rf'op_name="[^"]*\b{scope}\b', c)]  # noqa: E731
    flash = _flash_calls(text)
    assert _signatures("\n".join(flash)) == [(3, 0), (4, 1), (4, 2)]  # the forward ONCE: its output is kept
    assert all("bf16[1,8192,1024]" in c for c in flash), flash[:1]  # 8 query heads of 128; K and V repeated to as many
    assert under("moe_experts") and len(under("moe_experts")) % 5 == 0
    scan, m_layers = under("ssm_scan"), params["hybrid_override_pattern"].count("M")
    kernels = {name: sum(f"%{name}" in c.split(" = ")[0] for c in scan) for name in ("ssm_chunk_states", "ssm_chunk_outputs", "ssm_chunk_grads")}
    # a layer: ends + what y asks of the starts; the outputs (forward ONCE: the site is kept); the gradients
    assert kernels == {"ssm_chunk_states": 2 * m_layers, "ssm_chunk_outputs": m_layers, "ssm_chunk_grads": m_layers} and len(scan) == 4 * m_layers
    assert all("f32[1,64,2,16,64,128]" in c for c in scan if "ssm_chunk_outputs" not in c.split(" = ")[0])  # the states through HBM
    scoped = [line for line in text.splitlines() if re.search(r'op_name="[^"]*\bssm_scan\b', line)]
    assert scoped and not any(re.search(r"f32\[(\d+,)*128,128\]", line.split(" = ")[1].split("(")[0]) for line in scoped if " = " in line)
    assert any("attention path: pallas-compiled" in line and "ssm_scan groups=2 state=128 chunk=128" in line for line in path_lines), path_lines
    # the patterns of the roofline entry the cell joined tell exactly these three apart
    with open(os.path.join(root, "benchmark", "metrics", "flash_roofline_pct.tok.json")) as f:
        patterns = [k["pattern"] for k in json.load(f)["params"]["kernels"]]
    assert any("attention path: pallas-compiled" in line and "heads_per_block=1" in line for line in path_lines), path_lines
    assert len(patterns) == 3 and params["remat"]


@pytest.mark.slow  # 184 s under the driver's command (PR 66); its lowered twin, which runs: ``test_kimi_linear_step_lowers_for_v5e_with_its_scopes_kernels_and_no_score_matrix``
def test_kimi_linear_step_compiles_for_v5e_inside_the_line_with_its_scopes_and_the_flash_kernels_lists(
    v5e_device, olmoe_as_on_the_chip, path_lines, monkeypatch
):
    """``kimi_linear_job``'s real step (Kimi Linear's widths: the dense layer
    and four expert layers of 8 held experts, Kimi Delta Attention on four of
    the five layers and latent attention on the fourth; ONE sequence of 8192
    tokens, the traffic's two steps a dispatch, per-layer rematerialisation)
    compiled for a described v5e with the byte budget the trainer resolves
    from a v5e's memory: 602.4 M parameters and their moments are 6.73 GiB of
    arguments, the layers keep every save site and the step stays between
    11.5 GiB and the trainer's line of 14.25 AT THE FIRST COMPILE (the
    trainer's loop over a step that reads over the line compiles again, two
    minutes a time; with a sequence's chunks all at once the op's masks and
    solves put this step at 15.46 GiB, and at 15.89 with nothing kept: PR
    47); the device scopes the ``.kda`` / ``.tok`` / ``.mla`` metrics read
    are there; the latent-attention layer is the three flash kernels at the
    operand lists ``flash_roofline_pct.mla`` reads (5 / 6 + 1 / 6 + 2: the
    rotary columns are handed over, unturned), the forward once; the delta
    rule is XLA products under ``kda_scan``, a group of chunks at a time,
    with NO array of a sequence's pairwise differences or column factors,
    NO triangular solve of XLA's (a group's systems are solved by
    ``ops/delta_rule._solve``'s blocked forward substitution: multiply-adds
    and products, no custom call; PR 48) and NO [SUB, SUB, dk] array of a
    sub-block's differences at all: the same-sub-block masks are the two
    Mosaic kernels of ``ops/delta_rule_kernels.py`` under ``kda_scan`` /
    ``kda_mask`` (PR 49: the masks a layer's forward scan and again in its
    backward scan, their transpose there once), which the op's ``attention
    path:`` line says; each of a layer's three convolution chains (conv ->
    silu -> l2norm) is the Mosaic pair of ``ops/short_conv_kernels.py`` under
    ``kda_glue`` / ``kda_conv`` and nothing else (PR 53: the forward in the
    layer's forward and in its rematerialised repeat, the gradient once; no
    float32 array of a chain's [8192, 4096], no padded copy, and
    ``ops/ssm.causal_conv``'s ``ssm_conv`` is gone from THIS step), which
    the part's two ``attention path:`` lines say; the experts are grouped
    matmuls; and no [*, 8192, 8192] score matrix exists."""
    import json
    import os

    from elasticdl_tpu.parallel import trainer as trainer_lib

    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: V5E_BYTES_LIMIT[0])
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", "kimi_linear_48b_a3b_ep32_l5.json")) as f:
        params = json.load(f)["model_params"]
    with open(os.path.join(root, "benchmark", "traffic", "job_seq8k_x1_v20480.json")) as f:
        traffic = json.load(f)
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **params)
    mesh = create_mesh([v5e_device], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    step, args = _abstract_scan_step(
        trainer, mesh, minibatch=traffic["minibatch_size"], steps=traffic["minibatches_per_task"])
    compiled = step.trace(*args).lower(lowering_platforms=("tpu",)).compile()
    total, plan = trainer_lib.compiled_bytes(compiled), trainer.keep_plan
    assert 11.5 * 2**30 < total < plan.line == V5E_BYTES_LIMIT[0] - trainer_lib.REMAT_HEADROOM, total / 2**30
    assert abs(compiled.memory_analysis().argument_size_in_bytes - 12 * 602434432) < 2**20  # parameters and two moments
    assert plan.kept == plan.tagged <= plan.budget and plan.tagged > 2 * 2**30
    text = compiled.as_text()
    scopes = ("kda_proj", "kda_glue", "kda_conv", "kda_scan", "mla_proj", "flash_attn", "moe_shared", "moe_router",
              "moe_dispatch", "moe_experts", "moe_combine", "mlp", "lm_head")
    for scope in scopes:
        assert re.search(rf'op_name="[^"]*\b{scope}\b', text), scope
    # the state-space family's are not this model's; ``ssm_conv`` (the XLA chains' convolution) left with the chains (PR 53)
    assert not re.search(r'op_name="[^"]*\bssm_(proj|scan|norm|conv)\b', text)
    assert not re.search(r"\[(\d+,)*8192,8192\]", text)
    flash = _flash_calls(text)
    assert _signatures("\n".join(flash)) == [(5, 0), (6, 1), (6, 2)]  # ONE latent-attention layer, the forward ONCE: its output is kept
    mosaic = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    under = lambda scope: [c for c in mosaic if re.search(rf'op_name="[^"]*\b{scope}\b', c)]  # noqa: E731
    assert under("moe_experts") and len(under("moe_experts")) % 4 == 0
    # the op's ONE kernel pair (PR 49), in each of the four KDA layers: the masks in the forward scan's body and again
    # (rematerialised) in the backward scan's, their transpose there; every call under ``kda_scan`` AND ``kda_mask``
    kernels = [re.search(r"kda_sub_block_\w+", call).group(0) for call in under("kda_scan") if "kda_sub_block_" in call]
    assert len(kernels) == len(under("kda_scan")) == len(under("kda_mask")) == 12, (len(kernels), len(under("kda_scan")), len(under("kda_mask")))
    assert sorted(set(kernels)) == ["kda_sub_block_mask_grads", "kda_sub_block_masks"] and kernels.count("kda_sub_block_mask_grads") == 4
    assert any("attention path: pallas-compiled" in line and "kda_mask chunk=64" in line for line in path_lines), path_lines
    # the three chains of a layer (PR 53), in each of the four KDA layers: ONE forward kernel a chain in the layer's forward
    # and again in its rematerialised repeat, ONE gradient kernel; every call under ``kda_glue`` AND ``kda_conv``, and
    # nothing else of Mosaic's under either
    chains = [re.search(r"%(kda_conv_chain\w*?)(\.\d+)? = ", call).group(1) for call in under("kda_glue")]
    assert len(chains) == len(under("kda_conv")) == 3 * 4 * 3, (len(chains), len(under("kda_conv")))
    assert chains.count("kda_conv_chain") == 3 * 4 * 2 and chains.count("kda_conv_chain_grads") == 3 * 4
    assert all("bf16[1,8192,4096]" in call.split(" = ")[1].split("custom-call(")[0] for call in under("kda_conv"))
    for norm in (128, None):
        assert any("attention path: pallas-compiled" in line and f"kda_conv taps=4 norm={norm})" in line for line in path_lines), path_lines
    # what a chain keeps in HBM is its bfloat16 operand and result: no float32 array of its size under its scope, and the
    # XLA convolution's padded float32 copy ([1, 8192 + 3, 4096]) nowhere in the step
    of_chains = [line.split(" = ")[1].split("(")[0] for line in text.splitlines() if re.search(r'op_name="[^"]*\bkda_conv\b', line) and " = " in line]
    assert of_chains and not any(re.search(r"f32\[(\d+,)*8192,(\d+,)*4096\]|f32\[(\d+,)*8192,32,128\]", out) for out in of_chains), of_chains
    assert "8195,4096]" not in text
    scoped = [line for line in text.splitlines() if re.search(r'op_name="[^"]*\bkda_scan\b', line)]
    # the solve a chunk is ``ops/delta_rule._solve``'s blocked forward substitution (PR 48): XLA's general triangular
    # solve (on the chip a custom call that is no Mosaic kernel, ``InvertDiagBlocksLowerTriangular``: 95 ms of the
    # step's 623) is nowhere in the step, and under the scope no custom call is left but XLA's own buffer bookkeeping
    targets = set(re.findall(r'custom_call_target="(\w+)"', "\n".join(line for line in scoped if " custom-call(" in line)))
    assert "triangular_solve" not in text and "Triangular" not in text
    assert targets <= {"AllocateBuffer", "AssumeGatherIndicesInBound", "ConcatBitcast", "tpu_custom_call"}, targets
    # a GROUP of 8 chunks at a time: the masks' column factors are [.., 8, 32, 4, 64, 128], never a sequence's 128 chunks;
    # the same-sub-block differences ([.., 8, 32, 4, 16, 16, 128] as XLA fusions until PR 49) are in no array of the step
    shapes = set(re.findall(r"f32\[([\d,]+)\]", "\n".join(line.split(" = ")[1].split("(")[0] for line in scoped if " = " in line)))
    assert any(shape.endswith("8,32,4,64,128") for shape in shapes), sorted(shapes)[:40]
    assert not any(re.search(r"\b16,16,128$", shape) or re.search(r"\b128,32,4,(16,16|64),128$", shape) for shape in shapes)
    assert any("attention path: pallas-compiled" in line and "rotary=64" in line for line in path_lines), path_lines
    # the patterns of the roofline entry the cell joined tell exactly these three apart
    with open(os.path.join(root, "benchmark", "metrics", "flash_roofline_pct.mla.json")) as f:
        patterns = [k["pattern"] for k in json.load(f)["params"]["kernels"]]
    assert len(patterns) == 3 and params["remat"]


#: sha256 of ``gpt2_medium``'s step lowered for the chip (StableHLO text,
#: 16 sequences of 1024, two steps a dispatch): a PR that may not move
#: ``gpt2m_job`` pins that its program is the same to the byte.  A PR that
#: changes ``transformer_lm`` or the trainer's step on purpose re-pins it
#: and says so.  RE-PINNED in PR 31 (was 58a2c564...6ca370 since f841ab1):
#: ``_block`` multiplies ``wqkv``'s three column blocks apart and views each
#: product by heads, so that the flash kernels read the projections' own
#: [B, L, H*D] and no activation is split or gradient concatenated.  Lowered from the CPU the
#: attention is the XLA reference, so the kernels are not in this text (a
#: Mosaic payload carries the checkout's path: tried, not stable); the step
#: compiled with them is held by
#: ``test_gpt2_medium_step_compiles_for_v5e_with_no_layout_glue_at_flash``.
#: RE-PINNED in PR 38 (was 7c496aa8...5a51b6 since PR 31): ``transformer_lm``
#: rematerialises through ``ops/remat.plan``, and the step of a model that
#: does reports two more counts, ``remat_bytes_tagged`` (6.44 GB here: the
#: XLA attention has no kernel output to tag) and ``remat_bytes_kept`` (0:
#: off the TPU the budget is 0).  The diff of the two texts is those two
#: constants with their sums over the mesh and the step's two more outputs;
#: every block is the plain ``jax.checkpoint`` it was and carries no name.
GPT2_MEDIUM_STEP_SHA256 = "c635201162024437d79e8ea579e276f9e51d2fe2b21fb4c4d2987008ca98cdc2"


#: sha256 of the four ``moe_lm`` cells' steps lowered for the chip
#: from their configuration and traffic files (StableHLO text, lowered from
#: the CPU as ``GPT2_MEDIUM_STEP_SHA256`` is: the attention is the XLA
#: reference, the kernels are not in the text).  PINNED in PR 37 at the
#: values the PARENT commit (b46a7c9) gives: ``models/moe_lm.py`` grew
#: EvaByte's keys, and the two cells that share its ``_block`` / ``_apply``
#: trace to the byte as they did.  A PR that changes ``moe_lm`` or the
#: trainer's step for these cells on purpose re-pins and says so.
MOE_LM_STEP_SHA256 = {
    ("olmoe_1b_7b_l1", "job_seq4k"): "6610fc1c02aea4649af2155ba6b4899fa16010f6156736d9da8378fde6ca8792",
    # RE-PINNED in PR 68 (was 1853512d...d5438e since PR 37): ``ops/moe.py``'s token sums hand ``ops/table_grad``'s sweep the
    # experts' bfloat16 rows themselves — no float32 ``[C, D]`` product, cast or permutation, the router's weight inside the
    # kernel's one-hot, the gather's transpose leaving in bfloat16 (lowered from the CPU the sweep is the interpreter's
    # expansion, so it IS in this text); ``olmoe_1b_7b_l1`` (every expert held: no token sum), ``evabyte_6b5_tp2_l4`` and
    # ``gpt2_medium`` are untouched
    ("kanana2_30b_a3b_ep8_l5", "job_seq8k"): "a6a155d523f28f5aa49d5cde57d3a03434b2667409db9e2fde66babbd8366da1",
    # PINNED in PR 45 at the values its PARENT commit (9611bdf) gives, ahead of parting the file along its layers
    ("evabyte_6b5_tp2_l4", "job_seq16k"): "87b44ef8533274d8216ec8aaeaaeb214522f60bb60cad971e1e29df4bf74797a",
    # RE-PINNED in PR 68 (was 8c69791b...6edc2e since PR 45): the token sums read bfloat16 rows, as ``kanana2``'s above
    ("nemotron3_super_tp4_ep64_l11", "job_seq8k_x1"): "b02861f2b547f4631d6f700909c3eda3b04838b3e5377e77db85e9b89fbbad63",
    # PINNED in PR 47, which added the family ``kimi_linear`` (a part, a builder's line, a field of ``LatentAttention``,
    # a second caller of ``ops/ssm.causal_conv``): the four above are the values they had, and the fifth was the new cell's
    # RE-PINNED in PR 48 (was 7f1534c8...83152be since PR 47): ``ops/delta_rule._solve`` is a blocked forward substitution
    # under a ``custom_vjp`` of its own where it was ``lax.linalg.triangular_solve``; the four above are untouched
    # RE-PINNED in PR 49 (was 8afaf5cd...bad675096 since PR 48): the part counts a third number (``kda_positions_mask_kernel``)
    # and ``ops/delta_rule._masks_of`` traces under the scope ``kda_mask``; lowered from the CPU the same-sub-block masks
    # are the XLA differences they were (the kernels are not in this text); the four above and ``gpt2_medium``'s are untouched
    # RE-PINNED in PR 53 (was 559b0adf...3b6500d75f734 since PR 49): the part counts a fourth number (``kda_positions_conv_kernel``)
    # and asks ``ops/short_conv.conv_path`` of each chain; lowered from the CPU the three chains are the XLA ones they were
    # (``_short_conv`` / ``_short_conv_l2`` over ``ops/ssm.causal_conv``: as many pads and rsqrts as before, no kernel in this
    # text; the text is 11 lines longer, the counter's sum and output); the four above and ``gpt2_medium``'s are untouched
    # RE-PINNED in PR 68 (was 98fb0198...3ffae2 since PR 53): the token sums read bfloat16 rows, as ``kanana2``'s above
    ("kimi_linear_48b_a3b_ep32_l5", "job_seq8k_x1_v20480"): "3bfac7a3c7e472faf5263217ccf39c9c2231002eb224f800dda4339469ae84e1",
    # PINNED in PR 61 at the values its PARENT commit (ead6e50) gives, computed before any other edit of that PR: the two
    # newest cells had no pin, so a refactor of ``moe_lm`` / ``flash_attention`` / ``sparse_select`` had nothing that said
    # "unchanged" for them (the windowed attention and the selected attention are the XLA references in this text too)
    # RE-PINNED in PR 68 (was 372ec530...2d8141 since PR 61): the token sums read bfloat16 rows, as ``kanana2``'s above
    ("trinity_mini_26b_a3b_ep8_l5", "job_seq8k_x1_v25024"): "8e960704489982205aea5de76b7735009d1a895d1dde77d733399115dfb366ba",
    # RE-PINNED in PR 68 (was af5e2c59...ebb5ef since PR 61): the token sums read bfloat16 rows, as ``kanana2``'s above
    ("keye_vl2_30b_a3b_ep8_l5", "job_seq16k_x1_v18992"): "61263b940e81962ed90a3ced1edc6689d736d4b85c5523d557d5a0299ae63ca6",
}


#: ... and ``gpt2_medium``'s beside them: ONE script lowers a cell from its two files, whatever its ``model_def``
LOWERED_STEP_SHA256 = {("gpt2_medium", "job_seq1k"): GPT2_MEDIUM_STEP_SHA256, **MOE_LM_STEP_SHA256}
