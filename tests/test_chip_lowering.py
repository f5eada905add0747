"""What the CPU can say about the chip-only code paths: they LOWER for
platform ``tpu`` as the real ops (``tpu_custom_call``, ``ragged_all_to_all``),
and — where libtpu can describe a v5e topology without a chip — they COMPILE
for it, Mosaic included.  Nothing here runs on a TPU; ``chip_smoke.py`` does.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer, build_train_step

# The MFU-bench shape and the kernel's documented bound (_MAX_L).
FLASH_SHAPES = [(16, 1024, 12, 64), (1, 8192, 12, 64)]


def _flash_loss(q, k, v):
    return jnp.sum(fa.flash_attention(q, k, v, True).astype(jnp.float32) ** 2)


@pytest.fixture
def compiled_kernel(monkeypatch):
    """Interpret mode off, as on the chip (off-TPU the kernel otherwise
    silently lowers to the Pallas interpreter)."""
    monkeypatch.setattr(fa, "_use_interpret", lambda: False)


@pytest.mark.parametrize("shape", FLASH_SHAPES)
def test_flash_fwd_bwd_lowers_to_three_mosaic_calls(compiled_kernel, shape):
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    lowered = (
        jax.jit(jax.value_and_grad(_flash_loss, argnums=(0, 1, 2)))
        .trace(arg, arg, arg)
        .lower(lowering_platforms=("tpu",))
    )
    # fwd, dq, dkv — compiled kernels, not the interpreter's XLA expansion.
    assert lowered.as_text().count("tpu_custom_call") == 3


@pytest.fixture(scope="module")
def v5e_device():
    """One device of a described (not attached) v5e host: libtpu compiles
    for it ahead of time.  Skips where the installed libtpu cannot."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu"
        )
    except Exception as e:  # noqa: BLE001 — any plugin failure means "cannot"
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    return topo.devices[0]


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
    assert compiled.memory_analysis().temp_size_in_bytes > 0


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
    # ids out, vectors back, cotangents out.
    assert text.count("ragged_all_to_all") == 3
