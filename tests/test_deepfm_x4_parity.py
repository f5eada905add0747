"""The system on four devices over the ragged route (``ragged_emulated``:
the same sort / offsets / unsort code, XLA:CPU has no ragged-all-to-all)
against ``deepfm_criteo_tb_x4``'s plain reference
(``benchmark/configs/deepfm_criteo_tb_x4_reference.py``: float32, plain
[U, 11] table of the touched rows, plain gather, one device), at a toy
size on the program's seeded weights.

Tolerances and why.  In float32 compute both sides do the same arithmetic
in another order (packed rows and a one-hot lane select against a plain
gather, per-device partial sums against one sum): logits, loss and table
gradient agree to a few float32 ulps of their magnitudes, and eight Adam
steps keep the task's mean loss within 2e-6 (5e-8 here).  In bfloat16 compute (what
the configuration states) the interactions and the MLP round to 8 bits of
mantissa: the first task's loss differs by 1e-4 here (4e-4 to 8e-4 on the
chip at the real size), inside the
configuration's ``reference_tolerance`` and OUTSIDE the float32
tolerance, so a run in a lower precision than stated cannot pass for one
in the stated precision.
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys

import jax
import numpy as np
import optax
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops.embedding import table_shape
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUCKETS, DIM, MB, STEPS = 300, 10, 64, 8
F32_TOLERANCE = 2e-6
with open(os.path.join(ROOT, "benchmark", "configs", "deepfm_criteo_tb_x4.json")) as _f:
    CONFIG_TOLERANCE = json.load(_f)["reference_tolerance"]


@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import resolve

    jax.config.update("jax_default_matmul_precision", "highest")
    yield resolve.load_module(
        os.path.join(ROOT, "benchmark", "configs", "deepfm_criteo_tb_x4_reference.py")
    )
    jax.config.update("jax_default_matmul_precision", None)


@pytest.fixture(scope="module")
def task():
    """One task's records.  Example 1 repeats example 0 (duplicate ids on
    one device), field 3 holds one id in every example and field 4 two
    (duplicates across devices: the batch is split four ways), the rest is
    uniform, so every shard of the table is asked by every device."""
    rng = np.random.default_rng(26)
    n = STEPS * MB
    cats = rng.integers(0, 2**31 - 1, (n, 26)).astype(np.int32)
    cats[1::MB] = cats[0::MB]
    cats[:, 3] = 12345
    cats[:, 4] = np.where(np.arange(n) % 2, 777, 778)
    return {
        "dense": (rng.random((n, 13)) * 10).astype(np.float32),
        "cat": cats,
        "labels": rng.integers(0, 2, (n,)).astype(np.int32),
    }


def _system(devices, compute_dtype, optimizer=None):
    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=BUCKETS, embedding_dim=DIM, hidden=(32, 16),
        host_tier=False, compute_dtype=compute_dtype,
    )
    if optimizer is not None:
        spec = dataclasses.replace(spec, optimizer=optimizer)
    config = JobConfig(
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        embedding_lookup_impl="ragged_emulated",
    )
    trainer = Trainer(spec, config, create_mesh(devices, num_devices=4))
    assert trainer.ctx.embedding_impl == "ragged_emulated"
    return spec, trainer, trainer.init_state(jax.random.key(0))


def _reference_inputs(reference, spec, task, n):
    rows = reference.rows_of(task["cat"][:n].astype(np.uint32), BUCKETS)
    touched, relabelled = reference.compact(rows)
    params = reference.initial_params(spec, touched, DIM)
    return touched, relabelled, params


def test_forward_and_loss_match_the_reference(devices, reference, task):
    spec, trainer, state = _system(devices, "float32")
    first = {k: v[:MB] for k, v in task.items()}
    touched, rows, params = _reference_inputs(reference, spec, task, MB)
    assert len(touched) < MB * 26  # there ARE duplicates
    logits = np.asarray(jax.jit(reference.logits_fn)(params, rows, first["dense"]))
    got = np.asarray(trainer.run_predict_step(state, first))  # sigmoid(logit)
    np.testing.assert_allclose(got, 1 / (1 + np.exp(-logits)), rtol=0, atol=2e-6)
    want = float(jax.jit(reference.loss_fn)(params, rows, first["dense"], first["labels"].astype(np.float32)))
    _, metrics = trainer.train_step(state, trainer.shard_batch(first))
    assert float(metrics["loss"]) == pytest.approx(want, rel=2e-6)


def test_table_gradient_matches_the_reference(devices, reference, task):
    """Duplicate and cross-shard ids accumulate into one row's gradient.
    The system's gradient is read off one step of SGD at rate 1
    (before - after), exact to an ulp of the weights (1e-9)."""
    spec, trainer, state = _system(devices, "float32", optimizer=optax.sgd(1.0))
    first = {k: v[:MB] for k, v in task.items()}
    touched, rows, params = _reference_inputs(reference, spec, task, MB)
    grads = jax.jit(jax.grad(reference.loss_fn))(params, rows, first["dense"], first["labels"].astype(np.float32))
    want = np.concatenate([np.asarray(grads["v"]), np.asarray(grads["w"])[:, None]], -1)
    before = np.asarray(state.params["fm_table"])
    after, _ = trainer.train_step(state, trainer.shard_batch(first))
    moved = (before - np.asarray(after.params["fm_table"])).reshape(-1, 16)
    np.testing.assert_allclose(moved[touched, : DIM + 1], want, rtol=1e-5, atol=5e-9)
    assert np.abs(want).max() > 1e-4  # against a gradient that is there
    untouched = np.ones(len(moved), bool)
    untouched[touched] = False
    assert not moved[untouched].any() and not moved[:, DIM + 1:].any()
    assert moved.shape[0] * 16 == np.prod(table_shape(26 * BUCKETS, DIM + 1))


@pytest.mark.parametrize("compute_dtype, tolerance", [
    ("float32", F32_TOLERANCE), ("bfloat16", CONFIG_TOLERANCE),
])
def test_first_task_loss_matches_the_compacted_reference(devices, reference, task, compute_dtype, tolerance):
    """Eight dense-Adam steps: the reference trains only the rows the task
    touches (its docstring says why that is the full run)."""
    spec, trainer, state = _system(devices, compute_dtype)
    touched, rows, params = _reference_inputs(reference, spec, task, STEPS * MB)
    losses = reference.train_task(params, rows, task["dense"], task["labels"].astype(np.float32), STEPS, MB)
    stacked = {k: v.reshape((STEPS, MB) + v.shape[1:]) for k, v in task.items()}
    _, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(stacked))
    got = float(np.mean(np.asarray(metrics["loss"])))
    difference = abs(got - np.mean(losses)) / np.mean(losses)
    assert difference <= tolerance, (got, losses)
    if compute_dtype == "bfloat16":
        assert difference > F32_TOLERANCE  # a lower precision does not pass for float32
    assert losses[-1] < losses[0]  # and the task trains
