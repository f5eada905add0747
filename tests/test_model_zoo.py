"""Model-zoo parity tests (BASELINE.json configs #2-#4): every model runs a
step on the 8-device mesh under its intended strategy, trains, and — the key
hybrid check — the ParameterServer (mesh-sharded tables) step matches the
AllReduce (replicated tables) step numerically on the same global batch."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

BATCH = 64


def _tabular_batch(rng, n, n_dense, n_cat, max_id=5000):
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "dense": jax.random.uniform(k1, (n, n_dense), jnp.float32, 0, 100),
        "cat": jax.random.randint(k2, (n, n_cat), 0, max_id),
        "labels": jax.random.bernoulli(k3, 0.3, (n,)).astype(jnp.int32),
    }


def _cifar_batch(rng, n):
    k1, k2 = jax.random.split(rng)
    return {
        "images": jax.random.normal(k1, (n, 32, 32, 3), jnp.float32),
        "labels": jax.random.randint(k2, (n,), 0, 10),
    }


def _deepfm_spec():
    return load_model_spec(
        "elasticdl_tpu.models",
        "deepfm.model_spec",
        compute_dtype="float32",
        buckets_per_feature=64,
        hidden=(32, 32),
    )


def _widedeep_spec():
    return load_model_spec(
        "elasticdl_tpu.models",
        "wide_deep.model_spec",
        compute_dtype="float32",
        buckets=32,
        hidden=(32,),
    )


def _resnet_spec():
    return load_model_spec(
        "elasticdl_tpu.models",
        "cifar10_resnet.model_spec",
        compute_dtype="float32",
        depth=14,
        width=8,
    )


@pytest.mark.parametrize(
    "spec_fn,batch_fn",
    [
        (_deepfm_spec, lambda r, n: _tabular_batch(r, n, 13, 26)),
        (_widedeep_spec, lambda r, n: _tabular_batch(r, n, 5, 9)),
    ],
    ids=["deepfm", "wide_deep"],
)
def test_ps_strategy_step_and_convergence(devices, spec_fn, batch_fn):
    spec = spec_fn()
    mesh = create_mesh(devices)
    cfg = JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER)
    trainer = Trainer(spec, cfg, mesh)
    assert trainer.sharded_embeddings
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(batch_fn(jax.random.key(1), BATCH))
    first = None
    for _ in range(8):
        state, metrics = trainer.train_step(state, batch)
        if first is None:
            first = float(metrics["loss"])
        assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first


@pytest.mark.parametrize("impl", ["dense", "ragged_emulated"])
@pytest.mark.parametrize(
    "spec_fn,batch_fn",
    [
        (_deepfm_spec, lambda r, n: _tabular_batch(r, n, 13, 26)),
        (_widedeep_spec, lambda r, n: _tabular_batch(r, n, 5, 9)),
    ],
    ids=["deepfm", "wide_deep"],
)
def test_ps_matches_allreduce(devices, spec_fn, batch_fn, impl):
    """The hybrid's sharded-table path must produce the same update as plain
    replicated-table allreduce — the decisive numerics check for the
    collective embedding transpose (both lookup routes)."""
    batch = batch_fn(jax.random.key(2), BATCH)
    results = {}
    for strategy in (
        DistributionStrategy.ALLREDUCE,
        DistributionStrategy.PARAMETER_SERVER,
    ):
        spec = spec_fn()
        mesh = create_mesh(devices)
        trainer = Trainer(
            spec,
            JobConfig(
                distribution_strategy=strategy, embedding_lookup_impl=impl
            ),
            mesh,
        )
        state = trainer.init_state(jax.random.key(0))
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        results[strategy] = (
            jax.device_get(state.params),
            float(metrics["loss"]),
        )

    p_ar, loss_ar = results[DistributionStrategy.ALLREDUCE]
    p_ps, loss_ps = results[DistributionStrategy.PARAMETER_SERVER]
    assert abs(loss_ar - loss_ps) < 1e-5
    for a, b in zip(jax.tree.leaves(p_ar), jax.tree.leaves(p_ps)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_resnet_allreduce_step(devices):
    spec = _resnet_spec()
    mesh = create_mesh(devices)
    trainer = Trainer(spec, JobConfig(), mesh)
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_cifar_batch(jax.random.key(1), 32))
    first = None
    for _ in range(5):
        state, metrics = trainer.train_step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert np.isfinite(float(metrics["loss"]))
    assert float(metrics["loss"]) < first


def test_resnet50_builds():
    """Full-size ResNet-50 param shapes build without error (no step — slow on
    fake CPU devices; the real-chip bench covers execution)."""
    spec = load_model_spec(
        "elasticdl_tpu.models", "cifar10_resnet.model_spec", depth=50
    )
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert n_params > 20_000_000  # ResNet-50 class size


def test_resnet_imagenet_stem_variant(devices):
    """The ImageNet-shaped configuration (224x224 input, 1000-class head,
    7x7/s2 stem + maxpool) trains
    a step at a reduced size: the stride-2 stem halves the spatial dims
    twice before the stages, and the head width follows num_classes."""
    spec = load_model_spec(
        "elasticdl_tpu.models",
        "cifar10_resnet.model_spec",
        compute_dtype="float32",
        depth=14,
        width=8,
        image_size=64,
        num_classes=7,
        imagenet_stem=True,
    )
    mesh = create_mesh(devices)
    cfg = JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE)
    trainer = Trainer(spec, cfg, mesh)
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.RandomState(3)
    batch = {
        "images": rng.rand(16, 64, 64, 3).astype(np.float32),
        "labels": rng.randint(0, 7, (16,)).astype(np.int32),
    }
    logits = spec.apply(jax.device_get(state).params, batch, train=False)
    assert logits.shape == (16, 7)
    state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    assert np.isfinite(float(metrics["loss"]))
    # Full-size shapes build: 1000-class ImageNet head + 7x7 stem kernel.
    full = load_model_spec(
        "elasticdl_tpu.models", "cifar10_resnet.model_spec",
        depth=50, image_size=224, num_classes=1000, imagenet_stem=True,
    )
    shapes = jax.eval_shape(full.init, jax.random.key(0))
    assert shapes["stem"]["conv"].shape == (7, 7, 3, 64)
    assert shapes["head"]["w"].shape[-1] == 1000
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    assert 24_000_000 < n_params < 27_000_000  # ImageNet ResNet-50 ~25.6M
