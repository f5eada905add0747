"""Async parameter-server mode (--use_async; SURVEY §2 #9 "async or
sync-by-version"): host-tier row pulls for batch n+1 overlap the in-flight
device step, reading rows one un-applied push stale."""

import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer


def _native_available() -> bool:
    from elasticdl_tpu.ps.host_store import native_lib_available

    return native_lib_available()


needs_native = pytest.mark.skipif(
    not _native_available(), reason="native lib unavailable"
)


def _spec():
    return load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=64, embedding_dim=8, hidden=(16,),
        host_tier=True, compute_dtype="float32",
    )


def _batches(n_batches, seed0=0, b=16):
    out = []
    for s in range(n_batches):
        rng = np.random.RandomState(seed0 + s)
        out.append({
            "dense": rng.rand(b, 13).astype(np.float32) * 100,
            "cat": rng.randint(0, 1 << 20, (b, 26)).astype(np.int64),
            "labels": rng.randint(0, 2, (b,)).astype(np.int32),
        })
    return out


def _run(devices, use_async, n_batches, async_staleness=1):
    """Depth pinned to 1 (not the config default, which a measurement may
    move): these tests characterize
    the CLASSIC async window and its sync equivalence."""
    import jax

    spec = _spec()
    trainer = Trainer(
        spec,
        JobConfig(
            distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
            async_staleness=async_staleness,
        ),
        create_mesh(devices[:4]),
    )
    state = trainer.init_state(jax.random.key(0))
    state, metrics = trainer.run_train_steps(
        state, _batches(n_batches), use_async=use_async
    )
    key = list(spec.host_io)[0]
    probe = np.arange(64, dtype=np.int64)
    return [float(m["loss"]) for m in metrics], trainer._host_stores[key].pull(probe)


@needs_native
def test_single_batch_async_equals_sync(devices):
    """With one batch there is nothing to overlap: the pipeline degenerates
    to pull->step->push and must match sync bit-for-bit (losses AND rows)."""
    sync_losses, sync_rows = _run(devices, use_async=False, n_batches=1)
    async_losses, async_rows = _run(devices, use_async=True, n_batches=1)
    assert async_losses == sync_losses
    np.testing.assert_array_equal(async_rows, sync_rows)


@needs_native
def test_async_staleness_bounded_by_one(devices):
    """Multi-batch: batch 0's loss is identical (same fresh rows); later
    batches may see 1-push-stale rows, but every push still lands and
    training still converges."""
    sync_losses, sync_rows = _run(devices, use_async=False, n_batches=4)
    async_losses, async_rows = _run(devices, use_async=True, n_batches=4)
    assert async_losses[0] == sync_losses[0]
    assert all(np.isfinite(async_losses))
    assert async_losses[-1] < async_losses[0]
    # Every push landed: rows this run touched moved off the sync run's
    # values by at most a staleness-induced delta, never back to init —
    # compare against a NEVER-trained store's deterministic init rows.
    _, init_rows = _run(devices, use_async=True, n_batches=0)
    trained_mask = np.any(sync_rows != init_rows, axis=-1)
    assert trained_mask.any()
    # Async trained the same touched rows (all 4 batches' pushes applied).
    async_moved = np.any(async_rows != init_rows, axis=-1)
    np.testing.assert_array_equal(async_moved, trained_mask)


@needs_native
def test_worker_task_uses_async_driver(devices, monkeypatch):
    """--use_async reaches the trainer through the worker's training-task
    loop, and metrics aggregate across the task's minibatches either way."""
    import jax

    from elasticdl_tpu.data.reader import Shard, create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.task_dispatcher import TASK_TRAINING, Task
    from elasticdl_tpu.worker.worker import Worker

    import tempfile, os

    tmp = tempfile.mkdtemp()
    path = os.path.join(tmp, "criteo.rio")
    generate("criteo", path, 48)
    spec = _spec()
    config = JobConfig(
        model_def="deepfm.model_spec",
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        training_data=path,
        minibatch_size=16,
        use_async=True,
    )
    reader = create_data_reader(path)
    worker = Worker(
        config, master=None, reader=reader, spec=spec, devices=jax.devices()[:4]
    )
    worker._apply_membership(
        {"version": 0, "world_size": 1, "ranks": {"w": 0}}, initial=True
    )
    worker.state = worker.trainer.init_state(jax.random.key(0))

    seen = {}
    orig = Trainer.run_train_steps

    def spy(self, state, batches, use_async=False, pre_sharded=False):
        seen["use_async"] = use_async
        return orig(
            self, state, batches, use_async=use_async, pre_sharded=pre_sharded
        )

    monkeypatch.setattr(Trainer, "run_train_steps", spy)
    task = Task(task_id=0, shard=Shard(name=path, start=0, end=48), type=TASK_TRAINING)
    metrics = worker._run_training_task(task)
    assert seen["use_async"] is True
    assert np.isfinite(metrics["loss"])


@needs_native
def test_async_depth_parameter(devices):
    """--async_staleness D: pulls may see up to D un-applied pushes, but
    every push still lands by the end of the run; depth 1 reproduces the
    r3 behavior exactly."""
    import jax

    spec = _spec()
    for depth in (1, 2, 4, 8):  # 8 > n_batches: everything drains at end
        trainer = Trainer(
            spec,
            JobConfig(
                distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
                async_staleness=depth,
            ),
            create_mesh(devices[:4]),
        )
        pushes = []
        orig = trainer._push_host_grads
        trainer._push_host_grads = lambda *a: (pushes.append(1), orig(*a))[1]
        state = trainer.init_state(jax.random.key(0))
        state, metrics = trainer.run_train_steps(
            state, _batches(5), use_async=True
        )
        assert len(pushes) == 5, f"depth {depth}: every push must land"
        assert all(np.isfinite(float(m["loss"])) for m in metrics)

    # depth 1 == the old pipeline bit-for-bit
    l1, r1 = _run(devices, use_async=True, n_batches=4)
    trainer = Trainer(
        spec,
        JobConfig(
            distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
            async_staleness=1,
        ),
        create_mesh(devices[:4]),
    )
    state = trainer.init_state(jax.random.key(0))
    state, metrics = trainer.run_train_steps(
        state, _batches(4), use_async=True
    )
    key = list(spec.host_io)[0]
    probe = np.arange(64, dtype=np.int64)
    np.testing.assert_array_equal(
        trainer._host_stores[key].pull(probe), r1
    )
    assert [float(m["loss"]) for m in metrics] == l1
