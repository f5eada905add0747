"""AllReduce trainer: parity of the mesh-psum step with a single-device step,
and convergence on a learnable toy problem — the TPU-native analogue of the
reference's AllReduceTrainer unit tests (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer


def _batch(rng, n=64):
    images = jax.random.normal(rng, (n, 28, 28, 1), jnp.float32)
    labels = jax.random.randint(jax.random.fold_in(rng, 1), (n,), 0, 10)
    return {"images": images, "labels": labels}


@pytest.fixture(scope="module")
def spec():
    return load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )


def test_step_runs_on_8_device_mesh(spec, devices):
    mesh = create_mesh(devices)
    trainer = Trainer(spec, JobConfig(), mesh)
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_batch(jax.random.key(1)))
    new_state, metrics = trainer.train_step(state, batch)
    assert int(new_state.step) == 1
    assert np.isfinite(float(metrics["loss"]))
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0


def test_psum_step_matches_single_device(spec, devices):
    """Same global batch, mesh of 8 vs mesh of 1 => identical updates."""
    batch = _batch(jax.random.key(2), n=32)

    results = []
    for n_dev in (1, 8):
        mesh = create_mesh(devices, num_devices=n_dev)
        trainer = Trainer(spec, JobConfig(), mesh)
        state = trainer.init_state(jax.random.key(0))
        sharded = trainer.shard_batch(batch)
        state, metrics = trainer.train_step(state, sharded)
        results.append((jax.device_get(state.params), float(metrics["loss"])))

    p1, loss1 = results[0]
    p8, loss8 = results[1]
    assert abs(loss1 - loss8) < 1e-4
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p8)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)


def test_loss_decreases(spec, devices):
    mesh = create_mesh(devices)
    trainer = Trainer(spec, JobConfig(), mesh)
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_batch(jax.random.key(3), n=64))
    first = None
    for _ in range(10):
        state, metrics = trainer.train_step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    assert float(metrics["loss"]) < first


def test_eval_step(spec, devices):
    mesh = create_mesh(devices)
    trainer = Trainer(spec, JobConfig(), mesh)
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_batch(jax.random.key(4)))
    metrics = trainer.eval_step(state, batch)
    assert set(metrics) >= {"accuracy", "loss"}


def test_masked_train_tail_matches_unpadded(spec, devices):
    """A wrap-padded training tail with ``__mask__`` must produce EXACTLY the
    update of the true partial batch: padded duplicates carry zero gradient
    (VERDICT r3 item 4 — eval got the mask in r3, training gets it here)."""
    real, padded_size = 10, 16
    b = _batch(jax.random.key(7), n=real)
    # Wrap-pad like worker._minibatches: records repeat cyclically.
    idx = np.arange(padded_size) % real
    padded = {k: np.asarray(v)[idx] for k, v in b.items()}
    padded["__mask__"] = (np.arange(padded_size) < real).astype(np.float32)

    mesh = create_mesh(devices[:1])
    trainer_m = Trainer(spec, JobConfig(), mesh)
    state = trainer_m.init_state(jax.random.key(0))
    host_state = jax.device_get(state)  # before the step donates its buffers
    masked_state, masked_metrics = trainer_m.train_step(
        state, trainer_m.shard_batch(padded)
    )

    trainer_t = Trainer(spec, JobConfig(), mesh)
    state_t = trainer_t.shard_state(host_state)
    truth_state, truth_metrics = trainer_t.train_step(
        state_t,
        trainer_t.shard_batch({k: np.asarray(v) for k, v in b.items()}),
    )

    assert abs(
        float(masked_metrics["loss"]) - float(truth_metrics["loss"])
    ) < 1e-6
    for a, t in zip(
        jax.tree.leaves(jax.device_get(masked_state.params)),
        jax.tree.leaves(jax.device_get(truth_state.params)),
    ):
        np.testing.assert_allclose(a, t, rtol=1e-5, atol=1e-6)


def test_masked_tail_differs_from_unmasked_padding(spec, devices):
    """Without the mask the duplicated examples double-count (the r3 bug);
    this pins that the mask actually changes the update."""
    real, padded_size = 10, 16
    b = _batch(jax.random.key(8), n=real)
    idx = np.arange(padded_size) % real
    padded = {k: np.asarray(v)[idx] for k, v in b.items()}
    mesh = create_mesh(devices[:1])
    trainer = Trainer(spec, JobConfig(), mesh)
    state = trainer.init_state(jax.random.key(0))
    host_state = jax.device_get(state)
    unmasked_state, _ = trainer.train_step(state, trainer.shard_batch(padded))

    masked = dict(padded)
    masked["__mask__"] = (np.arange(padded_size) < real).astype(np.float32)
    trainer2 = Trainer(spec, JobConfig(), mesh)
    state2 = trainer2.shard_state(host_state)
    masked_state, _ = trainer2.train_step(state2, trainer2.shard_batch(masked))
    diffs = [
        np.max(np.abs(np.asarray(a) - np.asarray(t)))
        for a, t in zip(
            jax.tree.leaves(jax.device_get(masked_state.params)),
            jax.tree.leaves(jax.device_get(unmasked_state.params)),
        )
    ]
    assert max(diffs) > 1e-7


def test_train_scan_matches_step_loop(spec, devices):
    """The fused lax.scan task (one dispatch, T steps) must produce the
    same params and per-step losses as T individual train_step calls."""
    T, mb = 3, 16
    rng = np.random.default_rng(4)
    stacked_host = {
        "images": rng.standard_normal((T, mb, 28, 28, 1)).astype(np.float32),
        "labels": rng.integers(0, 10, (T, mb)).astype(np.int32),
    }
    mesh = create_mesh(devices)

    trainer_a = Trainer(spec, JobConfig(), mesh)
    state = trainer_a.init_state(jax.random.key(0))
    host_state = jax.device_get(state)
    loop_losses = []
    for t in range(T):
        batch = {k: v[t] for k, v in stacked_host.items()}
        state, m = trainer_a.train_step(state, trainer_a.shard_batch(batch))
        loop_losses.append(float(m["loss"]))
    loop_params = jax.device_get(state.params)

    trainer_b = Trainer(spec, JobConfig(), mesh)
    state_b = trainer_b.shard_state(host_state)
    state_b, metrics = trainer_b.train_scan(
        state_b, trainer_b.shard_stacked_batch(stacked_host)
    )
    scan_losses = [float(x) for x in np.asarray(metrics["loss"])]
    np.testing.assert_allclose(scan_losses, loop_losses, rtol=1e-5, atol=1e-6)
    assert int(state_b.step) == T
    for a, b in zip(
        jax.tree.leaves(loop_params),
        jax.tree.leaves(jax.device_get(state_b.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_sharded_optimizer_matches_replicated(spec, devices):
    """ZeRO-sharded update parity: same seed, same batches, 3 steps on a
    4-way mesh — sharded params track replicated to float32 last-ulp
    (psum vs psum_scatter reduce in different ring orders, so exact bit
    equality is not guaranteed; the RESIZE path, which is pure data
    movement, is asserted bit-exact in test_elastic)."""
    mesh = create_mesh(devices, num_devices=4)
    tr = Trainer(spec, JobConfig(), mesh)
    state_r = tr.init_state(jax.random.key(0))
    ts = Trainer(spec, JobConfig(optimizer_sharding="sharded"), mesh)
    state_s = ts.init_state(jax.random.key(0))

    # The memory claim itself: each device holds ~1/4 of the param-shaped
    # optimizer slots instead of a full copy.
    rep = max(tr.opt_state_bytes_per_device(state_r).values())
    sh = max(ts.opt_state_bytes_per_device(state_s).values())
    assert sh <= rep / 4 * 1.05 + 1024  # /dp plus padding slack

    for i in range(3):
        b = _batch(jax.random.key(20 + i))
        state_r, m_r = tr.train_step(state_r, tr.shard_batch(b))
        state_s, m_s = ts.train_step(state_s, ts.shard_batch(b))
    assert abs(float(m_r["loss"]) - float(m_s["loss"])) < 1e-6
    for a, b in zip(
        jax.tree.leaves(jax.device_get(state_r.params)),
        jax.tree.leaves(jax.device_get(state_s.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-7)


@pytest.mark.parametrize("dp", [2, 8])
def test_sharded_optimizer_bytes_per_replica(spec, devices, dp):
    """The memory claim at the other widths (4-way is asserted above):
    each replica holds at most 1/dp of the param-shaped optimizer slots
    plus padding, where the replicated layout holds a full copy."""
    mesh = create_mesh(devices, num_devices=dp)
    tr = Trainer(spec, JobConfig(), mesh)
    ts = Trainer(spec, JobConfig(optimizer_sharding="sharded"), mesh)
    rep = max(tr.opt_state_bytes_per_device(tr.init_state(jax.random.key(0))).values())
    sh = max(ts.opt_state_bytes_per_device(ts.init_state(jax.random.key(0))).values())
    assert sh <= rep / dp * 1.05 + 1024


def test_sharded_train_scan_matches_step_loop(spec, devices):
    """The fused lax.scan task must carry the FLAT sharded optimizer state
    through its scan body identically to per-step dispatch."""
    T, mb = 3, 16
    rng = np.random.default_rng(9)
    stacked = {
        "images": rng.standard_normal((T, mb, 28, 28, 1)).astype(np.float32),
        "labels": rng.integers(0, 10, (T, mb)).astype(np.int32),
    }
    mesh = create_mesh(devices, num_devices=4)
    cfg = JobConfig(optimizer_sharding="sharded")
    t1 = Trainer(spec, cfg, mesh)
    state = t1.init_state(jax.random.key(0))
    host = t1.host_state(state)
    losses = []
    for t in range(T):
        b = {k: v[t] for k, v in stacked.items()}
        state, m = t1.train_step(state, t1.shard_batch(b))
        losses.append(float(m["loss"]))

    t2 = Trainer(spec, cfg, mesh)
    state2 = t2.shard_state(host)
    state2, metrics = t2.train_scan(state2, t2.shard_stacked_batch(stacked))
    np.testing.assert_allclose(
        [float(x) for x in np.asarray(metrics["loss"])], losses,
        rtol=1e-5, atol=1e-6,
    )
    for a, b in zip(
        jax.tree.leaves(jax.device_get(state.params)),
        jax.tree.leaves(jax.device_get(state2.params)),
    ):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def test_auto_mode_thresholds_on_state_size(spec, devices):
    """auto = sharded iff the replicated dense optimizer state exceeds the
    threshold; dp=1 meshes never shard (nothing to cut)."""
    mesh = create_mesh(devices, num_devices=4)
    big = Trainer(
        spec,
        JobConfig(optimizer_sharding="auto", optimizer_sharding_auto_mb=1e-3),
        mesh,
    )
    big.init_state(jax.random.key(0))
    assert big._opt_plan is not None
    small = Trainer(
        spec,
        JobConfig(optimizer_sharding="auto", optimizer_sharding_auto_mb=1e6),
        mesh,
    )
    small.init_state(jax.random.key(0))
    assert small._opt_plan is None
    one = Trainer(
        spec, JobConfig(optimizer_sharding="sharded"),
        create_mesh(devices, num_devices=1),
    )
    one.init_state(jax.random.key(0))
    assert one._opt_plan is None


def test_donation_knob_off_keeps_input_state_alive(spec, devices):
    """--donate_train_state=false: the jitted step must NOT consume its
    input buffers (the debugging trade documented in common/config.py)."""
    mesh = create_mesh(devices, num_devices=2)
    t = Trainer(spec, JobConfig(donate_train_state=False), mesh)
    state = t.init_state(jax.random.key(0))
    new_state, _ = t.train_step(state, t.shard_batch(_batch(jax.random.key(3))))
    assert not any(
        leaf.is_deleted() for leaf in jax.tree.leaves(state)
    )
    assert int(new_state.step) == 1
