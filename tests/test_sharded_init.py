"""The one jitted, sharded-out init (``Trainer.init_state``, PR 26): the
same logical state on any number of devices, every device born holding its
shard only; DeepFM's table born lane-packed from per-element counters."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops.embedding import (
    _flat_position,
    normal_packed_table,
    pack_table,
    table_shape,
)
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import TrainState, Trainer

BUCKETS, DIM = 300, 10


def _spec():
    return load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=BUCKETS, embedding_dim=DIM, hidden=(16, 16),
        host_tier=False,
    )


def _trainer(devices, n, **cfg):
    config = JobConfig(
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER, **cfg
    )
    return Trainer(_spec(), config, create_mesh(devices, num_devices=n))


def test_packed_normal_table_is_the_plain_normal_packed():
    """Logical row r holds jax.random.normal(key, (V, live))[r] * scale:
    bit for bit when run op by op; inside a jit XLA fuses the two constant
    factors (sqrt(2) of the normal, the scale), so there within one ulp."""
    key = jax.random.key(7)
    vocab = 26 * BUCKETS
    plain = jnp.concatenate(
        [jax.random.normal(key, (vocab, DIM)) * 0.01, jnp.zeros((vocab, 1))], -1
    )
    want = np.asarray(pack_table(plain, DIM + 1))
    got = np.asarray(normal_packed_table(key, vocab, DIM + 1, live_dim=DIM))
    assert got.shape == table_shape(vocab, DIM + 1)
    np.testing.assert_array_equal(got, want)
    jitted = jax.jit(
        lambda k: normal_packed_table(k, vocab, DIM + 1, live_dim=DIM)
    )(key)
    np.testing.assert_array_max_ulp(np.asarray(jitted), want, maxulp=1)
    # dead lanes, the first-order lane and the padding rows are zero
    logical = got.reshape(-1, 16)
    assert not logical[:, DIM:].any() and not logical[vocab:].any()
    assert logical[:vocab, :DIM].all()


@pytest.mark.parametrize("row, col, width", [
    (0, 0, 10), (65535, 9, 10), (65536, 0, 10), (429496729, 5, 10),
    (429496730, 0, 10), (4294967295, 15, 16), (3000000000, 7, 65535),
])
def test_flat_position_is_the_64_bit_product(row, col, width):
    hi, lo = _flat_position(
        jnp.asarray([row], jnp.uint32), jnp.asarray([col], jnp.uint32), width
    )
    assert (int(hi[0]) << 32) + int(lo[0]) == row * width + col


def test_packed_normal_table_refuses_what_its_counters_cannot_hold():
    with pytest.raises(ValueError, match="live_dim"):
        normal_packed_table(jax.random.key(0), 64, 8, live_dim=9)
    with pytest.raises(ValueError, match="32-bit row counter"):
        normal_packed_table(jax.random.key(0), 1 << 32, 8)


@pytest.fixture(scope="module")
def one_device_state():
    trainer = _trainer(jax.devices(), 1)
    return jax.device_get(trainer.init_state(jax.random.key(0)))


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_init_gives_the_same_state_on_any_number_of_devices(devices, one_device_state, n):
    """Params AND optimizer state, leaf by leaf, bit for bit; the table and
    its moments live 1/n on each device, the dense part whole."""
    trainer = _trainer(devices, n)
    state = trainer.init_state(jax.random.key(0))
    assert trainer.init_state_s > 0
    for got, want in zip(jax.tree.leaves(state), jax.tree.leaves(one_device_state)):
        np.testing.assert_array_equal(np.asarray(got), want)
    rows, width = table_shape(26 * BUCKETS, DIM + 1)
    table_like = [leaf for leaf in jax.tree.leaves(state) if leaf.shape == (rows, width)]
    assert len(table_like) == 3  # rows, mu, nu
    for leaf in table_like:
        shards = leaf.addressable_shards
        assert len(shards) == n
        assert {s.data.shape for s in shards} == {(rows // n, width)}
        starts = sorted(s.index[0].start or 0 for s in shards)
        assert starts == [i * rows // n for i in range(n)]
    dense = state.params["mlp"]["layer0"]["w"]
    assert {s.data.shape for s in dense.addressable_shards} == {dense.shape}


@pytest.mark.parametrize("mode", ["replicated", "sharded"])
def test_init_state_is_what_shard_state_places(devices, mode):
    """``shard_state`` (restore, elastic reform) lays the same logical
    state out the same way the jitted init bears it: same shardings, same
    values, in both optimizer layouts (``sharded`` keeps dense moments flat
    and padded over dp)."""
    trainer = _trainer(devices, 4, optimizer_sharding=mode)
    born = trainer.init_state(jax.random.key(0))
    plan = trainer._opt_plan
    assert (plan is not None) == (mode == "sharded")
    spec = trainer.spec
    params = spec.init(jax.random.key(0))
    placed = trainer.shard_state(TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=spec.optimizer.init(params),
    ))
    assert jax.tree.structure(born) == jax.tree.structure(placed)
    for a, b in zip(jax.tree.leaves(born), jax.tree.leaves(placed)):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert a.sharding.is_equivalent_to(b.sharding, a.ndim)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_init_logs_what_a_chip_holds_of_the_table(devices, monkeypatch):
    from elasticdl_tpu.parallel import trainer as trainer_module

    lines = []
    monkeypatch.setattr(
        trainer_module.logger, "info", lambda msg, *args: lines.append(msg % args)
    )
    _trainer(devices, 4).init_state(jax.random.key(0))
    (line,) = [text for text in lines if "embedding table" in text]
    assert "7800 logical rows of 11 floats" in line and "row-sharded 4 ways" in line


def test_route_rows_ride_the_step_metrics_on_the_ragged_route(devices):
    """Rows a shard RECEIVED over the route, max and mean over shards: the
    mean is the lookups a shard asks for (every id is served once), the max
    at least that."""
    trainer = _trainer(devices, 4, embedding_lookup_impl="ragged_emulated")
    state = trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    batch = {
        "dense": rng.random((3, 64, 13)).astype(np.float32),
        "cat": rng.integers(0, 2**31 - 1, (3, 64, 26)).astype(np.int32),
        "labels": rng.integers(0, 2, (3, 64)).astype(np.int32),
    }
    _, metrics = trainer.train_scan(state, trainer.shard_stacked_batch(batch))
    mean = np.asarray(metrics["route_rows_recv_mean"])
    peak = np.asarray(metrics["route_rows_recv_max"])
    np.testing.assert_array_equal(mean, [64 * 26 / 4] * 3)
    assert (peak >= mean).all() and (peak <= 64 * 26).all()
    dense = _trainer(devices, 4, embedding_lookup_impl="dense")
    _, metrics = dense.train_scan(
        dense.init_state(jax.random.key(0)), dense.shard_stacked_batch(batch)
    )
    assert not [k for k in metrics if k.startswith("route_")]


@pytest.mark.parametrize(
    "n, impl, rows_a_step",
    [
        (1, "auto", 64 * 26),             # local gather: the ids inside the table
        (4, "ragged_emulated", 64 * 26),  # every id reaches exactly one shard
        (4, "dense", 4 * 64 * 26),        # every shard gathers every id
    ],
)
def test_table_grad_rows_ride_the_step_metrics(devices, monkeypatch, n, impl, rows_a_step):
    """Update rows offered to the table's gradient, those of them the
    merge sweep delivered, and those whose table it updated itself: none
    on the CPU, all of them where the program would choose the sweep
    (platform and threshold steered here; the kernel itself runs in the
    interpreter) — DeepFM declares plain Adam, so the sweep applies it."""
    from elasticdl_tpu.ops import embedding

    rng = np.random.default_rng(0)
    batch = {
        "dense": rng.random((2, 64, 13)).astype(np.float32),
        "cat": rng.integers(0, 2**31 - 1, (2, 64, 26)).astype(np.int32),
        "labels": rng.integers(0, 2, (2, 64)).astype(np.int32),
    }

    def counts():
        trainer = _trainer(devices, n, embedding_lookup_impl=impl)
        _, metrics = trainer.train_scan(
            trainer.init_state(jax.random.key(0)), trainer.shard_stacked_batch(batch)
        )
        return (
            np.asarray(metrics["table_grad_rows"]).tolist(),
            np.asarray(metrics["table_grad_rows_swept"]).tolist(),
            np.asarray(metrics["table_grad_rows_fused"]).tolist(),
            np.asarray(metrics["loss"]),
        )

    rows, swept, fused, loss = counts()
    assert rows == [rows_a_step] * 2 and swept == fused == [0, 0]
    monkeypatch.setattr(embedding, "_on_tpu", lambda: True)
    monkeypatch.setattr(embedding, "SWEEP_MIN_ROWS", 8)
    rows, swept, fused, swept_loss = counts()
    assert rows == swept == fused == [rows_a_step] * 2
    # the second step's loss has been through one swept table gradient
    np.testing.assert_allclose(swept_loss, loss, rtol=1e-6)
