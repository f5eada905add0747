"""Hybrid-parallel 2D (data x model) mesh — r20.

The legal-shape resolver and dp_factorization's multi-axis behavior;
tensor-parallel transformer_lm parity against its own 1-D run; the
elastic 2D re-partitioner (re-lowers exactly once, shape-preserving
reforms add zero recompiles, moments carried bit-exactly); cross-shape
checkpoint restore; and the mesh-shape observability surface.
"""

import types

import jax
import numpy as np
import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.parallel import mesh as mesh_mod
from elasticdl_tpu.parallel.mesh import (
    create_mesh,
    dp_factorization,
    mesh_shape,
    resolve_2d_shape,
)
from elasticdl_tpu.parallel.trainer import Trainer
from elasticdl_tpu.models.spec import load_model_spec

SEQ = 32
VOCAB = 128


def _tp_spec(**kw):
    params = dict(
        compute_dtype="float32", vocab=VOCAB, dim=32, n_heads=4,
        n_layers=2, max_seq=SEQ, seq_len=SEQ, parallelism="tensor",
    )
    params.update(kw)
    return load_model_spec(
        "elasticdl_tpu.models", "transformer_lm.model_spec", **params
    )


def _batch(rng, b=8):
    toks = rng.integers(0, VOCAB, size=(b, SEQ + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


# ---- legal-shape resolver ----


def test_resolve_2d_shape_prefers_shrinking_dp():
    """tp is a model-fit constraint: reform keeps it and shrinks dp;
    only when fewer than tp devices remain does tp degrade, and then
    only along the configured degree's divisor chain."""
    assert resolve_2d_shape(8, 4) == (2, 4)
    assert resolve_2d_shape(4, 4) == (1, 4)  # lost a host: dp 2 -> 1
    assert resolve_2d_shape(8, 2) == (4, 2)
    assert resolve_2d_shape(8, 1) == (8, 1)
    assert resolve_2d_shape(2, 4) == (1, 2)  # < tp devices: divisor chain
    assert resolve_2d_shape(3, 4) == (1, 2)
    assert resolve_2d_shape(1, 4) == (1, 1)
    # dp * tp may undershoot: the remainder idles, the axis stays regular.
    assert resolve_2d_shape(7, 2) == (3, 2)
    with pytest.raises(ValueError, match="at least one device"):
        resolve_2d_shape(0, 2)


def test_create_mesh_2d_axes_and_shape(devices):
    mesh = create_mesh(devices, num_devices=8, tensor_parallelism=4)
    assert mesh.axis_names == ("dp", "tp")
    assert dict(mesh.shape) == {"dp": 2, "tp": 4}
    assert mesh_shape(mesh) == (2, 4)
    # The (dp, tp) view is total over every mesh kind.
    assert mesh_shape(create_mesh(devices, num_devices=4)) == (4, 1)
    assert mesh_shape(
        create_mesh(devices, num_devices=8, dcn_parallelism=2)
    ) == (8, 1)
    with pytest.raises(ValueError, match="mutually exclusive"):
        create_mesh(
            devices, num_devices=8, dcn_parallelism=2, tensor_parallelism=2
        )
    with pytest.raises(ValueError, match="does not divide"):
        create_mesh(devices, num_devices=8, tensor_parallelism=3)


# ---- dp_factorization on multi-axis / exotic device orders ----


class _Dev:
    def __init__(self, process_index):
        self.process_index = process_index


def _stub_mesh(grid, axis_names):
    return types.SimpleNamespace(
        devices=np.array(grid, dtype=object), axis_names=axis_names
    )


def _procs(*indexes):
    return [_Dev(i) for i in indexes]


def test_dp_factorization_multi_axis_process_pairs():
    """The dp axis of a (dp, tp) mesh whose positions are owned by
    disjoint process GROUPS factors by those groups — each dp row is one
    'host' of the hierarchy."""
    row0 = _procs(0, 0, 1, 1)  # dp row 0: processes {0, 1}
    row1 = _procs(2, 2, 3, 3)  # dp row 1: processes {2, 3}
    mesh = _stub_mesh([row0, row1], ("dp", "tp"))
    assert dp_factorization(mesh) == (2, 1)


def test_dp_factorization_contiguous_1d():
    mesh = _stub_mesh(_procs(0, 0, 0, 0, 1, 1, 1, 1), ("dp",))
    assert dp_factorization(mesh) == (2, 4)


def test_dp_factorization_ragged_demotes_silently(monkeypatch):
    """Unequal per-process runs have no clean hierarchy: flat (1, n),
    and — single-owner positions — without the multi-axis warning."""
    warned = []
    monkeypatch.setattr(
        mesh_mod.logger, "warning", lambda *a, **k: warned.append(a)
    )
    mesh = _stub_mesh(_procs(0, 0, 0, 1), ("dp",))
    assert dp_factorization(mesh) == (1, 4)
    assert not warned


def test_dp_factorization_tp_major_demotes_loudly(monkeypatch):
    """A tp-major order threads every process through every dp position
    (owner sets identical along the axis): a real host hierarchy is
    being hidden by the device order, so the demotion to flat WARNS."""
    warned = []
    monkeypatch.setattr(
        mesh_mod.logger, "warning", lambda *a, **k: warned.append(a)
    )
    row0 = _procs(0, 1)  # dp position 0 spans BOTH processes...
    row1 = _procs(0, 1)  # ...and so does position 1: no grouping.
    mesh = _stub_mesh([row0, row1], ("dp", "tp"))
    assert dp_factorization(mesh) == (1, 2)
    assert warned


def test_dp_factorization_overlapping_groups_demote_loudly(monkeypatch):
    """Owner groups that re-use a process across runs overlap — equally
    sized runs are not enough; the union must be disjoint."""
    warned = []
    monkeypatch.setattr(
        mesh_mod.logger, "warning", lambda *a, **k: warned.append(a)
    )
    mesh = _stub_mesh(
        [_procs(0, 1), _procs(1, 2)], ("dp", "tp")
    )
    assert dp_factorization(mesh) == (1, 2)
    assert warned


def test_dp_factorization_single_process_2d(devices):
    """The real fake-device world is single-process: the dp axis of a
    live (dp, tp) mesh demotes to flat quietly (nothing to exploit)."""
    mesh = create_mesh(devices, num_devices=8, tensor_parallelism=4)
    assert dp_factorization(mesh) == (1, 2)


# ---- tensor-parallel parity ----


def test_tensor_parallel_matches_1d(devices, default_compile_level):
    """Column/row-split attention + MLP through the tp psum reproduce the
    dense math: same spec, same batches, 1-D dp=2 vs 2-D (dp=2, tp=2) —
    losses within float32 reduction-order noise for the ISSUE's 1e-6 bar.
    At the compiler's default level: at the run's level 1 the sums of the
    two layouts are ordered further apart, and the one element below reads
    2.4e-5 (PR 51)."""
    cfg = JobConfig(distribution_strategy="AllReduce")
    t2 = Trainer(_tp_spec(), cfg,
                 create_mesh(devices, num_devices=4, tensor_parallelism=2))
    t1 = Trainer(_tp_spec(), cfg, create_mesh(devices, num_devices=2))
    s2 = t2.init_state(jax.random.key(0))
    s1 = t1.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    for _ in range(4):
        host = _batch(rng)
        s2, m2 = t2.train_step(s2, t2.shard_batch(host))
        s1, m1 = t1.train_step(s1, t1.shard_batch(host))
        assert abs(float(m2["loss"]) - float(m1["loss"])) <= 1e-6
    for a, b in zip(
        jax.tree.leaves(jax.device_get(s2.params)),
        jax.tree.leaves(jax.device_get(s1.params)),
    ):
        # Four Adam steps amplify reduction-order noise where a gradient
        # is near zero (the update is lr * m / sqrt(v), sign-like early
        # on): with the jitted init's values (PR 26; one ulp from the
        # eager ones) one element of 4096 lands 1.9e-6 apart.
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=5e-6
        )


def test_tp_weights_are_sharded_and_bytes_drop(devices):
    """The declared tensor_sharding lands in the placement (column
    matrices split over tp dim 1, row matrices dim 0, norms replicated),
    and the analytic grad-reduce bytes fall vs the 1-D layout — each rank
    reduces only its 1/tp shard over dp."""
    from jax.sharding import PartitionSpec as P

    cfg = JobConfig(distribution_strategy="AllReduce")
    t2 = Trainer(_tp_spec(), cfg,
                 create_mesh(devices, num_devices=4, tensor_parallelism=2))
    s2 = t2.init_state(jax.random.key(0))
    blk = s2.params["blocks"]["b0"]
    assert blk["wqkv"].sharding.spec == P(None, "tp")
    assert blk["w1"].sharding.spec == P(None, "tp")
    assert blk["wo"].sharding.spec == P("tp", None)
    assert blk["w2"].sharding.spec == P("tp", None)
    assert blk["ln1"].sharding.spec == P()

    t1 = Trainer(_tp_spec(), cfg, create_mesh(devices, num_devices=2))
    s1 = t1.init_state(jax.random.key(0))
    b2 = t2.collective_bytes_per_step(s2)
    b1 = t1.collective_bytes_per_step(s1)
    assert b2["resolved"] < b1["resolved"]


@pytest.fixture(scope="module")
def resolved_bytes_by_tp(devices):
    """Resolved grad-reduce bytes of the (dp, tp) factorizations of 8."""
    cfg = JobConfig(distribution_strategy="AllReduce")
    out = {}
    for tp in (1, 2, 4, 8):
        t = Trainer(_tp_spec(n_heads=8, dim=64), cfg,
                    create_mesh(devices, num_devices=8, tensor_parallelism=tp))
        out[tp] = t.collective_bytes_per_step(t.init_state(jax.random.key(0)))["resolved"]
    return out


@pytest.mark.parametrize("tp_lo,tp_hi", [(1, 2), (2, 4), (4, 8)])
def test_resolved_bytes_fall_as_tp_rises(resolved_bytes_by_tp, tp_lo, tp_hi):
    """The grad reduce runs over dp only and each rank reduces 1/tp of
    every tp-sharded leaf, so the resolved inter-host bytes fall strictly
    as tp rises, down to none at dp = 1 — the traffic the 2D layout exists
    to not move."""
    assert resolved_bytes_by_tp[tp_hi] < resolved_bytes_by_tp[tp_lo]
    assert resolved_bytes_by_tp[8] == 0


# ---- the elastic 2D re-partitioner ----


def test_2d_reform_relowers_once_and_carries_moments(devices):
    """Every re-partition — 2D -> smaller 2D -> back, and 2D -> 1D —
    bridges the sharded Adam moments bit-exactly through the canonical
    host layout, and trainer.train_step re-lowers exactly ONCE per
    topology (jitsan v6 counters; repeat steps add zero)."""
    from elasticdl_tpu.common import jitsan

    cfg = JobConfig(
        distribution_strategy="AllReduce", optimizer_sharding="sharded"
    )
    t = Trainer(_tp_spec(), cfg,
                create_mesh(devices, num_devices=8, tensor_parallelism=4))
    state = t.init_state(jax.random.key(0))
    rng = np.random.default_rng(1)
    c0 = jitsan.compiles("trainer.train_step")
    for _ in range(2):
        state, _ = t.train_step(state, t.shard_batch(_batch(rng)))
    if jitsan.enabled():
        assert jitsan.compiles("trainer.train_step") == c0 + 1

    def reshard(mesh):
        before = jax.device_get(t.host_state(state))
        t.set_mesh(mesh)
        placed = t.shard_state(before)
        after = jax.device_get(t.host_state(placed))
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return placed

    # (dp2, tp4) -> lose a host -> (dp1, tp4): tp preserved.
    state = reshard(create_mesh(devices, num_devices=4, tensor_parallelism=4))
    assert mesh_shape(t.mesh) == (1, 4)
    state, m = t.train_step(state, t.shard_batch(_batch(rng)))
    assert np.isfinite(float(m["loss"]))
    state, _ = t.train_step(state, t.shard_batch(_batch(rng)))
    if jitsan.enabled():
        assert jitsan.compiles("trainer.train_step") == c0 + 2

    # Back to (dp2, tp4), carrying the steps trained at (1, 4).
    state = reshard(create_mesh(devices, num_devices=8, tensor_parallelism=4))
    state, _ = t.train_step(state, t.shard_batch(_batch(rng)))
    if jitsan.enabled():
        assert jitsan.compiles("trainer.train_step") == c0 + 3

    # The 2D -> 1D re-partition: tensor mode on a flat mesh runs dense.
    state = reshard(create_mesh(devices, num_devices=4))
    assert mesh_shape(t.mesh) == (4, 1)
    state, m = t.train_step(state, t.shard_batch(_batch(rng)))
    assert int(state.step) == 6 and np.isfinite(float(m["loss"]))
    if jitsan.enabled():
        assert jitsan.compiles("trainer.train_step") == c0 + 4


def test_shape_preserving_reform_adds_zero_recompiles(tmp_path, devices):
    """The worker's identical-topology guard holds on the 2D path: a
    membership version bump that keeps ranks+addresses adopts WITHOUT
    set_mesh, so no re-lower and no state churn; a genuine world change
    re-forms to the resolved legal 2D shape exactly once."""
    from elasticdl_tpu.common import jitsan
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.worker.worker import Worker

    path = str(tmp_path / "lm.rio")
    generate("lm", path, 8, seq_len=SEQ, vocab=VOCAB)
    config = JobConfig(
        model_def="transformer_lm.model_spec", training_data=path,
        minibatch_size=8, tensor_parallelism=2,
    )
    worker = Worker(
        config, master=None, reader=create_data_reader(path),
        spec=_tp_spec(), devices=devices, devices_per_worker=4,
    )
    worker._apply_membership(
        {"version": 0, "world_size": 1, "ranks": {"w": 0}}, initial=True
    )
    assert mesh_shape(worker.trainer.mesh) == (2, 2)
    worker.state = worker.trainer.init_state(jax.random.key(0))
    rng = np.random.default_rng(0)
    t = worker.trainer
    worker.state, _ = t.train_step(worker.state, t.shard_batch(_batch(rng)))
    c1 = jitsan.compiles("trainer.train_step")

    # Version churn, identical topology: adopt, don't re-form.
    worker._apply_membership(
        {"version": 1, "world_size": 1, "ranks": {"w": 0}}
    )
    assert worker.reforms == 0 and worker.trainer is t
    worker.state, _ = t.train_step(worker.state, t.shard_batch(_batch(rng)))
    assert jitsan.compiles("trainer.train_step") == c1  # zero recompiles

    # A real join doubles the world: reform to the legal (dp4, tp2).
    worker._apply_membership(
        {"version": 2, "world_size": 2, "ranks": {"w": 0, "x": 1}}
    )
    assert worker.reforms == 1
    assert mesh_shape(worker.trainer.mesh) == (4, 2)
    worker.state, m = worker.trainer.train_step(
        worker.state, worker.trainer.shard_batch(_batch(rng))
    )
    assert np.isfinite(float(m["loss"]))
    if jitsan.enabled():
        assert jitsan.compiles("trainer.train_step") == c1 + 1


def test_worker_publishes_mesh_shape_gauge(tmp_path, devices):
    """edl_mesh_shape{axis=dp|tp} rides the worker's registry, and
    watch_job renders the pair as one ``mesh: dpNxtpM`` line."""
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.worker.worker import Worker
    from tools.watch_job import render_mesh

    path = str(tmp_path / "lm.rio")
    generate("lm", path, 8, seq_len=SEQ, vocab=VOCAB)
    config = JobConfig(
        model_def="transformer_lm.model_spec", training_data=path,
        minibatch_size=8, tensor_parallelism=4,
    )
    worker = Worker(
        config, master=None, reader=create_data_reader(path),
        spec=_tp_spec(), devices=devices, devices_per_worker=8,
    )
    worker._apply_membership(
        {"version": 0, "world_size": 1, "ranks": {"w": 0}}, initial=True
    )
    snap = worker.gauges.snapshot()
    fam = snap["edl_mesh_shape"]
    by_axis = {
        dict(s["labels"])["axis"]: s["value"] for s in fam["samples"]
    }
    assert by_axis == {"dp": 2.0, "tp": 4.0}
    assert render_mesh({"edl_mesh_shape": fam}) == "mesh: dp2xtp4"


# ---- cross-shape checkpoint restore ----


def test_checkpoint_restores_across_2d_shapes(tmp_path, devices):
    """A 4x2-sharded save (tp-major: dp=2, tp=4) restores bit-exactly —
    dense params AND canonical moments — into (2, 2), (1, 4) and the 1-D
    dp=4 mesh, and trains on each target topology."""
    from elasticdl_tpu.common.checkpoint import CheckpointManager

    cfg = JobConfig(
        distribution_strategy="AllReduce", optimizer_sharding="sharded"
    )
    spec = _tp_spec()
    t8 = Trainer(spec, cfg,
                 create_mesh(devices, num_devices=8, tensor_parallelism=4))
    state = t8.init_state(jax.random.key(0))
    rng = np.random.default_rng(2)
    for _ in range(2):
        state, _ = t8.train_step(state, t8.shard_batch(_batch(rng)))
    canonical = t8.host_state(state)

    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(2, canonical, wait=True)

    targets = (
        create_mesh(devices, num_devices=4, tensor_parallelism=2),  # (2, 2)
        create_mesh(devices, num_devices=4, tensor_parallelism=4),  # (1, 4)
        create_mesh(devices, num_devices=4),                        # 1-D dp4
    )
    for mesh in targets:
        t = Trainer(spec, cfg, mesh)
        template = t.init_state(jax.random.key(1))  # different init
        restored = t.adopt_restored(
            ckpt.restore(t.restore_template(template))
        )
        assert int(restored.step) == 2
        got = t.host_state(restored)
        for a, b in zip(jax.tree.leaves(canonical), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        state_t, m = t.train_step(restored, t.shard_batch(_batch(rng)))
        assert int(state_t.step) == 3
        assert np.isfinite(float(m["loss"]))
    ckpt.close()
