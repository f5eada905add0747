"""The table's dense Adam update applied inside the merge sweep (PR 29):
``ops/table_grad.sweep_adam`` against the pair it replaces (the sweep into a
gradient buffer + ``optax.adam``), the lookup's hand-over of its update rows
(``ops/embedding.py``), the trainer's fused leaf against the unfused step on
every route, and each fallback.  The kernel runs in the Pallas interpreter
on the CPU, as ``tests/test_embedding.py``'s sweep does.  Last, the four
benchmark metric files that read the new scope and counter."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import Adam, ModelSpec, load_model_spec
from elasticdl_tpu.ops import embedding
from elasticdl_tpu.ops.table_grad import sweep_adam, sweep_table_grad
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

ROWS, TILE, CHUNK = 256, 64, 128
RULE = Adam(learning_rate=3e-3, b1=0.8, b2=0.95, eps=1e-6)

# name -> (table rows, ids of a step as a function of (rng, step))
APPLY_CASES = {
    "distinct": (ROWS, lambda r, s: r.permutation(ROWS)[:200]),
    "duplicates": (ROWS, lambda r, s: r.integers(0, ROWS, 300)),
    # 300 rows for one tile of 64: chunks 0, 1 and the loop's 2.
    "a_hot_tile_of_three_chunks": (ROWS, lambda r, s: r.integers(64, 128, 300)),
    "three_quarters_filler": (
        ROWS,
        lambda r, s: r.permutation(
            np.concatenate([r.permutation(ROWS)[:80], np.full(240, ROWS)])
        ),
    ),
    "rows_not_a_multiple_of_the_tile": (200, lambda r, s: r.permutation(200)[:150]),
    # rows 128.. get updates in step 0 only, rows ..127 never: both halves
    # must move in steps 1 and 2 (tiles with NO update row are not skipped).
    "tiles_without_an_update": (
        ROWS, lambda r, s: r.permutation(128)[:100] + 128 if s == 0 else np.full(4, ROWS),
    ),
}


def _reference_step(rule):
    optimizer = optax.adam(rule.learning_rate, b1=rule.b1, b2=rule.b2, eps=rule.eps)

    @jax.jit
    def step(table, state, ids, rows):
        grad = sweep_table_grad(ids, rows, table.shape[0], tile=TILE, chunk=CHUNK)
        updates, state = optimizer.update(grad, state, table)
        return optax.apply_updates(table, updates), state

    return optimizer, step


@pytest.mark.parametrize("case", sorted(APPLY_CASES))
def test_sweep_adam_is_the_sweep_then_optax_adam_over_three_steps(case):
    """Three consecutive steps (the bias corrections change), non-default
    hyper-parameters: m and v to the bit (the gradient tile is the same
    sweep's), p to an ulp or two of XLA's own fusion choices."""
    num_rows, make_ids = APPLY_CASES[case]
    rng = np.random.default_rng(29)
    table = jnp.asarray(rng.standard_normal((num_rows, 128)) * 0.01, jnp.float32)
    optimizer, reference = _reference_step(RULE)
    state = optimizer.init(table)
    fused = jax.jit(lambda t, m, v, c, i, r: sweep_adam(
        t, m, v, c, i, r, tile=TILE, chunk=CHUNK, **dataclasses.asdict(RULE)
    ))
    got = (table, state[0].mu, state[0].nu)
    for step in range(3):
        ids = jnp.asarray(make_ids(rng, step), jnp.int32)
        rows = jnp.asarray(rng.standard_normal((ids.shape[0], 128)), jnp.float32)
        before = np.asarray(table)
        table, state = reference(table, state, ids, rows)
        got = fused(*got, state[0].count, ids, rows)
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(state[0].mu))
        np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(state[0].nu))
        np.testing.assert_allclose(
            np.asarray(got[0]), np.asarray(table), rtol=0, atol=2 * 2**-23 * 0.05
        )
        if case == "tiles_without_an_update" and step > 0:
            moved = np.asarray(table) != before
            # zero gradient, live moments: every row of step 0 still moves;
            # zero gradient, zero moments: the others stay.
            assert moved[128:].any(axis=1).sum() == 100 and not moved[:128].any()
            assert (np.asarray(got[0]) != before)[128:].any(axis=1).sum() == 100


def test_a_declared_adam_is_optax_adam_of_its_numbers():
    declared, plain = RULE.transformation(), optax.adam(3e-3, b1=0.8, b2=0.95, eps=1e-6)
    rng = np.random.default_rng(0)
    params = {"a": jnp.asarray(rng.standard_normal((5, 3)), jnp.float32), "b": jnp.ones((4,))}
    grads = jax.tree.map(lambda p: p * 0.5 + 0.1, params)
    states = declared.init(params), plain.init(params)
    for _ in range(2):
        (ours, s0), (theirs, s1) = (
            o.update(grads, s, params) for o, s in zip((declared, plain), states)
        )
        states = s0, s1
        for x, y in zip(jax.tree.leaves((ours, s0)), jax.tree.leaves((theirs, s1))):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    spec = ModelSpec("m", init=None, apply=None, loss=None, metrics=None, optimizer=RULE)
    assert spec.adam == RULE and isinstance(spec.optimizer, optax.GradientTransformation)
    assert dataclasses.replace(spec, optimizer=plain).adam is None
    # DeepFM declares its rule from --learning_rate
    deepfm = load_model_spec("elasticdl_tpu.models", "deepfm.model_spec", learning_rate=0.25)
    assert deepfm.adam == Adam(learning_rate=0.25)


@pytest.fixture
def swept(monkeypatch):
    """The choice the program makes on a TPU for a big table, made here
    for a small one (``tests/test_embedding.py``'s fixture)."""
    monkeypatch.setattr(embedding, "_on_tpu", lambda: True)
    monkeypatch.setattr(embedding, "SWEEP_MIN_ROWS", 8)


def _deepfm(**kw):
    return load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec", buckets_per_feature=300,
        embedding_dim=10, hidden=(32, 16), host_tier=False, compute_dtype="float32",
        **kw,
    )


def _undeclared(spec):
    """The same optax transformation, without the record: the unfused step."""
    return dataclasses.replace(spec, optimizer=spec.optimizer)


def _batches(steps=2, mb=64):
    rng = np.random.default_rng(1)
    out = []
    for _ in range(steps):
        cat = rng.integers(0, 2**31 - 1, (mb, 26)).astype(np.int32)
        cat[1::2] = cat[0::2]  # duplicate ids, on one device and across devices
        out.append({
            "dense": rng.random((mb, 13)).astype(np.float32), "cat": cat,
            "labels": rng.integers(0, 2, (mb,)).astype(np.int32),
        })
    return out


def _train(devices, spec, n, strategy, impl="auto", **config):
    trainer = Trainer(
        spec,
        JobConfig(distribution_strategy=strategy, embedding_lookup_impl=impl, **config),
        create_mesh(devices, num_devices=n),
    )
    state = trainer.init_state(jax.random.key(0))
    for batch in _batches():
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    return jax.device_get(state), {k: float(v) for k, v in metrics.items()}


PS = DistributionStrategy.PARAMETER_SERVER
ROUTES = {
    "local": (1, DistributionStrategy.ALLREDUCE, "auto", 64 * 26),
    "dense": (4, PS, "dense", 4 * 64 * 26),
    "ragged_emulated": (4, PS, "ragged_emulated", 64 * 26),
}


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_fused_train_state_is_the_unfused_one_after_two_steps(devices, swept, route):
    """Tree structure, shapes, dtypes and values of the whole ``TrainState``
    (count included): what checkpoints and the elastic bridge see has not
    changed.  In the interpreter the values come out equal to the bit."""
    n, strategy, impl, rows = ROUTES[route]
    fused, metrics = _train(devices, _deepfm(), n, strategy, impl)
    plain, plain_metrics = _train(devices, _undeclared(_deepfm()), n, strategy, impl)
    assert metrics["table_grad_rows"] == metrics["table_grad_rows_swept"] == rows
    assert metrics["table_grad_rows_fused"] == rows
    assert plain_metrics["table_grad_rows_swept"] == rows
    assert plain_metrics["table_grad_rows_fused"] == 0
    assert jax.tree.structure(fused) == jax.tree.structure(plain)
    assert int(fused.opt_state[0].count) == 2
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(fused), jax.tree.leaves(plain)):
        assert (x.shape, x.dtype) == (y.shape, y.dtype), path
        np.testing.assert_allclose(x, y, rtol=0, atol=1e-7, err_msg=str(path))
    assert np.abs(fused.opt_state[0].nu["fm_table"]).max() > 0
    assert metrics["loss"] == pytest.approx(plain_metrics["loss"], rel=1e-6)


def _looked_up_twice(spec):
    def apply(params, batch, **kw):
        return 0.5 * (spec.apply(params, batch, **kw) + spec.apply(params, batch, **kw))
    return dataclasses.replace(spec, apply=apply, optimizer=spec.adam)


# name -> (spec, devices, config, update rows swept a step)
FALLBACKS = {
    "a_small_table": (lambda: _deepfm(), 1, {}, 0),
    "another_optimizer": (
        lambda: dataclasses.replace(_deepfm(), optimizer=optax.adamw(1e-3)), 1, {}, 64 * 26,
    ),
    "two_lookups_of_one_table": (lambda: _looked_up_twice(_deepfm()), 1, {}, 2 * 64 * 26),
    "a_sharded_optimizer": (
        lambda: _deepfm(), 4, {"optimizer_sharding": "sharded"}, 4 * 64 * 26,
    ),
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_a_fallback_takes_todays_path_and_reports_no_fused_row(
    devices, monkeypatch, swept, case
):
    make_spec, n, config, swept_rows = FALLBACKS[case]
    if case == "a_small_table":
        monkeypatch.setattr(embedding, "SWEEP_MIN_ROWS", 1 << 20)
    spec = make_spec()
    state, metrics = _train(devices, spec, n, PS, "dense" if n > 1 else "auto", **config)
    assert metrics["table_grad_rows_fused"] == 0
    assert metrics["table_grad_rows_swept"] == swept_rows
    # ... and is the step of the same spec without a declaration
    plain, _ = _train(
        devices, _undeclared(spec), n, PS, "dense" if n > 1 else "auto", **config
    )
    for x, y in zip(jax.tree.leaves(state), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(x, y)


def test_the_hand_over_is_one_mosaic_call_and_no_table_shaped_cotangent(devices, swept):
    """In the fused step's jaxpr: ONE pallas_call, the one with the three
    aliases onto the table and its moments, where the unfused has the
    buffer-writing sweep's (``tests/test_chip_lowering.py`` weighs the
    compiled step's temporaries)."""
    def jaxpr_of(spec):
        trainer = Trainer(
            spec, JobConfig(distribution_strategy=PS), create_mesh(devices, num_devices=1)
        )
        state = trainer.init_state(jax.random.key(0))
        batch = trainer.shard_batch(_batches(1)[0])
        return str(jax.make_jaxpr(
            lambda s, b: trainer.train_step(s, b)
        )(state, batch))

    fused, plain = jaxpr_of(_deepfm()), jaxpr_of(_undeclared(_deepfm()))
    assert fused.count("pallas_call") == plain.count("pallas_call") == 1
    assert "input_output_aliases=((8, 0), (9, 1), (10, 2))" in fused
    assert "input_output_aliases=()" in plain


# The benchmark's four metrics of this kernel (its scope, its counters) are held with the table gradient's, by what they
# report and in which cells, in ``tests/test_deepfm_zipf_cell.py`` (D25: no entry is named outside ``tests/benchmark``).
