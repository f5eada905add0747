"""jitsan (v6): deterministic retrace detection, disabled-mode identity,
variant budgets, the gauge/artifact bridges, and the transfer-guard
window — the runtime twin of graftlint's jit-discipline passes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common import gauge, jitsan
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.jax_compat import jit_compiled, jit_donating
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer


# Registry names are process-global: each test below uses its own
# distinct "test.<x>" literal and asserts DELTAS, never absolute counts.

# ---- counting + budgets ----------------------------------------------------

def test_same_shape_never_relowers():
    f = jit_compiled(lambda x: x * 2, name="test.stable", expected_variants=1)
    base = jitsan.compiles("test.stable")
    f(jnp.ones((4,)))
    assert jitsan.compiles("test.stable") == base + 1
    for _ in range(3):
        f(jnp.ones((4,)))
    # Steady state: zero further lowerings — the contract every
    # recompile-free test in the suite asserts through this counter.
    assert jitsan.compiles("test.stable") == base + 1


def test_shape_drift_raises_deterministically():
    f = jit_compiled(lambda x: x + 1, name="test.drift", expected_variants=1)
    f(jnp.ones((4,)))
    with pytest.raises(jitsan.JitSanViolation) as e:
        f(jnp.ones((8,)))  # second shape: one lowering past the budget
    assert "test.drift" in str(e.value) and "expected_variants=1" in str(e.value)
    # Deterministic, not flaky: the SAME drifting call raises again (a
    # third distinct shape), while the original shape stays served from
    # the compile cache.
    assert float(f(jnp.ones((4,)))[0]) == 2.0
    with pytest.raises(jitsan.JitSanViolation):
        f(jnp.ones((16,)))


def test_variant_budget_allows_declared_shapes():
    # expected_variants=2 is the serving bucket story: two padded shapes
    # are the declared contract, the third is the violation.
    f = jit_compiled(lambda x: x.sum(), name="test.buckets", expected_variants=2)
    f(jnp.ones((4,)))
    f(jnp.ones((8,)))
    with pytest.raises(jitsan.JitSanViolation):
        f(jnp.ones((16,)))


def test_instances_carry_their_own_budget():
    # Two structural builds under ONE name (the trainer's mask/no-mask
    # variants): each instance may lower its own budget's worth.
    a = jit_compiled(lambda x: x * 1, name="test.twin", expected_variants=1)
    b = jit_compiled(lambda x: x * 3, name="test.twin", expected_variants=1)
    base = jitsan.compiles("test.twin")
    a(jnp.ones((4,)))
    b(jnp.ones((4,)))
    assert jitsan.compiles("test.twin") == base + 2
    rec = jitsan.stats()["test.twin"]
    assert rec["instances"] >= 2 and rec["budget"] == 1


def test_jit_donating_counts_and_still_donates():
    f = jit_donating(
        lambda s, b: s + b, name="test.donate", expected_variants=1
    )
    s = jnp.ones((4,))
    base = jitsan.compiles("test.donate")
    out = f(s, jnp.ones((4,)))
    assert jitsan.compiles("test.donate") == base + 1
    assert s.is_deleted()  # donation survived the counting wrapper
    assert float(out[0]) == 2.0


# ---- disabled mode ---------------------------------------------------------

def test_disabled_mode_returns_plain_jit(monkeypatch):
    monkeypatch.setenv("GRAFT_JITSAN", "0")
    assert not jitsan.enabled()
    before = dict(jitsan.stats())
    f = jit_compiled(lambda x: x * 2, name="test.disabled")
    g = jit_donating(lambda s, b: s + b, name="test.disabled")
    # Nothing registered: the declaration costs nothing when disabled.
    assert jitsan.stats() == before
    # And the callables are the PLAIN jitted functions — the wrapped
    # (counting) spelling would expose the shim, not the user function.
    assert float(f(jnp.ones(()))) == 2.0
    s = jnp.ones(())
    g(s, jnp.ones(()))
    assert s.is_deleted()


# ---- gauge + artifact bridges ----------------------------------------------

def test_gauge_bridge_publishes_per_fn_counts():
    f = jit_compiled(lambda x: x - 1, name="test.gaugefn", expected_variants=1)
    f(jnp.ones((4,)))
    reg = gauge.Registry()
    collector = gauge.install_jit_collector(reg)
    try:
        fam = reg.snapshot()["edl_jit_compiles_total"]
        by_fn = {
            s["labels"]["fn"]: s["value"] for s in fam["samples"]
        }
        assert by_fn.get("test.gaugefn", 0) >= 1
    finally:
        reg.remove_collector(collector)


def test_dump_stats_writes_json(tmp_path):
    f = jit_compiled(lambda x: x * 5, name="test.dump", expected_variants=1)
    f(jnp.ones((2,)))
    path = str(tmp_path / "jitsan_stats.json")
    assert jitsan.dump_stats(path) == path
    with open(path) as fh:
        payload = json.load(fh)
    assert payload["test.dump"]["compiles"] >= 1
    assert payload["test.dump"]["budget"] == 1


def test_dump_stats_without_target_is_noop(monkeypatch):
    monkeypatch.delenv("GRAFT_JITSAN_DUMP", raising=False)
    assert jitsan.dump_stats() is None


# ---- transfer guard --------------------------------------------------------

def test_transfer_guard_disarmed_is_nullcontext(monkeypatch):
    monkeypatch.delenv("GRAFT_JITSAN_TRANSFER_GUARD", raising=False)
    with jitsan.transfer_guard():
        # Implicit transfers stay legal: the guard is opt-in.
        assert jax.config.jax_transfer_guard is None
        np.asarray(jax.device_put(np.ones(2)))


def test_transfer_guard_armed_sets_disallow(monkeypatch):
    monkeypatch.setenv("GRAFT_JITSAN_TRANSFER_GUARD", "1")
    assert jitsan.transfer_guard_armed()
    with jitsan.transfer_guard():
        # Introspect the armed level rather than provoking a transfer:
        # XLA's host platform serves arrays zero-copy, so an actual
        # implicit-D2H repro is backend-dependent; the config flip is
        # the deterministic, backend-free half of the contract.
        assert jax.config.jax_transfer_guard == "disallow"
        # Explicit spellings stay legal under "disallow" — the worker's
        # dispatch window relies on exactly this split.
        jax.device_get(jax.device_put(np.ones(2)))
    assert jax.config.jax_transfer_guard is None


def test_transfer_guard_needs_jitsan_enabled(monkeypatch):
    monkeypatch.setenv("GRAFT_JITSAN", "0")
    monkeypatch.setenv("GRAFT_JITSAN_TRANSFER_GUARD", "1")
    assert not jitsan.transfer_guard_armed()


# ---- the trainer's declared names hold their budgets -----------------------

def _mnist_batch(trainer, stacked=False):
    rng = np.random.default_rng(0)
    lead = (2, 16) if stacked else (16,)
    host = {
        "images": rng.standard_normal(lead + (28, 28, 1)).astype(np.float32),
        "labels": rng.integers(0, 10, lead).astype(np.int32),
    }
    return trainer.shard_stacked_batch(host) if stacked else trainer.shard_batch(host)


def _train_step(t, s):
    return t.train_step(s, _mnist_batch(t))[0]


def _train_scan(t, s):
    return t.train_scan(s, _mnist_batch(t, stacked=True))[0]


def _predict_step(t, s):
    t.predict_step(s, _mnist_batch(t))
    return s


def _snapshot_state(t, s):
    t.snapshot_state(s)
    return s


#: one call of each declared name: (trainer, state) -> the state to go on with
_DRIVES = {
    "trainer.train_step": _train_step,
    "trainer.train_scan": _train_scan,
    "trainer.predict_step": _predict_step,
    "trainer.snapshot_state": _snapshot_state,
}


@pytest.mark.parametrize("name", sorted(_DRIVES))
def test_trainer_name_lowers_once_and_stays_in_budget(devices, name):
    """Lowerings per jitted name, on the live tree: a steady shape lowers
    each of the trainer's declared names ONCE, and compiles never pass
    instances x budget (the arithmetic the retired LINT-stamp gate held
    at zero for these four names)."""
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    trainer = Trainer(spec, JobConfig(), create_mesh(devices, num_devices=2))
    state = trainer.init_state(jax.random.key(0))
    base = jitsan.compiles(name)
    for _ in range(3):
        state = _DRIVES[name](trainer, state)
    assert jitsan.compiles(name) == base + 1
    rec = jitsan.stats()[name]
    assert rec["compiles"] <= rec["instances"] * rec["budget"]


# ---- reset -----------------------------------------------------------------

def test_reset_clears_aggregates_not_budgets():
    f = jit_compiled(lambda x: x / 2, name="test.reset", expected_variants=1)
    f(jnp.ones((4,)))
    assert jitsan.compiles("test.reset") >= 1
    jitsan.reset()
    assert jitsan.compiles("test.reset") == 0
    # The per-instance budget survives the aggregate reset: the violation
    # contract is an instance property.
    with pytest.raises(jitsan.JitSanViolation):
        f(jnp.ones((8,)))
