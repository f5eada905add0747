"""``models/moe_lm.py`` and ``ops/moe.py`` against the plain reference
(``benchmark/configs/olmoe_1b_7b_l1_reference.py``: dense experts, explicit
softmax attention, no code shared with either) on seeded random weights at
small sizes, and the pieces one by one: the dropless expert layer, the
router, rotary positions, the whole-width QK-norm, the step's counters,
two devices against one, a checkpoint round trip.  CPU only."""

from __future__ import annotations

import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import moe
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402

#: The benchmark's rehearsal shape, and one with more experts a token.
SHAPES = {
    "rehearsal": dict(vocab_size=256, hidden_size=64, num_attention_heads=4, num_hidden_layers=2,
                      num_experts=8, num_experts_per_tok=2, intermediate_size=32, seq_len=128),
    "e16_top4": dict(vocab_size=128, hidden_size=64, num_attention_heads=2, num_hidden_layers=1,
                     num_experts=16, num_experts_per_tok=4, intermediate_size=48, seq_len=64),
}
TERMS = ("loss", "ce", "lb_loss", "z_loss")


@pytest.fixture(scope="module")
def reference():
    return resolve.load_module(os.path.join(BENCH_DIR, "configs", "olmoe_1b_7b_l1_reference.py"))


def _spec(shape: str, dtype: str = "float32", **kw):
    return load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", compute_dtype=dtype, **SHAPES[shape], **kw)


def _weights(spec, seed: int = 0):
    """Seeded weights, away from the init's symmetries: gains that are not
    1 (a forgotten gain would pass at 1) and matrices five times the init's
    scale (a router whose probabilities are not all 1/E)."""
    params = spec.init(jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), len(jax.tree.leaves(params))))
    return jax.tree.map(
        lambda a: a * 5.0 if a.ndim > 1 else a + 0.2 * jax.random.normal(next(keys), a.shape), params)


def _batch(shape: str, b: int = 4, seed: int = 0):
    p = SHAPES[shape]
    toks = np.random.default_rng(seed).integers(0, p["vocab_size"], (b, p["seq_len"] + 1)).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def _system(spec, params, batch):
    def total(params):
        out = spec.apply(params, batch, train=True)
        return spec.loss(out, batch), (spec.metrics(out, batch), out)

    (loss, (metrics, out)), grads = jax.jit(jax.value_and_grad(total, has_aux=True))(params)
    np.testing.assert_allclose(float(loss), float(metrics["loss"]), rtol=1e-6)
    return metrics, out, grads


def _reference(reference, shape: str, params, batch):
    _, loss_terms = reference.build(SHAPES[shape])

    def total(params):
        terms = loss_terms(params, batch["tokens"], batch["labels"])
        return terms["loss"], terms

    with jax.default_matmul_precision("highest"):
        (_, terms), grads = jax.jit(jax.value_and_grad(total, has_aux=True))(params)
    return terms, grads


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _assert_system_is_the_reference(reference, shape, tol, dtype="float32"):
    spec = _spec(shape, dtype)
    params, batch = _weights(spec), _batch(shape)
    metrics, out, grads = _system(spec, params, batch)
    terms, ref_grads = _reference(reference, shape, params, batch)
    assert _rel(out["logits"], terms["logits"]) <= tol, "logits"
    for key in TERMS:
        assert abs(float(metrics[key]) - float(terms[key])) <= tol * abs(float(terms[key])), key
    flat, ref_flat = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat)
    for (path, g), r in zip(flat, ref_flat):
        assert float(jnp.max(jnp.abs(r))) > 0, jax.tree_util.keystr(path)  # every leaf is trained
        assert _rel(g, r) <= tol, jax.tree_util.keystr(path)
    # the slots each expert was given, rank by rank, are the reference's
    pairs = float(terms["pairs"])
    np.testing.assert_array_equal(np.asarray(out["router"]["f"]) * pairs, np.asarray(terms["f_sum"]))


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_float32_system_equals_the_plain_reference(reference, shape):
    """Logits, the total loss and each of its three terms, the gradient of
    EVERY leaf, the per-expert slot counts: to 1e-5 of the largest value
    (float32 on both sides; the orders of summation differ)."""
    _assert_system_is_the_reference(reference, shape, 1e-5)


def _rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_bfloat16_system_is_near_the_float32_reference(reference):
    """bfloat16 compute against the float32 reference, as the chip runs it.
    bfloat16 keeps 8 bits: one rounding is 2^-9 = 2e-3 relative, a value
    that went through a few dozen (norms, projections, attention, experts,
    head) sits about 1e-2 away, and a handful of the 4,096 slots land on
    another expert where two probabilities were that close (7 here).  So
    the comparison is in the L2 norm (a flipped slot moves single elements
    a lot and the whole little): logits read 1.4e-2, the loss terms 4e-6 to
    5e-4, the gradients 1e-2 to 9e-2 — the largest the router's, whose
    softmax Jacobian subtracts nearly equal numbers.  The limits are twice
    that.  What must be float32 inside this model, the ROUTER, is held to
    1e-5 in the next test, which bfloat16 logits fail."""
    spec = _spec("rehearsal", "bfloat16")
    params, batch = _weights(spec), _batch("rehearsal")
    metrics, out, grads = _system(spec, params, batch)
    terms, ref_grads = _reference(reference, "rehearsal", params, batch)
    assert out["logits"].dtype == jnp.float32
    assert _rel_l2(out["logits"], terms["logits"]) <= 3e-2
    for key in TERMS:
        assert abs(float(metrics[key]) - float(terms[key])) <= 2e-3 * abs(float(terms[key])), key
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)):
        assert g.dtype == jnp.float32 and _rel_l2(g, r) <= 0.2, jax.tree_util.keystr(path)
    flips = np.abs(np.asarray(out["router"]["f"]) * float(terms["pairs"]) - np.asarray(terms["f_sum"])).sum() / 2
    assert flips <= 40  # of 4,096 slots


def _numpy_route(u, wg, k):
    r = np.asarray(u, np.float64) @ np.asarray(wg, np.float64)
    p = np.exp(r - r.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    choices = np.argsort(-p, axis=-1, kind="stable")[:, :k]
    return np.take_along_axis(p, choices, -1), choices, r, p


def test_the_router_is_float32_whatever_the_activations_are():
    """On bfloat16 activations the router's logits, probabilities and
    weights are float64's to 1e-5 of the largest (float32 holds 24 bits)
    and its choices the same; computed in bfloat16 — the precision below
    the one the configuration states — the same comparison FAILS by two
    orders of magnitude, and choices flip."""
    u = jax.random.normal(jax.random.key(0), (2048, 64), jnp.bfloat16)
    wg = jax.random.normal(jax.random.key(1), (64, 16), jnp.float32) * 0.3
    want_w, want_c, want_r, want_p = _numpy_route(u.astype(jnp.float32), wg, 4)
    got = moe.route(u, wg, 4)
    assert got.logits.dtype == got.probs.dtype == got.weights.dtype == jnp.float32
    assert _rel(got.logits, want_r) <= 1e-5 and _rel(got.probs, want_p) <= 1e-5
    np.testing.assert_array_equal(np.asarray(got.choices), want_c)
    assert _rel(got.weights, want_w) <= 1e-5
    # norm_topk_prob false: the k weights are the softmax's own, and sum to less than 1
    assert float(jnp.max(jnp.sum(got.weights, -1))) < 1.0
    low_r = (u @ wg.astype(jnp.bfloat16)).astype(jnp.float32)
    low_w, low_c = jax.lax.top_k(jax.nn.softmax(low_r.astype(jnp.bfloat16), -1), 4)
    assert _rel(low_r, want_r) > 1e-3
    assert int(np.sum(np.asarray(low_c) != want_c)) > 20


def _dense_expert_sum(u, choices, weights, w_gate, w_up, w_down):
    """Every expert on every token, times the [T, E] matrix of weights."""
    n_experts = w_gate.shape[0]
    m = jnp.sum(jax.nn.one_hot(choices, n_experts) * weights[..., None], 1)
    h = jax.nn.silu(jnp.einsum("td,edf->tef", u, w_gate)) * jnp.einsum("td,edf->tef", u, w_up)
    return jnp.einsum("te,ted->td", m, jnp.einsum("tef,efd->ted", h, w_down))


def _routings(n_tokens: int, n_experts: int):
    rng = np.random.default_rng(0)
    uniform = np.argsort(rng.random((n_tokens, n_experts)), -1)[:, :3]
    return {
        "uniform": uniform,
        # every token to one expert: all other groups are empty
        "one_expert": np.full((n_tokens, 1), 5),
        # every token makes the same choices, as a router of equal logits does
        "all_tied": np.tile(np.arange(4), (n_tokens, 1)),
        # a slot count that is not a multiple of anything
        "odd": uniform[:37, :1],
    }


@pytest.mark.parametrize("routing", ["uniform", "one_expert", "all_tied", "odd"])
def test_expert_ffn_is_the_dense_masked_sum_forward_and_backward(routing):
    n_tokens, d, f, n_experts = 96, 32, 24, 8
    choices = jnp.asarray(_routings(n_tokens, n_experts)[routing], jnp.int32)
    keys = jax.random.split(jax.random.key(3), 5)
    u = jax.random.normal(keys[0], (choices.shape[0], d))
    weights = jax.random.uniform(keys[1], choices.shape, minval=0.05, maxval=0.5)
    w_gate, w_up = (jax.random.normal(k, (n_experts, d, f)) * 0.3 for k in keys[2:4])
    w_down = jax.random.normal(keys[4], (n_experts, f, d)) * 0.3
    args = (u, weights, w_gate, w_up, w_down)

    def ours(u, weights, *w):
        out, sizes, given = moe.expert_ffn(u, choices, weights, *w)
        return jnp.sum(jnp.sin(out)), (out, sizes, given)

    def dense(u, weights, *w):
        out = _dense_expert_sum(u, choices, weights, *w)
        return jnp.sum(jnp.sin(out)), out

    (_, (out, sizes, given)), grads = jax.jit(jax.value_and_grad(ours, argnums=range(5), has_aux=True))(*args)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.jit(jax.value_and_grad(dense, argnums=range(5), has_aux=True))(*args)
    np.testing.assert_array_equal(np.asarray(sizes), np.bincount(np.asarray(choices).ravel(), minlength=n_experts))
    assert int(jnp.sum(sizes)) == choices.size  # dropless
    assert (int(given.first), int(given.second)) == (choices.size, 0)  # all in the one tier
    assert _rel(out, want) <= 1e-5
    for g, w in zip(grads, want_grads):
        assert _rel(g, w) <= 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_windowed_paths_row_movements_are_each_others_transposes(dtype):
    """``_sum_to_tokens`` (a window's rows, as the experts wrote them, times
    their float32 weights and summed into their tokens) and
    ``_rows_of_tokens`` (the gather out) against plain ``segment_sum`` /
    indexing and what autodiff derives from them — values and both
    cotangents, a filler token and repeated tokens included.  The weighted
    sum is float32 whatever the rows are, to float32's rounding; the
    gather's transpose leaves in the rows' dtype, a float32 sum rounded
    once; a filler row reads 0 both ways."""
    n_tokens, width, d = 40, 72, 32
    rng = np.random.default_rng(5)
    tok = jnp.asarray(np.concatenate([rng.integers(0, n_tokens, 60), np.full(12, n_tokens)]), jnp.int32)
    y = jnp.asarray(rng.standard_normal((width, d)), dtype)
    w = jnp.asarray(rng.uniform(0.05, 0.5, width), jnp.float32)
    g = jnp.asarray(rng.standard_normal((n_tokens, d)), jnp.float32)

    def plain(y, w):
        return jax.ops.segment_sum(y.astype(jnp.float32) * w[:, None], tok, n_tokens + 1)[:n_tokens]

    out, back = jax.vjp(lambda y, w: moe._sum_to_tokens(y, w, tok, n_tokens), y, w)
    want, want_back = jax.vjp(plain, y, w)
    assert out.dtype == jnp.float32 and _rel(out, want) <= 1e-6
    (dy, dw), (want_dy, want_dw) = back(g), want_back(g)
    assert (dy.dtype, dw.dtype) == (dtype, jnp.float32)
    np.testing.assert_array_equal(np.asarray(dy, np.float32), np.asarray(want_dy, np.float32))
    assert _rel(dw, want_dw) <= 1e-6 and not np.any(np.asarray(dy[60:], np.float32)) and not np.any(np.asarray(dw[60:]))
    # the gather out and its transpose, the unweighted sum
    u = jnp.asarray(rng.standard_normal((n_tokens, d)), dtype)
    rows, back = jax.vjp(lambda u: moe._rows_of_tokens(u, tok, n_tokens), u)
    np.testing.assert_array_equal(np.asarray(rows[:60], np.float32), np.asarray(u, np.float32)[np.asarray(tok[:60])])
    (du,) = back(y)
    summed = jax.ops.segment_sum(y.astype(jnp.float32), tok, n_tokens + 1)[:n_tokens]
    assert du.dtype == dtype
    if dtype == jnp.float32:
        assert _rel(du, summed) <= 1e-6
    else:  # (another order of at most a few addends may round the last bit the other way)
        np.testing.assert_allclose(np.asarray(du, np.float32), np.asarray(summed.astype(dtype), np.float32), rtol=2**-7, atol=1e-6)


def test_no_row_scatter_in_the_expert_layer():
    """Both directions of the row movement are gathers, forward and
    backward: the jaxpr of the gradient holds sorts and gathers and no
    scatter of rows.  (Every expert held: the row buffers hold all ``T *
    k`` slots.  ``tests/test_latent_moe.py::
    test_no_row_buffer_of_all_the_slots_in_the_held_path`` holds the rule
    for a share of the experts: no scatter of rows there either, and no
    gather of ``T * k`` rows.)"""
    choices = jnp.asarray(_routings(96, 8)["uniform"], jnp.int32)
    u = jnp.ones((96, 32))
    w = jnp.ones((8, 32, 24)), jnp.ones((8, 32, 24)), jnp.ones((8, 24, 32))

    def loss(u, weights, w):
        return jnp.sum(moe.expert_ffn(u, choices, weights, *w)[0])

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(u, jnp.ones(choices.shape), w))
    assert text.count("gather") >= 4 and text.count("sort") >= 2
    # the only scatters are the grouped matmul's own bookkeeping: vectors
    # of about E numbers (tile and group ids), never [slots, D] rows
    scattered = re.findall(r":\w+\[([\d,]*)\] = scatter", text)
    assert scattered and all(shape.isdigit() and int(shape) <= 32 for shape in scattered), scattered


@pytest.mark.parametrize("position", [0, 1, 4095])
def test_rope_is_a_complex_rotation_of_the_pairs(position):
    """Element i of a head with element i + hd/2 is one complex number,
    turned by position * theta^(-2i/hd): float64 numpy against ours."""
    hd, theta = 128, 10000.0
    x = np.random.default_rng(position).standard_normal((1, 1, 2, hd)).astype(np.float32)
    z = x[..., : hd // 2].astype(np.float64) + 1j * x[..., hd // 2:].astype(np.float64)
    turned = z * np.exp(1j * position * theta ** (-2.0 * np.arange(hd // 2) / hd))
    want = np.concatenate([turned.real, turned.imag], -1)
    got = attentions.rope(jnp.asarray(x), jnp.asarray([position]), theta)
    # float32 angles: 4095 rad x 2^-24 is 2.4e-4 rad of turn
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-3 if position else 0.0)
    if position == 0:
        np.testing.assert_array_equal(np.asarray(got), x)


def test_qk_norm_is_taken_over_all_heads_columns(reference, monkeypatch):
    """OLMoE normalises q and k over the whole projection before the heads
    are split.  With a per-head norm in its place the comparison with the
    reference must fail."""
    def per_head(x, scale, eps, n_heads=SHAPES["rehearsal"]["num_attention_heads"]):
        shaped = x.reshape(x.shape[:-1] + (n_heads, -1))
        var = jnp.mean(jnp.square(shaped), -1, keepdims=True)
        return (shaped * jax.lax.rsqrt(var + eps)).reshape(x.shape) * scale

    monkeypatch.setattr(attentions, "_qk_norm", per_head)
    with pytest.raises(AssertionError):
        _assert_system_is_the_reference(reference, "rehearsal", 1e-5)


def test_step_counters_count_the_slots_and_the_load(reference, devices):
    """One train step's ``moe_*`` metrics against a numpy count of the
    reference's routing: every slot computed (dropless), the fullest and
    the average expert of each layer."""
    shape = "e16_top4"
    spec = _spec(shape)
    trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), create_mesh(devices, num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    batch = _batch(shape)
    terms, _ = _reference(reference, shape, jax.device_get(state.params), batch)
    state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    per_expert = np.asarray(terms["f_sum"]).sum(0)  # one layer
    slots = batch["tokens"].size * SHAPES[shape]["num_experts_per_tok"]
    assert per_expert.sum() == slots
    assert float(metrics["moe_slots"]) == float(metrics["moe_slots_computed"]) == slots
    assert float(metrics["moe_expert_load_max"]) == per_expert.max()
    assert float(metrics["moe_expert_load_mean"]) == per_expert.mean()
    assert spec.step_counters == moe_lm.MOE_COUNTERS
    # another routing (group sizes are data): the same compiled program
    from elasticdl_tpu.common import jitsan

    before = jitsan.compiles("trainer.train_step")
    state, again = trainer.train_step(state, trainer.shard_batch(_batch(shape, seed=1)))
    assert jitsan.compiles("trainer.train_step") == before
    assert float(again["moe_expert_load_max"]) != float(metrics["moe_expert_load_max"])
    assert float(again["moe_slots_computed"]) == slots
    # evaluation reports the model's metrics, not the counts
    evaluated = trainer.eval_step(state, trainer.shard_batch(batch))
    assert set(evaluated) == {"loss", "ce", "lb_loss", "z_loss", "accuracy"}


def test_two_devices_under_allreduce_equal_one(devices):
    """The sequence sharded over two devices (ring attention, global rotary
    positions, each device routing its own tokens through all experts, the
    load-balancing loss from GLOBAL shares) trains as one device does."""
    batch = _batch("rehearsal")
    cfg = JobConfig(distribution_strategy="AllReduce")
    runs = {}
    for n in (1, 2):
        trainer = Trainer(_spec("rehearsal"), cfg, create_mesh(devices, num_devices=n))
        state = trainer.init_state(jax.random.key(0))
        for _ in range(2):
            state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        runs[n] = (jax.device_get(state.params), {k: float(v) for k, v in metrics.items()})
    for key in TERMS + ("accuracy", "moe_slots", "moe_slots_computed", "moe_expert_load_mean"):
        np.testing.assert_allclose(runs[2][1][key], runs[1][1][key], rtol=2e-6, err_msg=key)
    # each device's own fullest expert, summed: at least the global one's load
    assert runs[2][1]["moe_expert_load_max"] >= runs[1][1]["moe_expert_load_max"]
    for a, b in zip(jax.tree.leaves(runs[1][0]), jax.tree.leaves(runs[2][0])):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=5e-6)


def test_checkpoint_round_trip_of_the_state(tmp_path, devices):
    from elasticdl_tpu.common.checkpoint import CheckpointManager

    spec = _spec("e16_top4")
    trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), create_mesh(devices, num_devices=2))
    state = trainer.init_state(jax.random.key(0))
    batch = _batch("e16_top4")
    state, _ = trainer.train_step(state, trainer.shard_batch(batch))
    saved = trainer.host_state(state)
    ckpt = CheckpointManager(str(tmp_path / "ckpt"))
    ckpt.save(1, saved, wait=True)
    other = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), create_mesh(devices, num_devices=1))
    restored = other.adopt_restored(ckpt.restore(other.restore_template(other.init_state(jax.random.key(7)))))
    ckpt.close()
    assert int(restored.step) == 1
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(other.host_state(restored))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _, after = other.train_step(restored, other.shard_batch(batch))
    _, straight = trainer.train_step(state, trainer.shard_batch(batch))
    np.testing.assert_allclose(float(after["loss"]), float(straight["loss"]), rtol=2e-6)


def test_layer_types_and_a_tied_head():
    """The block's other settings: a dense gated feed-forward in some
    layers, the head tied to the token embedding.  The model trains, has no
    head of its own, and only the ``moe`` layer routes."""
    spec = _spec("rehearsal", layer_types=("dense", "moe"), tie_word_embeddings=True)
    params = spec.init(jax.random.key(0))
    assert "head" not in params and "router" not in params["blocks"]["b00"] and "router" in params["blocks"]["b01"]
    trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), create_mesh(jax.devices()[:1], num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    batch = _batch("rehearsal")
    losses = []
    for _ in range(4):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert float(metrics["moe_slots"]) == batch["tokens"].size * 2  # one routed layer
    dense_only = _spec("rehearsal", layer_types=("dense", "dense"))
    assert dense_only.step_counters == {}
    out = dense_only.apply(dense_only.init(jax.random.key(0)), batch)
    assert set(out) == {"logits"} and float(dense_only.loss(out, batch)) > 0
    with pytest.raises(ValueError, match="layer_types"):
        _spec("rehearsal", layer_types=("moe",))
