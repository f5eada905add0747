"""``models/moe_lm.py`` under DeepSeek-V3's keys — latent attention, a
sigmoid router with a correction bias, shared experts, a leading dense
layer, a SHARE of the experts held — against the plain reference
(``benchmark/configs/kanana2_30b_a3b_ep8_l5_reference.py``: textbook 192-wide
attention, dense experts, no code shared with the model or ``ops/``) on seeded
random weights at small sizes, and the pieces one by one: the router, the
bias's rule, the held range of ``ops/moe.expert_ffn``, the shares that add
up to the whole layer.  CPU only."""

from __future__ import annotations

import functools
import json
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import moe
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

from lm_family import ROOT, resolve

CONFIG = "kanana2_30b_a3b_ep8_l5"

#: kanana-2's keys at a small size: 16 experts of which 4 are held, top-3.
KEYS = dict(
    vocab_size=256, hidden_size=64, num_attention_heads=4, num_experts=16, num_experts_per_tok=3,
    intermediate_size=96, moe_intermediate_size=32, n_shared_experts=2, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_interleave=True, rope_theta=1e6,
    rms_norm_eps=1e-6, scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=2.448,
    topk_method="noaux_tc", bias_update_speed=0.001, seq_len=128, learning_rate=2.2e-4, weight_decay=0.1,
    router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
#: (layers, leading dense layers, experts held, the first of them)
SHAPES = {
    "dense_layer": dict(num_hidden_layers=1, first_k_dense_replace=1, experts_held=4, first_expert_held=0),
    "expert_layer": dict(num_hidden_layers=1, first_k_dense_replace=0, experts_held=4, first_expert_held=8),
    "dense_then_experts": dict(num_hidden_layers=3, first_k_dense_replace=1, experts_held=4, first_expert_held=4),
    "every_expert_held": dict(num_hidden_layers=2, first_k_dense_replace=1, experts_held=16, first_expert_held=0),
}


@pytest.fixture(scope="module")
def reference():
    return lm_family.reference(CONFIG)


def _keys(shape: str, **kw):
    return {**KEYS, **SHAPES[shape], **kw}


def _spec(shape: str, dtype: str = "float32", **kw):
    return lm_family.spec(_keys(shape, **kw), dtype)


def _moved(name, a, noise):
    """Gains that are not 1, matrices five times the init's scale (a router
    whose scores are not all 1/2), and a correction bias that is NOT zero and
    as large as the scores' spread, so that it changes choices."""
    return a * 5.0 if a.ndim > 1 else a + 0.2 * noise()


_weights = functools.partial(lm_family.weights, move=_moved)
_batch = functools.partial(lm_family.batch, KEYS)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _system(spec, batch):
    """``w -> ((loss, the training forward's outputs), gradients)``"""
    def total(params):
        out = spec.apply(params, batch, train=True)
        return spec.loss(out, batch), out

    return jax.value_and_grad(total, has_aux=True)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_float32_system_equals_the_plain_reference(reference, shape):
    """Logits, the loss, the gradient of EVERY leaf that has one (by group:
    the leaf's path is in the message) and the slots each of the router's
    experts was sent, to 1e-5 of the largest value.  The bias is seeded
    non-zero: it gets NO gradient on either side (it chooses, it never
    weighs), and the choices it makes are the reference's."""
    ((loss, out), grads), ((ref_loss, (ref_logits, ref_slots)), ref_grads) = lm_family.system_and_reference(
        CONFIG, KEYS, _moved, _system, **SHAPES[shape])
    assert _rel(out["logits"], ref_logits) <= 1e-5, "logits"
    assert abs(float(loss) - float(ref_loss)) <= 1e-5 * abs(float(ref_loss))
    flat, ref_flat = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(ref_grads)
    assert len(flat) == len(ref_flat)
    for (path, g), r in zip(flat, ref_flat):
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(r)), name
            continue
        assert float(jnp.max(jnp.abs(r))) > 0, name  # every other leaf is trained
        assert _rel(g, r) <= 1e-5, name
    if SHAPES[shape]["first_k_dense_replace"] < SHAPES[shape]["num_hidden_layers"]:
        np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(ref_slots))
        held = SHAPES[shape]["experts_held"]
        lo = SHAPES[shape]["first_expert_held"]
        counters = out["moe_counters"]
        assert float(counters["moe_slots_held"]) == float(np.asarray(ref_slots)[:, lo:lo + held].sum())
        assert float(counters["moe_slots_computed"]) == float(counters["moe_slots_held"])
        assert float(counters["moe_slots"]) == float(np.asarray(ref_slots).sum())


def test_the_bias_chooses_and_never_weighs():
    """A bias large enough to force the choice: the chosen experts are the
    biased ones, and their weights are the UNBIASED scores, normalised and
    scaled — against float64."""
    rng = np.random.default_rng(3)
    u = rng.standard_normal((64, 32)).astype(np.float32)
    wg = rng.standard_normal((32, 16)).astype(np.float32) * 0.3
    bias = np.zeros(16, np.float32)
    bias[[2, 5, 11]] = 4.0  # sigmoid < 1: these three win every token
    routing = moe.route(jnp.asarray(u), jnp.asarray(wg), 3, scoring_func="sigmoid", bias=jnp.asarray(bias),
                        norm_topk_prob=True, routed_scaling_factor=2.448)
    assert set(np.asarray(routing.choices).ravel()) == {2, 5, 11}
    s = 1.0 / (1.0 + np.exp(-(u.astype(np.float64) @ wg.astype(np.float64))))
    want = np.take_along_axis(s, np.asarray(routing.choices), axis=1)
    want = want / (want.sum(-1, keepdims=True) + 1e-20) * 2.448
    np.testing.assert_allclose(np.asarray(routing.weights), want, rtol=2e-6)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sigmoid_router_is_float32_against_float64(seed):
    """bfloat16 rows in, as the model hands them: the logits are the
    float64 product's to 1e-6 of the largest, and the top-k of sigmoid + bias
    is float64's but for scores float32 cannot tell apart."""
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((512, 64)), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((64, 32)) * 0.1, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(32) * 0.05, jnp.float32)
    routing = moe.route(u, wg, 4, scoring_func="sigmoid", bias=bias, norm_topk_prob=True)
    want_r = np.asarray(u, np.float64) @ np.asarray(wg, np.float64)
    assert np.abs(np.asarray(routing.logits, np.float64) - want_r).max() <= 1e-6 * np.abs(want_r).max()
    want_s = 1.0 / (1.0 + np.exp(-want_r)) + np.asarray(bias, np.float64)
    want_c = np.argsort(-want_s, axis=-1, kind="stable")[:, :4]
    assert int(np.sum(np.asarray(routing.choices) != want_c)) <= 2
    np.testing.assert_allclose(np.asarray(routing.weights).sum(-1), 1.0, rtol=1e-6)
    # rounding the operands to bfloat16 on the way is seen: the control
    low = moe.route(u, wg.astype(jnp.bfloat16), 4, scoring_func="sigmoid", bias=bias, norm_topk_prob=True)
    assert np.abs(np.asarray(low.logits, np.float64) - want_r).max() > 1e-4 * np.abs(want_r).max()


def test_softmax_route_is_unchanged_by_the_new_keys():
    """OLMoE's call (no key given) and the same with every key at its
    default: the parent's arithmetic, to the bit."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((128, 32)), jnp.bfloat16)
    wg = jnp.asarray(rng.standard_normal((32, 8)) * 0.1, jnp.float32)
    plain = moe.route(u, wg, 2)
    keyed = moe.route(u, wg, 2, scoring_func="softmax", bias=None, norm_topk_prob=False, routed_scaling_factor=1.0)
    for a, b in zip(plain, keyed):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p = jax.nn.softmax(jnp.dot(u.astype(jnp.float32), wg, precision=jax.lax.Precision.HIGHEST), -1)
    values, choices = jax.lax.top_k(p, 2)
    np.testing.assert_array_equal(np.asarray(plain.choices), np.asarray(choices))
    np.testing.assert_array_equal(np.asarray(plain.weights), np.asarray(values))


def _parents_expert_ffn(u, choices, weights, w_gate, w_up, w_down):
    """``ops/moe.expert_ffn`` as the parent commit (49def3e) had it: every
    expert held, the grouped matmuls given no offset."""
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    def gmm(x, w, sizes):
        tm, tk, tn = moe.GMM_TILING
        tiling = (math.gcd(x.shape[0], tm), min(tk, w.shape[1]), min(tn, w.shape[2]))
        return megablox.gmm(x, w, sizes, preferred_element_type=x.dtype, tiling=tiling, interpret=True)

    n_tokens, k = choices.shape
    order, inverse, sizes = moe.sort_slots(choices, w_gate.shape[0])
    x = moe._rows_out(u, order, inverse, k)
    y = gmm(jax.nn.silu(gmm(x, w_gate, sizes)) * gmm(x, w_up, sizes), w_down, sizes)
    y = moe._rows_back(y, order, inverse).reshape(n_tokens, k, -1).astype(jnp.float32)
    return jnp.sum(y * weights[..., None], axis=1).astype(u.dtype), sizes


def _layer_inputs(dtype, tokens=64, d=64, f=32, experts=16, k=3, seed=0):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    logits = rng.standard_normal((tokens, experts))
    choices = jnp.asarray(np.argsort(-logits, -1)[:, :k], jnp.int32)
    weights = jnp.asarray(rng.random((tokens, k)), jnp.float32)
    w = lambda *shape: jnp.asarray(rng.standard_normal(shape) * 0.1, dtype)  # noqa: E731
    return u, choices, weights, w(experts, d, f), w(experts, d, f), w(experts, f, d)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_every_expert_held_is_the_parents_layer_to_the_bit(dtype):
    """``n = E`` at ``lo = 0`` is the same code as a share, and its output,
    its group sizes and its gradients are the parent's bits."""
    u, choices, weights, wg, wu, wd = _layer_inputs(dtype)
    got, slots, given = moe.expert_ffn(u, choices, weights, wg, wu, wd)
    want, sizes = _parents_expert_ffn(u, choices, weights, wg, wu, wd)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    np.testing.assert_array_equal(np.asarray(slots), np.asarray(sizes))
    assert (int(given.first), int(given.second)) == (choices.size, 0)  # one window holds every slot
    loss = lambda f: lambda u, wg, wu, wd: jnp.sum(f(u, choices, weights, wg, wu, wd)[0].astype(jnp.float32) ** 2)  # noqa: E731
    g_got = jax.grad(loss(moe.expert_ffn), argnums=(0, 1, 2, 3))(u, wg, wu, wd)
    g_want = jax.grad(loss(_parents_expert_ffn), argnums=(0, 1, 2, 3))(u, wg, wu, wd)
    for a, b in zip(g_got, g_want):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


@pytest.mark.parametrize("lo,held", [(0, 4), (4, 4), (12, 4), (2, 8), (0, 16)])
def test_a_held_range_computes_its_own_experts_part(lo, held):
    """Against a dense masked sum over the held experts only: a slot on an
    absent expert adds nothing, forward and in every gradient; the slots
    returned count ALL of the router's experts."""
    u, choices, weights, wg, wu, wd = _layer_inputs(jnp.float32, seed=lo + held)
    part = lambda w: w[lo:lo + held]  # noqa: E731

    def dense(u, wg, wu, wd):
        h = jax.nn.silu(jnp.einsum("td,edf->tef", u, wg)) * jnp.einsum("td,edf->tef", u, wu)
        y = jnp.einsum("tef,efd->ted", h, wd)  # [T, held, D]
        onehot = jax.nn.one_hot(choices - lo, held, dtype=jnp.float32)  # out of range: all zero
        return jnp.einsum("tk,tke,ted->td", weights, onehot, y)

    def system(u, wg, wu, wd):
        out, slots, _ = moe.expert_ffn(u, choices, weights, wg, wu, wd, n_experts=16, lo=lo)
        return out, slots

    def read(layer):  # ONE program a side: the output, what rides with it, the four gradients (the system's forward was compiled three times)
        def loss(*a):
            out, *rest = layer(*a)
            return jnp.sum(out ** 2), (out, *rest)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(*args)

    args = (u, part(wg), part(wu), part(wd))
    with jax.default_matmul_precision("highest"):
        (_, (want,)), g_want = read(lambda *a: (dense(*a),))
    (_, (got, slots)), g_got = read(system)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    for a, b in zip(g_got, g_want):
        assert _rel(a, b) <= 2e-5
    np.testing.assert_array_equal(np.asarray(slots), np.bincount(np.asarray(choices).ravel(), minlength=16))
    with pytest.raises(ValueError, match="are not among the router's"):
        moe.expert_ffn(u, choices, weights, part(wg), part(wu), part(wd), n_experts=16, lo=16 - held + 1)


#: The held range's edges, at 40 tokens x top-3 = 120 slots over 16 experts of
#: which 4 are held: ``C`` = 48 rows (1.5 x 120 x 4 / 16 = 45, up to the row
#: tile 8).  (name, held slots, the first held expert, on one expert only,
#: under ``jax.checkpoint``, the compute dtype: in bfloat16 the token sums
#: read the experts' rows as they are, PR 68 — the second tier making no
#: trip, and two)
HELD_EDGES = [
    ("under_the_bound", 31, 4, False, False, jnp.float32),
    ("exactly_the_bound", 48, 4, False, False, jnp.float32),
    ("one_over_the_bound", 49, 4, False, False, jnp.float32),
    ("every_choice_held", 120, 4, False, False, jnp.float32),
    ("no_slot_held", 0, 4, False, False, jnp.float32),
    ("all_on_one_expert", 40, 4, True, False, jnp.float32),
    ("top_of_the_range", 57, 12, False, False, jnp.float32),
    ("over_the_bound_under_remat", 77, 8, False, True, jnp.float32),
    ("bfloat16_under_the_bound", 31, 4, False, False, jnp.bfloat16),
    ("bfloat16_every_choice_held", 120, 4, False, False, jnp.bfloat16),
]


def _choices_with(held_slots: int, lo: int, one_expert: bool, tokens=40, k=3, experts=16, held=4, seed=0):
    """[tokens, k] choices, distinct within a token, of which exactly
    ``held_slots`` fall in ``[lo, lo + held)``: spread over the tokens and
    the held experts, or all on expert ``lo + 1``."""
    rng = np.random.default_rng(seed)
    absent = np.array([e for e in range(experts) if not lo <= e < lo + held])
    choices = np.stack([rng.permutation(absent)[:k] for _ in range(tokens)])
    places = rng.permutation(tokens * k)[:held_slots] if not one_expert else rng.permutation(tokens)[:held_slots] * k
    for place in places:
        t, i = divmod(int(place), k)
        choices[t, i] = lo + 1 if one_expert else lo + (i + t) % held  # ranks of a token get distinct experts
    assert int(((choices >= lo) & (choices < lo + held)).sum()) == held_slots
    assert all(len(set(row)) == k for row in choices)
    return jnp.asarray(choices, jnp.int32)


@functools.lru_cache(maxsize=None)
def _windowed_and_dense(lo: int, remat: bool, experts: int = 16, held: int = 4):
    """``(the windowed path, the dense masked sum over the held experts)``, each ``(choices, u, weights, wg, wu, wd) ->
    ((loss, its outputs), the five gradients)`` as ONE jitted program: the choices are an OPERAND, as they are in the
    model, so the edges that differ only in where the slots fall read one compiled pair (each case closed over its
    own and compiled the interpreter's grouped matmuls again: six of the eight share ``lo`` = 4; PR 66)."""
    def dense(choices, u, weights, wg, wu, wd):
        h = jax.nn.silu(jnp.einsum("td,edf->tef", u, wg)) * jnp.einsum("td,edf->tef", u, wu)
        y = jnp.einsum("tef,efd->ted", h, wd)
        onehot = jax.nn.one_hot(choices - lo, held, dtype=jnp.float32)  # out of range: all zero
        out = jnp.einsum("tk,tke,ted->td", weights, onehot, y)
        return jnp.sum(jnp.sin(out)), out

    def system(choices, u, weights, wg, wu, wd):
        out, _, given = moe.expert_ffn(u, choices, weights, wg, wu, wd, n_experts=experts, lo=lo)
        return jnp.sum(jnp.sin(out)), (out, given)

    ours = jax.checkpoint(system) if remat else system
    return (jax.jit(jax.value_and_grad(ours, argnums=range(1, 6), has_aux=True)),
            jax.jit(jax.value_and_grad(dense, argnums=range(1, 6), has_aux=True)))


@pytest.mark.parametrize("name,held_slots,lo,one_expert,remat,dtype", HELD_EDGES, ids=[e[0] for e in HELD_EDGES])
def test_the_held_range_is_exact_at_every_edge_of_its_row_buffers(name, held_slots, lo, one_expert, remat, dtype):
    """The windowed path (``C < T * k``) against the dense masked sum over
    the held experts, forward and all five gradients, whatever share of the
    slots the held experts receive: under the always-run buffers' rows,
    exactly, over (the second tier runs), none, one expert's alone, a range
    that ends at the router's last expert, under ``jax.checkpoint``.  The
    rows the grouped matmuls were given are the held slots, and those past
    the bound are the second tier's.  In bfloat16 (the rows and the expert
    matrices; the router's weights stay float32) the same against the
    float32 dense sum of the same rounded operands, to bfloat16's rounding
    of the layer's intermediates."""
    tokens, k, d, f, experts, held = 40, 3, 32, 24, 16, 4
    bound = moe.held_rows_bound(tokens * k, held, experts)
    assert bound == 48 < tokens * k
    choices = _choices_with(held_slots, lo, one_expert)
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((tokens, d)), dtype)
    weights = jnp.asarray(rng.uniform(0.05, 0.5, (tokens, k)), jnp.float32)
    wg, wu, wd = (jnp.asarray(rng.standard_normal(shape) * 0.3, dtype)
                  for shape in ((held, d, f), (held, d, f), (held, f, d)))
    windowed, dense = _windowed_and_dense(lo, remat)
    (_, (out, given)), grads = windowed(choices, u, weights, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = dense(choices, *(a.astype(jnp.float32) for a in (u, weights, wg, wu, wd)))
    assert int(given.first) + int(given.second) == held_slots  # moe_slots_computed == moe_slots_held
    assert int(given.second) == max(held_slots - bound, 0)  # moe_slots_overflow
    assert out.dtype == dtype and [g.dtype for g in grads] == [dtype, jnp.float32, dtype, dtype, dtype]
    tol = 1e-5 if dtype == jnp.float32 else 4e-2  # (bfloat16: 2**-8 a rounding, a dozen of them a value; 2.3e-2 read)
    assert _rel(out, want) <= tol
    for g, w, leaf in zip(grads, want_grads, ("u", "weights", "w_gate", "w_up", "w_down")):
        if held_slots == 0:
            assert not np.any(np.asarray(g)) and not np.any(np.asarray(w)), leaf
        else:
            assert _rel(g, w) <= tol, leaf


@pytest.mark.parametrize("held", [16, 4], ids=["every_expert_held", "a_share_held"])
def test_no_row_buffer_of_all_the_slots_in_the_held_path(held):
    """The rule that survives the row buffers' resizing, read off the jaxpr
    of the gradient.  No scatter of ROWS anywhere (the grouped matmul's and
    the token sum's bookkeeping scatter vectors of a few numbers).  With
    every expert held the rows move by gathers of all ``T * k`` slots, both
    ways; with a share held NO gather reads or writes ``T * k`` rows: the
    always-run tier moves ``C`` of them, the second tier the rest."""
    tokens, k, d, f, experts = 40, 3, 32, 24, 16
    choices = _choices_with(31, 4, False)
    u = jnp.ones((tokens, d))
    w = jnp.ones((held, d, f)), jnp.ones((held, d, f)), jnp.ones((held, f, d))

    def loss(u, weights, w):
        return jnp.sum(moe.expert_ffn(u, choices, weights, *w, n_experts=experts, lo=0)[0])

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(u, jnp.ones(choices.shape), w))
    scattered = re.findall(r":\w+\[([\d,]*)\] = scatter", text)
    assert all(shape.isdigit() and int(shape) <= 64 for shape in scattered), scattered
    gathered = [shape.split(",") for shape in re.findall(r":\w+\[([\d,]*)\] = gather", text)]
    rows = sorted({int(shape[0]) for shape in gathered if len(shape) == 2 and int(shape[1]) == d})
    if held == experts:
        assert rows == [tokens * k]
    else:
        bound = moe.held_rows_bound(tokens * k, held, experts)
        # the window's rows, and the token sum's copy of them in whole chunks of 128
        assert rows and set(rows) <= {bound, tokens * k - bound, 128}, rows
        assert "cond" in text and tokens * k not in rows


def _afmoe_shares_add_up_to_the_uncut_layer():
    """The same for a layer whose parts sit between TWO norms (``afmoe``,
    through that family's builder): the norm after the feed-forward is not
    linear, so what the chips' shares add up to is the PART's output — the
    routed parts of the eight, the shared expert counted once — and the
    layer is the residual add of its norm; against the family's plain
    reference with all 16 held."""
    keys = dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=1, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        layer_types=["sliding_attention"], sliding_window=32, num_dense_layers=0, intermediate_size=48, moe_intermediate_size=24,
        num_shared_experts=1, num_experts=16, num_experts_per_tok=3, score_func="sigmoid", route_norm=True, route_scale=2.826,
        mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000, seq_len=128, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
    )
    spec_of = lambda **kw: load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", compute_dtype="float32", **{**keys, **kw})  # noqa: E731
    layer_of = lambda spec: spec.init.keywords["layers"][0]  # noqa: E731
    reference = lm_family.reference("trinity_mini_26b_a3b_ep8_l5")
    params = _weights(spec_of())
    blk = params["blocks"]["b00"]
    assert blk["w_up"].shape == (16, 32, 24)
    x = jax.random.normal(jax.random.key(3), (2, 128, 32), jnp.float32)
    positions, cast = jnp.arange(128), lambda w: w  # noqa: E731
    common = dict(axis=None, eps=1e-5, compute_dtype=jnp.float32)
    (attention_entry, (_, _, post_ffn_norm)) = layer_of(spec_of())

    @jax.jit  # ONE program for what every chip computes alike ...
    def alike_parts(x, blk):
        want, want_slots = reference.build({**keys, "experts_held": 16}).layer(x, blk, "sliding_attention")
        whole, _ = moe_lm._block(x, blk, positions, layer_of(spec_of()), **common)
        # the stream the feed-forward reads, and the shared expert on it
        h, _ = moe_lm._block(x, blk, positions, (attention_entry,), **common)
        u = moe_lm._rms_norm(h, blk["ffn_norm"], 1e-5)
        return want, want_slots, whole, h, u, moe_lm._gated_mlp(u, blk["ws_gate"], blk["ws_up"], blk["ws_down"])

    def share(lo):  # ... and the eight shares' parts, each through the family's own builder, in ONE more
        experts = layer_of(spec_of(experts_held=2, first_expert_held=lo))[1][1]
        assert experts.router == moe_lm.Router(16, 3, 2, lo, experts.router.keys) and experts.shared_width == 24
        return lambda u, blk: experts.apply(
            u, {**blk, **{name: blk[name][lo:lo + 2] for name in ("w_gate", "w_up", "w_down")}}, positions, None, cast)

    shares = [share(lo) for lo in range(0, 16, 2)]
    with jax.default_matmul_precision("highest"):
        want, want_slots, whole, h, u, alike = alike_parts(x, blk)
        np.testing.assert_allclose(whole, want, atol=2e-5 * float(jnp.max(jnp.abs(want))))  # the uncut layer IS the reference's
        parts = []
        for got, stats in jax.jit(lambda u, blk: [part(u, blk) for part in shares])(u, blk):
            np.testing.assert_array_equal(np.asarray(stats["slots"]), np.asarray(want_slots))
            parts.append(got - alike)
        assert sum(float(jnp.abs(p).max()) > 0 for p in parts) >= 6  # (the seeded bias keeps a pair of experts from every token)
        layer = h + moe_lm._rms_norm(alike + sum(parts), blk[post_ffn_norm], 1e-5)  # the shared expert counted ONCE
    assert _rel(layer, want) <= 2e-5


@pytest.mark.parametrize("family", ["deepseek_v3", "afmoe"])
def test_the_eight_shares_add_up_to_the_uncut_layer(reference, family):
    """The share tied to the model.  ONE expert layer, 16 experts, at 8
    chips of 2: every chip runs the model's block with its own range held,
    on the same weights and tokens.  What differs between the chips' block
    outputs is the routed part alone (attention and the shared expert are
    computed alike by all): so chip 0's whole output plus the other chips'
    routed parts — their output less what every chip computes alike, i.e.
    less a run with NO slot held — is the uncut layer, which the plain
    reference gives with all 16 held."""
    if family == "afmoe":
        return _afmoe_shares_add_up_to_the_uncut_layer()
    shares, per = 8, 2
    whole = _keys("expert_layer", experts_held=16, first_expert_held=0)
    full = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", compute_dtype="float32", **whole)
    params, batch = _weights(full), _batch()
    blk = params["blocks"]["b00"]
    forward = reference.build(whole)
    with jax.default_matmul_precision("highest"):
        want_logits, want_slots = forward(params, batch["tokens"])
    # the model's block as ``_apply`` calls it, a share's weights at a time
    x = params["tok_emb"][batch["tokens"]]
    positions = jnp.arange(x.shape[1])
    common = dict(axis=None, eps=KEYS["rms_norm_eps"], compute_dtype=jnp.float32)
    attention = attentions.LatentAttention(
        n_heads=4, rank=32, nope=16, rot=8, v=16, theta=KEYS["rope_theta"], eps=KEYS["rms_norm_eps"], interleave=True)

    def share_of(lo):  # the layer with ``per`` of the 16 experts held from ``lo`` on
        router = moe_lm.Router(16, KEYS["num_experts_per_tok"], per, lo, tuple(
            (key, KEYS[key]) for key in ("scoring_func", "norm_topk_prob", "routed_scaling_factor")))
        return (("attn_norm", attention), ("ffn_norm", moe_lm.RoutedExperts(router, width=32, correction_bias=True, shared_width=64)))

    parts, alike = [], None
    for lo in range(0, shares * per, per):
        cut = {**blk, **{name: blk[name][lo:lo + per] for name in ("w_gate", "w_up", "w_down")}}
        out, (_, stats) = moe_lm._block(x, cut, positions, share_of(lo), **common)
        # what every chip computes alike: the same block with its held experts' weights zeroed
        zeroed = {**cut, **{name: jnp.zeros_like(cut[name]) for name in ("w_gate", "w_up", "w_down")}}
        same, _ = moe_lm._block(x, zeroed, positions, share_of(lo), **common)
        alike = same if alike is None else alike
        np.testing.assert_allclose(same, alike, atol=1e-6)  # attention + shared: every chip's alike
        parts.append(out - same)
        np.testing.assert_array_equal(np.asarray(stats["slots"]), np.asarray(want_slots[0]))
    assert sum(float(jnp.abs(p).max()) > 0 for p in parts) == shares  # every share routes something
    layer = alike + sum(parts)  # the shared expert and attention counted ONCE
    # ... through the head (final norm, untied head), against the reference's whole model
    with jax.default_matmul_precision("highest"):
        normed = layer * jax.lax.rsqrt(jnp.mean(layer * layer, -1, keepdims=True) + KEYS["rms_norm_eps"])
        got_logits = (normed * params["norm_f"]) @ params["head"]
    assert _rel(got_logits, want_logits) <= 1e-5


def test_bias_rule_over_two_steps_is_the_references_to_the_bit(reference):
    """Two training steps through the Trainer (AdamW that leaves the bias
    alone, then the model's own rule) against the reference's rule on the
    reference's own counts: every expert layer's bias, bit for bit, and
    neither is all zero."""
    import optax

    shape = "dense_then_experts"
    spec = _spec(shape)
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), mesh)
    state = trainer.init_state(jax.random.key(0))
    params = jax.tree.map(np.asarray, state.params)
    keys = _keys(shape)
    forward = reference.build(keys)
    optimizer = optax.adamw(keys["learning_rate"], b1=0.9, b2=0.95, eps=1e-8, weight_decay=0.1, mask=reference.decayed)
    ref_params = jax.tree.map(jnp.asarray, params)
    opt_state = optimizer.init(ref_params)

    def loss(params, batch):
        logits, slots = forward(params, batch["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(logits, batch["labels"]).mean(), slots

    ref_grads = jax.jit(jax.value_and_grad(loss, has_aux=True))  # compiled once, for both steps
    for step in range(2):
        batch = _batch(seed=step)
        state, _ = trainer.train_step(state, trainer.shard_batch(batch))
        with jax.default_matmul_precision("highest"):
            (_, slots), grads = ref_grads(ref_params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, ref_params)
        ref_params = reference.update_bias(optax.apply_updates(ref_params, updates), slots, keys["bias_update_speed"])
    for name in ("b01", "b02"):
        got = np.asarray(state.params["blocks"][name]["router_bias"])
        want = np.asarray(ref_params["blocks"][name]["router_bias"])
        np.testing.assert_array_equal(got, want)
        assert set(np.unique(np.abs(got))) <= {np.float32(0.0), np.float32(0.001), np.float32(0.002)} and got.any()
    assert "router_bias" not in state.params["blocks"]["b00"]


@pytest.mark.parametrize("tilt", [0.0, 4.0], ids=["the_inits_router", "a_router_tilted_onto_the_held"])
def test_step_counters_count_the_overflow(reference, tilt):
    """One train step's counters at 4 held experts of 16 (768 slots, always-
    run buffers of 512 rows) against the reference's own count of the slots
    each expert was sent: every held slot computed whatever the routing;
    with the init's router the held run fits the first tier
    (``moe_slots_overflow`` 0), with a correction bias that tilts every
    choice onto the held experts the rows past the bound are the second
    tier's — and the step is the same compiled program."""
    from elasticdl_tpu.common import jitsan

    shape = "expert_layer"
    lo, held = SHAPES[shape]["first_expert_held"], SHAPES[shape]["experts_held"]
    spec = _spec(shape)
    trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), create_mesh(jax.devices()[:1], num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    blk = state.params["blocks"]["b00"]
    bias = blk["router_bias"].at[lo:lo + held].add(tilt)
    state = state.replace(params={**state.params, "blocks": {"b00": {**blk, "router_bias": bias}}})
    batch = _batch()
    with jax.default_matmul_precision("highest"):
        _, ref_slots = jax.jit(reference.build(_keys(shape)))(jax.device_get(state.params), batch["tokens"])
    sent = float(np.asarray(ref_slots)[0, lo:lo + held].sum())
    slots = batch["tokens"].size * KEYS["num_experts_per_tok"]
    bound = moe.held_rows_bound(slots, held, KEYS["num_experts"])
    assert (slots, bound) == (768, 512)
    before = jitsan.compiles("trainer.train_step")
    state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
    assert jitsan.compiles("trainer.train_step") <= before + 1
    assert float(metrics["moe_slots"]) == slots and float(metrics["moe_slots_held"]) == sent
    assert float(metrics["moe_slots_computed"]) == sent
    assert float(metrics["moe_slots_overflow"]) == max(sent - bound, 0.0)
    assert (sent > bound) == bool(tilt), sent
    assert "moe_slots_overflow" in spec.step_counters and spec.step_counters == moe_lm.MOE_COUNTERS


def test_the_overflow_metric_reads_the_counter_and_nothing_on_the_parent(tmp_path, monkeypatch):
    """``moe_slots_overflow_pct.mla``: a data file and an entry appended to
    BENCHMARK.json, read by ``counter_delta`` as the growth of
    ``moe_slots_overflow`` over that of ``moe_slots_held`` inside the
    window; a program without the counter (the parent) gives no metric and
    no error."""
    import runfiles

    name, cell = "moe_slots_overflow_pct.mla", "kanana2_job"
    bench = resolve.Bench(ROOT)
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == name]
    assert cell in entry["workloads"]  # BENCHMARK.json's entry is where a metric's cells live (PR 39)
    spec = bench.metric_file(name)
    assert spec["reader"] == "counter_delta" and spec["better"] == "lower"
    for key in ("unit", "layer", "moves", "better", "source"):
        assert spec[key] == entry[key], key
    assert name in [m["name"] for m in bench.metrics_of(cell, "per_layer")]
    run = tmp_path / "run"
    (run / "metrics").mkdir(parents=True)
    monkeypatch.setattr(runfiles, "run_dir", lambda ctx: str(run))
    ctx = {"window": {"ts": [10.0, 13.0]}}

    def reading(overflow):
        records = [{"kind": "counter", "ts": 10.0 + i, "moe_slots_held": 1000.0 * (i + 1), **overflow(i)} for i in range(4)]
        (run / "metrics" / "metrics.jsonl").write_text("".join(json.dumps(r) + "\n" for r in records))
        return bench.reader(spec["reader"]).read(ctx, spec["params"])

    assert reading(lambda i: {"moe_slots_overflow": 0.0}) == 0.0
    assert reading(lambda i: {"moe_slots_overflow": 30.0 * i}) == pytest.approx(3.0)
    assert reading(lambda i: {}) is None


def test_other_routing_keys_raise():
    for bad in (dict(n_group=2), dict(topk_group=2), dict(q_lora_rank=1536), dict(scoring_func="tanh"),
                dict(topk_method="group_limited_greedy"), dict(experts_held=4, first_expert_held=13)):
        with pytest.raises(ValueError):
            _spec("expert_layer", **bad)


def test_two_devices_equal_one_with_latent_attention():
    """Sequence-sharded over two devices (the ring carries the shared rotary
    key with K and V, the slot counts are summed over the axis): the loss,
    the bias after a step and a weight's update are one device's."""
    spec = _spec("dense_then_experts")
    batch = _batch(b=2)
    results = []
    for n in (1, 2):
        mesh = create_mesh(jax.devices()[:n], num_devices=n)
        trainer = Trainer(spec, JobConfig(distribution_strategy="AllReduce"), mesh)
        state = trainer.init_state(jax.random.key(0))
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        results.append((float(metrics["loss"]), jax.tree.map(np.asarray, state.params)))
    (loss1, p1), (loss2, p2) = results
    assert abs(loss1 - loss2) <= 1e-5 * abs(loss1)
    np.testing.assert_array_equal(p1["blocks"]["b01"]["router_bias"], p2["blocks"]["b01"]["router_bias"])
    np.testing.assert_allclose(p1["blocks"]["b01"]["wq"], p2["blocks"]["b01"]["wq"], atol=2e-6)
