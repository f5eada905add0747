"""``moe_lm`` under ``smallthinker``'s keys (SmallThinker-21BA3B) against its plain reference
(``benchmark/configs/smallthinker_21b_a3b_ep8_l8_reference.py``): logits, loss, slots and every gradient leaf; the router
that reads the rows the ATTENTION reads (the value one entry of a layer hands a later one); relu-gated experts through
every tier of ``ops/moe.expert_ffn``; the eight shares that add up to the uncut layer; the full / window flash calls past
the old contract of 8192 rows; the family's rule and what is refused.  CPU only."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import moe
from elasticdl_tpu.ops.ring_attention import attention_reference

CONFIG = "smallthinker_21b_a3b_ep8_l8"

#: the family's keys at a small size, in the PUBLISHED spelling: a full layer without the turn and a sliding one with it
#: (the period's two kinds; a case's seconds are its layers'), 6 query heads over 2 key/value heads (an ODD group of
#: three), a window of 32 in a sequence of 128, 4 of 16 experts top-3.
KEYS = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=6, num_key_value_heads=2, head_dim=8,
    sliding_window_layout=[0, 1], rope_layout=[0, 1], sliding_window_size=32, rope_theta=1.5e6, rms_norm_eps=1e-6,
    moe_num_primary_experts=16, moe_num_active_primary_experts=3, moe_ffn_hidden_size=24, moe_primary_router_apply_softmax=True,
    norm_topk_prob=True, experts_held=4, first_expert_held=4, tie_word_embeddings=False, decay_matrices_only=True, seq_len=128,
    learning_rate=3e-4, weight_decay=0.1, lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
LAYER = ("attn_norm", "ffn_norm", "wq", "wk", "wv", "wo", "router", "w_gate", "w_up", "w_down")
LEAVES = ["tok_emb", "norm_f", "head"] + [f"blocks/b{i:02d}/{name}" for i in range(2) for name in LAYER]


def _moved(name, a, noise):
    """Gains that are not 1, matrices five times the init's scale (``wo`` and ``w_down`` from their stand-in scale too)."""
    return a * 5.0 if a.ndim > 1 else a + 0.3 * noise()


reference = functools.partial(lm_family.reference, CONFIG)
_spec = functools.partial(lm_family.spec, KEYS)
_batch = functools.partial(lm_family.batch, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_layers, _leaf = lm_family.layers, lm_family.leaf


def test_float32_system_gives_the_references_logits_loss_slots_and_gradient_in_every_leaf():
    spec, batch = _spec(), _batch()
    ((loss, grads), out), ((want, (want_logits, want_slots)), want_grads) = lm_family.system_and_reference(CONFIG, KEYS, _moved)
    logits = out["logits"]
    assert logits.shape == want_logits.shape == (2, 128, 96) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(want_slots))
    assert out["router_slots"].shape == (2, 16) and float(out["router_slots"].sum()) == 2 * 2 * 128 * 3
    assert sorted("/".join(key.key for key in path) for path, _ in jax.tree_util.tree_leaves_with_path(grads)) == sorted(LEAVES)
    for leaf in LEAVES:
        got, ref = _leaf(grads, leaf), _leaf(want_grads, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 1e-4 * float(jnp.max(jnp.abs(ref))), leaf
    # the step counters are what the shapes give: one sliding layer, one full; a sequence of ONE block is the XLA path's
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(attentions.WINDOW_COUNTERS)
    metrics = spec.metrics(out, batch)
    assert float(metrics["attn_pairs_window"]) == 2 * 6 * (32 * 33 // 2 + 96 * 32)
    assert float(metrics["attn_pairs_full"]) == 2 * 6 * (128 * 129 // 2)
    assert float(metrics["moe_slots"]) == 2 * 2 * 128 * 3 and float(metrics["moe_slots_computed"]) == float(metrics["moe_slots_held"])


def test_the_router_reads_the_rows_the_attention_reads_and_not_the_rows_the_experts_read(monkeypatch):
    """``ops/moe.route`` tapped while the block runs: in every layer it is called ONCE, BEFORE the layer's attention, on
    the rows the attention's projections read (``u``), and its choices are ``route``'s on those rows; ``route`` on the
    rows the experts read (``v``) chooses otherwise on the same seed — and a layer whose expert part routes on its own
    rows (``routes_on`` "": every older family) gives other logits."""
    spec = _spec()
    params, batch = _weights(spec), _batch(b=1)
    calls, real_route, real_attend = [], moe.route, attentions.ring_attention
    real_norm, normed = moe_lm._rms_norm, []
    monkeypatch.setattr(moe, "route", lambda u, wg, k, **keys: calls.append(("route", u)) or real_route(u, wg, k, **keys))
    monkeypatch.setattr(attentions, "ring_attention", lambda q, k, v, **keys: calls.append(("attend", None)) or real_attend(q, k, v, **keys))
    monkeypatch.setattr(moe_lm, "_rms_norm", lambda *a: normed.append(real_norm(*a)) or normed[-1])
    with jax.default_matmul_precision("highest"):
        out = spec.apply(params, batch)
    assert [what for what, _ in calls] == ["route", "attend"] * 2
    assert len(normed) == 2 * 2 + 1
    monkeypatch.undo()
    differing = 0
    for i, (_, rows) in enumerate(calls[0::2]):
        u, v = normed[2 * i].reshape(-1, 32), normed[2 * i + 1].reshape(-1, 32)
        np.testing.assert_array_equal(np.asarray(rows), np.asarray(u))
        wg = params["blocks"][f"b{i:02d}"]["router"]
        on_u, on_v = (moe.route(rows_, wg, 3, norm_topk_prob=True) for rows_ in (u, v))
        np.testing.assert_array_equal(np.asarray(jnp.sum(jax.nn.one_hot(on_u.choices, 16), (0, 1))), np.asarray(out["router_slots"][i]))
        differing += int(jnp.sum(on_u.choices != on_v.choices))
    assert differing > 50  # of 2 x 128 x 3: the attention moves the stream between the two norms
    late = tuple((norm, dataclasses.replace(part, routes_on="") if part.routes_on else part) for norm, part in _layers(spec)[1])
    x = params["tok_emb"][batch["tokens"]]
    common = dict(axis=None, eps=1e-6, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        early_out, _ = moe_lm._block(x, params["blocks"]["b01"], jnp.arange(128), _layers(spec)[1], **common)
        late_out, _ = moe_lm._block(x, params["blocks"]["b01"], jnp.arange(128), late, **common)
    assert float(jnp.max(jnp.abs(early_out - late_out))) > 1e-3 * float(jnp.max(jnp.abs(early_out)))


def _expert_loop(u, choices, weights, wg, wu, wd, lo, act):
    """A loop over the held experts, every one on every token."""
    y = 0.0
    for e in range(wu.shape[0]):
        w = jnp.sum(jnp.where(choices == lo + e, weights, 0.0), -1)
        y = y + ((act(u @ wg[e]) * (u @ wu[e])) @ wd[e]) * w[:, None]
    return y


@functools.lru_cache(maxsize=None)
def _relu_gated_programs(lo, experts):
    def system(choices, u, weights, wg, wu, wd):
        out, _, given = moe.expert_ffn(u, choices, weights, wg, wu, wd, n_experts=experts, lo=lo, activation="relu")
        return jnp.sum(jnp.sin(out)), (out, given)

    def loop(choices, u, weights, wg, wu, wd):
        out = _expert_loop(u, choices, weights, wg, wu, wd, lo, jax.nn.relu)
        return jnp.sum(jnp.sin(out)), out

    return (jax.jit(jax.value_and_grad(system, argnums=range(1, 6), has_aux=True)),
            jax.jit(jax.value_and_grad(loop, argnums=range(1, 6), has_aux=True)))


@pytest.mark.parametrize(
    "name,held,lo,held_slots", [("every_expert_held", 16, 0, 120), ("first_tier", 4, 4, 30), ("overflow_tier", 4, 4, 110), ("none_held", 4, 12, 0)])
def test_relu_gated_experts_are_a_loop_over_the_held_experts_in_every_tier(name, held, lo, held_slots):
    """``expert_ffn(activation="relu")`` against a loop over the held experts under relu, forward and all five gradients: the
    path whose buffers hold every slot, the windowed path inside its first tier, past it (the second tier's loop makes
    trips and its backward runs the windows again), and with no slot held; silu is another answer."""
    tokens, k, d, f, experts = 40, 3, 32, 24, 16
    rng = np.random.default_rng(held_slots + lo)
    if held < experts:
        flat = rng.integers(0, experts - held, tokens * k)
        flat = np.where(flat >= lo, flat + held, flat)  # nowhere on a held expert ...
        flat[rng.permutation(tokens * k)[:held_slots]] = lo + rng.integers(0, held, held_slots)  # ... but on ``held_slots`` slots
    else:
        flat = rng.integers(0, experts, tokens * k)
    choices = jnp.asarray(flat.reshape(tokens, k), jnp.int32)
    u = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    weights = jnp.asarray(rng.uniform(0.05, 0.5, (tokens, k)), jnp.float32)
    wg, wu, wd = (jnp.asarray(rng.standard_normal(shape) * 0.3, jnp.float32) for shape in ((held, d, f), (held, d, f), (held, f, d)))
    system, loop = _relu_gated_programs(lo, experts)
    (_, (out, given)), grads = system(choices, u, weights, wg, wu, wd)
    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = loop(choices, u, weights, wg, wu, wd)
    if held < experts:
        bound = moe.held_rows_bound(tokens * k, held, experts)
        assert int(given.first) + int(given.second) == held_slots and int(given.second) == max(held_slots - bound, 0)
        assert (int(given.second) > 0) == (name == "overflow_tier")
    rel = lambda a, b: float(jnp.max(jnp.abs(a - b))) / max(float(jnp.max(jnp.abs(b))), 1e-30)  # noqa: E731
    if held_slots == 0:
        assert not np.any(np.asarray(out)) and not np.any(np.asarray(want))
        return
    assert rel(out, want) <= 1e-5
    for g, w, leaf in zip(grads, want_grads, ("u", "weights", "w_gate", "w_up", "w_down")):
        assert rel(g, w) <= 1e-5, leaf
    silu = _expert_loop(u, choices, weights, wg, wu, wd, lo, jax.nn.silu)
    assert rel(out, silu) > 1e-2
    with pytest.raises(ValueError, match="activation"):
        moe.expert_ffn(u, choices, weights, wg, wu, wd, n_experts=experts, lo=lo, activation="gelu")


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The share tied to the model.  ONE layer of the family at 64 experts, top-6, at 8 chips of 8 (experts 0..7, 8..15,
    ...): every chip runs the model's block with its own range held, on the same weights and tokens.  What differs between
    the chips' block outputs is the routed part alone (the attention and the EARLY routing are computed alike by all): so
    what all compute alike plus every chip's routed part — its output less a run with the held experts' weights zeroed
    — is the uncut layer, which the plain reference gives with all 64 held."""
    whole = {**KEYS, "num_hidden_layers": 1, "sliding_window_layout": [1], "rope_layout": [1], "moe_num_primary_experts": 64,
             "moe_num_active_primary_experts": 6, "experts_held": 64, "first_expert_held": 0, "seq_len": 64}
    full = lm_family.spec(whole)
    params, batch = lm_family.weights(full, _moved), lm_family.batch(whole, b=1)
    blk = params["blocks"]["b00"]
    with jax.default_matmul_precision("highest"):
        want_logits, want_slots = jax.jit(reference().build(whole))(params, batch["tokens"])
    x, positions = params["tok_emb"][batch["tokens"]], jnp.arange(64)
    common = dict(axis=None, eps=1e-6, compute_dtype=jnp.float32)
    (norm_a, attention), (norm_f, experts) = _layers(full)[0]

    def share_of(lo):
        return ((norm_a, attention), (norm_f, dataclasses.replace(experts, router=dataclasses.replace(experts.router, held=8, first_held=lo))))

    @jax.jit
    def shares(x, blk):
        outs, slots = [], []
        for lo in range(0, 64, 8):
            cut = {**blk, **{name: blk[name][lo:lo + 8] for name in ("w_gate", "w_up", "w_down")}}
            out, (_, stats) = moe_lm._block(x, cut, positions, share_of(lo), **common)
            outs.append(out)
            slots.append(stats["slots"])
        # what every chip computes alike (the attention on the stream): a share's block with its held experts' weights zeroed
        zeroed = {**blk, **{name: jnp.zeros_like(blk[name][:8]) for name in ("w_gate", "w_up", "w_down")}}
        alike, _ = moe_lm._block(x, zeroed, positions, share_of(0), **common)
        return outs, slots, alike

    with jax.default_matmul_precision("highest"):
        outs, slots, alike = shares(x, blk)
        for sent in slots:  # the EARLY routing: every chip's alike, and the reference's
            np.testing.assert_array_equal(np.asarray(sent), np.asarray(want_slots[0]))
        parts = [out - alike for out in outs]
        assert sum(float(jnp.abs(p).max()) > 0 for p in parts) == 8  # every share routes something
        layer = alike + sum(parts)  # the attention counted ONCE
        logits = moe_lm._rms_norm(layer, params["norm_f"], 1e-6) @ params["head"]
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))


@pytest.mark.parametrize("window", [512, None], ids=["a_window_of_four_blocks", "full_causal"])
def test_the_flash_calls_past_the_old_contract_are_the_masked_softmax(monkeypatch, window):
    """The cell's two calls through the Pallas interpreter at the cell's GEOMETRY — 16 blocks a head, a window of four of
    them — with blocks of 128 rows so that the interpreter is cheap (``_BLOCK`` patched, with ``_MAX_L`` in its
    proportion: 16 blocks is past the 8 the old contract held), forward and VJP against the explicit mask: the far edge
    four blocks back, the ``w + 1`` = 5 steps a row block over 16 row blocks, the folded triangle's 136 steps."""
    monkeypatch.setattr(fa, "_BLOCK", 128)
    monkeypatch.setattr(fa, "_T_FWD", 64)
    monkeypatch.setattr(fa, "_T_BWD", 32)
    l = 2048
    assert fa._blocks(l) == (16, 128) and fa.outside_contract(*(jax.ShapeDtypeStruct((1, l, 1, 128), jnp.float32),) * 3, window=window) == ""
    plan = fa._Plan((1, l, 1, 128), True, 0, window or 0)
    assert plan.steps == ((80, 70) if window else (136, 136))
    q, k, v = (jax.random.normal(key, (1, l, 1, 128), jnp.float32) for key in jax.random.split(jax.random.key(7), 3))
    cot = jax.random.normal(jax.random.key(2), q.shape, jnp.float32)
    both = lambda attend: jax.jit(lambda q, k, v: jax.vjp(attend, q, k, v)[1](cot) + (attend(q, k, v),))  # noqa: E731
    got = both(lambda q, k, v: fa.flash_attention(q, k, v, True, window=window))(q, k, v)
    with jax.default_matmul_precision("highest"):
        want = both(lambda q, k, v: attention_reference(q, k, v, causal=True, window=window))(q, k, v)
    for a, b, name in zip(got, want, ("dq", "dk", "dv", "o")):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5, err_msg=name)


def test_the_contract_is_sixteen_thousand_rows_for_the_full_and_window_calls_and_eight_for_the_rotary():
    shape = lambda l, r=None: jax.ShapeDtypeStruct((1, l, 4, r or 128), jnp.bfloat16)  # noqa: E731
    assert fa._MAX_L == 16384 and fa._MAX_L_ROTARY == 8192
    assert fa.outside_contract(*(shape(16384),) * 3) == "" and fa.outside_contract(*(shape(16384),) * 3, window=4096) == ""
    assert "16384" in fa.outside_contract(*(shape(16512),) * 3)
    assert "whole blocks" in fa.outside_contract(*(shape(16384),) * 3, window=4000)
    k_rot = jax.ShapeDtypeStruct((1, 16384, 64), jnp.bfloat16)
    assert "over 8192" in fa.outside_contract(*(shape(16384),) * 3, shape(16384, 64), k_rot)
    assert fa.outside_contract(*(shape(8192),) * 3, shape(8192, 64), jax.ShapeDtypeStruct((1, 8192, 64), jnp.bfloat16)) == ""
    assert fa.window_pairs_computed(16384, 4096) < 16384 * 16384 / 3 and fa.window_outside_contract(16384, 4096) == ""


def test_the_parameters_are_the_held_share_of_the_published_shapes_and_the_layers_what_the_keys_say():
    spec = _spec()
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(spec.init, jax.random.key(0)))
    assert all(sorted(shapes["blocks"][f"b{i:02d}"]) == sorted(LAYER) for i in range(2))
    blk = shapes["blocks"]["b01"]
    assert blk["wq"] == (32, 48) and blk["wk"] == blk["wv"] == (32, 16) and blk["wo"] == (48, 32)
    assert blk["router"] == (32, 16) and blk["w_up"] == blk["w_gate"] == (4, 32, 24) and blk["w_down"] == (4, 24, 32)
    layers = _layers(spec)
    experts = moe_lm.RoutedExperts(
        moe_lm.Router(16, 3, 4, 4, (("norm_topk_prob", True),)), width=24, into_stream=moe_lm.KEYE_VL2_INTO_STREAM, activation="relu", routes_on="attn_norm")
    attention = functools.partial(
        attentions.GatedWindowAttention, 6, 2, 8, theta=1.5e6, eps=1e-6, gate=False, head_norm=False, into_stream=moe_lm.KEYE_VL2_INTO_STREAM,
        product_sites=False)
    assert layers[0] == (("attn_norm", attention(window=0, rotary=False)), ("ffn_norm", experts))
    assert layers[1] == (("attn_norm", attention(window=32, rotary=True)), ("ffn_norm", experts))
    assert spec.after_update is None  # no correction bias: nothing moves after a step but what AdamW moves
    # the stand-in scale of the two matrices that write into the stream; every other draw at the init's
    params = spec.init(jax.random.key(0))["blocks"]["b01"]
    assert 0.5 < float(jnp.std(params["wo"])) / (0.02 * 0.01) < 1.5 and 0.5 < float(jnp.std(params["w_down"])) / (0.02 * 0.01) < 1.5
    assert 0.8 < float(jnp.std(params["wq"])) / 0.02 < 1.2 and 0.8 < float(jnp.std(params["w_up"])) / 0.02 < 1.2
    decayed = moe_lm._is_decayed(jax.eval_shape(spec.init, jax.random.key(0)), moe_lm._NOT_MATRICES)
    assert not decayed["blocks"]["b01"]["attn_norm"] and not decayed["blocks"]["b01"]["ffn_norm"] and decayed["blocks"]["b01"]["router"]


def test_the_family_follows_from_sliding_window_layout_and_foreign_keys_are_refused():
    named = dict(hybrid_override_pattern=None, attention_class="mha", linear_attn_config=None, kv_lora_rank=0)
    assert moe_lm._family(**named, sliding_window_layout=[0, 1]) == "smallthinker" and moe_lm._family(**named) == "olmoe"
    with pytest.raises(ValueError, match="each name a family"):
        _spec(sliding_window=32)
    with pytest.raises(ValueError, match="no part of the 'smallthinker' family reads"):
        _spec(num_experts=16)
    with pytest.raises(ValueError, match="no part of the 'olmoe' family reads"):
        lm_family.spec({}, rope_layout=[0, 1])
    with pytest.raises(ValueError, match="a 0 or a 1"):
        _spec(sliding_window_layout=[0, 2])
    with pytest.raises(ValueError, match="a 0 or a 1"):
        _spec(rope_layout=[0, 1, 1])
    with pytest.raises(ValueError, match="moe_primary_router_apply_softmax false"):
        _spec(moe_primary_router_apply_softmax=False)
    with pytest.raises(ValueError, match="moe_primary_router_apply_softmax false"):
        _spec(norm_topk_prob=False)
    # rope_layout left out follows the window's layout, as the published model has them
    assert _layers(_spec(rope_layout=None)) == _layers(_spec())
