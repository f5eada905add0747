"""``moe_lm`` under ``lfm2_moe``'s keys (LFM2-8B-A1B) against its plain
reference (``benchmark/configs/lfm2_8b_a1b_ep4_l5_reference.py``): the
double-gated short convolution as ONE op (``ops/short_conv.gated_conv``: the
XLA chain against a position-by-position float32 reference, the Pallas kernel
pair under the interpreter against the XLA chain, the output and EVERY
gradient; a block's edge, a sequence's first rows, two sequences that must not
see each other; which path a call takes), the part, the whole model (logits,
loss, every gradient leaf, the correction bias's counts), the four shares that
add up to the uncut layer, the family's rule and what is refused.  CPU only."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.models import attentions, gated_conv, moe_lm
from elasticdl_tpu.models.parts import Draws
from elasticdl_tpu.ops import short_conv as sc
from elasticdl_tpu.ops import short_conv_kernels as kernels
from elasticdl_tpu.ops import ssm

CONFIG = "lfm2_8b_a1b_ep4_l5"

#: lfm2_moe's keys at a small size, in the PUBLISHED spelling: a conv layer with the dense feed-forward, an attention
#: layer (4 query heads over 2 key/value heads of 8) and a conv layer with experts, 4 of 16 held from the fourth, top-3
KEYS = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
    layer_types=["conv", "full_attention", "conv"], conv_L_cache=3, conv_bias=False, rope_theta=1000000, norm_eps=1e-5,
    num_dense_layers=1, intermediate_size=48, moe_intermediate_size=24, num_experts=16, experts_held=4, first_expert_held=4,
    num_experts_per_tok=3, use_expert_bias=True, norm_topk_prob=True, routed_scaling_factor=1, bias_update_speed=0.001,
    tie_word_embeddings=True, decay_matrices_only=True, seq_len=64, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
#: the other mix of operators and feed-forwards: attention over the DENSE layer, two conv layers with experts, 4 taps
MIXES = {"conv_dense_first": {}, "attention_dense_first": dict(layer_types=["full_attention", "conv", "conv"], conv_L_cache=4)}
CONV, ATTENTION = ("gconv_in", "gconv_taps", "gconv_out"), ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
DENSE, EXPERTS, NORMS = ("w_gate", "w_up", "w_down"), ("router", "w_gate", "w_up", "w_down"), ("operator_norm", "ffn_norm")


def _leaves(kinds):
    layers = [(CONV if kind == "conv" else ATTENTION) + (DENSE if i == 0 else EXPERTS) for i, kind in enumerate(kinds)]
    return ["tok_emb", "norm_f"] + [f"blocks/b{i:02d}/{name}" for i, names in enumerate(layers) for name in NORMS + names]


def _moved(name, a, noise):
    """Gains that are not 1 (the per-head ones too), matrices five times the init's scale, the taps as drawn."""
    if name in ("router_bias", "gconv_taps"):
        return a
    return a * 5.0 if a.ndim > 1 else a + 0.3 * noise()


_spec = functools.partial(lm_family.spec, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_layers, _leaf = lm_family.layers, lm_family.leaf

# ---- the op ----


def _operands(seed=0, *, b=2, length=48, channels=512, dtype=jnp.float32, taps=3):
    ks = jax.random.split(jax.random.key(seed), 5)
    bcz = tuple(jax.random.normal(k, (b, length, channels)).astype(dtype) for k in ks[:3])
    w = jax.random.uniform(ks[3], (taps, channels), jnp.float32, -taps ** -0.5, taps ** -0.5)
    return bcz, w, jax.random.normal(ks[4], (b, length, channels))


def _by_position(b, c, z, w):
    """``c * conv(b * z)`` a position at a time, float32 throughout."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    return f32(c) * ssm.causal_conv_reference(f32(b) * f32(z), w, jnp.zeros((b.shape[-1],)))


def _xla_chain(b, c, z, w):
    y, by_kernels = sc.gated_conv(b, c, z, w)
    assert not by_kernels
    return y


def _kernels(b, c, z, w):
    y, by_kernels = sc.gated_conv(b, c, z, w, interpret=True)
    assert by_kernels
    return y


def _read(op, bcz, w, weigh):
    """``(y, db, dc, dz, dtaps)`` of ``sum(op(b, c, z, w) * weigh)``: ONE program."""
    def loss(b, c, z, w):
        y = op(b, c, z, w)
        return jnp.sum(y.astype(jnp.float32) * weigh), y

    (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))(*bcz, w)
    return (y,) + grads


def _close(got, want, rel, what=""):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got))), what
    assert float(jnp.max(jnp.abs(got - want))) <= rel * float(jnp.max(jnp.abs(want))), what


NAMES = ("y", "db", "dc", "dz", "dtaps")


@pytest.fixture
def small_blocks(monkeypatch):
    """A grid step of 16 positions by 256 channels: [2, 48, 512] is three
    blocks of rows (a first, a middle and a last one) by two of lanes, a sequence."""
    monkeypatch.setattr(kernels, "_ROWS", 16)
    monkeypatch.setattr(kernels, "_COLS", 256)


@pytest.mark.parametrize("taps", [3, 4])
def test_the_xla_chain_is_the_position_by_position_reference_forward_and_in_all_four_gradients(taps):
    bcz, w, weigh = _operands(taps=taps, length=24, channels=128)
    got, want = _read(_xla_chain, bcz, w, weigh), _read(_by_position, bcz, w, weigh)
    for name, a, b in zip(NAMES, got, want):
        _close(a, b, 1e-5, name)
    assert float(jnp.max(jnp.abs(want[4]))) > 0 and got[4].shape == (taps, 128)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype,of_f32", [(jnp.float32, 1e-5), (jnp.bfloat16, 8e-3)], ids=["float32", "bfloat16"])
def test_the_kernel_pair_is_the_xla_chain_to_the_rounding_and_the_float32_reference_in_every_gradient(small_blocks, dtype, of_f32, taps):
    """Both paths make the product, the convolution and the second gate in
    float32 from the operands and round ONCE: the interpreter's kernels give
    the XLA chain's very values (the taps' sums in another order)."""
    bcz, w, weigh = _operands(dtype=dtype, taps=taps)
    assert "pallas_call" in str(jax.make_jaxpr(lambda *o: _kernels(*o))(*bcz, w))
    got = _read(_kernels, bcz, w, weigh)
    assert all(g.dtype == dtype for g in got[:4]) and got[4].dtype == jnp.float32
    chain = _read(_xla_chain, bcz, w, weigh)
    for name, a, b in zip(NAMES[:4], got, chain):
        np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)), err_msg=name)
    _close(got[4], chain[4], 2e-6, "dtaps")
    for name, a, b in zip(NAMES, got, _read(_by_position, bcz, w, weigh)):
        _close(a, b, of_f32, name)


def test_no_row_of_one_sequence_reaches_the_next_and_the_first_positions_see_zeros_before_them(small_blocks):
    """A batch of two is two sequences: the second row's output and gradients
    are what it gives ALONE, whatever the first row ends in; and the first K
    - 1 positions are the taps' last ones on the positions there are."""
    bcz, w, weigh = _operands(3)
    bcz = tuple(t.at[0, -3:].set(1e3) for t in bcz)  # what a leak over the batch row's start would carry
    both, alone = _read(_kernels, bcz, w, weigh), _read(_kernels, tuple(t[1:] for t in bcz), w, weigh[1:])
    for name, a, b in zip(NAMES[:4], both, alone):
        _close(a[1:], b, 1e-6, name)
    first = _by_position(*(t[1:, :2] for t in bcz), w)  # the sequence cut after K - 1 positions: nothing before them
    _close(both[0][1:, :2], first, 1e-5)
    assert float(jnp.max(jnp.abs(first))) > 0


def test_whole_blocks_of_the_real_size_are_the_xla_chain():
    """The module's own block (no patch): 1024 positions are two blocks of
    512 rows worked through in tiles of 128; a block's last rows feed the
    next block's first through the halo, forward and in the gradient."""
    bcz, w, weigh = _operands(7, b=1, length=1024, channels=256)
    for name, a, b in zip(NAMES, _read(_kernels, bcz, w, weigh), _read(_xla_chain, bcz, w, weigh)):
        _close(a, b, 2e-5, name)  # dtaps sums 1024 positions


@pytest.mark.parametrize("backend,shape,taps,path,why", [
    ("cpu", (2, 48, 512), 3, "xla-reference", "backend=cpu"),
    ("tpu", (2, 48, 512), 3, "pallas-compiled", ""),
    ("tpu", (4, 8192, 2048), 3, "pallas-compiled", ""),
    ("tpu", (2, 48, 200), 3, "xla-reference", "C = 200 is not whole multiples of 128"),
    ("tpu", (2, 100, 512), 3, "xla-reference", "L = 100 is not whole multiples of 16"),
    ("tpu", (2, 48, 512), 18, "xla-reference", "K - 1 = 17 reaches past a halo of 16"),
    ("tpu", (48, 512), 3, "xla-reference", "t (48, 512) is not [B, L, C]"),
], ids=["off_the_tpu", "on_the_tpu_inside_the_contract", "the_cells_shape", "channels_not_whole_lanes", "ragged_length", "taps_past_the_halo",
        "no_batch_axis"])
def test_the_backend_and_the_shapes_alone_choose_the_gated_path(monkeypatch, backend, shape, taps, path, why):
    """No flag: ``gated_path`` reads the backend and the operands' shapes and
    names why not the kernels; asked for by name (``interpret``), the kernels
    refuse what is outside their contract."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    t, w = jax.ShapeDtypeStruct(shape, jnp.bfloat16), jax.ShapeDtypeStruct((taps, shape[-1]), jnp.float32)
    assert sc.gated_path(t, t, t, w) == (path, why) and sc.outside_gated_contract(t, t, t, w) == (why if backend == "tpu" else "")
    if why and backend == "tpu":
        with pytest.raises(ValueError, match="outside their contract"):
            sc.gated_path(t, t, t, w, True)
    else:
        assert sc.gated_path(t, t, t, w, True) == ("pallas-interpret", "") and sc.gated_path(t, t, t, w, False) == ("pallas-compiled", "")


def test_operands_that_differ_are_outside_the_contract(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    t, w = jax.ShapeDtypeStruct((2, 48, 512), jnp.bfloat16), jax.ShapeDtypeStruct((3, 512), jnp.float32)
    for other in (jax.ShapeDtypeStruct((2, 48, 256), jnp.bfloat16), jax.ShapeDtypeStruct((2, 48, 512), jnp.float32)):
        path, why = sc.gated_path(t, other, t, w)
        assert path == "xla-reference" and why.startswith("the operands differ")


# ---- the part and the model ----


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_float32_system_gives_the_references_logits_loss_slots_and_gradient_in_every_leaf(mix):
    keys = {**KEYS, **MIXES[mix]}
    ((loss, grads), out), ((want, (want_logits, want_slots)), want_grads) = lm_family.system_and_reference(CONFIG, KEYS, _moved, **MIXES[mix])
    logits = out["logits"]
    assert logits.shape == want_logits.shape == (2, KEYS["seq_len"], 96) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 2e-6 * float(want)  # the divisor's 1e-20 against the published 1e-6 is in here
    np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(want_slots))
    assert out["router_slots"].shape == (2, 16) and float(out["router_slots"].sum()) == 2 * 2 * 64 * 3
    leaves = _leaves(keys["layer_types"])
    assert len(jax.tree.leaves(grads)) == len(leaves) + 2  # and the two correction biases, which get none; NO head: it is tied
    for leaf in leaves:
        got, ref = _leaf(grads, leaf), _leaf(want_grads, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 1e-4 * float(jnp.max(jnp.abs(ref))), leaf
    for name in ("b01", "b02"):
        assert float(jnp.max(jnp.abs(grads["blocks"][name]["router_bias"]))) == 0.0


def test_the_step_counters_are_the_conv_layers_positions_and_the_attention_layers_pairs():
    """Two conv layers of 2 sequences x 64 positions, none by the kernels off
    the TPU; one attention layer's causal pairs; no window's counters in a
    model that has no window."""
    spec, batch = _spec(), lm_family.batch(KEYS)
    (_, out), _ = lm_family.system_and_reference(CONFIG, KEYS, _moved)
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(gated_conv.GCONV_COUNTERS) | {"attn_pairs_full"}
    metrics = spec.metrics(out, batch)
    assert float(metrics["gconv_positions"]) == 2 * 2 * 64 and float(metrics["gconv_positions_kernel"]) == 0.0
    assert float(metrics["attn_pairs_full"]) == 2 * 4 * (64 * 65 // 2)


def test_the_part_counts_what_the_op_said_it_ran(monkeypatch):
    """``gconv_positions_kernel`` is the op's own second return, not a constant re-derived elsewhere."""
    part = gated_conv.GatedShortConv(3)
    blk = part.init(Draws(jax.random.key(0), 3, 0.02), 128)
    u = jax.random.normal(jax.random.key(1), (2, 32, 128))
    for interpret, by_kernels in ((None, 0.0), (True, 2 * 32.0)):
        real = sc.gated_conv
        monkeypatch.setattr(sc, "gated_conv", lambda b, c, z, w, real=real, interpret=interpret: real(b, c, z, w, interpret=interpret))
        _, counts = part.apply(u, blk, jnp.arange(32), None, lambda w: w)
        monkeypatch.setattr(sc, "gated_conv", real)
        assert float(counts["gconv_positions"]) == 64.0 and float(counts["gconv_positions_kernel"]) == by_kernels


def test_the_conv_part_is_the_equations_and_a_sharded_sequence_is_refused():
    """The part alone against the reference's shifts on the three column
    blocks of ONE wide product: a slice of the weight gives the same numbers
    as a split of the activation."""
    part = _layers(_spec())[0][0][1]
    assert part == gated_conv.GatedShortConv(3, False)
    blk = _weights(_spec())["blocks"]["b00"]
    u = jax.random.normal(jax.random.key(3), (2, 64, 32))
    with jax.default_matmul_precision("highest"):
        got, _ = part.apply(u, blk, jnp.arange(64), None, lambda w: w)
        wide = u @ blk["gconv_in"]
        want = lm_family.reference(CONFIG).gated_convolution(wide[..., :32], wide[..., 32:64], wide[..., 64:], blk["gconv_taps"]) @ blk["gconv_out"]
    _close(got, want, 1e-5)
    # a change at position t moves the outputs at t, t + 1, t + 2 and no other: causal, three taps
    moved, _ = part.apply(u.at[:, 10].add(1.0), blk, jnp.arange(64), None, lambda w: w)
    changed = np.flatnonzero(np.asarray(jnp.max(jnp.abs(moved - got), axis=(0, 2))) > 0)
    assert changed.tolist() == [10, 11, 12]
    mesh = jax.make_mesh((2,), ("x",), devices=jax.devices()[:2])
    sharded = jax.shard_map(lambda u: part.apply(u, blk, jnp.arange(32), "x", lambda w: w)[0], mesh=mesh,
                            in_specs=jax.sharding.PartitionSpec(None, "x"), out_specs=jax.sharding.PartitionSpec(None, "x"))
    with pytest.raises(ValueError, match="sharded sequence is not supported"):
        jax.eval_shape(sharded, u)


def test_the_attention_layer_norms_a_head_then_turns_and_has_no_gate():
    """``GatedWindowAttention`` with the fields this family sets: no ``wz``,
    a rotary turn on a FULL layer (positions that run twice as fast move the
    output; a common offset does not), every earlier key."""
    part = _layers(_spec())[1][0][1]
    assert part == attentions.GatedWindowAttention(4, 2, 8, 0, 1000000.0, 1e-5, gate=False, rotary=True)
    blk = _weights(_spec())["blocks"]["b01"]
    assert "wz" not in blk and blk["q_norm"].shape == (8,) and blk["wk"].shape == (32, 16)
    u, at = jax.random.normal(jax.random.key(3), (2, 64, 32)), jnp.arange(64)
    run = lambda positions: part.apply(u, blk, positions, None, lambda w: w)[0]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        here = run(at)
        assert float(jnp.max(jnp.abs(run(2 * at) - here))) > 1e-3 * float(jnp.max(jnp.abs(here)))
        np.testing.assert_allclose(run(at + 1000), here, atol=5e-5 * float(jnp.max(jnp.abs(here))))


def test_the_gate_and_the_turn_are_fields_and_every_draw_of_the_part_that_had_them_is_where_it_was():
    """``afmoe``'s part draws wq, wk, wv, wz, wo from the stream's next five
    keys, as before the fields; without the gate the fourth key goes to wo."""
    keys = jax.random.split(jax.random.key(0), 5)
    draw = lambda key, shape: jax.random.normal(key, shape, jnp.float32) * 0.02  # noqa: E731
    with_gate = attentions.GatedWindowAttention(4, 2, 8, 32, 1e4, 1e-5).init(Draws(jax.random.key(0), 5, 0.02), 32)
    for key, name, shape in zip(keys, ("wq", "wk", "wv", "wz", "wo"), ((32, 32), (32, 16), (32, 16), (32, 32), (32, 32))):
        np.testing.assert_array_equal(np.asarray(with_gate[name]), np.asarray(draw(key, shape)), err_msg=name)
    without = attentions.GatedWindowAttention(4, 2, 8, 0, 1e4, 1e-5, gate=False, rotary=True).init(Draws(jax.random.key(0), 5, 0.02), 32)
    assert sorted(without) == ["k_norm", "q_norm", "wk", "wo", "wq", "wv"]
    np.testing.assert_array_equal(np.asarray(without["wo"]), np.asarray(draw(keys[3], (32, 32))))
    assert attentions.GatedWindowAttention(4, 2, 8, 32, 1e4, 1e-5).counters == attentions.WINDOW_COUNTERS


def test_the_four_shares_add_up_to_the_uncut_layer():
    """ONE expert layer at a 32-wide router, top-4: the parts that hold experts
    0..7, 8..15, 16..23 and 24..31 (each through the family's own builder)
    add up to the reference's layer with all 32 held; every share routes
    alike (the router is whole on each) and their held slots are all the slots."""
    keys = dict(KEYS, num_hidden_layers=1, num_dense_layers=0, layer_types=["conv"], num_experts=32, num_experts_per_tok=4)
    whole = lm_family.spec({**keys, "experts_held": 32, "first_expert_held": 0})
    params = lm_family.weights(whole, _moved)
    blk = params["blocks"]["b00"]
    u = jax.random.normal(jax.random.key(5), (2, 64, 32))
    forward = lm_family.reference(CONFIG).build({**keys, "experts_held": 32, "first_expert_held": 0})

    def reference_part(u, blk):  # the reference's layer less its operator and the stream: x + ffn(norm(x)) at a zero operator
        still = dict(blk, gconv_out=jnp.zeros_like(blk["gconv_out"]))
        y, slots = forward.layer(u, still, "conv")
        return y - u, slots

    def shares(u, blk):
        out = []
        for lo in range(0, 32, 8):
            part = _layers(lm_family.spec({**keys, "experts_held": 8, "first_expert_held": lo}))[0][1][1]
            assert part.router == moe_lm.Router(32, 4, 8, lo, (("scoring_func", "sigmoid"), ("norm_topk_prob", True)))
            held = {name: blk[name][lo:lo + 8] for name in ("w_gate", "w_up", "w_down")}
            normed = moe_lm._rms_norm(u, blk["ffn_norm"], 1e-5)
            out.append(part.apply(normed, {**blk, **held}, None, None, lambda w: w))
        return out

    with jax.default_matmul_precision("highest"):
        (want, want_slots), parts = jax.jit(reference_part)(u, blk), jax.jit(shares)(u, blk)
    total = sum(y for y, _ in parts)
    _close(total, want, 2e-5)
    assert float(jnp.max(jnp.abs(parts[0][0] - want))) > 0.1 * float(jnp.max(jnp.abs(want)))  # one share is not the layer
    for _, stats in parts:
        np.testing.assert_array_equal(np.asarray(stats["slots"]), np.asarray(want_slots))
    assert sum(float(stats["moe_slots_held"]) for _, stats in parts) == float(parts[0][1]["moe_slots"]) == 2 * 64 * 4


def test_the_parameters_are_the_held_share_of_the_published_shapes_and_no_gain_or_tap_is_decayed():
    spec = _spec()
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(spec.init, jax.random.key(0)))
    assert sorted(shapes) == ["blocks", "norm_f", "tok_emb"]  # the head is the table
    assert sorted(shapes["blocks"]["b00"]) == sorted(NORMS + CONV + DENSE)
    assert sorted(shapes["blocks"]["b01"]) == sorted(NORMS + ATTENTION + EXPERTS + ("router_bias",))
    blk = shapes["blocks"]["b02"]
    assert blk["gconv_in"] == (32, 96) and blk["gconv_taps"] == (3, 32) and blk["gconv_out"] == (32, 32)
    assert blk["router"] == (32, 16) and blk["w_up"] == (4, 32, 24) and shapes["blocks"]["b00"]["w_up"] == (32, 48)
    assert spec.after_update.keywords["speed"] == 0.001
    decayed = moe_lm._is_decayed(jax.eval_shape(spec.init, jax.random.key(0)), moe_lm._NOT_MATRICES)
    assert not any(decayed["blocks"]["b02"][name] for name in NORMS + ("gconv_taps", "router_bias")) and decayed["blocks"]["b02"]["gconv_in"]
    assert decayed == lm_family.reference(CONFIG).decayed(jax.eval_shape(spec.init, jax.random.key(0)))
    taps = _weights(spec)["blocks"]["b00"]["gconv_taps"]
    assert float(jnp.max(jnp.abs(taps))) <= 3 ** -0.5 and float(jnp.std(taps)) > 0.2  # uniform(+-K^-1/2)


@pytest.mark.parametrize("keys,refusal", [
    (dict(conv_bias=True), "conv_bias true"),
    (dict(use_expert_bias=False), "use_expert_bias false"),
    (dict(sliding_window=32), "each name a family"),
    (dict(kv_lora_rank=16), "each name a family"),
    (dict(layer_types=["conv", "moe", "conv"]), "must name the OPERATOR"),
    (dict(layer_types=["conv", "sliding_attention", "conv"]), "must name the OPERATOR"),
    (dict(rms_norm_eps=1e-6), "norm_eps"),
    (dict(norm_eps=0.0), "norm_eps"),
    (dict(head_dim=16), "no part of the 'lfm2_moe' family reads"),
    (dict(first_k_dense_replace=1), "no part of the 'lfm2_moe' family reads"),
    (dict(num_key_value_heads=3), "query heads over 3 key/value heads"),
    (dict(conv_L_cache=0, norm_eps=0.0, conv_bias=False, use_expert_bias=False, num_dense_layers=0, layer_types=None), "no part of the 'olmoe' family reads"),
], ids=["conv_bias", "no_expert_bias", "with_a_window", "with_a_latent_rank", "feed_forward_kinds", "afmoe_kinds", "the_other_epsilon",
        "no_epsilon", "a_foreign_key", "another_familys_dense_key", "heads_that_do_not_group", "its_keys_without_the_family"])
def test_the_family_follows_from_conv_L_cache_and_what_no_cell_runs_is_refused_by_name(keys, refusal):
    assert moe_lm._family(hybrid_override_pattern=None, attention_class="mha", linear_attn_config=None, kv_lora_rank=0, conv_L_cache=3) == "lfm2_moe"
    with pytest.raises(ValueError, match=refusal):
        _spec(**keys)
