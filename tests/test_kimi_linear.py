"""``moe_lm`` under ``kimi_linear``'s keys against its plain reference
(``benchmark/configs/kimi_linear_48b_a3b_ep32_l5_reference.py``): the part,
the model's logits, loss, every gradient leaf, two AdamW steps and the
correction bias; the shares tied to the uncut layer; latent attention
without a rotary turn; the family's rule and what is refused."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, linear_attention, moe_lm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

CONFIG = "kimi_linear_48b_a3b_ep32_l5"

#: kimi_linear's keys at a small size, in the PUBLISHED spelling: a leading dense layer, two KDA layers and one
#: latent-attention layer, 4 of 16 experts top-3 and one shared, a sequence of two chunks of 64.
KEYS = dict(
    vocab_size=96, hidden_size=32, num_attention_heads=4, num_hidden_layers=3,
    linear_attn_config={"kda_layers": [1, 2], "full_attn_layers": [3], "num_heads": 4, "head_dim": 8, "short_conv_kernel_size": 4},
    mla_use_nope=True, kv_lora_rank=16, q_lora_rank=None, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, rope_theta=10000,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_token=3, intermediate_size=48, moe_intermediate_size=24,
    num_shared_experts=1, first_k_dense_replace=1, moe_layer_freq=1, moe_router_activation_func="sigmoid", moe_renormalize=True,
    routed_scaling_factor=2.446, use_grouped_topk=True, num_expert_group=1, topk_group=1, bias_update_speed=0.001,
    rms_norm_eps=1e-5, tie_word_embeddings=False, decay_matrices_only=True, seq_len=128, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
KDA = ("kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k", "kda_conv_v", "A_log", "dt_bias", "kda_wf_a", "kda_wf_b",
       "kda_wb", "kda_wg_a", "kda_wg_b", "kda_norm", "kda_wo")
MLA = ("wq", "wkv_a", "kv_norm", "wkv_b", "wo")
DENSE = ("w_gate", "w_up", "w_down")
EXPERTS = ("router", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down")
LAYERS = [KDA + DENSE, KDA + EXPERTS, MLA + EXPERTS]
LEAVES = ["tok_emb", "norm_f", "head"] + [
    f"blocks/b{i:02d}/{name}" for i, names in enumerate(LAYERS) for name in ("attn_norm", "ffn_norm") + names
]


def _moved(name, a, noise):
    """Gains that are not 1, matrices five times the init's scale (a trained
    model's decays and gates are not the init's near-constants)."""
    if name in ("A_log", "dt_bias", "router_bias") or name.startswith("kda_conv"):
        return a
    if name.endswith("norm") or name == "norm_f":
        return a + 0.3 * noise()
    return a * 5.0


reference = functools.partial(lm_family.reference, CONFIG)
_spec = functools.partial(lm_family.spec, KEYS)
_batch = functools.partial(lm_family.batch, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_layers, _leaf = lm_family.layers, lm_family.leaf


def _system_and_reference():
    (got, out), want = lm_family.system_and_reference(CONFIG, KEYS, _moved)
    return got, want, out


def test_float32_system_gives_the_references_logits_loss_slots_and_gradient_in_every_leaf():
    (loss, _), ((want, (want_logits, want_slots)), _), out = _system_and_reference()
    logits = out["logits"]
    assert logits.shape == want_logits.shape == (2, KEYS["seq_len"], 96) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    # the routers' counts, which the correction bias's rule reads: all 16 experts, 3 slots a token, two expert layers
    np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(want_slots))
    assert out["router_slots"].shape == (2, 16) and float(out["router_slots"].sum()) == 2 * 2 * 128 * 3


    # ... and the gradient in EVERY leaf, in the same test: the two sides are computed once a process (two minutes
    # of compiles) and a test of their own would make them again on another worker of the suite.  3e-4 of a leaf's
    # largest entry: float32 sums in another order (the chunked rule against 128 steps of the recurrence)
    (_, grads), (_, want), _ = _system_and_reference()
    assert len(jax.tree.leaves(grads)) == len(LEAVES) + 2  # and the two correction biases, which get none
    for leaf in LEAVES:
        got, ref = _leaf(grads, leaf), _leaf(want, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 3e-4 * float(jnp.max(jnp.abs(ref))), leaf
    for name in ("b01", "b02"):
        assert float(jnp.max(jnp.abs(grads["blocks"][name]["router_bias"]))) == 0.0


def test_the_part_alone_is_the_references_linear_attention():
    """``KimiDeltaAttention.apply`` on a normed stream against the reference's
    mixer on the same parameters: the convolutions, silu, l2norm, the decay,
    the rule, the gated norm a head and ``Wo``."""
    spec = _spec()
    blk = _weights(spec)["blocks"]["b01"]
    u = jax.random.normal(jax.random.key(3), (2, KEYS["seq_len"], 32))
    part = linear_attention.KimiDeltaAttention(heads=4, head_dim=8, conv_kernel=4, eps=1e-5)
    with jax.default_matmul_precision("highest"):
        got, counts = part.apply(u, blk, None, None, lambda w: w)
        # the reference's layer is mixer + feed-forward: the mixer alone is what it adds before the second norm
        forward = reference().build(dict(KEYS))
        silent = {**blk, **{name: jnp.zeros_like(blk[name]) for name in ("w_down", "ws_down")}}  # the feed-forward adds nothing
        want = forward.layer(u, {**silent, "attn_norm": jnp.ones((32,))})[0] - u
        normed = u * jax.lax.rsqrt(jnp.mean(u * u, -1, keepdims=True) + 1e-5)
        got_normed, _ = part.apply(normed, blk, None, None, lambda w: w)
    assert float(jnp.max(jnp.abs(got_normed - want))) <= 2e-5 * float(jnp.max(jnp.abs(want)))
    assert float(counts["kda_positions"]) == float(counts["kda_positions_chunked"]) == 2 * 128 * 4
    assert float(counts["kda_positions_mask_kernel"]) == 0  # off the TPU (and dk = 8): the XLA differences
    assert float(counts["kda_positions_conv_kernel"]) == 0  # likewise: the XLA chains over ops/ssm.causal_conv
    assert float(jnp.max(jnp.abs(got - got_normed))) > 0


def test_two_adamw_steps_and_the_correction_bias_are_the_references():
    """The trainer's own step twice on one minibatch against AdamW written
    out on the REFERENCE's gradients (this file's, float32): every leaf's
    change in each step to 1 % of its size (Adam's first steps are rate x
    g / (|g| + 1e-8): where float32 noise is a share of a small entry it is
    the same share of that entry's step), the correction biases TO THE BIT."""
    spec = _spec("float32", lr_warmup_steps=0, learning_rate=1e-3)
    ref = reference()
    batch = _batch()
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    w = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), state.params)  # a copy: the step donates its state
    m = jax.tree.map(jnp.zeros_like, w)
    nu = jax.tree.map(jnp.zeros_like, w)
    decayed = ref.decayed(w)
    assert decayed == moe_lm._is_decayed(w, moe_lm._NOT_MATRICES)

    with jax.default_matmul_precision("highest"):
        ref_grads = lm_family.reference_program(CONFIG, KEYS, _moved)  # the memo's program: on this batch, compiled already
        for t in (1, 2):
            state, metrics = trainer.train_step(state, trainer.shard_batch({k: np.asarray(v) for k, v in batch.items()}))
            (loss, (_, slots)), grads = ref_grads(w)
            assert float(metrics["loss"]) == pytest.approx(float(loss), rel=2e-6)
            m = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, m, grads)
            nu = jax.tree.map(lambda v, g: 0.95 * v + 0.05 * g * g, nu, grads)
            step = jax.tree.map(
                lambda m, v, p, d: (m / (1 - 0.9 ** t)) / (jnp.sqrt(v / (1 - 0.95 ** t)) + 1e-8) + (0.1 * p if d else 0.0), m, nu, w, decayed)
            before = w
            w = ref.update_bias(jax.tree_util.tree_map_with_path(
                lambda path, p, s: p if path[-1].key == "router_bias" else p - 1e-3 * s, w, step), slots, KEYS["bias_update_speed"])
            for (path, got), want, was in zip(jax.tree_util.tree_leaves_with_path(state.params), jax.tree.leaves(w), jax.tree.leaves(before)):
                if path[-1].key == "router_bias":
                    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=str(path))
                    assert float(jnp.max(jnp.abs(got))) <= t * 0.001 + 1e-9 and float(jnp.max(jnp.abs(got - was))) == pytest.approx(0.001)
                else:
                    assert float(jnp.linalg.norm(got - want)) <= 1e-2 * float(jnp.linalg.norm(want - was)), (t, path)


def test_the_parameters_are_the_held_share_of_the_published_shapes():
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(_spec().init, jax.random.key(0)))
    assert sorted(shapes["blocks"]) == ["b00", "b01", "b02"]
    for i, names in enumerate(LAYERS):
        bias = ("router_bias",) if "router" in names else ()
        assert sorted(shapes["blocks"][f"b{i:02d}"]) == sorted(("attn_norm", "ffn_norm") + names + bias), i
    kda, mla = shapes["blocks"]["b01"], shapes["blocks"]["b02"]
    assert kda["kda_wq"] == kda["kda_wk"] == kda["kda_wv"] == (32, 32) and kda["kda_wo"] == (32, 32)
    assert kda["kda_conv_q"] == (4, 32) and kda["A_log"] == (4,) and kda["dt_bias"] == (32,) and kda["kda_norm"] == (8,)
    assert kda["kda_wf_a"] == kda["kda_wg_a"] == (32, 8) and kda["kda_wf_b"] == kda["kda_wg_b"] == (8, 32) and kda["kda_wb"] == (32, 4)
    assert mla["wq"] == (32, 4 * 12) and mla["wkv_a"] == (32, 16 + 4) and mla["wkv_b"] == (16, 4 * 16) and mla["wo"] == (32, 32)
    assert kda["router"] == (32, 16) and kda["w_up"] == (4, 32, 24) and kda["ws_up"] == (32, 24)  # 4 of 16 held; ONE shared expert
    assert shapes["blocks"]["b00"]["w_up"] == (32, 48)


def test_the_expert_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once():
    """Guide section 4: 4 shares of 4 of 16 experts (a stand-in for 32
    shares of 8 of 256) give parts that add up to the uncut layer's; what
    every chip computes alike (the shared expert) is counted once."""
    uncut = _spec(experts_held=0, first_expert_held=0)
    blk = _weights(uncut)["blocks"]["b01"]
    assert blk["w_up"].shape == (16, 32, 24)
    u = jax.random.normal(jax.random.key(3), (2, KEYS["seq_len"], 32), jnp.float32)
    cast = lambda w: w  # noqa: E731
    keys = (("scoring_func", "sigmoid"), ("norm_topk_prob", True), ("routed_scaling_factor", 2.446))
    experts = lambda held, lo: moe_lm.RoutedExperts(moe_lm.Router(16, 3, held, lo, keys), width=24, correction_bias=True, shared_width=24)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole, stats = experts(16, 0).apply(u, blk, None, None, cast)
        alike = moe_lm._gated_mlp(u, blk["ws_gate"], blk["ws_up"], blk["ws_down"])
        parts = []
        for lo in (0, 4, 8, 12):
            share = {**blk, **{name: blk[name][lo:lo + 4] for name in ("w_gate", "w_up", "w_down")}}
            got, held = experts(4, lo).apply(u, share, None, None, cast)
            assert float(held["moe_slots_computed"]) == float(held["moe_slots_held"]) < float(stats["moe_slots"])
            parts.append(got - alike)
        parts.append(alike)  # once
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(parts[0] - parts[1]))) > 0
    assert float(jnp.max(jnp.abs(sum(parts) - whole))) <= 2e-5 * float(jnp.max(jnp.abs(whole)))
    # and the model's own layer IS that part: the family's builder maps its keys onto these
    ((_, mixer), (_, ffn)) = _layers(_spec())[1]
    assert ffn == experts(4, 4) and isinstance(mixer, linear_attention.KimiDeltaAttention)


def test_latent_attention_without_a_rotary_turn_is_kanana2s_with_theta_irrelevant():
    """``mla_use_nope``: the same part as ``deepseek_v3``'s with its rotary
    columns and shared key UNTURNED: equal to the turned part at position 0
    everywhere (a turn by nothing), whatever ``rope_theta`` is."""
    kimi = _layers(_spec())[2][0][1]
    assert isinstance(kimi, attentions.LatentAttention) and not kimi.rotary and kimi == _layers(_spec(rope_theta=10000))[2][0][1]
    other_theta = _layers(_spec(rope_theta=500000.0))[2][0][1]
    turned = attentions.LatentAttention(4, 16, 8, 4, 8, 10000.0, 1e-5, False)
    assert turned.rotary and dataclasses.replace(kimi, rotary=True) == turned
    blk = _weights(_spec())["blocks"]["b02"]
    u = jax.random.normal(jax.random.key(3), (2, 64, 32))
    positions = jnp.arange(64)
    with jax.default_matmul_precision("highest"):
        got, _ = kimi.apply(u, blk, positions, None, lambda w: w)
        same, _ = other_theta.apply(u, blk, positions, None, lambda w: w)
        at_zero, _ = turned.apply(u, blk, jnp.zeros_like(positions), None, lambda w: w)
        moved, _ = turned.apply(u, blk, positions, None, lambda w: w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(same))
    np.testing.assert_allclose(got, at_zero, rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(moved - got))) > 1e-3 * float(jnp.max(jnp.abs(got)))
    # kanana2's own builder is as it was: a deepseek_v3 model turns
    deepseek = {k: v for k, v in KEYS.items() if k not in (
        "linear_attn_config", "mla_use_nope", "num_experts_per_token", "num_shared_experts", "moe_router_activation_func",
        "moe_renormalize", "use_grouped_topk", "num_expert_group", "moe_layer_freq", "decay_matrices_only")}
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", num_experts_per_tok=3, scoring_func="sigmoid", **deepseek)
    assert all(layer[0][1] == turned for layer in _layers(spec))


def test_the_step_counters_are_what_the_shapes_give_and_a_ragged_sequence_is_stepwise():
    spec = _spec()
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(linear_attention.KDA_COUNTERS)
    assert all(spec.step_counters[name] for name in linear_attention.KDA_COUNTERS)
    batch = _batch()
    metrics_of = jax.jit(lambda batch: spec.metrics(spec.apply(spec.init(jax.random.key(0)), batch), batch))  # a program a length
    metrics = metrics_of(batch)
    assert float(metrics["kda_positions"]) == float(metrics["kda_positions_chunked"]) == 2 * 128 * 4 * 2  # two KDA layers of four heads
    assert float(metrics["moe_slots"]) == 2 * 2 * 128 * 3
    ragged = _batch(l=100)  # not whole chunks of 64: the op's stepwise path, counted as such
    metrics = metrics_of(ragged)
    assert float(metrics["kda_positions"]) == 2 * 100 * 8 and float(metrics["kda_positions_chunked"]) == 0


@pytest.mark.parametrize("backend,head_dim,length,kernel", [("tpu", 128, 128, 1), ("tpu", 64, 128, 0), ("cpu", 128, 128, 0), ("tpu", 128, 100, 0)],
                         ids=["on_the_tpu_inside_the_contract", "narrow_channels", "off_the_tpu", "a_ragged_sequence_is_stepwise"])
def test_the_mask_kernels_share_is_counted_where_the_op_is_called_from_what_it_was_called_with(monkeypatch, backend, head_dim, length, kernel):
    """``kda_positions_mask_kernel`` asks
    ``ops/delta_rule.mask_path`` of the very ``k`` the op is given, and only
    of a call that is chunked at all (``tests/test_delta_rule.py`` holds the
    traced op to the same answer: the Pallas calls are there or not)."""
    from elasticdl_tpu.models.parts import Draws
    from elasticdl_tpu.ops import delta_rule, short_conv

    part = linear_attention.KimiDeltaAttention(heads=2, head_dim=head_dim, conv_kernel=4, eps=1e-5)
    blk = part.init(Draws(jax.random.key(0), 32, 0.02), 16)
    given = []

    def rule(q, k, v, g, beta, *, chunk):  # the op's place: what it is given, and nothing computed
        given.append((k, chunk))
        return jnp.zeros_like(v)

    monkeypatch.setattr(delta_rule, "delta_rule", rule)
    # ... and the chains' (their kernels compile for a TPU alone: the case after this one holds their share)
    monkeypatch.setattr(short_conv, "short_conv", lambda t, taps, head_dim=None, *, interpret=False: jnp.zeros_like(t))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    _, counts = part.apply(jnp.zeros((1, length, 16)), blk, None, None, lambda w: w)
    ((k, chunk),) = given
    assert k.shape == (1, length, 2, head_dim) and chunk == 64
    assert float(counts["kda_positions"]) == length * 2 and float(counts["kda_positions_chunked"]) == length * 2 * (length % 64 == 0)
    assert float(counts["kda_positions_mask_kernel"]) == length * 2 * kernel
    # the same question the op asks (``_masks_of``, of the same width and chunk), where the op is chunked at all
    assert (delta_rule.mask_path(k, chunk)[0] == "pallas-compiled") == bool(kernel or length % 64)


@pytest.mark.parametrize("backend,head_dim,length,kernel,why", [
    ("tpu", 128, 128, 1, ""), ("tpu", 64, 128, 0, "; a head of 64 is not whole multiples of 128 that divide C = 128"),
    ("cpu", 128, 128, 0, "; backend=cpu"), ("tpu", 128, 100, 0, "; L = 100 is not whole multiples of 16"),
], ids=["on_the_tpu_inside_the_contract", "narrow_heads", "off_the_tpu", "a_ragged_sequence"])
def test_the_conv_kernels_share_is_counted_where_the_chains_are_called_and_the_path_is_logged(monkeypatch, backend, head_dim, length, kernel, why):
    """``kda_positions_conv_kernel`` asks ``ops/short_conv.conv_path`` of the
    very products and taps a chain is given, ``apply`` calls the kernels' op
    or the XLA chain by the same answer, and ONE ``attention path:`` line a
    distinct chain says which, with the reason (``tests/test_short_conv.py``
    holds ``conv_path`` itself).  A head narrower than a lane tile leaves the
    two NORMED chains outside the contract and the third inside: the pairs
    count where all three are the kernels'."""
    from elasticdl_tpu.models.parts import Draws
    from elasticdl_tpu.ops import delta_rule, ring_attention, short_conv

    part = linear_attention.KimiDeltaAttention(heads=2, head_dim=head_dim, conv_kernel=4, eps=1e-5)
    blk = part.init(Draws(jax.random.key(0), 32, 0.02), 16)
    by_kernels, lines = [], []

    def op(t, taps, head_dim=None, *, interpret=False):  # the kernels' place (they compile for a TPU alone): what they are given
        by_kernels.append((t.shape, taps.shape, head_dim, interpret))
        return jnp.zeros_like(t)

    monkeypatch.setattr(short_conv, "short_conv", op)
    monkeypatch.setattr(delta_rule, "delta_rule", lambda q, k, v, g, beta, *, chunk: jnp.zeros_like(v))
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    _, counts = part.apply(jnp.zeros((1, length, 16)), blk, None, None, lambda w: w)
    width = 2 * head_dim
    normed_by_kernels = backend == "tpu" and head_dim % 128 == 0 and length % 16 == 0
    unnormed_by_kernels = backend == "tpu" and length % 16 == 0
    assert by_kernels == [((1, length, width), (4, width), head_dim, False)] * 2 * normed_by_kernels + [((1, length, width), (4, width), None, False)] * unnormed_by_kernels
    assert float(counts["kda_positions"]) == length * 2 and float(counts["kda_positions_conv_kernel"]) == length * 2 * kernel
    line = "attention path: {path} (q=(1, {length}, {width}) float32 causal=True; kda_conv taps=4 norm={norm}{why})"
    assert lines[:3] == [
        line.format(path="pallas-compiled" if normed_by_kernels else "xla-reference", length=length, width=width, norm=head_dim, why=why)
    ] * 2 + [line.format(path="pallas-compiled" if unnormed_by_kernels else "xla-reference", length=length, width=width, norm=None,
                         why=why if head_dim % 128 == 0 else "")]


@pytest.fixture
def on_the_conv_kernels(monkeypatch):
    """``conv_path`` answers as if a test had given ``interpret``: every chain
    below is the Pallas pair under the interpreter.  The blocks are
    rematerialised, and jax keeps a ``jax.checkpoint``'s trace by shapes: the
    caches are dropped on both sides, so that no trace made on one path
    answers for the other."""
    from elasticdl_tpu.ops import short_conv

    path = short_conv.conv_path
    jax.clear_caches()
    monkeypatch.setattr(short_conv, "conv_path", lambda t, taps, head_dim, interpret=None: path(t, taps, head_dim, True))
    yield
    jax.clear_caches()


def test_the_model_on_the_conv_kernels_is_the_model_on_the_xla_chains_in_loss_and_every_gradient(request):
    """Heads of one lane tile put all three chains inside the kernels'
    contract: with ``interpret`` forced the part counts every pair by the
    kernels, and the model's loss and every gradient leaf are the XLA
    path's (float32: both sides the same arithmetic but for the order of a
    sum).  The model is its first layer alone (a KDA part and a dense
    feed-forward) over one chunk: what the two programs have to differ in."""
    wide = {"kda_layers": [1], "full_attn_layers": [], "num_heads": 2, "head_dim": 128, "short_conv_kernel_size": 4}
    spec, batch = _spec(linear_attn_config=wide, num_hidden_layers=1, seq_len=64), _batch(l=64)
    params = _weights(spec)

    def read():  # ONE program a side
        def loss(w):
            out = spec.apply(w, batch, train=True)
            return spec.loss(out, batch), spec.metrics(out, batch)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (want, counted), want_grads = read()
    assert float(counted["kda_positions_conv_kernel"]) == 0 and float(counted["kda_positions"]) == 2 * 64 * 2
    request.getfixturevalue("on_the_conv_kernels")
    (got, counted), got_grads = read()  # the count below is ``_chain``'s own answer: the kernels' op was what it called
    assert float(counted["kda_positions_conv_kernel"]) == float(counted["kda_positions"]) == 2 * 64 * 2
    assert abs(float(got) - float(want)) <= 1e-6 * abs(float(want))
    leaves = jax.tree_util.tree_leaves_with_path(want_grads)
    assert {path[-1].key for path, _ in leaves} >= {"kda_conv_q", "kda_conv_k", "kda_conv_v", "kda_wq", "tok_emb"}
    for (path, b), a in zip(leaves, jax.tree.leaves(got_grads)):
        assert bool(jnp.all(jnp.isfinite(a))) and float(jnp.max(jnp.abs(a - b))) <= 2e-5 * max(float(jnp.max(jnp.abs(b))), 1e-12), path


def test_the_job_trains_through_the_trainer():
    spec = _spec("float32", lr_warmup_steps=0, learning_rate=1e-2)
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    batch = {k: np.asarray(v) for k, v in _batch(b=2).items()}
    losses = []
    for _ in range(4):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.03, losses
    assert float(metrics["kda_positions"]) == 2 * 128 * 8
    mask = moe_lm._is_decayed(state.params, moe_lm._NOT_MATRICES)
    block = mask["blocks"]["b01"]
    assert block["kda_wq"] and block["kda_conv_q"] and block["kda_wo"] and mask["head"]
    assert not any(block[name] for name in ("attn_norm", "ffn_norm", "kda_norm", "A_log", "dt_bias", "router_bias"))


def test_bfloat16_compute_stays_near_the_float32_reference():
    spec, batch = _spec("bfloat16"), _batch()
    params = _weights(spec)
    logits = jax.jit(lambda w: spec.apply(w, batch)["logits"])(params)
    want, _ = jax.jit(reference().build(dict(KEYS)))(params, batch["tokens"])  # (not the memo's: this case may run on another worker)
    assert logits.dtype == jnp.float32
    # three layers at five times the init's scale (five read 0.10; nemotron_h's toy reads under 0.05 at its scale)
    assert float(jnp.sqrt(jnp.mean((logits - want) ** 2) / jnp.mean(want ** 2))) < 0.2


def test_linear_attn_config_decides_the_family_over_kv_lora_rank_and_no_other_pair_goes_together():
    family = lambda **keys: moe_lm._family(**{  # noqa: E731
        "hybrid_override_pattern": None, "attention_class": "mha", "linear_attn_config": None, "kv_lora_rank": 0, **keys})
    assert family() == "olmoe" and family(kv_lora_rank=512) == "deepseek_v3"
    assert family(linear_attn_config=KEYS["linear_attn_config"], kv_lora_rank=512) == "kimi_linear"  # the ONE rule
    assert family(linear_attn_config=KEYS["linear_attn_config"]) == "kimi_linear"
    for pair in (dict(hybrid_override_pattern="M", kv_lora_rank=512), dict(attention_class="eva", linear_attn_config={}),
                 dict(hybrid_override_pattern="M", linear_attn_config={})):
        with pytest.raises(ValueError, match="each name a family"):
            family(**pair)


@pytest.mark.parametrize("keys,match", [
    (dict(linear_attn_config={**KEYS["linear_attn_config"], "kda_layers": [1]}), "must name each of the layers 1..3 once"),
    (dict(linear_attn_config={**KEYS["linear_attn_config"], "full_attn_layers": [2, 3]}), "must name each of the layers 1..3 once"),
    (dict(linear_attn_config={**KEYS["linear_attn_config"], "chunk": 64}), "would be read by nothing"),
    (dict(moe_layer_freq=2), "moe_layer_freq 2"),
    (dict(num_expert_group=4, topk_group=2), "group-limited routing"),
    (dict(moe_router_activation_func="tanh"), "scoring_func 'tanh'"),
    (dict(head_dim=72), "head_dim: set, but no part of the 'kimi_linear' family reads it"),
    (dict(num_experts_per_tok=3), "num_experts_per_tok: set, but no part of the 'kimi_linear' family reads it"),
    (dict(linear_attn_config=None), "mla_use_nope.*no part of the 'deepseek_v3' family reads|num_experts_per_token"),
], ids=lambda v: v if isinstance(v, str) else "-".join(f"{k}" for k in v)[:40])
def test_keys_that_do_not_go_together_raise(keys, match):
    with pytest.raises(ValueError, match=match):
        _spec(**keys)


def test_a_sharded_sequence_is_refused():
    """The state at a shard's start lives on the shard before it: no silent
    wrong answer."""
    spec = _spec()
    mesh = create_mesh(num_devices=2)
    with pytest.raises(ValueError, match="sharded sequence"):
        trainer = Trainer(spec, JobConfig(), mesh)
        state = trainer.init_state(jax.random.key(0))
        batch = {k: np.asarray(v) for k, v in _batch(l=256).items()}
        trainer.train_step(state, trainer.shard_batch(batch))
