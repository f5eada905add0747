"""``moe_lm`` under ``afmoe``'s keys (Trinity-Mini) against its plain reference
(``benchmark/configs/trinity_mini_26b_a3b_ep8_l5_reference.py``): logits, loss,
every gradient leaf and the correction bias; a window that moves with the
query beside full attention with no position signal; the norm after a part;
the family's rule and what is refused.  The window INSIDE the flash kernels is
``tests/test_flash_attention.py``'s; the shares that add up to the uncut layer
``tests/test_latent_moe.py``'s.  CPU only."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

CONFIG = "trinity_mini_26b_a3b_ep8_l5"

#: afmoe's keys at a small size, in the PUBLISHED spelling: a leading dense layer, two sliding layers around a full
#: one, 4 query heads over 2 key/value heads, a window of 32 in a sequence of 128, 4 of 16 experts top-3 and one shared.
KEYS = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    layer_types=["sliding_attention", "full_attention", "sliding_attention"], sliding_window=32, rope_theta=10000,
    num_dense_layers=1, intermediate_size=48, moe_intermediate_size=24, num_shared_experts=1,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=3, score_func="sigmoid", route_norm=True,
    route_scale=2.826, n_group=1, topk_group=1, load_balance_coeff=0.001, mup_enabled=True,
    rms_norm_eps=1e-5, tie_word_embeddings=False, decay_matrices_only=True, seq_len=128, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
ATTENTION = ("wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm")
NORMS = ("attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm")
DENSE = ("w_gate", "w_up", "w_down")
EXPERTS = ("router", "w_gate", "w_up", "w_down", "ws_gate", "ws_up", "ws_down")
LAYERS = [ATTENTION + DENSE, ATTENTION + EXPERTS, ATTENTION + EXPERTS]
LEAVES = ["tok_emb", "norm_f", "head"] + [f"blocks/b{i:02d}/{name}" for i, names in enumerate(LAYERS) for name in NORMS + names]


def _moved(name, a, noise):
    """Gains that are not 1 (the per-head ones too), matrices five times the init's scale."""
    if name == "router_bias":
        return a
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
    assert logits.shape == want_logits.shape == (2, KEYS["seq_len"], 96) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(want_slots))
    assert out["router_slots"].shape == (2, 16) and float(out["router_slots"].sum()) == 2 * 2 * 128 * 3
    assert len(jax.tree.leaves(grads)) == len(LEAVES) + 2  # and the two correction biases, which get none
    for leaf in LEAVES:
        got, ref = _leaf(grads, leaf), _leaf(want_grads, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 1e-4 * float(jnp.max(jnp.abs(ref))), leaf
    for name in ("b01", "b02"):
        assert float(jnp.max(jnp.abs(grads["blocks"][name]["router_bias"]))) == 0.0
    # the step counters are what the shapes give: two sliding layers, 2 sequences x 4 heads x (32 x 33 / 2 + 96 x 32); one full
    # layer, 128 x 129 / 2; and a sequence of ONE block is outside the window kernels' contract: the XLA path multiplies every pair
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(attentions.WINDOW_COUNTERS)
    metrics = spec.metrics(out, batch)
    assert float(metrics["attn_pairs_window"]) == 2 * 2 * 4 * (32 * 33 // 2 + 96 * 32)
    assert float(metrics["attn_pairs_full"]) == 2 * 4 * (128 * 129 // 2)
    assert float(metrics["attn_pairs_window_computed"]) == 2 * 2 * 4 * 128 * 128


def test_the_models_window_is_the_references_and_one_key_either_way_is_not():
    """The model under ``sliding_window`` 32 against the reference under 32,
    31 and 33: one key fewer or more in every sliding layer moves the logits
    far past the agreement of the two sides."""
    spec = _spec()
    params, batch = _weights(spec), _batch(b=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda w: spec.apply(w, batch)["logits"])(params)
        for window, same in ((32, True), (31, False), (33, False)):
            want, _ = jax.jit(reference().build({**KEYS, "sliding_window": window}))(params, batch["tokens"])
            off = float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))
            assert (off <= 2e-5) if same else (off > 1e-3), (window, off)


def test_a_full_layer_has_no_position_signal_and_a_sliding_layer_has():
    """The part alone at positions p and p + 1000: a full layer's output is
    the same (no rotary turn, and the mask knows order, not position); a
    sliding layer's scores depend on DIFFERENCES of positions alone, so a
    common offset leaves it too — but positions that run twice as fast do
    not, and they leave the full layer where it was."""
    sliding, full = (_layers(_spec())[i][0][1] for i in (0, 1))
    assert sliding.window == 32 and full.window == 0 and sliding == _layers(_spec())[2][0][1]
    blk = _weights(_spec())["blocks"]["b00"]
    u = jax.random.normal(jax.random.key(3), (2, 128, 32))
    at = jnp.arange(128)
    run = lambda part, positions: part.apply(u, blk, positions, None, lambda w: w)[0]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        for part, moves in ((full, False), (sliding, True)):
            here, stretched = run(part, at), run(part, 2 * at)
            assert (float(jnp.max(jnp.abs(stretched - here))) > 1e-3 * float(jnp.max(jnp.abs(here)))) == moves
            np.testing.assert_allclose(run(part, at + 1000), here, atol=2e-5 * float(jnp.max(jnp.abs(here))))
        # and the two kinds differ on the same weights: the window hides keys, the turn moves scores
        assert float(jnp.max(jnp.abs(run(full, at) - run(sliding, at)))) > 1e-2 * float(jnp.max(jnp.abs(run(full, at))))


def test_the_correction_bias_after_two_steps_is_the_references_to_the_bit():
    """The trainer's own step twice (warm-up: the first update's rate is 0)
    against the reference's rule on the reference's own counts."""
    spec = _spec("float32")
    ref, batch = reference(), _batch()
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    w = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), state.params)
    assert ref.decayed(w) == moe_lm._is_decayed(w, moe_lm._NOT_MATRICES)
    forward = jax.jit(ref.build(dict(KEYS)))
    with jax.default_matmul_precision("highest"):
        for t in (1, 2):
            state, _ = trainer.train_step(state, trainer.shard_batch({k: np.asarray(v) for k, v in batch.items()}))
            # the reference's counts from the program's weights of the step before (the optimizer's part is not this test's)
            _, slots = forward(w, batch["tokens"])
            want = ref.update_bias(w, slots, KEYS["load_balance_coeff"])
            for name in ("b01", "b02"):
                got = np.asarray(state.params["blocks"][name]["router_bias"])
                np.testing.assert_array_equal(got, np.asarray(want["blocks"][name]["router_bias"]))
                assert 0 < np.abs(got).max() <= t * 0.001 + 1e-9
            w = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), state.params)


def test_the_parameters_are_the_held_share_of_the_published_shapes_and_the_layers_what_the_keys_say():
    spec = _spec()
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(spec.init, jax.random.key(0)))
    for i, names in enumerate(LAYERS):
        bias = ("router_bias",) if "router" in names else ()
        assert sorted(shapes["blocks"][f"b{i:02d}"]) == sorted(NORMS + names + bias), i
    blk = shapes["blocks"]["b01"]
    assert blk["wq"] == blk["wz"] == (32, 32) and blk["wk"] == blk["wv"] == (32, 16) and blk["wo"] == (32, 32)
    assert blk["q_norm"] == blk["k_norm"] == (8,) and blk["post_attn_norm"] == blk["post_ffn_norm"] == (32,)
    assert blk["router"] == (32, 16) and blk["w_up"] == (4, 32, 24) and blk["ws_up"] == (32, 24)
    assert shapes["blocks"]["b00"]["w_up"] == (32, 48)
    # every entry of a layer names the norm AFTER its part; the experts are deepseek_v3's part under this family's keys
    layers = _layers(spec)
    assert all(len(entry) == 3 for layer in layers for entry in layer)
    keys = (("scoring_func", "sigmoid"), ("norm_topk_prob", True), ("routed_scaling_factor", 2.826))
    assert layers[1][1][1] == moe_lm.RoutedExperts(moe_lm.Router(16, 3, 4, 4, keys), width=24, correction_bias=True, shared_width=24)
    assert layers[0][1][1] == moe_lm.GatedMLP(48)
    assert layers[1][0][1] == attentions.GatedWindowAttention(4, 2, 8, 0, 10000.0, 1e-5)
    assert spec.after_update.keywords["speed"] == 0.001
    # no gain is decayed, the per-head ones and the post-norms included
    decayed = moe_lm._is_decayed(jax.eval_shape(spec.init, jax.random.key(0)), moe_lm._NOT_MATRICES)
    assert not any(decayed["blocks"]["b01"][name] for name in NORMS + ("q_norm", "k_norm", "router_bias")) and decayed["blocks"]["b01"]["wz"]


def test_the_family_follows_from_sliding_window_and_foreign_keys_are_refused():
    assert moe_lm._family(hybrid_override_pattern=None, attention_class="mha", linear_attn_config=None, kv_lora_rank=0, sliding_window=2048) == "afmoe"
    with pytest.raises(ValueError, match="each name a family"):
        _spec(kv_lora_rank=16)
    with pytest.raises(ValueError, match="no part of the 'afmoe' family reads"):
        _spec(first_k_dense_replace=1)
    with pytest.raises(ValueError, match="no part of the 'olmoe' family reads"):
        load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", num_dense_layers=1)
    with pytest.raises(ValueError, match="must name the ATTENTION"):
        _spec(layer_types=["moe", "dense", "moe"])
    with pytest.raises(ValueError, match="load_balance_coeff"):
        _spec(bias_update_speed=0.01)
    # a sharded sequence under a window raises where the ring is asked, as the three mixers that cannot run there do
    from elasticdl_tpu.ops import ring_attention

    with pytest.raises(ValueError, match="window is a causal"):
        ring_attention.ring_attention(*(jnp.zeros((1, 128, 1, 8)),) * 3, causal=False, window=32)
