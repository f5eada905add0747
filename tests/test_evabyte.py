"""``models/moe_lm.py`` under EvaByte's keys — EVA attention over a SHARE of
the heads, norms that multiply by 1 + gain, a float32 residual stream,
eight heads of prediction — against the plain reference
(``benchmark/configs/evabyte_6b5_tp2_l4_reference.py``: no code shared with
the model or ``ops/``) on seeded random weights at a small size, and the
pieces one by one: the eight heads' targets at the record's end, the two
head shares that add up to the uncut layer, the counters, the decay mask.
CPU only."""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import lm_family
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.ops import eva_attention as eva_ops
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

CONFIG = "evabyte_6b5_tp2_l4"

#: EvaByte's keys at a small size: 4 heads of 16 of which 2 are held, windows of 64 in chunks of 8,
#: a sequence of three windows and a ragged tail that is not whole chunks.
KEYS = dict(
    vocab_size=320, hidden_size=64, num_attention_heads=4, heads_held=2, num_hidden_layers=2,
    layer_types=("dense", "dense"), intermediate_size=96, attention_class="eva", window_size=64, chunk_size=8,
    rope_theta=1e5, rms_norm_eps=1e-5, norm_add_unit_offset=True, fp32_skip_add=True, num_pred_heads=8,
    init_std=0.01275, tie_word_embeddings=False, decay_matrices_only=True, seq_len=203, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
BLOCK_LEAVES = ("attn_norm", "wq", "wk", "wv", "wo", "eva_phi", "eva_mu", "ffn_norm", "w_gate", "w_up", "w_down")
LEAVES = ["tok_emb", "norm_f", "head"] + [f"blocks/{b}/{name}" for b in ("b00", "b01") for name in BLOCK_LEAVES]


def _moved(name, a, noise):
    """Gains that are not 0, EVA's vectors that are not 0, matrices five times the init's scale."""
    return a * 5.0 if name.startswith("w") or name in ("head", "tok_emb") else a + 0.3 * noise()


_spec = functools.partial(lm_family.spec, KEYS)
_batch = functools.partial(lm_family.batch, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_leaf = lm_family.leaf


def _system(spec, batch):
    """``w -> ((loss, gradients), logits)``"""
    def system(w):
        return jax.value_and_grad(lambda w: spec.loss(spec.apply(w, batch, train=True), batch))(w), spec.apply(w, batch)["logits"]

    return system


def _plain(ref, keys, batch):
    """The same of the plain reference, whose ``build`` gives the forward and the eight heads' loss."""
    ref_forward, ref_loss = ref.build(dict(keys))
    return lambda w: (jax.value_and_grad(ref_loss)(w, batch["tokens"], batch["labels"]), ref_forward(w, batch["tokens"]))


def _system_and_reference():
    (got, logits), (want, want_logits) = lm_family.system_and_reference(CONFIG, KEYS, _moved, _system, _plain)
    return got, want, (logits, want_logits)


def test_float32_system_gives_the_references_logits_and_loss():
    (loss, _), (want, _), (logits, want_logits) = _system_and_reference()
    assert logits.shape == want_logits.shape == (2, KEYS["seq_len"], 8, 320) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)


@pytest.mark.parametrize("leaf", LEAVES)
def test_float32_system_gives_the_references_gradient(leaf):
    (_, grads), (_, want), _ = _system_and_reference()
    got, ref = _leaf(grads, leaf), _leaf(want, leaf)
    assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0
    assert float(jnp.max(jnp.abs(got - ref))) <= 2e-4 * float(jnp.max(jnp.abs(ref))), leaf


def test_the_parameters_are_the_held_heads_of_the_published_shapes():
    shapes = jax.eval_shape(_spec().init, jax.random.key(0))
    blk = shapes["blocks"]["b00"]
    assert sorted(blk) == sorted(BLOCK_LEAVES)  # no QK-norm, no router
    assert blk["wq"].shape == blk["wk"].shape == blk["wv"].shape == (64, 2 * 16) and blk["wo"].shape == (2 * 16, 64)
    assert blk["eva_phi"].shape == blk["eva_mu"].shape == (2, 16)
    assert shapes["head"].shape == (64, 8 * 320) and shapes["tok_emb"].shape == (320, 64)
    params = _spec().init(jax.random.key(0))
    # gains and EVA's vectors start at 0 (the norms multiply by 1 + gain); matrices at init_std
    for name in ("attn_norm", "ffn_norm", "eva_phi", "eva_mu"):
        assert not jnp.any(params["blocks"]["b00"][name])
    assert not jnp.any(params["norm_f"])
    assert abs(float(jnp.std(params["blocks"]["b00"]["w_gate"])) - 0.01275) < 1e-3


@pytest.mark.parametrize("l", [5, 8, 30])
def test_the_eight_heads_are_held_to_the_targets_the_record_has(l):
    """Head p at position i is held to ``labels[i + p]`` where ``i + p <
    L``: each head's mean over its own L - p positions, the heads weighed
    alike; a record shorter than the heads reach leaves the far heads out."""
    rng = np.random.default_rng(l)
    logits = jnp.asarray(rng.normal(size=(2, l, 8, 320)), jnp.float32)
    labels = jnp.asarray(rng.integers(0, 320, (2, l)), jnp.int32)
    loss = jax.jit(lambda logits: moe_lm._cross_entropy({"logits": logits}, {"labels": labels}))  # ONE program, not a few dozen eager ones a head
    got = loss(logits)
    per_head = jax.jit(lambda logits: jnp.stack([
        jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits[:, : l - p, p], labels[:, p:])) for p in range(min(8, l))
    ]))(logits)
    assert abs(float(got) - float(np.mean(np.asarray(per_head)))) < 1e-5
    # no head reads past the record: heads 1..7 have no target at the last position
    moved = loss(logits.at[:, l - 1, 1:].add(100.0))
    assert float(moved) == float(got)


def test_the_two_head_shares_add_up_to_the_uncut_layer():
    """Guide section 4: the ``o Wo`` parts of heads 0..1 and 2..3 add up to
    the uncut layer's attention output; the MLP, which every chip computes
    alike on the summed result, is counted once."""
    uncut = _spec(heads_held=0, num_hidden_layers=1, layer_types=("dense",))
    params = _weights(uncut)
    blk = params["blocks"]["b00"]
    assert blk["wq"].shape == (64, 64)
    x = jax.random.normal(jax.random.key(3), (2, KEYS["seq_len"], 64), jnp.float32)
    positions = jnp.arange(KEYS["seq_len"])
    attention = attentions.EvaAttention(held=4, head_dim=16, theta=1e5, window=64, chunk=8)
    layer = (("attn_norm", attention), ("ffn_norm", moe_lm.GatedMLP(96)))  # the uncut model's, as its family builds it
    attend = lambda a, blk: dataclasses.replace(attention, held=blk["eva_phi"].shape[0]).apply(  # noqa: E731
        a, blk, positions, None, lambda w: w)[0]
    a = moe_lm._rms_norm(x, 1.0 + blk["attn_norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        whole = attend(a, blk)
        parts = []
        for lo in (0, 2):
            cols = slice(lo * 16, (lo + 2) * 16)
            share = {**blk, "wq": blk["wq"][:, cols], "wk": blk["wk"][:, cols], "wv": blk["wv"][:, cols],
                     "wo": blk["wo"][cols], "eva_phi": blk["eva_phi"][lo : lo + 2], "eva_mu": blk["eva_mu"][lo : lo + 2]}
            parts.append(attend(a, share))
        assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(parts[0] - parts[1]))) > 0
        assert float(jnp.max(jnp.abs(sum(parts) - whole))) <= 1e-5 * float(jnp.max(jnp.abs(whole)))
        h = x + sum(parts)
        u = moe_lm._rms_norm(h, 1.0 + blk["ffn_norm"], 1e-5)
        once = h + moe_lm._gated_mlp(u, blk["w_gate"], blk["w_up"], blk["w_down"])
        out, _ = moe_lm._block(x, blk, positions, layer, axis=None, eps=1e-5, compute_dtype=jnp.float32, unit_offset=True)
    assert float(jnp.max(jnp.abs(once - out))) <= 1e-5 * float(jnp.max(jnp.abs(out)))


def test_the_residual_stream_is_float32_and_the_blocks_compute_in_bfloat16():
    spec = _spec("bfloat16")
    params, batch = spec.init(jax.random.key(0)), _batch()
    seen = []
    real = eva_ops.eva_attention
    eva_ops.eva_attention = lambda q, *a, **kw: (seen.append(q.dtype), real(q, *a, **kw))[1]
    try:
        jaxpr = jax.make_jaxpr(lambda w: spec.apply(w, batch)["logits"])(params)
    finally:
        eva_ops.eva_attention = real
    assert seen == [jnp.bfloat16, jnp.bfloat16]
    # every block's result is added into a float32 [B, L, d] stream
    adds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "add" and e.outvars[0].aval.shape == (2, KEYS["seq_len"], 64)]
    assert len(adds) == 4 and all(e.outvars[0].aval.dtype == jnp.float32 for e in adds)


def test_the_step_counters_are_the_pairs_the_shapes_give():
    spec = _spec()
    params, batch = spec.init(jax.random.key(0)), _batch()
    metrics = spec.metrics(spec.apply(params, batch), batch)
    exact, far = eva_ops.pairs(KEYS["seq_len"], 64, 8)
    scored = 2 * 2 * 2  # sequences x held heads x layers
    assert sorted(spec.step_counters) == ["eva_pairs_exact", "eva_pairs_summary"]
    assert float(metrics["eva_pairs_exact"]) == scored * exact and float(metrics["eva_pairs_summary"]) == scored * far
    assert 0.0 <= float(metrics["accuracy"]) <= 1.0 and float(metrics["lb_loss"]) == 0.0


def test_adamw_decays_the_matrices_alone_and_the_job_trains():
    """Through ``Trainer``: a few steps lower the loss; the gains and EVA's
    vectors, which get a gradient but no decay, move by Adam's step alone
    (at most the learning rate a step), whatever their size."""
    spec = _spec("float32", lr_warmup_steps=0, learning_rate=1e-2)
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    batch = {k: np.asarray(v) for k, v in _batch(b=2).items()}
    losses = []
    for _ in range(6):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses
    mask = moe_lm._is_decayed(state.params, moe_lm._NOT_MATRICES)
    assert mask["blocks"]["b00"]["wq"] and mask["head"] and mask["tok_emb"]
    for name in ("attn_norm", "ffn_norm", "eva_phi", "eva_mu"):
        assert not mask["blocks"]["b00"][name], name
    assert not mask["norm_f"]
    # the other models' mask is as it was: every leaf but the correction biases;
    # and it is the configuration's own key that chooses, not the norm's
    assert all(jax.tree.leaves(moe_lm._is_decayed(state.params)))
    everything = _spec("float32", decay_matrices_only=False).optimizer.init(state.params)
    is_masked = lambda s: isinstance(s, optax.MaskedState)  # noqa: E731
    assert any(map(is_masked, jax.tree.leaves(state.opt_state, is_leaf=is_masked)))
    assert not any(map(is_masked, jax.tree.leaves(everything, is_leaf=is_masked))), "no key, no mask"


def test_keys_that_do_not_go_together_raise():
    with pytest.raises(ValueError, match="attention_class"):
        _spec(attention_class="sliding")
    with pytest.raises(ValueError, match="whole chunks"):
        _spec(window_size=60)
    with pytest.raises(ValueError, match="heads_held"):
        _spec(heads_held=5)
    with pytest.raises(ValueError, match="heads_held, window_size: set, but no part of the 'olmoe' family reads them"):
        _spec(attention_class="mha", heads_held=2, norm_add_unit_offset=False)
    with pytest.raises(ValueError, match="num_pred_heads"):
        _spec(tie_word_embeddings=True)
    with pytest.raises(ValueError, match="each name a family"):
        _spec(kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)


def test_a_sharded_sequence_is_refused():
    """The summaries of earlier windows would live on other shards: no
    silent wrong answer."""
    spec = _spec()
    mesh = create_mesh(num_devices=2)
    with pytest.raises(ValueError, match="sharded sequence"):
        trainer = Trainer(spec, JobConfig(), mesh)
        state = trainer.init_state(jax.random.key(0))
        batch = {k: np.asarray(v) for k, v in _batch(l=256).items()}
        trainer.train_step(state, trainer.shard_batch(batch))
