"""``moe_lm`` under ``nemotron_h``'s keys against its plain reference
(``benchmark/configs/nemotron3_super_tp4_ep64_l11_reference.py``): logits,
loss and every gradient leaf for a pattern that holds all three kinds of
layer; the shares tied to the model; what is refused."""

import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, mamba, moe_lm
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import moe
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

CONFIG = "nemotron3_super_tp4_ep64_l11"

#: nemotron_h's keys at a small size: a period of the three kinds, 4 of 8 state-space heads (2 of 4 groups),
#: 2 of 8 query heads on 1 of 2 key/value heads, 4 of 16 experts top-5, a sequence of three chunks.
KEYS = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=5, hybrid_override_pattern="EMEM*",
    mamba_num_heads=8, mamba_heads_held=4, mamba_head_dim=8, n_groups=4, ssm_state_size=8, conv_kernel=4, chunk_size=16,
    num_attention_heads=8, heads_held=2, num_key_value_heads=2, kv_heads_held=1, head_dim=8,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=5, moe_latent_size=16,
    moe_intermediate_size=24, moe_shared_expert_intermediate_size=40, mlp_hidden_act="relu2",
    scoring_func="sigmoid", topk_method="noaux_tc", norm_topk_prob=True, routed_scaling_factor=5.0,
    bias_update_speed=0.001, rms_norm_eps=1e-5, tie_word_embeddings=False, decay_matrices_only=True,
    rescale_prenorm_residual=True, residual_layers=88, seq_len=48, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
KINDS = {
    "M": ("norm", "ssm_in", "conv_w", "conv_b", "dt_bias", "A_log", "D", "ssm_norm", "ssm_out"),
    "*": ("norm", "wq", "wk", "wv", "wo"),
    "E": ("norm", "router", "w_lat_down", "w_lat_up", "w_up", "w_down", "ws_up", "ws_down"),
}
LEAVES = ["tok_emb", "norm_f", "head"] + [
    f"blocks/b{i:02d}/{name}" for i, kind in enumerate(KEYS["hybrid_override_pattern"]) for name in KINDS[kind]
]


def _moved(name, a, noise):
    """Gains, biases and ``D`` that are not 0 or 1, matrices five times the
    init's scale (the writers into the stream, scaled down by 88^-1/2, fifty times)."""
    if name in ("ssm_out", "wo", "w_down", "ws_down", "w_lat_up"):
        return a * 50.0
    if name.startswith("w") or name in ("head", "tok_emb", "router", "ssm_in"):
        return a * 5.0
    if name in ("A_log", "dt_bias", "conv_w"):
        return a
    return a + 0.3 * noise()


reference = functools.partial(lm_family.reference, CONFIG)
_spec = functools.partial(lm_family.spec, KEYS)
_batch = functools.partial(lm_family.batch, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_leaf = lm_family.leaf


def _system_and_reference():
    (got, out), want = lm_family.system_and_reference(CONFIG, KEYS, _moved)
    return got, want, out


def test_float32_system_gives_the_references_logits_loss_and_slots():
    (loss, _), ((want, (want_logits, want_slots)), _), out = _system_and_reference()
    logits = out["logits"]
    assert logits.shape == want_logits.shape == (2, KEYS["seq_len"], 96) and logits.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(logits - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want)
    # the routers' counts, which the correction bias's rule reads: all 16 experts, 5 slots a token
    np.testing.assert_array_equal(np.asarray(out["router_slots"]), np.asarray(want_slots))
    assert out["router_slots"].shape == (2, 16) and float(out["router_slots"].sum()) == 2 * 2 * 48 * 5


def test_float32_system_gives_the_references_gradient_in_every_leaf():
    """ONE test over all the leaves: the two gradients are computed once a
    process (two minutes of compiles), and a case a leaf spreads them over
    every worker of the suite."""
    (_, grads), (_, want), _ = _system_and_reference()
    assert len(jax.tree.leaves(grads)) == len(LEAVES) + 2  # and the two correction biases, which get none
    for leaf in LEAVES:
        got, ref = _leaf(grads, leaf), _leaf(want, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 3e-4 * float(jnp.max(jnp.abs(ref))), leaf
    for name in ("b00", "b02"):
        assert float(jnp.max(jnp.abs(grads["blocks"][name]["router_bias"]))) == 0.0


def test_the_correction_bias_moves_by_the_models_rule():
    # (that it gets no gradient: the gradient test above, which holds the gradients)
    spec = _spec()
    params = _weights(spec)
    out = jax.jit(lambda w: spec.apply(w, _batch()))(params)
    moved = spec.after_update(params, out)
    want = reference().update_bias(params, out["router_slots"], KEYS["bias_update_speed"])
    for name in ("b00", "b02"):
        np.testing.assert_array_equal(np.asarray(moved["blocks"][name]["router_bias"]), np.asarray(want["blocks"][name]["router_bias"]))
        assert float(jnp.max(jnp.abs(moved["blocks"][name]["router_bias"] - params["blocks"][name]["router_bias"]))) > 0


def test_the_parameters_are_the_held_share_of_the_published_shapes():
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(_spec().init, jax.random.key(0)))
    assert sorted(shapes["blocks"]) == ["b00", "b01", "b02", "b03", "b04"]
    m, a, e = shapes["blocks"]["b01"], shapes["blocks"]["b04"], shapes["blocks"]["b00"]
    assert sorted(m) == sorted(KINDS["M"]) and sorted(a) == sorted(KINDS["*"]) and sorted(e) == sorted(KINDS["E"] + ("router_bias",))
    # 4 heads of 8 and their 2 groups of state 8: z | x, B, C | dt
    assert m["ssm_in"] == (32, 32 + (32 + 2 * 2 * 8) + 4) and m["conv_w"] == (4, 64) and m["ssm_out"] == (32, 32) and m["A_log"] == (4,)
    assert a["wq"] == (32, 2 * 8) and a["wk"] == a["wv"] == (32, 8) and a["wo"] == (16, 32)
    assert e["router"] == (32, 16) and e["w_up"] == (4, 16, 24) and e["w_down"] == (4, 24, 16) and e["ws_up"] == (32, 40)
    assert e["w_lat_down"] == (32, 16) and e["w_lat_up"] == (16, 32)


def _share_of_mamba(blk, lo: int, n: int, *, heads=8, width=8, groups=4, state=8):
    """Heads ``[lo, lo + n)`` and their groups of an UNCUT Mamba-2 layer's parameters."""
    inner, per = heads * width, heads // groups
    g_lo, g_n = lo // per, n // per
    z = np.arange(lo * width, (lo + n) * width)
    b = inner + np.arange(g_lo * state, (g_lo + g_n) * state)
    conv = np.concatenate([z, b, groups * state + b])  # x | B | C channels of the convolution
    cols = np.concatenate([z, inner + conv, 2 * inner + 2 * groups * state + np.arange(lo, lo + n)])
    return {
        **blk, "ssm_in": blk["ssm_in"][:, cols], "conv_w": blk["conv_w"][:, conv], "conv_b": blk["conv_b"][conv],
        "dt_bias": blk["dt_bias"][lo:lo + n], "A_log": blk["A_log"][lo:lo + n], "D": blk["D"][lo:lo + n],
        "ssm_norm": blk["ssm_norm"][z], "ssm_out": blk["ssm_out"][z],
    }


@pytest.mark.parametrize("kind", ["M", "*", "E"])
def test_the_shares_add_up_to_the_uncut_layer(kind):
    """Guide section 4: the parts that 4 head shares (``M``, ``*``) or 4
    expert shares (``E``) give add up to the uncut layer's part; what every
    chip computes alike (the shared expert, the latent projections of the
    token) is counted once."""
    uncut = _spec(mamba_heads_held=0, heads_held=0, kv_heads_held=0, experts_held=0, first_expert_held=0)
    params = _weights(uncut)
    u = jax.random.normal(jax.random.key(3), (2, KEYS["seq_len"], 32), jnp.float32)
    cast = lambda w: w  # noqa: E731
    with jax.default_matmul_precision("highest"):
        if kind == "M":
            blk = params["blocks"]["b01"]
            assert blk["ssm_in"].shape == (32, 64 + (64 + 64) + 8)
            mixer = lambda heads: mamba.MambaMixer(  # noqa: E731
                heads, head_dim=8, groups=heads // 2, state=8, conv_kernel=4, chunk=16, eps=1e-5, dt_range=(1e-3, 0.1, 1e-4))
            part = jax.jit(lambda blk: mixer(blk["A_log"].shape[0]).apply(u, blk, None, None, cast))  # a program a shape: the whole layer's, a share's
            (whole, counts), shares = part(blk), [part(_share_of_mamba(blk, lo, 2)) for lo in (0, 2, 4, 6)]
            assert float(counts["ssm_positions"]) == sum(float(held["ssm_positions"]) for _, held in shares) == 2 * KEYS["seq_len"] * 8
            parts = [got for got, _ in shares]
        elif kind == "*":
            blk = params["blocks"]["b04"]
            assert blk["wq"].shape == (32, 64) and blk["wk"].shape == (32, 16)
            part = lambda blk: attentions.GroupedQueryAttention(  # noqa: E731
                blk["wq"].shape[1] // 8, blk["wk"].shape[1] // 8, head_dim=8).apply(u, blk, None, None, cast)[0]
            parts = []
            for lo in (0, 2, 4, 6):  # query heads lo, lo + 1 on key/value head lo // 4
                q, kv = slice(lo * 8, (lo + 2) * 8), slice(lo // 4 * 8, (lo // 4 + 1) * 8)
                parts.append(part({**blk, "wq": blk["wq"][:, q], "wk": blk["wk"][:, kv], "wv": blk["wv"][:, kv], "wo": blk["wo"][q]}))
            whole = part(blk)
        else:
            blk = params["blocks"]["b00"]
            assert blk["w_up"].shape == (16, 16, 24)
            experts = lambda held, lo: moe_lm.LatentMoE(  # noqa: E731
                moe_lm.Router(16, 5, held, lo, (("scoring_func", "sigmoid"), ("norm_topk_prob", True), ("routed_scaling_factor", 5.0))),
                latent=16, width=24, shared_width=40)
            whole, stats = experts(16, 0).apply(u, blk, None, None, cast)
            alike = moe_lm._relu2_mlp(u, blk["ws_up"], blk["ws_down"], "shared_up")
            parts = []
            for lo in (0, 4, 8, 12):
                share = {**blk, "w_up": blk["w_up"][lo:lo + 4], "w_down": blk["w_down"][lo:lo + 4]}
                got, held = experts(4, lo).apply(u, share, None, None, cast)
                assert float(held["moe_slots_computed"]) == float(held["moe_slots_held"]) < float(stats["moe_slots"])
                parts.append(got - alike)
            parts.append(alike)  # once
    assert float(jnp.max(jnp.abs(parts[0]))) > 0 and float(jnp.max(jnp.abs(parts[0] - parts[1]))) > 0
    assert float(jnp.max(jnp.abs(sum(parts) - whole))) <= 2e-5 * float(jnp.max(jnp.abs(whole)))


def test_the_step_counters_are_what_the_shapes_give():
    spec = _spec()
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(mamba.SSM_COUNTERS)
    batch = _batch()
    metrics = jax.jit(lambda: spec.metrics(spec.apply(spec.init(jax.random.key(0)), batch), batch))()
    assert float(metrics["ssm_positions"]) == 2 * 48 * (4 + 4)
    assert float(metrics["ssm_positions_kernel"]) == 0  # the CPU's XLA path (and chunks of 16 are outside the kernels' contract)
    assert float(metrics["moe_slots"]) == 2 * 2 * 48 * 5
    assert float(metrics["moe_slots_computed"]) == float(metrics["moe_slots_held"]) <= float(metrics["moe_slots"])


def test_the_kernel_counter_is_all_of_the_positions_where_the_scans_kernels_run(monkeypatch):
    """Two M layers inside the kernels' contract (ops/ssm.outside_contract):
    off the TPU the einsums run and ``ssm_positions_kernel`` is 0; where the
    backend says TPU the kernels run (here Pallas's TPU interpreter stands in
    for the chip), it is all of ``ssm_positions``, counted where each scan is
    called, and the model computes the same logits."""
    from jax.experimental.pallas import tpu as pltpu

    spec = _spec(num_hidden_layers=2, hybrid_override_pattern="MM", mamba_num_heads=4, mamba_heads_held=4, mamba_head_dim=64,
                 n_groups=2, ssm_state_size=128, chunk_size=128, seq_len=256)
    params, batch = _weights(spec), _batch(b=1, l=256)
    # traced anew at each call: the backend is asked at trace time
    read = lambda: jax.jit(lambda w: (lambda out: (out["logits"], spec.metrics(out, batch)))(spec.apply(w, batch)))(params)  # noqa: E731
    logits, metrics = read()
    assert float(metrics["ssm_positions"]) == 256 * (4 + 4) and float(metrics["ssm_positions_kernel"]) == 0
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pltpu.force_tpu_interpret_mode():
        by_kernels, metrics = read()
    assert float(metrics["ssm_positions_kernel"]) == float(metrics["ssm_positions"]) == 256 * (4 + 4)
    np.testing.assert_allclose(by_kernels, logits, rtol=2e-4, atol=2e-4)


def test_adamw_decays_the_matrices_alone_and_the_job_trains():
    spec = _spec("float32", lr_warmup_steps=0, learning_rate=1e-2)
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    batch = {k: np.asarray(v) for k, v in _batch(b=2).items()}
    losses = []
    for _ in range(6):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] - 0.05, losses
    assert float(metrics["ssm_positions"]) == 2 * 48 * 8
    mask = moe_lm._is_decayed(state.params, moe_lm._NOT_MATRICES)
    want = reference().decayed(state.params)
    assert mask == want
    block = mask["blocks"]["b01"]
    assert block["ssm_in"] and block["ssm_out"] and block["conv_w"] and mask["head"]
    assert not any(block[name] for name in ("norm", "ssm_norm", "A_log", "D", "dt_bias", "conv_b"))
    assert not mask["blocks"]["b00"]["router_bias"] and not mask["norm_f"]


def test_bfloat16_compute_stays_near_the_float32_reference():
    spec, batch = _spec("bfloat16"), _batch()
    params = _weights(spec)
    logits = jax.jit(lambda w: spec.apply(w, batch)["logits"])(params)
    want, _ = jax.jit(reference().build(dict(KEYS)))(params, batch["tokens"])  # (not the memo's: this case may run on another worker)
    assert logits.dtype == jnp.float32
    assert float(jnp.sqrt(jnp.mean((logits - want) ** 2) / jnp.mean(want ** 2))) < 0.05


@pytest.mark.parametrize("keys,match", [
    (dict(hybrid_override_pattern="EM-M*"), "letter of"),
    (dict(hybrid_override_pattern="EMEM"), "must give 5 layers"),
    (dict(seq_len=40), "not whole chunks of 16"),
    (dict(mamba_heads_held=3), "whole groups of 2 heads"),
    (dict(heads_held=3), "query heads sit evenly"),
    (dict(heads_held=8, kv_heads_held=1), "query heads sit evenly"),
    (dict(mlp_hidden_act="silu"), "relu2"),
    (dict(topk_method="greedy"), "correction bias"),
    (dict(moe_latent_size=0), "moe_latent_size"),
    (dict(layer_types=("moe",) * 5), "layer_types: set, but no part of the 'nemotron_h' family reads it"),
    (dict(hybrid_override_pattern=None, num_hidden_layers=1), "mamba_heads_held, .*mlp_hidden_act, .*no part of the 'olmoe' family reads them"),
], ids=lambda v: v if isinstance(v, str) else "-".join(f"{k}={x}" for k, x in v.items())[:40])
def test_keys_that_do_not_go_together_raise(keys, match):
    with pytest.raises(ValueError, match=match):
        _spec(**keys)


def test_a_sequence_that_is_not_whole_chunks_is_refused_by_the_op_too():
    spec = _spec()
    with pytest.raises(ValueError, match="whole chunks of 16"):
        spec.apply(spec.init(jax.random.key(0)), _batch(l=40))


def test_a_sharded_sequence_is_refused():
    """The state at a shard's start lives on the shard before it: no silent
    wrong answer."""
    spec = _spec()
    mesh = create_mesh(num_devices=2)
    with pytest.raises(ValueError, match="sharded sequence"):
        trainer = Trainer(spec, JobConfig(), mesh)
        state = trainer.init_state(jax.random.key(0))
        batch = {k: np.asarray(v) for k, v in _batch(l=96).items()}
        trainer.train_step(state, trainer.shard_batch(batch))


@pytest.mark.parametrize("under_checkpoint", [False, True], ids=["plain", "rematerialised"])
def test_two_matrix_experts_through_the_overflow_tier_stay_dropless(under_checkpoint):
    """All 22 slots of many tokens on held experts: the run is longer than
    the always-run buffers, the second tier takes the rest, every held slot
    is computed and the result is the dense masked sum's."""
    t, d, f, n_experts, held, lo, k = 64, 16, 24, 64, 8, 8, 6
    ks = jax.random.split(jax.random.key(0), 5)
    u = jax.random.normal(ks[0], (t, d))
    w_up, w_down = 0.3 * jax.random.normal(ks[1], (held, d, f)), 0.3 * jax.random.normal(ks[2], (held, f, d))
    weights = jax.random.uniform(ks[3], (t, k))
    # the first 40 tokens send all k slots to held experts, the rest none
    inside = lo + jnp.argsort(jax.random.uniform(ks[4], (t, held)), axis=-1)[:, :k]
    choices = jnp.where(jnp.arange(t)[:, None] < 40, inside, (jnp.arange(k)[None, :] + 20)).astype(jnp.int32)

    def dense(u, w_up, w_down, weights):
        out = jnp.zeros_like(u)
        for e in range(held):
            m = jnp.sum(jnp.where(choices == lo + e, weights, 0.0), -1)
            out += m[:, None] * (jnp.square(jax.nn.relu(u @ w_up[e])) @ w_down[e])
        return out

    def system(u, w_up, w_down, weights):
        return moe.expert_ffn(u, choices, weights, None, w_up, w_down, n_experts=n_experts, lo=lo)

    bound = moe.held_rows_bound(t * k, held, n_experts)
    assert bound < 40 * k
    run = jax.checkpoint(lambda *a: system(*a)[0]) if under_checkpoint else (lambda *a: system(*a)[0])
    with jax.default_matmul_precision("highest"):
        y, slots, given = system(u, w_up, w_down, weights)
        assert int(given.first) == bound and int(given.first + given.second) == 40 * k == int(jnp.sum(slots[lo:lo + held]))
        np.testing.assert_allclose(y, dense(u, w_up, w_down, weights), rtol=1e-5, atol=1e-5)
        g = jax.random.normal(jax.random.key(9), y.shape)
        got = jax.jit(jax.grad(lambda *a: jnp.sum(run(*a) * g), argnums=(0, 1, 2, 3)))(u, w_up, w_down, weights)
        want = jax.jit(jax.grad(lambda *a: jnp.sum(dense(*a) * g), argnums=(0, 1, 2, 3)))(u, w_up, w_down, weights)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


#: sha256 of the sorted ``path:shape:dtype`` rows of ``spec.init``'s tree, as the PARENT commit (42c20f3) builds them
TREES = {
    "defaults": ("51554f0bc52c3aaadfd5b6948894f2f088c961878fa03f0c2a5bd3c0020f2869", 27),
    "olmoe_1b_7b_l1": ("1b39c68707886e352f6c19d44b16efd65fb0a670531170838106c8cf5fb87a4d", 15),
    "kanana2_30b_a3b_ep8_l5": ("ea32f0a02a5f4dc8a5e3a5ddf3bff70ce180d94e0d8cf2fb1b562742e67d59e2", 73),
    "evabyte_6b5_tp2_l4": ("5d25ffdcc25c2520ddb205090f34601df7b7782ca879ab579d4efdfef62bb519", 47),
}


@pytest.mark.parametrize("config", sorted(TREES))
def test_the_older_configurations_build_the_parameter_trees_they_did(config):
    keys = {}
    if config != "defaults":
        with open(os.path.join(lm_family.BENCH_DIR, "configs", config + ".json")) as f:
            keys = json.load(f)["model_params"]
    spec = load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", **keys)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    rows = sorted(
        "/".join(str(k.key) for k in path) + ":" + str(tuple(leaf.shape)) + ":" + str(leaf.dtype)
        for path, leaf in jax.tree_util.tree_leaves_with_path(shapes)
    )
    assert (hashlib.sha256("\n".join(rows).encode()).hexdigest(), len(rows)) == TREES[config]
