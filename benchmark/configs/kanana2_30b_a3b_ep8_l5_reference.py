"""Plain reference of the decoder the ``kanana2_30b_a3b_ep8_l5`` configuration
runs: float32 ``jax.numpy``, matmul precision ``highest``, attention as an
explicit masked softmax over 192-wide queries and keys built the textbook
way (``q = q_nope | q_rot``, ``k = k_nope | k_rot`` with the ONE rotary key
copied to every head; in blocks of queries: 32 heads x 8192 x 8192 float32
scores are 8 GiB a sequence), and the expert layer DENSE: every held expert
on every token, times a ``[T, held]`` matrix that holds the router's weight
where the token chose that expert and 0 elsewhere.  No sort, no groups, no
kernels, and no code of ``elasticdl_tpu/ops/`` or
``elasticdl_tpu/models/moe_lm.py`` (the reference takes ONE thing of the
model: ``model_spec.init(key(0))``, whose weights are data here; the
``checks`` at the end of this file run the model itself, as the thing
measured).

``transformers``' ``DeepseekV3`` modules with ``q_lora_rank`` null, as
``kanana-2-30b-a3b-instruct-2601`` configures them; with ``rmsnorm(x, g) =
x * rsqrt(mean(x^2) + eps) * g``, eps 1e-6, H heads:

    a      = rmsnorm(x, attn_norm)
    q      = a Wq                     -> [T, H, 192] = (q_nope [128], q_rot [64])
    (c, k_rot) = a Wkv_a              -> c [T, 512], k_rot [T, 64]: ONE rotary key for all heads
    c      = rmsnorm(c, kv_norm)
    (k_nope, v) = c Wkv_b             -> [T, H, 128] each
    q_rot, k_rot = rope(q_rot), rope(k_rot)     theta 1e6, the 64 rotary columns only, INTERLEAVED pairing
                                                (2i, 2i + 1) (``apply_rotary_pos_emb_interleave``: the
                                                even columns moved first, then rotate-half)
    s_h    = (q_nope_h . k_nope_h + q_rot_h . k_rot) * 192^-0.5 ; causal softmax ; o_h = p_h v_h
    x     += o Wo
    u      = rmsnorm(x, ffn_norm)
    layer 0 (first_k_dense_replace 1):  x += (silu(u Wgate) * (u Wup)) Wdown            width 6144
    layers 1.. :  r = u Wg (float32, [T, 128]) ; s = sigmoid(r)
                  e_1..e_6 = top-6 of (s + b)          b = e_score_correction_bias [128]
                  w_i = s[e_i] / (sum_j s[e_j] + 1e-20) * 2.448
                  x += sum_{i: e_i held} w_i * expert_{e_i}(u)  +  shared(u)
    logits = rmsnorm(x, norm_f) Whead                  (untied, float32)
    loss   = CE(logits, next token)                    (no router loss)
    AdamW (0.9, 0.95, 1e-8, decay 0.1 on all but b), the rate raised linearly from 0 over ``lr_warmup_steps``
    after each step, per expert layer:  b_e += 0.001 * sign(mean_e'(c_e') - c_e),
                  c_e = slots the step's tokens sent to expert e, over ALL 128

ONE chip's share: experts ``first_expert_held .. + experts_held`` of the
router's 128 are here, so a slot on another expert adds nothing (what the
absent chips would add is left out, in the program and here alike), and the
vocabulary is the slice the configuration states.  Departures from the
published model are the configuration's ``assumed`` list.

It trains the first task (``minibatches_per_task`` AdamW steps, in order)
from the same initial weights as the system and reports the mean of the
steps' losses, which is what the worker reports for a task.  To hold
float32 state (9.2 GB at the published widths) beside a step's activations
it takes the gradient a micro-batch of ``MICRO`` sequences at a time: the
loss is a mean, so the micro-batches' gradients average exactly; the slot
counts ``c_e`` are summed over them.  Blocks, query blocks and expert
chunks are rematerialised and walked by ``lax.map`` (one compiled body
each).

After the loss, in the same process, a reading for each of the
configuration's ``checks``: the float32 sigmoid router, which the first
task's mean loss cannot see.  The model's own entry — ``spec.apply`` of
``moe_lm.model_spec`` at the job's dtypes — runs on the run's first
minibatch from the initial weights, and what it hands
``elasticdl_tpu.ops.moe.route`` and gets back is read
(:func:`routers_of_the_model`) against float64 on the host
(:func:`router_readings`).  The child reports the bare readings as
``"checks": {name: value}``; ``run.py`` holds each against the limit in the
configuration's file.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
from reference_common import device_report, parse_args, read_records  # noqa: E402

MICRO = 1
QUERY_BLOCK = 512
EXPERT_CHUNK = 4


def build(p: dict):
    """``forward(params, tokens) -> (float32 logits, [expert layers, E] slot
    counts; None without an expert layer)`` for the model parameters ``p`` (the published keys), in the
    precision of the weights it is given (float32 here; the softmax, the
    router and the cross-entropy in float32 whatever that is)."""
    import jax
    import jax.numpy as jnp

    heads, top_k = int(p["num_attention_heads"]), int(p["num_experts_per_tok"])
    nope, rot, v_dim = int(p["qk_nope_head_dim"]), int(p["qk_rope_head_dim"]), int(p["v_head_dim"])
    rank = int(p["kv_lora_rank"])
    theta, eps = float(p["rope_theta"]), float(p["rms_norm_eps"])
    scaling, lo = float(p["routed_scaling_factor"]), int(p.get("first_expert_held", 0))
    assert p["scoring_func"] == "sigmoid" and p["norm_topk_prob"] and p["rope_interleave"]

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(x.dtype)

    def rope(x):  # [B, L, H, rot], pairs (2i, 2i + 1)
        b, l, h, r = x.shape
        x = x.reshape(b, l, h, r // 2, 2).swapaxes(-1, -2).reshape(b, l, h, r)  # evens first, then odds
        freq = theta ** (-jnp.arange(0, r, 2) / r)
        ang = jnp.arange(l)[:, None] * freq[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :].astype(x.dtype)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :].astype(x.dtype)
        rotated = jnp.concatenate([-x[..., r // 2:], x[..., : r // 2]], -1)
        return x * cos + rotated * sin

    @jax.checkpoint
    def attend(q_blk, k, v, first):  # q_blk [B, bq, H, 192]; k [B, L, H, 192]; v [B, L, H, 128]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / np.sqrt(nope + rot)
        q_pos = first + jnp.arange(q_blk.shape[1])
        mask = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q_blk.dtype), v)

    @jax.checkpoint
    def experts(u, wg, wu, wd, m):  # u [T, D]; wg, wu [c, D, F]; wd [c, F, D]; m [T, c]
        h = jax.nn.silu(jnp.einsum("td,cdf->tcf", u, wg)) * jnp.einsum("td,cdf->tcf", u, wu)
        return jnp.einsum("tc,tcd->td", m, jnp.einsum("tcf,cfd->tcd", h, wd))

    def mlp(u, wg, wu, wd):
        return (jax.nn.silu(u @ wg) * (u @ wu)) @ wd

    def block(x, blk):
        b, l, d = x.shape
        a = rmsnorm(x, blk["attn_norm"])
        q = (a @ blk["wq"]).reshape(b, l, heads, nope + rot)
        kv_a = a @ blk["wkv_a"]
        c, k_rot = rmsnorm(kv_a[..., :rank], blk["kv_norm"]), kv_a[..., rank:]
        kv = (c @ blk["wkv_b"]).reshape(b, l, heads, nope + v_dim)
        k_rot = jnp.broadcast_to(rope(k_rot[:, :, None, :]), (b, l, heads, rot))
        q = jnp.concatenate([q[..., :nope], rope(q[..., nope:])], -1)
        k = jnp.concatenate([kv[..., :nope], k_rot], -1)
        v = kv[..., nope:]
        bq = min(QUERY_BLOCK, l)
        blocks = jnp.moveaxis(q.reshape(b, l // bq, bq, heads, nope + rot), 1, 0)
        att = jax.lax.map(lambda blk_: attend(blk_[0], k, v, blk_[1]), (blocks, jnp.arange(0, l, bq)))
        x = x + jnp.moveaxis(att, 0, 1).reshape(b, l, heads * v_dim) @ blk["wo"]
        u = rmsnorm(x, blk["ffn_norm"]).reshape(b * l, d)
        if "router" not in blk:
            return x + mlp(u, blk["w_gate"], blk["w_up"], blk["w_down"]).reshape(b, l, d), None
        r = (u @ blk["router"]).astype(jnp.float32)
        s = jax.nn.sigmoid(r)
        n_experts, held = s.shape[-1], blk["w_gate"].shape[0]
        chosen = jnp.argsort(-(s + blk["router_bias"]), axis=-1, stable=True)[:, :top_k]  # [T, k], best first
        onehot = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)  # [T, k, E]
        picked = jnp.sum(onehot * s[:, None, :], 1)  # [T, E]: s at the chosen experts, 0 elsewhere
        m = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scaling
        m = m[:, lo:lo + held]  # the held experts' columns: a slot on an absent expert adds nothing
        c_ = min(EXPERT_CHUNK, held)
        chunked = lambda name: blk[name].reshape((held // c_, c_) + blk[name].shape[1:])  # noqa: E731
        m_chunks = jnp.moveaxis(m.reshape(-1, held // c_, c_), 1, 0).astype(u.dtype)
        y = jnp.sum(jax.lax.map(
            lambda part: experts(u, *part), (chunked("w_gate"), chunked("w_up"), chunked("w_down"), m_chunks)), 0)
        y = y + mlp(u, blk["ws_gate"], blk["ws_up"], blk["ws_down"])
        return x + y.reshape(b, l, d), jnp.sum(onehot, (0, 1))

    def forward(params, tokens):
        x = params["tok_emb"][tokens]
        slots = []
        for name in sorted(params["blocks"]):
            x, sent = jax.checkpoint(block)(x, params["blocks"][name])
            if sent is not None:
                slots.append(sent)
        logits = (rmsnorm(x, params["norm_f"]) @ params["head"]).astype(jnp.float32)
        return logits, jnp.stack(slots) if slots else None

    return forward


def update_bias(params, slots, speed: float):
    """``b_e += speed * sign(mean(c) - c_e)``, each expert layer from its own
    counts ``slots[layer]`` [E] (float32, as every parameter)."""
    import jax.numpy as jnp

    routed = [name for name in sorted(params["blocks"]) if "router" in params["blocks"][name]]
    blocks = dict(params["blocks"])
    for name, c in zip(routed, slots):
        b = blocks[name]["router_bias"]
        blocks[name] = {**blocks[name], "router_bias": b + jnp.float32(speed) * jnp.sign(jnp.mean(c) - c)}
    return {**params, "blocks": blocks}


def decayed(params):
    """AdamW's weight-decay mask: every leaf but the correction biases."""
    import jax

    return jax.tree_util.tree_map_with_path(lambda path, _: path[-1].key != "router_bias", params)


def router_readings(u, wg, bias, logits, choices, top_k: int) -> dict:
    """A router's float32 ``logits`` [T, E] and ``choices`` [T, k] on the
    rows ``u`` [T, D], the weight ``wg`` [D, E] and the bias [E], against
    float64 on the host (the product, its sigmoid, the bias added, a stable
    sort): the largest error of a logit relative to the largest logit, and
    the number of (token, rank) choices that differ from float64's."""
    want_r = np.asarray(u, np.float64) @ np.asarray(wg, np.float64)
    want_s = 1.0 / (1.0 + np.exp(-want_r)) + np.asarray(bias, np.float64)
    want_c = np.argsort(-want_s, axis=-1, kind="stable")[:, :top_k]
    return {
        "router_logits": float(np.abs(np.asarray(logits, np.float64) - want_r).max() / np.abs(want_r).max()),
        "router_choices_differing": int(np.sum(np.asarray(choices) != want_c)),
    }


def routers_of_the_model(spec, before_the_call=None):
    """A compiled ``(params, tokens, labels) -> [{"u", "logits", "choices"}]``,
    one entry an expert layer in layer order: the rows the MODEL's own entry
    ``spec.apply`` (at the job's dtypes) hands
    ``elasticdl_tpu.ops.moe.route`` on a minibatch, and the float32 logits
    and the choices it gets back.  The op is tapped where the model looks it
    up (the module's attribute) while ``apply`` is traced, and at no other
    time; a model that routes by another function hands it nothing, and the
    list is empty.  ``before_the_call(u, wg) -> (u, wg)`` stands for a step
    that changes the operands on their way to the op (the control)."""
    import jax

    from elasticdl_tpu.ops import moe

    def run(params, tokens, labels):
        real, seen = moe.route, []

        def tapped(u, wg, k, **keys):
            if before_the_call is not None:
                u, wg = before_the_call(u, wg)
            routing = real(u, wg, k, **keys)
            seen.append({"u": u, "logits": routing.logits, "choices": routing.choices})
            return routing

        moe.route = tapped
        try:
            # at jax's own default matmul precision, as the job runs: this
            # process's ``highest`` would lend the model a precision it does not ask for
            with jax.default_matmul_precision(None):
                # train=False: the same forward without the per-block
                # jax.checkpoint (memory, not values), out of which the tap
                # could not hand what it saw
                spec.apply(params, {"tokens": tokens, "labels": labels}, train=False)
        finally:
            moe.route = real
        return seen

    return jax.jit(run)


def router_checks(routed: list, params, top_k: int) -> dict:
    """The configuration's two ``checks``, bare readings: every expert
    layer's router as the model ran it against float64 of the rows it was
    handed, the layer's float32 router PARAMETER and its bias (not the
    operand the op was handed: a weight rounded on the way shows); the worst
    layer.  ``{}`` where the model did not hand the op every expert layer's
    rows: ``run.py`` then finds no reading."""
    names = [name for name in sorted(params["blocks"]) if "router" in params["blocks"][name]]
    if not names or len(routed) != len(names):
        return {}
    readings = [
        router_readings(
            r["u"], params["blocks"][name]["router"], params["blocks"][name]["router_bias"],
            r["logits"], r["choices"], top_k,
        )
        for name, r in zip(names, routed)
    ]
    return {key: max(reading[key] for reading in readings) for key in readings[0]}


def main() -> None:
    t_start = time.time()
    config, traffic, data, out = parse_args()
    import jax
    import jax.numpy as jnp
    import optax

    jax.config.update("jax_default_matmul_precision", "highest")
    p = config["model_params"]
    seq = int(p["seq_len"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])
    micro = min(MICRO, mb)

    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0)))
    forward = build(p)

    def micro_loss(params, tokens, labels):
        logits, slots = forward(params, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean(), slots

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_grad(total, params, tokens, labels):
        """``total`` + this micro-batch's gradient, in ``total``'s memory."""
        (loss, slots), grads = jax.value_and_grad(micro_loss, has_aux=True)(params, tokens, labels)
        return jax.tree.map(jnp.add, total, grads), loss, slots

    warmup = int(p.get("lr_warmup_steps", 0))
    rate = float(p["learning_rate"])
    optimizer = optax.adamw(
        optax.linear_schedule(0.0, rate, warmup) if warmup else rate,
        b1=0.9, b2=0.95, eps=1e-8, weight_decay=float(p["weight_decay"]), mask=decayed,
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(params, opt_state, total, n, slots):
        grads = jax.tree.map(lambda a: a / n, total)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return update_bias(optax.apply_updates(params, updates), slots, float(p["bias_update_speed"])), opt_state

    records = read_records(data, steps * mb)
    toks = np.stack([np.frombuffer(r, "<i4") for r in records])
    assert toks.shape[1] == seq + 1
    opt_state = optimizer.init(params)
    losses, held_share = [], []
    for i in range(steps):
        batch = toks[i * mb : (i + 1) * mb]
        parts = [batch[j : j + micro] for j in range(0, mb, micro)]
        n = len(parts)
        loss_sum, slots_sum = 0.0, 0.0
        total = jax.tree.map(jnp.zeros_like, params)
        for part in parts:
            total, loss, slots = add_grad(total, params, part[:, :-1], part[:, 1:])
            loss_sum += float(loss)
            slots_sum = slots_sum + slots
        params, opt_state = apply(params, opt_state, total, float(n), slots_sum)
        losses.append(loss_sum / n)
        held = int(p.get("experts_held") or p["num_experts"])
        lo = int(p.get("first_expert_held", 0))
        held_share.append(float(jnp.sum(slots_sum[:, lo:lo + held]) / jnp.sum(slots_sum)))
        print(f"step {i}: loss {losses[-1]:.6f}, held share {held_share[-1]:.4f} at {time.time() - t_start:.1f} s", flush=True)
    result = {"loss": float(np.mean(losses)), "step_losses": losses, "held_share": held_share, "device": device_report()}
    if config.get("checks"):
        del params, opt_state, total
        t_checks = time.time()
        first = toks[:mb]  # the run's first minibatch, at the step's own size
        params = spec.init(jax.random.key(0))
        routed = routers_of_the_model(spec)(params, first[:, :-1], first[:, 1:])
        result["checks"] = router_checks(routed, params, int(p["num_experts_per_tok"]))
        result["checks_seconds"] = time.time() - t_checks
        print(f"checks: {result['checks']} in {result['checks_seconds']:.1f} s", flush=True)
    with open(out, "w") as f_out:
        json.dump(result, f_out)


if __name__ == "__main__":
    main()
