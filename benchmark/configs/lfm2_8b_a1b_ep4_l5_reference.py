"""Plain reference of the decoder the ``lfm2_8b_a1b_ep4_l5`` configuration
runs (LFM2-8B-A1B, ``lfm2_moe``): float32 ``jax.numpy``, matmul precision
``highest``, the short convolution by EXPLICIT SHIFTS of the padded product,
softmax attention under an explicit [L, L] causal mask (a block of queries at
a time against all keys, the key/value heads read by index: no repeat), the
experts by a LOOP over the held experts of a 32-wide router, each on every
token under the router's weights — no kernel, no sort, no rematerialisation
but for memory, and no code of ``elasticdl_tpu/ops/`` or
``elasticdl_tpu/models/`` (the reference takes ONE thing of the model:
``model_spec.init(key(0))``, whose weights are data here; the checks at the
end of this file run the model itself, as the thing measured).

A sequence of L tokens from the vocabulary slice; ``rmsnorm(x, g) = x *
rsqrt(mean(x^2) + eps) * g``, eps = ``norm_eps``; d = ``hidden_size``, H query
heads over G key/value heads of hd = d / H, K = ``conv_L_cache`` taps; HELD
experts only (what the others would add is left out, here and in the program
alike):

    h  = E[x]
    layer i, kind = layer_types[i]:
      u  = rmsnorm(h, operator_norm)
      conv:            (B, C, z) = split3(u W_in) ; p = B * z ; c_t = sum_{j<K} taps[j] * p_{t-(K-1)+j}   (zeros before the start)
                       h += (C * c) W_out                                  (NO activation, no bias)
      full_attention:  q = u Wq [H, hd] ; k, v = u Wk, u Wv [G, hd]
                       q = rmsnorm(q, q_norm[hd]) ; k = rmsnorm(k, k_norm[hd])    (a norm a head, ONE gain for all heads)
                       q, k = rope(q), rope(k)                             (rotate-half over the whole head, theta; AFTER the norm)
                       head h reads key/value head h // (H / G) ; softmax over j <= p of q k / sqrt(hd) ; h += o Wo
      u  = rmsnorm(h, ffn_norm)
      layers < num_dense_layers: h += (silu(u W1) * (u W3)) W2
      the rest: s = sigmoid(u Wg) (float32) ; e_1..e_k = top-k of s + b   (b chooses, never weighs)
                w_i = routed_scaling_factor x s[e_i] / (sum_j s[e_j] + 1e-6)
                h += sum_{i: e_i held} w_i expert_{e_i}(u)                 (no shared expert)
    z  = rmsnorm(h, g_f) E^T ; loss = mean CE(z_i, x_{i+1})               (the head is TIED to the embedding)
    after a step: b_e += bias_update_speed x sign(mean(c) - c_e), c_e the slots the step's router sent expert e
    AdamW (0.9, 0.95, 1e-8; decay on the matrices alone), the rate raised linearly from 0

The renormalising divisor is the published 1e-6 HERE and 1e-20 in the program
(``ops/moe.route``, shared with five cells): under 1e-6 of a weight (the
configuration's ``assumed``).  Other departures from the published model are
that list's.

It runs the first task (``minibatches_per_task`` steps, in order) from the
same initial weights as the system and reports the mean of the steps'
losses, which is what the worker reports for a task.  The warm-up's rate is
0 at the first update, so a step moves NO weight the optimizer owns (this
file refuses a configuration without a warm-up); what moves between a
task's steps is the routers' correction bias, by the rule above.  A
minibatch is walked a SEQUENCE at a time (the loss is a mean, so the rows'
gradients average exactly and their slot counts add): float32 activations
of one 8k sequence at a time beside 2 GB of float32 weights.

Then, in the same process, a bare reading for each of the configuration's
``checks`` (``benchmark/run.py`` judges them against the limits in the
configuration's file), on the run's first minibatch from
:func:`check_weights`.  The SYSTEM's side is the program itself, not a
copy: the model's own ``spec.apply`` with its attention call, the gated
convolution op, the router and the norm tapped (:func:`taps_of_the_model`),
and ``parallel/trainer.Trainer``'s own train step
(:func:`trained_by_the_program`).  Two kinds of reading:

- against this file's float32 model on float32 weights (the MECHANISM, every
  layer, forward and backward; reads the bfloat16 compute's noise):
  ``logits``, ``grad_<group>``;
- against float32 / float64 arithmetic on the operands THE SYSTEM ITSELF
  handed over: ``gconv_output`` (what ``ops/short_conv.gated_conv`` returned,
  on the chip the kernel pair's forward, against the shifted products on the
  B, C, z and taps it was handed), ``attention_output`` (the model's call of
  the attention against the masked softmax on its q, k, v),
  ``router_logits`` / ``router_choices_differing`` (float64),
  ``head_logits``, ``adamw_update``.

``LFM2_CONTROL=<one of CONTROLS>`` in the child's environment swaps a fault
into the system's side (:func:`faults`), so that ``benchmark/run.py`` ends
with ``correct`` false: how each limit was shown to catch what the
configuration's file says it catches.  The driver never sets it.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
from reference_common import device_report, parse_args, read_records  # noqa: E402

#: Faults the configuration's checks must catch (:func:`faults`).
CONTROLS = ("bfloat16_conv", "bfloat16_router", "bfloat16_logits", "all_bfloat16", "no_rotary", "no_weight_decay", "state_unchanged")
KINDS = ("conv", "full_attention")
GROUPS = {
    "conv": ("gconv_in", "gconv_taps", "gconv_out"),
    "attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
    "experts": ("w_gate", "w_up", "w_down"),  # of a layer with a router; the leading dense layer's are "dense" (group_of)
    "router": ("router", "router_bias"),  # the bias has no gradient on either side
    "embedding": ("tok_emb",),  # the tied head's gradient is in it
    "norms": ("operator_norm", "ffn_norm", "norm_f"),
}
NOT_DECAYED = GROUPS["norms"] + ("q_norm", "k_norm", "router_bias", "gconv_taps")
B1, B2, EPS = 0.9, 0.95, 1e-8
QUERY_BLOCK = 1024
#: the published renormalising divisor (``norm_topk_prob``)
RENORM_EPS = 1e-6


def masked_attention(q, k, v):
    """Causal softmax attention under an explicit mask: ``q`` [B, L, H, hd];
    ``k``, ``v`` [B, L, G, hd] with G a divisor of H (query head h reads
    key/value head ``h // (H / G)``); position p sees the keys ``j <= p``.  A
    block of queries at a time against all keys: the scores of one block are
    alive at a time."""
    import jax
    import jax.numpy as jnp

    bsz, l, heads, hd = q.shape
    group = heads // k.shape[2]
    at = jnp.arange(l)

    @jax.checkpoint
    def queries(part):
        q_blk, first = part
        by_group = q_blk.reshape(bsz, q_blk.shape[1], heads // group, group, hd)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", by_group, k) / np.sqrt(hd)
        seen = at[None, :] <= (first + jnp.arange(q_blk.shape[1]))[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v).reshape(q_blk.shape)

    size = QUERY_BLOCK if l % QUERY_BLOCK == 0 else l
    blocks = jnp.moveaxis(q.reshape(bsz, l // size, size, heads, hd), 1, 0)
    return jnp.moveaxis(jax.lax.map(queries, (blocks, jnp.arange(0, l, size))), 0, 1).reshape(q.shape)


def rotate(x, theta: float):
    """Rotary positions 0..L-1 on ``x`` [B, L, heads, hd]: element i of a head
    paired with i + hd/2, the pair turned by position x theta^(-2i/hd)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    angle = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * theta ** (-jnp.arange(half, dtype=jnp.float32) / half)[None, :]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def gated_convolution(b, c, z, taps):
    """``c * conv(b * z)``: the causal depthwise convolution by explicit shifts
    of the product padded with K - 1 zeros before the sequence's start;
    operands [B, L, C], ``taps`` [K, C]."""
    import jax.numpy as jnp

    k, l = taps.shape[0], b.shape[1]
    padded = jnp.pad(b * z, ((0, 0), (k - 1, 0), (0, 0)))
    return c * sum(taps[j] * padded[:, j:j + l] for j in range(k))


def build(p: dict):
    """``forward(params, tokens) -> (logits [B, L, V] float32, slots [expert
    layers, E])`` for the model parameters ``p`` (the published keys), in
    the precision of the weights it is given."""
    import jax
    import jax.numpy as jnp

    eps, theta = float(p["norm_eps"]), float(p["rope_theta"])
    heads, kv_heads = int(p["num_attention_heads"]), int(p["num_key_value_heads"])
    hd, kinds = int(p["hidden_size"]) // heads, tuple(p["layer_types"])
    top_k, scaling, lo = int(p["num_experts_per_tok"]), float(p.get("routed_scaling_factor", 1.0)), int(p.get("first_expert_held", 0))
    assert p["norm_topk_prob"] and p.get("use_expert_bias", True) and not p.get("conv_bias", False) and p["tie_word_embeddings"]
    assert set(kinds) <= set(KINDS) and int(p["conv_L_cache"]) > 0

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    silu = lambda t: t * jax.nn.sigmoid(t)  # noqa: E731

    def convolution(u, blk):
        d = u.shape[-1]
        assert blk["gconv_taps"].shape[0] == int(p["conv_L_cache"])
        wide = u @ blk["gconv_in"]
        return gated_convolution(wide[..., :d], wide[..., d:2 * d], wide[..., 2 * d:], blk["gconv_taps"]) @ blk["gconv_out"]

    def attention(u, blk):
        bsz, l, _ = u.shape
        q = rmsnorm((u @ blk["wq"]).reshape(bsz, l, heads, hd), blk["q_norm"])
        k = rmsnorm((u @ blk["wk"]).reshape(bsz, l, kv_heads, hd), blk["k_norm"])
        v = (u @ blk["wv"]).reshape(bsz, l, kv_heads, hd)
        return masked_attention(rotate(q, theta), rotate(k, theta), v).reshape(bsz, l, -1) @ blk["wo"]

    def gated(t, w_gate, w_up, w_down):
        return (silu(t @ w_gate) * (t @ w_up)) @ w_down

    def experts(u, blk):
        bsz, l, d = u.shape
        t = u.reshape(bsz * l, d)
        s = jax.nn.sigmoid((t @ blk["router"]).astype(jnp.float32))
        n_experts, held = s.shape[-1], blk["w_up"].shape[0]
        chosen = jnp.argsort(-(s + blk["router_bias"]), axis=-1, stable=True)[:, :top_k]  # [T, k], best first
        onehot = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)  # [T, k, E]
        picked = jnp.sum(onehot * s[:, None, :], 1)  # [T, E]: s at the chosen experts, 0 elsewhere
        m = (picked / (jnp.sum(picked, -1, keepdims=True) + RENORM_EPS) * scaling).astype(u.dtype)

        @jax.checkpoint
        def one(total, expert):
            # a HELD expert on every token, weighed by its column of m: a slot on an absent expert adds nothing
            w_gate, w_up, w_down, column = expert
            return total + gated(t, w_gate, w_up, w_down) * column[:, None], None

        columns = m[:, lo:lo + held].T  # [held, T]
        y, _ = jax.lax.scan(one, jnp.zeros_like(t), (blk["w_gate"], blk["w_up"], blk["w_down"], columns))
        return y.reshape(bsz, l, d), jnp.sum(onehot, (0, 1))

    def layer(h, blk, kind):
        operator = convolution if kind == "conv" else attention
        h = h + operator(rmsnorm(h, blk["operator_norm"]), blk)
        u = rmsnorm(h, blk["ffn_norm"])
        if "router" in blk:
            y, sent = experts(u, blk)
        else:
            y, sent = gated(u, blk["w_gate"], blk["w_up"], blk["w_down"]), None
        return h + y, sent

    def logits(h, norm_f, tok_emb):
        return (rmsnorm(h, norm_f) @ tok_emb.T).astype(jnp.float32)

    def forward(params, tokens):
        h = params["tok_emb"][tokens]
        slots = []
        for name, kind in zip(sorted(params["blocks"]), kinds):
            h, sent = layer(h, params["blocks"][name], kind)
            if sent is not None:
                slots.append(sent)
        return logits(h, params["norm_f"], params["tok_emb"]), jnp.stack(slots)

    # the parts, for a program that runs them one at a time
    forward.layer, forward.logits, forward.kinds = layer, logits, kinds
    return forward


def group_of(path, tree) -> str:
    """The group of :data:`GROUPS` a leaf's gradient is read in: by its
    name, the leading dense layer's gated MLP apart (``dense``)."""
    name = path[-1].key
    if name in GROUPS["experts"] and "router" not in tree["blocks"][path[-2].key]:
        return "dense"
    return next(g for g, names in GROUPS.items() if name in names)


def update_bias(params, slots, speed: float):
    """``b_e += speed * sign(mean(c) - c_e)``, each expert layer from its own
    counts ``slots[layer]`` [E]."""
    import jax.numpy as jnp

    routed = [name for name in sorted(params["blocks"]) if "router" in params["blocks"][name]]
    blocks = dict(params["blocks"])
    for name, c in zip(routed, slots):
        b = blocks[name]["router_bias"]
        blocks[name] = {**blocks[name], "router_bias": b + jnp.float32(speed) * jnp.sign(jnp.mean(c) - c)}
    return {**params, "blocks": blocks}


def decayed(params):
    """AdamW's weight-decay mask: the matrices alone."""
    import jax

    return jax.tree_util.tree_map_with_path(lambda path, _: path[-1].key not in NOT_DECAYED, params)


# ---- the configuration's checks: the SYSTEM's side, then the readings ----


def check_weights(params):
    """The weights every check runs from: the initial weights with every
    router's correction bias drawn normal(0, 0.02) (at the start itself it
    is zero and chooses nothing), seeded, the same for both sides."""
    import jax

    blocks = dict(params["blocks"])
    routed = [name for name in sorted(blocks) if "router_bias" in blocks[name]]
    for key, name in zip(jax.random.split(jax.random.key(1), len(routed)), routed):
        blocks[name] = dict(blocks[name], router_bias=0.02 * jax.random.normal(key, blocks[name]["router_bias"].shape))
    return dict(params, blocks=blocks)


class _patched:
    """``module.name = value`` inside a ``with``."""

    def __init__(self, module, name, value):
        self.args, self.was = (module, name, value), getattr(module, name)

    def __enter__(self):
        setattr(*self.args)

    def __exit__(self, *exc):
        setattr(*self.args[:2], self.was)


@contextlib.contextmanager
def faults(control: str):
    """The fault ``control`` names, in the PROGRAM, while a part of it is
    traced.  ``bfloat16_conv``: the gated convolution keeps its product ``B
    z`` and its convolution in bfloat16 (the XLA chain with two roundings more,
    in place of whichever path ``ops/short_conv.gated_conv`` would take).
    ``bfloat16_router`` rounds the router's operands to bfloat16 on their way
    to ``ops/moe.route``; ``all_bfloat16`` is both and bfloat16 logits
    (:func:`system_under`).  ``no_rotary``: the attention layer's q and k miss
    the rotary turn (``models/attentions.rope`` the identity).  The others
    swap nothing here."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import attentions
    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import short_conv as conv_ops
    from elasticdl_tpu.ops import ssm as ssm_ops

    assert control in ("",) + CONTROLS, f"LFM2_CONTROL {control!r}: known are {CONTROLS}"
    route = moe.route
    # ``reduce_precision``, not a cast there and back: XLA:TPU drops such a pair of converts inside a
    # program (it may keep excess precision), and the control then rounds nothing (PERF.md, PR 40)
    rounded = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)  # noqa: E731

    def lower_conv(b, c, z, taps, **_):
        f32 = jnp.float32
        p = rounded(b.astype(f32) * z.astype(f32))
        conv = rounded(ssm_ops.causal_conv(p, taps.astype(f32), jnp.zeros((p.shape[-1],), f32)))
        return (c.astype(f32) * conv).astype(b.dtype), False

    with contextlib.ExitStack() as stack:
        if control in ("bfloat16_conv", "all_bfloat16"):
            stack.enter_context(_patched(conv_ops, "gated_conv", lower_conv))
        if control in ("bfloat16_router", "all_bfloat16"):
            stack.enter_context(_patched(moe, "route", lambda u, wg, k, **keys: route(rounded(u), rounded(wg), k, **keys)))
        if control == "no_rotary":
            stack.enter_context(_patched(attentions, "rope", lambda x, positions, theta: x))
        yield


def system_under(control: str, p: dict) -> dict:
    """What a control swaps outside the traced program: ``params`` the model
    is built with, ``logits`` the model's logits pass through,
    ``state_unchanged`` for the train step."""
    import jax.numpy as jnp

    assert control in ("",) + CONTROLS, f"LFM2_CONTROL {control!r}: known are {CONTROLS}"
    lower = control in ("bfloat16_logits", "all_bfloat16")
    return {
        "params": dict(p, **({"weight_decay": 0.0} if control == "no_weight_decay" else {})),
        "logits": (lambda z: z.astype(jnp.bfloat16).astype(jnp.float32)) if lower else (lambda z: z),
        "state_unchanged": control == "state_unchanged",
    }


def taps_of_the_model(spec, control: str = ""):
    """A compiled ``(params, tokens, labels) -> {"convolutions": [{"b", "c",
    "z", "taps", "y"} a conv layer], "attention": [{"q", "k", "v", "o"} an
    attention layer], "routers": [{"u", "logits", "choices"} an expert layer],
    "head_input": [a], "logits": z}``: what the MODEL's own entry
    ``spec.apply`` (at the job's dtypes) hands ``ops/short_conv.gated_conv``
    (on the chip the kernel pair), its attention call
    (``models/attentions.ring_attention``: on the chip the flash kernels) and
    ``ops/moe.route`` in each layer and what it gets back, the last thing its
    norm returned and its logits.  The functions are tapped where the model
    looks them up (the modules' attributes) while ``apply`` is traced, and at
    no other time; a model that mixes, attends, routes or norms by another
    function hands them nothing.  The taps wrap the faults.  Every tapped
    operand passes an ``optimization_barrier``: without it XLA hands the op a
    copy of the producer fused into the consumer at a higher precision than
    the array this program returns, and a reading against the RETURNED
    operands reads that difference (PERF.md, PR 40)."""
    import jax

    from elasticdl_tpu.models import attentions, moe_lm
    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import short_conv as conv_ops

    def run(params, tokens, labels):
        mixed, attended, routed, normed = [], [], [], []
        with faults(control):
            real_conv, real_attend, real_route, real_norm = conv_ops.gated_conv, attentions.ring_attention, moe.route, moe_lm._rms_norm

            def conv(b, c, z, taps, **keys):
                # the operands as MATERIALISED arrays, the same for the op and for the reading (below)
                b, c, z = jax.lax.optimization_barrier((b, c, z))
                y, by_kernels = real_conv(b, c, z, taps, **keys)
                mixed.append({"b": b, "c": c, "z": z, "taps": taps, "y": y})
                return y, by_kernels

            def attend(q, k, v, **keys):
                q, k, v = jax.lax.optimization_barrier((q, k, v))
                o = real_attend(q, k, v, **keys)
                attended.append({"q": q, "k": k, "v": v, "o": o})
                return o

            def route(u, wg, k, **keys):
                u = jax.lax.optimization_barrier(u)
                routing = real_route(u, wg, k, **keys)
                routed.append({"u": u, "logits": routing.logits, "choices": routing.choices})
                return routing

            def norm(*args):
                normed.append(jax.lax.optimization_barrier(real_norm(*args)))
                return normed[-1]

            with _patched(conv_ops, "gated_conv", conv), _patched(attentions, "ring_attention", attend), \
                    _patched(moe, "route", route), _patched(moe_lm, "_rms_norm", norm):
                # at jax's own default matmul precision, as the job runs; and
                # train=False: the same forward without the per-layer
                # jax.checkpoint, out of which the taps could not hand what they saw
                with jax.default_matmul_precision(None):
                    out = spec.apply(params, {"tokens": tokens, "labels": labels}, train=False)
        return {"convolutions": mixed, "attention": attended, "routers": routed, "head_input": normed[-1:], "logits": out["logits"]}

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _operator_programs():
    """``(attention, convolution)``: this file's float32 arithmetic on operands of any type, compiled once."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731

    def conv_off(b, c, z, taps, y):
        want = gated_convolution(f32(b), f32(c), f32(z), f32(taps))
        return jnp.sqrt(jnp.sum(jnp.square(f32(y) - want)) / jnp.sum(jnp.square(want)))

    return jax.jit(lambda q, k, v: masked_attention(f32(q), f32(k), f32(v))), jax.jit(conv_off)


def router_readings(u, wg, bias, logits, choices, top_k: int) -> dict:
    """A router's float32 ``logits`` [T, E] and ``choices`` [T, k] on the
    rows ``u``, the weight ``wg`` and the bias, against float64 on the host
    (the product, its sigmoid, the bias added, a stable sort): the largest
    error of a logit relative to the largest logit, and the number of
    (token, rank) choices that differ from float64's."""
    want_r = np.asarray(u, np.float64) @ np.asarray(wg, np.float64)
    want_s = 1.0 / (1.0 + np.exp(-want_r)) + np.asarray(bias, np.float64)
    want_c = np.argsort(-want_s, axis=-1, kind="stable")[:, :top_k]
    return {
        "router_logits": float(np.abs(np.asarray(logits, np.float64) - want_r).max() / np.abs(want_r).max()),
        "router_choices_differing": int(np.sum(np.asarray(choices) != want_c)),
    }


def forward_readings(taps: dict, weights, top_k: int) -> dict:
    """Bare readings of the forward pass the model ran (``taps``), each
    against this file's arithmetic on the OPERANDS THE MODEL HANDED OVER:

    - ``gconv_output``: the root-mean-square error of a convolution layer's
      ``y`` over the root-mean-square of the float32 shifted products on the
      call's own B, C, z and taps; the worst layer (ONE rounding of a float32
      result to bfloat16 reads 0.00166; a rounding more of the product or of
      the convolution adds its own in quadrature);
    - ``attention_output``: the largest error of the attention layer's ``o``
      relative to its largest ``|o|``, against the float32 softmax under the
      explicit causal mask on the call's own q, k, v, a sequence at a time;
    - ``router_logits`` / ``router_choices_differing``: every router against
      float64 of the rows it was handed and the layer's float32 PARAMETERS
      (a weight rounded on the way shows); the worst layer / their sum;
    - ``head_logits``: the largest error of the logits relative to the
      largest, against the head's input times the TIED embedding rounded to
      the input's type, float32 at precision highest, a sequence at a time.

    A reading whose taps are empty (or fewer than the layers) is left out."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    blocks = weights["blocks"]
    attention, convolution = _operator_programs()
    out: dict = {}
    with jax.default_matmul_precision("highest"):
        convs = [name for name in sorted(blocks) if "gconv_in" in blocks[name]]
        if convs and len(taps["convolutions"]) == len(convs):
            out["gconv_output"] = max(float(convolution(t["b"], t["c"], t["z"], t["taps"], t["y"])) for t in taps["convolutions"])
        if taps["attention"] and len(taps["attention"]) == len(blocks) - len(convs):
            for layer in taps["attention"]:
                for row in range(layer["q"].shape[0]):
                    q, k, v, o = (layer[name][row:row + 1] for name in "qkvo")
                    want = attention(q, k, v)
                    off = float(jnp.max(jnp.abs(f32(o) - want)) / jnp.max(jnp.abs(want)))
                    out["attention_output"] = max(out.get("attention_output", 0.0), off)
        names = [name for name in sorted(blocks) if "router" in blocks[name]]
        if names and len(taps["routers"]) == len(names):
            each = [
                router_readings(r["u"], blocks[name]["router"], blocks[name]["router_bias"], r["logits"], r["choices"], top_k)
                for name, r in zip(names, taps["routers"])
            ]
            out["router_logits"] = max(reading["router_logits"] for reading in each)
            out["router_choices_differing"] = sum(reading["router_choices_differing"] for reading in each)
        for a in taps["head_input"]:
            head = f32(jnp.asarray(weights["tok_emb"]).astype(a.dtype)).T
            a = a.reshape(taps["logits"].shape[0], -1, a.shape[-1])
            for row in range(a.shape[0]):
                z, want = f32(taps["logits"][row]), f32(a[row]) @ head
                out["head_logits"] = max(out.get("head_logits", 0.0), float(jnp.max(jnp.abs(z - want)) / jnp.max(jnp.abs(want))))
    return out


_T0 = time.time()


def _tick(what: str) -> None:
    print(f"  [{time.time() - _T0:6.1f} s] {what}", flush=True)


@functools.lru_cache(maxsize=None)
def _system(model_def: str, params: str, control: str, strategy: str):
    """``(spec, taps, trainer)`` of the model under ``control``, built once a
    process (the sizing tool reads several seeds)."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    spec = load_model_spec("elasticdl_tpu.models", model_def, **system_under(control, json.loads(params))["params"])
    trainer = Trainer(spec, JobConfig(distribution_strategy=strategy), create_mesh(num_devices=1))
    return spec, taps_of_the_model(spec, control), trainer


def build_the_step(trainer, tokens, labels) -> None:
    """The program's train step built (and, for a model whose blocks keep by
    budget, compiled) from shapes alone: ``Trainer.build_train_step``."""
    import jax

    from jax.sharding import NamedSharding

    # placed as the real call's arrays will be: the same program, so the call finds this compile
    placed = lambda leaf, spec: jax.ShapeDtypeStruct(leaf.shape, leaf.dtype, sharding=NamedSharding(trainer.mesh, spec))  # noqa: E731
    state = jax.eval_shape(trainer.init_state, jax.random.key(0))
    state = jax.tree.map(placed, state, trainer.state_specs())
    batch = {"tokens": tokens, "labels": labels}
    with jax.default_matmul_precision(None):
        trainer.build_train_step(state, jax.tree.map(placed, batch, trainer.batch_specs(batch)))


def trained_by_the_program(trainer, weights, tokens, labels, p: dict, control: str = "") -> dict:
    """The program's own train step (``parallel/trainer.Trainer`` on the
    model's spec: per-layer rematerialisation, the flash kernels under a
    and the gated convolution's kernel pair, forward and backward, the grouped matmuls, AdamW with
    its mask and warm-up, the bias's rule — what the job's worker compiles,
    one step a call) run TWICE on one minibatch from ``weights`` (a numpy
    tree): ``gradient`` (numpy), read off AdamW's first moment after the
    first step (``m = (1 - b1) g``; the warm-up's rate is 0 there, so the
    optimizer moves nothing); ``update``, the distance of the parameters'
    change after the second step from what AdamW written out in float32
    makes of the two steps' OWN gradients (the second read off the moments'
    change: the correction bias moved between them, so it is not the first
    again) under the CONFIGURATION's parameters ``p``, over the latter's
    size, the correction biases left out (the model's rule moves them, not
    AdamW); ``loss`` the first step's."""
    import jax
    import jax.numpy as jnp
    import optax

    state = trainer.init_state(jax.random.key(0))
    state = state.replace(params=jax.tree.map(
        lambda new, old: jax.device_put(np.asarray(new, old.dtype), old.sharding), weights, state.params))
    batch = {"tokens": np.ascontiguousarray(tokens), "labels": np.ascontiguousarray(labels)}
    is_adam = lambda s: isinstance(s, optax.ScaleByAdamState)  # noqa: E731

    def first_moment(state):
        (adam,) = [s for s in jax.tree.leaves(state.opt_state, is_leaf=is_adam) if is_adam(s)]
        return adam.mu

    rate, warmup, decay = float(p["learning_rate"]), int(p["lr_warmup_steps"]), float(p["weight_decay"])
    unchanged = system_under(control, p)["state_unchanged"]

    @functools.partial(jax.jit, static_argnums=(4, 5))
    def squares(w0, m1, m2, new, is_decayed, moved):
        """Of one leaf: (|change - AdamW's|^2, |AdamW's|^2), AdamW written out
        in float32 over the two steps' own gradients."""
        w0, m1, m2, new = (t.astype(jnp.float32) for t in (w0, m1, m2, new))
        gradients = (m1 / (1 - B1), (m2 - B1 * m1) / (1 - B1))
        w, m, v = w0, jnp.zeros_like(w0), jnp.zeros_like(w0)
        for t, g in enumerate(gradients, 1):
            m, v = B1 * m + (1 - B1) * g, B2 * v + (1 - B2) * g * g
            update = (m / (1 - B1 ** t)) / (jnp.sqrt(v / (1 - B2 ** t)) + EPS)
            if is_decayed:
                update = update + decay * w
            w = w - (rate * min(t - 1, warmup) / warmup) * update
        want, change = (w - w0) * moved, ((w0 if unchanged else new) - w0) * moved
        return jnp.sum(jnp.square(change - want)), jnp.sum(jnp.square(want))

    with faults(control), jax.default_matmul_precision(None):
        state, metrics = trainer.run_train_step(state, batch)
        m1, loss = first_moment(state), float(metrics["loss"])  # on the host: the second step needs the chip
        m1 = jax.tree.map(np.asarray, m1)
        state, _ = trainer.run_train_step(state, batch)
    off = size = 0.0
    for (path, w0), old, new_m, new, dec in zip(
        jax.tree_util.tree_leaves_with_path(weights), jax.tree.leaves(m1), jax.tree.leaves(first_moment(state)),
        jax.tree.leaves(state.params), jax.tree.leaves(decayed(weights)),
    ):
        # a leaf at a time ON THE DEVICE (the state stays there: a tree of 705 M floats is 2.8 GB of the host's 40)
        o, s = squares(w0, old, new_m, new, bool(dec), path[-1].key != "router_bias")
        off, size = off + float(o), size + float(s)
    del state  # the moments: 8 bytes a parameter
    gradient = jax.tree.map(lambda m: m / np.float32(1.0 - B1), m1)  # m = (1 - b1) g
    return {"gradient": gradient, "update": (off / max(size, 1e-300)) ** 0.5, "loss": loss}


def relative_distance(got, want, by_group: bool = False):
    """``|got - want| / |want|`` of two parameter trees (Euclidean, all
    leaves together), or ``{group: that}`` over :data:`GROUPS`; the sums a
    leaf on the device."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def squares(a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))

    sums: dict = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        group = group_of(path, got) if by_group else ""
        off, size = (float(x) for x in squares(jnp.asarray(a), jnp.asarray(b)))
        sums[group] = sums.get(group, (0.0, 0.0))[0] + off, sums.get(group, (0.0, 0.0))[1] + size
    out = {g: (off / max(size, 1e-300)) ** 0.5 for g, (off, size) in sums.items()}
    return out if by_group else out[""]


class _ReferenceProgram:
    """``program(w, tokens, labels, gradient=True) -> ((loss, (logits,
    slots)), gradient or None)``: :func:`build`'s model and its
    ``value_and_grad``, run a LAYER at a time — each layer's forward, then,
    from the head down, each layer's ``jax.vjp`` on the input it saw (its
    forward again: memory, not values) — a SEQUENCE of the minibatch at a time
    (the loss is a mean: the rows' gradients average, their slots add) — so
    that a layer KIND (convolution or attention, dense or experts) is compiled
    once, for one sequence; and compiled AHEAD,
    on a thread, from shapes (:meth:`warm`), while the system's side of the
    checks holds the chip (``kimi_linear_48b_a3b_ep32_l5_reference.py`` has
    the readings that made it so, PR 40).  The same arithmetic as
    ``jax.value_and_grad`` of ``build(p)``'s loss
    (tests/benchmark/test_lfm2_cell.py holds them together)."""

    def __init__(self, p: dict):
        import concurrent.futures

        import jax
        import jax.numpy as jnp
        import optax

        forward = build(p)
        self.kinds = forward.kinds

        def top(h, norm_f, tok_emb, labels):
            z = forward.logits(h, norm_f, tok_emb)
            return optax.softmax_cross_entropy_with_integer_labels(z, labels).mean(), z

        self.parts = {
            "top": jax.value_and_grad(top, argnums=(0, 1, 2), has_aux=True),
            # the TIED table's gradient: the head's, and the rows the tokens looked up
            "rows_summed": lambda g, tokens, of_head: of_head.at[tokens].add(g),
        }
        for kind in KINDS:
            layer = functools.partial(forward.layer, kind=kind)
            self.parts["layer " + kind] = layer
            self.parts["layer_vjp " + kind] = lambda h, blk, g, layer=layer: jax.vjp(lambda h, blk: layer(h, blk)[0], h, blk)[1](g)
        self.compiled: dict = {}
        self.pool = concurrent.futures.ThreadPoolExecutor(max_workers=2)

    def _compiled(self, part: str, *args):
        """The (future of the) executable of ``parts[part]`` for the shapes of
        ``args`` (arrays or shapes), its compile started on the pool at the
        first asking."""
        import jax

        shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        key = (part, str(jax.tree.structure(shapes)), tuple((s.shape, str(s.dtype)) for s in jax.tree.leaves(shapes)))
        if key not in self.compiled:
            def compile_it():
                with jax.default_matmul_precision("highest"):
                    return jax.jit(self.parts[part]).lower(*shapes).compile()

            self.compiled[key] = self.pool.submit(compile_it)
        return self.compiled[key]

    def _run(self, part: str, *args):
        return self._compiled(part, *args).result()(*args)

    def warm(self, weights, batch: int, length: int, gradient: bool = True) -> None:
        """Start compiling every part this model's shapes need (``weights``:
        arrays or shapes), without touching the device; returns at once."""
        import jax
        import jax.numpy as jnp

        w = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, jnp.float32), weights)
        del batch  # a sequence at a time
        h = jax.ShapeDtypeStruct((1, length, w["tok_emb"].shape[1]), jnp.float32)
        ids = jax.ShapeDtypeStruct((1, length), jnp.int32)
        layers = list(zip(self.kinds, (w["blocks"][name] for name in sorted(w["blocks"]))))
        for kind, blk in layers:
            self._compiled("layer " + kind, h, blk)
        self._compiled("top", h, w["norm_f"], w["tok_emb"], ids)
        if gradient:
            for kind, blk in layers:
                self._compiled("layer_vjp " + kind, h, blk, h)
            self._compiled("rows_summed", h, ids, w["tok_emb"])

    def _a_sequence(self, w, tokens, labels, gradient: bool):
        import jax.numpy as jnp

        names, seen, slots = sorted(w["blocks"]), [w["tok_emb"][tokens]], []
        for name, kind in zip(names, self.kinds):
            h, sent = self._run("layer " + kind, seen[-1], w["blocks"][name])
            seen.append(h)
            if sent is not None:
                slots.append(sent)
        (loss, z), (g, g_norm, g_head) = self._run("top", seen.pop(), w["norm_f"], w["tok_emb"], labels)
        out = (loss, (z, jnp.stack(slots)))
        if not gradient:
            return out, None
        grads = {"norm_f": g_norm, "blocks": {}}
        for name, kind in reversed(list(zip(names, self.kinds))):
            g, grads["blocks"][name] = self._run("layer_vjp " + kind, seen.pop(), w["blocks"][name], g)
        grads["tok_emb"] = self._run("rows_summed", g, tokens, g_head)
        return out, grads

    def __call__(self, w, tokens, labels, gradient: bool = True):
        import jax
        import jax.numpy as jnp

        rows = tokens.shape[0]
        loss, logits, slots, grads = 0.0, [], 0.0, None
        for row in range(rows):
            (a_loss, (z, sent)), a_gradient = self._a_sequence(w, tokens[row:row + 1], labels[row:row + 1], gradient)
            loss, slots = loss + a_loss / rows, slots + sent
            logits.append(z)
            if gradient:
                a_gradient = jax.tree.map(lambda g: g / rows, a_gradient)
                grads = a_gradient if grads is None else jax.tree.map(jnp.add, grads, a_gradient)
        return (loss, (jnp.concatenate(logits), slots)), grads


@functools.lru_cache(maxsize=None)
def _reference_program(params: str) -> _ReferenceProgram:
    return _ReferenceProgram(json.loads(params))


def reference_of_the_checks(p: dict, weights, tokens, labels, to_host: bool = False) -> dict:
    """This file's float32 model on the checks' weights and minibatch:
    ``loss``, ``logits`` and ``gradient``, at matmul precision highest,
    each layer rematerialised."""
    import jax

    import jax.numpy as jnp

    weights = jax.tree.map(jnp.asarray, weights)
    with jax.default_matmul_precision("highest"):
        (loss, (z, _)), gradient = _reference_program(json.dumps(p, sort_keys=True))(weights, jnp.asarray(tokens), jnp.asarray(labels))
    out = {"loss": float(loss), "logits": z, "gradient": gradient}
    _tick("the reference's loss, logits and gradient")
    return dict(jax.tree.map(np.asarray, out), loss=out["loss"]) if to_host else out


def system_of_the_checks(config: dict, tokens, labels, control: str = "", train: bool = True) -> dict:
    """The system's side under ``control``: ``weights`` (numpy;
    :func:`check_weights`), the ``forward`` readings, its ``logits`` and,
    with ``train``, what :func:`trained_by_the_program` returns."""
    import jax
    import jax.numpy as jnp

    import threading

    p = config["model_params"]
    spec, taps, trainer = _system(config["model_def"], json.dumps(p, sort_keys=True), control, config["distribution_strategy"])
    weights = check_weights(jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0))))
    # The taps are TRACED first (their patches of the program's modules are
    # process-wide while they last); only then is the train step built, on
    # a thread of its own, while this one compiles and runs the taps: its
    # compile is the child's longest.  Under a control the step is built
    # later, inside the control's own patches.
    lowered = taps.lower(weights, tokens, labels)
    ahead = threading.Thread(target=build_the_step, args=(trainer, tokens, labels), daemon=True)
    if train and not control:
        ahead.start()
    seen = jax.block_until_ready(lowered.compile()(weights, tokens, labels))
    _tick("the model's forward pass, tapped")
    seen["logits"] = system_under(control, p)["logits"](seen["logits"])
    out = {"forward": forward_readings(seen, weights, int(p["num_experts_per_tok"]))}
    _tick(f"forward readings {out['forward']}")
    out["logits"] = np.asarray(seen["logits"], np.float32)
    out["weights"] = jax.tree.map(np.asarray, weights)  # off the device: the train step's state is 12 bytes a parameter
    del seen, weights
    if train:
        if ahead.is_alive():
            ahead.join()
            _tick("the train step, built and compiled on its thread")
        out["trained"] = trained_by_the_program(trainer, out["weights"], tokens, labels, p, control)
        _tick("two train steps of the program, and AdamW on their gradients")
    return out


def readings_of(system: dict, reference: dict) -> dict:
    """Every check of the configuration as a bare reading, from the two
    sides: the forward readings; ``logits``, the root-mean-square error of
    the model's logits over the reference's root-mean-square; ``grad_<group>``,
    the distance of the train step's gradient from the reference's over the
    reference's size, a group of :data:`GROUPS`; ``adamw_update``
    (:func:`trained_by_the_program`: 1 where the state was left as it was)."""
    out = dict(system["forward"])
    out["logits"] = relative_distance({"tok_emb": system["logits"]}, {"tok_emb": reference["logits"]})
    if "trained" in system:
        trained = system["trained"]
        out.update({f"grad_{g}": d for g, d in relative_distance(trained["gradient"], reference["gradient"], True).items()})
        out["adamw_update"] = trained["update"]
    return out


def warm_the_reference(config: dict, batch: int) -> None:
    """Start compiling the reference's parts (a sequence at a time), on
    threads, from shapes alone."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    p = config["model_params"]
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    shapes = jax.eval_shape(spec.init, jax.random.key(0))
    _reference_program(json.dumps(p, sort_keys=True)).warm(shapes, batch, int(p["seq_len"]))


def readings(config: dict, tokens, labels, control: str = "") -> dict:
    """The configuration's checks on ONE minibatch (module docstring)."""
    warm_the_reference(config, tokens.shape[0])
    system = system_of_the_checks(config, tokens, labels, control)
    reference = reference_of_the_checks(config["model_params"], system["weights"], tokens, labels)
    return readings_of(system, reference)


def main() -> None:
    t_start = time.time()
    config, traffic, data, out = parse_args()
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_default_matmul_precision", "highest")
    p = config["model_params"]
    seq = int(p["seq_len"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])
    if steps > 1 and int(p.get("lr_warmup_steps", 0)) <= 0:
        raise SystemExit("this reference runs a task's later steps from the weights of its first: it needs a warm-up (rate 0 at the first update)")

    from elasticdl_tpu.models.spec import load_model_spec

    warm_the_reference(config, mb)  # compiles on threads while the system's side of the checks holds the chip
    records = read_records(data, steps * mb)
    toks = np.stack([np.frombuffer(r, "<i4") for r in records])
    assert toks.shape[1] == seq + 1
    result = {"device": device_report()}
    system = None
    if config.get("checks"):
        t_checks = time.time()
        first = toks[:mb]  # the run's first minibatch, at the step's own size
        control = os.environ.get("LFM2_CONTROL", "")
        system = system_of_the_checks(config, first[:, :-1], first[:, 1:], control)
        result["control"] = control

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0)))
    program = _reference_program(json.dumps(p, sort_keys=True))
    losses = []
    for i in range(steps):
        batch = toks[i * mb : (i + 1) * mb]
        (loss, (_, slots)), _ = program(params, jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:]), gradient=False)
        # the first update's rate is 0: the optimizer moves nothing; the model's rule moves the bias
        params = update_bias(params, slots, float(p.get("bias_update_speed", 0.001)))
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.6f} at {time.time() - t_start:.1f} s", flush=True)
    result.update({"loss": float(np.mean(losses)), "step_losses": losses})
    del params
    if system is not None:
        reference = reference_of_the_checks(p, system["weights"], first[:, :-1], first[:, 1:])
        result["checks"] = readings_of(system, reference)
        result["checks_seconds"] = time.time() - t_checks
        print(f"checks{' under ' + control if control else ''}: {result['checks']} in {result['checks_seconds']:.1f} s", flush=True)
    with open(out, "w") as f_out:
        json.dump(result, f_out)


if __name__ == "__main__":
    main()
