"""Plain reference of the decoder the ``trinity_mini_26b_a3b_ep8_l5``
configuration runs (Trinity-Mini, ``afmoe``): float32 ``jax.numpy``, matmul
precision ``highest``, softmax attention under an EXPLICIT [L, L] mask (a
block of queries at a time against all keys: a window layer's mask is ``p - W
< j <= p``, a full layer's ``j <= p``), the key/value heads read by index (no
repeat), every held expert on every token under the router's weights — no
kernel, no skipped block, and no code of ``elasticdl_tpu/ops/`` or
``elasticdl_tpu/models/`` (the reference takes ONE thing of the model:
``model_spec.init(key(0))``, whose weights are data here; the checks at the
end of this file run the model itself, as the thing measured).

A sequence of L tokens from the vocabulary slice; ``rmsnorm(x, g) = x *
rsqrt(mean(x^2) + eps) * g``; d = ``hidden_size``, H query heads over G
key/value heads of hd, W = ``sliding_window``; HELD experts only (what the
others would add is left out, here and in the program alike):

    h  = E[x] * sqrt(d)                                                (mup_enabled)
    layer i, kind = layer_types[i]:
      a  = rmsnorm(h, attn_norm)
      q  = a Wq [H, hd] ; k, v = a Wk, a Wv [G, hd] ; z = a Wz [H x hd]
      q  = rmsnorm(q, q_norm[hd]) ; k = rmsnorm(k, k_norm[hd])          (a norm a head, ONE gain for all heads)
      sliding_attention only: q, k = rope(q), rope(k)                   (rotate-half over the whole head; full layers: NO position signal)
      head h reads key/value head h // (H / G) ; scores / sqrt(hd) ; softmax over j <= p (full) or p - W < j <= p (sliding)
      h += rmsnorm((o * sigmoid(z)) Wo, post_attn_norm)
      u  = rmsnorm(h, ffn_norm)
      layers < num_dense_layers: m = (silu(u Wgate) * (u Wup)) Wdown
      the rest: r = u Wg (float32) ; s = sigmoid(r) ; e_1..e_k = top-k of s + b
                w_i = route_scale x s[e_i] / (sum_j s[e_j] + 1e-20)
                m = sum_{i: e_i held} w_i expert_{e_i}(u) + shared(u)
      h += rmsnorm(m, post_ffn_norm)
    z  = rmsnorm(h, g_f) Whead ; loss = mean CE(z_i, x_{i+1})
    after a step: b_e += load_balance_coeff x sign(mean(c) - c_e), c_e the slots the step's router sent expert e
    AdamW (0.9, 0.95, 1e-8; decay on the matrices alone), the rate raised linearly from 0

Departures from the published model are the configuration's ``assumed``
list.

It runs the first task (``minibatches_per_task`` steps, in order) from the
same initial weights as the system and reports the mean of the steps'
losses, which is what the worker reports for a task.  The warm-up's rate is
0 at the first update, so a step moves NO weight the optimizer owns (this
file refuses a configuration without a warm-up); what moves between a
task's steps is the routers' correction bias, by the rule above.

Then, in the same process, a bare reading for each of the configuration's
``checks`` (``benchmark/run.py`` judges them against the limits in the
configuration's file), on the run's first minibatch from
:func:`check_weights`.  The SYSTEM's side is the program itself, not a
copy: the model's own ``spec.apply`` with its attention call, the router and
the norm tapped (:func:`taps_of_the_model`), and ``parallel/trainer.Trainer``'s
own train step (:func:`trained_by_the_program`).  Two kinds of reading:

- against this file's float32 model on float32 weights (the MECHANISM, every
  layer, forward and backward; reads the bfloat16 compute's noise):
  ``logits``, ``grad_<group>``;
- against float32 / float64 arithmetic on the operands THE SYSTEM ITSELF
  handed over: ``window_output`` / ``full_output`` (what the model's call of
  the attention returned against the masked softmax on the q, k, v it was
  handed), ``router_logits`` / ``router_choices_differing`` (float64),
  ``head_logits``, ``adamw_update``.

``TRINITY_MINI_CONTROL=<one of CONTROLS>`` in the child's environment swaps a
fault into the system's side (:func:`faults`), so that ``benchmark/run.py``
ends with ``correct`` false: how each limit was shown to catch what the
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
CONTROLS = (
    "full_for_window", "window_off_by_one", "rotary_on_full_layers", "bfloat16_router", "bfloat16_logits", "all_bfloat16",
    "no_weight_decay", "state_unchanged",
)
KINDS = ("sliding_attention", "full_attention")
GROUPS = {
    "attention": ("wq", "wk", "wv", "wz", "wo", "q_norm", "k_norm"),
    "experts": ("w_gate", "w_up", "w_down"),  # of a layer with a router; the leading dense layer's are "dense" (group_of)
    "shared": ("ws_gate", "ws_up", "ws_down"),
    "router": ("router", "router_bias"),  # the bias has no gradient on either side
    "head": ("head",), "embedding": ("tok_emb",),
    "norms": ("attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm", "norm_f"),
}
NOT_DECAYED = GROUPS["norms"] + ("q_norm", "k_norm", "router_bias")
B1, B2, EPS = 0.9, 0.95, 1e-8
QUERY_BLOCK = 1024


def masked_attention(q, k, v, window: int):
    """Softmax attention under an explicit mask: ``q`` [B, L, H, hd]; ``k``,
    ``v`` [B, L, G, hd] with G a divisor of H (query head h reads key/value
    head ``h // (H / G)``); position p sees the keys ``j <= p`` and, under a
    ``window`` > 0, ``j > p - window``.  A block of queries at a time against
    all keys: the scores of one block are alive at a time."""
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
        p = (first + jnp.arange(q_blk.shape[1]))[:, None]
        seen = at[None, :] <= p
        if window:
            seen &= at[None, :] > p - window
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


def build(p: dict):
    """``forward(params, tokens) -> (logits [B, L, V] float32, slots [expert
    layers, E])`` for the model parameters ``p`` (the published keys), in
    the precision of the weights it is given."""
    import jax
    import jax.numpy as jnp

    eps, theta = float(p["rms_norm_eps"]), float(p["rope_theta"])
    heads, kv_heads, hd = int(p["num_attention_heads"]), int(p["num_key_value_heads"]), int(p["head_dim"])
    window, kinds = int(p["sliding_window"]), tuple(p["layer_types"])
    top_k, scaling, lo = int(p["num_experts_per_tok"]), float(p["route_scale"]), int(p.get("first_expert_held", 0))
    embed_scale = float(p["hidden_size"]) ** 0.5 if p.get("mup_enabled") else 1.0
    assert p["score_func"] == "sigmoid" and p["route_norm"] and set(kinds) <= set(KINDS)

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    silu = lambda t: t * jax.nn.sigmoid(t)  # noqa: E731

    def attention(a, blk, kind):
        bsz, l, _ = a.shape
        q = rmsnorm((a @ blk["wq"]).reshape(bsz, l, heads, hd), blk["q_norm"])
        k = rmsnorm((a @ blk["wk"]).reshape(bsz, l, kv_heads, hd), blk["k_norm"])
        v = (a @ blk["wv"]).reshape(bsz, l, kv_heads, hd)
        if kind == "sliding_attention":  # a full layer has NO position signal
            q, k = rotate(q, theta), rotate(k, theta)
        o = masked_attention(q, k, v, window if kind == "sliding_attention" else 0)
        return (o.reshape(bsz, l, -1) * jax.nn.sigmoid(a @ blk["wz"])) @ blk["wo"]

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
        m = (picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20) * scaling).astype(u.dtype)
        # every held expert on every token, weighed by the held experts' columns of m: a slot on an
        # absent expert adds nothing (E x T x f values: 0.54 GB at the cell's size)
        hidden = silu(jnp.einsum("td,edf->etf", t, blk["w_gate"])) * jnp.einsum("td,edf->etf", t, blk["w_up"])
        y = jnp.einsum("etf,efd,te->td", hidden, blk["w_down"], m[:, lo:lo + held])
        y = y + gated(t, blk["ws_gate"], blk["ws_up"], blk["ws_down"])
        return y.reshape(bsz, l, d), jnp.sum(onehot, (0, 1))

    def layer(h, blk, kind):
        h = h + rmsnorm(attention(rmsnorm(h, blk["attn_norm"]), blk, kind), blk["post_attn_norm"])
        u = rmsnorm(h, blk["ffn_norm"])
        if "router" in blk:
            y, sent = experts(u, blk)
        else:
            y, sent = gated(u, blk["w_gate"], blk["w_up"], blk["w_down"]), None
        return h + rmsnorm(y, blk["post_ffn_norm"]), sent

    def logits(h, norm_f, head):
        return (rmsnorm(h, norm_f) @ head).astype(jnp.float32)

    def forward(params, tokens):
        h = params["tok_emb"][tokens] * embed_scale
        slots = []
        for name, kind in zip(sorted(params["blocks"]), kinds):
            h, sent = layer(h, params["blocks"][name], kind)
            if sent is not None:
                slots.append(sent)
        return logits(h, params["norm_f"], params["head"]), jnp.stack(slots)

    # the parts, for a program that runs them one at a time
    forward.layer, forward.logits, forward.kinds, forward.embed_scale = layer, logits, kinds, embed_scale
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
    traced.  ``full_for_window``: the model's attention call loses its window
    (every layer full causal attention).  ``window_off_by_one``: a window one
    key too long, in whichever path runs — the flash kernels' far-edge mask
    keeps the key exactly a window back (``ops/flash_attention._seen``), the
    XLA path gets ``window + 1``.  ``rotary_on_full_layers``: the full layers'
    q and k take the rotary turn too, on their way into the attention (a
    position signal where the model has none).  ``bfloat16_router`` rounds the router's
    operands to bfloat16 on their way to ``ops/moe.route``; ``all_bfloat16``
    is that and bfloat16 logits (:func:`system_under`).  The others swap
    nothing here."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import attentions
    from elasticdl_tpu.ops import flash_attention as flash_ops
    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import ring_attention as ring_ops

    assert control in ("",) + CONTROLS, f"TRINITY_MINI_CONTROL {control!r}: known are {CONTROLS}"
    attend, route, seen, plain = attentions.ring_attention, moe.route, flash_ops._seen, ring_ops.attention_reference
    # ``reduce_precision``, not a cast there and back: XLA:TPU drops such a pair of converts inside a
    # program (it may keep excess precision), and the control then rounds nothing (PERF.md, PR 40)
    rounded = lambda t: jax.lax.reduce_precision(t.astype(jnp.float32), exponent_bits=8, mantissa_bits=7)  # noqa: E731

    def one_key_more(masked, q0, k0, shape, q_axis):
        if masked != flash_ops._EDGE:
            return seen(masked, q0, k0, shape, q_axis)
        return ~flash_ops._causal_mask(q0, k0 + 1, shape, q_axis)  # the key's local position >= the query's

    def turned(q, k, v, **keys):
        if keys.get("window") is None:
            at = jnp.arange(q.shape[1])
            q, k = attentions.rope(q, at, 10000.0), attentions.rope(k, at, 10000.0)
        return attend(q, k, v, **keys)

    with contextlib.ExitStack() as stack:
        if control == "full_for_window":
            stack.enter_context(_patched(attentions, "ring_attention", lambda q, k, v, **keys: attend(q, k, v, **dict(keys, window=None))))
        if control == "window_off_by_one":
            stack.enter_context(_patched(flash_ops, "_seen", one_key_more))
            stack.enter_context(_patched(
                ring_ops, "attention_reference",
                lambda q, k, v, causal=False, q_rot=None, k_rot=None, window=None: plain(
                    q, k, v, causal, q_rot, k_rot, None if window is None else window + 1)))
        if control == "rotary_on_full_layers":
            stack.enter_context(_patched(attentions, "ring_attention", turned))
        if control in ("bfloat16_router", "all_bfloat16"):
            stack.enter_context(_patched(moe, "route", lambda u, wg, k, **keys: route(rounded(u), rounded(wg), k, **keys)))
        yield


def system_under(control: str, p: dict) -> dict:
    """What a control swaps outside the traced program: ``params`` the model
    is built with, ``logits`` the model's logits pass through,
    ``state_unchanged`` for the train step."""
    import jax.numpy as jnp

    assert control in ("",) + CONTROLS, f"TRINITY_MINI_CONTROL {control!r}: known are {CONTROLS}"
    lower = control in ("bfloat16_logits", "all_bfloat16")
    return {
        "params": dict(p, **({"weight_decay": 0.0} if control == "no_weight_decay" else {})),
        "logits": (lambda z: z.astype(jnp.bfloat16).astype(jnp.float32)) if lower else (lambda z: z),
        "state_unchanged": control == "state_unchanged",
    }


def taps_of_the_model(spec, control: str = ""):
    """A compiled ``(params, tokens, labels) -> {"attention": [{"q", "k", "v",
    "o", "window"} a layer], "routers": [{"u", "logits", "choices"} an expert
    layer], "head_input": [a], "logits": z}``: what the MODEL's own entry
    ``spec.apply`` (at the job's dtypes) hands its attention call
    (``models/attentions.ring_attention``: on the chip the flash kernels,
    under a window or full) and ``ops/moe.route`` in each layer and what it
    gets back, the last thing its norm returned and its logits.  The
    functions are tapped where the model looks them up (the modules'
    attributes) while ``apply`` is traced, and at no other time; a model
    that attends, routes or norms by another function hands them nothing.
    The taps wrap the faults: a tap hears what the MODEL asked for (``window``
    is what its call said, 0 for none) and reads what came back.
    Every tapped operand passes an ``optimization_barrier``: without it XLA
    hands the op a copy of the producer fused into the consumer at a higher
    precision than the array this program returns, and a reading against the
    RETURNED operands reads that difference (PERF.md, PR 40)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import attentions, moe_lm
    from elasticdl_tpu.ops import moe

    def run(params, tokens, labels):
        attended, routed, normed = [], [], []
        with faults(control):
            real_attend, real_route, real_norm = attentions.ring_attention, moe.route, moe_lm._rms_norm

            def attend(q, k, v, **keys):
                # the operands as MATERIALISED arrays, the same for the op and for the reading (below)
                q, k, v = jax.lax.optimization_barrier((q, k, v))
                o = real_attend(q, k, v, **keys)
                attended.append({"q": q, "k": k, "v": v, "o": o, "window": jnp.int32(keys.get("window") or 0)})
                return o

            def route(u, wg, k, **keys):
                u = jax.lax.optimization_barrier(u)
                routing = real_route(u, wg, k, **keys)
                routed.append({"u": u, "logits": routing.logits, "choices": routing.choices})
                return routing

            def norm(*args):
                normed.append(jax.lax.optimization_barrier(real_norm(*args)))
                return normed[-1]

            with _patched(attentions, "ring_attention", attend), _patched(moe, "route", route), _patched(moe_lm, "_rms_norm", norm):
                # at jax's own default matmul precision, as the job runs; and
                # train=False: the same forward without the per-layer
                # jax.checkpoint, out of which the taps could not hand what they saw
                with jax.default_matmul_precision(None):
                    out = spec.apply(params, {"tokens": tokens, "labels": labels}, train=False)
        return {"attention": attended, "routers": routed, "head_input": normed[-1:], "logits": out["logits"]}

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _attention_program():
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    return jax.jit(lambda q, k, v, window: masked_attention(f32(q), f32(k), f32(v), window), static_argnums=3)


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

    - ``window_output`` / ``full_output``: the largest error of any sliding /
      full layer's ``o`` relative to that layer's largest ``|o|``, against the
      float32 softmax under the explicit mask of the window the model's call
      NAMED (none for a full layer), on the call's own q, k, v;
    - ``router_logits`` / ``router_choices_differing``: every router against
      float64 of the rows it was handed and the layer's float32 PARAMETERS
      (a weight rounded on the way shows); the worst layer / their sum;
    - ``head_logits``: the largest error of the logits relative to the
      largest, against the head's input times the head's matrix rounded to
      the input's type, float32 at precision highest.

    A reading whose taps are empty (or fewer than the layers) is left out."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    blocks = weights["blocks"]
    out: dict = {}
    with jax.default_matmul_precision("highest"):
        if len(taps["attention"]) == len(blocks):
            for layer in taps["attention"]:
                window = int(layer["window"])
                want = _attention_program()(layer["q"], layer["k"], layer["v"], window)
                off = float(jnp.max(jnp.abs(f32(layer["o"]) - want)) / jnp.max(jnp.abs(want)))
                name = "window_output" if window else "full_output"
                out[name] = max(out.get(name, 0.0), off)
        names = [name for name in sorted(blocks) if "router" in blocks[name]]
        if names and len(taps["routers"]) == len(names):
            each = [
                router_readings(r["u"], blocks[name]["router"], blocks[name]["router_bias"], r["logits"], r["choices"], top_k)
                for name, r in zip(names, taps["routers"])
            ]
            out["router_logits"] = max(reading["router_logits"] for reading in each)
            out["router_choices_differing"] = sum(reading["router_choices_differing"] for reading in each)
        for a in taps["head_input"]:
            z = f32(taps["logits"])
            want = (f32(a) @ f32(jnp.asarray(weights["head"]).astype(a.dtype))).reshape(z.shape)
            out["head_logits"] = float(jnp.max(jnp.abs(z - want)) / jnp.max(jnp.abs(want)))
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
    window and full, forward and backward, the grouped matmuls, AdamW with
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
    forward again: memory, not values) — so that a layer KIND (sliding or
    full attention, dense or experts) is compiled once; and compiled AHEAD,
    on a thread, from shapes (:meth:`warm`), while the system's side of the
    checks holds the chip (``kimi_linear_48b_a3b_ep32_l5_reference.py`` has
    the readings that made it so, PR 40).  The same arithmetic as
    ``jax.value_and_grad`` of ``build(p)``'s loss
    (tests/benchmark/test_trinity_mini_cell.py holds them together)."""

    def __init__(self, p: dict):
        import concurrent.futures

        import jax
        import jax.numpy as jnp
        import optax

        forward = build(p)
        self.kinds, self.embed_scale = forward.kinds, forward.embed_scale

        def top(h, norm_f, head, labels):
            z = forward.logits(h, norm_f, head)
            return optax.softmax_cross_entropy_with_integer_labels(z, labels).mean(), z

        self.parts = {
            "top": jax.value_and_grad(top, argnums=(0, 1, 2), has_aux=True),
            "rows_summed": lambda g, tokens, like: jnp.zeros_like(like).at[tokens].add(g * forward.embed_scale),
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
        h = jax.ShapeDtypeStruct((batch, length, w["tok_emb"].shape[1]), jnp.float32)
        ids = jax.ShapeDtypeStruct((batch, length), jnp.int32)
        layers = list(zip(self.kinds, (w["blocks"][name] for name in sorted(w["blocks"]))))
        for kind, blk in layers:
            self._compiled("layer " + kind, h, blk)
        self._compiled("top", h, w["norm_f"], w["head"], ids)
        if gradient:
            for kind, blk in layers:
                self._compiled("layer_vjp " + kind, h, blk, h)
            self._compiled("rows_summed", h, ids, w["tok_emb"])

    def __call__(self, w, tokens, labels, gradient: bool = True):
        import jax.numpy as jnp

        names, seen, slots = sorted(w["blocks"]), [w["tok_emb"][tokens] * self.embed_scale], []
        for name, kind in zip(names, self.kinds):
            h, sent = self._run("layer " + kind, seen[-1], w["blocks"][name])
            seen.append(h)
            if sent is not None:
                slots.append(sent)
        (loss, z), (g, g_norm, g_head) = self._run("top", seen.pop(), w["norm_f"], w["head"], labels)
        out = (loss, (z, jnp.stack(slots)))
        if not gradient:
            return out, None
        grads = {"norm_f": g_norm, "head": g_head, "blocks": {}}
        for name, kind in reversed(list(zip(names, self.kinds))):
            g, grads["blocks"][name] = self._run("layer_vjp " + kind, seen.pop(), w["blocks"][name], g)
        grads["tok_emb"] = self._run("rows_summed", g, tokens, w["tok_emb"])
        return out, grads


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
    out["logits"] = relative_distance({"head": system["logits"]}, {"head": reference["logits"]})
    if "trained" in system:
        trained = system["trained"]
        out.update({f"grad_{g}": d for g, d in relative_distance(trained["gradient"], reference["gradient"], True).items()})
        out["adamw_update"] = trained["update"]
    return out


def warm_the_reference(config: dict, batch: int) -> None:
    """Start compiling the reference's parts for ``batch`` sequences, on
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
        control = os.environ.get("TRINITY_MINI_CONTROL", "")
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
        params = update_bias(params, slots, float(p["load_balance_coeff"]))
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
