"""Plain reference of the decoder the ``smallthinker_21b_a3b_ep8_l8``
configuration runs (SmallThinker-21BA3B-Instruct): float32 ``jax.numpy``,
matmul precision ``highest``, softmax attention under an EXPLICIT [L, L] mask
(a block of queries at a time against all keys, so that 16,384 rows fit: a
sliding layer's mask is ``p - W < j <= p``, a full layer's ``j <= p``), the
key/value heads read by index (no repeat), every held expert on every token
under the router's weights (a loop over the held experts) — no kernel, no
grouped matmul, no skipped block, and no code of ``elasticdl_tpu/ops/`` or
``elasticdl_tpu/models/`` (the reference takes ONE thing of the model:
``model_spec.init(key(0))``, whose weights are data here; the checks at the
end of this file run the model itself, as the thing measured).

A sequence of L tokens from the vocabulary slice; ``rmsnorm(x, g) = x *
rsqrt(mean(x^2) + eps) * g``; d = ``hidden_size``, H query heads over G
key/value heads of hd (a group of H / G = 7), W = ``sliding_window_size``;
HELD experts only (what the others would add is left out, here and in the
program alike):

    h  = E[x]
    layer i:
      u  = rmsnorm(h, attn_norm)
      r  = u Wr  [T, E] (float32)                                       THE ROUTER READS u, the rows the attention reads
      c  = top-k of r (best first, ties to the lower index) ; w = softmax(r[c])
      q  = u Wq [H, hd] ; k, v = u Wk, u Wv [G, hd]                     (no bias, no norm a head, no gate)
      rope_layout[i] = 1: q, k = rope(q), rope(k)                       (rotate-half over the whole head, theta; 0: NO position signal)
      head h reads key/value head h // (H / G) ; scores / sqrt(hd) ; softmax over j <= p (sliding_window_layout[i] = 0) or
                                                                    p - W < j <= p (1)
      h += o Wo
      v  = rmsnorm(h, ffn_norm)
      h += sum_{i: c_i held} w_i (relu(v Wgate[c_i]) * (v Wup[c_i])) Wdown[c_i]     the experts read v, are CHOSEN by u
    z  = rmsnorm(h, g_f) Whead ; loss = mean CE(z_i, x_{i+1})
    AdamW (0.9, 0.95, 1e-8; decay on the matrices alone), the rate raised linearly from 0

Departures from the published model are the configuration's ``assumed``
list.

It runs the first task (``minibatches_per_task`` steps, in order) from the
same initial weights as the system and reports the mean of the steps'
losses, which is what the worker reports for a task.  The warm-up's rate is
0 at the first update, so a step moves NO weight (this file refuses a
configuration of several steps a task without a warm-up).

Then, in the same process, a bare reading for each of the configuration's
``checks`` (``benchmark/run.py`` judges them against the limits in the
configuration's file), on the run's first minibatch from the initial weights.
The SYSTEM's side is the program itself, not a copy: the model's own
``spec.apply`` with its attention call, the router, the expert layer and the
norm tapped (:func:`taps_of_the_model`), and ``parallel/trainer.Trainer``'s
own train step (:func:`trained_by_the_program`).  Two kinds of reading:

- against this file's float32 model on float32 weights (the MECHANISM, every
  layer, forward and backward; reads the bfloat16 compute's noise):
  ``logits``, ``grad_<group>``;
- against float32 / float64 arithmetic on the operands THE SYSTEM ITSELF
  handed over: ``window_output`` / ``full_output`` (what the model's call of
  the attention returned against the masked softmax on the q, k, v it was
  handed, at L = 16,384), ``router_logits`` / ``router_choices_differing``
  (float64, of every layer's EARLY router, against the rows the model's
  ``attn_norm`` returned: the rows the attention read), ``expert_output`` (a
  layer's ``ops/moe.expert_ffn`` against a loop over the held experts under
  relu on its own operands), ``head_logits``, ``adamw_update``.

``SMALLTHINKER_CONTROL=<one of CONTROLS>`` in the child's environment swaps a
fault into the system's side (:func:`faults`), so that ``benchmark/run.py``
ends with ``correct`` false: how each limit was shown to catch what the
configuration's file says it catches.  The driver never sets it.
"""

from __future__ import annotations

import contextlib
import dataclasses
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
    "router_reads_v", "silu_for_relu", "full_for_window", "window_off_by_one", "rotary_on_full_layers", "rotary_off_sliding_layers",
    "bfloat16_router", "bfloat16_logits", "all_bfloat16", "no_weight_decay", "state_unchanged",
)
GROUPS = {
    "attention": ("wq", "wk", "wv", "wo"),
    "experts": ("w_gate", "w_up", "w_down"),
    "router": ("router",),
    "head": ("head",), "embedding": ("tok_emb",),
    "norms": ("attn_norm", "ffn_norm", "norm_f"),
}
NOT_DECAYED = GROUPS["norms"]
B1, B2, EPS = 0.9, 0.95, 1e-8
QUERY_BLOCK = 512


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


def kinds_of(p: dict) -> tuple:
    """Each layer's ``(slides, turns)``: whether its attention is under the window, whether q and k take the rotary turn."""
    windows = tuple(int(x) for x in p["sliding_window_layout"])
    turns = tuple(int(x) for x in (p.get("rope_layout") or windows))
    assert len(windows) == len(turns) == int(p["num_hidden_layers"]) and set(windows) | set(turns) <= {0, 1}
    return tuple(zip(windows, turns))


def held_experts(t, choices_weights, w_gate, w_up, w_down, lo: int, act):
    """``sum_i w[t, e] (act(t Wgate[e]) * (t Wup[e])) Wdown[e]`` over the HELD experts ``lo .. lo + n`` for the rows
    ``t`` [T, d], ``choices_weights`` [T, E] the router's weight of every expert for every token (0 where not chosen):
    a loop over the held experts, every one on every token; a slot on an absent expert adds nothing."""
    y = 0.0
    for e in range(w_up.shape[0]):
        hidden = act(t @ w_gate[e]) * (t @ w_up[e])
        y = y + (hidden @ w_down[e]) * choices_weights[:, lo + e, None]
    return y


def build(p: dict):
    """``forward(params, tokens) -> (logits [B, L, V] float32, slots [layers,
    E])`` for the model parameters ``p`` (the published keys), in the
    precision of the weights it is given."""
    import jax
    import jax.numpy as jnp

    eps, theta = float(p["rms_norm_eps"]), float(p["rope_theta"])
    heads, kv_heads, hd = int(p["num_attention_heads"]), int(p["num_key_value_heads"]), int(p["head_dim"])
    window, kinds = int(p["sliding_window_size"]), kinds_of(p)
    top_k, lo = int(p["moe_num_active_primary_experts"]), int(p.get("first_expert_held", 0))
    assert p.get("moe_primary_router_apply_softmax", True) and p.get("norm_topk_prob", True)

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g

    def attention(u, blk, kind):
        slides, turns = kind
        bsz, l, _ = u.shape
        q = (u @ blk["wq"]).reshape(bsz, l, heads, hd)
        k = (u @ blk["wk"]).reshape(bsz, l, kv_heads, hd)
        v = (u @ blk["wv"]).reshape(bsz, l, kv_heads, hd)
        if turns:  # a layer without the turn has NO position signal
            q, k = rotate(q, theta), rotate(k, theta)
        return masked_attention(q, k, v, window if slides else 0).reshape(bsz, l, -1) @ blk["wo"]

    def routing(u, blk):
        """``(m [T, E]: w at the chosen experts, 0 elsewhere; slots [E])`` of the rows ``u`` [B, L, d]."""
        r = (u.reshape(-1, u.shape[-1]) @ blk["router"]).astype(jnp.float32)
        chosen = jnp.argsort(-r, axis=-1, stable=True)[:, :top_k]  # [T, k], best first
        onehot = jax.nn.one_hot(chosen, r.shape[-1], dtype=jnp.float32)  # [T, k, E]
        w = jax.nn.softmax(jnp.take_along_axis(r, chosen, axis=-1), axis=-1)  # over the chosen alone
        return jnp.sum(onehot * w[:, :, None], 1), jnp.sum(onehot, (0, 1))

    def layer(h, blk, kind):
        u = rmsnorm(h, blk["attn_norm"])
        m, sent = routing(u, blk)  # the router reads u: BEFORE the attention
        h = h + attention(u, blk, kind)
        v = rmsnorm(h, blk["ffn_norm"])
        bsz, l, d = v.shape
        y = held_experts(v.reshape(bsz * l, d), m.astype(v.dtype), blk["w_gate"], blk["w_up"], blk["w_down"], lo, jax.nn.relu)
        return h + y.reshape(bsz, l, d), sent

    def logits(h, norm_f, head):
        return (rmsnorm(h, norm_f) @ head).astype(jnp.float32)

    def forward(params, tokens):
        h = params["tok_emb"][tokens]
        slots = []
        for name, kind in zip(sorted(params["blocks"]), kinds):
            h, sent = layer(h, params["blocks"][name], kind)
            slots.append(sent)
        return logits(h, params["norm_f"], params["head"]), jnp.stack(slots)

    # the parts, for a program that runs them one at a time
    forward.layer, forward.logits, forward.kinds, forward.embed_scale = layer, logits, kinds, 1.0
    return forward


def group_of(path, tree) -> str:
    """The group of :data:`GROUPS` a leaf's gradient is read in: by its name."""
    return next(g for g, names in GROUPS.items() if path[-1].key in names)


def decayed(params):
    """AdamW's weight-decay mask: the matrices alone."""
    import jax

    return jax.tree_util.tree_map_with_path(lambda path, _: path[-1].key not in NOT_DECAYED, params)


# ---- the configuration's checks: the SYSTEM's side, then the readings ----


def check_weights(params):
    """The weights every check runs from: the initial weights (this family has no correction bias to draw)."""
    return params


class _patched:
    """``module.name = value`` (or ``mapping[name] = value``) inside a ``with``."""

    def __init__(self, module, name, value):
        self.item = isinstance(module, dict)
        self.args, self.was = (module, name, value), module[name] if self.item else getattr(module, name)

    def _set(self, value):
        module, name, _ = self.args
        if self.item:
            module[name] = value
        else:
            setattr(module, name, value)

    def __enter__(self):
        self._set(self.args[2])

    def __exit__(self, *exc):
        self._set(self.was)


#: the turn the control ``rotary_on_full_layers`` gives the full layers (the configuration's ``rope_theta``)
CONTROL_THETA = 1.5e6


@contextlib.contextmanager
def faults(control: str):
    """The fault ``control`` names, in the PROGRAM, while a part of it is
    traced.  ``router_reads_v``: every layer's router reads the rows its
    EXPERTS read (``moe_lm._block`` is handed layers whose expert part routes
    on its own rows: the routing no longer crosses the attention).
    ``silu_for_relu``: the experts' gate passes silu (``ops/moe.ACTIVATIONS``).
    ``full_for_window``: the model's attention call loses its window (every
    layer full causal attention).  ``window_off_by_one``: a window one key too
    long, in whichever path runs — the flash kernels' far-edge mask keeps the
    key exactly a window back (``ops/flash_attention._seen``), the XLA path
    gets ``window + 1``.  ``rotary_on_full_layers``: the full layers' q and k
    take the rotary turn too, on their way into the attention (a position
    signal where the model has none).  ``rotary_off_sliding_layers``: no layer's
    q, k take the turn (``models/attentions.rope`` returns what it was given).
    ``bfloat16_router`` rounds the router's operands to bfloat16 on their way
    to ``ops/moe.route``; ``all_bfloat16`` is that and bfloat16 logits
    (:func:`system_under`).  The others swap nothing here."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import attentions, moe_lm
    from elasticdl_tpu.ops import flash_attention as flash_ops
    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import ring_attention as ring_ops

    assert control in ("",) + CONTROLS, f"SMALLTHINKER_CONTROL {control!r}: known are {CONTROLS}"
    attend, route, seen, plain, block = attentions.ring_attention, moe.route, flash_ops._seen, ring_ops.attention_reference, moe_lm._block
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
            q, k = attentions.rope(q, at, CONTROL_THETA), attentions.rope(k, at, CONTROL_THETA)
        return attend(q, k, v, **keys)

    def routed_late(x, blk, positions, layer, **keys):
        late = lambda part: dataclasses.replace(part, routes_on="") if part.routes_on else part  # noqa: E731
        return block(x, blk, positions, tuple((norm, late(part), *after) for norm, part, *after in layer), **keys)

    with contextlib.ExitStack() as stack:
        if control == "router_reads_v":
            stack.enter_context(_patched(moe_lm, "_block", routed_late))
        if control == "silu_for_relu":
            stack.enter_context(_patched(moe.ACTIVATIONS, "relu", jax.nn.silu))
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
        if control == "rotary_off_sliding_layers":
            stack.enter_context(_patched(attentions, "rope", lambda x, positions, theta: x))
        if control in ("bfloat16_router", "all_bfloat16"):
            stack.enter_context(_patched(moe, "route", lambda u, wg, k, **keys: route(rounded(u), rounded(wg), k, **keys)))
        yield


def system_under(control: str, p: dict) -> dict:
    """What a control swaps outside the traced program: ``params`` the model
    is built with, ``logits`` the model's logits pass through,
    ``state_unchanged`` for the train step."""
    import jax.numpy as jnp

    assert control in ("",) + CONTROLS, f"SMALLTHINKER_CONTROL {control!r}: known are {CONTROLS}"
    lower = control in ("bfloat16_logits", "all_bfloat16")
    return {
        "params": dict(p, **({"weight_decay": 0.0} if control == "no_weight_decay" else {})),
        "logits": (lambda z: z.astype(jnp.bfloat16).astype(jnp.float32)) if lower else (lambda z: z),
        "state_unchanged": control == "state_unchanged",
    }



def taps_of_the_model(spec, control: str = ""):
    """A compiled ``(params, tokens, labels) -> {"attention": [{"q", "k", "v",
    "o", "window"} a layer], "routers": [{"u", "logits", "choices"} a layer],
    "experts": [{"u", "choices", "weights", "y"} a layer], "norms": [what each
    call of the model's norm returned, in order: a layer's ``attn_norm`` rows,
    its ``ffn_norm`` rows, ..., the head's input], "logits": z}``: what the
    MODEL's own entry ``spec.apply`` (at the job's dtypes) hands its attention
    call (``models/attentions.ring_attention``: on the chip the flash kernels,
    under a window or full), ``ops/moe.route`` and ``ops/moe.expert_ffn`` in
    each layer and what it gets back, what its norm returned and its logits.
    The functions are tapped where the model looks them up (the modules'
    attributes) while ``apply`` is traced, and at no other time; a model that
    attends, routes, computes its experts or norms by another function hands
    them nothing.  The taps wrap the faults: a tap hears what the MODEL asked
    for (``window`` is what its call said, 0 for none) and reads what came back.
    Every tapped operand passes an ``optimization_barrier``: without it XLA
    hands the op a copy of the producer fused into the consumer at a higher
    precision than the array this program returns, and a reading against the
    RETURNED operands reads that difference (PERF.md, PR 40)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models import attentions, moe_lm
    from elasticdl_tpu.ops import moe

    def run(params, tokens, labels):
        attended, routed, computed, normed = [], [], [], []
        with faults(control):
            real_attend, real_route, real_experts, real_norm = attentions.ring_attention, moe.route, moe.expert_ffn, moe_lm._rms_norm

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

            def experts(u, choices, weights, *matrices, **keys):
                u, choices, weights = jax.lax.optimization_barrier((u, choices, weights))
                y, slots, given = real_experts(u, choices, weights, *matrices, **keys)
                computed.append({"u": u, "choices": choices, "weights": weights, "y": y})
                return y, slots, given

            def norm(*args):
                normed.append(jax.lax.optimization_barrier(real_norm(*args)))
                return normed[-1]

            with _patched(attentions, "ring_attention", attend), _patched(moe, "route", route), _patched(moe, "expert_ffn", experts), \
                    _patched(moe_lm, "_rms_norm", norm):
                # at jax's own default matmul precision, as the job runs; and
                # train=False: the same forward without the per-layer
                # jax.checkpoint, out of which the taps could not hand what they saw
                with jax.default_matmul_precision(None):
                    out = spec.apply(params, {"tokens": tokens, "labels": labels}, train=False)
        return {"attention": attended, "routers": routed, "experts": computed, "norms": normed, "logits": out["logits"]}

    return jax.jit(run)


@functools.lru_cache(maxsize=None)
def _attention_program():
    import jax
    import jax.numpy as jnp

    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    return jax.jit(lambda q, k, v, window: masked_attention(f32(q), f32(k), f32(v), window), static_argnums=3)


@functools.lru_cache(maxsize=None)
def _experts_program():
    """``(u, choices, weights, w_gate, w_up, w_down, lo) -> max |y - want| / max |want|`` of one expert layer: the
    float32 loop over the held experts under RELU on the layer's own rows, choices and weights, the matrices rounded
    to the rows' type as the model casts them."""
    import jax
    import jax.numpy as jnp

    def off(u, choices, weights, y, w_gate, w_up, w_down, n_experts, lo):
        f32 = lambda t: t.astype(u.dtype).astype(jnp.float32)  # noqa: E731
        m = jnp.sum(jax.nn.one_hot(choices, n_experts, dtype=jnp.float32) * weights[:, :, None], 1)
        want = held_experts(u.astype(jnp.float32), m, f32(w_gate), f32(w_up), f32(w_down), lo, jax.nn.relu)
        return jnp.max(jnp.abs(y.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want))

    return jax.jit(off, static_argnums=(7, 8))


def router_readings(u, wg, logits, choices, top_k: int) -> dict:
    """A router's float32 ``logits`` [T, E] and ``choices`` [T, k] against
    float64 on the host of the rows ``u`` — the rows the ATTENTION read — and
    the weight ``wg`` (the product, a stable sort of it, best first): the
    largest error of a logit relative to the largest logit, and the number of
    (token, rank) choices that differ from float64's."""
    want_r = np.asarray(u, np.float64).reshape(-1, wg.shape[0]) @ np.asarray(wg, np.float64)
    want_c = np.argsort(-want_r, axis=-1, kind="stable")[:, :top_k]
    return {
        "router_logits": float(np.abs(np.asarray(logits, np.float64) - want_r).max() / np.abs(want_r).max()),
        "router_choices_differing": int(np.sum(np.asarray(choices) != want_c)),
    }


def forward_readings(taps: dict, weights, p: dict) -> dict:
    """Bare readings of the forward pass the model ran (``taps``), each
    against this file's arithmetic on the OPERANDS THE MODEL HANDED OVER:

    - ``window_output`` / ``full_output``: the largest error of any sliding /
      full layer's ``o`` relative to that layer's largest ``|o|``, against the
      float32 softmax under the explicit mask of the window the model's call
      NAMED (none for a full layer), on the call's own q, k, v;
    - ``router_logits`` / ``router_choices_differing``: every layer's router
      as the model ran it against float64 of the rows the model's
      ``attn_norm`` returned in that layer (what the ATTENTION read: a router
      fed other rows shows) and the layer's float32 PARAMETER (a weight
      rounded on the way shows); the worst layer / their sum;
    - ``expert_output``: the largest error of any layer's expert result
      relative to its largest, against a float32 loop over the held experts
      under relu on the rows, choices and weights the layer was handed;
    - ``head_logits``: the largest error of the logits relative to the
      largest, against the head's input times the head's matrix rounded to
      the input's type, float32 at precision highest.

    A reading whose taps are empty (or fewer than the layers) is left out."""
    import jax
    import jax.numpy as jnp

    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    blocks = weights["blocks"]
    names, top_k = sorted(blocks), int(p["moe_num_active_primary_experts"])
    n_experts, lo = int(p["moe_num_primary_experts"]), int(p.get("first_expert_held", 0))
    out: dict = {}
    with jax.default_matmul_precision("highest"):
        if len(taps["attention"]) == len(blocks):
            for layer in taps["attention"]:
                window = int(layer["window"])
                want = _attention_program()(layer["q"], layer["k"], layer["v"], window)
                off = float(jnp.max(jnp.abs(f32(layer["o"]) - want)) / jnp.max(jnp.abs(want)))
                name = "window_output" if window else "full_output"
                out[name] = max(out.get(name, 0.0), off)
        if len(taps["routers"]) == len(names) and len(taps["norms"]) == 2 * len(names) + 1:
            each = [
                router_readings(read_by_attention, blocks[name]["router"], r["logits"], r["choices"], top_k)
                for name, r, read_by_attention in zip(names, taps["routers"], taps["norms"][0::2])
            ]
            out["router_logits"] = max(reading["router_logits"] for reading in each)
            out["router_choices_differing"] = sum(reading["router_choices_differing"] for reading in each)
        if len(taps["experts"]) == len(names):
            out["expert_output"] = max(
                float(_experts_program()(
                    e["u"], e["choices"], e["weights"], e["y"], *(jnp.asarray(blocks[name][w]) for w in ("w_gate", "w_up", "w_down")),
                    n_experts, lo))
                for name, e in zip(names, taps["experts"])
            )
        for a in taps["norms"][-1:]:
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
    window and full, forward and backward, the early routing, the grouped
    matmuls, AdamW with its mask and warm-up — what the job's worker compiles,
    one step a call) run TWICE on one minibatch from ``weights`` (a numpy
    tree): ``gradient`` (numpy), read off AdamW's first moment after the
    first step (``m = (1 - b1) g``; the warm-up's rate is 0 there, so the
    optimizer moves nothing); ``update``, the distance of the parameters'
    change after the second step from what AdamW written out in float32
    makes of the two steps' OWN gradients (the second read off the moments'
    change) under the CONFIGURATION's parameters ``p``, over the latter's
    size; ``loss`` the first step's."""
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
    assert warmup > 0, "the update's reading needs a warm-up (the first step's rate is 0: the optimizer moves nothing there)"
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
        # a leaf at a time ON THE DEVICE (the state stays there: a tree of 644 M floats is 2.6 GB of the host's 40)
        o, s = squares(w0, old, new_m, new, bool(dec), True)
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
    forward again: memory, not values) — so that a layer KIND (under the window or full,
    with the rotary turn or without) is compiled once; and compiled AHEAD,
    on a thread, from shapes (:meth:`warm`), while the system's side of the
    checks holds the chip (``kimi_linear_48b_a3b_ep32_l5_reference.py`` has
    the readings that made it so, PR 40).  The same arithmetic as
    ``jax.value_and_grad`` of ``build(p)``'s loss
    (tests/benchmark/test_smallthinker_cell.py holds them together)."""

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
        for kind in sorted(set(forward.kinds)):
            layer = functools.partial(forward.layer, kind=kind)
            self.parts[f"layer {kind}"] = layer
            self.parts[f"layer_vjp {kind}"] = lambda h, blk, g, layer=layer: jax.vjp(lambda h, blk: layer(h, blk)[0], h, blk)[1](g)
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
            self._compiled(f"layer {kind}", h, blk)
        self._compiled("top", h, w["norm_f"], w["head"], ids)
        if gradient:
            for kind, blk in layers:
                self._compiled(f"layer_vjp {kind}", h, blk, h)
            self._compiled("rows_summed", h, ids, w["tok_emb"])

    def __call__(self, w, tokens, labels, gradient: bool = True):
        import jax.numpy as jnp

        names, seen, slots = sorted(w["blocks"]), [w["tok_emb"][tokens] * self.embed_scale], []
        for name, kind in zip(names, self.kinds):
            h, sent = self._run(f"layer {kind}", seen[-1], w["blocks"][name])
            seen.append(h)
            slots.append(sent)
        (loss, z), (g, g_norm, g_head) = self._run("top", seen.pop(), w["norm_f"], w["head"], labels)
        out = (loss, (z, jnp.stack(slots)))
        if not gradient:
            return out, None
        grads = {"norm_f": g_norm, "head": g_head, "blocks": {}}
        for name, kind in reversed(list(zip(names, self.kinds))):
            g, grads["blocks"][name] = self._run(f"layer_vjp {kind}", seen.pop(), w["blocks"][name], g)
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
    out = {"forward": forward_readings(seen, weights, p)}
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
        control = os.environ.get("SMALLTHINKER_CONTROL", "")
        system = system_of_the_checks(config, first[:, :-1], first[:, 1:], control)
        result["control"] = control

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0)))
    program = _reference_program(json.dumps(p, sort_keys=True))
    losses = []
    for i in range(steps):
        batch = toks[i * mb : (i + 1) * mb]
        # the first updates' rate is 0 or next to it: the steps of a task are the forward pass at the initial weights
        (loss, _), _ = program(params, jnp.asarray(batch[:, :-1]), jnp.asarray(batch[:, 1:]), gradient=False)
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
