"""Decoder-only LM whose layers are lists of PARTS (``models/parts.py``): an
attention or a state-space mixer (``models/attentions.py``,
``models/mamba.py``) and a feed-forward that is dense or a dropless mixture
of experts layer by layer (here, beside the router's losses) — with or
without shared experts and a router correction bias — and a tied or untied
head.  ONE decoder: ``model_spec``'s arguments are the published keys, from
which it picks a FAMILY, whose builder turns its own keys into the layer
list that the one init, the one block and the counters all read.  The
defaults and parameter names are OLMoE's (``OLMoE-1B-7B-0125``: every layer
``moe``, 64 experts, 8 a token, softmax router, untied head);
``kv_lora_rank`` > 0 makes it DeepSeek-V3's block (``kanana-2-30b-a3b``),
``attention_class`` ``"eva"`` EvaByte's, ``hybrid_override_pattern``
``nemotron_h``'s, ``linear_attn_config`` ``kimi_linear``'s, ``sliding_window``
> 0 ``afmoe``'s (Trinity), ``sa_config`` ``KeyeVL2``'s (a learned indexer's
sparse attention; its own loss is a second term of the total),
``conv_L_cache`` > 0 ``lfm2_moe``'s (LFM2: a double-gated short convolution
in place of attention on most layers), ``block_length`` > 0 ``sdar_moe``'s
(SDAR: Qwen3-MoE's layer TRAINED BY DIFFUSION OVER BLOCKS — the first family
whose objective is not next-token cross-entropy and whose feed draws),
``sliding_window_layout`` ``smallthinker``'s (SmallThinker-21BA3B: full and
window attention layer by layer, relu-gated experts in every layer, and a
router that reads the rows the ATTENTION reads — the first family in which a
value crosses from one entry of a layer to a later one).

The model, with ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) * g``:

    x   = tok_emb[tokens]                                 (no position table; times sqrt(d) under ``mup_enabled``)
    for each layer, for each (norm, part) of it:  x += part(rmsnorm(x, norm))
                 (an entry ``(norm, part, norm after)``: x += rmsnorm(part(rmsnorm(x, norm)), norm after);
                  a part whose ``routes_on`` names an EARLIER entry's norm is handed what its ``route`` made of that
                  entry's normed rows, computed ahead of that entry's own part: ``_block``)
    logits = rmsnorm(x, norm_f) Whead                     (Whead = tok_emb^T when tied; float32 logits)
    loss = CE(logits, next token) + lb_coef * LB + z_coef * Z
    LB  = E * sum_{i, e} f[i, e] * P[e],   f[i, e] = share of (layer, token) pairs whose i-th choice is e,
                                           P[e] = mean over (layer, token) pairs of p[e]
    Z   = mean over (layer, token) pairs of logsumexp(r)^2

OLMoE's, DeepSeek-V3's and EvaByte's layers are ``(attn_norm, an
attention), (ffn_norm, a feed-forward)``; the feed-forwards, ``u`` the
normed stream:

    dense:  (silu(u Wgate) * (u Wup)) Wdown                      ``intermediate_size`` wide
    moe:    r = u Wg ; p = softmax(r) ; (w_i, e_i) = top-k of p   (float32; NOT renormalised)
            sum_i w_i * (silu(u Wgate[e_i]) * (u Wup[e_i])) Wdown[e_i]
    moe under the ``deepseek_v3`` keys (layers < ``first_k_dense_replace`` are dense):
            r = u Wg (float32) ; s = sigmoid(r)                  (``scoring_func``)
            e_1..e_k = top-k of (s + b)        b = the layer's correction bias (``topk_method`` noaux_tc):
                                               it chooses, it never weighs
            w_i = s[e_i] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor      (``norm_topk_prob``)
            sum_i w_i * expert_{e_i}(u) + shared(u)              experts ``moe_intermediate_size`` wide;
                                               shared = ONE gated MLP ``n_shared_experts`` times as wide
    after every step, by the model's own rule (``ModelSpec.after_update``; no gradient, no weight decay):
            b_e += bias_update_speed * sign(mean_e'(c_e') - c_e),   c_e = slots the step sent to expert e

Which experts are HELD (``experts_held`` of the router's ``num_experts``,
from ``first_expert_held`` on: one chip's share under expert parallelism):
the router keeps its width, the layer computes its own experts' part
(``ops/moe.expert_ffn``), and ``c_e`` counts over all of the router's.

``LB`` is ``transformers``' ``load_balancing_loss_func`` (the layers'
router outputs concatenated, one ``f`` and one ``P`` for the model); ``Z``
is the OLMoE paper's router z-loss.  ``apply`` returns what ``loss`` and
``metrics`` need — logits, ``f``, ``P``, ``Z``, the expert layers' slot
counts and the parts' step counters: a small pytree, no second forward.

EvaByte's keys besides its attention: the residual stream is float32
(``fp32_skip_add``: a part reads it cast to bfloat16 and its output is added
in float32), every norm multiplies by 1 + its gain (``norm_add_unit_offset``:
the gains start at 0), and the head is ``[d, num_pred_heads x vocab]``: head
p at position i predicts token i + 1 + p, the loss the mean over the heads
of each head's mean CE over the positions whose target the record holds.

With ``hybrid_override_pattern`` (``nemotron_h``'s keys) a layer is ONE
``(norm, part)``, its kind a letter of the pattern — ``M`` a Mamba-2 mixer,
``*`` grouped-query attention with no rotary turn, ``E`` a LatentMoE:

    E : r = u Wg (float32), s = sigmoid(r), top-k of s + b, w_i = scaling x s[e_i] / sum_j s[e_j]
        c = u W_down_lat                       [``moe_latent_size``]
        part = (sum_i w_i relu(c W1[e_i])^2 W2[e_i]) W_up_lat + relu(u Ws1)^2 Ws2     (``mlp_hidden_act`` relu2:
                                               experts and the shared expert are TWO matrices each)

With ``linear_attn_config`` (``kimi_linear``'s keys) a layer is DeepSeek-V3's
two parts, its mixer read off the config's two lists of layer NUMBERS (from
1): Kimi Delta Attention (``models/linear_attention.py``) on ``kda_layers``,
latent attention on ``full_attn_layers`` — with no rotary turn under
``mla_use_nope`` — and the feed-forward and router of the ``deepseek_v3``
block under this family's own spelling of the keys (``num_experts_per_token``,
``num_shared_experts``, ``moe_router_activation_func``, ``moe_renormalize``,
``use_grouped_topk`` / ``num_expert_group`` / ``topk_group``; always a
correction bias).

With ``sliding_window`` > 0 (``afmoe``'s keys: Trinity) a layer is attention and
a feed-forward, each BETWEEN two norms (``attn_norm`` / ``post_attn_norm``,
``ffn_norm`` / ``post_ffn_norm``):

    x  = tok_emb[tokens] * sqrt(d)                         (``mup_enabled``)
    x += rmsnorm(attention_i(rmsnorm(x, attn_norm)), post_attn_norm)
              attention_i (``models/attentions.GatedWindowAttention``): ``layer_types[i]`` is ``sliding_attention``
              (the ``sliding_window`` keys up to the query's own, rotary) or ``full_attention`` (every earlier key,
              NO position signal); H query heads over G key/value heads, a norm a head, a sigmoid gate on the output
    x += rmsnorm(ffn_i(rmsnorm(x, ffn_norm)), post_ffn_norm)
              ffn_i: dense for i < ``num_dense_layers``, else the ``deepseek_v3`` expert layer under this family's
              spelling (``score_func``, ``route_norm``, ``route_scale``, ``num_shared_experts``; always a correction
              bias, moved by ``load_balance_coeff``)

ONE collision of spellings, settled by the family's builder: this family PUBLISHES
``layer_types`` as each layer's ATTENTION kind, where ``model_spec(layer_types=...)``
otherwise names feed-forwards (``"moe"`` / ``"dense"``); under ``sliding_window`` the
key is read the published way and the feed-forwards follow from ``num_dense_layers``
(``first_k_dense_replace``'s role).

With ``conv_L_cache`` > 0 (``lfm2_moe``'s keys: LFM2) a layer is an OPERATOR and a
feed-forward, ``(operator_norm, operator), (ffn_norm, feed-forward)``:

    x += operator_i(rmsnorm(x, operator_norm))             eps = ``norm_eps``
              ``layer_types[i]`` ``conv`` (``models/gated_conv.GatedShortConv``): (B, C, z) = split3(u W_in), W_in [d, 3d];
              c = conv(B * z) (causal, depthwise, ``conv_L_cache`` taps a channel, no bias); (C * c) W_out: NO attention, NO
              recurrence, NO activation; or ``full_attention`` (``models/attentions.GatedWindowAttention`` without its gate):
              H query heads over G key/value heads of d / H, a norm a head THEN the rotary turn, every earlier key
    x += ffn_i(rmsnorm(x, ffn_norm))
              ffn_i: dense for i < ``num_dense_layers``, else the ``deepseek_v3`` expert layer under this family's spelling
              (a sigmoid router always; ``use_expert_bias``: the correction bias; ``norm_topk_prob``, ``routed_scaling_factor``;
              no shared expert)
    logits = rmsnorm(x, norm_f) tok_emb^T                  (``tie_word_embeddings``)

The THIRD reading of ``layer_types`` (beside the collision above): this family PUBLISHES
it as each layer's OPERATOR kind, ``conv`` / ``full_attention``; under ``conv_L_cache`` the
key is read that way and the feed-forwards follow from ``num_dense_layers``.

With ``block_length`` = g > 0 (``sdar_moe``'s keys: SDAR-30B-A3B; everything not in
its ``config.json`` from memory of ``modeling_sdar.py`` and of the SDAR, arXiv:2510.06303,
and BD3-LMs, arXiv:2503.09573, papers: there is no network here) the LAYER is
``KeyeVL2``'s less the indexer — ``x += attention(rmsnorm(x))``, ``x += moe(rmsnorm(x))``,
eps 1e-6, q, k, v, o without bias, H query heads over G key/value heads of an explicit
``head_dim``, an RMS norm a head on q and k THEN the rotate-half turn, scores / sqrt(head_dim);
a float32 softmax router, top-k renormalised (``norm_topk_prob``), gated-SiLU experts, no
shared expert, no bias, no auxiliary loss; an UNTIED head — and what is new is the
TRAINING RULE.  For a record's tokens ``x[0..L-1]`` the FEED (``data/codecs.block_diffusion_feed``,
on the host, from ``noise_seed`` and the record's bytes) draws ``t ~ U(0, 1)`` a sequence,
``p = (1 - 1e-3) t + 1e-3``, and masks each position independently with probability p:
``xt[i] = MASK if masked else x[i]``, MASK the slice's last id, ``vocab_size - 1`` (one t a
sequence is LLaDA's forward process, which SDAR's code is remembered to follow; one t a block
is BD3-LMs').  The batch is ``tokens`` (x), ``noisy_tokens`` (xt), ``p_mask`` (p) and:

    h      = tok_emb[[xt ; x]]                             the DOUBLED sequence: 2 L rows, both copies at the rotary positions 0..L-1
    attention, with b(i) = i // g:  noisy query i sees noisy key j iff b(j) == b(i), clean key j iff b(j) < b(i);
                                    clean query i sees clean key j iff b(j) <= b(i) and no noisy key
                                    (``models/attentions.block_diffusion_attention``: ``ops/flash_attention``'s fifth predicate, a static rule)
    logits = rmsnorm(h[:L], norm_f) Whead                  the NOISY copy's rows alone, float32
    loss   = (1 / (B L)) * sum over masked (b, i) of CE(logits[b, i], x[b, i]) / p[b]      the token AT the position: no shift

The metrics report that loss, the unweighed mean CE over the masked positions (``ce``) and the
masked share; both copies route (2 L positions a sequence through every expert layer).  A sharded
sequence raises.  The sampler (a block's tokens denoised over several steps against a cache of the
finished blocks) is serving work and is not here (ROADMAP R8).

With ``sliding_window_layout`` (``smallthinker``'s keys: SmallThinker-21BA3B-Instruct; what is not in its
``config.json`` from memory of its ``modeling_smallthinker.py`` and of arXiv:2507.20984: there is no network here) a
layer is ``(attn_norm, attention), (ffn_norm, experts)`` in EVERY layer — no dense layer, no shared expert — and the
router sits BEFORE the attention:

    u  = rmsnorm(x, attn_norm)                             eps = ``rms_norm_eps``
    r  = u Wr (float32) ; c = top-k of r ; w = softmax(r[c])       THE ROUTER READS u, the rows the attention reads
                                                           (= ``ops/moe.route``'s softmax over all E with the chosen renormalised)
    x += attention_i(u)                                    (``models/attentions.GatedWindowAttention`` without its gate and its
              norms a head): ``sliding_window_layout[i]`` 1 = the last ``sliding_window_size`` keys, 0 = every earlier key;
              ``rope_layout[i]`` 1 = q, k take the rotary turn, 0 = NO position signal; H query heads over G key/value heads
    v  = rmsnorm(x, ffn_norm)
    x += sum_i w_i (relu(v Wgate[c_i]) * (v Wup[c_i])) Wdown[c_i]          relu-GATED experts: they read v, are CHOSEN by u
    logits = rmsnorm(x, norm_f) Whead                      (untied)

What the heads and experts that are not held would add is left out; the
all-reduce of the head shares and the experts' exchange are not here.

Parallelism: as ``transformer_lm``'s sequence path.  ``batch_shard_dim=1``:
the mesh axis shards the SEQUENCE, attention runs over the ring
(``ops/ring_attention``), rotary positions are global (the device's axis
index times its chunk), parameters are replicated with psum'd gradients
(the AllReduce strategy).  Every device holds ALL experts and routes its
own tokens; ``f`` and ``P`` are averaged over the axis before they are
multiplied, so the load-balancing loss is the global one, and ``c_e`` is
summed over it.  The exchange of expert parallelism (experts sharded over
the mesh, tokens exchanged) is out of scope here: ROADMAP R7.

bfloat16 compute, float32 parameters; router, norms' statistics, rotary
arithmetic, logits and losses in float32.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import inspect
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from elasticdl_tpu.data.codecs import block_diffusion_feed, lm_feed
from elasticdl_tpu.models.attentions import (
    EvaAttention,
    GatedWindowAttention,
    GroupedQueryAttention,
    IndexedSparseAttention,
    LatentAttention,
    QKNormAttention,
)
from elasticdl_tpu.models.gated_conv import GatedShortConv
from elasticdl_tpu.models.linear_attention import KimiDeltaAttention
from elasticdl_tpu.models.mamba import MambaMixer
from elasticdl_tpu.models.parts import Draws, Part
from elasticdl_tpu.models.parts import rms_norm as _rms_norm  # looked up HERE by the block and the head (the references tap it)
from elasticdl_tpu.models.spec import ModelSpec
from elasticdl_tpu.ops import moe
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops.embedding import ParallelContext

#: The expert layers' counts a step reports (``ModelSpec.step_counters``:
#: summed over devices by the trainer and over steps by the worker), with
#: the help text of their gauges ``edl_moe_*_total``.
MOE_COUNTERS = {
    "moe_slots": "(token, expert) slots the routers filled, summed over "
    "expert layers, training steps and devices",
    "moe_slots_held": "slots the routers sent to experts held on the device "
    "(all of moe_slots where every expert is), summed likewise",
    "moe_slots_computed": "rows the experts' grouped matmuls ran (the sum of "
    "the held groups' sizes): equals moe_slots_held, or a slot was dropped",
    "moe_slots_overflow": "held slots that fell past the always-run row buffers "
    "and were computed by the second tier (ops/moe.SLACK), summed likewise",
    "moe_expert_load_max": "slots on a device's fullest held expert, summed "
    "over expert layers, training steps and devices",
    "moe_expert_load_mean": "slots on a device's average held expert, summed likewise",
}
LAYER_TYPES = ("moe", "dense")
#: ``layer_types`` as ``afmoe`` publishes it: each layer's ATTENTION
ATTENTION_LAYER_TYPES = ("sliding_attention", "full_attention")
#: ``layer_types`` as ``lfm2_moe`` publishes it: each layer's OPERATOR
OPERATOR_LAYER_TYPES = ("conv", "full_attention")
#: ``hybrid_override_pattern``'s letters (``-``, a dense MLP layer, is not one: no cell runs it)
PATTERN_KINDS = {"M": "a Mamba-2 mixer", "*": "attention", "E": "a latent mixture of experts"}
ATTENTION_CLASSES = ("mha", "eva")
TOPK_METHODS = ("greedy", "noaux_tc")

#: a layer: its ``(norm's parameter name, part)`` in the order they are applied; an entry may name a
#: second norm, ``(norm, part, norm after)``, applied to the part's OUTPUT before the residual add
Layer = Tuple[tuple, ...]


def _parts(layer: Layer):
    return [entry[1] for entry in layer]


def _gain(g, unit_offset: bool):
    """What a norm multiplies by: the gain, or 1 + it (``norm_add_unit_offset``)."""
    return 1.0 + g if unit_offset else g


def _gated_mlp(u, w_gate, w_up, w_down):
    with jax.named_scope("mlp"):
        # gate and up are save sites (ops/remat.py); silu and the product never
        return (jax.nn.silu(remat_lib.product("mlp_gate", u, w_gate)) * remat_lib.product("mlp_up", u, w_up)) @ w_down


def _relu2_mlp(u, w_up, w_down, site: str):
    with jax.named_scope("mlp"):
        # the first product is a save site (ops/remat.py); relu and the square never
        return jnp.square(jax.nn.relu(remat_lib.product(site, u, w_up))) @ w_down


@dataclasses.dataclass(frozen=True)
class Router:
    """What the parts that route share: a router ``n_experts`` wide that
    takes ``top_k`` a token by ``keys``; ``held`` experts from ``first_held``
    on are computed here.  ``keys`` are ``ops/moe.route``'s published keys,
    ONLY those that depart from its defaults (OLMoE's): OLMoE's call stays
    ``route(u, wg, k)``, which is also what the benchmark's tap of it
    (``olmoe_1b_7b_l1_reference.py``) wraps."""

    n_experts: int
    top_k: int
    held: int
    first_held: int
    keys: Tuple[Tuple[str, Any], ...] = ()


def _routed_stats(router: Router, routing, slots, given, pairs: int):
    """An expert layer's router sums and slot counts (``_apply`` adds them up)."""
    f, p, z = moe.router_stats(routing)
    slots = slots.astype(jnp.float32)
    sizes = slots[router.first_held:router.first_held + router.held]
    here = (routing.choices >= router.first_held) & (routing.choices < router.first_held + router.held)
    return {
        "f": f, "p": p, "z": z, "pairs": jnp.float32(pairs),
        "slots": slots,
        "moe_slots": jnp.float32(pairs * router.top_k),
        "moe_slots_held": jnp.sum(here.astype(jnp.float32)),
        "moe_slots_computed": (given.first + given.second).astype(jnp.float32),
        "moe_slots_overflow": given.second.astype(jnp.float32),
        "moe_expert_load_max": jnp.max(sizes),
        "moe_expert_load_mean": jnp.mean(sizes),
    }


@dataclasses.dataclass(frozen=True)
class GatedMLP(Part):
    """The dense feed-forward, ``width`` wide."""

    width: int

    def init(self, draw: Draws, d: int):
        return {"w_gate": draw.normal((d, self.width)), "w_up": draw.normal((d, self.width)), "w_down": draw.normal((self.width, d))}

    def apply(self, u, blk, positions, axis, cast):
        return _gated_mlp(u, cast(blk["w_gate"]), cast(blk["w_up"]), cast(blk["w_down"])), None


@dataclasses.dataclass(frozen=True)
class RoutedExperts(Part):
    """Gated experts ``width`` wide behind a router, with a correction bias
    that chooses (``correction_bias``) and ONE gated MLP ``shared_width``
    wide that every token passes (0: none).  The part is two pieces,
    :meth:`route` and the rest of :meth:`apply` ("compute what was routed"):
    with ``routes_on`` the block calls the first on an EARLIER entry's normed
    rows and hands the routing to the second (``_block``)."""

    router: Router
    width: int
    correction_bias: bool = False
    shared_width: int = 0
    #: scales the draw of the routed experts' ``w_down``, the matrix that writes into the stream (:data:`KEYE_VL2_INTO_STREAM`)
    into_stream: float = 1.0
    #: the gate's activation (a key of ``ops/moe.ACTIVATIONS``): a static argument of ``expert_ffn``
    activation: str = "silu"
    #: the norm's name of the EARLIER entry of the layer whose rows the router reads ("": the rows the experts read)
    routes_on: str = ""

    counters = MOE_COUNTERS
    routes = True

    def init(self, draw: Draws, d: int):
        e, held, f = self.router.n_experts, self.router.held, self.width
        blk = {"router": draw.normal((d, e))}
        if self.correction_bias:
            blk["router_bias"] = jnp.zeros((e,), jnp.float32)
        blk["w_gate"], blk["w_up"] = draw.normal((held, d, f)), draw.normal((held, d, f))
        blk["w_down"] = draw.normal((held, f, d), self.into_stream)
        if self.shared_width:
            blk["ws_gate"], blk["ws_up"] = draw.normal((d, self.shared_width)), draw.normal((d, self.shared_width))
            blk["ws_down"] = draw.normal((self.shared_width, d))
        return blk

    def route(self, u, blk):
        """The routing of the rows ``u`` [B, L, d] or [T, d] (``ops/moe.route``, under its ``moe_router`` scope)."""
        keys = dict(self.router.keys)
        if self.correction_bias:
            keys["bias"] = blk["router_bias"]
        return moe.route(u.reshape(-1, u.shape[-1]), blk["router"], self.router.top_k, **keys)

    def apply(self, u, blk, positions, axis, cast, routing=None):
        b, l, dim = u.shape
        tokens = u.reshape(b * l, dim)
        if routing is None:
            routing = self.route(tokens, blk)  # (the one view of the rows the experts read too: the older families' text)
        y, slots, given = moe.expert_ffn(
            tokens, routing.choices, routing.weights,
            cast(blk["w_gate"]), cast(blk["w_up"]), cast(blk["w_down"]),
            n_experts=self.router.n_experts, lo=self.router.first_held, activation=self.activation,
        )
        if self.shared_width:
            with jax.named_scope("moe_shared"):
                y = y + _gated_mlp(tokens, cast(blk["ws_gate"]), cast(blk["ws_up"]), cast(blk["ws_down"]))
        stats = _routed_stats(self.router, routing, slots, given, b * l)
        return y.reshape(b, l, dim), stats


@dataclasses.dataclass(frozen=True)
class LatentMoE(Part):
    """LatentMoE: the router (always with a correction bias) reads the
    token, the routed experts (two matrices ``width`` wide under relu
    squared) work in a latent ``latent`` wide between two projections, their
    weighted sum is taken IN the latent (linear: the same result as after
    ``w_lat_up``, a quarter of the rows' width); the shared expert,
    ``shared_width`` wide, reads the token.  ``into_stream`` scales the draws
    of the matrices that write into the stream or the latent
    (``rescale_prenorm_residual``)."""

    router: Router
    latent: int
    width: int
    shared_width: int
    into_stream: float = 1.0

    counters = MOE_COUNTERS
    routes = True
    correction_bias = True

    def init(self, draw: Draws, d: int):
        e, held, scale = self.router.n_experts, self.router.held, self.into_stream
        return {
            "router": draw.normal((d, e)), "router_bias": jnp.zeros((e,), jnp.float32),
            "w_lat_down": draw.normal((d, self.latent)), "w_lat_up": draw.normal((self.latent, d), scale),
            "w_up": draw.normal((held, self.latent, self.width)),
            "w_down": draw.normal((held, self.width, self.latent), scale),
            "ws_up": draw.normal((d, self.shared_width)), "ws_down": draw.normal((self.shared_width, d), scale),
        }

    def apply(self, u, blk, positions, axis, cast):
        b, l, dim = u.shape
        tokens = u.reshape(b * l, dim)
        routing = moe.route(tokens, blk["router"], self.router.top_k, bias=blk["router_bias"], **dict(self.router.keys))
        with jax.named_scope("moe_latent"):
            c = remat_lib.product("moe_latent_down", tokens, cast(blk["w_lat_down"]))
        y, slots, given = moe.expert_ffn(
            c, routing.choices, routing.weights, None, cast(blk["w_up"]), cast(blk["w_down"]),
            n_experts=self.router.n_experts, lo=self.router.first_held,
        )
        with jax.named_scope("moe_latent"):
            y = y @ cast(blk["w_lat_up"])
        with jax.named_scope("moe_shared"):
            y = y + _relu2_mlp(tokens, cast(blk["ws_up"]), cast(blk["ws_down"]), "shared_up")
        return y.reshape(b, l, dim), _routed_stats(self.router, routing, slots, given, b * l)


def _block(x, blk, positions, layer: Layer, *, axis, eps, compute_dtype, unit_offset=False):
    """One layer: for each ``(norm, part)`` of it the norm (times 1 + the
    gain where ``unit_offset``), the part, the residual add — behind a
    second norm where the entry names one.  ``x`` may be
    wider than ``compute_dtype`` (a float32 residual stream): a part reads
    it cast and its output is added in ``x``'s own type.  Returns (x, each
    part's stats — None for a part that counts nothing).

    An entry may hand a value to a LATER entry of the same layer: a part
    whose ``routes_on`` names this entry's norm has its :meth:`route` called
    on this entry's normed rows, ahead of this entry's own part in program
    order, and is given the routing when its turn comes (SmallThinker: the
    experts are CHOSEN by the rows the attention reads)."""
    cast = lambda w: w.astype(compute_dtype)  # noqa: E731
    stats, handed = [], {}
    for at, (norm, part, *after) in enumerate(layer):
        u = _rms_norm(cast(x), _gain(blk[norm], unit_offset), eps)
        for later in _parts(layer[at + 1:]):
            if later.routes_on == norm:
                handed[later] = {"routing": later.route(u, blk)}
        y, counted = part.apply(u, blk, positions, axis, cast, **handed.pop(part, {}))
        for norm_after in after:
            y = _rms_norm(y, _gain(blk[norm_after], unit_offset), eps)
        x = x + y.astype(x.dtype)
        stats.append(counted)
    return x, tuple(stats)


def _init(rng, *, layers: Sequence[Layer], draws_a_layer: int, vocab_size: int, hidden_size: int, init_std: float,
          tie_word_embeddings: bool, unit_offset: bool, num_pred_heads: int) -> Dict[str, Any]:
    """``tok_emb``, ``norm_f``, ``head`` (unless tied), then each layer's
    norms and parts in order, every matrix from the next key of ONE stream
    of 2 + ``draws_a_layer`` a layer.  ``unit_offset`` starts the layers'
    and the head's gains at 0 (the norms multiply by 1 + g)."""
    d = hidden_size
    draw = Draws(rng, 2 + draws_a_layer * len(layers), init_std)
    gain = jnp.zeros if unit_offset else jnp.ones
    params: Dict[str, Any] = {"tok_emb": draw.normal((vocab_size, d)), "norm_f": gain((d,), jnp.float32), "blocks": {}}
    if not tie_word_embeddings:
        params["head"] = draw.normal((d, num_pred_heads * vocab_size))
    for i, layer in enumerate(layers):
        blk: Dict[str, Any] = {}
        for norm, part, *after in layer:
            made = {name: gain((d,), jnp.float32) for name in (norm, *after)}
            made.update(part.init(draw, d))
            if set(made) & set(blk):
                raise ValueError(f"layer {i}: two parts name a parameter alike ({sorted(set(made) & set(blk))})")
            blk.update(made)
        # Zero-padded names keep sorted() in layer order past nine layers.
        params["blocks"][f"b{i:02d}"] = blk
    return params


def _apply(
    params, batch, train: bool = False, ctx: ParallelContext = ParallelContext(),
    *, layers: Sequence[Layer], compute_dtype, remat: bool, eps: float, unit_offset: bool = False,
    residual_dtype=None, num_pred_heads: int = 1, embed_scale: float = 1.0, block_length: int = 0,
):
    tokens = batch["tokens"]  # [B, L_local]: sequence-sharded over the axis
    l = tokens.shape[1]
    axis = ctx.axis_name
    offset = lax.axis_index(axis) * l if axis is not None else 0
    positions = offset + jnp.arange(l)
    if block_length:
        # diffusion over blocks: the body runs the DOUBLED sequence, the noisy copy's L rows and then the clean
        # copy's, both at the positions 0..L-1 (the attention's rule tells the copies apart by their rows)
        tokens, positions = jnp.concatenate([batch["noisy_tokens"], tokens], axis=1), jnp.concatenate([positions, positions])
    x = params["tok_emb"][tokens]
    if embed_scale != 1.0:
        x = x * embed_scale  # in the table's float32, ahead of the one downcast
    x = x.astype(residual_dtype or compute_dtype)
    names = sorted(params["blocks"])
    shared = dict(axis=axis, eps=eps, compute_dtype=compute_dtype, unit_offset=unit_offset)
    blocks = [functools.partial(_block, layer=layer, **shared) for layer in layers]
    if remat and train:
        # Every block rematerialised, each keeping what the byte budget the
        # trainer resolved gives it (ops/remat.py; 0 = nothing, as ever).
        blocks = remat_lib.plan(blocks, [(x, params["blocks"][name], positions) for name in names], ctx.remat_keep_bytes)
    routed, plain = [], {}  # the routing parts' stats; the other parts' counts by name: both in layer order
    for name, layer, block in zip(names, layers, blocks):
        x, stats = block(x, params["blocks"][name], positions)
        for part, given in zip(_parts(layer), stats):
            if part.routes:
                routed.append(given)
            else:
                for key, value in (given or {}).items():
                    plain.setdefault(key, []).append(value)
    if block_length:
        x = x[:, :l]    # the head reads the NOISY copy's rows alone: the clean copy is there to be attended
    with jax.named_scope("lm_head"):
        norm_f = _gain(params["norm_f"], unit_offset)
        x = _rms_norm(x.astype(compute_dtype), norm_f, eps)
        head = params["head"] if "head" in params else params["tok_emb"].T
        logits = jnp.dot(x, head.astype(compute_dtype), preferred_element_type=jnp.float32)
        if num_pred_heads > 1:  # [B, L, heads of prediction, vocabulary]
            logits = logits.reshape(logits.shape[:2] + (num_pred_heads, -1))
    out = {"logits": logits}
    of_shapes = collections.Counter()
    for layer in layers:
        for part in _parts(layer):
            of_shapes.update(part.shape_counts(tokens.shape[0], l))
    counters = {key: jnp.float32(n) for key, n in of_shapes.items()}
    if "indexer_loss" in plain:  # a learned indexer's own loss, every layer's (``_terms`` adds it to the total)
        out["indexer_loss"] = sum(plain.pop("indexer_loss"))
    counters.update({key: sum(values) for key, values in plain.items()})  # all layers
    if counters:
        out["counters"] = counters
    if routed:
        slots = jnp.stack([stats.pop("slots") for stats in routed])  # [expert layers, E]
        total = jax.tree.map(lambda *leaves: sum(leaves), *routed)
        with jax.named_scope("moe_router"):
            # Shares over every (layer, token) pair of the GLOBAL batch: the
            # load-balancing loss multiplies two means, so they are taken
            # over the axis before the product, not after.  The slots an
            # expert was sent (the correction bias's rule reads them) are
            # summed over the devices that share the layer.
            f, p, z, pairs = (total[key] for key in ("f", "p", "z", "pairs"))
            if axis is not None:
                # Trace-time import, as transformer_lm's: a module-level one
                # closes the ops -> parallel -> ops import cycle.
                from elasticdl_tpu.parallel.collectives import psum

                f, p, z, pairs, slots = (psum(t, axis) for t in (f, p, z, pairs, slots))
            out["router"] = {"f": f / pairs, "p": p / pairs, "z": z / pairs}
            out["router_slots"] = slots
        out["moe_counters"] = {key: total[key] for key in MOE_COUNTERS}
    return out


def _update_correction_bias(params, out, *, layers: Sequence[Layer], speed: float):
    """The model's own rule for the routers' correction biases
    (``ModelSpec.after_update``; DeepSeek-V3, arXiv:2412.19437, section
    2.1.2): after a step, an expert that was sent more slots than the mean
    of its layer has its bias lowered by ``speed``, one that was sent fewer
    has it raised.  Each expert layer has its own bias and its own counts."""
    blocks = dict(params["blocks"])
    routed = [name for name, layer in zip(sorted(blocks), layers) if any(part.routes for part in _parts(layer))]
    for name, slots in zip(routed, out["router_slots"]):  # both in layer order
        step = speed * jnp.sign(jnp.mean(slots) - slots)
        blocks[name] = {**blocks[name], "router_bias": blocks[name]["router_bias"] + step}
    return {**params, "blocks": blocks}


def _cross_entropy(out, batch):
    with jax.named_scope("lm_head"):
        logits, labels = out["logits"], batch["labels"]
        if logits.ndim == 3:
            return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(logits, labels))
        # Several heads of prediction: head p at position i is held to
        # labels[i + p] (token i + 1 + p) where the record has it; each
        # head's mean over its own positions, the heads weighed alike.
        # (A record shorter than the heads reach leaves the far heads out.)
        l, n_heads = logits.shape[1], min(logits.shape[1:3])
        ahead = jnp.arange(l)[:, None] + jnp.arange(n_heads)[None, :]          # [L, P]
        targets = jnp.take(labels, jnp.minimum(ahead, l - 1), axis=1)          # [B, L, P]
        ce = optax.softmax_cross_entropy_with_integer_labels(logits[:, :, :n_heads], targets)
        held = (ahead < l).astype(jnp.float32)
        return jnp.mean(jnp.sum(ce * held, (0, 1)) / (logits.shape[0] * jnp.sum(held, 0)))


#: the token counts a step of diffusion over blocks reports (``ModelSpec.step_counters``; gauges ``edl_bd_tokens*_total``)
BD_TOKEN_COUNTERS = {
    "bd_tokens_masked": "positions of the noisy copy that the feed masked (the positions the loss is taken over), summed over "
    "training steps and devices",
    "bd_tokens": "positions of the clean sequences (B x L a step), summed likewise",
}


def _masked_cross_entropy(out, batch, *, mask_token: int):
    """``(the loss of diffusion over blocks, the unweighed mean CE over the masked positions, the masked positions'
    count)``: logits on the noisy copy's rows, the target the token AT the position, the masked positions alone, each
    sequence weighed by 1 / its noise level: ``(1 / (B L)) sum over masked (b, i) of CE(logits[b, i], x[b, i]) / p[b]``."""
    with jax.named_scope("lm_head"):
        logits, tokens = out["logits"], batch["tokens"]
        masked = (batch["noisy_tokens"] == mask_token).astype(jnp.float32)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, tokens) * masked
        count = jnp.sum(masked)
        weighed = jnp.sum(jnp.sum(ce, axis=1) / batch["p_mask"].astype(jnp.float32)) / masked.size
        return weighed, jnp.sum(ce) / jnp.maximum(count, 1.0), count


def _router_losses(out):
    """(LB, Z) of the module docstring; zeros for a model without experts."""
    router = out.get("router")
    if router is None:
        return jnp.float32(0.0), jnp.float32(0.0)
    with jax.named_scope("moe_router"):
        n_experts = router["p"].shape[0]
        # f comes from a top-k: no gradient flows through it, as in
        # transformers' one-hot mask.
        lb = n_experts * jnp.sum(lax.stop_gradient(router["f"]) * router["p"][None, :])
        return lb, router["z"]


#: what the layers' indexer losses weigh in the total (looked up HERE by ``_terms`` at every trace: the references' control zeroes it)
INDEXER_LOSS_COEF = 1.0


def _terms(out, batch, lb_coef: float, z_coef: float, mask_token: Optional[int] = None, ce=None):
    """(the total the optimizer descends, CE, LB, Z).  ``mask_token``: the model is trained by diffusion over blocks,
    and its CE is :func:`_masked_cross_entropy`'s weighed one (``ce``: that term, where the caller has it).  With both
    coefficients 0 (a model trained on CE alone) the total IS the CE — plus,
    for a model with a learned indexer, the layers' indexer losses
    (``out["indexer_loss"]``: a SECOND loss, whose gradient reaches the
    indexers' parameters and no other, as the CE's reaches none of them)."""
    if ce is None:
        ce = _cross_entropy(out, batch) if mask_token is None else _masked_cross_entropy(out, batch, mask_token=mask_token)[0]
    lb, z = _router_losses(out)
    total = ce if not lb_coef and not z_coef else ce + lb_coef * lb + z_coef * z
    if "indexer_loss" in out:
        total = total + INDEXER_LOSS_COEF * out["indexer_loss"]
    return total, ce, lb, z


def _loss(out, batch, lb_coef: float, z_coef: float, mask_token: Optional[int] = None):
    return _terms(out, batch, lb_coef, z_coef, mask_token)[0]


def _metrics(out, batch, lb_coef: float, z_coef: float):
    # ``loss`` is the total (the train step reports its own, equal, value
    # under the same key).
    loss, ce, lb, z = _terms(out, batch, lb_coef, z_coef)
    logits = out["logits"] if out["logits"].ndim == 3 else out["logits"][:, :, 0]  # the next token's head
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
    metrics = {"loss": loss, "ce": ce, "lb_loss": lb, "z_loss": z, "accuracy": acc}
    if "indexer_loss" in out:
        metrics["indexer_loss"] = out["indexer_loss"]
    metrics.update(out.get("moe_counters", {}))
    metrics.update(out.get("counters", {}))
    return metrics


def _diffusion_metrics(out, batch, lb_coef: float, z_coef: float, mask_token: int):
    """A model trained by diffusion over blocks: ``loss`` (the total: the weighed CE), ``ce`` the unweighed mean CE
    over the masked positions, ``masked_share`` and ``accuracy`` over the masked positions, the counters."""
    weighed, ce, count = _masked_cross_entropy(out, batch, mask_token=mask_token)
    loss, _, lb, z = _terms(out, batch, lb_coef, z_coef, ce=weighed)
    masked = batch["noisy_tokens"] == mask_token
    hit = jnp.sum(((jnp.argmax(out["logits"], -1) == batch["tokens"]) & masked).astype(jnp.float32))
    metrics = {
        "loss": loss, "ce": ce, "lb_loss": lb, "z_loss": z, "accuracy": hit / jnp.maximum(count, 1.0),
        "masked_share": count / masked.size, "bd_tokens_masked": count, "bd_tokens": jnp.float32(masked.size),
    }
    metrics.update(out.get("moe_counters", {}))
    metrics.update(out.get("counters", {}))
    return metrics


def _predict(params, batch, ctx: ParallelContext = ParallelContext(), *, apply):
    return apply(params, batch, train=False, ctx=ctx)["logits"]


def _example_batch(batch_size: int, seq_len: int):
    return {
        "tokens": jnp.zeros((batch_size, seq_len), jnp.int32),
        "labels": jnp.zeros((batch_size, seq_len), jnp.int32),
    }


def _diffusion_example_batch(batch_size: int, seq_len: int):
    """What :func:`block_diffusion_feed` returns, in shapes."""
    ids = jnp.zeros((batch_size, seq_len), jnp.int32)
    return {"tokens": ids, "noisy_tokens": ids, "p_mask": jnp.ones((batch_size,), jnp.float32)}


#: Leaves AdamW never decays: the correction biases; and, for a model that
#: decays its matrices alone (``decay_matrices_only``), the gains and EVA's vectors.
_NEVER_DECAYED = ("router_bias",)
_NOT_MATRICES = _NEVER_DECAYED + (
    "attn_norm", "ffn_norm", "norm_f", "kv_norm", "q_norm", "k_norm", "eva_phi", "eva_mu",
    "norm", "ssm_norm", "A_log", "D", "dt_bias", "conv_b", "kda_norm", "post_attn_norm", "post_ffn_norm",
    "idx_norm", "idx_norm_bias", "operator_norm", "gconv_taps",
)


def _is_decayed(params, skip=_NEVER_DECAYED):
    """AdamW's weight-decay mask: every leaf but those named in ``skip``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in skip, params
    )



def _head_width(hidden_size: int, num_attention_heads: int) -> int:
    if hidden_size % num_attention_heads or (hidden_size // num_attention_heads) % 2:
        raise ValueError(
            f"hidden_size {hidden_size} must split into {num_attention_heads} heads "
            f"of even width (rotary pairs)"
        )
    return hidden_size // num_attention_heads


# A family's builders: each takes the published keys it names (``model_spec``
# hands them over and notes them as read) and checks their ranges.

def _qk_norm_attention(*, hidden_size, num_attention_heads, rope_theta, rms_norm_eps):
    _head_width(hidden_size, num_attention_heads)
    return QKNormAttention(num_attention_heads, float(rope_theta), float(rms_norm_eps))


def _latent_attention(
    *, num_attention_heads, kv_lora_rank, q_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    rope_interleave, rope_theta, rms_norm_eps, mla_use_nope,
):
    if q_lora_rank is not None:
        raise ValueError("a low-rank query projection (q_lora_rank) is not supported: no cell runs one")
    if min(qk_nope_head_dim, v_head_dim) <= 0 or qk_rope_head_dim <= 0 or qk_rope_head_dim % 2:
        raise ValueError(
            f"latent attention needs qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim, got "
            f"{qk_nope_head_dim} / {v_head_dim} / {qk_rope_head_dim}"
        )
    return LatentAttention(
        num_attention_heads, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
        float(rope_theta), float(rms_norm_eps), bool(rope_interleave), rotary=not mla_use_nope,
    )


#: ``KeyeVL2``'s init of the two matrices that write a branch into the stream (the attention's ``wo``, the routed experts'
#: ``w_down``), as a share of the other matrices' scale: a STAND-IN, found on the chip (PERF.md section 6, PR 58).  An
#: untrained attention that is near-uniform over thousands of keys keeps what a sequence's tokens share; written back at the
#: other matrices' scale it outgrows the stream layer on layer until every token of a sequence routes alike.
KEYE_VL2_INTO_STREAM = 0.01


def _indexed_sparse_attention(*, sa_config, num_attention_heads, num_key_value_heads, head_dim, rope_theta, rms_norm_eps):
    """``KeyeVL2``'s attention from ``sa_config`` (the published group, every key of it read)."""
    config = dict(sa_config)
    known = {"indexer_head_dim", "indexer_num_heads", "indexer_num_kv_heads", "kv_chunk_size", "q_chunk_size", "topk"}
    if set(config) != known:
        raise ValueError(f"sa_config: its keys are {sorted(known)}, got {sorted(config)}")
    heads, e, topk, chunk = (int(config[key]) for key in ("indexer_num_heads", "indexer_head_dim", "topk", "q_chunk_size"))
    if int(config["indexer_num_kv_heads"]) != 1 or int(config["kv_chunk_size"]) != chunk:
        raise ValueError("sa_config: ONE index key a position (indexer_num_kv_heads 1) and kv_chunk_size = q_chunk_size (no cell runs another)")
    kv_heads = num_key_value_heads or num_attention_heads
    if min(heads, e, topk, chunk) <= 0 or e % 2 or num_attention_heads % kv_heads or head_dim <= 0 or head_dim % 2:
        raise ValueError(
            f"sa_config {config}: positive sizes and an even indexer_head_dim (rotary pairs); {num_attention_heads} query heads "
            f"over {kv_heads} key/value heads of head_dim {head_dim} (even)"
        )
    return IndexedSparseAttention(
        num_attention_heads, kv_heads, head_dim, float(rope_theta), float(rms_norm_eps), heads, e, topk, chunk, KEYE_VL2_INTO_STREAM)


def _keye_vl2_layers(own, attention=_indexed_sparse_attention, draws=12):  # 7 of the attention, 4 of the experts
    layers, draws = _two_part_layers(own(attention), own, draws=draws)
    into = lambda part: dataclasses.replace(part, into_stream=KEYE_VL2_INTO_STREAM) if isinstance(part, RoutedExperts) else part  # noqa: E731
    return tuple((attention, (norm, into(feed_forward))) for attention, (norm, feed_forward) in layers), draws


def _block_diffusion_attention(*, block_length, num_attention_heads, num_key_value_heads, head_dim, rope_theta, rms_norm_eps):
    """``sdar_moe``'s attention: ``keye_vl2``'s less the indexer — fewer key/value heads of an explicit ``head_dim``, a norm
    a head THEN the rotary turn, ``wo`` at the same stand-in scale — under the rule of diffusion over blocks of ``block_length``."""
    kv_heads = num_key_value_heads or num_attention_heads
    if num_attention_heads % kv_heads or head_dim <= 0 or head_dim % 2:
        raise ValueError(f"{num_attention_heads} query heads over {kv_heads} key/value heads of head_dim {head_dim} (even: rotary pairs)")
    return GatedWindowAttention(
        num_attention_heads, kv_heads, head_dim, 0, float(rope_theta), float(rms_norm_eps), gate=False, rotary=True,
        block_length=int(block_length), into_stream=KEYE_VL2_INTO_STREAM,
    )


def _eva_attention(*, hidden_size, num_attention_heads, heads_held, window_size, chunk_size, rope_theta):
    head_dim = _head_width(hidden_size, num_attention_heads)
    if chunk_size <= 0 or window_size <= 0 or window_size % chunk_size:
        raise ValueError(f"EVA attention needs a window_size in whole chunks, got {window_size} / {chunk_size}")
    if not 0 <= heads_held <= num_attention_heads:
        raise ValueError(f"heads_held {heads_held} of {num_attention_heads} heads")
    return EvaAttention(heads_held or num_attention_heads, head_dim, float(rope_theta), window_size, chunk_size)


def _router(
    *, num_experts, num_experts_per_tok, experts_held, first_expert_held, scoring_func, norm_topk_prob,
    routed_scaling_factor, topk_method, n_group, topk_group,
):
    """(the :class:`Router` of the family's expert layers, whether it has a correction bias)."""
    if num_experts_per_tok > num_experts:
        raise ValueError(f"top-{num_experts_per_tok} of {num_experts} experts")
    if (n_group, topk_group) != (1, 1):
        raise ValueError(f"group-limited routing (n_group {n_group}, topk_group {topk_group}) is not supported: no cell runs it")
    if topk_method not in TOPK_METHODS or scoring_func not in moe.SCORING_FUNCS:
        raise ValueError(
            f"topk_method {topk_method!r} / scoring_func {scoring_func!r}: known are "
            f"{TOPK_METHODS} / {moe.SCORING_FUNCS}"
        )
    held = experts_held or num_experts
    if not 0 <= first_expert_held <= num_experts - held:
        raise ValueError(f"experts [{first_expert_held}, {first_expert_held + held}) are not among the router's {num_experts}")
    keys = tuple(
        (key, value)
        for key, value, default in (
            ("scoring_func", scoring_func, "softmax"),
            ("norm_topk_prob", bool(norm_topk_prob), False),
            ("routed_scaling_factor", float(routed_scaling_factor), 1.0),
        )
        if value != default
    )
    return Router(num_experts, num_experts_per_tok, held, first_expert_held, keys), topk_method == "noaux_tc"


def _gated_feed_forwards(
    router, correction_bias,
    *, num_hidden_layers, layer_types, first_k_dense_replace, intermediate_size, moe_intermediate_size, n_shared_experts,
):
    """(each layer's feed-forward, the keys a layer takes of the stream: OLMoE's
    block draws 8, one with shared experts has always been given 12)."""
    if layer_types is None:
        dense = min(first_k_dense_replace, num_hidden_layers)
        layer_types = ("dense",) * dense + ("moe",) * (num_hidden_layers - dense)
    layer_types = tuple(layer_types)
    if len(layer_types) != num_hidden_layers or set(layer_types) - set(LAYER_TYPES):
        raise ValueError(
            f"layer_types must name {num_hidden_layers} layers from {LAYER_TYPES}, "
            f"got {layer_types!r}"
        )
    width = moe_intermediate_size or intermediate_size
    kinds = {"moe": RoutedExperts(router, width, correction_bias, n_shared_experts * width), "dense": GatedMLP(intermediate_size)}
    return [kinds[kind] for kind in layer_types], 12 if n_shared_experts else 8


def _two_part_layers(attention, own, draws=0):
    feed_forwards, needed = own(functools.partial(_gated_feed_forwards, *own(_router)))
    return tuple((("attn_norm", attention), ("ffn_norm", feed_forward)) for feed_forward in feed_forwards), max(draws, needed)


def _nemotron_h_layers(
    router, _,
    *, hybrid_override_pattern, num_hidden_layers, hidden_size, num_attention_heads, seq_len, rms_norm_eps,
    mlp_hidden_act, topk_method, scoring_func, tie_word_embeddings,
    mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size, conv_kernel, chunk_size,
    time_step_min, time_step_max, time_step_floor, mamba_heads_held,
    num_key_value_heads, head_dim, heads_held, kv_heads_held,
    moe_latent_size, moe_intermediate_size, moe_shared_expert_intermediate_size,
    rescale_prenorm_residual, residual_layers,
):
    pattern = str(hybrid_override_pattern)
    if len(pattern) != num_hidden_layers or set(pattern) - set(PATTERN_KINDS):
        raise ValueError(
            f"hybrid_override_pattern must give {num_hidden_layers} layers a letter of {sorted(PATTERN_KINDS)} "
            f"({PATTERN_KINDS}), got {pattern!r}"
        )
    if mlp_hidden_act != "relu2" or topk_method != "noaux_tc" or scoring_func != "sigmoid" or tie_word_embeddings:
        raise ValueError(
            "a hybrid_override_pattern model is nemotron_h's: mlp_hidden_act 'relu2', a sigmoid router with a "
            f"correction bias (topk_method 'noaux_tc') and an untied head; got {mlp_hidden_act!r} / {scoring_func!r} / "
            f"{topk_method!r} / tie_word_embeddings {tie_word_embeddings}"
        )
    m_held, q_held = mamba_heads_held or mamba_num_heads, heads_held or num_attention_heads
    kv_all = num_key_value_heads or num_attention_heads
    kv_held = kv_heads_held or kv_all
    if "M" in pattern:
        per_group = mamba_num_heads // max(n_groups, 1)
        if min(mamba_num_heads, mamba_head_dim, ssm_state_size, chunk_size, conv_kernel) <= 0 or mamba_num_heads % n_groups:
            raise ValueError(
                f"a Mamba-2 layer needs mamba_num_heads in whole n_groups, mamba_head_dim, ssm_state_size, conv_kernel "
                f"and chunk_size, got {mamba_num_heads} / {n_groups} / {mamba_head_dim} / {ssm_state_size} / {conv_kernel} / {chunk_size}"
            )
        if not 0 < m_held <= mamba_num_heads or m_held % per_group:
            raise ValueError(f"mamba_heads_held {m_held} of {mamba_num_heads}: whole groups of {per_group} heads (the gated norm's)")
        if seq_len % chunk_size:
            raise ValueError(f"seq_len {seq_len} is not whole chunks of {chunk_size}: the chunked scan needs them")
    if "*" in pattern and (
        num_attention_heads % kv_all or not 0 < q_held <= num_attention_heads or not 0 < kv_held <= kv_all
        or q_held % kv_held or (num_attention_heads // kv_all) % (q_held // kv_held)
    ):
        raise ValueError(
            f"{q_held} of {num_attention_heads} query heads on {kv_held} of {kv_all} key/value heads: a share's "
            f"query heads sit evenly on its key/value heads, a divisor of the published {num_attention_heads // max(kv_all, 1)} on each"
        )
    if "E" in pattern and min(moe_latent_size, moe_intermediate_size, moe_shared_expert_intermediate_size) <= 0:
        raise ValueError("a LatentMoE layer needs moe_latent_size, moe_intermediate_size and moe_shared_expert_intermediate_size")
    width = _head_width(hidden_size, num_attention_heads)
    # the matrices that write into the stream are drawn smaller by the depth's root
    into_stream = ((residual_layers or num_hidden_layers) if rescale_prenorm_residual else 1) ** -0.5
    kinds = {
        "M": MambaMixer(
            m_held, mamba_head_dim, m_held * n_groups // mamba_num_heads if mamba_num_heads else 0, ssm_state_size,
            conv_kernel, chunk_size, float(rms_norm_eps), (time_step_min, time_step_max, time_step_floor), into_stream,
        ),
        "*": GroupedQueryAttention(q_held, kv_held, head_dim or width, into_stream),
        "E": LatentMoE(router, moe_latent_size, moe_intermediate_size, moe_shared_expert_intermediate_size, into_stream),
    }
    return tuple((("norm", kinds[letter]),) for letter in pattern), 8  # keys of the stream a layer: as the family always split it


def _kimi_linear_router(
    *, num_experts, num_experts_per_token, experts_held, first_expert_held, moe_router_activation_func, moe_renormalize,
    routed_scaling_factor, use_grouped_topk, num_expert_group, topk_group,
):
    """``kimi_linear``'s spelling of the router's keys, mapped onto :func:`_router`'s: always a
    correction bias (``e_score_correction_bias``); ``use_grouped_topk`` with one group of which
    one is taken limits nothing."""
    if not use_grouped_topk and (num_expert_group, topk_group) != (1, 1):
        raise ValueError(f"num_expert_group {num_expert_group} / topk_group {topk_group} without use_grouped_topk")
    return _router(
        num_experts=num_experts, num_experts_per_tok=num_experts_per_token, experts_held=experts_held,
        first_expert_held=first_expert_held, scoring_func=moe_router_activation_func, norm_topk_prob=moe_renormalize,
        routed_scaling_factor=routed_scaling_factor, topk_method="noaux_tc", n_group=num_expert_group, topk_group=topk_group,
    )


def _kimi_linear_feed_forwards(
    router, correction_bias,
    *, num_hidden_layers, first_k_dense_replace, moe_layer_freq, intermediate_size, moe_intermediate_size, num_shared_experts,
):
    if moe_layer_freq != 1:
        raise ValueError(f"moe_layer_freq {moe_layer_freq}: every layer after the leading dense ones is an expert layer (no cell runs another)")
    return _gated_feed_forwards(
        router, correction_bias, num_hidden_layers=num_hidden_layers, layer_types=None, first_k_dense_replace=first_k_dense_replace,
        intermediate_size=intermediate_size, moe_intermediate_size=moe_intermediate_size, n_shared_experts=num_shared_experts,
    )[0]


def _kimi_linear_layers(latent_attention, feed_forwards, *, linear_attn_config, num_hidden_layers, rms_norm_eps):
    config = dict(linear_attn_config)
    unread = sorted(set(config) - {"kda_layers", "full_attn_layers", "num_heads", "head_dim", "short_conv_kernel_size"})
    if unread:
        raise ValueError(f"linear_attn_config: {unread} would be read by nothing")
    # the two lists number the layers from 1
    kda, full = set(config["kda_layers"]), set(config["full_attn_layers"])
    if kda & full or kda | full != set(range(1, num_hidden_layers + 1)):
        raise ValueError(
            f"linear_attn_config: kda_layers {sorted(kda)} and full_attn_layers {sorted(full)} must name each of the "
            f"layers 1..{num_hidden_layers} once"
        )
    heads, head_dim, taps = int(config["num_heads"]), int(config["head_dim"]), int(config["short_conv_kernel_size"])
    if min(heads, head_dim, taps) <= 0:
        raise ValueError(f"linear_attn_config: num_heads {heads}, head_dim {head_dim}, short_conv_kernel_size {taps}")
    linear = KimiDeltaAttention(heads, head_dim, taps, float(rms_norm_eps))
    mixers = [linear if number in kda else latent_attention for number in range(1, num_hidden_layers + 1)]
    # keys of the stream a layer: a linear-attention layer draws 14 and shared experts 7
    return tuple((("attn_norm", mixer), ("ffn_norm", feed_forward)) for mixer, feed_forward in zip(mixers, feed_forwards)), 24


def _afmoe_router(
    *, num_experts, num_experts_per_tok, experts_held, first_expert_held, score_func, route_norm, route_scale, n_group, topk_group,
):
    """``afmoe``'s spelling of the router's keys, mapped onto :func:`_router`'s: always a
    correction bias (``expert_bias``: it chooses, it never weighs)."""
    return _router(
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok, experts_held=experts_held,
        first_expert_held=first_expert_held, scoring_func=score_func, norm_topk_prob=route_norm,
        routed_scaling_factor=route_scale, topk_method="noaux_tc", n_group=n_group, topk_group=topk_group,
    )


def _afmoe_layers(
    router, correction_bias,
    *, layer_types, num_hidden_layers, num_dense_layers, hidden_size, num_attention_heads, num_key_value_heads, head_dim,
    sliding_window, rope_theta, rms_norm_eps, intermediate_size, moe_intermediate_size, num_shared_experts,
    load_balance_coeff, bias_update_speed,
):
    """``afmoe``'s layers: ``layer_types`` names each layer's ATTENTION here (module docstring: the one
    collision of spellings), the first ``num_dense_layers`` feed-forwards are dense; every part between
    two norms.  Its third return renames the family's own key onto the one the spec reads."""
    kinds = tuple(layer_types if layer_types is not None else ("sliding_attention",) * num_hidden_layers)
    if len(kinds) != num_hidden_layers or set(kinds) - set(ATTENTION_LAYER_TYPES):
        raise ValueError(
            f"under sliding_window, layer_types must name the ATTENTION of {num_hidden_layers} layers from "
            f"{ATTENTION_LAYER_TYPES}, got {kinds!r}"
        )
    kv_heads = num_key_value_heads or num_attention_heads
    if num_attention_heads % kv_heads or head_dim <= 0 or head_dim % 2:
        raise ValueError(f"{num_attention_heads} query heads over {kv_heads} key/value heads of head_dim {head_dim} (even: rotary pairs)")
    if bias_update_speed != _DEFAULTS["bias_update_speed"]:
        raise ValueError("bias_update_speed: this family's key for the correction bias's speed is load_balance_coeff")
    attentions = {
        kind: GatedWindowAttention(
            num_attention_heads, kv_heads, head_dim, sliding_window if kind == "sliding_attention" else 0,
            float(rope_theta), float(rms_norm_eps),
        )
        for kind in ATTENTION_LAYER_TYPES
    }
    feed_forwards, draws = _gated_feed_forwards(
        router, correction_bias, num_hidden_layers=num_hidden_layers, layer_types=None, first_k_dense_replace=num_dense_layers,
        intermediate_size=intermediate_size, moe_intermediate_size=moe_intermediate_size, n_shared_experts=num_shared_experts,
    )
    layers = tuple(
        (("attn_norm", attentions[kind], "post_attn_norm"), ("ffn_norm", feed_forward, "post_ffn_norm"))
        for kind, feed_forward in zip(kinds, feed_forwards)
    )
    return layers, draws, {"bias_update_speed": float(load_balance_coeff)}  # 12 keys a layer: five projections, the experts' seven


def _lfm2_router(
    *, num_experts, num_experts_per_tok, experts_held, first_expert_held, use_expert_bias, norm_topk_prob, routed_scaling_factor,
    n_group, topk_group,
):
    """``lfm2_moe``'s spelling of the router's keys, mapped onto :func:`_router`'s: a sigmoid router
    (the family has no other) whose ``use_expert_bias`` is the correction bias (it chooses, it never weighs)."""
    if not use_expert_bias:
        raise ValueError("use_expert_bias false (a router that chooses by its scores alone) is not supported under conv_L_cache: no cell runs it")
    return _router(
        num_experts=num_experts, num_experts_per_tok=num_experts_per_tok, experts_held=experts_held,
        first_expert_held=first_expert_held, scoring_func="sigmoid", norm_topk_prob=norm_topk_prob,
        routed_scaling_factor=routed_scaling_factor, topk_method="noaux_tc", n_group=n_group, topk_group=topk_group,
    )


def _lfm2_layers(
    router, correction_bias,
    *, layer_types, num_hidden_layers, num_dense_layers, hidden_size, num_attention_heads, num_key_value_heads,
    conv_L_cache, conv_bias, rope_theta, norm_eps, rms_norm_eps, intermediate_size, moe_intermediate_size,
):
    """``lfm2_moe``'s layers: ``layer_types`` names each layer's OPERATOR here (module docstring: the THIRD
    reading of the key), ``conv`` a double-gated short convolution of ``conv_L_cache`` taps or
    ``full_attention`` (fewer key/value heads, a norm a head, then the rotary turn; no gate, no window); the
    first ``num_dense_layers`` feed-forwards are dense.  Its third return renames the family's own key
    onto the one the spec reads."""
    kinds = tuple(layer_types if layer_types is not None else ("conv",) * num_hidden_layers)
    if len(kinds) != num_hidden_layers or set(kinds) - set(OPERATOR_LAYER_TYPES):
        raise ValueError(
            f"under conv_L_cache, layer_types must name the OPERATOR of {num_hidden_layers} layers from "
            f"{OPERATOR_LAYER_TYPES}, got {kinds!r}"
        )
    if norm_eps <= 0 or rms_norm_eps != _DEFAULTS["rms_norm_eps"]:
        raise ValueError(f"norm_eps {norm_eps}: this family's key for the norms' epsilon (positive), not rms_norm_eps")
    kv_heads = num_key_value_heads or num_attention_heads
    head_dim = _head_width(hidden_size, num_attention_heads)
    if num_attention_heads % kv_heads:
        raise ValueError(f"{num_attention_heads} query heads over {kv_heads} key/value heads")
    operators = {
        "conv": GatedShortConv(int(conv_L_cache), bool(conv_bias)),
        "full_attention": GatedWindowAttention(
            num_attention_heads, kv_heads, head_dim, 0, float(rope_theta), float(norm_eps), gate=False, rotary=True),
    }
    feed_forwards, draws = _gated_feed_forwards(
        router, correction_bias, num_hidden_layers=num_hidden_layers, layer_types=None, first_k_dense_replace=num_dense_layers,
        intermediate_size=intermediate_size, moe_intermediate_size=moe_intermediate_size, n_shared_experts=0,
    )
    layers = tuple((("operator_norm", operators[kind]), ("ffn_norm", feed_forward)) for kind, feed_forward in zip(kinds, feed_forwards))
    return layers, draws, {"rms_norm_eps": float(norm_eps)}  # 8 keys a layer: an operator's 3 or 4, the experts' 4


def _smallthinker_router(
    *, moe_num_primary_experts, moe_num_active_primary_experts, experts_held, first_expert_held,
    moe_primary_router_apply_softmax, norm_topk_prob, n_group, topk_group,
):
    """``smallthinker``'s spelling of the router's keys, mapped onto :func:`_router`'s: the top-k of the LOGITS, then a
    softmax over the chosen (``moe_primary_router_apply_softmax``) — which is the softmax over all
    ``moe_num_primary_experts`` with the chosen renormalised (``norm_topk_prob``): ``ops/moe.route`` as it is.  No
    correction bias, no scale."""
    if not moe_primary_router_apply_softmax or not norm_topk_prob:
        raise ValueError(
            "moe_primary_router_apply_softmax false (sigmoids of the chosen logits) or norm_topk_prob false is not supported under "
            "sliding_window_layout: no cell runs either"
        )
    return _router(
        num_experts=moe_num_primary_experts, num_experts_per_tok=moe_num_active_primary_experts, experts_held=experts_held,
        first_expert_held=first_expert_held, scoring_func="softmax", norm_topk_prob=True, routed_scaling_factor=1.0,
        topk_method="greedy", n_group=n_group, topk_group=topk_group,
    )


def _smallthinker_layers(
    router, correction_bias,
    *, sliding_window_layout, rope_layout, sliding_window_size, num_hidden_layers, num_attention_heads, num_key_value_heads,
    head_dim, rope_theta, rms_norm_eps, moe_ffn_hidden_size,
):
    """``smallthinker``'s layers: attention (``sliding_window_layout[i]`` 1: the last ``sliding_window_size`` keys, 0:
    every earlier key; ``rope_layout[i]`` 1: q, k take the rotary turn, 0: NO position signal; no norm a head, no gate,
    no bias) and relu-gated experts ``moe_ffn_hidden_size`` wide in EVERY layer, whose router reads the rows the
    ATTENTION reads (``routes_on``: ``_block`` routes ahead of the attention and hands the routing on).  ``wo`` and the
    experts' ``w_down`` are drawn at :data:`KEYE_VL2_INTO_STREAM` of the init's scale, a STAND-IN for the same reason as
    ``KeyeVL2``'s: at the init's own scale every token of a sequence routes alike from the third layer on (all 4096
    tokens on the same six experts: the reference on the CPU at the published widths, PERF.md section 6, PR 69)."""
    windows = tuple(sliding_window_layout)
    turns = tuple(rope_layout if rope_layout is not None else windows)
    if not len(windows) == len(turns) == num_hidden_layers or (set(windows) | set(turns)) - {0, 1}:
        raise ValueError(
            f"sliding_window_layout and rope_layout must give each of {num_hidden_layers} layers a 0 or a 1, got {windows!r} / {turns!r}"
        )
    kv_heads = num_key_value_heads or num_attention_heads
    if num_attention_heads % kv_heads or head_dim <= 0 or head_dim % 2 or (any(windows) and sliding_window_size <= 0) or moe_ffn_hidden_size <= 0:
        raise ValueError(
            f"{num_attention_heads} query heads over {kv_heads} key/value heads of head_dim {head_dim} (even: rotary pairs), "
            f"sliding_window_size {sliding_window_size}, moe_ffn_hidden_size {moe_ffn_hidden_size}"
        )
    attentions = {
        (slides, turn): GatedWindowAttention(
            num_attention_heads, kv_heads, head_dim, sliding_window_size if slides else 0, float(rope_theta), float(rms_norm_eps),
            gate=False, rotary=bool(turn), head_norm=False, into_stream=KEYE_VL2_INTO_STREAM, product_sites=False,
        )
        for slides, turn in set(zip(windows, turns))
    }
    experts = RoutedExperts(
        router, moe_ffn_hidden_size, correction_bias, into_stream=KEYE_VL2_INTO_STREAM, activation="relu", routes_on="attn_norm")
    layers = tuple((("attn_norm", attentions[kind]), ("ffn_norm", experts)) for kind in zip(windows, turns))
    return layers, 8  # keys of the stream a layer: four projections, the experts' four


def _family(*, hybrid_override_pattern, attention_class, linear_attn_config, kv_lora_rank, sliding_window=0, sa_config=None, conv_L_cache=0, block_length=0, sliding_window_layout=None) -> str:
    """Which family's builders read the keys.  ``hybrid_override_pattern``,
    ``attention_class`` ``'eva'``, ``linear_attn_config``, ``kv_lora_rank``,
    ``sliding_window``, ``sa_config``, ``conv_L_cache``, ``block_length`` and ``sliding_window_layout`` each name one, and one model is of one — with ONE rule for a pair:
    ``linear_attn_config`` decides over ``kv_lora_rank`` (``kimi_linear``'s
    full-attention layers ARE latent attention: the rank is one of its own
    keys).  Any other two together are refused."""
    if attention_class not in ATTENTION_CLASSES:
        raise ValueError(f"attention_class {attention_class!r}: known are {ATTENTION_CLASSES}")
    named = [
        family
        for family, said in (
            ("nemotron_h", hybrid_override_pattern is not None), ("evabyte", attention_class == "eva"),
            ("kimi_linear", linear_attn_config is not None), ("deepseek_v3", bool(kv_lora_rank) and linear_attn_config is None),
            ("afmoe", sliding_window > 0), ("keye_vl2", sa_config is not None), ("lfm2_moe", conv_L_cache > 0),
            ("sdar_moe", block_length > 0), ("smallthinker", sliding_window_layout is not None),
        )
        if said
    ]
    if len(named) > 1:
        raise ValueError(
            "hybrid_override_pattern, attention_class 'eva', linear_attn_config, kv_lora_rank, sliding_window, sa_config, conv_L_cache, block_length and sliding_window_layout each name a family and "
            f"one model is of one (linear_attn_config alone decides over kv_lora_rank): got those of {named}"
        )
    return named[0] if named else "olmoe"


#: family -> (own) -> (the layers, the keys of the init's stream a layer takes[, the family's own spelling
#: of keys the spec reads, renamed]).  ``own(builder)`` calls a builder with the published keys it names.
#: A new architecture is a part and a line here.
FAMILIES = {
    "olmoe": lambda own: _two_part_layers(own(_qk_norm_attention), own),
    "deepseek_v3": lambda own: _two_part_layers(own(_latent_attention), own, draws=12),  # 12 with or without shared experts
    "evabyte": lambda own: _two_part_layers(own(_eva_attention), own),
    "nemotron_h": lambda own: own(functools.partial(_nemotron_h_layers, *own(_router))),
    "kimi_linear": lambda own: own(functools.partial(
        _kimi_linear_layers, own(_latent_attention), own(functools.partial(_kimi_linear_feed_forwards, *own(_kimi_linear_router))))),
    "afmoe": lambda own: own(functools.partial(_afmoe_layers, *own(_afmoe_router))),
    "keye_vl2": _keye_vl2_layers,
    "lfm2_moe": lambda own: own(functools.partial(_lfm2_layers, *own(_lfm2_router))),
    "sdar_moe": functools.partial(_keye_vl2_layers, attention=_block_diffusion_attention, draws=0),  # 4 of the attention, 4 of the experts
    "smallthinker": lambda own: own(functools.partial(_smallthinker_layers, *own(_smallthinker_router))),
}


def _spec_of_layers(
    layers: Sequence[Layer], draws_a_layer: int,
    *, learning_rate, compute_dtype, vocab_size, hidden_size, rms_norm_eps, seq_len, tie_word_embeddings,
    router_aux_loss_coef, router_z_loss_coef, weight_decay, lr_warmup_steps, remat, bias_update_speed,
    norm_add_unit_offset, fp32_skip_add, num_pred_heads, init_std, decay_matrices_only, mup_enabled,
    block_length, noise_seed,
) -> ModelSpec:
    """The model of ``layers`` (what :func:`model_spec` ends in): the init,
    the block, the step counters and the correction bias's rule all follow
    from the list."""
    if num_pred_heads < 1 or (num_pred_heads > 1 and tie_word_embeddings):
        raise ValueError(f"num_pred_heads {num_pred_heads}: at least one, and more than one only with an untied head")
    if (block_length and (num_pred_heads > 1 or seq_len % block_length)) or (noise_seed and not block_length):
        raise ValueError(
            f"block_length {block_length} / noise_seed {noise_seed}: diffusion over blocks needs a seq_len ({seq_len}) in whole "
            f"blocks and one head of prediction ({num_pred_heads}); noise_seed is its feed's alone"
        )
    layers = tuple(layers)
    parts = [part for layer in layers for part in _parts(layer)]
    correction_bias = any(part.correction_bias for part in parts)
    # a model trained by diffusion over blocks: the slice's LAST row is the mask, the feed noises, the loss is over masked positions
    objective = dict(mask_token=vocab_size - 1) if block_length else {}
    apply = functools.partial(
        _apply, layers=layers, compute_dtype=jnp.dtype(compute_dtype), remat=remat, eps=float(rms_norm_eps),
        unit_offset=bool(norm_add_unit_offset), residual_dtype=jnp.float32 if fp32_skip_add else None,
        num_pred_heads=num_pred_heads, embed_scale=float(hidden_size) ** 0.5 if mup_enabled else 1.0,
        block_length=int(block_length),
    )
    skip = _NOT_MATRICES if decay_matrices_only else _NEVER_DECAYED
    coefs = dict(lb_coef=router_aux_loss_coef, z_coef=router_z_loss_coef, **objective)
    return ModelSpec(
        name="moe_lm",
        init=functools.partial(
            _init, layers=layers, draws_a_layer=draws_a_layer, vocab_size=vocab_size, hidden_size=hidden_size,
            init_std=init_std, tie_word_embeddings=tie_word_embeddings, unit_offset=bool(norm_add_unit_offset),
            num_pred_heads=num_pred_heads,
        ),
        apply=apply,
        loss=functools.partial(_loss, **coefs),
        metrics=functools.partial(_diffusion_metrics if block_length else _metrics, **coefs),
        optimizer=optax.adamw(
            optax.linear_schedule(0.0, learning_rate, lr_warmup_steps) if lr_warmup_steps else learning_rate,
            b1=0.9, b2=0.95, eps=1e-8, weight_decay=weight_decay,
            # a correction bias gets no gradient (it only chooses) and no decay:
            # Adam's update of a leaf whose gradient is always 0 is exactly 0
            mask=functools.partial(_is_decayed, skip=skip) if correction_bias or decay_matrices_only else None,
        ),
        feed=functools.partial(block_diffusion_feed, noise_seed=int(noise_seed), **objective) if block_length else lm_feed,
        example_batch=functools.partial(_diffusion_example_batch if block_length else _example_batch, seq_len=seq_len),
        batch_shard_dim=1,
        predict=functools.partial(_predict, apply=apply),
        step_counters={**{key: text for part in parts for key, text in part.counters.items()}, **(BD_TOKEN_COUNTERS if block_length else {})},
        after_update=(
            functools.partial(_update_correction_bias, layers=layers, speed=float(bias_update_speed))
            if correction_bias else None
        ),
        rematerialises=bool(remat),
    )


def model_spec(
    learning_rate: float = 4e-4,
    compute_dtype: str = "bfloat16",
    vocab_size: int = 8192,
    hidden_size: int = 256,
    num_attention_heads: int = 4,
    num_hidden_layers: int = 2,
    num_experts: int = 8,
    num_experts_per_tok: int = 2,
    intermediate_size: int = 128,
    rope_theta: float = 10000.0,
    rms_norm_eps: float = 1e-5,
    seq_len: int = 256,
    tie_word_embeddings: bool = False,
    layer_types: Optional[Sequence[str]] = None,
    router_aux_loss_coef: float = 0.01,
    router_z_loss_coef: float = 0.001,
    weight_decay: float = 0.1,
    lr_warmup_steps: int = 0,
    remat: bool = True,
    # deepseek_v3's keys (defaults: OLMoE's block)
    kv_lora_rank: int = 0,
    q_lora_rank: Optional[int] = None,
    qk_nope_head_dim: int = 0,
    qk_rope_head_dim: int = 0,
    v_head_dim: int = 0,
    rope_interleave: bool = False,
    moe_intermediate_size: int = 0,
    n_shared_experts: int = 0,
    first_k_dense_replace: int = 0,
    scoring_func: str = "softmax",
    norm_topk_prob: bool = False,
    routed_scaling_factor: float = 1.0,
    topk_method: str = "greedy",
    n_group: int = 1,
    topk_group: int = 1,
    bias_update_speed: float = 0.001,
    experts_held: int = 0,
    first_expert_held: int = 0,
    # evabyte's keys (defaults: OLMoE's block)
    attention_class: str = "mha",
    window_size: int = 0,
    chunk_size: int = 0,
    heads_held: int = 0,
    norm_add_unit_offset: bool = False,
    fp32_skip_add: bool = False,
    num_pred_heads: int = 1,
    init_std: float = 0.02,
    decay_matrices_only: bool = False,
    # nemotron_h's keys (defaults: OLMoE's block)
    hybrid_override_pattern: Optional[str] = None,
    mamba_num_heads: int = 0,
    mamba_head_dim: int = 0,
    n_groups: int = 1,
    ssm_state_size: int = 0,
    conv_kernel: int = 4,
    time_step_min: float = 0.001,
    time_step_max: float = 0.1,
    time_step_floor: float = 1e-4,
    num_key_value_heads: int = 0,
    head_dim: int = 0,
    moe_latent_size: int = 0,
    moe_shared_expert_intermediate_size: int = 0,
    mlp_hidden_act: str = "silu",
    mamba_heads_held: int = 0,
    kv_heads_held: int = 0,
    rescale_prenorm_residual: bool = False,
    residual_layers: int = 0,
    # kimi_linear's keys (defaults: OLMoE's block)
    linear_attn_config: Optional[Dict[str, Any]] = None,
    mla_use_nope: bool = False,
    num_experts_per_token: int = 0,
    num_shared_experts: int = 0,
    moe_router_activation_func: str = "softmax",
    moe_renormalize: bool = False,
    use_grouped_topk: bool = False,
    num_expert_group: int = 1,
    moe_layer_freq: int = 1,
    # afmoe's keys (defaults: OLMoE's block)
    sliding_window: int = 0,
    num_dense_layers: int = 0,
    score_func: str = "softmax",
    route_norm: bool = False,
    route_scale: float = 1.0,
    load_balance_coeff: float = 0.001,
    mup_enabled: bool = False,
    # KeyeVL2's keys (defaults: OLMoE's block)
    sa_config: Optional[Dict[str, Any]] = None,
    # lfm2_moe's keys (defaults: OLMoE's block)
    conv_L_cache: int = 0,
    conv_bias: bool = False,
    norm_eps: float = 0.0,
    use_expert_bias: bool = True,
    # sdar_moe's keys (defaults: OLMoE's block)
    block_length: int = 0,
    noise_seed: int = 0,
    # smallthinker's keys (defaults: OLMoE's block)
    sliding_window_layout: Optional[Sequence[int]] = None,
    rope_layout: Optional[Sequence[int]] = None,
    sliding_window_size: int = 0,
    moe_num_primary_experts: int = 0,
    moe_num_active_primary_experts: int = 0,
    moe_ffn_hidden_size: int = 0,
    moe_primary_router_apply_softmax: bool = True,
) -> ModelSpec:
    """``layer_types`` names each layer's feed-forward, ``"moe"`` or
    ``"dense"`` (both gated; dense layers ``intermediate_size`` wide, experts
    ``moe_intermediate_size``, 0 = the same); None = the first
    ``first_k_dense_replace`` are dense and the rest ``moe`` (all ``moe`` in
    OLMoE).  ``kv_lora_rank`` > 0 = latent attention at ``qk_nope_head_dim``
    + ``qk_rope_head_dim`` / ``v_head_dim``.  ``num_experts`` is the
    ROUTER's width; ``experts_held`` of them (0 = all), from
    ``first_expert_held`` on, are computed here.  ``topk_method``
    ``noaux_tc`` gives every expert layer a correction bias, moved after
    each step by ``bias_update_speed`` (module docstring).
    ``lr_warmup_steps`` > 0 raises the learning rate linearly from 0 over
    that many steps (0: flat from the first step).  ``attention_class``
    ``"eva"`` = EVA attention at ``window_size`` / ``chunk_size`` over
    ``heads_held`` of the ``num_attention_heads`` heads (0 = all; the heads
    are alike, so which are held changes nothing), with no QK-norm;
    ``norm_add_unit_offset``: norms multiply by 1 + gain (gains start at
    0); ``fp32_skip_add``: a float32 residual stream; ``num_pred_heads``
    heads of prediction (module docstring); ``decay_matrices_only``: AdamW
    decays no gain and neither of EVA's vectors (default: every leaf but
    the correction biases).  ``hybrid_override_pattern`` (``nemotron_h``): a
    letter a layer, ``M`` a Mamba-2 mixer (``mamba_num_heads`` heads of
    ``mamba_head_dim`` in ``n_groups`` groups, state ``ssm_state_size``,
    ``conv_kernel`` taps, the scan in chunks of ``chunk_size``;
    ``mamba_heads_held`` of the heads, whole groups, are computed here), ``*``
    attention with ``num_key_value_heads`` key/value heads of ``head_dim``
    (``heads_held`` / ``kv_heads_held`` here) and no rotary turn, ``E`` a
    LatentMoE (sigmoid router with a correction bias over ``num_experts``,
    experts of two matrices ``moe_latent_size`` -> ``moe_intermediate_size``
    under ``mlp_hidden_act`` relu2, a shared expert
    ``moe_shared_expert_intermediate_size`` wide); ``rescale_prenorm_residual``
    scales the matrices that write into the stream by ``residual_layers``^-1/2
    (0: this model's depth).  Every held count 0 = all.
    ``linear_attn_config`` (``kimi_linear``): ``{"kda_layers", "full_attn_layers"``
    (layer numbers from 1), ``"num_heads", "head_dim", "short_conv_kernel_size"}``:
    Kimi Delta Attention on the first list's layers, latent attention (the
    ``deepseek_v3`` keys; ``mla_use_nope``: no rotary turn) on the second's;
    the router and the feed-forwards are ``deepseek_v3``'s under this family's
    spelling (``num_experts_per_token``, ``num_shared_experts``,
    ``moe_router_activation_func``, ``moe_renormalize``, ``use_grouped_topk`` /
    ``num_expert_group``, ``moe_layer_freq`` 1).
    ``sliding_window`` > 0 (``afmoe``): ``layer_types`` names each layer's ATTENTION,
    ``"sliding_attention"`` (the ``sliding_window`` keys up to the query's own, rotary) or
    ``"full_attention"`` (every earlier key, NO position signal) — not its feed-forward, which
    follows from ``num_dense_layers`` (the leading ones dense, ``intermediate_size`` wide; the rest
    ``num_experts`` experts ``moe_intermediate_size`` wide with ``num_shared_experts`` shared);
    ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value heads of ``head_dim``,
    a norm a head on q and k, a sigmoid gate on the output, a norm before AND after every part; the
    router ``score_func`` / ``route_norm`` / ``route_scale`` with a correction bias moved by
    ``load_balance_coeff``.  ``mup_enabled``: the embedding's output times ``hidden_size``^0.5.
    ``sa_config`` (``KeyeVL2``): ``{"indexer_num_heads", "indexer_head_dim", "indexer_num_kv_heads"`` (1), ``"topk",
    "q_chunk_size", "kv_chunk_size"}``: every layer's attention is grouped-query attention (``num_key_value_heads`` heads of
    ``head_dim``, a norm a head, rotary) over the ``topk`` keys a learned indexer selects for each query
    (``models/attentions.IndexedSparseAttention``); the indexer's own loss is added to the total;
    the feed-forwards and the router are OLMoE's keys (``norm_topk_prob``, ``moe_intermediate_size``, ``experts_held``);
    the matrices that write into the stream — the attention's ``wo`` and the routed experts' ``w_down`` — are drawn at
    :data:`KEYE_VL2_INTO_STREAM` of ``init_std``.
    ``conv_L_cache`` > 0 (``lfm2_moe``): ``layer_types`` names each layer's OPERATOR, ``"conv"`` (a double-gated short
    convolution of ``conv_L_cache`` taps, ``models/gated_conv.GatedShortConv``; ``conv_bias`` true is refused) or
    ``"full_attention"`` (``num_attention_heads`` query heads over ``num_key_value_heads`` key/value heads of
    ``hidden_size / num_attention_heads``, a norm a head THEN the rotary turn, no gate, no window); the first
    ``num_dense_layers`` feed-forwards dense, the rest ``num_experts`` sigmoid-routed experts ``moe_intermediate_size`` wide
    with a correction bias (``use_expert_bias``; false is refused), ``norm_topk_prob`` / ``routed_scaling_factor``; the
    norms' epsilon is ``norm_eps``; a layer's two norms are ``operator_norm`` and ``ffn_norm``.
    ``block_length`` > 0 (``sdar_moe``: SDAR, trained by diffusion over blocks): every layer is ``KeyeVL2``'s less the indexer
    (``num_key_value_heads`` heads of ``head_dim``, a norm a head THEN the rotary turn; OLMoE's router keys, ``norm_topk_prob``,
    ``moe_intermediate_size``, ``experts_held``; the same stand-in scale of ``wo`` and ``w_down``); the feed noises each record
    from ``noise_seed`` (``data/codecs.block_diffusion_feed``; the mask is the id ``vocab_size - 1``), the body runs the noisy
    and the clean copy as ONE sequence of ``2 x seq_len`` rows under the rule of diffusion over blocks of ``block_length``
    (``ops/flash_attention.bd_mask``), the head reads the noisy copy and the loss is over the masked positions, weighed 1 / p.
    ``sliding_window_layout`` (``smallthinker``: SmallThinker-21BA3B): a 0 or a 1 a layer, 1 = attention over the last
    ``sliding_window_size`` keys, 0 = over every earlier key; ``rope_layout`` likewise, 1 = q and k take the rotary turn
    (``rope_theta``), 0 = no position signal; ``num_attention_heads`` query heads over ``num_key_value_heads`` key/value heads
    of ``head_dim``, no norm a head, no gate; EVERY layer's feed-forward ``moe_num_primary_experts`` relu-gated experts
    ``moe_ffn_hidden_size`` wide, ``moe_num_active_primary_experts`` a token, whose router reads the rows the ATTENTION
    reads (a softmax over the chosen logits: ``moe_primary_router_apply_softmax`` with ``norm_topk_prob``, both required);
    ``experts_held`` / ``first_expert_held`` as ever; ``wo`` and ``w_down`` drawn at :data:`KEYE_VL2_INTO_STREAM` of ``init_std``.

    Which FAMILY the model is of follows from ``hybrid_override_pattern``,
    ``attention_class``, ``linear_attn_config``, ``kv_lora_rank``, ``sliding_window``, ``sa_config``, ``conv_L_cache``, ``block_length`` and ``sliding_window_layout`` (:func:`_family`); the family's
    builders take their own keys and check their ranges, and a key that no
    builder of the chosen family reads, set to other than its default, is
    refused: it would change nothing."""
    keys = dict(locals())
    read = set()

    def own(builder):
        names = tuple(inspect.signature(builder).parameters)
        read.update(names)
        return builder(**{name: keys[name] for name in names})

    family = own(_family)
    layers, draws_a_layer, *renamed = FAMILIES[family](own)
    keys.update(*renamed)
    spec = own(functools.partial(_spec_of_layers, layers, draws_a_layer))
    foreign = sorted(key for key, value in keys.items() if key not in read and value != _DEFAULTS[key])
    if foreign:
        raise ValueError(
            f"{', '.join(foreign)}: set, but no part of the {family!r} family reads "
            f"{'it' if len(foreign) == 1 else 'them'} (the family follows from hybrid_override_pattern / attention_class / "
            f"linear_attn_config / kv_lora_rank / sliding_window / sa_config / conv_L_cache / block_length / sliding_window_layout)"
        )
    return spec


_DEFAULTS = {name: parameter.default for name, parameter in inspect.signature(model_spec).parameters.items()}
