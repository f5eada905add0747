"""Decoder-only LM with a modern block: rotary positions, multi-head
attention with QK-norm or latent attention, a gated (SwiGLU) feed-forward
that is dense or a dropless mixture of experts layer by layer — with or
without shared experts and a router correction bias — and a tied or untied
head.  ONE decoder that adapts to the published keys, which are
``model_spec``'s arguments: its defaults and parameter names are OLMoE's
(``OLMoE-1B-7B-0125``: every layer ``moe``, 64 experts, 8 a token, softmax
router, untied head); ``kv_lora_rank`` > 0 and the ``deepseek_v3`` keys make
it DeepSeek-V3's block (``kanana-2-30b-a3b``: below).

The block, with ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) * g``:

    x   = tok_emb[tokens]                                 (no position table)
    a   = rmsnorm(x, attn_norm)
    q, k, v = a Wq, a Wk, a Wv                            (no bias, no clip)
    q   = rmsnorm(q, q_norm) ; k = rmsnorm(k, k_norm)     (over ALL heads' columns, before the split)
    q, k = rope(q), rope(k)                               (per head, rotate-half pairing (i, i + hd/2), theta)
    x  += causal_attention(q, k, v) Wo                    (scores / sqrt(hd))
    u   = rmsnorm(x, ffn_norm)
    moe:    r = u Wg ; p = softmax(r) ; (w_i, e_i) = top-k of p   (float32; NOT renormalised)
            x += sum_i w_i * (silu(u Wgate[e_i]) * (u Wup[e_i])) Wdown[e_i]
    dense:  x += (silu(u Wgate) * (u Wup)) Wdown
    logits = rmsnorm(x, norm_f) Whead                     (Whead = tok_emb^T when tied; float32 logits)
    loss = CE(logits, next token) + lb_coef * LB + z_coef * Z
    LB  = E * sum_{i, e} f[i, e] * P[e],   f[i, e] = share of (layer, token) pairs whose i-th choice is e,
                                           P[e] = mean over (layer, token) pairs of p[e]
    Z   = mean over (layer, token) pairs of logsumexp(r)^2

With ``kv_lora_rank`` > 0 (``transformers``' ``DeepseekV3`` modules,
``q_lora_rank`` null) the attention and the expert layer read instead, H
heads, ``nope`` / ``rot`` / ``v`` = ``qk_nope_head_dim`` /
``qk_rope_head_dim`` / ``v_head_dim``:

    q      = a Wq                 -> [T, H, nope + rot] = (q_nope, q_rot)
    (c, k_rot) = a Wkv_a          -> c [T, kv_lora_rank], k_rot [T, rot]: ONE rotary key for all heads
    (k_nope, v) = rmsnorm(c, kv_norm) Wkv_b   -> [T, H, nope], [T, H, v]
    q_rot, k_rot = rope(q_rot), rope(k_rot)   (the rot columns only; ``rope_interleave``: pairs (2i, 2i + 1))
    s_h    = (q_nope_h . k_nope_h + q_rot_h . k_rot) * (nope + rot)^-0.5 ; causal softmax ; o_h = p_h v_h
    x     += o Wo
    layers < first_k_dense_replace: dense, ``intermediate_size`` wide
    others: r = u Wg (float32) ; s = sigmoid(r)                  (``scoring_func``)
            e_1..e_k = top-k of (s + b)        b = the layer's correction bias (``topk_method`` noaux_tc):
                                               it chooses, it never weighs
            w_i = s[e_i] / (sum_j s[e_j] + 1e-20) * routed_scaling_factor      (``norm_topk_prob``)
            x += sum_i w_i * expert_{e_i}(u) + shared(u)         experts ``moe_intermediate_size`` wide;
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
``metrics`` need — logits, ``f``, ``P``, ``Z`` and the expert layers' slot
counts: a small pytree, no second forward.

With ``attention_class`` ``"eva"`` (EvaByte's keys) the block reads instead,
``heads_held`` of the ``num_attention_heads`` published heads here (one
chip's share under head parallelism: wq / wk / wv ``[d, held * hd]``, wo
``[held * hd, d]``; what the other heads would add to ``o Wo`` is left out):

    h    = tok_emb[tokens]                     float32 (``fp32_skip_add``): a block reads it cast to
                                               bfloat16 and adds its output in float32
    a    = rmsnorm(h, 1 + attn_norm)           (``norm_add_unit_offset``: the gains start at 0)
    q, k = rope(a Wq), rope(a Wk) ; v = a Wv   (no QK-norm)
    h   += eva_attention(q, k, v, eva_phi, eva_mu) Wo      (``ops/eva_attention``: exact inside the
                                               query's ``window_size``, one learned summary a
                                               ``chunk_size`` of every earlier window, ONE softmax)
    h   += gated MLP(rmsnorm(h, 1 + ffn_norm))
    logits = rmsnorm(h, 1 + norm_f) Whead      Whead [d, num_pred_heads x vocab]: head p at position i
                                               predicts token i + 1 + p
    loss = mean over the heads of each head's mean CE over the positions whose target the record holds

With ``hybrid_override_pattern`` (``nemotron_h``'s keys) a layer is ONE norm,
ONE mixer or feed-forward part and one residual, its kind a letter of the
pattern (``M`` / ``*`` / ``E``; the kind is read off the layer's parameters),
with no position table and no rotary turn:

    u  = rmsnorm(x, norm) ;  x += part(u)
    M (Mamba-2, ``ops/ssm``; H heads of P in G groups, state N, HELD: ``mamba_heads_held`` heads and their groups):
        (z, xBC, dt) = u W_in                  columns (H P | H P + 2 G N | H) of the held heads and groups
        (x, B, C) = silu(conv(xBC))            causal, depthwise, ``conv_kernel`` taps and a bias a channel
        dt = softplus(dt + dt_bias) ; a_t = exp(-exp(A_log) dt_t)
        S_t = a_t S_{t-1} + dt_t x_t B_t^T ; y_t = S_t C_t + D x_t      S_0 = 0 at each sequence's start
        part = rmsnorm over each GROUP's channels of (y * silu(z)), times a gain, then W_out
    * : q = u Wq (``heads_held`` heads of ``head_dim``), k, v = u Wk, u Wv (``kv_heads_held`` heads);
        query head h reads key/value head h // (heads / kv heads); causal softmax of q k / sqrt(head_dim); part = o Wo
    E (LatentMoE): r = u Wg (float32), s = sigmoid(r), top-k of s + b, w_i = scaling x s[e_i] / sum_j s[e_j]
        c = u W_down_lat                       [``moe_latent_size``]
        part = (sum_i w_i relu(c W1[e_i])^2 W2[e_i]) W_up_lat + relu(u Ws1)^2 Ws2     (``mlp_hidden_act`` relu2:
                                               experts and the shared expert are TWO matrices each)

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

import functools
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import optax
from jax import lax

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.data.codecs import lm_feed
from elasticdl_tpu.models.spec import ModelSpec
from elasticdl_tpu.ops import eva_attention as eva_ops
from elasticdl_tpu.ops import moe
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops import ssm as ssm_ops
from elasticdl_tpu.ops.embedding import ParallelContext
from elasticdl_tpu.ops.ring_attention import ring_attention

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
#: EVA attention's counts a step reports, likewise (gauges ``edl_eva_pairs_*_total``):
#: what the TRAFFIC asks of the attention, a function of the shapes it was
#: called with and of nothing the kernels do.
EVA_COUNTERS = {
    "eva_pairs_exact": "(query, key) pairs of a query's own window that the steps' queries were "
    "scored against, from the shapes the attention was called with, summed over heads, layers, "
    "training steps and devices",
    "eva_pairs_summary": "(query, chunk summary) pairs of earlier windows, summed likewise",
}
#: The state-space layers' counts a step reports, likewise (gauges
#: ``edl_ssm_positions*_total``): what the traffic asks of the scan, from
#: shapes (the operator's measure of scanned work), and the part of it the
#: scan's kernels took, each layer's counted where its scan is called
#: (``_mamba_mixer``; no per-layer metric reads them yet: PERF.md section 7).
SSM_COUNTERS = {
    "ssm_positions": "(head, position) pairs the state-space scans advanced a state over, from the "
    "shapes they were called with, summed over layers, training steps and devices",
    "ssm_positions_kernel": "those of them whose chunks the scan's Pallas kernels computed (ops/ssm_kernels.py: "
    "on a TPU inside their contract, decided when the step is traced), summed likewise",
}
LAYER_TYPES = ("moe", "dense")
#: ``hybrid_override_pattern``'s letters (``-``, a dense MLP layer, is not one: no cell runs it)
PATTERN_KINDS = {"M": "a Mamba-2 mixer", "*": "attention", "E": "a latent mixture of experts"}
ATTENTION_CLASSES = ("mha", "eva")
TOPK_METHODS = ("greedy", "noaux_tc")


def _rms_norm(x, scale, eps):
    # Statistics and arithmetic in f32, ONE downcast (transformer_lm's form).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * scale).astype(x.dtype)


def _gain(g, unit_offset: bool):
    """What a norm multiplies by: the gain, or 1 + it (``norm_add_unit_offset``)."""
    return 1.0 + g if unit_offset else g


def _qk_norm(x, scale, eps):
    """OLMoE's QK-norm: over ALL of the projection's columns (every head's),
    before the split into heads — not a norm per head."""
    return _rms_norm(x, scale, eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on ``x`` [B, L, H, hd]: element ``i`` of a head is
    paired with ``i + hd/2`` and the pair turned by ``positions * theta^
    (-2i/hd)``.  Float32 arithmetic, one downcast."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [L, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _rotary_columns(w: jax.Array, interleave: bool) -> jax.Array:
    """The rotary output columns of a projection ``w`` [..., rot] in the
    order :func:`rope` pairs them, (i, i + rot/2).  A model that pairs
    (2i, 2i + 1) (``rope_interleave``) has its even columns moved to the
    first half here, on the WEIGHT: the same permutation of q_rot and k_rot
    leaves every score as it is, and no activation is shuffled."""
    if not interleave:
        return w
    # a transpose, whose gradient is a transpose (two strided slices'
    # gradient is a scatter of rows, one at a time on the TPU)
    pairs = w.reshape(w.shape[:-1] + (w.shape[-1] // 2, 2))
    return jnp.swapaxes(pairs, -1, -2).reshape(w.shape)


def _init_params(
    rng, vocab_size: int, hidden_size: int, intermediate_size: int, num_experts: int,
    layer_types: Sequence[str], tie_word_embeddings: bool, init_std: float = 0.02,
    *, n_heads: int = 0, latent: Optional[Dict[str, int]] = None, moe_intermediate_size: int = 0,
    experts_held: int = 0, n_shared_experts: int = 0, correction_bias: bool = False,
    eva_heads: int = 0, unit_offset: bool = False, num_pred_heads: int = 1,
) -> Dict[str, Any]:
    """``latent`` (``kv_lora_rank``, ``nope``, ``rot``, ``v``) makes every
    layer's attention latent; the experts are ``moe_intermediate_size`` wide
    (0: ``intermediate_size``, as the dense layers) and ``experts_held`` of
    the router's ``num_experts`` are here (0: all).  ``eva_heads`` > 0
    makes it EVA attention over that many HELD heads of ``n_heads``;
    ``unit_offset`` starts every gain at 0 (the norms multiply by 1 + g)."""
    d, f, e = hidden_size, intermediate_size, num_experts
    gain = jnp.zeros if unit_offset else jnp.ones
    f_moe, held = moe_intermediate_size or f, experts_held or e
    # OLMoE's block draws 8 keys a layer; the draws below keep their order.
    per_layer = 12 if latent or n_shared_experts else 8
    ks = iter(jax.random.split(rng, 2 + per_layer * len(layer_types)))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * init_std

    params: Dict[str, Any] = {
        "tok_emb": normal((vocab_size, d)),
        "norm_f": gain((d,), jnp.float32),
        "blocks": {},
    }
    if not tie_word_embeddings:
        params["head"] = normal((d, num_pred_heads * vocab_size))
    for i, kind in enumerate(layer_types):
        blk = {"attn_norm": gain((d,), jnp.float32)}
        if eva_heads:
            hd = d // n_heads
            blk.update({
                "wq": normal((d, eva_heads * hd)), "wk": normal((d, eva_heads * hd)),
                "wv": normal((d, eva_heads * hd)), "wo": normal((eva_heads * hd, d)),
                "eva_phi": jnp.zeros((eva_heads, hd), jnp.float32),
                "eva_mu": jnp.zeros((eva_heads, hd), jnp.float32),
            })
        elif latent:
            rank, nope, rot, v = (latent[key] for key in ("kv_lora_rank", "nope", "rot", "v"))
            # The published shapes: a head's columns of wq are (nope | rot),
            # of wkv_b (nope | v); wkv_a's are (the latent | the rotary key).
            blk["wq"] = normal((d, n_heads * (nope + rot)))
            blk["wkv_a"] = normal((d, rank + rot))
            blk["kv_norm"] = jnp.ones((rank,), jnp.float32)
            blk["wkv_b"] = normal((rank, n_heads * (nope + v)))
            blk["wo"] = normal((n_heads * v, d))
        else:
            blk.update({
                "wq": normal((d, d)), "wk": normal((d, d)), "wv": normal((d, d)),
                "wo": normal((d, d)),
                "q_norm": jnp.ones((d,), jnp.float32),
                "k_norm": jnp.ones((d,), jnp.float32),
            })
        blk["ffn_norm"] = gain((d,), jnp.float32)
        if kind == "moe":
            blk["router"] = normal((d, e))
            if correction_bias:
                blk["router_bias"] = jnp.zeros((e,), jnp.float32)
            blk["w_gate"], blk["w_up"] = normal((held, d, f_moe)), normal((held, d, f_moe))
            blk["w_down"] = normal((held, f_moe, d))
            if n_shared_experts:
                f_shared = n_shared_experts * f_moe
                blk["ws_gate"], blk["ws_up"] = normal((d, f_shared)), normal((d, f_shared))
                blk["ws_down"] = normal((f_shared, d))
        else:
            blk["w_gate"], blk["w_up"] = normal((d, f)), normal((d, f))
            blk["w_down"] = normal((f, d))
        # Zero-padded names keep sorted() in layer order past nine layers.
        params["blocks"][f"b{i:02d}"] = blk
    return params


def _init_hybrid_params(
    rng, *, pattern: str, vocab_size: int, hidden_size: int, init_std: float, residual_layers: int,
    mamba_heads: int, mamba_head_dim: int, groups: int, state: int, conv_kernel: int, dt_range,
    q_heads: int, kv_heads: int, head_dim: int,
    num_experts: int, experts_held: int, latent: int, expert_width: int, shared_width: int,
) -> Dict[str, Any]:
    """``nemotron_h``'s parameters, a layer's by its letter of ``pattern``;
    every count is what is HELD here.  Matrices normal(0, ``init_std``),
    those that write into the residual stream (``ssm_out``, ``wo``,
    ``w_down``, ``ws_down``) scaled by ``residual_layers``^-1/2
    (``rescale_prenorm_residual``); ``A_log`` = log uniform(1, 16), ``dt_bias``
    the inverse softplus of a log-uniform draw in ``dt_range`` (min, max,
    floor), ``D`` = 1; the taps and their bias uniform(+-``conv_kernel``^-1/2)."""
    d = hidden_size
    ks = iter(jax.random.split(rng, 2 + 8 * len(pattern)))
    into_stream = residual_layers ** -0.5

    def normal(shape, scale=1.0):
        return jax.random.normal(next(ks), shape, jnp.float32) * (init_std * scale)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(ks), shape, jnp.float32, lo, hi)

    params: Dict[str, Any] = {
        "tok_emb": normal((vocab_size, d)), "norm_f": jnp.ones((d,), jnp.float32),
        "head": normal((d, vocab_size)), "blocks": {},
    }
    for i, kind in enumerate(pattern):
        blk: Dict[str, Any] = {"norm": jnp.ones((d,), jnp.float32)}
        if kind == "M":
            inner, conv_dim = mamba_heads * mamba_head_dim, mamba_heads * mamba_head_dim + 2 * groups * state
            lo, hi, floor = dt_range
            dt = jnp.maximum(jnp.exp(uniform((mamba_heads,), jnp.log(lo), jnp.log(hi))), floor)
            blk.update({
                "ssm_in": normal((d, inner + conv_dim + mamba_heads)),
                "conv_w": uniform((conv_kernel, conv_dim), -conv_kernel ** -0.5, conv_kernel ** -0.5),
                "conv_b": uniform((conv_dim,), -conv_kernel ** -0.5, conv_kernel ** -0.5),
                "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
                "A_log": jnp.log(uniform((mamba_heads,), 1.0, 16.0)),
                "D": jnp.ones((mamba_heads,), jnp.float32),
                "ssm_norm": jnp.ones((inner,), jnp.float32),
                "ssm_out": normal((inner, d), into_stream),
            })
        elif kind == "*":
            blk.update({
                "wq": normal((d, q_heads * head_dim)), "wk": normal((d, kv_heads * head_dim)),
                "wv": normal((d, kv_heads * head_dim)), "wo": normal((q_heads * head_dim, d), into_stream),
            })
        else:
            blk.update({
                "router": normal((d, num_experts)), "router_bias": jnp.zeros((num_experts,), jnp.float32),
                "w_lat_down": normal((d, latent)), "w_lat_up": normal((latent, d), into_stream),
                "w_up": normal((experts_held, latent, expert_width)),
                "w_down": normal((experts_held, expert_width, latent), into_stream),
                "ws_up": normal((d, shared_width)), "ws_down": normal((shared_width, d), into_stream),
            })
        params["blocks"][f"b{i:02d}"] = blk
    return params


def _attention(a, blk, positions, *, axis, n_heads, theta, eps, cast):
    """OLMoE's: three projections, whole-width QK-norm, rotate-half rope
    over the whole head."""
    b, l, dim = a.shape
    q = _qk_norm(a @ cast(blk["wq"]), blk["q_norm"], eps)
    k = _qk_norm(a @ cast(blk["wk"]), blk["k_norm"], eps)
    v = a @ cast(blk["wv"])
    heads = lambda t: t.reshape(b, l, n_heads, dim // n_heads)  # noqa: E731
    q, k = rope(heads(q), positions, theta), rope(heads(k), positions, theta)
    att = ring_attention(q, k, heads(v), axis_name=axis, causal=True)
    return att.reshape(b, l, dim) @ cast(blk["wo"])


def _eva_attention(a, blk, positions, *, axis, theta, cast, window, chunk):
    """EvaByte's: three projections onto the HELD heads (read off ``eva_phi``),
    rotate-half rope over the whole head, ``ops/eva_attention``."""
    if axis is not None and axis_size(axis) > 1:
        raise ValueError("EVA attention over a sharded sequence is not supported: the summaries of earlier windows live on other shards")
    b, l, _ = a.shape
    held, hd = blk["eva_phi"].shape
    heads = lambda t: t.reshape(b, l, held, hd)  # noqa: E731
    with jax.named_scope("eva_proj"):
        # save sites (ops/remat.py): each projection as the attention reads it
        wq, wk, wv = cast(blk["wq"]), cast(blk["wk"]), cast(blk["wv"])
        q = remat_lib.site("q", 2 * a.size * wq.shape[1], rope(heads(a @ wq), positions, theta))
        k = remat_lib.site("k", 2 * a.size * wk.shape[1], rope(heads(a @ wk), positions, theta))
        v = heads(remat_lib.product("v", a, wv))
    att = eva_ops.eva_attention(q, k, v, blk["eva_phi"], blk["eva_mu"], window=window, chunk=chunk)
    with jax.named_scope("eva_proj"):
        return att.reshape(b, l, held * hd) @ cast(blk["wo"])


def _latent_attention(a, blk, positions, *, axis, n_heads, theta, eps, cast, rot, interleave):
    """DeepSeek-V3's (module docstring): keys and values through a latent,
    ``rot`` rotary columns a head of q against ONE shared rotary key.  The
    widths are read off the parameters.  Each projection is multiplied by
    its own column block of the published matrix (a slice of the WEIGHT):
    every product is then born in the layout the attention kernels read,
    [B, L, H * width], with no slice of an activation in between."""
    b, l, dim = a.shape
    rank = blk["kv_norm"].shape[0]
    with jax.named_scope("mla_proj"):
        wq = blk["wq"].reshape(dim, n_heads, -1)
        nope = wq.shape[-1] - rot
        wkv_b = blk["wkv_b"].reshape(rank, n_heads, -1)
        columns = lambda w: cast(w.reshape(w.shape[0], -1))  # noqa: E731
        heads = lambda t: t.reshape(b, l, n_heads, -1)  # noqa: E731
        q = heads(a @ columns(wq[..., :nope]))
        q_rot = heads(a @ columns(_rotary_columns(wq[..., nope:], interleave)))
        c = _rms_norm(a @ cast(blk["wkv_a"][:, :rank]), blk["kv_norm"], eps)
        k_rot = a @ cast(_rotary_columns(blk["wkv_a"][:, rank:], interleave))
        k, v = heads(c @ columns(wkv_b[..., :nope])), heads(c @ columns(wkv_b[..., nope:]))
        q_rot = rope(q_rot, positions, theta)
        k_rot = rope(k_rot[:, :, None, :], positions, theta)[:, :, 0]
    att = ring_attention(q, k, v, axis_name=axis, causal=True, q_rot=q_rot, k_rot=k_rot)
    with jax.named_scope("mla_proj"):
        return att.reshape(b, l, -1) @ cast(blk["wo"])


def _gated_mlp(u, w_gate, w_up, w_down):
    with jax.named_scope("mlp"):
        # gate and up are save sites (ops/remat.py); silu and the product never
        return (jax.nn.silu(remat_lib.product("mlp_gate", u, w_gate)) * remat_lib.product("mlp_up", u, w_up)) @ w_down


def _relu2_mlp(u, w_up, w_down, site: str):
    with jax.named_scope("mlp"):
        # the first product is a save site (ops/remat.py); relu and the square never
        return jnp.square(jax.nn.relu(remat_lib.product(site, u, w_up))) @ w_down


def _mamba_mixer(u, blk, *, axis, eps, cast, state: int, chunk: int):
    """Mamba-2's mixer over the HELD heads and groups (read off ``A_log``
    and the convolution's channels).  Returns (the part, the layer's
    ``SSM_COUNTERS``)."""
    if axis is not None and axis_size(axis) > 1:
        raise ValueError("a state-space layer over a sharded sequence is not supported: the state at a shard's start lives on the shard before it")
    b, l, _ = u.shape
    heads, inner, conv_dim = blk["A_log"].shape[0], blk["ssm_norm"].shape[0], blk["conv_w"].shape[1]
    groups = (conv_dim - inner) // (2 * state)
    with jax.named_scope("ssm_proj"):
        # Each part is multiplied by its own column block of the published
        # matrix (a slice of the WEIGHT, as latent attention's); z and xBC
        # are save sites (ops/remat.py).
        w_in = blk["ssm_in"]
        z = remat_lib.product("ssm_z", u, cast(w_in[:, :inner]))
        xbc = remat_lib.product("ssm_xbc", u, cast(w_in[:, inner:inner + conv_dim]))
        dt = (u @ cast(w_in[:, inner + conv_dim:])).astype(jnp.float32)
    xbc = ssm_ops.causal_conv(xbc, blk["conv_w"], blk["conv_b"])
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(xbc)
        x = xbc[..., :inner].reshape(b, l, heads, inner // heads)
        bm = xbc[..., inner:inner + groups * state].reshape(b, l, groups, state)
        cm = xbc[..., inner + groups * state:].reshape(b, l, groups, state)
        dt = jax.nn.softplus(dt + blk["dt_bias"])
    y = ssm_ops.ssm_scan(x, dt, -jnp.exp(blk["A_log"]), bm, cm, blk["D"], chunk=chunk)
    # counted where the scan is called, from what it was called with
    by_kernels = ssm_ops.scan_path(x, bm, chunk)[0] != ssm_ops.PATH_XLA_REFERENCE
    counts = {"ssm_positions": jnp.float32(b * l * heads), "ssm_positions_kernel": jnp.float32(b * l * heads * by_kernels)}
    y = ssm_ops.gated_group_norm(y.reshape(b, l, inner), z, blk["ssm_norm"], groups, eps)
    with jax.named_scope("ssm_proj"):
        return y @ cast(blk["ssm_out"]), counts


def _grouped_query_attention(u, blk, *, axis, cast, head_dim: int):
    """Attention whose key/value heads are fewer than its query heads
    (query head h reads key/value head ``h // group``), over the HELD heads
    (read off the projections); no position signal.  The key/value heads are
    REPEATED to the queries' ahead of the attention (PERF.md section 7:
    the flash kernels' contract wants as many; one layer in eleven)."""
    b, l, _ = u.shape
    with jax.named_scope("attn_proj"):
        heads = lambda t: t.reshape(b, l, -1, head_dim)  # noqa: E731
        q, k, v = (heads(remat_lib.product(name, u, cast(blk["w" + name]))) for name in ("q", "k", "v"))
        group = q.shape[2] // k.shape[2]
        if group > 1:
            k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    att = ring_attention(q, k, v, axis_name=axis, causal=True)
    with jax.named_scope("attn_proj"):
        return att.reshape(b, l, -1) @ cast(blk["wo"])


def _routed_stats(routing, slots, given, pairs: int, top_k: int, first_expert_held: int, held: int):
    """An expert layer's router sums and slot counts (``_apply`` adds them up)."""
    f, p, z = moe.router_stats(routing)
    slots = slots.astype(jnp.float32)
    sizes = slots[first_expert_held:first_expert_held + held]
    here = (routing.choices >= first_expert_held) & (routing.choices < first_expert_held + held)
    return {
        "f": f, "p": p, "z": z, "pairs": jnp.float32(pairs),
        "slots": slots,
        "moe_slots": jnp.float32(pairs * top_k),
        "moe_slots_held": jnp.sum(here.astype(jnp.float32)),
        "moe_slots_computed": (given.first + given.second).astype(jnp.float32),
        "moe_slots_overflow": given.second.astype(jnp.float32),
        "moe_expert_load_max": jnp.max(sizes),
        "moe_expert_load_mean": jnp.mean(sizes),
    }


def _latent_moe(u, blk, *, top_k, router, first_expert_held, cast):
    """LatentMoE: the router reads the token, the routed experts (two
    matrices under relu squared) work in a latent between two projections,
    their weighted sum is taken IN the latent (linear: the same result as
    after ``w_lat_up``, a quarter of the rows' width); the shared expert
    reads the token.  Returns (the part, the layer's stats)."""
    b, l, dim = u.shape
    tokens = u.reshape(b * l, dim)
    n_experts, held = blk["router"].shape[1], blk["w_up"].shape[0]
    routing = moe.route(tokens, blk["router"], top_k, bias=blk["router_bias"], **(router or {}))
    with jax.named_scope("moe_latent"):
        c = remat_lib.product("moe_latent_down", tokens, cast(blk["w_lat_down"]))
    y, slots, given = moe.expert_ffn(
        c, routing.choices, routing.weights, None, cast(blk["w_up"]), cast(blk["w_down"]),
        n_experts=n_experts, lo=first_expert_held,
    )
    with jax.named_scope("moe_latent"):
        y = y @ cast(blk["w_lat_up"])
    with jax.named_scope("moe_shared"):
        y = y + _relu2_mlp(tokens, cast(blk["ws_up"]), cast(blk["ws_down"]), "shared_up")
    return y.reshape(b, l, dim), _routed_stats(routing, slots, given, b * l, top_k, first_expert_held, held)


def _hybrid_layer(x, blk, *, axis, top_k, eps, compute_dtype, router, first_expert_held, hybrid):
    """One ``nemotron_h`` layer: one norm, ONE mixer or feed-forward part
    (read off its parameters), one residual."""
    cast = lambda w: w.astype(compute_dtype)  # noqa: E731
    u = _rms_norm(cast(x), blk["norm"], eps)
    if "ssm_in" in blk:
        y, counts = _mamba_mixer(u, blk, axis=axis, eps=eps, cast=cast, state=hybrid["state"], chunk=hybrid["chunk"])
        return x + y, counts
    if "router" in blk:
        y, stats = _latent_moe(u, blk, top_k=top_k, router=router, first_expert_held=first_expert_held, cast=cast)
        return x + y, stats
    return x + _grouped_query_attention(u, blk, axis=axis, cast=cast, head_dim=hybrid["head_dim"]), None


def _block(
    x, blk, positions, *, axis, n_heads, top_k, theta, eps, compute_dtype,
    rot=0, interleave=False, router=None, first_expert_held=0, eva=None, unit_offset=False, hybrid=None,
):
    """One block: an attention and a feed-forward whose kinds are read off
    its parameters (``wkv_a`` makes the attention latent, ``eva_phi`` makes
    it EVA's with ``eva`` = its window and chunk, a ``router`` makes
    the feed-forward ``moe``, ``ws_gate`` adds the shared experts).
    ``router`` are ``ops/moe.route``'s published keys.  ``x`` may be wider
    than ``compute_dtype`` (a float32 residual stream): the block reads it
    cast and adds in ``x``'s own type.  Returns (x, the
    layer's router sums and slot counts — None for a dense layer).  A layer
    with ONE norm (``norm``: ``nemotron_h``) is one part alone
    (:func:`_hybrid_layer`)."""
    if "norm" in blk:
        return _hybrid_layer(
            x, blk, axis=axis, top_k=top_k, eps=eps, compute_dtype=compute_dtype, router=router,
            first_expert_held=first_expert_held, hybrid=hybrid,
        )
    b, l, dim = x.shape
    cast = lambda w: w.astype(compute_dtype)  # noqa: E731
    a = _rms_norm(cast(x), _gain(blk["attn_norm"], unit_offset), eps)
    common = dict(axis=axis, n_heads=n_heads, theta=theta, eps=eps, cast=cast)
    if "wkv_a" in blk:
        x = x + _latent_attention(a, blk, positions, rot=rot, interleave=interleave, **common)
    elif "eva_phi" in blk:
        x = x + _eva_attention(a, blk, positions, axis=axis, theta=theta, cast=cast, **eva).astype(x.dtype)
    else:
        x = x + _attention(a, blk, positions, **common)
    u = _rms_norm(cast(x), _gain(blk["ffn_norm"], unit_offset), eps)
    if "router" not in blk:
        y = _gated_mlp(u, cast(blk["w_gate"]), cast(blk["w_up"]), cast(blk["w_down"]))
        return x + y.astype(x.dtype), None
    tokens = u.reshape(b * l, dim)
    n_experts, held = blk["router"].shape[1], blk["w_gate"].shape[0]
    # Only the keys that depart from ``route``'s defaults (OLMoE's) are
    # passed: OLMoE's call stays ``route(u, wg, k)``, which is also what the
    # benchmark's tap of it (``olmoe_1b_7b_l1_reference.py``) wraps.
    keys = dict(router or {})
    if "router_bias" in blk:
        keys["bias"] = blk["router_bias"]
    routing = moe.route(tokens, blk["router"], top_k, **keys)
    y, slots, given = moe.expert_ffn(
        tokens, routing.choices, routing.weights,
        cast(blk["w_gate"]), cast(blk["w_up"]), cast(blk["w_down"]),
        n_experts=n_experts, lo=first_expert_held,
    )
    if "ws_gate" in blk:
        with jax.named_scope("moe_shared"):
            y = y + _gated_mlp(tokens, cast(blk["ws_gate"]), cast(blk["ws_up"]), cast(blk["ws_down"]))
    stats = _routed_stats(routing, slots, given, b * l, top_k, first_expert_held, held)
    return x + y.reshape(b, l, dim), stats


def _apply(
    params, batch, train: bool = False, ctx: ParallelContext = ParallelContext(),
    *, compute_dtype, remat: bool, residual_dtype=None, num_pred_heads: int = 1, **block_args,
):
    tokens = batch["tokens"]  # [B, L_local]: sequence-sharded over the axis
    l = tokens.shape[1]
    axis = ctx.axis_name
    offset = lax.axis_index(axis) * l if axis is not None else 0
    positions = offset + jnp.arange(l)
    x = params["tok_emb"][tokens].astype(residual_dtype or compute_dtype)
    block_fn = functools.partial(_block, axis=axis, compute_dtype=compute_dtype, **block_args)
    names = sorted(params["blocks"])
    blocks = [block_fn] * len(names)
    if remat and train:
        # Every block rematerialised, each keeping what the byte budget the
        # trainer resolved gives it (ops/remat.py; 0 = nothing, as ever).
        blocks = remat_lib.plan(
            block_fn, [(x, params["blocks"][name], positions) for name in names], ctx.remat_keep_bytes
        )
    routed, scanned = [], []
    for name, block in zip(names, blocks):
        x, stats = block(x, params["blocks"][name], positions)
        if stats is not None:
            (scanned if "ssm_positions" in stats else routed).append(stats)
    with jax.named_scope("lm_head"):
        norm_f = _gain(params["norm_f"], block_args["unit_offset"])
        x = _rms_norm(x.astype(compute_dtype), norm_f, block_args["eps"])
        head = params["head"] if "head" in params else params["tok_emb"].T
        logits = jnp.dot(x, head.astype(compute_dtype), preferred_element_type=jnp.float32)
        if num_pred_heads > 1:  # [B, L, heads of prediction, vocabulary]
            logits = logits.reshape(logits.shape[:2] + (num_pred_heads, -1))
    out = {"logits": logits}
    eva = [blk["eva_phi"].shape[0] for blk in params["blocks"].values() if "eva_phi" in blk]
    if eva:
        exact, far = eva_ops.pairs(l, block_args["eva"]["window"], block_args["eva"]["chunk"])
        scored = tokens.shape[0] * sum(eva)  # sequences x (heads, all layers)
        out["eva_counters"] = {
            "eva_pairs_exact": jnp.float32(scored * exact),
            "eva_pairs_summary": jnp.float32(scored * far),
        }
    if scanned:
        out["ssm_counters"] = jax.tree.map(lambda *leaves: sum(leaves), *scanned)  # all layers
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


def _update_correction_bias(params, out, *, speed: float):
    """The model's own rule for the routers' correction biases
    (``ModelSpec.after_update``; DeepSeek-V3, arXiv:2412.19437, section
    2.1.2): after a step, an expert that was sent more slots than the mean
    of its layer has its bias lowered by ``speed``, one that was sent fewer
    has it raised.  Each expert layer has its own bias and its own counts."""
    blocks = dict(params["blocks"])
    routed = [name for name in sorted(blocks) if "router_bias" in blocks[name]]
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


def _terms(out, batch, lb_coef: float, z_coef: float):
    """(the total the optimizer descends, CE, LB, Z).  With both
    coefficients 0 (a model trained on CE alone) the total IS the CE."""
    ce = _cross_entropy(out, batch)
    lb, z = _router_losses(out)
    if not lb_coef and not z_coef:
        return ce, ce, lb, z
    return ce + lb_coef * lb + z_coef * z, ce, lb, z


def _loss(out, batch, lb_coef: float, z_coef: float):
    return _terms(out, batch, lb_coef, z_coef)[0]


def _metrics(out, batch, lb_coef: float, z_coef: float):
    # ``loss`` is the total (the train step reports its own, equal, value
    # under the same key).
    loss, ce, lb, z = _terms(out, batch, lb_coef, z_coef)
    logits = out["logits"] if out["logits"].ndim == 3 else out["logits"][:, :, 0]  # the next token's head
    acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
    metrics = {"loss": loss, "ce": ce, "lb_loss": lb, "z_loss": z, "accuracy": acc}
    metrics.update(out.get("moe_counters", {}))
    metrics.update(out.get("eva_counters", {}))
    metrics.update(out.get("ssm_counters", {}))
    return metrics


def _predict(params, batch, ctx: ParallelContext = ParallelContext(), *, apply):
    return apply(params, batch, train=False, ctx=ctx)["logits"]


def _example_batch(batch_size: int, seq_len: int):
    return {
        "tokens": jnp.zeros((batch_size, seq_len), jnp.int32),
        "labels": jnp.zeros((batch_size, seq_len), jnp.int32),
    }


#: Leaves AdamW never decays: the correction biases; and, for a model that
#: decays its matrices alone (``decay_matrices_only``), the gains and EVA's vectors.
_NEVER_DECAYED = ("router_bias",)
_NOT_MATRICES = _NEVER_DECAYED + (
    "attn_norm", "ffn_norm", "norm_f", "kv_norm", "q_norm", "k_norm", "eva_phi", "eva_mu",
    "norm", "ssm_norm", "A_log", "D", "dt_bias", "conv_b",
)


def _is_decayed(params, skip=_NEVER_DECAYED):
    """AdamW's weight-decay mask: every leaf but those named in ``skip``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, _: getattr(path[-1], "key", None) not in skip, params
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
    (0: this model's depth).  Every held count 0 = all."""
    hybrid = None
    if hybrid_override_pattern is not None:
        pattern = str(hybrid_override_pattern)
        if len(pattern) != num_hidden_layers or set(pattern) - set(PATTERN_KINDS):
            raise ValueError(
                f"hybrid_override_pattern must give {num_hidden_layers} layers a letter of {sorted(PATTERN_KINDS)} "
                f"({PATTERN_KINDS}), got {pattern!r}"
            )
        if layer_types is not None or kv_lora_rank or attention_class != "mha":
            raise ValueError("hybrid_override_pattern names every layer's kind: layer_types, latent attention and attention_class do not go with it")
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
        # what the step's counters and the correction bias's rule read
        layer_types = tuple("moe" if kind == "E" else "dense" for kind in pattern)
        hybrid = {"state": ssm_state_size, "chunk": chunk_size, "head_dim": head_dim or hidden_size // num_attention_heads}
    elif mlp_hidden_act != "silu" or mamba_heads_held or kv_heads_held:
        raise ValueError("mlp_hidden_act, mamba_heads_held and kv_heads_held go with hybrid_override_pattern: every other block's feed-forward is gated silu")
    if layer_types is None:
        dense = min(first_k_dense_replace, num_hidden_layers)
        layer_types = ("dense",) * dense + ("moe",) * (num_hidden_layers - dense)
    layer_types = tuple(layer_types)
    if len(layer_types) != num_hidden_layers or set(layer_types) - set(LAYER_TYPES):
        raise ValueError(
            f"layer_types must name {num_hidden_layers} layers from {LAYER_TYPES}, "
            f"got {layer_types!r}"
        )
    latent = None
    if kv_lora_rank:
        if q_lora_rank is not None:
            raise ValueError("a low-rank query projection (q_lora_rank) is not supported: no cell runs one")
        if min(qk_nope_head_dim, v_head_dim) <= 0 or qk_rope_head_dim <= 0 or qk_rope_head_dim % 2:
            raise ValueError(
                f"latent attention needs qk_nope_head_dim, v_head_dim and an even qk_rope_head_dim, got "
                f"{qk_nope_head_dim} / {v_head_dim} / {qk_rope_head_dim}"
            )
        latent = {"kv_lora_rank": kv_lora_rank, "nope": qk_nope_head_dim, "rot": qk_rope_head_dim, "v": v_head_dim}
    elif hidden_size % num_attention_heads or (hidden_size // num_attention_heads) % 2:
        raise ValueError(
            f"hidden_size {hidden_size} must split into {num_attention_heads} heads "
            f"of even width (rotary pairs)"
        )
    if attention_class not in ATTENTION_CLASSES or (latent and attention_class != "mha"):
        raise ValueError(f"attention_class {attention_class!r}: known are {ATTENTION_CLASSES} (latent attention goes with 'mha')")
    eva = None
    if attention_class == "eva":
        if chunk_size <= 0 or window_size <= 0 or window_size % chunk_size:
            raise ValueError(f"EVA attention needs a window_size in whole chunks, got {window_size} / {chunk_size}")
        if not 0 <= heads_held <= num_attention_heads:
            raise ValueError(f"heads_held {heads_held} of {num_attention_heads} heads")
        eva = {"window": window_size, "chunk": chunk_size}
    elif heads_held and hybrid is None:
        raise ValueError("heads_held goes with attention_class 'eva' or a hybrid_override_pattern: no other attention takes a share of the heads")
    if num_pred_heads < 1 or (num_pred_heads > 1 and tie_word_embeddings):
        raise ValueError(f"num_pred_heads {num_pred_heads}: at least one, and more than one only with an untied head")
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
    correction_bias = topk_method == "noaux_tc" and "moe" in layer_types
    apply = functools.partial(
        _apply, n_heads=num_attention_heads, top_k=num_experts_per_tok,
        theta=float(rope_theta), eps=float(rms_norm_eps),
        compute_dtype=jnp.dtype(compute_dtype), remat=remat,
        rot=qk_rope_head_dim if latent else 0, interleave=bool(rope_interleave),
        router={
            key: value
            for key, value, default in (
                ("scoring_func", scoring_func, "softmax"),
                ("norm_topk_prob", bool(norm_topk_prob), False),
                ("routed_scaling_factor", float(routed_scaling_factor), 1.0),
            )
            if value != default
        },
        first_expert_held=first_expert_held, eva=eva, unit_offset=bool(norm_add_unit_offset), hybrid=hybrid,
        residual_dtype=jnp.float32 if fp32_skip_add else None, num_pred_heads=num_pred_heads,
    )
    skip = _NOT_MATRICES if decay_matrices_only else _NEVER_DECAYED
    coefs = dict(lb_coef=router_aux_loss_coef, z_coef=router_z_loss_coef)
    init = None
    if hybrid is not None:
        init = functools.partial(
            _init_hybrid_params, pattern=pattern, vocab_size=vocab_size, hidden_size=hidden_size, init_std=init_std,
            residual_layers=(residual_layers or num_hidden_layers) if rescale_prenorm_residual else 1,
            mamba_heads=m_held, mamba_head_dim=mamba_head_dim,
            groups=m_held * n_groups // mamba_num_heads if mamba_num_heads else 0,
            state=ssm_state_size, conv_kernel=conv_kernel, dt_range=(time_step_min, time_step_max, time_step_floor),
            q_heads=q_held, kv_heads=kv_held, head_dim=hybrid["head_dim"], num_experts=num_experts,
            experts_held=held, latent=moe_latent_size, expert_width=moe_intermediate_size,
            shared_width=moe_shared_expert_intermediate_size,
        )
    return ModelSpec(
        name="moe_lm",
        init=init or functools.partial(
            _init_params, vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size, num_experts=num_experts,
            layer_types=layer_types, tie_word_embeddings=tie_word_embeddings,
            n_heads=num_attention_heads, latent=latent,
            moe_intermediate_size=moe_intermediate_size, experts_held=experts_held,
            n_shared_experts=n_shared_experts, correction_bias=correction_bias,
            init_std=init_std, eva_heads=(heads_held or num_attention_heads) if eva else 0,
            unit_offset=norm_add_unit_offset, num_pred_heads=num_pred_heads,
        ),
        apply=apply,
        loss=functools.partial(_loss, **coefs),
        metrics=functools.partial(_metrics, **coefs),
        optimizer=optax.adamw(
            optax.linear_schedule(0.0, learning_rate, lr_warmup_steps) if lr_warmup_steps else learning_rate,
            b1=0.9, b2=0.95, eps=1e-8, weight_decay=weight_decay,
            # a correction bias gets no gradient (it only chooses) and no decay:
            # Adam's update of a leaf whose gradient is always 0 is exactly 0
            mask=functools.partial(_is_decayed, skip=skip) if correction_bias or decay_matrices_only else None,
        ),
        feed=lm_feed,
        example_batch=functools.partial(_example_batch, seq_len=seq_len),
        batch_shard_dim=1,
        predict=functools.partial(_predict, apply=apply),
        step_counters={
            **(MOE_COUNTERS if "moe" in layer_types else {}), **(EVA_COUNTERS if eva else {}),
            **(SSM_COUNTERS if hybrid and "M" in pattern else {}),
        },
        after_update=(
            functools.partial(_update_correction_bias, speed=float(bias_update_speed))
            if correction_bias else None
        ),
        rematerialises=bool(remat),
    )
