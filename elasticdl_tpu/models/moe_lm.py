"""Decoder-only LM with a modern block: rotary positions, QK-norm, a gated
(SwiGLU) feed-forward that is dense or a dropless mixture of experts layer
by layer, and a tied or untied head.  ``model_spec``'s defaults and
parameter names are OLMoE's (``OLMoE-1B-7B-0125``: every layer ``moe``, 64
experts, 8 a token, untied head); the published keys are the arguments.

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

``LB`` is ``transformers``' ``load_balancing_loss_func`` (the layers'
router outputs concatenated, one ``f`` and one ``P`` for the model); ``Z``
is the OLMoE paper's router z-loss.  ``apply`` returns what ``loss`` and
``metrics`` need — logits, ``f``, ``P``, ``Z`` and the expert layers' slot
counts: a small pytree, no second forward.

Parallelism: as ``transformer_lm``'s sequence path.  ``batch_shard_dim=1``:
the mesh axis shards the SEQUENCE, attention runs over the ring
(``ops/ring_attention``), rotary positions are global (the device's axis
index times its chunk), parameters are replicated with psum'd gradients
(the AllReduce strategy).  Every device holds ALL experts and routes its
own tokens; ``f`` and ``P`` are averaged over the axis before they are
multiplied, so the load-balancing loss is the global one.  Expert
parallelism (experts sharded over the mesh, tokens exchanged) is out of
scope here: ROADMAP R5.

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

from elasticdl_tpu.data.codecs import lm_feed
from elasticdl_tpu.models.spec import ModelSpec
from elasticdl_tpu.ops import moe
from elasticdl_tpu.ops.embedding import ParallelContext
from elasticdl_tpu.ops.ring_attention import ring_attention

#: The expert layers' counts a step reports (``ModelSpec.step_counters``:
#: summed over devices by the trainer and over steps by the worker), with
#: the help text of their gauges ``edl_moe_*_total``.
MOE_COUNTERS = {
    "moe_slots": "(token, expert) slots the routers filled, summed over "
    "expert layers, training steps and devices",
    "moe_slots_computed": "rows the experts' grouped matmuls ran (the sum of "
    "their group sizes): equals moe_slots, or a slot was dropped",
    "moe_expert_load_max": "slots on a device's fullest expert, summed over "
    "expert layers, training steps and devices",
    "moe_expert_load_mean": "slots on a device's average expert, summed likewise",
}
LAYER_TYPES = ("moe", "dense")


def _rms_norm(x, scale, eps):
    # Statistics and arithmetic in f32, ONE downcast (transformer_lm's form).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * scale).astype(x.dtype)


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


def _init_params(
    rng, vocab_size: int, hidden_size: int, intermediate_size: int, num_experts: int,
    layer_types: Sequence[str], tie_word_embeddings: bool, init_std: float = 0.02,
) -> Dict[str, Any]:
    d, f, e = hidden_size, intermediate_size, num_experts
    ks = iter(jax.random.split(rng, 2 + 8 * len(layer_types)))

    def normal(shape):
        return jax.random.normal(next(ks), shape, jnp.float32) * init_std

    params: Dict[str, Any] = {
        "tok_emb": normal((vocab_size, d)),
        "norm_f": jnp.ones((d,), jnp.float32),
        "blocks": {},
    }
    if not tie_word_embeddings:
        params["head"] = normal((d, vocab_size))
    for i, kind in enumerate(layer_types):
        blk = {
            "attn_norm": jnp.ones((d,), jnp.float32),
            "wq": normal((d, d)), "wk": normal((d, d)), "wv": normal((d, d)),
            "wo": normal((d, d)),
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
            "ffn_norm": jnp.ones((d,), jnp.float32),
        }
        if kind == "moe":
            blk["router"] = normal((d, e))
            blk["w_gate"], blk["w_up"] = normal((e, d, f)), normal((e, d, f))
            blk["w_down"] = normal((e, f, d))
        else:
            blk["w_gate"], blk["w_up"] = normal((d, f)), normal((d, f))
            blk["w_down"] = normal((f, d))
        # Zero-padded names keep sorted() in layer order past nine layers.
        params["blocks"][f"b{i:02d}"] = blk
    return params


def _block(x, blk, positions, *, axis, n_heads, top_k, theta, eps, compute_dtype):
    """One block: attention and a feed-forward whose kind is read off its
    parameters (a ``router`` makes it ``moe``).  Returns (x, the layer's
    router sums and slot counts — None for a dense layer)."""
    b, l, dim = x.shape
    cast = lambda w: w.astype(compute_dtype)  # noqa: E731
    a = _rms_norm(x, blk["attn_norm"], eps)
    q = _qk_norm(a @ cast(blk["wq"]), blk["q_norm"], eps)
    k = _qk_norm(a @ cast(blk["wk"]), blk["k_norm"], eps)
    v = a @ cast(blk["wv"])
    heads = lambda t: t.reshape(b, l, n_heads, dim // n_heads)  # noqa: E731
    q, k = rope(heads(q), positions, theta), rope(heads(k), positions, theta)
    att = ring_attention(q, k, heads(v), axis_name=axis, causal=True)
    x = x + att.reshape(b, l, dim) @ cast(blk["wo"])
    u = _rms_norm(x, blk["ffn_norm"], eps)
    if "router" not in blk:
        h = jax.nn.silu(u @ cast(blk["w_gate"])) * (u @ cast(blk["w_up"]))
        return x + h @ cast(blk["w_down"]), None
    tokens = u.reshape(b * l, dim)
    routing = moe.route(tokens, blk["router"], top_k)
    y, sizes = moe.expert_ffn(
        tokens, routing.choices, routing.weights,
        cast(blk["w_gate"]), cast(blk["w_up"]), cast(blk["w_down"]),
    )
    f, p, z = moe.router_stats(routing)
    sizes = sizes.astype(jnp.float32)
    stats = {
        "f": f, "p": p, "z": z, "pairs": jnp.float32(b * l),
        "moe_slots": jnp.float32(b * l * top_k),
        "moe_slots_computed": jnp.sum(sizes),
        "moe_expert_load_max": jnp.max(sizes),
        "moe_expert_load_mean": jnp.mean(sizes),
    }
    return x + y.reshape(b, l, dim), stats


def _apply(
    params, batch, train: bool = False, ctx: ParallelContext = ParallelContext(),
    *, n_heads: int, top_k: int, theta: float, eps: float, compute_dtype, remat: bool,
    **_,
):
    tokens = batch["tokens"]  # [B, L_local]: sequence-sharded over the axis
    l = tokens.shape[1]
    axis = ctx.axis_name
    offset = lax.axis_index(axis) * l if axis is not None else 0
    positions = offset + jnp.arange(l)
    x = params["tok_emb"][tokens].astype(compute_dtype)
    block_fn = functools.partial(
        _block, axis=axis, n_heads=n_heads, top_k=top_k, theta=theta, eps=eps,
        compute_dtype=compute_dtype,
    )
    if remat and train:
        block_fn = jax.checkpoint(block_fn)
    routed = []
    for name in sorted(params["blocks"]):
        x, stats = block_fn(x, params["blocks"][name], positions)
        if stats is not None:
            routed.append(stats)
    with jax.named_scope("lm_head"):
        x = _rms_norm(x, params["norm_f"], eps)
        head = params["head"] if "head" in params else params["tok_emb"].T
        logits = jnp.dot(x, head.astype(compute_dtype), preferred_element_type=jnp.float32)
    out = {"logits": logits}
    if routed:
        total = jax.tree.map(lambda *leaves: sum(leaves), *routed)
        with jax.named_scope("moe_router"):
            # Shares over every (layer, token) pair of the GLOBAL batch: the
            # load-balancing loss multiplies two means, so they are taken
            # over the axis before the product, not after.
            f, p, z, pairs = (total[key] for key in ("f", "p", "z", "pairs"))
            if axis is not None:
                # Trace-time import, as transformer_lm's: a module-level one
                # closes the ops -> parallel -> ops import cycle.
                from elasticdl_tpu.parallel.collectives import psum

                f, p, z, pairs = (psum(t, axis) for t in (f, p, z, pairs))
            out["router"] = {"f": f / pairs, "p": p / pairs, "z": z / pairs}
        out["moe_counters"] = {key: total[key] for key in MOE_COUNTERS}
    return out


def _cross_entropy(out, batch):
    with jax.named_scope("lm_head"):
        return jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(out["logits"], batch["labels"])
        )


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
    """(the total the optimizer descends, CE, LB, Z)."""
    ce = _cross_entropy(out, batch)
    lb, z = _router_losses(out)
    return ce + lb_coef * lb + z_coef * z, ce, lb, z


def _loss(out, batch, lb_coef: float, z_coef: float):
    return _terms(out, batch, lb_coef, z_coef)[0]


def _metrics(out, batch, lb_coef: float, z_coef: float):
    # ``loss`` is the total (the train step reports its own, equal, value
    # under the same key).
    loss, ce, lb, z = _terms(out, batch, lb_coef, z_coef)
    acc = jnp.mean((jnp.argmax(out["logits"], -1) == batch["labels"]).astype(jnp.float32))
    metrics = {"loss": loss, "ce": ce, "lb_loss": lb, "z_loss": z, "accuracy": acc}
    metrics.update(out.get("moe_counters", {}))
    return metrics


def _predict(params, batch, ctx: ParallelContext = ParallelContext(), *, apply):
    return apply(params, batch, train=False, ctx=ctx)["logits"]


def _example_batch(batch_size: int, seq_len: int):
    return {
        "tokens": jnp.zeros((batch_size, seq_len), jnp.int32),
        "labels": jnp.zeros((batch_size, seq_len), jnp.int32),
    }


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
    remat: bool = True,
) -> ModelSpec:
    """``layer_types`` names each layer's feed-forward, ``"moe"`` or
    ``"dense"`` (both gated, ``intermediate_size`` wide); None = every one
    of the ``num_hidden_layers`` is ``moe``, as in OLMoE."""
    layer_types = tuple(layer_types or ("moe",) * num_hidden_layers)
    if len(layer_types) != num_hidden_layers or set(layer_types) - set(LAYER_TYPES):
        raise ValueError(
            f"layer_types must name {num_hidden_layers} layers from {LAYER_TYPES}, "
            f"got {layer_types!r}"
        )
    if hidden_size % num_attention_heads or (hidden_size // num_attention_heads) % 2:
        raise ValueError(
            f"hidden_size {hidden_size} must split into {num_attention_heads} heads "
            f"of even width (rotary pairs)"
        )
    if num_experts_per_tok > num_experts:
        raise ValueError(f"top-{num_experts_per_tok} of {num_experts} experts")
    apply = functools.partial(
        _apply, n_heads=num_attention_heads, top_k=num_experts_per_tok,
        theta=float(rope_theta), eps=float(rms_norm_eps),
        compute_dtype=jnp.dtype(compute_dtype), remat=remat,
    )
    coefs = dict(lb_coef=router_aux_loss_coef, z_coef=router_z_loss_coef)
    return ModelSpec(
        name="moe_lm",
        init=functools.partial(
            _init_params, vocab_size=vocab_size, hidden_size=hidden_size,
            intermediate_size=intermediate_size, num_experts=num_experts,
            layer_types=layer_types, tie_word_embeddings=tie_word_embeddings,
        ),
        apply=apply,
        loss=functools.partial(_loss, **coefs),
        metrics=functools.partial(_metrics, **coefs),
        optimizer=optax.adamw(
            learning_rate, b1=0.9, b2=0.95, eps=1e-8, weight_decay=weight_decay
        ),
        feed=lm_feed,
        example_batch=functools.partial(_example_batch, seq_len=seq_len),
        batch_shard_dim=1,
        predict=functools.partial(_predict, apply=apply),
        step_counters=MOE_COUNTERS if "moe" in layer_types else {},
    )
