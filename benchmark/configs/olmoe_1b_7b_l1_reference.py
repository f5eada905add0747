"""Plain reference of the decoder the ``olmoe_1b_7b_l1`` configuration runs:
float32 ``jax.numpy``, matmul precision ``highest``, attention as an
explicit masked softmax (in blocks of queries: 16 heads x 4096 x 4096
float32 scores are 1 GiB a sequence), and the expert layer DENSE: every
expert on every token, times a ``[T, E]`` matrix that holds the router's
weight at the chosen experts and 0 elsewhere.  No sort, no groups, no
kernels, and no code of ``elasticdl_tpu/ops/moe.py`` or
``elasticdl_tpu/models/moe_lm.py`` (the reference takes ONE thing of the
model: ``model_spec.init(key(0))``, whose weights are data here; the
``checks`` at the end of this file run the model itself, as the thing
measured).

OLMoE-1B-7B's block (arXiv:2409.02060; ``transformers``' ``OlmoeModel``),
with ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) * g``, eps 1e-5:

    x   = tok_emb[tokens]                                             (no position table)
    a   = rmsnorm(x, attn_norm)
    q,k,v = a Wq, a Wk, a Wv                                          (no bias, no clip)
    q   = rmsnorm(q, q_norm) ; k = rmsnorm(k, k_norm)                 (over all hidden_size columns, BEFORE the split into heads)
    q,k = rope(q), rope(k)                                            (per head; theta 10000; rotate-half pairing (i, i + hd/2))
    x  += causal_softmax(q k^T / sqrt(hd)) v  Wo
    u   = rmsnorm(x, ffn_norm)
    r   = u Wg ; p = softmax(r) over the experts                       (float32)
    (w_1..w_k, e_1..e_k) = top-k of p                                  (norm_topk_prob false: NOT renormalised)
    x  += sum_i  w_i * ( silu(u Wgate[e_i]) * (u Wup[e_i]) ) Wdown[e_i]
    logits = rmsnorm(x, norm_f) Whead                                 (untied, float32)
    loss = CE(logits, next token) + 0.01 * LB + 0.001 * Z
    LB  = E * sum_{i,e} f[i,e] * P[e],  f[i,e] = share of (layer, token) pairs whose i-th choice is e,  P[e] = their mean p[e]
    Z   = mean over (layer, token) pairs of logsumexp(r)^2

``LB`` is ``transformers``' ``load_balancing_loss_func`` (all layers'
router outputs concatenated; no gradient through ``f``), ``Z`` the paper's
router z-loss.  Departures from the published model are the
configuration's ``assumed`` list (coefficients and optimizer from memory,
init, no dropout).

It trains the first task (``minibatches_per_task`` AdamW steps, in order)
from the same initial weights as the system and reports the mean of the
steps' losses, which is what the worker reports for a task.  To hold
float32 state (10 GB at the published widths) beside a step's activations
it takes the gradient a micro-batch of ``MICRO`` sequences at a time.  The
cross-entropy and ``Z`` are means, so their gradients average exactly;
``LB`` multiplies two means over the WHOLE minibatch, so a first
forward-only pass over the micro-batches computes the minibatch's ``f``,
and the second pass differentiates ``E * sum(f * P_micro)``, whose average
over micro-batches is ``LB`` and its gradient exactly (``P`` is a mean,
``f`` carries no gradient).  Blocks, query blocks and expert chunks are
rematerialised (``jax.checkpoint`` changes memory, not values), and the
query blocks and expert chunks are walked by ``lax.map`` (one compiled body
each: at matmul precision ``highest`` the unrolled form took 110 s to
compile of the child's 300).

After the loss, in the same process, a reading for each of the
configuration's ``checks``: the router's precision, which the first task's
mean loss cannot see.  The model's own entry — ``spec.apply`` of
``moe_lm.model_spec`` at the job's dtypes, the function the trainer's step
differentiates — runs on the run's first minibatch from the initial weights,
and what it hands ``elasticdl_tpu.ops.moe.route`` and gets back is read
(:func:`routers_of_the_model`) against the float64 product, on the host, of
those rows and the float32 router parameter (:func:`router_readings`).  The
child reports the bare readings as ``"checks": {name: value}``; ``run.py``
holds each against the limit in the configuration's file.
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
EXPERT_CHUNK = 8


def build(p: dict):
    """(forward, loss_terms) for the model parameters ``p`` (the published
    keys).  Everything is computed in the precision of the weights it is
    given (float32 here; the softmaxes, the router's statistics and the
    cross-entropy in float32 whatever that is)."""
    import jax
    import jax.numpy as jnp

    heads, top_k = int(p["num_attention_heads"]), int(p["num_experts_per_tok"])
    theta, eps = float(p.get("rope_theta", 10000.0)), float(p.get("rms_norm_eps", 1e-5))

    def rmsnorm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(x.dtype)

    def rope(x):  # [B, L, H, hd]
        l, hd = x.shape[1], x.shape[-1]
        freq = theta ** (-2.0 * jnp.arange(hd // 2) / hd)
        ang = jnp.arange(l)[:, None] * freq[None, :]
        cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :].astype(x.dtype)
        sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :].astype(x.dtype)
        rotated = jnp.concatenate([-x[..., hd // 2:], x[..., : hd // 2]], -1)
        return x * cos + rotated * sin

    @jax.checkpoint
    def attend(q_blk, k, v, first):  # q_blk [B, bq, H, hd]; k, v [B, L, H, hd]
        hd = q_blk.shape[-1]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / np.sqrt(hd)
        q_pos = first + jnp.arange(q_blk.shape[1])
        mask = q_pos[:, None] >= jnp.arange(k.shape[1])[None, :]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores.astype(jnp.float32), -1).astype(q_blk.dtype), v)

    @jax.checkpoint
    def experts(u, wg, wu, wd, m):  # u [T, D]; wg, wu [c, D, F]; wd [c, F, D]; m [T, c]
        h = jax.nn.silu(jnp.einsum("td,cdf->tcf", u, wg)) * jnp.einsum("td,cdf->tcf", u, wu)
        return jnp.einsum("tc,tcd->td", m, jnp.einsum("tcf,cfd->tcd", h, wd))

    def block(x, blk):
        b, l, d = x.shape
        a = rmsnorm(x, blk["attn_norm"])
        q = rmsnorm(a @ blk["wq"], blk["q_norm"]).reshape(b, l, heads, d // heads)
        k = rmsnorm(a @ blk["wk"], blk["k_norm"]).reshape(b, l, heads, d // heads)
        v = (a @ blk["wv"]).reshape(b, l, heads, d // heads)
        q, k = rope(q), rope(k)
        bq = min(QUERY_BLOCK, l)
        blocks = jnp.moveaxis(q.reshape(b, l // bq, bq, heads, d // heads), 1, 0)
        att = jax.lax.map(lambda blk_: attend(blk_[0], k, v, blk_[1]), (blocks, jnp.arange(0, l, bq)))
        att = jnp.moveaxis(att, 0, 1)
        x = x + att.reshape(b, l, d) @ blk["wo"]
        u = rmsnorm(x, blk["ffn_norm"]).reshape(b * l, d)
        r = (u @ blk["router"]).astype(jnp.float32)
        prob = jax.nn.softmax(r, -1)
        n_experts = prob.shape[-1]
        chosen = jnp.argsort(-prob, axis=-1, stable=True)[:, :top_k]  # [T, k], best first
        onehot = jax.nn.one_hot(chosen, n_experts, dtype=jnp.float32)  # [T, k, E]
        m = jnp.sum(onehot * prob[:, None, :], 1)  # [T, E]: p at the chosen experts
        c = min(EXPERT_CHUNK, n_experts)
        chunked = lambda name: blk[name].reshape((n_experts // c, c) + blk[name].shape[1:])  # noqa: E731
        m_chunks = jnp.moveaxis(m.reshape(-1, n_experts // c, c), 1, 0).astype(u.dtype)
        y = jnp.sum(jax.lax.map(
            lambda part: experts(u, *part), (chunked("w_gate"), chunked("w_up"), chunked("w_down"), m_chunks)), 0)
        stats = {
            "f_sum": jnp.sum(onehot, 0), "p_sum": jnp.sum(prob, 0),
            "z_sum": jnp.sum(jax.nn.logsumexp(r, -1) ** 2), "pairs": float(b * l),
        }
        return x + y.reshape(b, l, d), stats

    def forward(params, tokens):
        """(float32 logits, the router's sums over every (layer, token) pair)."""
        x = params["tok_emb"][tokens]
        total = None
        for name in sorted(params["blocks"]):
            x, stats = jax.checkpoint(block)(x, params["blocks"][name])
            total = stats if total is None else jax.tree.map(jnp.add, total, stats)
        logits = (rmsnorm(x, params["norm_f"]) @ params["head"]).astype(jnp.float32)
        return logits, total

    def loss_terms(params, tokens, labels, f=None):
        """{ce, lb_loss, z_loss, loss, counts} of one (micro-)batch; ``f``
        [k, E] is the whole minibatch's, its own when None."""
        import optax

        logits, s = forward(params, tokens)
        ce = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
        own_f = s["f_sum"] / s["pairs"]
        f = jax.lax.stop_gradient(own_f if f is None else f)
        lb = s["p_sum"].shape[0] * jnp.sum(f * (s["p_sum"] / s["pairs"])[None, :])
        z = s["z_sum"] / s["pairs"]
        loss = ce + float(p.get("router_aux_loss_coef", 0.01)) * lb + float(p.get("router_z_loss_coef", 0.001)) * z
        return {"loss": loss, "ce": ce, "lb_loss": lb, "z_loss": z, "f_sum": s["f_sum"], "pairs": s["pairs"], "logits": logits}

    return forward, loss_terms


def router_readings(u, wg, logits, choices, top_k: int) -> dict:
    """A router's float32 ``logits`` [T, E] and ``choices`` [T, k] on the
    rows ``u`` [T, D] and the weight ``wg`` [D, E], against the float64
    product on the host: the largest error of a logit relative to the
    largest logit, and the number of (token, rank) choices that differ from
    float64's (stable, best first)."""
    want_r = np.asarray(u, np.float64) @ np.asarray(wg, np.float64)
    want_c = np.argsort(-want_r, axis=-1, kind="stable")[:, :top_k]
    return {
        "router_logits": float(np.abs(np.asarray(logits, np.float64) - want_r).max() / np.abs(want_r).max()),
        "router_choices_differing": int(np.sum(np.asarray(choices) != want_c)),
    }


def routers_of_the_model(spec, before_the_call=None):
    """A compiled ``(params, tokens, labels) -> [{"u", "logits", "choices"}]``,
    one entry an expert layer in layer order: the rows the MODEL's own entry
    ``spec.apply`` (``train=True``, at the job's dtypes) hands
    ``elasticdl_tpu.ops.moe.route`` on a minibatch, and the float32 logits
    and the choices it gets back.  The op is tapped where the model looks it
    up (the module's attribute) while ``apply`` is traced, and at no other
    time; a model that routes by another function is seen handing it
    nothing, and the list is empty.  What of ``apply`` the routers' inputs do
    not need, the compiler prunes.  ``before_the_call(u, wg) -> (u, wg)``
    stands for a step that changes the operands on their way to the op (the
    control)."""
    import jax

    from elasticdl_tpu.ops import moe

    def run(params, tokens, labels):
        real, seen = moe.route, []

        def tapped(u, wg, k):
            if before_the_call is not None:
                u, wg = before_the_call(u, wg)
            routing = real(u, wg, k)
            seen.append({"u": u, "logits": routing.logits, "choices": routing.choices})
            return routing

        moe.route = tapped
        try:
            # at jax's own default matmul precision, as the job runs: this
            # process's ``highest`` would lend the model a precision it does not ask for
            with jax.default_matmul_precision(None):
                spec.apply(params, {"tokens": tokens, "labels": labels}, train=True)
        finally:
            moe.route = real
        return seen

    return jax.jit(run)


def router_checks(routed: list, params, top_k: int) -> dict:
    """The configuration's two ``checks``, bare readings: every expert
    layer's router as the model ran it (``routed``, of
    :func:`routers_of_the_model`) against the float64 product of the rows it
    was handed and the layer's float32 router PARAMETER (not the operand the
    op was handed: a weight rounded on the way shows); the worst layer.
    ``{}`` where the model did not hand the op every expert layer's rows:
    ``run.py`` then finds no reading."""
    names = [name for name in sorted(params["blocks"]) if "router" in params["blocks"][name]]
    if not names or len(routed) != len(names):
        return {}
    readings = [
        router_readings(r["u"], params["blocks"][name]["router"], r["logits"], r["choices"], top_k)
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
    _, loss_terms = build(p)

    shares = jax.jit(lambda params, tokens, labels: loss_terms(params, tokens, labels)["f_sum"])

    def micro_loss(params, tokens, labels, f):
        terms = loss_terms(params, tokens, labels, f)
        return terms["loss"], {k: terms[k] for k in ("ce", "lb_loss", "z_loss")}

    @functools.partial(jax.jit, donate_argnums=(0,))
    def add_grad(total, params, tokens, labels, f):
        """``total`` + this micro-batch's gradient, in ``total``'s memory
        (three copies of 2.5 GB of float32 are what the chip has room for)."""
        (loss, aux), grads = jax.value_and_grad(micro_loss, has_aux=True)(params, tokens, labels, f)
        return jax.tree.map(jnp.add, total, grads), loss, aux

    optimizer = optax.adamw(
        float(p.get("learning_rate", 4e-4)), b1=0.9, b2=0.95, eps=1e-8,
        weight_decay=float(p.get("weight_decay", 0.1)),
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(params, opt_state, total, n):
        grads = jax.tree.map(lambda a: a / n, total)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    records = read_records(data, steps * mb)
    toks = np.stack([np.frombuffer(r, "<i4") for r in records])
    assert toks.shape[1] == seq + 1
    opt_state = optimizer.init(params)
    losses, terms = [], []
    n_layers = len(params["blocks"])
    for i in range(steps):
        batch = toks[i * mb : (i + 1) * mb]
        parts = [batch[j : j + micro] for j in range(0, mb, micro)]
        n = len(parts)
        f = sum(shares(params, part[:, :-1], part[:, 1:]) for part in parts) / (mb * seq * n_layers)
        loss_sum, aux_sum = 0.0, None
        total = jax.tree.map(jnp.zeros_like, params)
        for part in parts:
            total, loss, aux = add_grad(total, params, part[:, :-1], part[:, 1:], f)
            loss_sum += float(loss)
            aux = {k: float(v) for k, v in aux.items()}
            aux_sum = aux if aux_sum is None else {k: aux_sum[k] + aux[k] for k in aux}
        params, opt_state = apply(params, opt_state, total, float(n))
        losses.append(loss_sum / n)
        print(f"step {i}: loss {losses[-1]:.6f} at {time.time() - t_start:.1f} s", flush=True)
        terms.append({k: v / n for k, v in aux_sum.items()})
    result = {"loss": float(np.mean(losses)), "step_losses": losses, "step_terms": terms, "device": device_report()}
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
