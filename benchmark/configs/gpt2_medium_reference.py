"""Plain reference of the decoder the ``gpt2_medium`` configuration runs:
float32 ``jax.numpy``, matmul precision ``highest``, attention as an
explicit masked softmax over the full L x L scores, no kernels.

GPT-2 medium's shape (24 blocks, width 1024, 16 heads, 1024 positions,
50257 tokens, learned positions, tied head, tanh GELU) with the program's
departures from the published block, followed here so that both compute
the same function (the configuration file lists them under ``assumed``):
RMSNorm in place of LayerNorm, no biases, no dropout.

    x   = tok_emb[tokens] + pos_emb[positions]
    x  += attention(rmsnorm(x) Wqkv) Wo ;  x += gelu(rmsnorm(x) W1) W2   (x 24)
    logits = rmsnorm(x) tok_emb^T ; loss = mean cross-entropy of next token

It trains the first task (``minibatches_per_task`` AdamW steps, in order)
from the SAME initial weights as the system (``model_spec.init(key(0))``:
data here) and reports the mean of the steps' losses, which is what the
worker reports for a task.  To hold a whole minibatch at float32 it takes
the gradient over micro-batches of ``MICRO`` sequences and averages them,
which is the minibatch's gradient exactly (equal sizes, mean loss), and
rematerialises each block (``jax.checkpoint`` changes memory, not values).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
from reference_common import device_report, parse_args, read_records  # noqa: E402

MICRO = 4


def main() -> None:
    config, traffic, data, out = parse_args()
    import jax
    import jax.numpy as jnp
    import optax

    jax.config.update("jax_default_matmul_precision", "highest")
    p = config["model_params"]
    heads, seq = int(p["n_heads"]), int(p["seq_len"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])
    micro = min(MICRO, mb)

    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), spec.init(jax.random.key(0)))
    block_names = sorted(params["blocks"])

    def rmsnorm(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * scale

    def block(x, blk):
        b, l, d = x.shape
        qkv = (rmsnorm(x, blk["ln1"]) @ blk["wqkv"]).reshape(b, l, 3 * heads, d // heads)
        q, k, v = qkv[:, :, :heads], qkv[:, :, heads : 2 * heads], qkv[:, :, 2 * heads :]
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d // heads) ** -0.5
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
        att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
        x = x + att.reshape(b, l, d) @ blk["wo"]
        return x + jax.nn.gelu(rmsnorm(x, blk["ln2"]) @ blk["w1"]) @ blk["w2"]

    def loss_fn(params, tokens, labels):
        x = params["tok_emb"][tokens] + params["pos_emb"][jnp.arange(tokens.shape[1])][None]
        for name in block_names:
            x = jax.checkpoint(block)(x, params["blocks"][name])
        logits = rmsnorm(x, params["ln_f"]) @ params["tok_emb"].T
        return optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    optimizer = optax.adamw(3e-4)

    @jax.jit
    def apply(params, opt_state, grads):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    records = read_records(data, steps * mb)
    toks = np.stack([np.frombuffer(r, "<i4") for r in records])
    assert toks.shape[1] == seq + 1
    opt_state = optimizer.init(params)
    losses = []
    for i in range(steps):
        batch = toks[i * mb : (i + 1) * mb]
        total, grads = 0.0, None
        for j in range(0, mb, micro):
            part = batch[j : j + micro]
            loss, g = grad_fn(params, part[:, :-1], part[:, 1:])
            total += float(loss)
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        n = mb // micro
        grads = jax.tree.map(lambda a: a / n, grads)
        params, opt_state = apply(params, opt_state, grads)
        losses.append(total / n)
    with open(out, "w") as f:
        json.dump({"loss": float(np.mean(losses)), "step_losses": losses, "device": device_report()}, f)


if __name__ == "__main__":
    main()
