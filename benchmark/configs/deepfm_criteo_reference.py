"""Plain reference of DeepFM (Guo et al. 2017, arXiv:1703.04247) for the
``deepfm_criteo`` configuration: float32 ``jax.numpy``, matmul precision
``highest``, a plain [rows, dim] table and a plain gather, no packed
layout, no kernels.

    y = sigmoid(y_FM + y_DNN)
    y_FM  = sum_f w[id_f] + w_dense . x_dense + b            (first order)
          + 1/2 sum_d ((sum_f v[id_f])^2 - sum_f v[id_f]^2)  (second order)
    y_DNN = MLP([v[id_1] .. v[id_26], x_dense]), ReLU, widths from the file

It trains the first task (``minibatches_per_task`` Adam steps on the
task's records, in order) from the SAME initial weights as the system and
reports the mean of the steps' losses, which is what the worker reports
for a task.  The initial weights are data here: they come from the
program's ``model_spec.init(jax.random.key(0))`` and are unpacked from its
lane-packed table by this file's own arithmetic.

Departures of the program from the paper, followed here so that both
compute the same function: ids are hashed into ``buckets_per_feature``
buckets per field by a multiplicative hash; dense features pass through
log(1 + max(x, 0)); the dense features have a first-order weight too.
"""

from __future__ import annotations

import json
import sys
import os

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
from reference_common import device_report, parse_args, read_records  # noqa: E402


def parse_criteo(records: list):
    labels = np.empty(len(records), np.float32)
    dense = np.empty((len(records), 13), np.float32)
    cats = np.empty((len(records), 26), np.uint32)
    for i, rec in enumerate(records):
        parts = rec.decode().split("\t")
        labels[i] = float(parts[0])
        dense[i] = [float(v) if v else 0.0 for v in parts[1:14]]
        cats[i] = [int(v, 16) if v else 0 for v in parts[14:40]]
    return labels, dense, cats


def rows_of(cats: np.ndarray, buckets: int) -> np.ndarray:
    h = cats.astype(np.uint32) * np.uint32(2654435761)
    h ^= h >> np.uint32(16)
    return (h % np.uint32(buckets)).astype(np.int32) + np.arange(26, dtype=np.int32) * buckets


def main() -> None:
    config, traffic, data, out = parse_args()
    import jax
    import jax.numpy as jnp
    import optax

    jax.config.update("jax_default_matmul_precision", "highest")
    p = config["model_params"]
    dim, buckets = int(p["embedding_dim"]), int(p["buckets_per_feature"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])

    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    init = spec.init(jax.random.key(0))
    stride = 16
    while stride < dim + 1:
        stride *= 2
    table = init["fm_table"].reshape(-1, stride)[: 26 * buckets, : dim + 1]
    n_hidden = len(init["mlp"]) - 1
    params = {
        "v": table[:, :dim],
        "w": table[:, dim],
        "dense_w": init["dense_linear"]["w"][:, 0],
        "b": init["dense_linear"]["b"][0],
        "mlp": [(init["mlp"][f"layer{i}"]["w"], init["mlp"][f"layer{i}"]["b"]) for i in range(n_hidden)]
        + [(init["mlp"]["out"]["w"], init["mlp"]["out"]["b"])],
    }
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    del init, table

    def loss_fn(params, rows, dense, labels):
        x = jnp.log1p(jnp.maximum(dense, 0.0))
        v = params["v"][rows]  # [b, 26, dim]
        first = params["w"][rows].sum(-1) + x @ params["dense_w"] + params["b"]
        sum_v = v.sum(1)
        second = 0.5 * (sum_v * sum_v - (v * v).sum(1)).sum(-1)
        h = jnp.concatenate([v.reshape(v.shape[0], -1), x], -1)
        for w, b in params["mlp"][:-1]:
            h = jax.nn.relu(h @ w + b)
        w, b = params["mlp"][-1]
        logit = first + second + (h @ w + b)[:, 0]
        bce = jnp.maximum(logit, 0) - logit * labels + jnp.log1p(jnp.exp(-jnp.abs(logit)))
        return bce.mean()

    optimizer = optax.adam(1e-3)

    @jax.jit
    def step(params, opt_state, rows, dense, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, rows, dense, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    labels, dense, cats = parse_criteo(read_records(data, steps * mb))
    rows = rows_of(cats, buckets)
    opt_state = optimizer.init(params)
    losses = []
    for i in range(steps):
        s = slice(i * mb, (i + 1) * mb)
        params, opt_state, loss = step(params, opt_state, rows[s], dense[s], labels[s])
        losses.append(float(loss))
    with open(out, "w") as f:
        json.dump({"loss": float(np.mean(losses)), "step_losses": losses, "device": device_report()}, f)


if __name__ == "__main__":
    main()
