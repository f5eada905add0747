"""Plain reference of DeepFM (Guo et al. 2017, arXiv:1703.04247) for the
``deepfm_criteo_tb_x4`` configuration: float32 ``jax.numpy``, matmul
precision ``highest``, a plain [rows, dim] table and a plain gather, no
packed layout, no collective, one device.  Its own copy: nothing here is
shared with ``deepfm_criteo_reference.py``.

    y = sigmoid(y_FM + y_DNN)
    y_FM  = sum_f w[id_f] + w_dense . x_dense + b            (first order)
          + 1/2 sum_d ((sum_f v[id_f])^2 - sum_f v[id_f]^2)  (second order)
    y_DNN = MLP([v[id_1] .. v[id_26], x_dense]), ReLU, widths from the file

It trains the first task (``minibatches_per_task`` Adam steps on the
task's records, in order) from the SAME initial weights as the system and
reports the mean of the steps' losses, which is what the worker reports
for a task.

The table has 163.6 M rows (21.6 GB as [rows, 11] float32 with two
moments): no one device holds it.  The reference trains the COMPACTED set
of rows the task touches instead: the U <= steps x minibatch x 26 distinct
rows, relabelled 0..U-1, as a [U, 11] table.  That run IS the full run,
exactly, under the dense Adam the configuration states:

- Adam is elementwise, and its bias correction depends on the step count
  alone.  A row whose gradient is zero and whose moments are zero gets the
  update ``-lr * 0 / (sqrt(0) + eps) = 0`` and keeps zero moments: it does
  not move.
- A row never looked up in the task has a zero gradient in every step, so
  it stays at its initial value, and no loss of the task reads it.
- A row first looked up at step k has not moved before step k (previous
  point), and it is in the compacted set, where it likewise sat at its
  initial value with zero moments until step k.  From step k on both runs
  apply the same arithmetic to the same numbers.

The initial weights are data here: they come from the program's own init
(``model_spec.init(jax.random.key(0))``), run as one jitted program whose
table is born row-sharded over every local device (this child runs after
the job has released the chips), and the touched rows are read out shard
by shard with a plain row gather; the lane-packed rows are unpacked by
this file's own arithmetic.  No device ever holds the whole table.

Departures of the program from the paper, followed here so that both
compute the same function: ids are hashed into ``buckets_per_feature``
buckets per field by a multiplicative hash; dense features pass through
log(1 + max(x, 0)); the dense features have a first-order weight too.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402
from reference_common import device_report, parse_args, read_records  # noqa: E402

NUM_CAT = 26
LANES = 128


def parse_criteo(records: list):
    labels = np.empty(len(records), np.float32)
    dense = np.empty((len(records), 13), np.float32)
    cats = np.empty((len(records), NUM_CAT), np.uint32)
    for i, rec in enumerate(records):
        parts = rec.decode().split("\t")
        labels[i] = float(parts[0])
        dense[i] = [float(v) if v else 0.0 for v in parts[1:14]]
        cats[i] = [int(v, 16) if v else 0 for v in parts[14:40]]
    return labels, dense, cats


def rows_of(cats: np.ndarray, buckets: int) -> np.ndarray:
    """Logical table row of every (example, field): the program's hash."""
    h = cats.astype(np.uint32) * np.uint32(2654435761)
    h ^= h >> np.uint32(16)
    return (h % np.uint32(buckets)).astype(np.int64) + np.arange(NUM_CAT, dtype=np.int64) * buckets


def compact(rows: np.ndarray):
    """(the distinct rows, ascending; ``rows`` relabelled 0..U-1)."""
    touched, relabelled = np.unique(rows, return_inverse=True)
    return touched, relabelled.reshape(rows.shape).astype(np.int32)


def initial_params(spec, touched: np.ndarray, dim: int) -> dict:
    """The program's initial weights with the table cut to ``touched``
    (logical rows, ascending): {"v": [U, dim], "w": [U], dense parts}."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    devices = jax.devices()
    n = 1
    while n * 2 <= len(devices):
        n *= 2
    mesh = Mesh(np.array(devices[:n]), ("rows",))
    key = jax.random.key(0)
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, PartitionSpec()), jax.eval_shape(spec.init, key))
    shardings["fm_table"] = NamedSharding(mesh, PartitionSpec("rows"))
    init = jax.jit(spec.init, out_shardings=shardings)(key)

    stride = 16
    while stride < dim + 1:
        stride *= 2
    pack = LANES // stride
    physical, slot = touched // pack, touched % pack
    table = np.empty((len(touched), dim + 1), np.float32)
    for shard in init["fm_table"].addressable_shards:
        if shard.replica_id:
            continue
        lo = shard.index[0].start or 0
        mine = (physical >= lo) & (physical < lo + shard.data.shape[0])
        got = np.asarray(shard.data[physical[mine] - lo]).reshape(-1, pack, stride)
        table[mine] = got[np.arange(len(got)), slot[mine], : dim + 1]
    n_hidden = len(init["mlp"]) - 1
    params = {
        "v": table[:, :dim],
        "w": table[:, dim],
        "dense_w": init["dense_linear"]["w"][:, 0],
        "b": init["dense_linear"]["b"][0],
        "mlp": [(init["mlp"][f"layer{i}"]["w"], init["mlp"][f"layer{i}"]["b"]) for i in range(n_hidden)]
        + [(init["mlp"]["out"]["w"], init["mlp"]["out"]["b"])],
    }
    one = jax.devices()[0]
    return jax.tree.map(lambda a: jax.device_put(jnp.asarray(np.asarray(a), jnp.float32), one), params)


def logits_fn(params, rows, dense):
    import jax
    import jax.numpy as jnp

    x = jnp.log1p(jnp.maximum(dense, 0.0))
    v = params["v"][rows]  # [b, 26, dim]
    first = params["w"][rows].sum(-1) + x @ params["dense_w"] + params["b"]
    sum_v = v.sum(1)
    second = 0.5 * (sum_v * sum_v - (v * v).sum(1)).sum(-1)
    h = jnp.concatenate([v.reshape(v.shape[0], -1), x], -1)
    for w, b in params["mlp"][:-1]:
        h = jax.nn.relu(h @ w + b)
    w, b = params["mlp"][-1]
    return first + second + (h @ w + b)[:, 0]


def loss_fn(params, rows, dense, labels):
    import jax.numpy as jnp

    logit = logits_fn(params, rows, dense)
    bce = jnp.maximum(logit, 0) - logit * labels + jnp.log1p(jnp.exp(-jnp.abs(logit)))
    return bce.mean()


def train_task(params, rows, dense, labels, steps: int, mb: int, learning_rate: float = 1e-3) -> list:
    """``steps`` dense-Adam steps over consecutive minibatches of ``mb``
    examples; the steps' losses."""
    import jax
    import optax

    optimizer = optax.adam(learning_rate)

    @jax.jit
    def step(params, opt_state, rows, dense, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, rows, dense, labels)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    opt_state = optimizer.init(params)
    losses = []
    for i in range(steps):
        s = slice(i * mb, (i + 1) * mb)
        params, opt_state, loss = step(params, opt_state, rows[s], dense[s], labels[s])
        losses.append(float(loss))
    return losses


def main() -> None:
    config, traffic, data, out = parse_args()
    import jax

    jax.config.update("jax_default_matmul_precision", "highest")
    p = config["model_params"]
    dim, buckets = int(p["embedding_dim"]), int(p["buckets_per_feature"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])

    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    labels, dense, cats = parse_criteo(read_records(data, steps * mb))
    touched, rows = compact(rows_of(cats, buckets))
    params = initial_params(spec, touched, dim)
    losses = train_task(params, rows, dense, labels, steps, mb)
    with open(out, "w") as f:
        json.dump(
            {"loss": float(np.mean(losses)), "step_losses": losses, "rows_touched": int(len(touched)),
             "devices": jax.device_count(), "device": device_report()},
            f,
        )


if __name__ == "__main__":
    main()
