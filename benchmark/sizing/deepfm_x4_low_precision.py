#!/usr/bin/env python3
"""Sizing of ``deepfm_criteo_tb_x4``'s ``reference_tolerance``: the plain
reference's first-task loss in float32 and again with EVERYTHING in
bfloat16 (table rows, dense weights, Adam moments, arithmetic, loss): the
nearest precision below what the configuration states (bfloat16 compute
over float32 parameters and loss).  The tolerance has to lie between the
largest difference the system shows over its seeds and this one.

    python3 benchmark/sizing/deepfm_x4_low_precision.py <data file of a run> [buckets a field, to rehearse on the CPU]

Run on the chip, after a run of ``deepfm_x4_job`` has left its data under
``benchmark/.state/runs/deepfm_x4_job/data/``; prints one JSON object.
Not part of a benchmark run.
"""

from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.dirname(BENCH_DIR))

import numpy as np  # noqa: E402
from resolve import Bench, load_module  # noqa: E402


def main() -> None:
    import jax
    import jax.numpy as jnp

    jax.config.update("jax_default_matmul_precision", "highest")
    bench = Bench()
    config = bench.config("deepfm_criteo_tb_x4")
    traffic = bench.traffic("job_uniform_8k")
    reference = load_module(bench.reference_path("deepfm_criteo_tb_x4"))
    sys.path.insert(0, os.path.join(BENCH_DIR, "configs"))
    from reference_common import read_records

    from elasticdl_tpu.models.spec import load_model_spec

    p = config["model_params"]
    if len(sys.argv) > 2:
        p["buckets_per_feature"] = int(sys.argv[2])
    dim, buckets = int(p["embedding_dim"]), int(p["buckets_per_feature"])
    steps, mb = int(traffic["minibatches_per_task"]), int(traffic["minibatch_size"])
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    labels, dense, cats = reference.parse_criteo(read_records(sys.argv[1], steps * mb))
    touched, rows = reference.compact(reference.rows_of(cats, buckets))
    params = reference.initial_params(spec, touched, dim)
    full = reference.train_task(params, rows, dense, labels, steps, mb)
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    half = reference.train_task(
        low, rows, dense.astype(jnp.bfloat16), labels.astype(jnp.bfloat16), steps, mb
    )
    out = {
        "float32": {"loss": float(np.mean(full)), "step_losses": full},
        "bfloat16_everywhere": {"loss": float(np.mean(half)), "step_losses": half},
        "relative_difference": abs(float(np.mean(half)) - float(np.mean(full))) / float(np.mean(full)),
        "rows_touched": int(len(touched)),
        "device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind, "count": jax.device_count()},
    }
    print(json.dumps(out))


if __name__ == "__main__":
    main()
