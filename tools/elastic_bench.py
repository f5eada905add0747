"""Measure elastic re-rendezvous latency — the SECOND driver-defined target
(BASELINE.md: "re-converge within one step after a worker preemption").

Scenario (in-process, 8 fake CPU devices — the same harness the elastic
tests use; the latency being measured is control-plane + re-shard +
recompile work, none of which runs on the accelerator):

  1. a DeepFM hybrid job trains on an 8-device mesh with periodic
     checkpoints;
  2. a membership bump simulates losing half the fleet (8 -> 4);
  3. the worker re-forms the mesh, re-places state from the latest
     checkpoint, and runs the next training step.

Reported: seconds from the membership bump to the FIRST completed
post-resize training step, split into re-form (mesh + state re-placement)
and step (incl. recompile — with the persistent compile cache warm, a
repeat topology skips XLA).  "Re-converge within one step" is satisfied by
construction — the first post-resize step trains on restored weights; this
tool puts a NUMBER on how long that step takes to arrive.

Usage: JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
           python tools/elastic_bench.py
Prints one JSON line: {"reform_s": ..., "first_step_s": ..., "total_s": ...,
"cold": {...}} (cold = first resize, warm = resized back to a seen size).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
# FORCE cpu (not setdefault): this is a CPU harness, and an inherited
# JAX_PLATFORMS (or none, on a TPU host) would aim it at the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"

from elasticdl_tpu.common.platform import enable_compile_cache

import numpy as np  # noqa: E402

# jax is imported inside main(): importing this module (lint/CLI paths)
# must never pay a backend init.


def _batch(n=64, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "dense": rng.rand(n, 13).astype(np.float32) * 100,
        "cat": rng.randint(0, 1 << 20, (n, 26)).astype(np.int64),
        "labels": rng.randint(0, 2, (n,)).astype(np.int32),
    }


def main() -> None:
    import jax

    enable_compile_cache()
    from elasticdl_tpu.common.checkpoint import CheckpointManager
    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    devices = jax.devices()
    assert len(devices) >= 8, f"need 8 fake devices, have {len(devices)}"
    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=4096, embedding_dim=8, hidden=(64, 64),
        compute_dtype="float32",
    )
    config = JobConfig(
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        embedding_lookup_impl="ragged_emulated",
    )
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_bench_")
    ckpt = CheckpointManager(ckpt_dir)

    trainer = Trainer(spec, config, create_mesh(devices, num_devices=8))
    state = trainer.init_state(jax.random.key(0))
    for s in range(3):
        state, metrics = trainer.train_step(state, trainer.shard_batch(_batch(seed=s)))
    jax.block_until_ready(metrics)
    ckpt.save(int(state.step), jax.device_get(state), wait=True)
    print("[elastic-bench] trained 3 steps on 8 devices, checkpointed",
          file=sys.stderr)

    def resize(n_devices, seed):
        """Membership bump -> re-form -> restore -> first step; timed."""
        t0 = time.perf_counter()
        trainer.set_mesh(create_mesh(devices, num_devices=n_devices))
        # Canonical bridge (trainer.host_state): with --optimizer_sharding
        # the live opt leaves are dp-flat and must canonicalize before
        # re-placement; the checkpoint itself is canonical in every mode.
        template = trainer.shard_state(trainer.host_state(state))
        restored = trainer.adopt_restored(
            ckpt.restore(trainer.restore_template(template))
        )
        t_reform = time.perf_counter() - t0
        t1 = time.perf_counter()
        new_state, m = trainer.train_step(
            restored, trainer.shard_batch(_batch(seed=seed))
        )
        jax.block_until_ready(m)
        t_step = time.perf_counter() - t1
        return {
            "devices": n_devices,
            "reform_s": round(t_reform, 3),
            "first_step_s": round(t_step, 3),
            "total_s": round(t_reform + t_step, 3),
        }

    cold = resize(4, seed=10)   # unseen topology: pays re-shard + compile
    print(f"[elastic-bench] cold 8->4: {cold}", file=sys.stderr)
    back = resize(8, seed=11)   # seen topology: compile cache warm
    print(f"[elastic-bench] warm 4->8: {back}", file=sys.stderr)
    again = resize(4, seed=12)  # seen 4-dev topology too
    print(f"[elastic-bench] warm 8->4: {again}", file=sys.stderr)

    result = {
        "metric": "elastic_rerendezvous_latency_s",
        "cold_8_to_4": cold,
        "warm_4_to_8": back,
        "warm_8_to_4": again,
        "value": again["total_s"],
        "unit": "seconds (membership bump -> first post-resize step done)",
    }
    print(json.dumps(result))
    from tools.artifact import write_artifact

    # Number-of-record artifact (docs/perf.md quotes the file).
    write_artifact(
        result, "elastic_inprocess_r05.json", env_var="ELASTIC_BENCH_OUT",
        log=lambda m: print(f"[elastic-bench] {m}", file=sys.stderr),
    )


if __name__ == "__main__":
    main()
