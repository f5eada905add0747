"""End-to-end DeepFM/Criteo training throughput — the WHOLE worker path.

Unlike bench.py's device-step phase (one pre-sharded synthetic batch reused
every step), this runs the real job stack on real files: recordio on disk ->
master task dispatch -> worker shard read (bulk C++ recordio read) -> criteo
decode (C++ codec) -> prefetch -> shard_batch -> jitted hybrid train step,
for every batch.  The number it reports is what a user's `elasticdl train`
job actually sustains per chip (SURVEY.md §3.1-3.3; the reference's
tf.data-fed worker loop is the parity target — VERDICT r3 Missing #1).

Measurement: per-task completion timestamps via a wrapping master proxy;
the first ``WARM_TASKS`` tasks (XLA compile + cache warmup) are excluded,
throughput = records in the remaining tasks / the time they took.

Standalone: ``python tools/bench_e2e.py`` prints the result dict.
bench.py imports ``run_e2e`` for the committed artifact.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

MINIBATCH = 8192
MINIBATCHES_PER_TASK = 8  # the reference's num_minibatches_per_task default
RECORDS_PER_TASK = MINIBATCH * MINIBATCHES_PER_TASK
FILE_TASKS = 2          # tasks per epoch; the file holds this many
WARM_TASKS = 2          # excluded from the measurement (compile + warmup)
MEASURE_TASKS = 46      # ~3M examples measured

_CACHE_VERSION = 1  # bump when the synthetic generator's output changes


def _dataset(tmp_dir: str = "/tmp") -> str:
    """Synthetic criteo recordio, cached across runs (generation is a
    Python-loop one-time cost, ~30 us/record)."""
    from elasticdl_tpu.data.synthetic import synthetic_criteo

    n = RECORDS_PER_TASK * FILE_TASKS
    path = os.path.join(tmp_dir, f"edl_bench_criteo_v{_CACHE_VERSION}_{n}.rio")
    if not os.path.exists(path):
        from elasticdl_tpu.common import durable

        tmp = durable.tmp_path(path)
        synthetic_criteo(tmp, n, seed=11, container="recordio")
        durable.atomic_replace(tmp, path)
    return path


def _link_probe(log=lambda msg: None) -> dict:
    """Measure the host<->device link before the run: dispatch RTT and
    effective H2D bandwidth (put + forced arrival via a device reduce +
    scalar fetch).  Where the chip is remote-attached this link can be the
    e2e bound, so the artifact carries the link quality its throughput
    number was recorded under."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.jax_compat import jit_compiled

    d = jax.devices()[0]
    # graftlint: allow[jit-stability] one-shot link probe: the process runs this exactly once, and the probe's 2 lowerings (8B + MB buffers) are the measurement
    f = jit_compiled(
        lambda a: jnp.sum(a, dtype=jnp.int32),
        name="bench_e2e.link_probe", expected_variants=2,
    )
    tiny = np.zeros(8, np.uint8)
    int(f(jax.device_put(tiny, d)))  # warm the compile
    rtts = []
    for _ in range(5):
        t0 = time.perf_counter()
        int(f(jax.device_put(tiny, d)))
        rtts.append(time.perf_counter() - t0)
    mbs = 5.2
    bws = []
    for i in range(3):
        buf = np.random.default_rng(i).integers(
            0, 255, size=(int(mbs * 1e6),), dtype=np.uint8
        )
        t0 = time.perf_counter()
        int(f(jax.device_put(buf, d)))
        bws.append(mbs / (time.perf_counter() - t0))
    out = {
        "link_rtt_ms": round(sorted(rtts)[len(rtts) // 2] * 1e3, 1),
        "link_h2d_mbps": round(sorted(bws)[len(bws) // 2], 1),
    }
    log(f"link probe: RTT {out['link_rtt_ms']} ms, "
        f"H2D {out['link_h2d_mbps']} MB/s")
    return out


def run_e2e(log=lambda msg: None) -> dict:
    import jax

    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    path = _dataset()
    log(f"dataset ready: {path} ({os.path.getsize(path) >> 20} MiB)")
    link = _link_probe(log)

    total_tasks = WARM_TASKS + MEASURE_TASKS
    epochs = -(-total_tasks // FILE_TASKS)  # ceil; runs epochs*FILE_TASKS tasks
    total_tasks = epochs * FILE_TASKS
    config = JobConfig(
        model_def="deepfm.model_spec",
        model_params="buckets_per_feature=65536;embedding_dim=8;hidden=[400,400]",
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        training_data=path,
        minibatch_size=MINIBATCH,
        num_minibatches_per_task=MINIBATCHES_PER_TASK,
        num_epochs=epochs,
    )
    reader = create_data_reader(path)
    dispatcher = TaskDispatcher(
        reader.create_shards(RECORDS_PER_TASK), num_epochs=epochs
    )
    servicer = MasterServicer(dispatcher)
    spec = load_model_spec(
        "elasticdl_tpu.models",
        "deepfm.model_spec",
        buckets_per_feature=65536,
        embedding_dim=8,
        hidden=(400, 400),
    )

    reports = []

    class TimingProxy(DirectMasterProxy):
        def call(self, method, request):
            resp = super().call(method, request)
            if method == "ReportTaskResult":
                reports.append(time.perf_counter())
                if len(reports) % 16 == 0:
                    log(f"{len(reports)} tasks done")
            return resp

    worker = Worker(
        config,
        TimingProxy(servicer),
        reader,
        worker_id="bench-w0",
        spec=spec,
        devices=jax.devices(),
    )
    log(f"running {total_tasks} tasks x {RECORDS_PER_TASK} records "
        f"(epochs={epochs})")
    t_start = time.perf_counter()
    result = worker.run()
    t_total = time.perf_counter() - t_start

    if len(reports) <= WARM_TASKS:
        raise RuntimeError(
            f"only {len(reports)} tasks completed; nothing to measure"
        )
    measured = len(reports) - WARM_TASKS
    elapsed = reports[-1] - reports[WARM_TASKS - 1]
    examples = measured * RECORDS_PER_TASK
    n_chips = len(jax.devices())
    from elasticdl_tpu.data.ingest_pool import resolve_threads

    return {
        "e2e_examples_per_sec_per_chip": examples / elapsed / n_chips,
        "tasks_measured": measured,
        "examples_measured": examples,
        "elapsed_s": elapsed,
        "wall_total_s": t_total,
        "steps": result["step"],
        "warm_tasks_excluded": WARM_TASKS,
        **link,
        # Pipeline config (r9): e2e numbers are only comparable at equal
        # ingest/prep/lease shape, exactly like the link fields above —
        # bench.py's record guard enforces it.
        "ingest_threads": resolve_threads(config.ingest_threads),
        "prep_depth": config.prep_depth,
        "lease_batch": config.lease_batch,
        # Step-shape config (r11): the optimizer layout and donation knob
        # change what the jitted step computes/holds resident, so runs at
        # different settings are different experiments — same guard.
        "optimizer_sharding": config.optimizer_sharding,
        "donate_train_state": config.donate_train_state,
    }


if __name__ == "__main__":
    from elasticdl_tpu.common.platform import enable_compile_cache

    enable_compile_cache()
    out = run_e2e(log=lambda m: print(f"[e2e] {m}", file=sys.stderr, flush=True))
    print(out)
