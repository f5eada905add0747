"""Host-tier DeepFM throughput vs async-PS staleness depth (VERDICT r3
item 7).

The host-tier step is: pull batch rows from the PS fleet (RPC) -> jitted
device step -> push sparse cotangents (RPC).  --use_async overlaps the pull
with the in-flight step; ``--async_staleness D`` lets up to D pushes ride
behind device steps.  This tool trains host-tier DeepFM against a real
local PS fleet at depth 0 (sync) / 1 / 2 / 4 and prints one JSON line per
depth, so the default depth is chosen by measurement, not by assumption.

Usage: python tools/async_depth_bench.py [--steps 30] [--shards 2]
(Runs on whatever jax.devices() offers; the RELATIVE depth effect is about
hiding RPC latency, which exists on any backend.)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.platform import enable_compile_cache


def bench_depth(depth: int, steps: int, n_shards: int, batch: int) -> dict:
    import jax
    import numpy as np

    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer
    from elasticdl_tpu.ps.service import PSServer

    spec = load_model_spec(
        "elasticdl_tpu.models",
        "deepfm.model_spec",
        buckets_per_feature=65536,
        embedding_dim=8,
        hidden=(400, 400),
        host_tier=True,
    )
    servers = [
        PSServer(spec.host_io, shard=s, num_shards=n_shards).start()
        for s in range(n_shards)
    ]
    config = JobConfig(
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        ps_addresses=",".join(s.address for s in servers),
        use_async=depth > 0,
        async_staleness=max(depth, 1),
    )
    rng = np.random.RandomState(0)

    def mk():
        return {
            "dense": rng.rand(batch, 13).astype(np.float32) * 100,
            "cat": rng.randint(0, 1 << 30, (batch, 26)).astype(np.int32),
            "labels": rng.randint(0, 2, (batch,)).astype(np.int32),
        }

    try:
        trainer = Trainer(spec, config, create_mesh(jax.devices()))
        state = trainer.init_state(jax.random.key(0))
        warm = [mk() for _ in range(3)]
        state, _ = trainer.run_train_steps(state, warm, use_async=depth > 0)
        jax.block_until_ready(state.step)
        batches = [mk() for _ in range(steps)]
        t0 = time.perf_counter()
        state, metrics = trainer.run_train_steps(
            state, batches, use_async=depth > 0
        )
        jax.block_until_ready(state.step)
        elapsed = time.perf_counter() - t0
    finally:
        for s in servers:
            s.stop()
    return {
        "mode": "sync" if depth == 0 else f"async_depth_{depth}",
        "depth": depth,
        "examples_per_s": round(batch * steps / elapsed),
        "step_ms": round(elapsed / steps * 1e3, 1),
        "shards": n_shards,
        "batch": batch,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--depths", default="0,1,2,4")
    args = ap.parse_args()
    enable_compile_cache()
    # The sweep's verdict flips with the wire's mood (a stall-window sweep
    # ranks sync > any async depth because the pull RTT dominates), so the
    # artifact must carry the link quality it was measured under.
    from tools.bench_e2e import _link_probe

    link = _link_probe(log=lambda m: print(m, file=sys.stderr, flush=True))
    results = []
    try:
        for d in (int(s) for s in args.depths.split(",")):
            result = bench_depth(d, args.steps, args.shards, args.batch)
            results.append(result)
            print(json.dumps(result), flush=True)
            print(f"  depth {d}: {result['examples_per_s']:,} ex/s "
                  f"({result['step_ms']} ms/step)", file=sys.stderr)
    finally:
        if results:  # a mid-sweep flake still deposits what was measured
            from tools.artifact import write_artifact

            write_artifact(
                {
                    "metric": "async_staleness_depth_sweep",
                    "depths": results,
                    **link,
                },
                "async_depth_r05.json", env_var="ASYNC_DEPTH_OUT",
            )


if __name__ == "__main__":
    main()
