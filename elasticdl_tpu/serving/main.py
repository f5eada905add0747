"""Serving replica entrypoint: ``python -m elasticdl_tpu.serving.main``.

The process the fleet controller (serving/fleet.py) spawns per slot via
ProcessPodBackend.  Configuration arrives ENTIRELY by environment — the
pod-manager contract — and is deliberately identity-free except for the
slot:

- ``ELASTICDL_SERVING_CONFIG``: one JSON blob (model zoo/def/params,
  checkpoint dir, PS addresses, batcher + bucket knobs, base ports).  The
  SAME string for every slot, so the spawn env signature is uniform and
  one warm standby spare can serve any slot.
- ``ELASTICDL_WORKER_SLOT``: this replica's slot N.  Ports derive from it
  (gRPC on ``base_port + N``, /metrics on ``metrics_base_port + N``) —
  the address contract the controller and the p2c client resolve by.
- ``ELASTICDL_STANDBY_GO_FILE``: warm-standby mode (worker.main's r13
  protocol, mirrored): pre-pay python + jax + framework imports, publish
  the ``.ready`` marker, park until the pod manager's go-file names the
  replica this process becomes.

Boot order is bind -> load checkpoint -> WARMUP ALL BUCKETS -> serve:
the gRPC port accepts only after every batch bucket is compiled, so a
replica that answers its readiness probe serves its first request at
forward speed, never at XLA-compile speed — the difference between a
scale-up that relieves a p99 blowout and one that deepens it.

Exit contract: SIGTERM (PodManager delete_pod) drains within the grace
window and exits 0.
"""

from __future__ import annotations

import json
import os
import signal
import sys
import threading
import time

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("serving.main")


def _park_as_standby(go_file: str) -> str:
    """Warm-standby parking, serving flavor (worker/main.py's protocol):
    pre-pay the boot tail — python + jax + framework + serving imports —
    then park until the pod manager writes the go file naming the replica
    id this process should become.  Nothing here may touch a jax backend:
    the spare must stay adoptable into any slot, and single-device
    backend init belongs after adoption with the slot known.  Returns the
    assigned replica id."""
    import importlib

    for mod in (
        "jax", "jax.numpy", "flax", "optax", "orbax.checkpoint",
        "elasticdl_tpu.parallel.trainer", "elasticdl_tpu.parallel.mesh",
        "elasticdl_tpu.models.spec", "elasticdl_tpu.serving.server",
        "elasticdl_tpu.serving.micro_batcher",
    ):
        importlib.import_module(mod)
    logger.info(
        "serving standby warmed (pid %d); parking on %s", os.getpid(), go_file
    )
    from elasticdl_tpu.common import durable

    ready = go_file + ".ready"
    durable.atomic_publish(ready, str(os.getpid()))
    parent0 = os.getppid()
    while not os.path.exists(go_file):
        if os.getppid() != parent0:
            # Controller died without close(): nothing will ever write the
            # go file — exit instead of parking a jax-loaded interpreter
            # forever (the worker standby's orphan rule).
            logger.info("serving standby orphaned (parent gone); exiting")
            raise SystemExit(0)
        time.sleep(0.05)
    payload = json.loads(open(go_file).read())
    for k, v in payload.get("env", {}).items():
        os.environ[k] = v
    replica_id = payload["worker_id"]
    logger.info("serving standby adopted as %s", replica_id)
    return replica_id


def main() -> int:
    go_file = os.environ.get("ELASTICDL_STANDBY_GO_FILE", "")
    if go_file:
        _park_as_standby(go_file)

    cfg = json.loads(os.environ["ELASTICDL_SERVING_CONFIG"])
    slot = int(os.environ.get("ELASTICDL_WORKER_SLOT", "0"))
    replica_id = os.environ.get("ELASTICDL_WORKER_ID", f"serve-{slot}")
    port = int(cfg.get("base_port", 8700)) + slot
    gauge_port = int(cfg.get("metrics_base_port", 8800)) + slot

    from elasticdl_tpu.common.platform import (
        device_summary,
        enable_compile_cache,
    )
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.serving.server import ServingServer

    # Every replica warms the same batch buckets: relaunches and scale-ups
    # load them from the shared cache instead of recompiling.
    enable_compile_cache()
    spec = load_model_spec(
        cfg.get("model_zoo", "elasticdl_tpu.models"),
        cfg["model_def"],
        **(cfg.get("model_params") or {}),
    )
    server = ServingServer(
        spec,
        checkpoint_dir=cfg.get("checkpoint_dir", ""),
        ps_addresses=cfg.get("ps_addresses", ""),
        max_batch=int(cfg.get("max_batch", 64)),
        max_delay_ms=float(cfg.get("max_delay_ms", 5.0)),
        cache_rows=int(cfg.get("cache_rows", 1 << 20)),
        poll_interval_s=float(cfg.get("poll_interval_s", 0.5)),
        port=port,
        gauge_port=gauge_port,
        seed=int(cfg.get("seed", 0)),
        target_p99_ms=float(cfg.get("target_p99_ms", 100.0)),
        batch_buckets=cfg.get("batch_buckets"),
        bulk_weight=float(cfg.get("bulk_weight", 0.25)),
        # Fleet sizing contract: the handler pool rides ABOVE the queue
        # bound so overload lands in the micro-batcher's measured, shedding
        # queue — never invisibly in the gRPC executor (the autoscaler
        # scrapes the batcher's signals, not grpc's).
        max_workers=int(cfg.get("max_workers", 16)),
        max_queue_rows=(
            int(cfg["max_queue_rows"])
            if cfg.get("max_queue_rows") is not None else None
        ),
    )
    warm_s = server.warmup()
    logger.info("replica %s device: %s", replica_id, json.dumps(device_summary()))
    logger.info(
        "replica %s (slot %d): warmed %d bucket(s) in %.2fs; serving on "
        "port %d, /metrics on %d",
        replica_id, slot, len(server._shape_buckets), warm_s, port, gauge_port,
    )
    server.start()

    done = threading.Event()

    def _terminate(signum, frame) -> None:
        logger.info("replica %s: signal %d, draining", replica_id, signum)
        done.set()

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)
    done.wait()
    server.stop(grace=1.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
