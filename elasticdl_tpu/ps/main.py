"""PS pod entry point — ``python -m elasticdl_tpu.ps.main``.

The master launches ``--num_ps_pods`` of these (master/main.py) exactly as it
launches worker pods; each serves one ``id mod n`` shard of every host-tier
table (ps/service.py).  Reference parity: the reference's PS pod main
(SURVEY.md §2 #10 [U]) — a gRPC server process created by the master, loading
its table slice from the latest checkpoint on (re)start.

Environment (set by the master's pod env, same bus as workers):

- ``ELASTICDL_JOB_CONFIG``  — the job config JSON (model spec -> host_io).
- ``ELASTICDL_WORKER_SLOT`` — this pod's slot = PS shard index.
- ``ELASTICDL_PS_PORTS``    — comma list; this shard binds its slot's port.

PS pods never touch an accelerator: the model spec is loaded only for its
``host_io`` table descriptors, on CPU.
"""

from __future__ import annotations

import os
import signal
import sys
import threading
from typing import List, Optional

# PS pods must not grab the TPU chips the workers need — force CPU
# UNCONDITIONALLY (not setdefault: the pod env inherits the worker-oriented
# JAX_PLATFORMS), before anything imports jax.
os.environ["JAX_PLATFORMS"] = "cpu"

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.log_utils import get_logger, set_level

logger = get_logger("ps.main")


def main(argv: Optional[List[str]] = None) -> int:
    config = JobConfig.from_env()
    set_level(config.log_level)
    if config.trace:
        # PS-shard spans (ps:pull / ps:push_grad server halves) record
        # locally; the dump tool reaches them via the shard's own process
        # buffer only if shipped — PS pods have no heartbeat channel, so
        # their window is in-process observability (logs/debug) for now.
        from elasticdl_tpu.common import trace as _trace

        _trace.configure(enabled=True, capacity=config.trace_buffer_events)
    if config.chaos:
        # graftchaos rides the same config bus as --trace: delay_ps faults
        # arm in the shard process itself (GRAFT_CHAOS env works too).
        from elasticdl_tpu import chaos as _chaos

        _chaos.configure(config.chaos)

    slot = int(os.environ.get("ELASTICDL_WORKER_SLOT", "0"))
    ports = [
        int(p) for p in os.environ.get("ELASTICDL_PS_PORTS", "0").split(",")
    ]
    num_shards = max(config.num_ps_pods, 1)
    port = ports[slot] if slot < len(ports) else 0

    from elasticdl_tpu.models.spec import load_model_spec_for_job

    spec = load_model_spec_for_job(config)
    if not spec.host_io:
        logger.warning(
            "model %s declares no host-tier tables; PS shard %d idles",
            spec.name, slot,
        )

    from elasticdl_tpu.ps.service import PSServer

    server = PSServer(
        spec.host_io, shard=slot, num_shards=num_shards, port=port
    )
    if config.checkpoint_dir:
        server.restore_latest(config.checkpoint_dir)

    # graftgauge (r14): the shard's live /metrics endpoint — pull/push
    # rates, latency histograms and per-table row counts (PSServer records
    # into the process-default registry).  Daemon threads of their own: a
    # shard wedged in a Save must still answer the scrape.
    from elasticdl_tpu.common.metrics_http import maybe_start

    metrics_server = maybe_start(
        config.gauge_port,
        server.gauges.render_prometheus,
        health_fn=lambda: {
            "role": "ps",
            "shard": slot,
            "num_shards": num_shards,
        },
        registry=server.gauges,
    )

    stop = threading.Event()

    def _terminate(signum, frame):
        logger.info("PS shard %d: signal %d, shutting down", slot, signum)
        stop.set()

    signal.signal(signal.SIGTERM, _terminate)
    signal.signal(signal.SIGINT, _terminate)

    server.start()
    try:
        while not stop.is_set():
            stop.wait(1.0)
    finally:
        server.stop(grace=5.0)
        if metrics_server is not None:
            metrics_server.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
