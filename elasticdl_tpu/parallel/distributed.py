"""Multi-host distributed backend — DCN control plane + ICI/DCN data plane.

Reference parity (SURVEY.md §5 "Distributed comm backend" [D]): the
reference's data plane is Horovod->NCCL/Gloo rings between worker pods and
its control plane is gRPC.  The TPU rebuild splits the same way:

- **Control plane**: the master's gRPC service (task dispatch, rendezvous
  versioning) — unchanged across single/multi host — plus JAX's built-in
  distributed coordination service (``jax.distributed``), which PJRT needs
  so every host sees the whole TPU slice as one device set.
- **Data plane**: XLA collectives compiled into the jitted step.  Inside a
  pod slice they ride ICI; across slices (multislice) XLA routes them over
  DCN.  No NCCL/MPI analogue exists or is needed — ``psum`` over the mesh
  IS the allreduce.

Process model: one worker process per TPU host (the reference's one worker
pod per GPU host).  The master assigns each worker a stable ``slot``
(ELASTICDL_WORKER_SLOT); slot 0's address (or an explicit coordinator flag)
seeds ``jax.distributed.initialize``.  After initialization,
``jax.devices()`` returns every chip of every live host, and the mesh spans
them; ``create_mesh`` then works unchanged.

Elasticity: a membership change means the JAX distributed runtime must be
re-initialized with the new host set (XLA's world is fixed per
initialization).  That is exactly the checkpoint-restore re-join the worker
already implements (worker.py ``_replace_state``): shutdown -> initialize
with new topology -> rebuild mesh -> restore.  ``reinitialize`` packages
that sequence.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from elasticdl_tpu.common.jax_compat import distributed_initialize
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.platform import free_port  # noqa: F401 — re-export
# (free_port lives in the jax-free common.platform: bench/test master
# processes that allocate coordinator ports must not pay this module's
# jax import for a socket probe)

logger = get_logger("parallel.distributed")


@dataclasses.dataclass(frozen=True)
class DistributedSpec:
    """Topology of one jax.distributed world."""

    coordinator_address: str  # host:port of process 0's coordination service
    num_processes: int
    process_id: int
    # Coordination-service peer-death detection.  JAX's default (100 s)
    # dominates elastic recovery: a survivor blocked inside a collective on
    # a dead peer sits there until THIS timeout aborts it (most of a
    # re-rendezvous on the CPU harness, round 4).  30 s is a 3.3x shorter
    # default that still tolerates heartbeat-thread starvation
    # on oversubscribed hosts (a 10 s bound produced FALSE peer-death under
    # 1-core CPU contention during XLA compiles: the coordinator declared a
    # live, compiling peer dead).  Dedicated TPU hosts can set
    # --distributed_heartbeat_timeout_s=10 (docs/perf.md).
    heartbeat_timeout_s: float = 30.0

    @property
    def enabled(self) -> bool:
        return self.num_processes > 1


_ACTIVE: Optional[DistributedSpec] = None


def initialize(spec: DistributedSpec) -> None:
    """Bring this process into the JAX distributed world.

    MUST run before the first JAX computation: ``jax.distributed.initialize``
    refuses once the PJRT backend exists, and the backend cannot be re-formed
    in-process.  ``worker.main`` therefore derives the spec from master
    membership over plain gRPC and calls this before constructing the Worker
    (whose first ``jax.devices()`` initializes the backend).  An elastic
    topology change requires a PROCESS RESTART — see
    ``worker.WorkerRestartRequired`` and the pod manager's budget-free
    RESTART relaunch path.

    Single-process specs are a no-op (local jax.devices() is already
    correct), so the same worker code runs one-host and multi-host.
    """
    global _ACTIVE
    if not spec.enabled:
        return
    if _ACTIVE == spec:
        return
    if _ACTIVE is not None:  # pragma: no cover - defensive; see docstring
        raise RuntimeError(
            "jax.distributed world already initialized with a different "
            "topology; an elastic change requires a worker process restart"
        )
    logger.info(
        "jax.distributed.initialize(%s, num_processes=%d, process_id=%d)",
        spec.coordinator_address, spec.num_processes, spec.process_id,
    )
    distributed_initialize(
        coordinator_address=spec.coordinator_address,
        num_processes=spec.num_processes,
        process_id=spec.process_id,
        heartbeat_timeout_seconds=max(int(spec.heartbeat_timeout_s), 1),
    )
    _ACTIVE = spec


def shutdown() -> None:
    global _ACTIVE
    if _ACTIVE is None:
        return
    try:
        jax.distributed.shutdown()
    except Exception:  # pragma: no cover - runtime may already be gone
        logger.exception("jax.distributed.shutdown failed")
    _ACTIVE = None


def advertised_address() -> str:
    """The host other workers can dial: pod IP (downward API) or FQDN."""
    import os
    import socket

    return os.environ.get("MY_POD_IP") or socket.getfqdn()


def active_spec() -> Optional[DistributedSpec]:
    return _ACTIVE


def spec_from_membership(
    membership: dict,
    worker_id: str,
    coordinator_port: int = 8476,
    heartbeat_timeout_s: float = 30.0,
) -> DistributedSpec:
    """Derive this worker's DistributedSpec from master membership.

    The membership dict carries ``ranks`` (worker_id -> rank) and
    ``addresses`` (worker_id -> host) when the pod backend populates them;
    rank 0's host seeds the coordinator.  Single-host deployments (no
    addresses) yield a disabled spec.
    """
    ranks = membership.get("ranks", {})
    addresses = membership.get("addresses", {})
    if not addresses or len(ranks) <= 1:
        return DistributedSpec("", 1, 0)
    rank0 = next((w for w, r in ranks.items() if r == 0), None)
    host0 = addresses.get(rank0)
    if host0 is None:
        return DistributedSpec("", 1, 0)
    return DistributedSpec(
        coordinator_address=f"{host0}:{coordinator_port}",
        num_processes=len(ranks),
        process_id=ranks.get(worker_id, 0),
        heartbeat_timeout_s=heartbeat_timeout_s,
    )
