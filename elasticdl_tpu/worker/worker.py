"""The worker main loop.

Reference parity (SURVEY.md §3.3-3.5 [U/D]): pull task -> build input from
the shard -> jitted step per minibatch -> report; on membership change,
re-form the mesh and resume from the latest checkpoint.  The reference's
trainer split (AllReduceTrainer vs PS path) collapses into one Trainer whose
partition specs differ by strategy (parallel/trainer.py).

Deployment note: in a real multi-host TPU job each worker is one host of a
``jax.distributed``-initialized slice and the mesh spans all hosts' devices;
in-process tests emulate elasticity by resizing the mesh over a fixed pool of
fake CPU devices (SURVEY.md §4 pattern).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Iterable, List, NamedTuple, Optional, Sequence

import grpc
import jax
import numpy as np

from elasticdl_tpu import chaos
from elasticdl_tpu.common import gauge as gaugelib
from elasticdl_tpu.common import jitsan, locksan, trace
from elasticdl_tpu.common.checkpoint import CheckpointManager
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.metrics import PhaseTimers, finalize_metrics
from elasticdl_tpu.common.program_store import ProgramStore
from elasticdl_tpu.common.platform import (
    compile_counts,
    compile_phase_seconds,
    count_compiles,
    device_bytes_in_use,
    device_peak_bytes,
)
from elasticdl_tpu.common.rpc import (
    IN_FLIGHT,
    PROTOCOL_VERSION,
    BackoffPolicy,
    JsonRpcClient,
    call_with_backoff,
)
from elasticdl_tpu.common.stall import StallRecorder
from elasticdl_tpu.data.ingest_pool import IngestPool, plan_chunks
from elasticdl_tpu.data.prefetch import prefetch
from elasticdl_tpu.data.reader import AbstractDataReader, Shard
from elasticdl_tpu.master.task_dispatcher import (
    TASK_EVALUATION,
    TASK_PREDICTION,
    TASK_TRAINING,
    Task,
)
from elasticdl_tpu.models.spec import ModelSpec, load_model_spec_for_job
from elasticdl_tpu.parallel.mesh import create_mesh, mesh_shape, resolve_2d_shape
from elasticdl_tpu.parallel.trainer import Trainer, TrainLoopError

logger = get_logger("worker")


#: The worker's own counters (cumulative since it started; they ride every
#: task report as ``counters``) and the gauge each is published under.
COUNTER_GAUGES = {
    "compiles": (
        "edl_xla_compiles_total", "XLA backend compiles of this process"),
    "compile_s": (
        "edl_xla_compile_seconds_total", "seconds in XLA backend compiles"),
    "hbm_peak_bytes": (
        "edl_hbm_peak_bytes",
        "peak_bytes_in_use + peak_bytes_reserved on the fullest local chip"),
    "dispatches": ("edl_dispatches_total", "training dispatches"),
    "dispatches_device_idle": (
        "edl_dispatches_device_idle_total",
        "training dispatches that found the previous one's output ready: "
        "the device queue had run dry and the chip waited for the host"),
    "stalls": (
        "edl_stalls_total",
        "gaps between two training reports that exceeded the job's own pace "
        "and that the next reports did not catch up (common/stall.py)"),
    "stall_s": (
        "edl_stall_seconds_total",
        "seconds those gaps lost: their excess over the median gap, less "
        "what the next gaps caught up"),
    "stall_unnamed_s": (
        "edl_stall_unnamed_seconds_total",
        "those of them the recorder found no cause for (cause 'unnamed')"),
    "route_rows_recv_max": (
        "edl_route_rows_recv_max_total",
        "table rows the fullest shard served over the ragged route, summed "
        "over training steps"),
    "route_rows_recv_mean": (
        "edl_route_rows_recv_mean_total",
        "table rows a shard served over the ragged route on average, "
        "summed over training steps"),
    "table_grad_rows": (
        "edl_table_grad_rows_total",
        "update rows offered to the embedding tables' gradients, summed over "
        "training steps and devices"),
    "table_grad_rows_swept": (
        "edl_table_grad_rows_swept_total",
        "those of them delivered by the sorted merge sweep "
        "(ops/table_grad.py) and not XLA's scatter-add"),
    "table_grad_rows_fused": (
        "edl_table_grad_rows_fused_total",
        "those of them whose table the merge sweep updated itself (dense "
        "Adam in the kernel, no gradient buffer)"),
    "remat_bytes_tagged": (
        "edl_remat_bytes_tagged_total",
        "bytes of the save sites of the model's rematerialised blocks (the "
        "tensors whose recomputation is a matmul or a kernel: ops/remat.py), "
        "summed over layers, training steps and devices"),
    "remat_bytes_kept": (
        "edl_remat_bytes_kept_total",
        "bytes of those sites the blocks kept for their backward instead of "
        "recomputing them (chosen by bytes against the device's memory), "
        "summed likewise"),
}

#: Step metrics (parallel/trainer.py) that are counts, not model metrics:
#: summed over steps into the counters of the same name, never reported
#: as a task's metrics.  These are the trainer's own; a model's are named
#: by its ``ModelSpec.step_counters`` and join them (gauge
#: ``edl_<name>_total``).
STEP_COUNTERS = (
    "route_rows_recv_max", "route_rows_recv_mean",
    "table_grad_rows", "table_grad_rows_swept", "table_grad_rows_fused",
    "remat_bytes_tagged", "remat_bytes_kept",
)


def _profile_annotation(name: str, attrs: dict):
    """common/trace.py's bridge while a profile window is open: the span
    as a ``jax.profiler.TraceAnnotation``, on the profiler's clock."""
    return jax.profiler.TraceAnnotation(
        name, **{k: v for k, v in attrs.items() if v is not None}
    )


class DirectMasterProxy:
    """In-process master (the reference's no-cluster test pattern).  Applies
    the same wire schemas as the gRPC path so in-process tests catch
    contract drift."""

    def __init__(self, servicer):
        self._s = servicer

    def call(self, method: str, request: dict) -> dict:
        from elasticdl_tpu.common.rpc import MASTER_SCHEMAS, validate_message

        validate_message(method, request, MASTER_SCHEMAS)
        with IN_FLIGHT.of(method):  # as JsonRpcClient.call does
            return self._s.method_table()[method](request)


class RpcMasterProxy:
    """The worker's wire boundary to the master: every ``master.call`` in
    this file funnels here, so the per-call deadline lives here (graftlint
    rpc-discipline treats ``master``-terminal receivers as owned by this
    proxy).  A master RPC that outlives the deadline surfaces as an error
    at the call site instead of wedging the task loop forever on a
    half-dead master.

    Master-outage ride-through (r18): a transport-level failure
    (UNAVAILABLE — the master process is down or restarting) does NOT
    surface to the call site while ``outage_tolerance_s`` lasts; the call
    retries under the shared exponential-backoff-with-jitter helper
    (common/rpc.call_with_backoff), which parks the calling thread — the
    task loop blocks at whatever safe boundary it was crossing, holding
    its buffered leases and in-flight prep, while already-dispatched
    device work keeps streaming.  The first call that succeeds after
    failures marks the proxy RECONNECTED (``take_reconnected``): the
    worker then re-registers with its held-lease inventory so the
    restarted master reconciles against its replayed journal.  Report
    retries across the outage are exactly-once by the report-seq dedup
    (common/rpc MASTER_SCHEMAS), never by hope.  Chaos drop_rpc faults
    raise ``ChaosRpcDropped`` — not a grpc error, deliberately NOT
    retried (r13's blackout fleets depend on drops dying client-side)."""

    #: Transport-level codes worth riding out: the server is not there.
    #: DEADLINE_EXCEEDED is deliberately absent — the call may have
    #: EXECUTED (only reports are dedup-protected), and a deadline on a
    #: live master is a latency pathology the caller should see.
    _TRANSIENT_CODES = (grpc.StatusCode.UNAVAILABLE,)

    def __init__(
        self,
        address: str,
        timeout_s: float = 30.0,
        call_timeout_s: float = 60.0,
        outage_tolerance_s: float = 120.0,
        gauges: Optional[gaugelib.Registry] = None,
    ):
        self._address = address
        self._client = JsonRpcClient(address)
        # Startup vs a slow master: short readiness probes under the
        # shared backoff (a master still binding its port is routine at
        # job start — the old one-shot wait_ready(30) hard-failed a
        # healthy worker), with a clear terminal error naming the flag.
        call_with_backoff(
            lambda: self._client.wait_ready(5.0),
            service="master",
            is_transient=lambda e: isinstance(
                e, (grpc.FutureTimeoutError, grpc.RpcError)
            ),
            policy=BackoffPolicy(
                base_s=0.5, max_s=4.0, budget_s=max(timeout_s, 1.0)
            ),
            terminal=lambda e, n, t: RuntimeError(
                f"master at {address} not reachable after {t:.0f}s "
                f"({n} attempt(s)) — check --master_addr / the master pod"
            ),
        )
        self._call_timeout_s = call_timeout_s
        self._tolerance_s = outage_tolerance_s
        # Reconnect flag, read-then-cleared by the task loop's membership
        # check; sets/reads are single ops (benign race with the beat
        # thread: worst case one extra reconcile handshake).
        self._reconnected = False  # gil-atomic
        self._g_outage = (gauges or gaugelib.default()).counter(
            "edl_master_outage_seconds_total",
            "seconds this worker spent riding out master outages "
            "(proxy reconnect backoff)",
        )

    def call(self, method: str, request: dict) -> dict:
        if self._tolerance_s <= 0:
            return self._client.call(
                method, request, timeout_s=self._call_timeout_s
            )
        state = {"t0": None}

        def _on_retry(e, attempt, delay):
            if state["t0"] is None:
                state["t0"] = time.monotonic()
                logger.warning(
                    "master at %s unreachable (%s on %s); riding out up "
                    "to %.0fs", self._address, type(e).__name__, method,
                    self._tolerance_s,
                )
            self._g_outage.inc(delay)

        def _attempt():
            if state["t0"] is not None:
                # Post-failure attempts force a re-dial first: after a few
                # fail-fast RPCs against a down server, the gRPC channel
                # parks in TRANSIENT_FAILURE and further fail-fast calls
                # do NOT trigger a fresh connection — a restarted master
                # on the same port stays "UNAVAILABLE" forever (observed
                # on grpcio 1.68).  A readiness probe is what re-dials;
                # its own timeout while the master is still down is just
                # the next transient failure.
                self._client.wait_ready(5.0)
            return self._client.call(
                method, request, timeout_s=self._call_timeout_s
            )

        resp = call_with_backoff(
            _attempt,
            service="master",
            is_transient=self._is_transient,
            policy=BackoffPolicy(
                base_s=0.5, multiplier=2.0, max_s=8.0, jitter=0.25,
            ),
            # Dynamic, not captured: limit_outage_tolerance (the
            # preemption path) must cut a ride-through that is ALREADY
            # parked in this loop short at its next wake, not after the
            # originally captured 120 s.
            budget_s_fn=lambda: self._tolerance_s,
            on_retry=_on_retry,
            terminal=lambda e, n, t: RuntimeError(
                f"master outage outlived --master_outage_tolerance_s: "
                f"{self._address} unreachable for {t:.0f}s across {n} "
                f"attempt(s) of {method}"
            ),
        )
        if state["t0"] is not None:
            outage_s = time.monotonic() - state["t0"]
            self._reconnected = True
            trace.instant(
                "worker:reconnect", cat="elastic", method=method,
                outage_s=round(outage_s, 3),
            )
            logger.warning(
                "master back after %.1fs outage (%s); reconcile pending",
                outage_s, method,
            )
        return resp

    @classmethod
    def _is_transient(cls, e: BaseException) -> bool:
        if isinstance(e, grpc.FutureTimeoutError):
            # The post-failure readiness probe timed out: still down.
            return True
        return (
            isinstance(e, grpc.RpcError)
            and getattr(e, "code", lambda: None)() in cls._TRANSIENT_CODES
        )

    def take_reconnected(self) -> bool:
        """True once per ridden-out outage: the caller owes the master a
        re-register + lease-reconcile handshake."""
        if not self._reconnected:
            return False
        self._reconnected = False
        return True

    def limit_outage_tolerance(self, budget_s: float) -> None:
        """Shrink (never grow) the ride-through budget — the preemption
        path calls this with a couple of seconds: a process that must be
        GONE inside PREEMPTION_EXIT_S cannot park two minutes in the
        outage backoff waiting for a master that may be restarting (the
        snapshot it still owes matters more than the report, whose loss
        the master's task timeout already covers).  Single float store,
        read per call; affects every thread of this proxy, which is the
        point — the whole process is exiting."""
        self._tolerance_s = min(self._tolerance_s, max(0.0, budget_s))


def _minibatches(
    records: List[bytes], batch_size: int, train: bool
) -> Iterable[tuple]:
    """Split shard records into fixed-size minibatches (static shapes for
    XLA).  The tail is wrap-padded to full size; yields (records, true_count)
    so eval weighting can use the real example count."""
    for start in range(0, len(records), batch_size):
        chunk = records[start : start + batch_size]
        true_count = len(chunk)
        if true_count < batch_size:
            # The tail de-packs to a plain list for the wrap; it is at most
            # one minibatch per task, off the hot path.
            chunk = list(chunk)
            reps = (batch_size + true_count - 1) // true_count
            chunk = (chunk * reps)[:batch_size]
        yield chunk, true_count


class WorkerRestartRequired(RuntimeError):
    """Raised when an elastic membership change needs a process restart
    (multihost mode: the jax.distributed world is fixed per process).  The
    worker main exits with RESTART_EXIT_CODE; the pod manager relaunches
    without consuming the failure budget."""


RESTART_EXIT_CODE = 3


class HostPrep(NamedTuple):
    """Result of a training task's host half (read + decode + stack).

    ``stacked`` is the ``[T, mb, ...]`` host batch over the ``n_full`` full
    minibatches (None when the task has none); ``tail`` is the plain record
    list past the last full minibatch (at most one minibatch — it trains as
    a wrap-padded masked step); ``total`` is the task's true record count.
    The parallel ingest path (data/ingest_pool.py) produces this from
    per-chunk decodes reassembled in record order, so it is bit-identical
    to the serial read — the contract tests pin."""

    total: int
    n_full: int
    stacked: Optional[dict]
    tail: List[bytes]


class Worker:
    def __init__(
        self,
        config: JobConfig,
        master,
        reader: AbstractDataReader,
        worker_id: str = "worker-0",
        spec: Optional[ModelSpec] = None,
        devices: Optional[Sequence[jax.Device]] = None,
        devices_per_worker: int = 0,
        poll_interval_s: float = 0.05,
        gauges: Optional[gaugelib.Registry] = None,
        incarnation: Optional[str] = None,
        setup: Optional[trace.SetupChain] = None,
        programs: Optional[ProgramStore] = None,
    ):
        # Where this worker's trainer keeps and looks for its compiled
        # train steps (common/program_store.py; worker.main passes the
        # process's).  Only a worker that loads its spec from the config
        # itself uses it: a spec handed in is not described by the config a
        # key is made of.
        self._programs = programs if spec is None else None
        # This incarnation's set-up chain (common/trace.py SetupChain):
        # worker.main hands over the process's, already marked up to the
        # device's opening; a standalone Worker's starts here.  _run and
        # the first training dispatch add their marks, the first
        # successful training report carries the whole to the master, and
        # from then on this is None: "chain already sent" is the one check
        # the task loop pays.
        self._setup: Optional[trace.SetupChain] = (  # single-writer: main
            setup if setup is not None else trace.SetupChain()
        )
        self.config = config
        self.master = master
        self.reader = reader
        self.worker_id = worker_id
        self.spec = spec or load_model_spec_for_job(config)
        self._pool = list(devices) if devices is not None else list(jax.devices())
        # Devices contributed per worker: in a real multi-host world each
        # worker is one host, so its share is its LOCAL device count (the
        # pool is global after jax.distributed.initialize); tests passing an
        # explicit pool emulate elasticity over that pool instead.
        if devices_per_worker:
            self._dpw = devices_per_worker
        elif devices is not None:
            self._dpw = len(self._pool)
        else:
            self._dpw = len(jax.local_devices())
        self._poll = poll_interval_s

        # The trainer/state pair is REPLACED only by the task loop
        # (membership reform, restore); checkpoint/prep threads read
        # the reference they were spawned with (happens-before via
        # thread start / _join_ckpt).
        self.trainer: Optional[Trainer] = None  # single-writer: main
        self.state = None  # single-writer: main
        self._membership_version = -1  # single-writer: main (the beat reads one int)
        self._rank = 0  # single-writer: main (reform happens on the task loop)
        # Replaced wholesale (fresh dicts) on reform; beat-thread readers
        # see either the old or the new reference, never a mid-mutation.
        self._ranks: Dict[str, int] = {}  # single-writer: main
        self._addresses: Dict[str, str] = {}  # single-writer: main
        # Multi-host lockstep: all processes of the world walk the master's
        # group task log in the same order (GetGroupTask seq counter); only
        # rank 0 reports results.
        self._group_mode = False  # single-writer: main
        self._task_seq = 0
        # Gang-boundary ARRIVAL counter (r13, the deadline-bounded gang
        # boundary's per-rank progress signal): group-log entries whose
        # device dispatch this rank has BEGUN.  Incremented immediately
        # before the first (blocking, collective-bearing) device call of
        # each group task, so a rank blocked INSIDE a wedged collective
        # has counted the entry while the straggler that never arrived at
        # it has not — consumption counters (_task_seq, boundary ask seq)
        # cannot make that distinction: lease batching and prep-ahead
        # freeze every rank's consumption at the same value the moment
        # the gang wedges.  Read cross-thread by the liveness beat (int
        # read under the GIL), which is the only RPC still leaving this
        # process while the task loop is blocked in the collective.
        # _gang_last_task guards the count against the in-place transient
        # collective retry (_retry_transient_collective re-dispatches the
        # SAME entry): a retried rank must not drift ahead of its peers,
        # or the deadline would read every HEALTHY rank as the laggard.
        self._gang_dispatched = 0  # single-writer: main (beat reads a recent value)
        self._gang_last_task = -1
        self._ckpt: Optional[CheckpointManager] = None
        # Checkpoint watermark + background-save thread handle: touched by
        # the task loop, the background save thread (failure rollback), and
        # the preemption thread.  The leaf lock makes the hand-off explicit
        # (graftlint lock-discipline); nothing blocking ever runs under it.
        self._ckpt_lock = locksan.lock("Worker._ckpt_lock", leaf=True)  # lock-order: leaf
        self._last_ckpt_step = 0  # guarded-by: _ckpt_lock
        # The newest step whose dense save is KNOWN committed (the manager's
        # wait returned on the thread that saved it).  NOT the watermark: a
        # failed group save keeps its watermark on purpose, so only this
        # says that the job-end save has nothing left to write.
        self._ckpt_committed_step = 0  # guarded-by: _ckpt_lock
        self.reforms = 0  # elastic mesh re-formations (observability/tests)
        self._training_tasks_done = 0  # training tasks dispatched
        # --profile_dir window (_profile_open_if_due): "" until it opens,
        # then "open", then "closed" for the rest of the worker's life.
        # The preemption thread reaches the close through _flush_pending,
        # which the _parked handshake serializes against the loop (see
        # preemption_snapshot): one writer at a time.
        self._profile_state = ""  # single-writer: main
        self._profile_traced = 0  # single-writer: main (dispatches inside the window)
        # Task whose report closes the window (the last traced one).
        self._profile_last_task: Optional[int] = None  # single-writer: main
        self._profile_closer: Optional[threading.Thread] = None  # single-writer: main
        self._profile_stops = 0  # single-writer: main (profiler stops begun)
        # Training dispatches, and those among them that began with the
        # previous dispatch's output already ready (the device had nothing
        # queued): see _dispatch_training_task.  _last_output is that
        # output's last leaf, kept for the one non-blocking is_ready().
        self._dispatches = 0  # single-writer: main
        self._dispatches_idle = 0  # single-writer: main
        self._last_output = None
        # Newest counter snapshot (_counter_snapshot): replaced wholesale
        # at every report, republished as gauges at scrape time.
        self._counters: Dict[str, float] = {}  # gil-atomic
        # Running sums of STEP_COUNTERS and of the model's own step
        # counters: added to wherever a task's metrics settle (the task
        # loop; the preemption thread's last flush).
        self._step_counts_lock = threading.Lock()
        self._step_counts = dict.fromkeys(  # guarded-by: _step_counts_lock
            (*STEP_COUNTERS, *self.spec.step_counters), 0.0
        )
        count_compiles()
        # Task-level pipeline: the previous training task's (report, device
        # metrics), fetched + reported only after the NEXT task's steps are
        # dispatched (see _dispatch_training_task for why).
        self._pending: Optional[tuple] = None
        # Prep-ahead pipeline (fused + pipelined mode): a bounded k-deep
        # queue of (task, report, host-prep future) for leased tasks whose
        # host half (bulk read + C++ decode + stacking) is in flight on the
        # prep pool while earlier tasks' transfers stream and metrics
        # settle (see run()).  Depth = config.prep_depth; 1 reproduces the
        # r6 one-slot behavior.  Each prep fans its chunk decodes out to
        # the shared IngestPool (config.ingest_threads).
        self._prep_queue: deque = deque()
        self._prep_pool = None
        # Built eagerly (ThreadPoolExecutor spawns its threads lazily on
        # first submit, so an eval/predict-only job still pays nothing):
        # prep_depth > 1 means _prep_fused_host runs concurrently on prep
        # threads, and a lazy check-then-create there would race into two
        # pools of decode threads competing for the same cores.
        self._ingest = IngestPool(config.ingest_threads)
        # Locally buffered task leases (batched GetTask/GetGroupTask, r9):
        # tasks the master leased in one RPC beyond the one being started.
        # Unstarted leases are returned to the master on preemption or
        # membership change (_abandon_leases) so elasticity semantics stay
        # requeue-on-loss/at-least-once.
        self._leased: deque = deque()
        self._tasks_done = 0
        # Report sequence numbers (r18): every ReportTaskResult carries a
        # per-worker monotone seq so the master can DEDUPE a retried
        # report across its own restart (the proxy's outage ride-through
        # re-sends the in-flight call; the old master may have applied +
        # journaled it before dying).  See MASTER_SCHEMAS.
        self._report_seq = 0
        # Process-incarnation nonce for the reconcile handshake: the
        # master resets a worker's report-seq dedup ledger when the
        # incarnation CHANGES (a fresh process restarts its seq counter
        # at 1).  worker.main passes the one it already registered with;
        # standalone Workers mint their own.
        self._incarnation = (
            incarnation or f"{os.getpid()}-{int(time.time() * 1e3)}"
        )
        # Python-side step counter mirroring state.step: reading the device
        # scalar would drain the dispatch pipeline at every task boundary.
        self._steps_dispatched = 0  # single-writer: main (prep/ckpt threads read a recent value)
        # Set by preemption_snapshot (SIGTERM thread): the task loop parks
        # at its next boundary instead of dispatching more work, so the
        # live state leaves the donated-in-flight window and can be saved.
        # _parked acknowledges the park — once True, the loop only sleeps,
        # so self.state can no longer be donated or reassigned.
        self._preempting = False  # single-writer: thread:preemption
        self._parked = False  # single-writer: main (the preemption thread spin-reads it)
        # Background periodic-checkpoint machinery (_save_snapshot_background
        # / _save_group_snapshot_background)
        self._ckpt_thread = None  # guarded-by: _ckpt_lock
        # graftgauge (r14): the live metrics registry this worker updates
        # from its hot path — counters for examples/steps/tasks, depth
        # gauges and the per-phase families collected at scrape time.
        # An INSTANCE per worker (in-process test fleets run several
        # workers in one process and each must keep its own families);
        # worker.main passes the process-default registry so the one
        # scrape endpoint also serves cross-cutting client-side families
        # (the PS retry counter).  Snapshots ride the Heartbeat/Report
        # ``gauge`` envelope (gauge_payload) so the master's endpoint can
        # aggregate the fleet.
        self.gauges = gauges if gauges is not None else gaugelib.Registry()
        self._g_examples = self.gauges.counter(
            gaugelib.EXAMPLES_TRAINED, "examples trained (records dispatched)"
        )
        self._g_steps = self.gauges.counter(
            gaugelib.STEPS_DISPATCHED, "device steps dispatched"
        )
        self._g_tasks = self.gauges.counter(
            gaugelib.TASKS_DONE, "training/eval/predict tasks completed"
        )
        self.gauges.add_collector(self._collect_gauges)
        # Envelope throttle: the loop heartbeat fires every task-loop
        # iteration (up to 1/poll_interval per second), and a full
        # registry snapshot per beat would be the dominant new
        # per-iteration cost — the fleet view needs ~1 Hz freshness, the
        # same cadence an external scraper would poll at.  Reports
        # (bounded frequency) bypass the throttle so the JSONL mirror
        # never starves.  Benign race between the loop beat and the
        # background liveness beat: worst case one extra snapshot.
        self._gauge_ship_interval_s = 1.0
        # Ship throttle: a cross-thread TOCTOU double-ship is harmless
        # (the fleet view banks the newest snapshot), so single-op
        # atomicity is the whole consistency story.
        self._last_gauge_ship = 0.0  # gil-atomic
        # Per-phase wall decomposition of the task loop (common/metrics.py
        # PhaseTimers); snapshots ride every report so the master and the
        # train-job artifact can attribute the job-vs-bench gap to named
        # phases.  The registry hook adds a per-entry duration histogram
        # per phase (edl_phase_ms) to the live scrape.
        self.phases = PhaseTimers(gauges=self.gauges)
        # The stall recorder (common/stall.py): judges the gap between two
        # training reports from the job's own pace, at each report; its
        # watchdog thread lives from run() to run()'s end.
        self._stalls = StallRecorder(
            self.phases, self._stall_probes, self._output_ready
        )
        # grafttrace: --trace turns the per-process span recorder on (every
        # phase above doubles as a span; RPC boundaries, gang waits and
        # elastic transitions add their own).  Bounded slices ship to the
        # master on the heartbeat/report channel; the RTT-midpoint clock
        # offset below is measured against the Heartbeat server stamp so
        # tools/trace_dump.py can align this process onto the master clock.
        if config.trace:
            trace.configure(
                enabled=True, capacity=config.trace_buffer_events
            )
        self._trace_clock_offset_us: Optional[float] = None  # single-writer: main (beat readers tolerate one stale estimate)
        # graftchaos (chaos/inject.py): the --chaos fault plan rides the
        # config bus exactly like --trace; faults address this process by
        # worker id or rank (set_context keeps the rank current across
        # reforms — see _apply_membership).
        if config.chaos:
            chaos.configure(config.chaos)
        chaos.set_context(worker_id=worker_id, rank=self._rank)
        # graftreduce in-step deadline gate (r15, _collective_gate): each
        # dp shard's host-side contribution crosses the gate before a
        # training task dispatches; one that stalls past
        # --collective_deadline_ms is EXCLUDED from the task's
        # collectives (subgroup mask -> trainer.set_active_contributors)
        # instead of holding every other shard.  All state below is
        # task-loop-thread-only (the daemon crossing threads run nothing
        # but the chaos hook crossing and an Event.set); the counters
        # are plain ints read by the heartbeat on the same thread.
        self._collective_pending: Dict[int, Any] = {}  # shard -> stalled crossing
        self._collective_consec: Dict[int, int] = {}  # consecutive exclusions
        self._collective_skips = 0  # cumulative (task, shard) exclusions
        self._g_coll_skips = self.gauges.counter(
            "edl_collective_skip_total",
            "in-collective straggler exclusions (task x shard) charged by "
            "the r15 in-step deadline gate",
        )
        self._g_coll_subgroup = self.gauges.gauge(
            "edl_collective_subgroup_size",
            "contributors the current training collectives reduce over "
            "(world size minus in-step exclusions)",
        )
        self._g_coll_bytes = self.gauges.counter(
            "edl_collective_interhost_bytes_total",
            "analytic per-replica inter-host bytes of the dense-grad "
            "all-reduce (collectives.interhost_bytes_per_step's model)",
        )
        # Analytic inter-host bytes per step under the resolved topology;
        # computed lazily at the first dispatch (needs the placed params)
        # and invalidated per mesh re-formation.
        self._collective_step_bytes: Optional[int] = None

        if config.checkpoint_dir:
            self._ckpt = CheckpointManager(
                config.checkpoint_dir, keep_max=config.keep_checkpoint_max
            )

    # ---- membership / elasticity ----

    def _mesh_size(self, world_size: int) -> int:
        return max(1, min(world_size * self._dpw, len(self._pool)))

    def _advertised_address(self) -> str:
        if not self.config.multihost:
            return ""
        from elasticdl_tpu.parallel.distributed import advertised_address

        return advertised_address()

    def _apply_membership(self, membership: dict, initial: bool = False) -> None:
        version = membership["version"]
        if version == self._membership_version:
            return
        if not initial and dict(membership["ranks"]) == self._ranks and (
            dict(membership.get("addresses") or {}) == self._addresses
        ):
            # Version churn with IDENTICAL topology: a peer's restart cycle
            # bumps the version twice (stale-incarnation eviction, then
            # re-registration) and can net out to exactly the membership
            # this worker already runs.  Restarting on the NUMBER alone made
            # two workers ping-pong restarts forever (each restart causing
            # the next bump); the world is defined by ranks+addresses, so
            # adopt the version and keep the world.
            #
            # Accepted hazard: ranks+addresses cannot distinguish a
            # RELAUNCHED peer on the same host from the incarnation this
            # worker's jax world actually spans, so adoption can briefly
            # keep a world whose peer process is new.  That wedge is
            # BOUNDED: the next collective aborts on the coordination
            # heartbeat (--distributed_heartbeat_timeout_s) and the restart
            # path re-forms.  Comparing per-worker incarnation nonces
            # instead would close the wedge but re-open the ping-pong (a
            # restart always bumps its own nonce, forcing the peer to
            # restart, which bumps again...), which does NOT self-heal —
            # the bounded wedge is the better failure mode.
            logger.info(
                "membership v%d has identical topology; adopting without "
                "re-forming", version,
            )
            self._membership_version = version
            return
        world = max(membership["world_size"], 1)
        prev_ranks = self._ranks
        self._ranks = dict(membership["ranks"])
        self._addresses = dict(membership.get("addresses") or {})
        self._rank = self._ranks.get(self.worker_id, 0)
        # Rank-addressed chaos faults must follow the rank across reforms.
        chaos.set_context(rank=self._rank)
        self._group_mode = self.config.multihost and len(self._ranks) > 1
        if self.config.multihost and not initial:
            # The jax.distributed world is fixed per process (PJRT can't be
            # re-formed in-process): snapshot, then restart.  The pod
            # manager relaunches RESTART exits without burning the relaunch
            # budget; the fresh process joins the new world at startup and
            # resumes from the checkpoint (the reference's elastic-Horovod
            # re-rendezvous, done the process way).
            #
            # The snapshot must come from a SURVIVOR of the previous
            # membership — a newly joined worker can take new-rank 0 with no
            # state, and gating on new rank would then silently lose all
            # progress since the last periodic checkpoint.  The lowest
            # previous-rank worker still present in the new membership saves.
            #
            # Only when the OLD world was single-process, though: in a
            # multi-process world every Orbax save is a COLLECTIVE (all
            # processes barrier; the primary writes), and the very reason the
            # membership changed is usually that a peer died — a lone
            # snapshot would deadlock in the barrier.  Multi-process worlds
            # rely on their periodic checkpoints (which are collective).
            was_group = self.config.multihost and len(prev_ranks) > 1
            survivors = set(prev_ranks) & set(self._ranks)
            saver = (
                min(survivors, key=lambda w: prev_ranks[w]) if survivors else None
            )
            if (
                not was_group
                and self._ckpt is not None
                and self.worker_id == saver
                and self.state is not None
            ):
                try:
                    # A background periodic save may be mid-flight on the
                    # same manager; interleaving two saves tears both.
                    self._join_ckpt()
                    step = int(self.state.step)
                    # host_state: the CANONICAL layout — a dp-sharded
                    # optimizer state must land on disk topology-agnostic,
                    # the relaunch may join a different world size.
                    self._ckpt.save(
                        step, self.trainer.host_state(self.state), wait=True
                    )
                    # Relaunched processes restore from the LOCAL checkpoint
                    # directory at startup (run()'s newest-restorable walk);
                    # this snapshot makes the resume point the pre-restart
                    # step instead of the last PERIODIC checkpoint.  The
                    # report is observability (JobStatus / metrics stream).
                    self.master.call(
                        "ReportCheckpoint",
                        {"path": self._ckpt.directory, "step": step},
                    )
                except Exception:
                    # A broken runtime must not block the restart itself —
                    # the periodic checkpoint covers the resume.
                    logger.exception("pre-restart snapshot failed; restarting anyway")
            trace.instant(
                "elastic:restart_required", cat="elastic",
                version=version, world=world,
            )
            raise WorkerRestartRequired(
                f"membership v{version}: world changed to {world} hosts"
            )
        n_dev = self._mesh_size(world)
        dcn = self.config.dcn_data_parallelism
        if dcn > 1 and n_dev % dcn != 0:
            # Training availability beats layout: an elastic resize can land
            # on a device count the configured hierarchy no longer divides
            # (dcn=2 after shrinking to 3 hosts) — fall back to the flat
            # mesh instead of crash-looping the relaunch budget away.
            # Checked HERE (not via exception) so a genuine too-few-devices
            # ValueError below keeps its own story.
            logger.warning(
                "dcn_data_parallelism=%d does not divide %d devices; "
                "falling back to a flat 1-D mesh",
                dcn, n_dev,
            )
            dcn = 1
        tp_conf = int(getattr(self.config, "tensor_parallelism", 1))
        if tp_conf > 1:
            # Hybrid-parallel (r20): reform picks a LEGAL 2D shape for the
            # live device count — tp preserved (the weight shards must keep
            # fitting one device), dp shrinks first; tp only degrades along
            # its divisor chain when fewer than tp devices remain
            # (mesh.resolve_2d_shape).  The r13/r15 deadline layers sit
            # ABOVE this choice unchanged: gang membership decides n_dev,
            # this just decides its factorization.
            dp, tp = resolve_2d_shape(n_dev, tp_conf)
            if dp * tp != n_dev:
                logger.warning(
                    "tensor_parallelism=%d: %d devices factor to dp=%d x "
                    "tp=%d; %d device(s) idle until the next reform",
                    tp_conf, n_dev, dp, tp, n_dev - dp * tp,
                )
            mesh = create_mesh(
                self._pool, num_devices=dp * tp, tensor_parallelism=tp
            )
        else:
            mesh = create_mesh(
                self._pool, num_devices=n_dev, dcn_parallelism=dcn
            )
        if initial or self.trainer is None:
            self.trainer = Trainer(self.spec, self.config, mesh, programs=self._programs)
        elif (
            list(self.trainer.mesh.devices.flat) == list(mesh.devices.flat)
            and self.trainer.mesh.shape == mesh.shape
        ):
            # Identical mesh: a non-multihost pool worker sees peers join
            # and leave without its LOCAL device set ever changing
            # (n_dev = min(world*dpw, len(pool)) saturates), and
            # re-sharding state onto the same devices is pure churn — a
            # dropped dispatch pipeline at best, and on the 1-real-cpu-
            # device harness an XLA:CPU crash at worst (the chaos bench's
            # pool fleets segfaulted HERE on every peer churn before this
            # guard).  Adopt the version; keep the trainer.
            logger.info(
                "membership v%d keeps this worker's mesh (%d devices); "
                "adopting without re-forming", version, mesh.devices.size,
            )
        else:
            self.reforms += 1
            old_dp, old_tp = mesh_shape(self.trainer.mesh)
            new_dp, new_tp = mesh_shape(mesh)
            logger.info(
                "membership v%d -> re-forming mesh to %d devices "
                "(dp%dxtp%d -> dp%dxtp%d)",
                version, mesh.devices.size, old_dp, old_tp, new_dp, new_tp,
            )
            trace.instant(
                "elastic:reform", cat="elastic",
                version=version, devices=int(mesh.devices.size),
                old_shape=f"{old_dp}x{old_tp}",
                new_shape=f"{new_dp}x{new_tp}",
            )
            self.trainer.set_mesh(mesh)
            self._replace_state()
        self._membership_version = version

    def _replace_state(self) -> None:
        """Re-place state on the re-formed mesh: restore the latest checkpoint
        if one exists (the reference's recover-from-snapshot path), else
        re-shard the live state (pure in-process resize).

        Both paths bridge through the trainer's CANONICAL host layout
        (``host_state``), so a dp-sharded optimizer state is
        REDISTRIBUTED across the new world size — a 4->8->4 resize moves
        the existing Adam moments, it never re-initializes them."""
        assert self.trainer is not None
        restored = None
        # Settle any in-flight BACKGROUND save first: latest_step() must not
        # see a step whose host-store half is still being written (the
        # bg thread runs the whole trio outside Orbax's own wait scope).
        self._join_ckpt()
        if self._ckpt is not None and self._ckpt.latest_step() is not None:
            self._ckpt.wait()
            template = self.trainer.shard_state(
                self.trainer.host_state(self.state)
            )
            restored = self._restore_checkpoint(template)
            try:
                self.trainer.restore_host_stores(
                    self._ckpt.directory, int(restored.step)
                )
            except FileNotFoundError:
                # In-process resize: the LIVE host stores survive in this
                # trainer, so a missing snapshot is tolerable (slightly newer
                # rows than the restored dense step) — log, don't die.
                logger.warning(
                    "no host-store snapshot for step %d; keeping live rows",
                    int(restored.step),
                )
            logger.info("restored checkpoint step %d", int(restored.step))
        if restored is None:
            restored = self.trainer.shard_state(
                self.trainer.host_state(self.state)
            )
        self.state = restored
        # graftreduce (r15): the mesh changed, so the contributor set and
        # the analytic inter-host bytes/step change with it.  Stalled
        # contributions of the OLD mesh are dropped (their futures run
        # out harmlessly on the gate pool) and the mask is all-active
        # again (trainer._adopt_mesh_axes already reset it).
        self._collective_pending.clear()
        self._collective_consec.clear()
        self._collective_step_bytes = None

    def _restore_checkpoint(self, state_like, step: Optional[int] = None):
        """Restore a checkpoint step into the live mesh AND optimizer
        layout.  Checkpoints always hold the canonical (unsharded)
        optimizer leaves; restore_template aims the read at param-shaped
        replicated targets when the live layout is dp-sharded, and
        adopt_restored lays the result back out flat over the shard axis.
        Replicated mode degenerates to the old direct restore-into-mesh
        path."""
        restored = self._ckpt.restore(
            self.trainer.restore_template(state_like), step=step
        )
        return self.trainer.adopt_restored(restored)

    # thread-role: thread:heartbeat — the beat thread (worker.main _beat)
    # reaches this through the worker holder dict, a hand-off the static
    # resolver cannot see.
    def death_watch_tick(
        self, state: dict, now: float, master_version=None
    ) -> bool:
        """One death-push decision (called from the liveness-heartbeat
        thread, worker.main): return True when this process must force-exit
        RESTART because a gang peer DIED while the main thread is wedged in
        a blocked collective.

        The main thread only notices membership changes at task boundaries
        (``_check_membership``); a survivor blocked in a collective on a
        dead peer otherwise waits out the jax.distributed coordination
        heartbeat (``--distributed_heartbeat_timeout_s``, default 30 s —
        the avoidable middle of a re-rendezvous on the CPU harness,
        round 4).  The master's reaper already knows within
        ~3 s; this push closes the gap: poll the master's version, and when
        a previous member has DEPARTED and the main thread still hasn't
        applied the change after ``death_push_grace_s``, exit now.

        Deliberately narrow:
        - pure JOINS never force-exit (the running task completes; the main
          loop restarts gracefully at the boundary — aborting would waste
          its work);
        - identical-topology churn never force-exits (the adoption path,
          see ``_apply_membership``);
        - the grace window lets an unblocked main thread win the race and
          do the snapshot-then-restart path;
        - only group mode (world > 1): a lone worker has no collective to
          be stuck in.

        ``state`` carries ``pending_since`` between ticks; it must be reset
        by the caller if the worker restarts in place.
        """
        if not self._group_mode or self.config.death_push_grace_s <= 0:
            state["pending_since"] = None
            return False
        if (
            master_version is not None
            and master_version == self._membership_version
        ):
            # The caller's own Heartbeat response already proves nothing
            # changed — skip the GetMembership RPC (the steady-state path,
            # so the push costs zero extra control-plane load).
            state["pending_since"] = None
            return False
        try:
            membership = self.master.call("GetMembership", {})
        except Exception:
            return False  # master briefly unreachable: retry next beat
        if membership["version"] == self._membership_version:
            state["pending_since"] = None
            return False
        same_topology = dict(membership["ranks"]) == self._ranks and dict(
            membership.get("addresses") or {}
        ) == self._addresses
        departed = set(self._ranks) - set(membership["ranks"])
        if same_topology or not departed:
            state["pending_since"] = None
            return False
        since = state.get("pending_since")
        if since is None:
            state["pending_since"] = now
            return False
        if now - since < self.config.death_push_grace_s:
            return False
        logger.warning(
            "death push: peer(s) %s departed (membership v%s vs applied "
            "v%s) and the main thread has not re-formed within %.1fs — "
            "assuming a blocked collective; forcing RESTART now",
            sorted(departed), membership["version"],
            self._membership_version, self.config.death_push_grace_s,
        )
        return True

    # thread-role: thread:heartbeat — ditto: invoked from the beat thread
    # via the worker holder.
    def gang_beat_fields(self) -> dict:
        """Fields the background liveness beat (worker.main ``_beat``)
        adds to its Heartbeat so the deadline-bounded gang boundary keeps
        seeing per-rank arrival progress while the task loop is blocked
        inside a wedged collective — the loop's own heartbeat (the other
        carrier) is silent exactly then.  Plain int/None reads under the
        GIL; safe from the beat thread."""
        if not self._group_mode:
            return {}
        return {
            "gang_seq": self._gang_dispatched,
            "version": self._membership_version,
        }

    def _collect_gauges(self) -> None:
        """Scrape-time collector (never the task loop): pull-model
        families that are cheap to READ — depths are GIL-safe ``len``s,
        the phase families re-publish ``PhaseTimers`` cumulative state —
        refreshed per scrape/snapshot instead of being pushed per
        update."""
        g = self.gauges
        g.gauge("edl_membership_version", "applied membership version").set(
            float(self._membership_version)
        )
        g.gauge("edl_rank", "rank in the current membership").set(
            float(self._rank)
        )
        g.gauge("edl_reforms_total", "elastic mesh re-formations").set(
            float(self.reforms)
        )
        g.gauge(
            gaugelib.LEASE_DEPTH, "locally buffered task leases"
        ).set(float(len(self._leased)))
        g.gauge(
            gaugelib.PREP_QUEUE_DEPTH, "prep-ahead tasks in flight"
        ).set(float(len(self._prep_queue)))
        if self._group_mode:
            g.gauge(
                "edl_gang_dispatched",
                "gang-boundary arrivals (lockstep entries begun)",
            ).set(float(self._gang_dispatched))
        if self.trainer is not None:
            # Current subgroup size from the trainer's live mask (reads
            # correctly even when the gate never armed: all-active).
            self._g_coll_subgroup.set(
                float(self.trainer.active_contributors().sum())
            )
            # The live mesh's (dp, tp) shape (mesh.mesh_shape — a 1-D mesh
            # reads dp=n, tp=1), one sample per axis; watch_job renders
            # the pair as its "mesh: dpNxtpM" line.
            dp, tp = mesh_shape(self.trainer.mesh)
            for ax, val in (("dp", dp), ("tp", tp)):
                g.gauge(
                    "edl_mesh_shape",
                    "current mesh extent per axis (dp=data, tp=model)",
                    labels={"axis": ax},
                ).set(float(val))
        # The newest report's counters (no device read at scrape time).
        for key, value in self._counters.items():
            family, doc = COUNTER_GAUGES.get(key) or (
                f"edl_{key}_total", self.spec.step_counters[key]
            )
            g.gauge(family, doc).set(float(value))
        for name, secs in self.phases.snapshot().items():
            g.gauge(
                "edl_phase_seconds_total",
                "cumulative seconds per task-loop phase",
                labels={"phase": name},
            ).set(secs)
        for name, n in self.phases.counts().items():
            g.gauge(
                "edl_phase_entries_total",
                "entries per task-loop phase",
                labels={"phase": name},
            ).set(float(n))

    # thread-role: thread:heartbeat — also shipped by the beat thread
    # (besides the loop heartbeat and checkpoint reports).
    def gauge_payload(self, force: bool = False) -> Optional[dict]:
        """The Heartbeat/Report ``gauge`` envelope: this worker's full
        registry snapshot (collectors run, so depths and phase families
        are fresh).  None when the registry is disabled, or — unless
        ``force`` — when one shipped within the last
        ``_gauge_ship_interval_s`` (the loop heartbeat fires every
        iteration; the fleet view needs ~1 Hz).  Called from
        control-plane boundaries only — the heartbeat in
        ``_check_membership``, the background liveness beat, checkpoint
        reports (forced: the JSONL mirror rides them) — never a
        ``# hot-path`` function (gauge-discipline)."""
        if not self.gauges.enabled:
            return None
        now = time.monotonic()
        if not force and now - self._last_gauge_ship < self._gauge_ship_interval_s:
            return None
        self._last_gauge_ship = now
        return {"families": self.gauges.snapshot()}

    def _trace_payload(self) -> Optional[dict]:
        """One bounded slice of this process's trace ring for the
        heartbeat/report channel, with the latest clock-offset estimate —
        or None when tracing is off or the buffer is empty.  Draining here
        (a control-plane boundary, NOT a ``# hot-path`` function) is
        exactly the split the trace-discipline lint rule enforces."""
        rec = trace.default()
        if not rec.enabled:
            return None
        events = rec.drain_slice(trace.SHIP_BATCH)
        if not events:
            return None
        payload: dict = {"events": events, "dropped": rec.dropped}
        if self._trace_clock_offset_us is not None:
            payload["clock_offset_us"] = self._trace_clock_offset_us
        return payload

    def _held_task_ids(self) -> List[int]:
        """Every training-task id this worker still HOLDS: buffered
        leases, queued preps, and the pipelined pending slot — the
        reconcile handshake's inventory.  Task-loop thread only."""
        held: List[int] = []
        for entry in self._leased:
            t = entry.get("task")
            if t:
                held.append(int(t["task_id"]))
        held.extend(task.task_id for task, _r, _f in self._prep_queue)
        if self._pending is not None:
            held.append(int(self._pending[0]["task_id"]))
        return held

    def _reconcile_with_master(self) -> None:
        """Post-outage handshake (r18): the proxy just rode out a master
        restart — re-register (the rendezvous is fresh) declaring the
        leases this worker holds, so the restarted master requeues its
        journal-replayed ``doing`` entries we DON'T hold and tells us
        which held ones IT no longer attributes to us (``stale_tasks`` —
        dropped unstarted here; training them would double-train records
        the master already re-leased).  Group mode declares nothing: the
        lockstep log owns gang leases, and its version-keyed
        invalidation requeues them master-side."""
        held = [] if self._group_mode else self._held_task_ids()
        resp = self.master.call(
            "RegisterWorker",
            {
                "worker_id": self.worker_id,
                "address": self._advertised_address(),
                "proto": PROTOCOL_VERSION,
                "incarnation": self._incarnation,
                "held_tasks": held,
            },
        )
        stale = {int(t) for t in resp.get("stale_tasks") or []}
        dropped = 0
        if stale and not self._group_mode:
            kept = deque()
            for entry in self._leased:
                t = entry.get("task")
                if t and int(t["task_id"]) in stale:
                    dropped += 1
                    continue
                kept.append(entry)
            self._leased = kept
            kept_prep: deque = deque()
            for task, report, fut in self._prep_queue:
                if task.task_id in stale:
                    fut.cancel()
                    dropped += 1
                    continue
                kept_prep.append((task, report, fut))
            self._prep_queue = kept_prep
        trace.instant(
            "worker:reconcile", cat="elastic",
            held=len(held), stale=len(stale), dropped=dropped,
            version=resp.get("version"),
        )
        logger.info(
            "reconciled with restarted master: declared %d held lease(s), "
            "dropped %d stale", len(held), dropped,
        )

    def _check_membership(self) -> None:
        # Post-outage reconcile FIRST (r18): the proxy flags the first
        # successful call after a ridden-out master outage, and the lease
        # inventory must reach the restarted master before this loop
        # leases anything new against its replayed queues.
        take = getattr(self.master, "take_reconnected", None)
        if take is not None and take():
            self._reconcile_with_master()
        # The heartbeat carries the version this worker has APPLIED: the
        # master's lockstep task log withholds collective tasks until every
        # member confirms the current topology (see RendezvousServer).
        hb = {"worker_id": self.worker_id, "version": self._membership_version}
        if self._collective_skips:
            # Cumulative in-collective exclusions (r15 gate): the master
            # banks the newest value per worker — the same bounded-skip
            # ledger the r13 boundary deadline charges (JobStatus
            # ``collective_skips``).
            hb["collective_skips"] = self._collective_skips
        if self._group_mode:
            # Gang-boundary arrival for the deadline-bounded boundary
            # (r13): entries whose dispatch this rank has BEGUN (see
            # _gang_dispatched in __init__).  Also carried by the
            # background liveness beat (gang_beat_fields) — this loop
            # heartbeat stops the moment the loop blocks inside a wedged
            # collective, which is exactly when the signal matters.
            hb["gang_seq"] = self._gang_dispatched
        if self._group_mode and self._rank != 0:
            # Non-rank-0 members never send task reports (rank-0-gated in
            # _flush), so the heartbeat carries their phase snapshot —
            # without it the master's per-worker decomposition only ever
            # held rank 0, and a straggler rank (prep is per-process-local
            # and CAN diverge) was invisible to the very instrument built
            # to see it.
            hb["phase_times"] = self.phases.snapshot()
            hb["phase_counts"] = self.phases.counts()
        tp = self._trace_payload()
        if tp is not None:
            hb["trace"] = tp
        gp = self.gauge_payload()
        if gp is not None:
            hb["gauge"] = gp
        t0_us = trace.now_us()
        resp = self.master.call("Heartbeat", hb)
        t1_us = trace.now_us()
        server_ts = resp.get("server_ts_us")
        if server_ts is not None:
            # RTT-midpoint clock alignment: assume the server stamped its
            # clock halfway through the round trip, so (master - worker) ~=
            # server_ts - (t0+t1)/2.  Error is bounded by RTT asymmetry —
            # microseconds in-cluster, and the next beat refreshes it.
            self._trace_clock_offset_us = server_ts - (t0_us + t1_us) / 2.0
        if not self._group_mode and resp.get("draining"):
            # Max-steps drain: buffered leases AND undispatched prepped
            # tasks carry no device work yet — return them all (requeue-
            # flagged; the STOPPED dispatcher drops them, so nothing
            # trains past the limit).  Overshoot shrinks to the tasks
            # already dispatched, the pre-lease pipeline bound.
            self._abandon_prep()
            self._abandon_leases()
        elif (
            resp.get("eval_pending")
            and self._leased
            and not self._group_mode
        ):
            # A pending eval round preempts training tasks; buffered
            # leases would delay it by up to lease_batch-1 tasks of
            # version skew.  Return them (immediate requeue) so the next
            # lease RPC pulls the eval task first — prepped tasks keep
            # their decode investment and still train, exactly the
            # pre-r9 preemption granularity.  Group mode is exempt from
            # both hints: the lockstep log already fixes the global
            # order.
            self._abandon_leases()
        if resp["version"] != self._membership_version:
            # Settle the in-flight pipelined tasks before re-forming: a
            # multihost change raises WorkerRestartRequired out of
            # _apply_membership, and an unflushed report would leave the
            # master waiting out the task timeout to requeue.  The prepped
            # tasks (if any) dispatch on the OLD mesh first — their state
            # is settled before the re-form.  Locally buffered leases, by
            # contrast, have no work invested: return them to the master
            # NOW (immediate requeue) rather than carrying them across a
            # membership whose lease the master may already have
            # invalidated.
            self._drain_prep()
            self._abandon_leases()
            membership = self.master.call("GetMembership", {})
            self._apply_membership(membership)

    # ---- checkpointing ----

    # hot-path: runs at every task boundary; the step mirror below exists
    # precisely so this never reads the device
    def _maybe_checkpoint(self) -> None:
        if self._ckpt is None or self.config.checkpoint_steps <= 0:
            return
        # The python-side step mirror, NOT int(self.state.step): reading the
        # device scalar drains the whole dispatch pipeline at the boundary —
        # exactly the stall the background save exists to remove.  The
        # mirror equals the step the live state settles to (every dispatched
        # step applies to it), which is the step the snapshot will carry.
        step = self._steps_dispatched
        with self._ckpt_lock:
            behind = step - self._last_ckpt_step
        if behind < self.config.checkpoint_steps:
            return
        with self.phases.phase("checkpoint"):
            if self._group_mode:
                self._save_group_snapshot_background(step)
            elif self._rank == 0:
                self._save_snapshot_background(step)

    def _save_snapshot(self, step: int, wait: bool = False, state=None) -> None:
        """The non-group save trio: Orbax dense state + host-store shards +
        master report.  One definition so the periodic checkpoint and the
        preemption snapshot cannot drift apart.  ``state`` lets the
        preemption path save its single captured reference."""
        state = self.state if state is None else state
        # Canonical layout on disk (trainer.host_state): restores must work
        # into a DIFFERENT world size / optimizer_sharding mode.
        self._save_dense(step, self.trainer.host_state(state), wait=wait)
        self.trainer.save_host_stores(self._ckpt.directory, step)
        if wait:
            # Publish LAST: the manifest is the serving watcher's only
            # trigger, so it must name a step whose Orbax commit AND
            # host-store snapshot are both complete (publish drains any
            # in-flight async save before writing).  The wait=False caller
            # (none today) would publish at its own completion point.
            self._ckpt.publish(step)
        with self._ckpt_lock:
            self._last_ckpt_step = step
        self.master.call(
            "ReportCheckpoint", self._checkpoint_report(step)
        )

    def _save_dense(self, step: int, payload, wait: bool) -> None:
        """The dense state's save, for every path that writes one.  A save
        that FAILED stays with Orbax's manager, which raises it again out of
        the next call of every thread that has not seen it yet — so one torn
        step would fail the next boundary's save (a new thread each time)
        and the job-end save with it, and "the next boundary retries" would
        never hold.  Settle it first: it was logged by the thread it failed
        on, and this save is the retry."""
        try:
            self._ckpt.wait()
        except Exception:
            logger.warning(
                "an earlier checkpoint save failed (logged where it did); "
                "the save of step %d is the retry", step, exc_info=True,
            )
        self._ckpt.save(step, payload, wait=wait)
        if wait:
            with self._ckpt_lock:
                self._ckpt_committed_step = step

    def _checkpoint_report(self, step: int) -> dict:
        """The ReportCheckpoint payload: path/step plus the phase snapshot
        AND a trace slice — checkpoint reports are the "Report" half of the
        heartbeat/report trace-shipping channel (the last word a finishing
        worker sends, so the tail of its buffer rides out here)."""
        report = {
            "path": self._ckpt.directory,
            "step": step,
            "worker_id": self.worker_id,
            "phase_times": self.phases.snapshot(),
            "phase_counts": self.phases.counts(),
        }
        tp = self._trace_payload()
        if tp is not None:
            report["trace"] = tp
        # Forced past the ship throttle: checkpoint reports are the JSONL
        # gauge mirror's carrier (bounded frequency by construction).
        gp = self.gauge_payload(force=True)
        if gp is not None:
            report["gauge"] = gp
        return report

    def _join_ckpt(self, timeout: float = None) -> None:
        with self._ckpt_lock:
            t = self._ckpt_thread
        if t is not None and t.is_alive():
            t.join(timeout)  # outside the lock: the join itself may block

    # hot-path: dispatch-only by design — the whole point is that the
    # boundary pays a dispatch RTT, never a drain
    def _snapshot_state(self):
        """ONE jitted device-side copy of the live state in the CANONICAL
        optimizer layout (trainer.snapshot_state): fresh buffers no later
        step can donate (copy_to_host_async on the live state would race
        donation), and group-mode collective Orbax saves — which stream
        the device arrays straight to disk — therefore write the
        topology-agnostic checkpoint format even when the live optimizer
        state is dp-sharded.  Dispatch-only, so the caller pays ~a
        dispatch RTT, not a pipeline drain."""
        return self.trainer.snapshot_state(self.state)

    def _save_snapshot_background(self, step: int) -> None:
        """Periodic checkpoint OFF the task loop's critical path.

        The synchronous trio stalls training for the whole state D2H
        (~165 MB for the flagship table+moments) plus the write.  Instead:
        ONE jitted
        device-side copy of the state (``_snapshot_state``), then the
        device_get + save trio runs on a background thread while training
        continues.  Saves are serialized (join before starting the next); a
        failed background save logs loudly and rolls the watermark back so
        the next boundary retries."""
        self._join_ckpt()
        snap = self._snapshot_state()
        with self._ckpt_lock:
            prev_watermark, self._last_ckpt_step = self._last_ckpt_step, step

        def _bg():
            try:
                with self.phases.phase("checkpoint_bg"):
                    self._save_snapshot(step, wait=True, state=snap)
            except Exception:
                logger.exception(
                    "background checkpoint at step %d failed; next "
                    "boundary retries", step,
                )
                with self._ckpt_lock:
                    self._last_ckpt_step = prev_watermark

        t = threading.Thread(target=_bg, name="edl-ckpt", daemon=True)
        with self._ckpt_lock:
            self._ckpt_thread = t
        t.start()

    def _save_group_snapshot_background(self, step: int) -> None:
        """Group-mode periodic checkpoint OFF the lockstep task loop.

        r5 ran the collective Orbax save synchronously at the boundary:
        every rank stalled for the full shard D2H + write + cross-process
        commit barrier — the gang-mode twin of the 58 s single-process gap
        that motivated ``_save_snapshot_background`` (VERDICT r5 Missing
        #1).  Now the boundary pays only the jitted device-side copy (plus
        the join of a still-in-flight PREVIOUS save), and the shard D2H +
        write + commit-barrier join run on a background thread.  Orbax
        saves stay COLLECTIVE — every process must participate — and they
        still do: all ranks walk the same lockstep seq with the same step
        watermark, so every rank starts its background save at the same
        boundary and the collective forms in the background symmetrically.

        Failure policy DIFFERS from the single-process path deliberately:
        the watermark is NOT rolled back.  A per-rank rollback would
        diverge the gang's save schedule — the failed rank would retry a
        collective save its peers never join, wedging it in the commit
        barrier.  A failed group save logs loudly and the NEXT boundary
        (same watermark arithmetic on every rank) writes a fresh step; a
        torn step is skipped by the restore walk.
        """
        self._join_ckpt()
        snap = self._snapshot_state()
        with self._ckpt_lock:
            self._last_ckpt_step = step

        def _bg():
            try:
                with self.phases.phase("checkpoint_bg"):
                    self._save_dense(step, snap, wait=True)
                    if self._rank == 0:
                        # Host-tier PS snapshot: ONE process fans the Save
                        # out to the PS shards (each dumps its own slice);
                        # plain RPC — not collective — so the rank gate
                        # cannot deadlock the group.
                        self.trainer.save_host_stores(
                            self._ckpt.directory, step
                        )
                        # Collective save committed (wait=True above) and
                        # host shards dumped: rank 0 publishes for serving.
                        self._ckpt.publish(step)
                        self.master.call(
                            "ReportCheckpoint", self._checkpoint_report(step)
                        )
            except Exception:
                logger.exception(
                    "group background checkpoint at step %d failed; the "
                    "next boundary saves (watermark kept — a per-rank "
                    "rollback would desync the gang's collective saves)",
                    step,
                )

        t = threading.Thread(target=_bg, name="edl-ckpt", daemon=True)
        with self._ckpt_lock:
            self._ckpt_thread = t
        t.start()

    # thread-role: thread:preemption — runs on the SIGTERM handler's
    # graceful-exit thread (worker.main), reached via the worker holder.
    def preemption_snapshot(self) -> bool:
        """Best-effort state save on SIGTERM (k8s preemption grace window).

        Returns True when a snapshot was written.  Deliberately narrow:
        - group mode never solo-saves (Orbax saves are COLLECTIVE in a
          multi-process world — see ``_maybe_checkpoint`` — and the gang
          is being preempted precisely when peers may already be gone);
          the fleet relies on its periodic collective checkpoints, and
          the fast RESTART exit is itself the win (peers re-form without
          waiting out heartbeats).
        - non-rank-0 workers never solo-save either (same shared-dir gate
          as ``_maybe_checkpoint``: a node drain preempting several
          workers at once must not race Orbax commits in one directory).
        - a state still donated-in-flight after the park window is
          skipped: the periodic checkpoint covers the resume rather than
          risking a read of consumed buffers.
        Runs on the preemption thread, not in the signal handler frame.
        """
        self._preempting = True  # parks the task loop at its next boundary
        # FIRST, before anything can block: a preempting process has
        # PREEMPTION_EXIT_S to live, so every remaining master RPC (this
        # thread's pending flush, the parked loop's abandons) must fail
        # fast-ish instead of parking in the r18 outage backoff — a
        # snapshot forfeited to a 120 s reconnect wait would be the exact
        # pre-r18 behavior regression.
        limit = getattr(self.master, "limit_outage_tolerance", None)
        if limit is not None:
            limit(2.0)
        trace.instant("elastic:preempt", cat="elastic", rank=self._rank)
        if (
            self._group_mode
            or self._rank != 0
            or self._ckpt is None
            or self.state is None
        ):
            if self._group_mode:
                # The fleet's resume point IS the periodic collective
                # checkpoint; an in-flight background group save must not be
                # torn by os._exit if it can finish inside the grace window.
                # Bounded: a save wedged on already-dead peers will never
                # complete, and the hard PREEMPTION_EXIT_S timer still owns
                # the exit.
                self._join_ckpt(timeout=5.0)
            logger.info(
                "preemption snapshot skipped (group=%s rank=%d ckpt=%s "
                "state=%s)",
                self._group_mode, self._rank, self._ckpt is not None,
                self.state is not None,
            )
            return False
        from elasticdl_tpu.parallel.trainer import _state_alive

        # Wait for the task loop to ACKNOWLEDGE the park: once _parked is
        # set the loop only sleeps, so self.state can no longer be donated
        # or reassigned under us.  Under continuous dispatch the state
        # spends most wall-clock donated into the in-flight step, so this
        # is the common path, bounded well inside the grace window.
        deadline = time.time() + 5.0
        while not self._parked and time.time() < deadline:
            time.sleep(0.05)
        if not self._parked:
            # The park is REQUIRED, not best-effort: a main thread merely
            # blocked in a master RPC (mass preemption is exactly when the
            # master is slow) resumes its iteration after we give up —
            # donating a state we captured as live and racing our
            # _flush_pending on the self._pending slot (duplicate or torn
            # report).  No snapshot then; the RESTART exit still happens
            # and the relaunch resumes from the last periodic checkpoint.
            logger.warning(
                "preemption snapshot skipped (task loop never parked "
                "within 5s — likely blocked in a master RPC)",
            )
            return False
        # Single capture: the parked loop only sleeps, so this reference
        # cannot be donated or reassigned under us.
        state = self.state
        if state is None or not _state_alive(state):
            logger.info("preemption snapshot skipped (state in flight)")
            return False
        # The pipelined previous task's report is already reflected in
        # this state; report it now or the master waits out the task
        # timeout and REQUEUES work the snapshot already contains
        # (double-applied examples on resume).
        try:
            self._flush_pending()
        except Exception:
            logger.exception("preemption flush of pending report failed")
        step = int(state.step)  # settles the in-flight dispatch
        try:
            # A background periodic save may be mid-flight; settle it first
            # (bounded inside the grace window) — both the same-step
            # collision check and a fresh save need it durable.
            self._join_ckpt(timeout=10.0)
            with self._ckpt_lock:
                bg = self._ckpt_thread
            if bg is not None and bg.is_alive():
                # Still saving after the bounded join: a fresh save here
                # would interleave with it on the same manager/step dirs
                # (tearing both), and waiting longer blows the grace
                # window.  Report no durable snapshot; os._exit tears the
                # in-flight write, whose step the torn-pair restore walk
                # skips — resume falls back to the last durable step.
                logger.warning(
                    "preemption: background checkpoint still in flight "
                    "after 10s join; exiting without a fresh snapshot",
                )
                return False
            with self._ckpt_lock:
                saved_this_step = self._last_ckpt_step == step
            if saved_this_step:
                # The flush above crossed the periodic-checkpoint threshold
                # and already saved THIS step (async): saving again would
                # collide on the step dir, and exiting now would tear the
                # in-flight write — settle it instead.
                self._ckpt.wait()
            else:
                self._save_snapshot(step, wait=True, state=state)
        except Exception:
            # Dense may have landed while host stores/report failed; the
            # torn-pair walk at restore refuses a dense-only step, so a
            # partial write degrades to the previous checkpoint.
            logger.exception("preemption snapshot incomplete")
            return False
        logger.info("preemption snapshot at step %d", step)
        return True

    # ---- profiling ----

    def _profile_open_if_due(self) -> None:
        """Open the ``--profile_dir`` window as the SECOND training task is
        taken up for dispatch (the first pays compilation; with prep-ahead
        a task is leased and prepped earlier than that, and opening at the
        lease would put the first task's compile inside the window).  The
        window spans ``--profile_tasks`` consecutive tasks and changes
        nothing about how they are prepped, dispatched, settled and
        reported.  Counts training tasks only, so interleaved eval/predict
        tasks neither skip the trace nor shift it onto a compiling step.

        While it is open every span of ``common/trace.py`` is also a
        ``jax.profiler.TraceAnnotation`` (the bridge), so the host's phases
        land in the same xplane as the device planes, on one clock.  The
        Python tracer is off (it hooks every call of the loop under
        study); host tracer level 1 is the lowest that still records the
        annotations.  Failing to start is a logged error, never a dead
        job."""
        if (
            not self.config.profile_dir
            or self._profile_state
            or self._training_tasks_done != 1
        ):
            return
        try:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(
                self.config.profile_dir, profiler_options=options
            )
        except Exception:
            logger.exception("profiler start failed")
            self._profile_state = "closed"
            return
        self._profile_state = "open"
        trace.set_bridge(_profile_annotation)
        logger.info(
            "profile window open: %d training tasks from dispatch seq %d "
            "into %s (pipelining %s, prep-ahead %s, %d task(s) in prep)",
            self.config.profile_tasks, self._dispatches, self.config.profile_dir,
            self._pipelining_enabled(), self._prep_ahead_eligible(),
            len(self._prep_queue),
        )

    def _profile_close_if_due(self, task_id: int) -> None:
        """Called once a task's report has gone out: the last traced
        task's report closes the window."""
        if self._profile_last_task == task_id:
            self._profile_close()

    def _profile_close(self) -> None:
        """Stop the annotations now and hand the session to a thread of
        its own: collecting the device trace and writing the files takes
        seconds on a TPU (1.6 s and 11.7 s in the benchmark's two cells,
        PERF.md) and must not sit on the task loop at the edge of the
        window it has just measured.  With ``--profile_inline`` the loop
        does it itself and stands still meanwhile: the files are whole
        before the next task reports."""
        if self._profile_state != "open":
            return
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against the loop (see preemption_snapshot)
        self._profile_state = "closed"
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against the loop (see preemption_snapshot)
        self._profile_last_task = None
        # A stall's span the watchdog still holds ends inside the window.
        self._stalls.close_span()
        trace.set_bridge(None)
        traced = self._profile_traced
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against the loop (see preemption_snapshot)
        self._profile_stops += 1

        def _stop():
            t0 = time.perf_counter()
            try:
                jax.profiler.stop_trace()
            except Exception:
                logger.exception("profiler stop failed")
                return
            logger.info(
                "profile window closed: %d task(s) traced into %s; "
                "collecting and writing took %.3f s %s the task loop",
                traced, self.config.profile_dir, time.perf_counter() - t0,
                "on" if self.config.profile_inline else "off",
            )

        if self.config.profile_inline:
            with self.phases.phase("control"):
                _stop()
            return
        closer = threading.Thread(target=_stop, name="edl-profile", daemon=True)
        closer.start()
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against the loop (see preemption_snapshot)
        self._profile_closer = closer

    def _profile_settle(self) -> None:
        """Job end (any exit of ``run``): close a window the job was too
        short to fill, and wait for the file."""
        self._profile_close()
        closer, self._profile_closer = self._profile_closer, None
        if closer is not None:
            closer.join(timeout=120.0)

    def _setup_first_dispatch(
        self, setup: trace.SetupChain, begins: bool
    ) -> None:
        """The set-up chain around the worker's FIRST training dispatch:
        ``setup:first_prep`` (the first lease and the first task's host
        half) ends where the dispatch call begins; ``setup:first_dispatch``
        is the call (trace, lower, compile or cache load, enqueue), with
        the seconds ``jax.monitoring`` reported of it riding as
        ``setup:first_dispatch.<part>``: the cumulative seconds at the
        end less those at the beginning."""
        setup.mark("setup:first_prep" if begins else "setup:first_dispatch")
        parts, sign = compile_phase_seconds(), -1.0 if begins else 1.0
        # what the program store cost (a key's digest, an entry's read and load)
        parts["restore_s"] = self._programs.counts()["restore_s"] if self._programs is not None else 0.0
        for part in ("trace_s", "lower_s", "compile_s", "cache_load_s", "restore_s"):
            key = f"setup:first_dispatch.{part}"
            setup.extras[key] = setup.extras.get(key, 0.0) + sign * parts[part]

    def _setup_payload(self, setup: trace.SetupChain) -> Dict[str, float]:
        """The chain as it rides the first report (``SetupChain.flat`` and
        this incarnation's compile requests), published on the way as the gauge family
        ``edl_setup_seconds{phase=}`` and, with the ring on, as
        ``cat="setup"`` spans: one set of stamps, three sinks."""
        parts = compile_phase_seconds()
        restored = float(self.trainer.programs_restored)
        setup.extras.update(
            # the nonce is "<pid>-<epoch ms>" (worker.main, __init__)
            incarnation_ms=float(self._incarnation.rpartition("-")[2]),
            # every compile request up to this report, and how the
            # persistent cache served them; a train step restored from the
            # program store made no request and WAS served from disk: it
            # counts as a hit
            compile_requests=float(compile_counts()[0]),
            cache_hits=parts["cache_hits"] + restored,
            cache_misses=parts["cache_misses"],
            # train steps this incarnation restored from the program store,
            # and those it traced (all of them, without a store)
            programs_restored=restored,
            programs_traced=float(self.trainer.programs_traced),
        )
        durations = dict(
            setup.durations(),
            **{"setup:first_dispatch.restore_s": setup.extras.get("setup:first_dispatch.restore_s", 0.0)},
        )
        for name in ("programs_restored", "programs_traced"):
            self.gauges.gauge(
                f"edl_{name}",
                "train steps this incarnation had restored from the program store / traced by its first training report",
            ).set(setup.extras[name])
        for phase, seconds in durations.items():
            self.gauges.gauge(
                "edl_setup_seconds",
                "wall seconds of each span of this incarnation's set-up "
                "chain, as sent with its first training report",
                labels={"phase": phase},
            ).set(seconds)
        setup.emit()
        return setup.flat()

    def _stall_probes(self) -> Dict[str, Any]:
        """What the stall recorder marks at every report besides its own
        clocks (``StallRecorder``'s ``probes``): cumulative numbers whose
        growth over a stalled gap names its cause, and what else is alive
        in this process right now."""
        compiles, compile_s = compile_counts()
        injected_ms = 0.0
        if chaos.enabled():
            injected_ms = sum(
                f["fired"] * f["ms"] for f in chaos.default().stats()
                if f["kind"] in ("stall", "delay_rpc")
            )
        closer = self._profile_closer
        with self._ckpt_lock:
            saver = self._ckpt_thread
        return {
            "compiles": compiles,
            "compile_s": compile_s,
            "dispatches_device_idle": self._dispatches_idle,
            "injected_s": injected_ms / 1e3,
            "profile": self._profile_state or "none",
            "profile_stops": self._profile_stops,
            "profile_stopping": closer is not None and closer.is_alive(),
            "saving": saver is not None and saver.is_alive(),
            "prepping": any(not e[2].done() for e in self._prep_queue),
        }

    def _output_ready(self) -> Optional[bool]:
        """Whether the newest training dispatch's output is ready (None
        before the first): one non-blocking call, made by the stall
        recorder's watchdog thread while a report is late."""
        output = self._last_output
        return None if output is None else bool(output.is_ready())

    def _counter_snapshot(self) -> Dict[str, float]:
        """The worker's cumulative counters, read once per report on the
        settle path (one ``memory_stats()`` per local device; nothing per
        step).  Keys: ``COUNTER_GAUGES`` and the model's step counters."""
        compiles, compile_s = compile_counts()
        with self._step_counts_lock:
            step_counts = dict(self._step_counts)
        self._counters = {
            "compiles": compiles,
            "compile_s": round(compile_s, 6),
            "hbm_peak_bytes": device_peak_bytes(),
            "dispatches": self._dispatches,
            "dispatches_device_idle": self._dispatches_idle,
            **self._stalls.counters(),
            **step_counts,
        }
        return self._counters

    # ---- task execution ----

    def _read_records(self, shard):
        """Shard records, packed (one bulk C++ read — data/packed.py) when
        the reader offers it, else a plain list."""
        fast = getattr(self.reader, "read_records_packed", None)
        if fast is not None:
            records = fast(shard)
            if records is not None:
                return records
        return list(self.reader.read_records(shard))

    def _stack_full_minibatches(self, records, mb: int, n_full: int):
        """Feed + stack every full minibatch into ONE [T, mb, ...] host
        batch (the fused-scan wire format); shared by the training prep and
        the fused eval path."""
        big = self.spec.feed(records[: n_full * mb])
        return jax.tree.map(
            lambda v: np.ascontiguousarray(v).reshape(
                (n_full, mb) + v.shape[1:]
            ),
            dict(big),
        )

    def _prep_fused_host(self, task: Task) -> HostPrep:
        """Host half of a fused training task: bulk read + C++ decode +
        [T, mb, ...] stacking.  Touches neither ``self.state`` nor the
        device, so the prep-ahead pipeline in ``run`` executes it on a
        background thread (the C++ codec and numpy copies release the GIL)
        while the previous task's wire transfer and metrics settle.

        With ``ingest_threads`` > 1 (and a reader declaring
        ``thread_safe_ranges``) the task's record range splits into
        minibatch-aligned sub-chunks read+decoded concurrently on the
        IngestPool, reassembled in chunk order — record order, ragged-tail
        records, and therefore the ``__mask__``/gradient-weighting
        semantics are bit-identical to the serial path (the feed decodes
        each record independently, so a chunked feed concatenates to
        exactly the serial feed's bytes)."""
        # One span per task on its prep thread; the task id ties it to the
        # loop's spans of the same task.
        with trace.span("prep", cat="ingest", task=task.task_id):
            # graftchaos: stall(point=prep) — the host-side straggler the
            # deadline-bounded gang boundary exists to cut short.
            chaos.hook(
                "worker:prep", rank=self._rank, step=self._steps_dispatched
            )
            mb = self.config.minibatch_size
            shard = task.shard
            pool = self._ingest
            chunks = (
                plan_chunks(shard.start, shard.end, mb, pool.threads)
                if pool.parallel
                and getattr(self.reader, "thread_safe_ranges", False)
                else None
            )
            if not chunks or len(chunks) < 2:
                records = self._read_records(shard)
                total = len(records)
                n_full = total // mb
                stacked = (
                    self._stack_full_minibatches(records, mb, n_full)
                    if n_full >= 1
                    else None
                )
                return HostPrep(
                    total, n_full, stacked, list(records[n_full * mb:])
                )

            def _decode_chunk(span):
                # Runs on an ingest-pool thread; its cumulative time lands
                # in the off-critical-path ``decode_parallel`` phase (the
                # phase stack is per-thread, so this never subtracts from
                # the foreground phases).
                with self.phases.phase("decode_parallel", task=task.task_id):
                    recs = self._read_records(
                        Shard(shard.name, span[0], span[1])
                    )
                    t = len(recs) // mb
                    stacked = (
                        self._stack_full_minibatches(recs, mb, t)
                        if t >= 1
                        else None
                    )
                    return len(recs), t, stacked, list(recs[t * mb:])

            parts = pool.map_ordered(_decode_chunk, chunks)
            total = sum(p[0] for p in parts)
            n_full = sum(p[1] for p in parts)
            stacks = [p[2] for p in parts if p[2] is not None]
            if not stacks:
                stacked = None
            elif len(stacks) == 1:
                stacked = stacks[0]
            else:
                # Ordered concat along the step axis: chunk i's [t_i, mb,
                # ...] rows precede chunk i+1's, exactly the serial
                # reshape's layout.
                stacked = {
                    k: np.concatenate([s[k] for s in stacks], axis=0)
                    for k in stacks[0]
                }
            # plan_chunks puts the ragged tail on the LAST chunk, so only it
            # can have leftover records.
            return HostPrep(total, n_full, stacked, parts[-1][3])

    def _gather_contribution(self, shard: int) -> None:
        """One dp shard's contribution crossing the collective gate.  On
        this harness the crossing is the graftchaos hook (the r13 stance:
        the injector is the supply side of stragglers the gate is the
        demand side for); a real fleet would await the shard's host-side
        inputs here (its PS row pull, its ingest chunk).  Runs on a gate
        thread when the in-step deadline is armed — a stalled crossing
        must stall ONE shard, never the dispatch."""
        chaos.hook(
            "worker:collective",
            rank=self._rank,
            step=self._steps_dispatched,
            shard=shard,
        )

    def _start_crossing(self, shard: int) -> threading.Event:
        """Run one shard's gate crossing on a DAEMON thread, signalling
        the returned event on completion.  Daemon deliberately (not an
        executor): a crossing wedged in a long stall must never block
        interpreter exit at job end — the severed straggler dies with
        the process, exactly the r13 teardown stance."""
        done = threading.Event()

        def _cross():
            try:
                self._gather_contribution(shard)
            finally:
                done.set()

        threading.Thread(
            target=_cross, name=f"edl-collgate-{shard}", daemon=True
        ).start()
        return done

    # hot-path: the gate's wait is the in-step deadline itself, accounted
    # under the collective_gate phase boundary
    def _collective_gate(self, task: Task) -> None:
        """graftreduce in-step straggler deadline (r15).

        Every dp shard's host-side contribution must cross the gate
        before the task's steps dispatch.  Deadline off (the default):
        the crossings run inline — a stalled contributor blocks the
        dispatch, the pre-r15 behavior (and the baseline the collective
        bench measures against).  Deadline on: crossings run on the gate
        pool, and a shard that misses ``--collective_deadline_ms`` is
        EXCLUDED — its weight in the subgroup mask drops to 0, the
        task's collectives renormalize over the survivors
        (``sum/|G'|``; trainer.set_active_contributors, a traced input,
        so no recompile), ``edl_collective_skip_total`` and a
        ``collective:exclude`` instant record the skip, and the
        cumulative count rides the heartbeat into the master's
        accounting.  A still-stalled shard stays excluded on later tasks
        WITHOUT re-submitting (its crossing is still in flight); when
        the crossing completes the shard re-joins (``collective:restore``).

        Bounded skip accounting (the r13 stance, same budget knob): a
        shard excluded more than ``--gang_skip_budget`` CONSECUTIVE
        tasks is waited out instead — a permanently dead contributor
        must surface as a visible stall, never as silently untrained
        data forever.

        Single-process meshes only: the mask is a replicated input, and
        every participant of a multi-process collective must dispatch
        the same mask — coordinating that across a gang needs a master
        round-trip per entry, so multi-process stragglers stay with the
        r13 task-boundary deadline (docs/robustness.md lays out the two
        layers)."""
        n = self.trainer.num_contributors()
        deadline_s = self.config.collective_deadline_ms / 1e3
        if deadline_s <= 0 or n <= 1 or self._group_mode:
            if chaos.enabled():
                # Inline crossings BLOCK the dispatch (the pre-r15
                # behavior the deadline exists to cut) — run them inside
                # the same phase the armed gate accounts to, so a
                # blocking stall and a bounded deadline wait decompose
                # under ONE name and the bench can compare them on phase
                # clocks instead of noisy whole-fleet walls.
                with self.phases.phase("collective_gate"):
                    for shard in range(n):
                        self._gather_contribution(shard)
            return
        if not chaos.enabled() and not self._collective_pending:
            # On this harness the chaos hook is the only crossing body
            # (_gather_contribution's docstring) — unarmed, nothing can
            # stall, so skip the per-shard thread spawn entirely.  The
            # mask invariant (exclusions == pending keys, rebuilt every
            # armed pass) means empty pending implies all-active already.
            self._g_coll_subgroup.set(float(n))
            return
        # Re-admit contributors whose stalled crossing finally finished.
        for shard, done in list(self._collective_pending.items()):
            if done.is_set():
                self._collective_pending.pop(shard)
                self._collective_consec.pop(shard, None)
                trace.instant(
                    "collective:restore", cat="collective",
                    shard=shard, task=task.task_id,
                )
        crossings = {
            shard: self._start_crossing(shard)
            for shard in range(n)
            if shard not in self._collective_pending
        }
        end = time.monotonic() + deadline_s
        with self.phases.phase("collective_gate"):
            for shard, done in crossings.items():
                if not done.wait(timeout=max(0.0, end - time.monotonic())):
                    self._collective_pending[shard] = done
            # Budget escalation: a shard past its consecutive-skip budget
            # is waited out (the stall becomes visible dispatch time in
            # this phase, exactly where a pre-r15 stall would land).
            budget = max(0, self.config.gang_skip_budget)
            for shard, done in list(self._collective_pending.items()):
                if self._collective_consec.get(shard, 0) < budget and (
                    len(self._collective_pending) < n
                ):
                    continue
                logger.warning(
                    "collective gate: shard %d exceeded %d consecutive "
                    "in-step skips (or no quorum remains); waiting it out",
                    shard, budget,
                )
                done.wait()  # accounted: inside the collective_gate phase
                self._collective_pending.pop(shard)
                self._collective_consec.pop(shard, None)
                trace.instant(
                    "collective:restore", cat="collective",
                    shard=shard, task=task.task_id, waited=True,
                )
        excluded = sorted(self._collective_pending)
        mask = np.ones(n, np.float32)
        for shard in excluded:
            mask[shard] = 0.0
            self._collective_consec[shard] = (
                self._collective_consec.get(shard, 0) + 1
            )
            self._collective_skips += 1
            self._g_coll_skips.inc()
            trace.instant(
                "collective:exclude", cat="collective",
                shard=shard, task=task.task_id,
                deadline_ms=self.config.collective_deadline_ms,
                consecutive=self._collective_consec[shard],
            )
        self.trainer.set_active_contributors(mask)
        self._g_coll_subgroup.set(float(n - len(excluded)))
        if excluded:
            logger.warning(
                "collective gate: task %d trains on subgroup %d/%d "
                "(excluded shard(s) %s past %.0f ms in-step deadline)",
                task.task_id, n - len(excluded), n, excluded,
                self.config.collective_deadline_ms,
            )

    # hot-path: THE dispatch function — every blocking transfer here shows
    # up as device idle on the remote-attached chip
    def _dispatch_training_task(
        self, task: Task, prep: Optional[HostPrep] = None
    ) -> tuple:
        """Dispatch every device step of a training task WITHOUT blocking on
        results.  Returns (per-batch device metrics, n_steps).

        Two overlap levels hide host and transfer latency behind the
        device:
        - the prefetch thread decodes AND device-places (``shard_batch``)
          upcoming batches while steps are in flight (mesh-tier specs only;
          host-tier tables need the host batch for the row pull);
        - the caller defers the metrics fetch (``_finalize_training_metrics``)
          until after the NEXT task's steps are dispatched (task-level
          pipelining in ``run``).

        ``prep`` is an already-computed ``_prep_fused_host`` result (the
        prep-ahead pipeline); when None the host work runs inline here.
        Prep is only ever produced on the fused pre-shard path
        (``_prep_ahead_eligible``), so a prepped task either takes the
        fused branch (``n_full >= 1``) or is a pure-tail task whose records
        are exactly ``prep.tail``.
        """
        if self._group_mode and task.task_id != self._gang_last_task:
            # Gang-boundary arrival (r13): this entry's dispatch BEGINS
            # now — counted before the first device call so a rank that
            # blocks inside the collective below has still arrived at it,
            # and at most once per entry so the in-place collective retry
            # cannot inflate it (see _gang_dispatched in __init__).
            self._gang_last_task = task.task_id
            self._gang_dispatched += 1
        # graftchaos: stall(point=step) — a device-dispatch-side straggler.
        chaos.hook(
            "worker:step", rank=self._rank, step=self._steps_dispatched
        )
        # graftreduce (r15): every shard's contribution crosses the
        # in-step deadline gate before the steps dispatch; a straggler
        # past --collective_deadline_ms is excluded-and-renormalized
        # instead of holding the collective.
        self._collective_gate(task)
        # One training dispatch begins.  Its ordinal rides the dispatch
        # span, so the n-th execution of the step program in a device
        # trace can be tied to the task that launched it.  If the previous
        # dispatch's output is ready already, the device has nothing
        # queued and waits for this host: the starvation signal that needs
        # no profiler (one non-blocking call per task).
        seq = self._dispatches
        self._dispatches = seq + 1
        if self._last_output is not None and self._last_output.is_ready():
            self._dispatches_idle += 1
        # The set-up chain's two stamps around the FIRST dispatch (None
        # from the second on, and once the chain is sent).
        setup = self._setup if seq == 0 else None
        if self._profile_state == "open" and self._profile_last_task is None:
            self._profile_traced += 1
            if self._profile_traced >= self.config.profile_tasks:
                self._profile_last_task = task.task_id
        tid = task.task_id
        mb = self.config.minibatch_size
        if prep is not None:
            records = None
            total, n_full, stacked_host, tail = prep
        else:
            with self.phases.phase("prep_wait", task=tid):
                records = self._read_records(task.shard)
            total = len(records)
            n_full = total // mb
            stacked_host = None
            tail = records[n_full * mb:]
        n_steps = (total + mb - 1) // mb
        pre_shard = not self.spec.host_io

        def _train_feed(chunk, true_count):
            """Feed a train chunk; wrap-padded tails get the eval-style
            ``__mask__`` so duplicated examples carry ZERO gradient (the
            train step weights shards by real count — build_train_step)."""
            batch = self.spec.feed(chunk)
            if true_count < mb:
                batch = dict(batch)
                batch["__mask__"] = (np.arange(mb) < true_count).astype(
                    np.float32
                )
            return batch

        try:
            if pre_shard and self.config.fused_task_scan and n_full >= 1:
                # Whole-task fused path: ONE feed call over every full
                # minibatch, ONE H2D transfer of the stacked [T, mb, ...]
                # batch, and ONE jitted lax.scan running all T steps — one
                # dispatch per task (per-step dispatch cost ~half the step
                # wall-clock on the retired backend's chip, and a single big
                # decode also sidesteps the GIL fight a per-batch producer
                # thread loses on 1-core hosts; docs/perf.md).  The
                # task-level pipeline in ``run`` overlaps this host work
                # with the PREVIOUS task's scan.  A ragged tail trains as
                # one extra masked step.
                if stacked_host is not None:
                    stacked = stacked_host
                else:
                    with self.phases.phase("prep_wait", task=tid):
                        stacked = self._stack_full_minibatches(
                            records, mb, n_full
                        )
                # jitsan (v6): the optional transfer guard makes any
                # IMPLICIT device->host materialization inside the
                # dispatch window a loud failure (explicit device_put /
                # device_get spellings stay legal) — the runtime half of
                # graftlint's transfer-discipline rule.
                with self.phases.phase(
                    "dispatch", task=tid, seq=seq,
                    step0=self._steps_dispatched,
                ), jitsan.transfer_guard():
                    if setup is not None:
                        self._setup_first_dispatch(setup, begins=True)
                    self.state, scan_metrics = self.trainer.train_scan(
                        self.state, self.trainer.shard_stacked_batch(stacked)
                    )
                    metrics_list = [scan_metrics]  # [T]-stacked dict
                    for chunk, true_count in _minibatches(tail, mb, True):
                        self.state, m = self.trainer.train_step(
                            self.state,
                            self.trainer.shard_batch(
                                _train_feed(chunk, true_count)
                            ),
                        )
                        metrics_list.append(m)
            else:
                # Inline: the full record list.  Prepped: only reachable as
                # a pure-tail task (n_full == 0), whose records ARE the tail.
                gen_records = records if records is not None else tail

                def _gen():
                    for chunk, true_count in _minibatches(
                        gen_records, mb, True
                    ):
                        batch = _train_feed(chunk, true_count)
                        yield (
                            self.trainer.shard_batch(batch)
                            if pre_shard
                            else batch
                        )

                # run_train_steps = (host-tier pull ->) shard -> jitted step
                # (-> sparse push) per batch; plain shard+step when no host
                # tables.  --use_async pipelines the host-tier pulls against
                # the device step (the reference's async-PS mode).  The
                # per-step feed runs inside the same consumer loop, so this
                # path's decode time lands under "dispatch" — honest for a
                # mode whose decode and dispatch genuinely interleave.
                # when=: host-tier models materialize sparse cotangents
                # (np.asarray in _push_host_grads) INSIDE this window by
                # design — the documented sync point — so the guard arms
                # only for the dense paths where any implicit transfer is
                # a genuine leak.
                with self.phases.phase(
                    "dispatch", task=tid, seq=seq,
                    step0=self._steps_dispatched,
                ), jitsan.transfer_guard(when=not self.spec.host_io):
                    if setup is not None:
                        self._setup_first_dispatch(setup, begins=True)
                    self.state, metrics_list = self.trainer.run_train_steps(
                        self.state,
                        prefetch(
                            _gen(),
                            self.config.prefetch_depth,
                            name=f"prefetch:{task.task_id}",
                        ),
                        use_async=self.config.use_async,
                        pre_sharded=pre_shard,
                    )
        except TrainLoopError as e:
            # The failed step may have consumed (donated) the state this
            # worker still references; adopt the newest live state — or
            # rebuild from the checkpoint — so the requeued task retries
            # against real buffers instead of wedging every later task.
            if e.state is not None:
                self.state = e.state
            else:
                self._recover_state()
            # Resync the python-side step mirror: recovery may have landed
            # on an older step, and later pipelined reports derive
            # model_version from this counter.
            self._steps_dispatched = int(self.state.step)
            raise
        except Exception:
            from elasticdl_tpu.parallel.trainer import _state_alive

            # Same donated-state hazard for the fused path's direct calls.
            if not _state_alive(self.state):
                self._recover_state()
            self._steps_dispatched = int(self.state.step)
            raise
        if setup is not None:
            self._setup_first_dispatch(setup, begins=False)
        # Live throughput counters (r14): O(1) adds under a leaf lock —
        # the only gauge API legal on the hot path (gauge-discipline).
        self._g_examples.inc(total)
        self._g_steps.inc(n_steps)
        if self._collective_step_bytes is None:
            self._collective_step_bytes = (
                self.trainer.collective_bytes_per_step(self.state)["resolved"]
            )
        self._g_coll_bytes.inc(n_steps * self._collective_step_bytes)
        # Start the D2H copy of the task's metrics NOW, in the background:
        # the runtime moves each value to the host as soon as its step
        # completes, so the deferred fetch in _finalize_training_metrics
        # finds them resident instead of paying a blocking transfer RTT
        # while the device queue sits idle.
        leaves = jax.tree.leaves(metrics_list)
        for leaf in leaves:
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self._last_output = (
            leaves[-1] if leaves and hasattr(leaves[-1], "is_ready") else None
        )
        return metrics_list, n_steps

    def _recover_state(self) -> None:
        """Rebuild training state after a failed step consumed the live
        buffers: newest restorable checkpoint if any, else fresh init
        (loudly — a training job loses at most the work since the last
        checkpoint; the failed task is requeued either way)."""
        logger.error(
            "training state lost to a failed step; rebuilding from checkpoint"
        )
        self._join_ckpt()  # a mid-flight background save should land first:
        # its step is the newest restorable state this recovery can adopt
        self.state = self.trainer.init_state(jax.random.key(0))
        steps = self._ckpt.all_steps() if self._ckpt is not None else []
        for step in steps:
            try:
                restored = self._restore_checkpoint(self.state, step=step)
                self.trainer.restore_host_stores(self._ckpt.directory, step)
                self.state = restored
                logger.info("recovered from checkpoint step %d", step)
                return
            except FileNotFoundError:
                continue
        logger.error(
            "no restorable checkpoint; training state re-initialized fresh"
        )

    # hot-path: the one deliberate drain per task — both blocking halves
    # sit inside their named phase boundaries
    def _finalize_training_metrics(
        self, metrics_list, task_id: int
    ) -> Dict[str, float]:
        """ONE device_get of the whole task's per-batch metrics, then host
        aggregation — per-batch device adds or per-scalar fetches would cost
        a dispatch/RTT each.  Entries are per-step scalar dicts OR
        [T]-stacked dicts (the fused lax.scan path); both weigh each step
        equally."""
        # The fetch is where the in-flight device steps drain: its wall is
        # the task's device-execution tail plus the transfer ("step_wait"),
        # distinct from the microseconds of host math after it ("metrics").
        with self.phases.phase("step_wait", task=task_id):
            host = jax.device_get(metrics_list)
        with self.phases.phase("metrics", task=task_id):
            sums: Dict[str, Any] = {}
            n = 0
            for metrics in host:
                steps = 1
                for k, v in metrics.items():
                    a = np.asarray(v, np.float64)
                    if a.ndim >= 1:  # [T]-stacked scan metrics
                        steps = a.shape[0]
                        a = a.sum(axis=0)
                    sums[k] = sums.get(k, 0.0) + a
                n += steps
            with self._step_counts_lock:
                for key in self._step_counts:
                    if key in sums:
                        self._step_counts[key] += float(sums.pop(key))
            # finalize: scalars -> float, histogram pairs -> scalar (AUC).
            return finalize_metrics(
                {k: s / max(n, 1) for k, s in sums.items()}
            )

    def _run_training_task(self, task: Task) -> Dict[str, float]:
        """Synchronous task execution (``--task_pipelining`` off)."""
        metrics_list, _ = self._dispatch_training_task(task)
        return self._finalize_training_metrics(metrics_list, task.task_id)

    #: Collective-formation failures worth retrying in place: a gang member
    #: still COMPILING while its peer already executes trips the runtime's
    #: hard context-init deadline (XLA:CPU Gloo: 30 s).  The peer just needs
    #: time, not a group teardown — by the retry it has usually reached its
    #: side of the collective.  Anything else stays fatal (desync -> the
    #: deregister/restart path).
    #:
    #: Exactly the runtime's message prefix (ADVICE r4 #3 found the broad
    #: "context initialization failed" fallback could over-match; jaxlib
    #: emits only this one Gloo-prefixed form).  Retrying here cannot desync
    #: the gang's collective order: context init precedes any data exchange,
    #: so a member that failed it never participated — no peer's collective
    #: can have COMPLETED one-sided (it is blocked waiting), and every
    #: member classifies this same message the same way, so re-dispatch
    #: replays the identical collective sequence on all sides.
    _TRANSIENT_COLLECTIVE_MARKERS = (
        # Deliberately suffixless: a jaxlib upgrade rewording what follows
        # the phrase must not silently kill the retry path (each formerly
        # ~1s in-place retry would become a full gang restart cycle).  The
        # "Gloo" prefix keeps the r4 tightening — generic "context
        # initialization failed" strings still do NOT match.
        "Gloo context initialization failed",
    )
    _GROUP_TASK_ATTEMPTS = 3

    # hot-path: wraps every dispatch; the retry sleep lives on the
    # exception path only
    def _retry_transient_collective(self, fn, task_id: int):
        """Run a task's device work; in group mode, retry the transient
        collective-formation failures above in place.  _dispatch_training_task
        settles self.state on every failure (adopts the last live state or
        recovers from the checkpoint), so an immediate re-dispatch is safe
        and keeps the collective ORDER identical across the gang.  Outside
        group mode there is no collective to re-form: one plain call, so
        every dispatch site routes through here without branching on
        mode.  The schedule runs on the shared backoff helper (r18): a
        fixed 1 s, jitter-free cadence — every gang member classifies the
        same failure the same way, and identical re-dispatch timing is
        what keeps the retried collective aligned across ranks."""
        if not self._group_mode:
            return fn()

        def _transient(e: BaseException) -> bool:
            msg = str(e)
            return any(m in msg for m in self._TRANSIENT_COLLECTIVE_MARKERS)

        def _on_retry(e: BaseException, attempt: int, _delay: float) -> None:
            logger.warning(
                "transient collective-formation failure on task %d "
                "(attempt %d/%d): %s — retrying",
                task_id, attempt, self._GROUP_TASK_ATTEMPTS,
                str(e)[:200],
            )

        return call_with_backoff(
            fn,
            service="collective",
            is_transient=_transient,
            policy=BackoffPolicy(
                base_s=1.0, multiplier=1.0, max_s=1.0, jitter=0.0,
                max_attempts=self._GROUP_TASK_ATTEMPTS,
            ),
            on_retry=_on_retry,
        )

    def _run_group_training_task(self, task: Task) -> Dict[str, float]:
        return self._retry_transient_collective(
            lambda: self._run_training_task(task), task.task_id
        )

    def _group_resync(self, report: dict, context: str) -> None:
        """A lockstep member that failed a task is DESYNCHRONIZED: its
        peers' next collective (step or checkpoint barrier) would wedge
        waiting for it.  Requeue the task (failure report), actively leave
        the membership (the version bump resyncs the peers), and restart.
        One definition serving the synchronous path and every pipelined
        failure site, so the resync contract cannot drift."""
        report["success"] = False
        report.pop("metrics", None)
        report["seq"] = self._next_report_seq()
        for call, payload in (
            ("ReportTaskResult", report),
            ("DeregisterWorker", {"worker_id": self.worker_id}),
        ):
            try:
                self.master.call(call, payload)
            except Exception:  # master unreachable: peers will
                pass           # still reap us via heartbeats
        raise WorkerRestartRequired(
            f"task {report['task_id']} failed in lockstep mode "
            f"({context}); deregistered for group resync"
        )

    def _next_report_seq(self) -> int:
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending (the only off-loop report path) against the loop (see preemption_snapshot)
        self._report_seq += 1
        return self._report_seq

    # hot-path: the report RPC is accounted under the metrics phase
    def _report_result(self, report: dict) -> None:
        """ReportTaskResult with the cumulative phase decomposition riding
        along (the master's JobStatus and the train-job artifact read it).
        ``phase_counts`` rides beside the seconds so per-phase AVERAGES are
        computable downstream, not just cumulative sums."""
        report["phase_times"] = self.phases.snapshot()
        report["phase_counts"] = self.phases.counts()
        report["seq"] = self._next_report_seq()
        # The stall recorder judges the gap this report ends (gaps run
        # between successful training reports); a stalled gap's record
        # rides this report and no other.  Before the counters, which
        # count it.
        if report["success"] and report.get("task_type") == TASK_TRAINING:
            stall = self._stalls.on_report(
                report["task_id"], report["seq"], report["phase_times"]
            )
            if stall is not None:
                report["stall"] = stall
        else:
            self._stalls.taint()
        # Before the gauge envelope: its collector republishes this
        # snapshot.
        report["counters"] = self._counter_snapshot()
        # Gauge envelope on every task report (forced past the ship
        # throttle: reports are bounded frequency by construction) — the
        # carrier of the master's per-report JSONL gauge mirror.
        gp = self.gauge_payload(force=True)
        if gp is not None:
            report["gauge"] = gp
        # The set-up chain rides the first successful training report and
        # no other: sent, it is dropped.
        setup = self._setup
        if (
            setup is not None
            and report["success"]
            and report.get("task_type") == TASK_TRAINING
            and setup.has("setup:first_dispatch")
        ):
            report["setup"] = self._setup_payload(setup)
        else:
            setup = None
        # The report RPC's own span (rpc:ReportTaskResult) nests inside
        # this one on this thread, so the task id covers it.
        with self.phases.phase("metrics", task=report["task_id"]):
            self.master.call("ReportTaskResult", report)
        if setup is not None:
            # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending (the only off-loop report path) against the loop (see preemption_snapshot)
            self._setup = None

    # hot-path: settles the PREVIOUS task while this one's steps run
    def _flush(self, pending: Optional[tuple]) -> None:
        """Settle a pipelined task: fetch its device metrics, report (rank 0
        only in group mode — peers ran the same collectives but exactly one
        report must hit the master's queues), and run the checkpoint hook.

        Failure containment differs by mode.  Single-process: a fetch
        failure fails THAT task's report (requeued by the master), never the
        task whose dispatch triggered the flush.  Group mode: a deferred
        error surfacing at the fetch can be a failed COLLECTIVE — peers may
        already be wedged waiting — so the member resyncs the gang
        (_group_resync) exactly as a synchronous task failure does."""
        if pending is None:
            return
        report, metrics_list = pending
        try:
            report["metrics"] = self._finalize_training_metrics(
                metrics_list, report["task_id"]
            )
        except Exception:
            logger.exception(
                "task %d failed at metrics fetch", report["task_id"]
            )
            if self._group_mode:
                self._group_resync(report, "metrics fetch")  # raises
            report["success"] = False
            report.pop("metrics", None)
        if not self._group_mode or self._rank == 0:
            if self._group_mode:
                # The checkpoint hook below must stay RANK-SYMMETRIC: a
                # rank-0 report-RPC blip that skipped it would leave the
                # peers starting a collective background save rank 0 never
                # joins (wedged commit barrier) and desync the watermark
                # arithmetic.  Swallow the failure — the master's task
                # timeout requeues a lost report, and the requeued task
                # re-enters the lockstep log symmetrically for every rank.
                try:
                    self._report_result(report)
                except Exception:
                    logger.exception(
                        "group report for task %d lost (master task "
                        "timeout requeues it)", report["task_id"]
                    )
            else:
                self._report_result(report)
        self._profile_close_if_due(report["task_id"])
        if report["success"]:
            # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against the loop (see preemption_snapshot)
            self._tasks_done += 1
            self._g_tasks.inc()
            self._maybe_checkpoint()

    # ---- prep-ahead pipeline (fused + pipelined mode) ----

    def _pipelining_enabled(self) -> bool:
        """Task-level pipelining: defer the previous task's metrics fetch +
        report behind this task's dispatched steps.

        r6 lifted the single-process (``not self._group_mode``) gate: every
        rank dispatches tasks in the lockstep seq order, so deferring the
        LOCAL metrics fetch reorders no collective — the gang's device
        programs still execute in identical task order on every rank.
        Reports stay rank-0-gated inside ``_flush``, and a pipelined-task
        failure resyncs the gang (``_group_resync``) exactly as a
        synchronous one does."""
        return self.config.task_pipelining

    def _prep_ahead_eligible(self) -> bool:
        """Prep-ahead runs the NEXT task's host work (read+decode+stack) on
        a background thread while the current task's H2D transfer streams
        and the previous task's metrics settle — without prep-ahead the
        host<->device link sits idle during every decode and metrics
        fetch.  Group mode is eligible too (r6):
        the host-side decode/pre-shard prep is per-process-local and touches
        no collective state, and a prepped task's DISPATCH still happens
        only at its own lockstep boundary — prep is submitted at task
        acquisition (GetGroupTask), so the gang's collective order is
        untouched.  Only the fused pre-shard path (host-tier tables need
        the host batch on the main thread)."""
        return (
            self.config.task_pipelining
            and self.config.fused_task_scan
            and not self.spec.host_io
        )

    # hot-path: submission only — the prep itself runs on the pool thread
    def _submit_prep(self, task: Task):
        if self._prep_pool is None:
            # One prep thread per pipeline slot: every queued task's host
            # half runs concurrently (each fanning its chunk decodes out to
            # the shared IngestPool), so a slow shard never serializes the
            # preps behind it.  A reader that does NOT declare
            # thread_safe_ranges (shared-connection sources) keeps the
            # pre-r9 one-thread pool: the k-deep queue still buffers k
            # leased tasks, but their reads serialize — concurrent
            # _read_records calls are exactly what such readers forbid.
            width = (
                max(1, self.config.prep_depth)
                if getattr(self.reader, "thread_safe_ranges", False)
                else 1
            )
            self._prep_pool = ThreadPoolExecutor(
                max_workers=width, thread_name_prefix="edl-prep",
                initializer=trace.name_os_thread,
            )
        return self._prep_pool.submit(self._prep_fused_host, task)

    # hot-path: the pipelined steady state — prep wait and the previous
    # task's settle are the only (phase-accounted) blocking points
    def _dispatch_prepped(self, prepped: tuple) -> None:
        """Dispatch a prepped task's device work, rotate it into the
        pending (report-deferred) slot, and settle the PREVIOUS pending
        task.  Single-process: a failure (prep or dispatch) fails THIS
        task's report — the master requeues it — exactly as the inline
        dispatch path does, and nothing is raised: the caller has often
        just queued a NEW task into ``_prep_queue`` whose report dict the
        run loop's outer exception handler would wrongly fail — a task the
        master would requeue while this worker still holds (and later
        trains) it, double-training its records.  Lost reports are the
        master's task timeout's job.

        Group mode: a dispatch failure is a gang DESYNC (peers' collectives
        would wedge on this rank), so after the in-place transient
        collective retry is exhausted this raises WorkerRestartRequired via
        ``_group_resync`` — the restart requeues everything this rank held,
        including the freshly prepped task, through the membership bump."""
        task, report, fut = prepped
        self._profile_open_if_due()
        try:
            with self.phases.phase("prep_wait", task=task.task_id):
                prep = fut.result()
            metrics_list, n_steps = self._retry_transient_collective(
                lambda: self._dispatch_training_task(task, prep=prep),
                task.task_id,
            )
        except Exception:
            logger.exception("task %d failed", task.task_id)
            if self._group_mode:
                self._group_resync(report, "prep/dispatch")  # raises
            report["success"] = False
            try:
                self._report_result(report)
            except Exception:
                logger.exception(
                    "failure report for task %d lost (master task timeout "
                    "will requeue it)", task.task_id,
                )
            return
        self._steps_dispatched += n_steps
        report["model_version"] = self._steps_dispatched
        self._training_tasks_done += 1
        # graftlint: allow[shared-state] the _parked spin-wait handshake serializes the preemption thread's _flush_pending against this swap (see preemption_snapshot)
        prev, self._pending = self._pending, (report, metrics_list)
        try:
            self._flush(prev)
        except WorkerRestartRequired:
            raise  # group resync: the whole process restarts
        except Exception:
            # _flush already contains metric-fetch failures; what escapes is
            # the report RPC itself.  The settled task's work is done and
            # this worker no longer holds it — the master's timeout requeues
            # it if the report truly never landed.
            logger.exception(
                "report of previous pipelined task lost (master task "
                "timeout will requeue it)",
            )

    def _drain_prep(self) -> None:
        """Run the prep-ahead queue to completion (dispatch every prepped
        task, then settle the deferred report slot): called whenever
        something must observe a fully settled task order — eval/predict
        tasks, membership changes, idle polls, job end.  A group resync
        raised mid-drain leaves the remaining entries queued; the restart's
        membership bump requeues them master-side."""
        while self._prep_queue:
            self._dispatch_prepped(self._prep_queue.popleft())
        self._flush_pending()

    def _abandon_prep(self) -> None:
        """Give every undispatched prepped task back to the master (failure
        report -> immediate requeue) — the preemption path must not start
        new device work, and silently dropping a task would make the
        master wait out its timeout.  Each queue entry is reported exactly
        once; tasks already dispatched left the queue and report through
        their pending slot instead (no double-report)."""
        while self._prep_queue:
            task, report, fut = self._prep_queue.popleft()
            fut.cancel()  # not-yet-started prep must not compete with the
            # preemption snapshot for host I/O inside the grace window
            report["success"] = False
            # No device work ran: requeue without charging the retry
            # budget (a genuine failure this is not).
            report["requeue"] = True
            report["seq"] = self._next_report_seq()
            try:
                self.master.call("ReportTaskResult", report)
            except Exception:
                logger.exception(
                    "abandoning prepped task %d failed", task.task_id
                )

    def _abandon_leases(self) -> None:
        """Return locally buffered (never-started) task leases to the
        master: a failure report requeues each immediately, preserving the
        at-least-once contract without waiting out the task timeout.  In
        group mode the buffer is lockstep-log read-ahead attributed to the
        group pseudo worker, and the master's log invalidation on a
        membership change already requeues it — reporting from here would
        double-requeue, so the local buffer is simply dropped."""
        leased, self._leased = self._leased, deque()
        if self._group_mode or not leased:
            return
        for entry in leased:
            t = entry.get("task")
            if not t:
                continue
            report = {
                "worker_id": self.worker_id,
                "task_id": t["task_id"],
                "task_type": t["type"],
                "success": False,
                # Never started: requeue without charging the retry budget.
                "requeue": True,
                "seq": self._next_report_seq(),
            }
            try:
                self.master.call("ReportTaskResult", report)
            except Exception:
                logger.exception(
                    "abandoning leased task %d failed (master task "
                    "timeout will requeue it)", t["task_id"],
                )

    def _flush_pending(self) -> None:
        pending, self._pending = self._pending, None
        self._flush(pending)

    # hot-path: steady-state task acquisition — buffered leases cost no
    # RPC at all; the batched lease RPC is accounted under lease_wait
    def _next_lease(self) -> dict:
        """The next task entry: from the local lease buffer when one is
        held, else one batched GetTask/GetGroupTask RPC (up to
        ``lease_batch`` tasks per round-trip — the r5 loop paid a full
        control-plane RTT per task).  Returns the wire shape
        ``{task?, finished, stale}``; extra leased tasks are buffered and
        consumed on later iterations (and returned to the master by
        ``_abandon_leases`` if preemption or a membership change strikes
        first)."""
        if self._leased:
            return self._leased.popleft()
        n = max(1, self.config.lease_batch)
        if self._group_mode:
            # Lockstep pull: every process of the world executes the same
            # task sequence (the jitted step is a collective over all their
            # devices); the master's group log keys entries by seq, and the
            # lease batches the log walk.
            with self.phases.phase("lease_wait"):
                # The gang-boundary wait, as its own span per rank: in
                # lockstep mode every rank crosses this boundary at the
                # same seq, so per-rank span totals are directly
                # comparable — the straggler report's skew input
                # (tools/straggler_report.py).
                with trace.span(
                    "gang_boundary", cat="gang",
                    seq=self._task_seq, rank=self._rank,
                    version=self._membership_version,
                ):
                    resp = self.master.call(
                        "GetGroupTask",
                        {
                            "worker_id": self.worker_id,
                            "seq": self._task_seq,
                            "version": self._membership_version,
                            "lease": n,
                        },
                    )
            if resp.get("stale"):
                return resp
            entries = resp.get("entries") or [
                {"task": resp.get("task"), "finished": resp["finished"]}
            ]
            self._leased.extend(
                {"task": e["task"], "finished": e["finished"], "stale": False}
                for e in entries[1:]
            )
            return {
                "task": entries[0]["task"],
                "finished": entries[0]["finished"],
                "stale": False,
            }
        with self.phases.phase("lease_wait"):
            resp = self.master.call(
                "GetTask", {"worker_id": self.worker_id, "lease": n}
            )
        tasks = resp.get("tasks")
        if tasks:
            self._leased.extend(
                {"task": t, "finished": False, "stale": False}
                for t in tasks[1:]
            )
            return {"task": tasks[0], "finished": False, "stale": False}
        return {
            "task": resp.get("task"), "finished": resp["finished"],
            "stale": False,
        }

    def _run_evaluation_task(self, task: Task) -> tuple:
        records = self._read_records(task.shard)
        mb = self.config.minibatch_size
        sums: Dict[str, Any] = {}
        total = 0.0

        def _accumulate(metrics, true_count):
            nonlocal total
            for k, v in metrics.items():
                # Histogram metrics (streaming AUC) are vectors; accumulate
                # with the same count weighting as the scalars.
                sums[k] = sums.get(k, 0.0) + np.asarray(v, np.float64) * true_count
            total += true_count

        n_full = len(records) // mb
        if (
            not self.spec.host_io
            and self.config.fused_task_scan
            and n_full >= 1
        ):
            # Fused eval: all full chunks in ONE decode + transfer + scan
            # (the eval twin of the fused training task); only the masked
            # tail runs as a separate step.
            stacked = self._stack_full_minibatches(records, mb, n_full)
            metrics = jax.device_get(
                self.trainer.eval_scan(
                    self.state, self.trainer.shard_stacked_batch(stacked)
                )
            )
            for t in range(n_full):
                _accumulate({k: v[t] for k, v in metrics.items()}, mb)
            tail = records[n_full * mb :]
        else:
            tail = records

        def _batches():
            for chunk, true_count in _minibatches(tail, mb, False):
                batch = dict(self.spec.feed(chunk))
                # Real-vs-padding mask for the wrap-padded tail: metrics
                # count only real rows (see models/metrics.py) — without it
                # the duplicated examples were over-weighted.
                batch["__mask__"] = (np.arange(mb) < true_count).astype(
                    np.float32
                )
                yield batch, true_count

        for batch, true_count in prefetch(
            _batches(), self.config.prefetch_depth,
            name=f"prefetch:{task.task_id}",
        ):
            metrics = self.trainer.run_eval_step(self.state, batch)
            _accumulate(metrics, true_count)
        # Report RAW weighted means — including histogram vectors (as JSON
        # lists) — so the MASTER's cross-worker aggregation stays exact; it
        # derives the AUC scalar at round end (evaluation_service).
        means = {k: s / max(total, 1e-12) for k, s in sums.items()}
        return {
            k: (v.tolist() if v.ndim else float(v)) for k, v in means.items()
        }, total

    def _run_prediction_task(self, task: Task) -> None:
        records = self._read_records(task.shard)
        outs = []
        for batch, true_count in prefetch(
            (
                (self.spec.feed(chunk), count)
                for chunk, count in _minibatches(
                    records, self.config.minibatch_size, False
                )
            ),
            self.config.prefetch_depth,
            name=f"prefetch:{task.task_id}",
        ):
            out = self.trainer.run_predict_step(self.state, batch)
            # graftlint: allow[transfer-discipline] the materialized outputs ARE the prediction task's product; the per-batch fetch is the work
            outs.append(np.asarray(out)[:true_count])
        if self.config.prediction_outputs:
            os.makedirs(self.config.prediction_outputs, exist_ok=True)
            np.save(
                os.path.join(
                    self.config.prediction_outputs, f"task-{task.task_id}.npy"
                ),
                np.concatenate(outs, axis=0),
            )

    def _ship_trace_tail(self, max_beats: int = 8) -> None:
        """Drain the remaining trace buffer to the master over bounded
        extra heartbeats (job end / final settle).  Best-effort: a dead
        master just loses the tail — the job is over either way."""
        rec = trace.default()
        for _ in range(max_beats):
            if not rec.enabled:
                return
            tp = self._trace_payload()
            if tp is None:
                return
            try:
                self.master.call(
                    "Heartbeat",
                    {
                        "worker_id": self.worker_id,
                        "version": self._membership_version,
                        "trace": tp,
                    },
                )
            except Exception:
                logger.info("trace tail ship failed; dropping the tail")
                return

    # ---- main loop ----

    def run(self, membership: Optional[dict] = None) -> Dict[str, Any]:
        """The task loop (``_run``) until the job ends, the world changes
        or something fails."""
        self._stalls.start()  # this thread is the task loop
        try:
            return self._run(membership)
        finally:
            # Whatever ends the loop (job end, a restart for a re-form, a
            # failure): an open profile window is closed and written.
            self._profile_settle()
            self._stalls.stop()

    # hot-path: the task loop itself — every deliberate blocking point is
    # either phase-accounted or individually waived with its reason
    def _run(self, membership: Optional[dict]) -> Dict[str, Any]:
        """Main loop.  ``membership`` is the view returned by an EARLIER
        RegisterWorker call (worker.main registers once, derives the
        jax.distributed spec from that view, and passes it here) — a second
        registration would race a concurrent join and silently absorb a
        membership this process's fixed distributed world does not match.
        Without it (single-process tests, in-process workers) we register
        here."""
        if membership is None:
            # graftlint: allow[hot-path-sync] one-time registration before the loop starts
            membership = self.master.call(
                "RegisterWorker",
                {
                    "worker_id": self.worker_id,
                    "address": self._advertised_address(),
                    "proto": PROTOCOL_VERSION,
                    "incarnation": self._incarnation,
                    # A fresh registration holds nothing: stale leases of
                    # a previous incarnation requeue now (r18 reconcile).
                    "held_tasks": [],
                },
            )
        # graftlint: allow[blocking-propagation] one-time initial membership application before the loop starts
        self._apply_membership(membership, initial=True)
        setup = self._setup
        if self.state is None:
            if setup is not None:
                # Worker.__init__, the model spec, the mesh, the Trainer.
                setup.mark("setup:build")
            self.state = self.trainer.init_state(jax.random.key(0))
            if setup is not None:
                setup.mark("init_state")
            # Every device should hold its shard and nothing else.
            logger.info(
                "device bytes in use after init: %s",
                json.dumps(device_bytes_in_use()),
            )
            # Adopt the newest restorable snapshot from the LOCAL checkpoint
            # directory.  Deliberately NOT gated on the master's
            # GetCheckpoint: a fresh master (standalone evaluation/
            # prediction job over a trained checkpoint, or a master restart)
            # has no reported checkpoint yet, and gating on it made such
            # jobs silently score freshly-initialized weights.
            #
            # Walk retained steps newest-first; adopt a step only when BOTH
            # halves restore (a torn pair — dense committed but the host
            # snapshot missing/truncated after a crash — would silently pair
            # trained dense layers with re-initialized embeddings).  An
            # older intact step beats starting over.
            steps = self._ckpt.all_steps() if self._ckpt is not None else []
            restored_step = None
            for step in steps:
                try:
                    restored = self._restore_checkpoint(self.state, step=step)
                    self.trainer.restore_host_stores(
                        self._ckpt.directory, step
                    )
                    self.state = restored
                    restored_step = step
                    logger.info("joined from checkpoint step %d", step)
                    break
                except FileNotFoundError as e:
                    logger.warning(
                        "checkpoint step %d torn (%s); trying older", step, e
                    )
            if restored_step is None:
                if self.config.job_type in ("evaluation", "prediction"):
                    if self._ckpt is not None:
                        # Fail-loud: scoring random weights is silent garbage.
                        raise RuntimeError(
                            f"{self.config.job_type} job found no restorable "
                            f"checkpoint under {self._ckpt.directory} "
                            f"(steps seen: {steps}); refusing to score "
                            "freshly initialized weights"
                        )
                    # No --checkpoint_dir at all: legitimate for smoke tests,
                    # a misconfiguration in production — say so loudly.
                    logger.warning(
                        "%s job has no --checkpoint_dir: scoring FRESHLY "
                        "INITIALIZED weights", self.config.job_type,
                    )
                if steps:
                    logger.error(
                        "every retained checkpoint step %s was torn; "
                        "training from freshly initialized state", steps,
                    )
            if setup is not None and steps:
                # A relaunched incarnation's walk over the checkpoints.
                setup.mark("setup:restore")

        self._tasks_done = 0
        # graftlint: allow[hot-path-sync] one-time mirror seed before the loop; the restore above already settled the state
        self._steps_dispatched = int(self.state.step)
        while True:
            if self._preempting:
                # SIGTERM arrived: the preemption thread owns the exit
                # (snapshot + os._exit); dispatching more work would keep
                # the state donated-in-flight and unsaveable.  Acknowledge
                # the park FIRST — the abandon report below is a blocking
                # RPC against a master that is slow exactly when a mass
                # preemption is in flight, and paying it before _parked
                # could consume the preemption thread's 5 s park deadline
                # and forfeit the snapshot (ADVICE r5).  Safe: from here
                # this loop only abandons and sleeps, so self.state can no
                # longer be donated or reassigned.
                self._parked = True
                # Give undispatched prepped tasks and unstarted leases
                # straight back to the master (they must not start device
                # work now), then park.
                # graftlint: allow[blocking-propagation] parked for preemption: the abandon report is the last useful work
                self._abandon_prep()
                # graftlint: allow[blocking-propagation] parked for preemption: returning unstarted leases is the last useful work
                self._abandon_leases()
                # graftlint: allow[hot-path-sync] parked for preemption: the loop must only idle here
                time.sleep(self._poll)
                continue
            with self.phases.phase("control"):
                self._check_membership()
                # Buffered lease or one batched GetTask/GetGroupTask RPC
                # (the lease RPC's wall lands in the nested lease_wait
                # phase; control keeps only the heartbeat + loop overhead).
                resp = self._next_lease()
            if self._group_mode and resp.get("stale"):
                # World changed under us: the next membership check
                # raises WorkerRestartRequired.
                # graftlint: allow[hot-path-sync] stale lockstep world: no work to overlap until the re-form
                time.sleep(self._poll)
                continue
            if resp["task"] is None:
                if resp["finished"]:
                    break
                # No new task to overlap with: settle the pipelined ones NOW
                # — the dispatcher cannot finish (or hand out follow-up
                # work, e.g. an eval round gated on this report's
                # model_version) until they land, and idling on unreported
                # tasks would eventually look like a timeout/requeue.
                self._drain_prep()
                # Waiting for work is not a stall of this worker's.
                self._stalls.taint()
                # graftlint: allow[hot-path-sync] dispatcher idle: nothing to dispatch, the poll IS the work
                time.sleep(self._poll)
                continue
            task = Task.from_dict(resp["task"])
            # graftchaos: kill / stall(point=task) faults fire at the task
            # boundary — after the lease, before any device work, so a
            # killed rank's task requeues through the ordinary loss path.
            # BEFORE the seq increment: a rank wedged in this hook has not
            # begun the entry, and its lockstep progress mirror (gang_seq,
            # the deadline-bounded boundary's per-rank signal) must not
            # count it — on a harness without dispatch lookahead the
            # healthy peers sit at the SAME consumed seq, and an
            # already-incremented straggler would be indistinguishable
            # from them, invisible to the very deadline built to cut it.
            chaos.hook(
                "worker:task", rank=self._rank,
                step=self._steps_dispatched, task_id=task.task_id,
            )
            self._task_seq += 1
            report = {
                "worker_id": self.worker_id,
                "task_id": task.task_id,
                "task_type": task.type,
                "success": True,
            }
            try:
                if task.type == TASK_TRAINING:
                    # Task-level pipelining: dispatch this task's steps,
                    # then settle the PREVIOUS task's metrics fetch +
                    # report while these steps run — the fetch is the one
                    # per-task blocking transfer, and overlapping it keeps
                    # the device queue full across task boundaries.  Group
                    # mode pipelines too since r6 (_pipelining_enabled):
                    # dispatch order is the lockstep seq order on every
                    # rank, so no collective is reordered.
                    pipelined = self._pipelining_enabled()
                    if pipelined and self._prep_ahead_eligible():
                        # Prep-ahead: submit THIS task's host work to
                        # the prep pool, then dispatch + settle the
                        # OLDEST prepped task once the queue exceeds
                        # its depth.  At depth k the wire transfer of
                        # task N streams while tasks N+1..N+k decode
                        # and task N-1's metrics settle — k+2 tasks in
                        # flight, link busy end to end.  In group mode
                        # the submission rides the gang
                        # task-acquisition path (this task was just
                        # pulled at its seq), so every prepped
                        # dispatch below stays inside the lockstep
                        # boundary of the task it belongs to.
                        fut = self._submit_prep(task)
                        self._prep_queue.append((task, report, fut))
                        while (
                            len(self._prep_queue)
                            > max(1, self.config.prep_depth)
                        ):
                            self._dispatch_prepped(
                                self._prep_queue.popleft()
                            )
                        continue
                    self._profile_open_if_due()
                    if pipelined:
                        metrics_list, n_steps = (
                            self._retry_transient_collective(
                                lambda: self._dispatch_training_task(
                                    task
                                ),
                                task.task_id,
                            )
                        )
                        self._steps_dispatched += n_steps
                        report["model_version"] = self._steps_dispatched
                        self._training_tasks_done += 1
                        prev, self._pending = (
                            self._pending, (report, metrics_list),
                        )
                        try:
                            self._flush(prev)
                        except WorkerRestartRequired:
                            raise  # group resync: process restarts
                        except Exception:
                            # Same containment as _dispatch_prepped: a
                            # report-RPC failure here must not fail THIS
                            # task's report (its steps are already in
                            # self.state; a master requeue would train
                            # its records twice).  The lost report is
                            # the master task timeout's to requeue.
                            logger.exception(
                                "report of previous pipelined task "
                                "lost (master task timeout requeues)",
                            )
                        continue
                    metrics = (
                        self._run_group_training_task(task)
                        if self._group_mode
                        else self._run_training_task(task)
                    )
                    self._training_tasks_done += 1
                    report["metrics"] = metrics
                    # graftlint: allow[hot-path-sync] synchronous (non-pipelined) mode settles every task by design
                    report["model_version"] = int(self.state.step)
                    # graftlint: allow[hot-path-sync] synchronous-mode mirror resync, same settle as the line above
                    self._steps_dispatched = int(self.state.step)
                elif task.type == TASK_EVALUATION:
                    # Settle the pipelined train tasks first: their reports
                    # must not interleave behind this round's eval
                    # aggregation, and the eval scores the settled state.
                    self._drain_prep()
                    # graftlint: allow[blocking-propagation] eval settles synchronously by design: it scores a settled state
                    metrics, weight = self._run_evaluation_task(task)
                    report["metrics"] = metrics
                    report["weight"] = weight
                elif task.type == TASK_PREDICTION:
                    self._drain_prep()
                    self._run_prediction_task(task)
                else:
                    raise ValueError(f"unknown task type {task.type}")
            except WorkerRestartRequired:
                # A pipelined group failure already reported + deregistered
                # (_group_resync); the restart must not be demoted to a
                # failed report for the task that merely triggered the
                # flush.
                raise
            except Exception:
                logger.exception("task %d failed", task.task_id)
                report["success"] = False
            if self._group_mode and not report["success"]:
                # graftlint: allow[blocking-propagation] failure exit protocol: the member is leaving the world
                self._group_resync(report, "synchronous task")  # raises
            if not self._group_mode or self._rank == 0:
                # In lockstep mode every process ran the task's collectives,
                # but exactly one report must hit the master's queues.
                self._report_result(report)
            self._profile_close_if_due(task.task_id)
            if report["success"]:
                self._tasks_done += 1
                self._g_tasks.inc()
                self._maybe_checkpoint()

        # Settle the last pipelined tasks before the final checkpoint.
        self._drain_prep()
        # Final checkpoint so a completed job is resumable/servable.  In
        # group mode the save is collective (see _maybe_checkpoint); all
        # processes reach this point together because the finished marker is
        # a logged lockstep entry.
        if self._ckpt is not None and self.state is not None and (
            self._group_mode or self._rank == 0
        ):
            with self.phases.phase("checkpoint"):
                # Settle any in-flight background periodic save first: the
                # final save below must not interleave with it.  In group
                # mode this is also the shutdown settle point for the
                # background COLLECTIVE save — every rank joins its own
                # thread here before entering the final collective save.
                self._join_ckpt()
                step = int(self.state.step)
                with self._ckpt_lock:
                    committed = self._ckpt_committed_step == step
                # A step is written ONCE: when the background save just
                # joined is KNOWN to have committed this very step (the job
                # ended on a checkpoint boundary), its dense state is on
                # disk and a second save of it would only meet the first
                # one's directories.  A background save that failed kept its
                # watermark (group policy) but committed nothing, so the
                # final save below still runs: a completed job ends with a
                # checkpoint that restores.
                if not committed:
                    # Canonical layout either way: group mode canonicalizes
                    # on device (collective saves stream device arrays), the
                    # single-process path on host.
                    payload = (
                        self.trainer.snapshot_state(self.state)
                        if self._group_mode
                        else self.trainer.host_state(self.state)
                    )
                    self._save_dense(step, payload, wait=True)
                if self._rank == 0:
                    # Rank-gated like _maybe_checkpoint: one Save fan-out
                    # per step (plain RPC, not collective — no deadlock
                    # risk).
                    self.trainer.save_host_stores(self._ckpt.directory, step)
                    # Publish for serving: the completed job's final state
                    # is exactly the checkpoint an online tier wants live.
                    self._ckpt.publish(step)
                if self._rank == 0:
                    self.master.call(
                        "ReportCheckpoint", self._checkpoint_report(step)
                    )
        # Ship the trace tail: events recorded since the last heartbeat
        # would otherwise die with this process (the merged view of a
        # COMPLETED job wants its final tasks too).  Inside a control
        # phase boundary: these are deliberate, accounted job-end RPCs.
        with self.phases.phase("control"):
            self._ship_trace_tail()
        return {
            "tasks_done": self._tasks_done,
            # graftlint: allow[hot-path-sync] job-end summary; everything is already settled
            "step": int(self.state.step) if self.state is not None else 0,
            "reforms": self.reforms,
            # The task loop's wall decomposition (common/metrics.PhaseTimers)
            # for in-process callers; out-of-process consumers read the same
            # snapshot off the master's JobStatus.
            "phase_times": self.phases.snapshot(),
        }
