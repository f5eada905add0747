"""Worker pod entry point.

Reference parity (SURVEY.md §2 #7 [U]): the master renders worker pods whose
command is the worker main module and whose args/env carry the job config;
here the config bus is the ``ELASTICDL_JOB_CONFIG`` env var (set by the
PodManager) with CLI flags as a fallback, and the worker id comes from
``ELASTICDL_WORKER_ID`` (the pod name).

Run as ``python -m elasticdl_tpu.worker.main``.

Two caches serve this process's programs (``main`` switches both on before
the first touch of jax's backend): jax's persistent compile cache
(``common/platform.enable_compile_cache``) serves every program the process
TRACES, at the price of the trace; the program store
(``common/program_store.py``, the directory beside it) serves a relaunched
worker its compiled train step with nothing traced.  Only this entry point
makes a store: a ``Trainer`` built anywhere else traces its step.
"""

from __future__ import annotations

# First, before anything heavy: importing the recorder stamps the origin of
# this incarnation's set-up chain (``setup:interp`` ends and
# ``setup:imports`` starts there).
from elasticdl_tpu.common import trace  # isort: skip

import json
import os
import sys
import threading
import time
from typing import List, Optional

from elasticdl_tpu.common.config import JobConfig, parse_args
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.rpc import PROTOCOL_VERSION
from elasticdl_tpu.data.reader import (
    AbstractDataReader,
    CompositeDataReader,
    create_data_reader,
)
from elasticdl_tpu.worker.worker import (
    RESTART_EXIT_CODE,
    RpcMasterProxy,
    Worker,
    WorkerRestartRequired,
)

logger = get_logger("worker.main")

# Multihost join settle window: after registering, wait for the rendezvous
# version to hold still this long (bounded by the max) before fixing the
# jax.distributed world.  Workers of one gang start near-simultaneously; the
# first to register would otherwise derive a world of 1 and pay a full
# process restart the moment the second joins.  Sampled at SETTLE_POLL_S so
# the wait costs ~the stability window itself, not a fixed sleep — the
# settle is on the relaunch critical path (docs/perf.md re-rendezvous), and
# a missed race now costs one CHEAP restart (warm standby + death push)
# rather than a cold boot.
SETTLE_STABLE_S = 1.0
SETTLE_POLL_S = 0.25
SETTLE_MAX_S = 15.0


def build_job_reader(config: JobConfig) -> AbstractDataReader:
    """One reader serving every dataset the job's tasks may name."""
    params = config.parsed_data_reader_params()
    paths = [
        p
        for p in (
            config.training_data,
            config.validation_data,
            config.prediction_data,
        )
        if p
    ]
    if not paths:
        raise ValueError("job config names no data paths")
    readers = [create_data_reader(p, params) for p in dict.fromkeys(paths)]
    return readers[0] if len(readers) == 1 else CompositeDataReader(readers)


def _park_as_standby(go_file: str) -> str:
    """Warm-standby mode (ELASTICDL_STANDBY_GO_FILE): pre-pay the boot tail
    — python + jax + framework imports, about half of a re-rendezvous on
    the CPU harness (docs/perf.md) — then park until the pod manager
    writes the go file naming the worker id this process should become.
    Nothing here may touch a jax *backend* (devices/compile): in multihost
    mode the backend must first bind to the jax.distributed world formed
    AFTER registration, and on a TPU host the live worker holds the chip —
    a spare that opened a backend while parked would fail or hang, or take
    the chip from it.
    Returns the assigned worker id."""
    import importlib

    for mod in (
        "jax", "jax.numpy", "flax", "optax", "orbax.checkpoint",
        "elasticdl_tpu.parallel.trainer", "elasticdl_tpu.parallel.mesh",
        "elasticdl_tpu.models.spec", "elasticdl_tpu.data.reader",
        "elasticdl_tpu.worker.worker",
    ):
        importlib.import_module(mod)
    logger.info("standby warmed (pid %d); parking on %s", os.getpid(), go_file)
    # Readiness marker (atomic publish, like the go file itself): only a
    # WARMED spare is worth adopting — the pod manager skips spares whose
    # marker is absent and cold-spawns instead (ProcessPodBackend
    # _adopt_standby), so a burst of failures never queues behind a spare
    # that is still paying its imports.
    from elasticdl_tpu.common import durable

    ready = go_file + ".ready"
    durable.atomic_publish(ready, str(os.getpid()))
    parent0 = os.getppid()
    while not os.path.exists(go_file):
        if os.getppid() != parent0:
            # The master died without close() (kill -9/OOM): nothing will
            # ever write the go file — exit instead of parking a jax-loaded
            # interpreter forever (review r5).
            logger.info("standby orphaned (parent gone); exiting")
            raise SystemExit(0)
        time.sleep(0.05)
    # JSON payload: the worker id plus per-pod identity env the backend
    # withheld at spawn time so one spare serves any slot (ProcessPodBackend
    # _IDENTITY_KEYS) — e.g. ELASTICDL_WORKER_SLOT, which
    # parallel/distributed.py reads for coordinator selection.
    payload = json.loads(open(go_file).read())
    for k, v in payload.get("env", {}).items():
        os.environ[k] = v
    worker_id = payload["worker_id"]
    logger.info("standby adopted as %s", worker_id)
    return worker_id


def settle_membership(
    master,
    worker_id: str,
    membership: dict,
    *,
    stable_s: Optional[float] = None,
    poll_s: Optional[float] = None,
    max_s: Optional[float] = None,
    clock=time.time,
    sleep=time.sleep,
) -> dict:
    """The gang-formation wait: return the membership view to fix the
    jax.distributed world on.

    When the master publishes the fleet's DESIRED size (``expected``),
    form the world once the full gang is registered AND every member has
    CONFIRMED the current version (registration or the versioned
    heartbeat this loop sends).  Both halves matter: without the size
    gate, staggered relaunches form worlds one member at a time; without
    the confirmation gate, a fresh relaunch forms a world with a STALE
    incarnation that is about to restart — each late restart then
    restarts everyone who already formed (tripling a 2-pod peer-death
    recovery on the CPU harness before these gates; docs/perf.md).  Fall
    back to the version-stability heuristic when the master doesn't
    publish a target (hand-spawned workers), and proceed with whoever is
    present at the deadline either way: a crash-looping peer must degrade
    the world, not wedge it.
    """
    stable_s = SETTLE_STABLE_S if stable_s is None else stable_s
    poll_s = SETTLE_POLL_S if poll_s is None else poll_s
    max_s = SETTLE_MAX_S if max_s is None else max_s
    deadline = clock() + max_s
    stable_since = clock()
    while clock() < deadline:
        expected = membership.get("expected") or 0
        confirmed = membership.get("confirmed") or {}
        version = membership["version"]
        if (
            expected
            and membership["world_size"] == expected
            and all(
                confirmed.get(w) == version for w in membership["workers"]
            )
        ):
            # EXACT size, not >=: during a scale-DOWN the doomed members
            # stay registered (and confirmed) through their terminate
            # grace; forming an oversized world with them guarantees an
            # immediate re-collapse as they exit.  An overshoot that
            # never drains falls back to the deadline path below, which
            # proceeds with whoever is present.
            break
        sleep(poll_s)
        try:
            # The versioned heartbeat IS this worker's confirmation of
            # the view it currently intends to form.
            master.call(
                "Heartbeat", {"worker_id": worker_id, "version": version}
            )
            current = master.call("GetMembership", {})
        except Exception:
            # Master briefly unreachable (mass relaunch is exactly when
            # this loop runs): retry next poll rather than burning
            # relaunch budget on a healthy worker.
            continue
        if current["version"] != membership["version"]:
            stable_since = clock()
        elif not expected and clock() - stable_since >= stable_s:
            membership = current
            break
        # Adopt unconditionally: the confirmed map advances WITHOUT a
        # version bump (peers confirm by heartbeat), so updating only on
        # version change would freeze the formation condition at its
        # registration-time snapshot and ride every settle to the
        # deadline.
        membership = current
    return membership


#: Hard-exit bound after SIGTERM: k8s preemption grants a grace window
#: (default 30 s) before SIGKILL; the snapshot must not gamble on using
#: all of it, and a wedged snapshot must still exit RESTART in time for
#: the relaunch to ride the warm standby.
PREEMPTION_EXIT_S = 15.0


def _install_preemption_handler(worker_holder: dict) -> None:
    """SIGTERM = preemption notice (k8s eviction, spot reclaim, pod
    delete): snapshot if safe, then exit RESTART so the pod manager
    relaunches without burning failure budget and gang peers re-form
    immediately instead of discovering the death by heartbeat.

    The handler only SPAWNS the graceful thread: the signal frame may be
    inside jax/XLA calls, where re-entering jax (device_get in the save)
    is not safe — the work happens on a plain thread while a hard timer
    bounds the whole exit (PS main has used this SIGTERM shape since r3;
    the worker was the gap).
    """
    import signal

    def _graceful() -> None:
        try:
            w = worker_holder.get("worker")
            if w is not None:
                w.preemption_snapshot()
        except Exception:
            logger.exception("preemption snapshot failed; exiting anyway")
        finally:
            sys.stderr.flush()
            sys.stdout.flush()
            os._exit(RESTART_EXIT_CODE)

    def _on_term(signum, frame):
        logger.info("SIGTERM: preemption notice; snapshot + RESTART exit")
        threading.Thread(
            target=_graceful, name="preemption", daemon=True
        ).start()
        t = threading.Timer(
            PREEMPTION_EXIT_S, lambda: os._exit(RESTART_EXIT_CODE)
        )
        t.daemon = True
        t.start()

    signal.signal(signal.SIGTERM, _on_term)


def main(argv: Optional[List[str]] = None) -> int:
    try:
        config = JobConfig.from_env()
    except KeyError:
        config = parse_args(argv)
    if not config.master_addr:
        raise SystemExit("worker needs --master_addr (or config via env)")
    from elasticdl_tpu.common.log_utils import set_level

    set_level(config.log_level)
    # This incarnation's set-up chain (common/trace.py SetupChain): the
    # marks below and Worker's partition the boot, and the whole rides the
    # first training report into the master's metrics.jsonl.
    setup = trace.setup()
    go_file = os.environ.get("ELASTICDL_STANDBY_GO_FILE", "")
    if go_file:
        worker_id = _park_as_standby(go_file)
        setup.restart()  # the imports were paid while parked
    else:
        worker_id = os.environ.get(
            "ELASTICDL_WORKER_ID", f"worker-{os.getpid()}"
        )
    logger.info("worker %s booting (pid %d)", worker_id, os.getpid())
    # Persistent XLA compile cache: every elastic re-join re-jits the train
    # step for its (program, topology); relaunched incarnations load the
    # executable from disk instead of recompiling (~20-40 s on TPU).  This
    # also bounds COMPILE SKEW between gang members forming a collective:
    # XLA:CPU's Gloo context init times out (hard 30 s) if one process is
    # still compiling while its peer already executes — observed when the
    # fused-scan compile ran under CPU contention.
    #
    # Which cache serves what: the compile cache serves every program this
    # process still TRACES (init_state, eval, predict, the snapshot copy, and
    # the train step of a first launch) at the price of the trace; the
    # program store, a sibling directory of it, serves a relaunch its
    # compiled TRAIN STEP with nothing traced (common/program_store.py).
    from elasticdl_tpu.common.platform import enable_compile_cache
    from elasticdl_tpu.common.program_store import ProgramStore

    enable_compile_cache()
    programs = ProgramStore.beside_compile_cache()
    setup.mark("setup:imports")

    # Call deadline + outage ride-through budget come off the config bus
    # (r18): the proxy owns both — see RpcMasterProxy.
    master = RpcMasterProxy(
        config.master_addr,
        call_timeout_s=config.master_call_timeout_s,
        outage_tolerance_s=config.master_outage_tolerance_s,
    )
    # Register EXACTLY ONCE, before any jax computation.  The membership view
    # from this call both (a) seeds the jax.distributed spec (the PJRT world
    # is fixed once created) and (b) is handed to Worker.run verbatim — a
    # second registration inside run() would race a concurrent join and
    # absorb a membership this process's fixed world does not match
    # (VERDICT r2 Weak #3).  Any later change surfaces as a heartbeat
    # version bump, which in multihost mode restarts the process.
    from elasticdl_tpu.parallel import distributed

    # Incarnation nonce (r18): this boot's identity across every
    # registration this process makes — the master resets the worker's
    # report-seq dedup ledger when it changes (a fresh process restarts
    # its seq counter at 1).
    incarnation = f"{os.getpid()}-{int(time.time() * 1e3)}"
    membership = master.call(
        "RegisterWorker",
        {
            "worker_id": worker_id,
            "address": distributed.advertised_address() if config.multihost else "",
            "proto": PROTOCOL_VERSION,
            "incarnation": incarnation,
            # held_tasks=[] (r18): a fresh boot HOLDS nothing — the master
            # requeues any journal-replayed leases still attributed to a
            # previous incarnation of this id NOW, instead of waiting out
            # task_timeout_s.
            "held_tasks": [],
        },
    )
    # Liveness is a background thread, decoupled from the task loop: the
    # startup window (jax.distributed waiting for peers, first XLA compile)
    # and long steps must not look like death to the master's reaper.  The
    # loop's own Heartbeat calls still drive version-change detection.
    hb_stop = threading.Event()
    # Set once the Worker exists; the beat thread then doubles as the
    # DEATH-PUSH receiver (Worker.death_watch_tick): a survivor blocked in
    # a collective on a dead peer force-exits RESTART within ~grace seconds
    # of the master's eviction instead of waiting out the coordination
    # heartbeat (--distributed_heartbeat_timeout_s).
    worker_holder: dict = {}

    def _beat() -> None:
        dw_state: dict = {"pending_since": None}
        while not hb_stop.wait(0.25 if dw_state["pending_since"] else 1.0):
            master_version = None
            w = worker_holder.get("worker")
            try:
                hb = {"worker_id": worker_id}
                if w is not None:
                    # Gang-boundary arrival progress (r13): the beat is
                    # the only RPC still leaving this process while the
                    # task loop is blocked inside a wedged collective —
                    # without it the deadline-bounded boundary could
                    # never tell the straggler (arrival counter frozen)
                    # from the ranks blocked on it (counter one ahead).
                    hb.update(w.gang_beat_fields())
                    # Gauge envelope (r14) on the SAME beat, for the same
                    # reason: a wedged gang's fleet metrics must keep
                    # flowing while the task loop's own heartbeat is
                    # silent (registry locks are leaves — safe from this
                    # thread).
                    gp = w.gauge_payload()
                    if gp is not None:
                        hb["gauge"] = gp
                resp = master.call("Heartbeat", hb)
                master_version = resp.get("version")
            except Exception:  # master briefly unreachable: retry next beat
                pass
            if w is None:
                continue
            try:
                # The Heartbeat response's version lets the tick skip its
                # own membership RPC in the steady state.
                if w.death_watch_tick(
                    dw_state, time.time(), master_version=master_version
                ):
                    sys.stderr.flush()
                    sys.stdout.flush()
                    os._exit(RESTART_EXIT_CODE)
            except Exception:
                logger.exception("death watch tick failed; will retry")

    threading.Thread(target=_beat, daemon=True, name="heartbeat").start()
    _install_preemption_handler(worker_holder)
    logger.info(
        "worker %s registered (membership v%s, world %s)",
        worker_id, membership.get("version"), membership.get("world_size"),
    )

    if config.multihost:
        membership = settle_membership(master, worker_id, membership)
        spec = distributed.spec_from_membership(
            membership,
            worker_id,
            config.coordinator_port,
            heartbeat_timeout_s=config.distributed_heartbeat_timeout_s,
        )
        distributed.initialize(spec)
    setup.mark("setup:register")
    # Boot line: what this process — the one that owns the device and runs
    # every step — actually landed on.  With JAX_PLATFORMS unset JAX falls
    # back to the CPU when the accelerator fails to initialise, and the job
    # would otherwise finish "green" on the host without saying so.  This
    # is the process's first touch of the backend (the TPU client opens
    # here, before Worker's own ``jax.devices()``), so it is a span of its
    # own.
    from elasticdl_tpu.common.platform import (
        compile_cache_stats,
        device_summary,
        wait_for_chips,
    )
    from elasticdl_tpu.ps.host_store import native_lib_available

    # A worker relaunched where one was just killed gets here before the
    # kernel has given the dead one's chips back; the TPU client would fail
    # the process on a busy chip, so the wait is this span's.
    waited_s, still_busy = wait_for_chips()
    if still_busy or waited_s >= 0.25:
        logger.info(
            "worker %s waited %.2f s for the chips an ended process held%s",
            worker_id, waited_s,
            f"; still busy: {still_busy}" if still_busy else "",
        )
    device = device_summary()
    setup.mark("setup:device_open")
    boot = dict(device, native_lib=native_lib_available())
    logger.info("worker %s device: %s", worker_id, json.dumps(boot))
    # The process-default registry (r14): the worker's own families plus
    # cross-cutting client-side ones (the PS retry counter records via
    # gauge.default()) all land in ONE registry, so the scrape endpoint
    # below serves everything this process measures.
    from elasticdl_tpu.common import gauge
    from elasticdl_tpu.common.metrics_http import maybe_start

    # The reader scans nothing here: a file is indexed at its first read,
    # inside setup:first_prep (docs/observability.md).
    with setup.child("setup:shards"):
        reader = build_job_reader(config)
    worker = Worker(
        config, master, reader, worker_id=worker_id,
        gauges=gauge.default(), incarnation=incarnation, setup=setup,
        programs=programs,
    )
    worker_holder["worker"] = worker
    metrics_server = maybe_start(
        config.gauge_port,
        worker.gauges.render_prometheus,
        health_fn=lambda: {
            "role": "worker",
            "worker_id": worker_id,
            "membership_version": worker._membership_version,
        },
        registry=worker.gauges,
    )
    try:
        result = worker.run(membership=membership)
    except WorkerRestartRequired as e:
        logger.info("worker %s restarting: %s", worker_id, e)
        hb_stop.set()
        # Skip interpreter teardown: atexit hooks (jax.distributed shutdown,
        # gRPC channels) can block for tens of seconds against peers that
        # are mid-collective or already gone.  The relaunch replaces the
        # whole process anyway — exit NOW so the pod manager can.
        sys.stderr.flush()
        sys.stdout.flush()
        os._exit(RESTART_EXIT_CODE)
    finally:
        hb_stop.set()
        if metrics_server is not None:
            metrics_server.stop()
    programs.settle()  # a job shorter than the store's write
    result["device"] = boot
    result["compile_cache"] = compile_cache_stats()
    result["program_store"] = dict(programs.counts(), dir=programs.directory)
    logger.info(
        "worker %s finished: %s", worker_id, json.dumps(result, default=str)
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
