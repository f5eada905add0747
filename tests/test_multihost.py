"""Executable multi-host training (SURVEY.md §3.5; VERDICT r2 Missing #2).

Three layers of evidence:

1. Unit: the master's GetGroupTask lockstep log — every process of a world
   walks the identical task sequence; version changes invalidate the log and
   requeue the group's in-flight tasks.
2. In-process: two Worker loops in group mode (threads, shared servicer)
   execute the same tasks and exactly one reports.
3. Integration: TWO real worker processes join one ``jax.distributed`` world
   over localhost (4 fake CPU devices each, 8-device global mesh), train
   lockstep through the gRPC master, one is SIGKILLed, the survivor restarts
   via RESTART_EXIT_CODE and the relaunched single-host worker resumes from
   the pre-restart snapshot and finishes the job.
"""

import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.rendezvous import RendezvousServer
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher


def _shards(tmp_path, n_records=64, records_per_task=16, name="train.rio"):
    path = str(tmp_path / name)
    generate("mnist", path, n_records)
    reader = create_data_reader(path)
    return path, reader, reader.create_shards(records_per_task)


# ---------------------------------------------------------------------------
# 1. GetGroupTask semantics
# ---------------------------------------------------------------------------


def test_group_task_lockstep_same_sequence(tmp_path):
    """Two processes pulling the same seqs get the same tasks, regardless of
    interleaving; the log survives out-of-order arrival."""
    _, _, shards = _shards(tmp_path)
    servicer = MasterServicer(TaskDispatcher(shards))
    servicer.RegisterWorker({"worker_id": "w-a"})
    v = servicer.RegisterWorker({"worker_id": "w-b"})["version"]

    # Until EVERY member confirms the current version, no collective task is
    # issued (a stale member would wedge its peers inside the collective).
    r = servicer.GetGroupTask({"worker_id": "w-a", "seq": 0, "version": v})
    assert r == {"task": None, "finished": False, "stale": False}
    servicer.Heartbeat({"worker_id": "w-a", "version": v})

    seq_a, seq_b = [], []
    # a pulls ahead two entries, then b catches up, then interleave.
    for seq, out in ((0, seq_a), (1, seq_a), (0, seq_b), (1, seq_b),
                     (2, seq_b), (2, seq_a), (3, seq_a), (3, seq_b)):
        r = servicer.GetGroupTask({"worker_id": "w", "seq": seq, "version": v})
        assert not r["stale"]
        out.append((r["task"] or {}).get("task_id"))
    assert seq_a == seq_b
    assert len({t for t in seq_a if t is not None}) == 4  # distinct tasks

    # report them (rank 0's job); later seqs drain the queue and mark finished
    for tid in seq_a:
        servicer.ReportTaskResult(
            {"worker_id": "w-a", "task_id": tid, "success": True,
             "task_type": "training"}
        )
    r = servicer.GetGroupTask({"worker_id": "w", "seq": 4, "version": v})
    assert r["task"] is None and r["finished"] and not r["stale"]
    # the finished marker is logged: the peer sees the identical terminal entry
    r2 = servicer.GetGroupTask({"worker_id": "w", "seq": 4, "version": v})
    assert r2 == r


def test_group_task_stale_on_version_change_and_requeue(tmp_path):
    """A membership bump invalidates the old world's log; its in-flight tasks
    requeue as soon as the new world asks for work."""
    _, _, shards = _shards(tmp_path)
    dispatcher = TaskDispatcher(shards)
    servicer = MasterServicer(dispatcher)
    v1 = servicer.RegisterWorker({"worker_id": "w-a"})["version"]
    r = servicer.GetGroupTask({"worker_id": "w-a", "seq": 0, "version": v1})
    assert r["task"] is not None
    assert dispatcher.counts()["doing"] == 1

    v2 = servicer.RegisterWorker({"worker_id": "w-b"})["version"]
    assert v2 != v1
    # old world is told it is stale
    stale = servicer.GetGroupTask({"worker_id": "w-a", "seq": 1, "version": v1})
    assert stale["stale"]
    servicer.Heartbeat({"worker_id": "w-a", "version": v2})  # w-a re-confirms
    # new world's first pull resets the log and requeues the orphaned task
    r2 = servicer.GetGroupTask({"worker_id": "w-b", "seq": 0, "version": v2})
    assert not r2["stale"] and r2["task"] is not None
    assert r2["task"]["task_id"] == r["task"]["task_id"]  # requeued, re-issued


def test_group_task_seq_ahead_is_stale(tmp_path):
    _, _, shards = _shards(tmp_path)
    servicer = MasterServicer(TaskDispatcher(shards))
    v = servicer.RegisterWorker({"worker_id": "w-a"})["version"]
    assert servicer.GetGroupTask(
        {"worker_id": "w-a", "seq": 7, "version": v}
    )["stale"]


# ---------------------------------------------------------------------------
# 2. Two in-process workers in lockstep group mode
# ---------------------------------------------------------------------------


def test_two_workers_lockstep_in_process(tmp_path, devices):
    """Both group-mode workers execute every task (their steps would be one
    collective on a real multi-host mesh); only rank 0 reports."""
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    path, reader, shards = _shards(tmp_path)
    dispatcher = TaskDispatcher(shards)
    servicer = MasterServicer(dispatcher)
    config = JobConfig(
        model_def="mnist.model_spec",
        training_data=path,
        minibatch_size=16,
        multihost=True,
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )

    # Register BOTH up front (as worker.main does) so neither sees a
    # membership bump mid-run (multihost bumps raise WorkerRestartRequired).
    memberships = {
        w: servicer.RegisterWorker({"worker_id": w}) for w in ("w-a", "w-b")
    }
    memberships["w-a"] = memberships["w-b"]  # both hold the final view

    workers = {
        w: Worker(
            config, DirectMasterProxy(servicer), reader,
            worker_id=w, spec=spec, devices=devices,
        )
        for w in ("w-a", "w-b")
    }
    results, errors = {}, {}

    def run(w):
        try:
            results[w] = workers[w].run(membership=memberships[w])
        except Exception as e:  # pragma: no cover - surfaced by asserts
            errors[w] = e

    threads = [threading.Thread(target=run, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors
    assert results["w-a"]["tasks_done"] == results["w-b"]["tasks_done"] == 4
    # every task ran on both workers, but the master saw each exactly once
    assert servicer.dispatcher.counts()["done"] == 4
    assert servicer.dispatcher.finished()


def test_heartbeat_revival_does_not_confirm(tmp_path):
    """An evicted worker revived by a bare heartbeat must NOT count as
    having confirmed the topology (its address is gone and it never applied
    the post-revival membership) — otherwise the lockstep log would issue
    collective work to a split-brain world."""
    t = [0.0]
    rdv = RendezvousServer(heartbeat_timeout_s=5.0, clock=lambda: t[0])
    rdv.register("w-a", address="10.0.0.1")
    t[0] = 10.0
    assert rdv.reap_dead() == ["w-a"]
    v = rdv.heartbeat("w-a")  # background-thread beat: no version
    assert "w-a" in rdv.membership()["workers"]
    assert not rdv.all_confirmed(v)
    # a version-carrying heartbeat (the worker re-applied) confirms
    rdv.heartbeat("w-a", version=v)
    assert rdv.all_confirmed(v)


def test_group_task_failure_forces_resync(tmp_path, devices):
    """A lockstep member that fails a task must requeue it, actively leave
    the membership (so peers resync instead of wedging in a collective), and
    restart — NOT swallow the error and run ahead of the group."""
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import (
        DirectMasterProxy,
        Worker,
        WorkerRestartRequired,
    )

    path, reader, shards = _shards(tmp_path)
    dispatcher = TaskDispatcher(shards)
    servicer = MasterServicer(dispatcher)
    config = JobConfig(
        model_def="mnist.model_spec",
        training_data=path,
        minibatch_size=16,
        multihost=True,
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )

    class FailingReader:
        def read_records(self, shard):
            raise IOError("storage hiccup")

    servicer.RegisterWorker({"worker_id": "w-a"})
    membership = servicer.RegisterWorker({"worker_id": "w-b"})
    servicer.Heartbeat({"worker_id": "w-a", "version": membership["version"]})
    worker = Worker(
        config, DirectMasterProxy(servicer), FailingReader(),
        worker_id="w-b", spec=spec, devices=devices,
    )
    with pytest.raises(WorkerRestartRequired, match="lockstep"):
        worker.run(membership=membership)
    m = servicer.GetMembership({})
    assert "w-b" not in m["workers"]  # actively left -> peers resync
    counts = dispatcher.counts()
    assert counts["doing"] == 0 and counts["todo"] == 4  # task requeued


# ---------------------------------------------------------------------------
# 3. Real 2-process jax.distributed world over localhost
# ---------------------------------------------------------------------------


def _free_port() -> int:
    # common.platform is jax-free: the shared helper without the jax
    # import parallel.distributed would drag in.
    from elasticdl_tpu.common.platform import free_port

    return free_port()


_incarnation = {}  # (log_dir, worker_id) -> launch count (per-test isolation)


def _spawn_worker(worker_id: str, config: JobConfig, log_dir) -> subprocess.Popen:
    env = dict(os.environ)
    env.update(config.to_env())
    env["ELASTICDL_WORKER_ID"] = worker_id
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    # Per-test compile cache, shared by the gang: incarnations re-join
    # without recompiling, and — critically — the cache state stays
    # SYMMETRIC across gang members.  A global cache left one member with a
    # warm hit and the other compiling cold, and that skew (under 1-core
    # contention) outlived XLA:CPU's hard 30 s Gloo context-init window,
    # collapsing every world formation.
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(str(log_dir), "jax_cache")
    # One log file PER INCARNATION: tail checks (fatal-marker classification)
    # must see only the CURRENT incarnation — a stale marker from a previous
    # life would misclassify a fresh crash as a relaunchable fatal — while
    # whole-run assertions read every incarnation's file.
    key = (str(log_dir), worker_id)
    n = _incarnation.get(key, 0)
    _incarnation[key] = n + 1
    log = open(os.path.join(log_dir, f"{worker_id}.log.{n}"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.worker.main"],
        env=env, stdout=log, stderr=subprocess.STDOUT, cwd="/root/repo",
    )


def _latest_log(log_dir, worker_id: str) -> str:
    """The CURRENT incarnation's full output."""
    n = _incarnation.get((str(log_dir), worker_id), 1) - 1
    path = os.path.join(log_dir, f"{worker_id}.log.{n}")
    return open(path).read() if os.path.exists(path) else ""


def _all_logs(log_dir, worker_id: str) -> str:
    """Every incarnation's output, concatenated launch order."""
    out = []
    for n in range(_incarnation.get((str(log_dir), worker_id), 0)):
        path = os.path.join(log_dir, f"{worker_id}.log.{n}")
        if os.path.exists(path):
            out.append(open(path).read())
    return "".join(out)


@pytest.mark.slow
def test_real_process_scale_4_8_4(tmp_path):
    """The BASELINE config-#5 scale story with REAL processes (the older
    in-process test emulates membership over a fixed pool): one worker
    process (4 fake devices) trains alone, a second joins (the world re-forms
    to 8 devices via RESTART + jax.distributed re-init), then the joiner is
    killed and the survivor drains the job back at 4 devices."""
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    path, _, shards = _shards(
        tmp_path, n_records=256, records_per_task=32, name="train.rio"
    )
    # Long task stream: the joiner needs ~15s to boot (jax import +
    # distributed init), and the solo phase must not drain the job first.
    dispatcher = TaskDispatcher(shards, num_epochs=60)
    # 20 s reaper: a joiner compiling under 1-core contention (the incumbent
    # saturates the core since the r4 fused-scan loop) can starve its
    # liveness thread past 6 s; evicting it mid-join collapses the world.
    rendezvous = RendezvousServer(heartbeat_timeout_s=20.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    from elasticdl_tpu.master.servicer import MasterServer

    server = MasterServer(servicer, port=0).start()
    stop = threading.Event()

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            time.sleep(0.25)

    threading.Thread(target=reap, daemon=True).start()

    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=_free_port(),
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=4,
        num_epochs=60,
        # This harness runs 3 python processes on ONE core: a freshly joined
        # peer's coordination heartbeats can starve >30 s during restore +
        # first compile, and the r4 default (30 s) then produces FALSE
        # peer-death that churns the world until the phase deadline.  Use
        # the conservative bound this scenario needs (JAX's own default,
        # what r3 implicitly ran with); kill-driven tests keep the fast
        # default so aborts stay quick.
        distributed_heartbeat_timeout_s=100.0,
        # The r4 fused-scan loop saturates the core; a solo incumbent then
        # starves the JOINER's cold compile past XLA:CPU's hard 30 s Gloo
        # context-init window, collapsing every world formation on this
        # 1-core harness.  The per-batch path leaves the scheduler slack
        # the join needs; the fused path's multi-process correctness is
        # covered by test_two_process_distributed_train_kill_resume, where
        # the gang compiles symmetrically.  (r5: said directly via the
        # dedicated flag — prefetch_depth=0 no longer implies it.)
        prefetch_depth=0,
        fused_task_scan=False,
        task_pipelining=False,
    )
    procs: dict = {}

    def _log_tail(w):
        return _latest_log(tmp_path, w)[-3000:]

    def supervise_until(cond, deadline_s):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if cond():
                return
            for w, p in list(procs.items()):
                rc = p.poll()
                if rc is None:
                    continue
                fatal = (
                    "JAX distributed service detected fatal errors"
                    in _log_tail(w)
                )
                if rc == RESTART_EXIT_CODE or fatal:
                    procs[w] = _spawn_worker(w, config, tmp_path)
                else:
                    pytest.fail(f"{w} exited rc={rc}; log:\n" + _log_tail(w))
            time.sleep(0.5)
        pytest.fail("condition not reached; logs:\n"
                    + "".join(_log_tail(w) for w in procs))

    try:
        # Phase 1: one worker, world of 1 (4 devices).
        procs["w-a"] = _spawn_worker("w-a", config, tmp_path)
        supervise_until(
            lambda: servicer.JobStatus({})["done"] >= 2
            and rendezvous.membership()["world_size"] == 1,
            deadline_s=120,
        )

        # Phase 2: scale up — second process joins; both must re-form into
        # one 2-process world (8 devices) and make lockstep progress.
        done_at_join = servicer.JobStatus({})["done"]
        procs["w-b"] = _spawn_worker("w-b", config, tmp_path)
        supervise_until(
            lambda: rendezvous.membership()["world_size"] == 2
            and servicer.JobStatus({})["done"] >= done_at_join + 2
            and servicer._group_version is not None,  # lockstep log active
            deadline_s=240,
        )

        # Phase 3: scale down — kill the joiner; the survivor restarts into
        # a world of 1 and the job drains to completion.
        procs.pop("w-b").send_signal(signal.SIGKILL)
        supervise_until(
            lambda: servicer.JobStatus({})["finished"], deadline_s=300
        )
        # the dead joiner was reaped out of the membership
        assert "w-b" not in rendezvous.membership()["workers"]
    finally:
        stop.set()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()


@pytest.mark.slow
def test_two_process_distributed_train_kill_resume(tmp_path):
    """The 2-process proof (VERDICT r2 next-round task 3): a real
    jax.distributed world of two worker PROCESSES (8-device global mesh)
    trains through the gRPC master in lockstep; killing one process evicts it
    via the heartbeat reaper, the survivor exits RESTART_EXIT_CODE (after
    snapshotting), and its relaunch finishes the job single-host from the
    snapshot."""
    from elasticdl_tpu.common.rpc import JsonRpcClient
    from elasticdl_tpu.master.servicer import MasterServer
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    path, _, shards = _shards(
        tmp_path, n_records=256, records_per_task=32, name="train.rio"
    )
    # Many epochs: a continuous task stream so the kill lands mid-training.
    dispatcher = TaskDispatcher(shards, num_epochs=6)
    rendezvous = RendezvousServer(heartbeat_timeout_s=6.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()

    stop = threading.Event()

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            time.sleep(0.25)

    reaper = threading.Thread(target=reap, daemon=True)
    reaper.start()

    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=_free_port(),
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=4,
        num_epochs=6,
    )

    procs: dict = {}
    relaunches = {"count": 0}

    def _log_tail(w):
        return _latest_log(tmp_path, w)[-3000:]

    def supervise_until(cond, deadline_s, max_relaunch=8):
        """Emulate the PodManager: relaunch membership-driven exits — rc=3
        (graceful RESTART) and jax.distributed runtime fatals (a peer's
        restart kills everyone attached to its coordinator).  Any other exit
        is a real failure."""
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if cond():
                return
            for w, p in list(procs.items()):
                rc = p.poll()
                if rc is None:
                    continue
                runtime_fatal = (
                    "JAX distributed service detected fatal errors"
                    in _log_tail(w)
                )
                if rc == RESTART_EXIT_CODE or runtime_fatal:
                    assert relaunches["count"] < max_relaunch, (
                        f"{w} restart churn; log:\n" + _log_tail(w)
                    )
                    relaunches["count"] += 1
                    procs[w] = _spawn_worker(w, config, tmp_path)
                else:
                    pytest.fail(f"{w} exited rc={rc}; log:\n" + _log_tail(w))
            time.sleep(0.5)
        pytest.fail(
            "condition not reached; logs:\n"
            + "".join(_log_tail(w) for w in procs)
        )

    try:
        procs.update(
            {w: _spawn_worker(w, config, tmp_path) for w in ("w-a", "w-b")}
        )
        client = JsonRpcClient(server.address)
        client.wait_ready(30)

        # Phase 1: lockstep training demonstrably progresses with world=2.
        supervise_until(
            lambda: servicer.JobStatus({})["done"] >= 4
            and servicer.rendezvous.membership()["world_size"] == 2,
            deadline_s=240,
        )

        # Phase 2: kill one process.  The survivor must notice (heartbeat
        # version bump or a collective error), snapshot, and exit
        # RESTART_EXIT_CODE.
        procs.pop("w-b").send_signal(signal.SIGKILL)
        survivor = procs["w-a"]
        try:
            rc = survivor.wait(timeout=150)
        except subprocess.TimeoutExpired:  # pragma: no cover - belt & braces
            # Production's pod liveness probe would reap a fully wedged
            # survivor; the resume path below is identical either way.
            survivor.kill()
            survivor.wait(timeout=10)
            rc = None
        # Two legitimate terminations: (a) the kill landed between tasks —
        # the heartbeat reaper bumps the version and the survivor exits
        # RESTART_EXIT_CODE gracefully; (b) the kill landed mid-collective
        # (or mid checkpoint barrier) — the survivor wedges inside the op
        # until the jax.distributed coordination service declares the peer
        # unhealthy and fatally terminates the process ("Terminating
        # process because the JAX distributed service detected fatal
        # errors").  Both are "peer loss detected"; a clean exit or an
        # unhandled Python error without the fatal marker is a real failure.
        runtime_fatal = (
            "JAX distributed service detected fatal errors" in _log_tail("w-a")
        )
        assert rc in (RESTART_EXIT_CODE, None) or runtime_fatal, (
            f"survivor exited {rc}, log:\n" + _log_tail("w-a")
        )
        done_before = servicer.JobStatus({})["done"]
        # The periodic (collective) checkpoints were reported along the way;
        # the relaunch resumes from them.
        assert servicer.GetCheckpoint({})["path"], "no checkpoint reported"

        # Phase 3: the relaunched worker (now a world of 1, single-host mode)
        # resumes and drains the job.
        procs["w-a"] = _spawn_worker("w-a", config, tmp_path)
        supervise_until(
            lambda: servicer.JobStatus({})["finished"], deadline_s=300
        )
        rc2 = procs["w-a"].wait(timeout=60)
        assert rc2 == 0, f"relaunched worker rc={rc2}; log:\n" + _log_tail("w-a")
        assert servicer.JobStatus({})["done"] > done_before
    finally:
        stop.set()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()


def _supervise(procs, spawn, cond, deadline_s, log_tail,
               max_relaunch=8):
    """Shared supervision loop: emulate the PodManager by relaunching
    membership-driven exits (RESTART_EXIT_CODE / jax.distributed runtime
    fatals), treating rc=0 as a clean retirement and anything else as a
    test failure.  Returns when ``cond()`` holds."""
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    relaunches = 0
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        if cond():
            return
        for w, p in list(procs.items()):
            rc = p.poll()
            if rc is None:
                continue
            if rc == 0:
                procs.pop(w)
                continue
            fatal = (
                "JAX distributed service detected fatal errors"
                in log_tail(w)
            )
            if rc == RESTART_EXIT_CODE or fatal:
                assert relaunches < max_relaunch, (
                    f"{w} restart churn; log:\n" + log_tail(w)
                )
                relaunches += 1
                procs[w] = spawn(w)
            else:
                pytest.fail(f"{w} exited rc={rc}; log:\n" + log_tail(w))
        time.sleep(0.5)
    pytest.fail("condition not reached; logs:\n"
                + "".join(log_tail(w) for w in list(procs)))


@pytest.mark.slow
def test_two_process_hierarchical_mesh_trains(tmp_path):
    """The hierarchical mesh's flagship layout, proven with REAL processes:
    dcn_data_parallelism=2 over a 2-process jax.distributed world puts the
    dp axis exactly on the PROCESS boundary (each process contributes one
    4-device ep slice) — gradient psums cross processes, collectives inside
    a step stay within each process's devices.  Lockstep progress must
    happen AT world=2 (a long task stream keeps a faster-booting worker from
    draining the job solo), and no worker may have fallen back to a flat
    mesh."""
    path, _, shards = _shards(
        tmp_path, n_records=256, records_per_task=32, name="train.rio"
    )
    dispatcher = TaskDispatcher(shards, num_epochs=60)  # continuous stream
    rendezvous = RendezvousServer(heartbeat_timeout_s=6.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    from elasticdl_tpu.master.servicer import MasterServer

    server = MasterServer(servicer, port=0).start()
    stop = threading.Event()

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            time.sleep(0.25)

    threading.Thread(target=reap, daemon=True).start()

    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=_free_port(),
        num_epochs=60,
        dcn_data_parallelism=2,
    )
    procs = {}

    def _log_tail(w):
        return _latest_log(tmp_path, w)[-3000:]

    def _full_log(w):
        return _all_logs(tmp_path, w)

    try:
        procs.update(
            {w: _spawn_worker(w, config, tmp_path) for w in ("w-a", "w-b")}
        )
        # The PROOF condition: tasks complete while the world is 2 and the
        # lockstep log is live — progress made BY the hierarchical layout.
        done_floor = {"at2": None}

        def lockstep_progress():
            if rendezvous.membership()["world_size"] != 2:
                return False
            done = servicer.JobStatus({})["done"]
            if done_floor["at2"] is None:
                done_floor["at2"] = done
                return False
            return done >= done_floor["at2"] + 4

        _supervise(
            procs, lambda w: _spawn_worker(w, config, tmp_path),
            lockstep_progress, deadline_s=300, log_tail=_log_tail,
        )
        # The hierarchical mesh really ran: search the WHOLE log of BOTH
        # workers, every incarnation (append-mode logs; a retired rc=0
        # worker must be checked too).
        for w in ("w-a", "w-b"):
            assert "falling back to a flat 1-D mesh" not in _full_log(w)
    finally:
        stop.set()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()


# ---------------------------------------------------------------------------
# 4. r6 gang-mode hot-path parity: prep-ahead pipelining + non-blocking
#    group checkpoints
# ---------------------------------------------------------------------------


def _lockstep_pair(tmp_path, devices, reader, servicer, **cfg_kwargs):
    """Two in-process group-mode workers over one servicer, both registered
    up front (the test_two_workers_lockstep_in_process harness)."""
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    config = JobConfig(
        model_def="mnist.model_spec",
        minibatch_size=16,
        multihost=True,
        **cfg_kwargs,
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    memberships = {
        w: servicer.RegisterWorker({"worker_id": w}) for w in ("w-a", "w-b")
    }
    memberships["w-a"] = memberships["w-b"]  # both hold the final view
    workers = {
        w: Worker(
            config, DirectMasterProxy(servicer), reader,
            worker_id=w, spec=spec, devices=devices,
        )
        for w in ("w-a", "w-b")
    }
    return workers, memberships


def _run_pair(workers, memberships):
    results, errors = {}, {}

    def run(w):
        try:
            results[w] = workers[w].run(membership=memberships[w])
        except Exception as e:  # pragma: no cover - surfaced by asserts
            errors[w] = e

    threads = [threading.Thread(target=run, args=(w,)) for w in workers]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 120  # ONE bound for the pair: a hang costs it once
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    assert not errors, errors
    return results


def test_group_prep_ahead_pipelined_lockstep(tmp_path, devices):
    """r6 tentpole: with the ``not _group_mode`` gate lifted, lockstep
    workers run the prep-ahead pipeline — every task's host decode/stack
    happens on the background prep thread, while its DISPATCH stays inside
    the lockstep boundary (both members dispatch the identical task order,
    every dispatch carrying a prepped payload)."""
    path, reader, shards = _shards(tmp_path)
    servicer = MasterServicer(TaskDispatcher(shards))
    workers, memberships = _lockstep_pair(
        tmp_path, devices, reader, servicer,
        training_data=path, fused_task_scan=True, task_pipelining=True,
    )

    prep_threads = {w: [] for w in workers}
    dispatch_order = {w: [] for w in workers}
    for w, worker in workers.items():
        orig_prep = worker._prep_fused_host
        orig_dispatch = worker._dispatch_training_task

        def spy_prep(task, _w=w, _orig=orig_prep):
            prep_threads[_w].append(threading.current_thread().name)
            return _orig(task)

        def spy_dispatch(task, prep=None, _w=w, _orig=orig_dispatch):
            dispatch_order[_w].append((task.task_id, prep is not None))
            return _orig(task, prep=prep)

        worker._prep_fused_host = spy_prep
        worker._dispatch_training_task = spy_dispatch

    results = _run_pair(workers, memberships)
    assert results["w-a"]["tasks_done"] == results["w-b"]["tasks_done"] == 4
    assert servicer.dispatcher.counts()["done"] == 4  # exactly one report
    assert servicer.dispatcher.finished()
    for w, worker in workers.items():
        assert worker._group_mode, w
        # the gate is gone: pipelining reports enabled in group mode
        assert worker._pipelining_enabled(), w
        # prep ran, and ran on the background prep thread
        assert len(prep_threads[w]) == 4, (w, prep_threads)
        assert all(n.startswith("edl-prep") for n in prep_threads[w]), (
            w, prep_threads,
        )
        # every dispatch consumed a prepped payload
        assert all(had_prep for _, had_prep in dispatch_order[w]), (
            w, dispatch_order,
        )
    # lockstep boundary: both members dispatched the identical task order
    assert dispatch_order["w-a"] == dispatch_order["w-b"]
    # EVERY rank's phase snapshot reaches the master: rank 0's rides its
    # reports, the other rank's rides the heartbeat (reports are
    # rank-0-gated) — a straggler rank must be visible per-worker
    status = servicer.JobStatus({})
    assert set(status["phase_times"]) == {"w-a", "w-b"}
    for w in ("w-a", "w-b"):
        assert status["phase_times"][w].get("dispatch", 0) > 0.0, w


def test_group_prep_drained_on_preemption(tmp_path, devices):
    """A group worker parking for preemption must hand its undispatched
    prepped task back to the master (failure report -> requeue), not hold
    it across the restart — and it must acknowledge the park BEFORE paying
    the abandon RPC (a slow master must not consume the snapshot window)."""
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import (
        DirectMasterProxy,
        Worker,
        WorkerRestartRequired,
    )

    path, reader, shards = _shards(tmp_path)
    dispatcher = TaskDispatcher(shards)
    servicer = MasterServicer(dispatcher)
    config = JobConfig(
        model_def="mnist.model_spec", training_data=path, minibatch_size=16,
        multihost=True, fused_task_scan=True, task_pipelining=True,
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    # Gang of two, but only w-b's loop runs — w-a is a confirmed phantom
    # peer (the lockstep log issues tasks once every member confirmed), so
    # the test observes the abandon without paying a full-job drain.
    servicer.RegisterWorker({"worker_id": "w-a"})
    membership = servicer.RegisterWorker({"worker_id": "w-b"})
    servicer.Heartbeat({"worker_id": "w-a", "version": membership["version"]})
    target = Worker(
        config, DirectMasterProxy(servicer), reader,
        worker_id="w-b", spec=spec, devices=devices,
    )
    seen = {"parked_at_abandon": None, "abandoned_task": None}
    orig_call = target.master.call

    def spy_call(method, payload=None, **kw):
        if (
            method == "ReportTaskResult"
            and payload is not None
            and not payload.get("success", True)
            and seen["abandoned_task"] is None
        ):
            seen["parked_at_abandon"] = target._parked
            seen["abandoned_task"] = payload["task_id"]
        resp = orig_call(method, payload, **kw)
        # Preempt as soon as a prepped-but-undispatched task exists: the
        # NEXT loop iteration must park and abandon it.
        if target._prep_queue and not target._preempting:
            target._preempting = True
        return resp

    target.master.call = spy_call
    errors = {}

    def run_target():
        try:
            target.run(membership=membership)
        except Exception as e:
            errors["w-b"] = e

    t_b = threading.Thread(target=run_target)
    t_b.start()
    deadline = time.time() + 90
    while time.time() < deadline and seen["abandoned_task"] is None:
        time.sleep(0.05)
    assert seen["abandoned_task"] is not None, "prep never abandoned"
    # the park was acknowledged BEFORE the (potentially slow) abandon RPC
    assert seen["parked_at_abandon"] is True
    # the abandoned task went straight back to the todo queue
    assert dispatcher.counts()["todo"] >= 1
    # end the run without draining the job: un-park, then bump the
    # membership — the next membership check restarts the worker
    servicer.RegisterWorker({"worker_id": "w-c"})
    target._preempting = False
    t_b.join(timeout=60)
    assert isinstance(errors.get("w-b"), WorkerRestartRequired), errors


#: Orbax numbers a process's save operations with ONE process-wide counter
#: (``synchronization.OperationIdGenerator``) and a handler waits for the
#: directory-creation signal of "the current" one.  A real rank has one
#: manager; this harness puts two ranks' managers in one process, and where
#: their saves overlap one handler takes the other's signal for its own and
#: writes into a temporary directory that its own creation then finds
#: existing (``FileExistsError``, or a wait for a signal nobody sends: D13's
#: two in seven red runs and its two 125 s hangs).  One save at a time ACROSS
#: the emulated ranks keeps the harness to what two processes would do; a
#: worker's own saves are ordered by the worker (what these cases are about).
_ONE_ORBAX_OPERATION_A_PROCESS = threading.Lock()


def _spied_checkpoint_managers(tmp_path, workers, before_save=None):
    """A ``CheckpointManager`` a worker under ``tmp_path`` (per-worker
    directories: two managers racing one directory would test the filesystem,
    not the worker), each ``save`` recorded as ``(thread name, step)``;
    ``before_save(w, thread_name, step)`` runs first, outside the lock."""
    from elasticdl_tpu.common.checkpoint import CheckpointManager

    saves = {w: [] for w in workers}
    for w, worker in workers.items():
        worker._ckpt = CheckpointManager(str(tmp_path / f"ckpt_{w}"))
        orig_save = worker._ckpt.save

        def spy_save(step, state, wait=False, _w=w, _orig=orig_save):
            name = threading.current_thread().name
            saves[_w].append((name, int(step)))
            if before_save is not None:
                before_save(_w, name, int(step))
            with _ONE_ORBAX_OPERATION_A_PROCESS:
                return _orig(step, state, wait=wait)

        worker._ckpt.save = spy_save
    return saves


def test_group_checkpoint_nonblocking(tmp_path, devices):
    """r6 tentpole: the group-mode periodic checkpoint pays only the
    device-side snapshot at the lockstep boundary — the shard write runs on
    the background checkpoint thread on EVERY rank, completes durably, and
    the job-end final save settles any in-flight background save first."""
    path, reader, shards = _shards(tmp_path, n_records=128)
    servicer = MasterServicer(TaskDispatcher(shards))
    # 8 steps, a boundary every 3: steps 3 and 6 in the background, and the
    # job ends OFF a boundary, so step 8 is the final save's alone (a job
    # that ends ON one is the next case's)
    workers, memberships = _lockstep_pair(
        tmp_path, devices, reader, servicer,
        training_data=path, checkpoint_steps=3,
    )
    save_threads = _spied_checkpoint_managers(tmp_path, workers)

    results = _run_pair(workers, memberships)
    assert results["w-a"]["tasks_done"] == results["w-b"]["tasks_done"] == 8
    # the boundary cost and the background write are split in the phase
    # decomposition: checkpoint (snapshot + joins) on the critical path,
    # checkpoint_bg (write + commit) off it
    for w in workers:
        assert results[w]["phase_times"].get("checkpoint", 0) > 0.0, w
        assert results[w]["phase_times"].get("checkpoint_bg", 0) > 0.0, w
    for w, worker in workers.items():
        names = [n for n, _ in save_threads[w]]
        # every periodic save ran OFF the task loop, on the background
        # checkpoint thread — every rank participates (collective saves)
        assert [n.startswith("edl-ckpt") for n in names] == [True, True, False], (w, save_threads)
        # the job-end final save runs ON the worker thread, after joining
        # the in-flight background save; every step was written once
        assert [step for _, step in save_threads[w]] == [3, 6, 8], (w, save_threads)
        # background saves completed durably
        assert worker._ckpt.all_steps() == [8, 6, 3], w
        worker._ckpt.close()


@pytest.mark.parametrize("background_save", ["commits", "fails"])
def test_a_job_that_ends_on_a_checkpoint_boundary_writes_that_step_once_and_ends_restorable(
    tmp_path, devices, monkeypatch, background_save
):
    """D13: the job ends at step 8 while the background save OF step 8 is
    still open — the order is forced (the save's thread is held until the
    task loop stands in the job-end join), not slept for.  Where that save
    commits, the job-end block writes nothing again: one save a step.  Where
    it FAILS (a group save keeps its watermark, so the watermark says
    "saved"; Orbax keeps the failure and raises it out of the next call of
    every thread that has not seen it), the job-end save still runs, is not
    failed by the old failure, and the job ends with step 8 on disk."""
    import jax

    path, reader, shards = _shards(tmp_path, n_records=128)
    servicer = MasterServicer(TaskDispatcher(shards))
    workers, memberships = _lockstep_pair(
        tmp_path, devices, reader, servicer,
        training_data=path, checkpoint_steps=2,
    )
    opened = {w: threading.Event() for w in workers}
    job_end = {w: threading.Event() for w in workers}

    def before_save(w, thread_name, step):
        if step == 8 and thread_name.startswith("edl-ckpt"):
            opened[w].set()
            assert job_end[w].wait(60), "the task loop never reached the job-end join"

    saves = _spied_checkpoint_managers(tmp_path, workers, before_save)
    for w, worker in workers.items():
        orig_join = worker._join_ckpt

        def spy_join(timeout=None, _w=w, _worker=worker, _orig=orig_join):
            with _worker._ckpt_lock:
                at_job_end = _worker._last_ckpt_step == 8  # the watermark moves AFTER the join that precedes step 8's save
            if at_job_end:
                assert opened[_w].wait(60), "step 8's background save never opened"
                job_end[_w].set()
            return _orig(timeout)

        worker._join_ckpt = spy_join

    torn = set()  # a rank's step 8 fails ONCE, at its commit (the rename of the temporary directory): the retry goes through
    rename = os.rename

    def rename_that_tears_step_8_once(src, dst, *args, **kwargs):
        if str(src).endswith("8.orbax-checkpoint-tmp") and str(src) not in torn:
            torn.add(str(src))
            raise OSError(5, "Input/output error", str(src))
        return rename(src, dst, *args, **kwargs)

    if background_save == "fails":
        monkeypatch.setattr(os, "rename", rename_that_tears_step_8_once)

    results = _run_pair(workers, memberships)
    assert results["w-a"]["tasks_done"] == results["w-b"]["tasks_done"] == 8
    assert len(torn) == (2 if background_save == "fails" else 0)
    for w, worker in workers.items():
        in_background = [step for name, step in saves[w] if name.startswith("edl-ckpt")]
        at_job_end = [step for name, step in saves[w] if not name.startswith("edl-ckpt")]
        assert in_background == [2, 4, 6, 8], (w, saves)
        # one save a step where the first committed; the retry, on the task loop's thread, where it did not
        assert at_job_end == ([] if background_save == "commits" else [8]), (w, saves)
        assert worker._ckpt.latest_step() == 8, (w, worker._ckpt.all_steps())
        restored = worker._ckpt.restore(worker.trainer.snapshot_state(worker.state), step=8)
        assert int(restored.step) == 8
        assert all(
            bool((a == b).all())
            for a, b in zip(jax.tree.leaves(restored.params), jax.tree.leaves(worker.state.params))
        ), w
        worker._ckpt.close()


def test_group_inflight_save_settles_before_preemption_exit(tmp_path, devices):
    """A group worker's preemption path never solo-saves, but it must JOIN
    an in-flight background collective save before the process exit can
    tear it (bounded by the grace window)."""
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    path, reader, shards = _shards(tmp_path)
    servicer = MasterServicer(TaskDispatcher(shards))
    config = JobConfig(
        model_def="mnist.model_spec", training_data=path, minibatch_size=16,
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    worker = Worker(
        config, DirectMasterProxy(servicer), reader,
        worker_id="w-a", spec=spec, devices=devices,
    )
    worker._group_mode = True  # the preemption path's group branch
    done = {"t": None}

    def slow_save():
        time.sleep(0.5)
        done["t"] = time.monotonic()

    t = threading.Thread(target=slow_save, name="edl-ckpt")
    worker._ckpt_thread = t
    t.start()
    assert worker.preemption_snapshot() is False  # group mode never solo-saves
    t_return = time.monotonic()
    assert done["t"] is not None, "preemption exit did not join the save"
    assert t_return >= done["t"]
