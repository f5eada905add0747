"""Measure REAL-PROCESS elastic re-rendezvous latency (VERDICT r3 item 6).

tools/elastic_bench.py times the in-process resize (mesh re-form + restore +
recompile: 0.39-2.13 s).  Production takes the other path: a peer dies, the
survivor snapshots and exits RESTART_EXIT_CODE, the pod manager relaunches
it, the fresh process re-initializes jax.distributed in the new world,
restores the checkpoint, and trains.  This tool runs that exact sequence
with real worker processes on the localhost harness (2 procs x 4 fake CPU
devices — the latency measured is control-plane + process-boot + re-init +
restore work, none of which runs on the accelerator) and reports each
phase:

  kill -> eviction        heartbeat reaper notices the dead peer
  eviction -> restart     survivor snapshots + exits RESTART_EXIT_CODE
  restart -> first step   relaunch, process boot (python + jax import),
                          jax.distributed re-init, checkpoint restore,
                          recompile, first post-change task completes

Prints ONE JSON line with the phase split and total, and writes the same
dict (plus timestamp + command) to ``artifacts/rendezvous_r05.json`` — the
number of record docs/perf.md quotes (override the path with the
``RDZV_BENCH_OUT`` env var).
Usage: python tools/rendezvous_bench.py
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# FORCE cpu (not setdefault): this is a CPU harness, and an inherited
# JAX_PLATFORMS (or none, on a TPU host) would aim it at the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")


def _free_port() -> int:
    # common.platform is jax-free: this master process never imports jax.
    from elasticdl_tpu.common.platform import free_port

    return free_port()


def _worker_env(config):
    env = dict(os.environ)
    env.update(config.to_env())
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    return env


_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn_worker(worker_id, config, log_dir, incarnation):
    env = _worker_env(config)
    env["ELASTICDL_WORKER_ID"] = worker_id
    log = open(os.path.join(log_dir, f"{worker_id}.log.{incarnation}"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.worker.main"],
        env=env, stdout=log, stderr=subprocess.STDOUT, cwd=_REPO_ROOT,
    )


def _spawn_standby(config, log_dir, tag):
    """Park a warm spare (worker.main standby mode): imports paid up front,
    adopted later by writing its go-file — the production mechanism
    (ProcessPodBackend warm_standby), spawned directly here so the bench
    keeps per-incarnation log capture."""
    env = _worker_env(config)
    go_file = os.path.join(log_dir, f"standby.go.{tag}")
    env["ELASTICDL_STANDBY_GO_FILE"] = go_file
    log = open(os.path.join(log_dir, f"standby.log.{tag}"), "w")
    proc = subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.worker.main"],
        env=env, stdout=log, stderr=subprocess.STDOUT, cwd=_REPO_ROOT,
    )
    return proc, go_file


def _adopt_standby(proc, go_file, worker_id):
    from elasticdl_tpu.common import durable

    durable.atomic_publish_json(go_file, {"worker_id": worker_id, "env": {}})
    return proc


def main() -> None:
    import tempfile

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    tmp = tempfile.mkdtemp(prefix="rdzv_bench_")
    path = os.path.join(tmp, "train.rio")
    generate("mnist", path, 256)
    shards = create_data_reader(path).create_shards(32)
    dispatcher = TaskDispatcher(shards, num_epochs=200)
    rendezvous = RendezvousServer(heartbeat_timeout_s=3.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()
    stop = threading.Event()

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            time.sleep(0.1)

    threading.Thread(target=reap, daemon=True).start()

    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=_free_port(),
        checkpoint_dir=os.path.join(tmp, "ckpt"),
        checkpoint_steps=4,
        num_epochs=200,
        # The dedicated-host setting (docs/perf.md): this bench measures the
        # best-tuned path; the shipped default is a starvation-tolerant 30 s.
        distributed_heartbeat_timeout_s=10.0,
    )

    def wait_for(cond, deadline_s, what):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if cond():
                return time.time()
            time.sleep(0.02)
        raise RuntimeError(f"timed out waiting for {what}")

    log = lambda m: print(f"[rdzv] {m}", file=sys.stderr, flush=True)
    procs = {}
    standby = None
    try:
        procs["w-a"] = _spawn_worker("w-a", config, tmp, 0)
        procs["w-b"] = _spawn_worker("w-b", config, tmp, 0)
        # Park the warm spare while the world is healthy — exactly when the
        # ProcessPodBackend would (start_pod spawns the replacement spare).
        standby = _spawn_standby(config, tmp, "0")
        wait_for(
            lambda: rendezvous.membership()["world_size"] == 2
            and servicer.JobStatus({})["done"] >= 2,
            240, "2-process world making progress",
        )
        log("2-process world training; killing w-b")

        version0 = rendezvous.membership()["version"]
        t_kill = time.time()
        procs.pop("w-b").send_signal(signal.SIGKILL)

        t_evict = wait_for(
            lambda: rendezvous.membership()["version"] != version0
            and "w-b" not in rendezvous.membership()["workers"],
            60, "heartbeat eviction",
        )
        log(f"evicted after {t_evict - t_kill:.2f}s")

        def survivor_exited():
            rc = procs["w-a"].poll()
            if rc is None:
                return False
            if rc == RESTART_EXIT_CODE:
                return True
            # The jax.distributed runtime may abort the survivor itself
            # ("fatal errors ... another task died") before our graceful
            # RESTART path runs — the pod manager treats that marker as
            # relaunchable too (same classification as test_multihost).
            tail = open(os.path.join(tmp, "w-a.log.0")).read()[-4000:]
            if "JAX distributed service detected fatal errors" in tail:
                return True
            raise RuntimeError(f"survivor died rc={rc}:\n{tail[-2000:]}")

        t_restart = wait_for(survivor_exited, 120, "survivor exit")
        exit_kind = (
            "RESTART" if procs["w-a"].poll() == RESTART_EXIT_CODE else "fatal"
        )
        log(f"survivor exit ({exit_kind}) after {t_restart - t_evict:.2f}s")

        done_before = servicer.JobStatus({})["done"]
        # Relaunch by ADOPTING the warm spare (its python + jax imports are
        # already paid); fall back to a cold spawn if it died while parked.
        warm = standby is not None and standby[0].poll() is None
        if warm:
            procs["w-a"] = _adopt_standby(*standby, "w-a")
            standby = None
        else:
            procs["w-a"] = _spawn_worker("w-a", config, tmp, 1)
        t_first = wait_for(
            lambda: servicer.JobStatus({})["done"] > done_before
            and rendezvous.membership()["world_size"] == 1,
            240, "first post-restart task",
        )
        log(f"relaunch -> first completed task {t_first - t_restart:.2f}s "
            f"({'warm standby' if warm else 'cold spawn'})")

        result = {
            "metric": "real_process_re_rendezvous_s",
            "kill_to_eviction_s": round(t_evict - t_kill, 2),
            "eviction_to_restart_exit_s": round(t_restart - t_evict, 2),
            "relaunch_to_first_task_s": round(t_first - t_restart, 2),
            "total_s": round(t_first - t_kill, 2),
            "survivor_exit": exit_kind,
            "warm_standby": warm,
            "death_push_grace_s": config.death_push_grace_s,
            "heartbeat_timeout_s": 3.0,
            "note": "first task = relaunch (warm: restore+recompile only; "
                    "cold: + python/jax import) + distributed re-init + one "
                    "full task (2 steps)",
        }
        print(json.dumps(result), flush=True)
        from tools.artifact import write_artifact

        write_artifact(
            result, "rendezvous_r05.json", env_var="RDZV_BENCH_OUT", log=log
        )
    finally:
        stop.set()
        if standby is not None and standby[0].poll() is None:
            standby[0].kill()
        for p in procs.values():
            if p.poll() is None:
                p.kill()
        server.stop()


def main_pod() -> None:
    """Scenario B — the PRODUCTION detection + recovery path.

    Scenario A (``main``) measures the heartbeat-evicted degrade-to-1 path
    with hand-spawned processes.  Here the fleet runs under the real
    ``PodManager`` + ``ProcessPodBackend(warm_standby=True)`` exactly as
    ``elasticdl train`` wires it: the backend's watcher turns the SIGKILL
    into a FAILED pod event in ~a poll interval (0.2 s) — no heartbeat
    wait — the listener cascades it into the rendezvous eviction, the
    manager relaunches the slot (adopting the warm spare), the survivor's
    death push restarts it into the new world, and the job is RECOVERED
    when the 2-process world is training again.  Artifact:
    ``artifacts/rendezvous_pod_r05.json``.
    """
    import tempfile

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.master.pod_manager import (
        PodManager,
        PodPhase,
        ProcessPodBackend,
    )
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    tmp = tempfile.mkdtemp(prefix="rdzv_pod_")
    path = os.path.join(tmp, "train.rio")
    generate("mnist", path, 256)
    shards = create_data_reader(path).create_shards(32)
    dispatcher = TaskDispatcher(shards, num_epochs=500)
    rendezvous = RendezvousServer(heartbeat_timeout_s=3.0)
    rendezvous.set_expected(2)  # as Master.run does before starting pods
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()
    stop = threading.Event()

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            time.sleep(0.1)

    threading.Thread(target=reap, daemon=True).start()

    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=_free_port(),
        checkpoint_dir=os.path.join(tmp, "ckpt"),
        checkpoint_steps=4,
        num_epochs=500,
        num_workers=2,
        warm_worker_standby=True,
        distributed_heartbeat_timeout_s=10.0,
    )
    # Pool of 2: a peer-death recovery relaunches the dead pod AND the
    # survivor (its RESTART exit) — both should boot warm.
    backend = ProcessPodBackend(warm_standby=True, standby_pool=2, log_dir=tmp)
    manager = PodManager(
        backend,
        config,
        worker_env={
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
        },
    )
    # master/main.py's wiring: terminal pod -> rendezvous eviction.
    manager.add_listener(
        lambda name, phase: rendezvous.remove(name)
        if phase in PodPhase.TERMINAL
        else None
    )

    def wait_for(cond, deadline_s, what):
        deadline = time.time() + deadline_s
        while time.time() < deadline:
            if cond():
                return time.time()
            time.sleep(0.02)
        raise RuntimeError(f"timed out waiting for {what}")

    log = lambda m: print(f"[rdzv-pod] {m}", file=sys.stderr, flush=True)
    try:
        manager.start(2)
        wait_for(
            lambda: rendezvous.membership()["world_size"] == 2
            and servicer.JobStatus({})["done"] >= 2,
            300, "2-pod world making progress",
        )
        victim = manager.live_pods()[-1]
        pid = backend.pid(victim)
        version0 = rendezvous.membership()["version"]
        log(f"2-pod world training; SIGKILL {victim} (pid {pid})")
        t_kill = time.time()
        os.kill(pid, signal.SIGKILL)

        t_evict = wait_for(
            lambda: rendezvous.membership()["version"] != version0
            and victim not in rendezvous.membership()["workers"],
            60, "pod-event eviction",
        )
        log(f"evicted after {t_evict - t_kill:.2f}s (pod event, not heartbeat)")

        done_mark = servicer.JobStatus({})["done"]
        t_rec = wait_for(
            lambda: rendezvous.membership()["world_size"] == 2
            and servicer.JobStatus({})["done"] > done_mark,
            240, "2-process world training again",
        )
        log(f"full fleet recovered {t_rec - t_evict:.2f}s after eviction")

        result = {
            "metric": "pod_event_full_recovery_s",
            "kill_to_eviction_s": round(t_evict - t_kill, 2),
            "eviction_to_recovered_s": round(t_rec - t_evict, 2),
            "total_s": round(t_rec - t_kill, 2),
            "note": "PodManager + ProcessPodBackend(warm_standby) fleet; "
                    "eviction = backend watcher FAILED event (poll 0.2s), "
                    "recovered = 2-process world completing tasks again "
                    "(one relaunch adopts the warm spare, the peer's "
                    "RESTART relaunch follows)",
        }
        print(json.dumps(result), flush=True)
        from tools.artifact import write_artifact

        write_artifact(
            result, "rendezvous_pod_r05.json", env_var="RDZV_POD_BENCH_OUT",
            log=log,
        )
    finally:
        stop.set()
        manager.stop()
        server.stop()


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "pod":
        main_pod()
    else:
        main()
