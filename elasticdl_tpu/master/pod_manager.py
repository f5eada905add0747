"""PodManager — the elasticity engine.

Reference parity (SURVEY.md §2 #4 [U — mount empty at survey time; capability
[D]: "worker preemption + scale 4→8→4" is a BASELINE.json config): the
reference's master watches Kubernetes pod events, relaunches failed worker
pods up to a restart budget, and honors scale-up/down requests; the
TaskDispatcher requeues a dead pod's tasks and the RendezvousServer bumps the
membership version so the collective re-forms.

TPU rebuild: the same slot/relaunch/scale state machine over a pluggable
``PodBackend``:

- ``FakePodBackend`` — in-memory, with test-injectable phase events (the
  reference's decisive mock-k8s unit-test pattern, SURVEY.md §4).
- ``ProcessPodBackend`` — local worker subprocesses (``python -m
  elasticdl_tpu.worker.main``), each one host of the job; exit code drives
  SUCCEEDED/FAILED events.  This is the no-cluster deployment used by the
  ``elasticdl train`` CLI's local mode and by chaos tests (kill -9 a worker).
- ``KubernetesPodBackend`` — renders TPU-pod manifests (``google.com/tpu``
  resources on a node pool selector) and drives them through the kubernetes
  client if one is installed; the manifest renderer is importable/testable
  without a cluster.

Pod death flows OUT of the manager through listeners (master main wires
``RendezvousServer.remove``, which cascades into task requeue via the
servicer's membership listener); it never reaches into dispatcher state
itself.
"""

from __future__ import annotations

import dataclasses
import os
import subprocess
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

from elasticdl_tpu.common import durable, locksan, racesan, trace
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("master.pod_manager")


class PodPhase:
    PENDING = "Pending"
    RUNNING = "Running"
    SUCCEEDED = "Succeeded"
    FAILED = "Failed"
    DELETED = "Deleted"
    # Worker asked to be restarted (multihost elastic re-join, exit code 3);
    # relaunched WITHOUT consuming the slot's failure budget.
    RESTART = "Restart"
    # An ADOPTED pod (a pre-restart orphan this master re-attached to,
    # r18) disappeared.  Its exit code is unknowable — it was never this
    # process's child — so the backend cannot tell a clean job-end exit
    # from a crash; PodManager._on_event resolves LOST to SUCCEEDED when
    # the job is already finished, else FAILED (relaunch path).  Never
    # reaches listeners unresolved.
    LOST = "Lost"

    TERMINAL = (SUCCEEDED, FAILED, DELETED, RESTART, LOST)


# Exit code the worker main uses to request a budget-free relaunch
# (worker.worker.RESTART_EXIT_CODE; duplicated to keep this module
# importable without jax).
WORKER_RESTART_EXIT_CODE = 3

#: The pod reattach registry's filename under checkpoint_dir (r18): the
#: ONE spelling Master's wiring, the whole-job-restart probe and the
#: masterfail bench all reference.
REGISTRY_FILENAME = "pod_registry.json"  # durable-file


def proc_cmdline(pid: int) -> Optional[str]:
    """Best-effort /proc cmdline fingerprint (None off-Linux or for a
    vanished pid): the pid-reuse guard for every registry-pid probe."""
    try:
        with open(f"/proc/{pid}/cmdline", "rb") as f:
            return (
                f.read().replace(b"\0", b" ").decode(errors="replace").strip()
            )
    except OSError:
        return None


def pid_alive(pid: int, cmdline: Optional[str] = None) -> bool:
    """THE pid-liveness probe for reattach decisions (r18) — one
    definition so the adoption check, the whole-job-restart probe and the
    bench cannot drift.  ``kill(pid, 0)`` alone lies twice: a ZOMBIE
    (exited, unreaped) still answers it, and a RECYCLED pid answers for a
    stranger.  /proc state 'Z' filters the first (best-effort; off-Linux
    the zombie case cannot arise for the processes this guards — adopted
    orphans reparent to init and are reaped there); a ``cmdline``
    fingerprint, when the caller recorded one, filters the second."""
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    try:
        with open(f"/proc/{pid}/stat") as f:
            # Field 3 (after the parenthesized comm, which may itself
            # contain spaces): the process state.
            state = f.read().rpartition(")")[2].split()[0]
        if state == "Z":
            return False
    except (OSError, IndexError):
        pass  # no /proc: fall through to the kill(0) verdict
    if cmdline:
        have = proc_cmdline(pid)
        if have is not None and have != cmdline:
            return False  # pid recycled by an unrelated process
    return True


@dataclasses.dataclass
class PodInfo:
    name: str
    slot: int
    phase: str = PodPhase.PENDING
    relaunches: int = 0  # relaunch generation of this slot
    #: When the backend's ``start_pod`` returned for this pod (epoch
    #: seconds on common/trace.py's clock; 0.0 until then): where the
    #: pod's own set-up chain begins (``setup:interp``).
    launched_at: float = 0.0


# Listener signature: fn(pod_name: str, phase: str)
PodListener = Callable[[str, str], None]


class PodBackend:
    """Starts/stops pods and reports phase transitions via a callback."""

    def set_event_callback(self, cb: PodListener) -> None:
        self._cb = cb

    def _emit(self, name: str, phase: str) -> None:
        cb = getattr(self, "_cb", None)
        if cb is not None:
            cb(name, phase)

    def start_pod(self, name: str, env: Dict[str, str]) -> None:
        raise NotImplementedError

    def delete_pod(self, name: str) -> None:
        raise NotImplementedError

    def close(self) -> None:
        pass


class FakePodBackend(PodBackend):
    """In-memory backend; tests inject pod events (mock-k8s pattern)."""

    def __init__(self, auto_run: bool = True):
        self.pods: Dict[str, str] = {}  # name -> phase; guarded-by: _lock
        self.start_log: List[str] = []  # guarded-by: _lock
        self._auto_run = auto_run
        self._lock = locksan.lock("FakePodBackend._lock", leaf=True)  # lock-order: leaf

    def start_pod(self, name: str, env: Dict[str, str]) -> None:
        with self._lock:
            self.pods[name] = PodPhase.PENDING
            self.start_log.append(name)
        if self._auto_run:
            self.set_phase(name, PodPhase.RUNNING)

    def delete_pod(self, name: str) -> None:
        self.set_phase(name, PodPhase.DELETED)

    # -- test injection --

    def set_phase(self, name: str, phase: str) -> None:
        with self._lock:
            if name not in self.pods or self.pods[name] == phase:
                return
            if self.pods[name] in PodPhase.TERMINAL:
                return  # terminal phases are final, as in k8s
            self.pods[name] = phase
        self._emit(name, phase)

    def fail_pod(self, name: str) -> None:
        self.set_phase(name, PodPhase.FAILED)

    def succeed_pod(self, name: str) -> None:
        self.set_phase(name, PodPhase.SUCCEEDED)

    def running(self) -> List[str]:
        with self._lock:
            return [n for n, p in self.pods.items() if p == PodPhase.RUNNING]


class ProcessPodBackend(PodBackend):
    """Worker pods as local subprocesses; a watcher thread maps exit codes to
    pod events.  ``argv`` defaults to the worker main module; the serving
    fleet controller (serving/fleet.py, r19) runs the SAME backend with
    ``argv=[..., "-m", "elasticdl_tpu.serving.main"]`` — replicas speak the
    identical standby/adoption env contract (ELASTICDL_WORKER_ID/SLOT +
    go-file), so spawn, warm standby, crash relaunch and the r18 reattach
    registry all carry over to serving without a parallel implementation.

    ``warm_standby=True`` keeps a small POOL of pre-booted spares parked:
    processes that have already paid python + jax + framework imports
    (about half of a re-rendezvous on the CPU harness, docs/perf.md) and
    wait on a go-file for their worker id (worker.main standby mode).
    ``start_pod`` adopts a spare when its environment matches and
    immediately refills the pool, so a relaunch boots in restore+compile
    time instead of import time.  ``standby_pool`` sizes it: 1 covers a
    lone failure; a peer-death recovery relaunches TWO processes (the dead
    pod plus the survivor's RESTART), so fleets that want both warm park 2.
    A failure burst beyond the pool falls back to cold spawns — spares are
    a latency optimization, never a correctness dependency.

    On a TPU host: every pod gets the launcher's environment and NO chip
    assignment, and a chip belongs to one process at a time, so the
    supported shape is one worker pod driving all local chips.  A parked
    spare has imported jax but opened no backend, which is what lets it wait
    beside the worker that holds the chip; it opens one only after
    adoption, when the pod it replaces has exited."""

    def __init__(
        self,
        argv: Optional[List[str]] = None,
        poll_interval_s: float = 0.2,
        inherit_env: bool = True,
        warm_standby: bool = False,
        standby_pool: int = 1,
        log_dir: Optional[str] = None,
    ):
        self._argv = argv or [sys.executable, "-m", "elasticdl_tpu.worker.main"]
        self._procs: Dict[str, subprocess.Popen] = {}  # guarded-by: _lock
        self._lock = locksan.lock("ProcessPodBackend._lock", leaf=True)  # lock-order: leaf
        self._poll = poll_interval_s
        self._inherit = inherit_env
        self._stop = threading.Event()
        self._watcher: Optional[threading.Thread] = None  # guarded-by: _lock
        self._warm = warm_standby
        self._pool_size = max(1, standby_pool)
        # Per-pod log capture (the process-backend analog of kubectl logs):
        # each pod's stdout+stderr goes to {log_dir}/{name}.log.  Pod names
        # are already unique per incarnation (PodManager's -rN suffix), so
        # no extra counter is needed.  None = inherit the parent's stdio.
        self._log_dir = log_dir
        # Parked spares: [(proc, go_file, env_signature)].
        self._standby: List[tuple] = []  # guarded-by: _lock
        self._standby_dir: Optional[str] = None  # guarded-by: _lock
        self._standby_seq = 0  # guarded-by: _lock
        # Adopted orphans (r18 master restart): name -> pid of a worker
        # process a PREVIOUS master spawned that this one re-attached to
        # (PodManager reattach registry).  Not our children — liveness is
        # kill(pid, 0) polling in the watcher, exit codes are unknowable
        # (PodPhase.LOST), teardown is signal-based.
        self._adopted: Dict[str, int] = {}  # guarded-by: _lock

    def _pod_stdio(self, name: str):
        if self._log_dir is None:
            return None
        os.makedirs(self._log_dir, exist_ok=True)
        return open(os.path.join(self._log_dir, f"{name}.log"), "w")

    #: Per-pod identity env: excluded from the spawn-time signature and
    #: delivered via the go file at adoption instead, so ONE spare serves a
    #: relaunch of ANY slot/id of the job (review r5: including
    #: ELASTICDL_WORKER_SLOT in the signature silently limited adoption to
    #: the last-started slot and churned the spare on every other launch).
    _IDENTITY_KEYS = ("ELASTICDL_WORKER_ID", "ELASTICDL_WORKER_SLOT")

    @classmethod
    def _env_sig(cls, full_env: Dict[str, str]) -> tuple:
        return tuple(
            sorted(
                (k, v)
                for k, v in full_env.items()
                if k not in cls._IDENTITY_KEYS + ("ELASTICDL_STANDBY_GO_FILE",)
            )
        )

    @staticmethod
    def _reap(proc) -> None:
        """wait() a killed process so it doesn't linger as a zombie."""
        try:
            proc.wait(timeout=5)
        except Exception:  # pragma: no cover — SIGKILL'd procs reap fast
            pass

    def _prune_spares_locked(self, sig) -> None:  # guarded-by: _lock
        """Drop dead spares; kill + drop spares whose job env changed."""
        keep = []
        for proc, go_file, s in self._standby:
            if proc.poll() is not None:
                continue
            if s != sig:
                proc.kill()
                self._reap(proc)
                continue
            keep.append((proc, go_file, s))
        self._standby = keep

    def _adopt_standby(self, name: str, full_env: Dict[str, str]):
        """Hand a parked spare its identity; None if no matching spare.

        Only a WARMED spare is adoptable: the standby writes a
        ``<go_file>.ready`` marker once its imports are paid (worker.main
        ``_park_as_standby``), and a spare still booting is skipped —
        adopting it would be a cold boot with extra moving parts, and the
        whole point of the pool is that the relaunch's wall is
        restore+compile, not imports.  Back-to-back failures beyond the
        warmed depth therefore degrade to cold spawns (and the pool
        refills behind them) — spares stay a latency optimization, never
        a correctness dependency."""
        sig = self._env_sig(full_env)
        with self._lock:
            self._prune_spares_locked(sig)
            chosen = None
            for i, (proc_i, go_i, _s) in enumerate(self._standby):
                if os.path.exists(go_i + ".ready"):
                    chosen = i
                    break
            if chosen is None:
                return None
            proc, go_file, _ = self._standby.pop(chosen)
        # Atomic publish: the standby polls for existence, so the content
        # must be complete the moment the path appears.
        payload = {
            "worker_id": name,
            "env": {
                k: full_env[k]
                for k in self._IDENTITY_KEYS
                if k in full_env and k != "ELASTICDL_WORKER_ID"
            },
        }
        durable.atomic_publish_json(go_file, payload)
        if self._log_dir is not None:
            # The spare's stdio was bound at spawn (it cannot be
            # redirected now); keep the per-pod-life log contract by
            # symlinking the pod name to the spare's file — the relaunch's
            # log is the one an operator needs most (review r5).
            spare_log = f"standby.{os.path.basename(go_file)}.log"
            link = os.path.join(self._log_dir, f"{name}.log")
            try:
                os.symlink(spare_log, link)
            except OSError:
                logger.warning("could not link %s -> %s", link, spare_log)
        logger.info("adopted warm standby (pid %d) as %s", proc.pid, name)
        # Two instants, one moment: the standby lifecycle event and the
        # splice-timeline stage recovery is decomposed over
        # (detect -> adopt -> reformed, docs/robustness.md).
        trace.instant("standby:adopt", cat="standby", pod=name, pid=proc.pid)
        trace.instant(
            "elastic:splice", cat="elastic", stage="adopt",
            pod=name, pid=proc.pid,
        )
        return proc

    def _fill_standby_pool(
        self, full_env: Dict[str, str], reason: str = "spawn"
    ) -> None:
        """Top the pool up to ``standby_pool`` live same-env spares.
        ``reason`` tags the lifecycle instant: ``spawn`` for the initial
        fill, ``refill`` when replacing an adopted spare."""
        import tempfile

        sig = self._env_sig(full_env)
        while True:
            with self._lock:
                if self._stop.is_set():
                    # close() may already have reaped the pool and removed
                    # the scratch dir; refilling now would park a fresh
                    # jax-loaded spare forever (the orphan self-reap only
                    # fires on parent-PID change, and the parent lives).
                    return
                self._prune_spares_locked(sig)
                if len(self._standby) >= self._pool_size:
                    return
                if self._standby_dir is None:
                    self._standby_dir = tempfile.mkdtemp(
                        prefix="edl_standby_"
                    )
                self._standby_seq += 1
                go_file = os.path.join(
                    self._standby_dir, f"go.{self._standby_seq}"
                )
            env = {
                k: v
                for k, v in full_env.items()
                if k not in self._IDENTITY_KEYS
            }
            env["ELASTICDL_STANDBY_GO_FILE"] = go_file
            log = self._pod_stdio(f"standby.{os.path.basename(go_file)}")
            try:
                proc = subprocess.Popen(
                    self._argv, env=env, stdout=log,
                    stderr=subprocess.STDOUT if log else None,
                )
            finally:
                if log is not None:
                    log.close()  # the child keeps its own fd
            with self._lock:
                # Popen ran outside the lock, so a concurrent start_pod
                # (scale() on the main thread racing a relaunch on the
                # watcher thread) may have topped the pool up meanwhile —
                # an over-full pool would orphan the extras (review r5).
                # Same for a concurrent close(): the spare must die, not
                # park in a scratch dir close() already removed.
                self._prune_spares_locked(sig)
                if self._stop.is_set() or len(self._standby) >= self._pool_size:
                    proc.kill()  # lost the race; pool full or closing
                    self._reap(proc)
                    return
                self._standby.append((proc, go_file, sig))
                depth = len(self._standby)
            logger.info("warm standby parked (pid %d)", proc.pid)
            trace.instant(
                f"standby:{reason}", cat="standby", pid=proc.pid, depth=depth
            )

    def start_pod(self, name: str, env: Dict[str, str]) -> None:
        full_env = dict(os.environ) if self._inherit else {}
        full_env.update(env)
        proc = self._adopt_standby(name, full_env) if self._warm else None
        adopted = proc is not None
        if proc is None:
            log = self._pod_stdio(name)
            try:
                proc = subprocess.Popen(
                    self._argv, env=full_env, stdout=log,
                    stderr=subprocess.STDOUT if log else None,
                )
            finally:
                if log is not None:
                    log.close()
        if self._warm:
            self._fill_standby_pool(
                full_env, reason="refill" if adopted else "spawn"
            )
        with self._lock:
            self._procs[name] = proc
            if self._watcher is None:
                self._watcher = threading.Thread(
                    target=self._watch, name="pod-watcher", daemon=True
                )
                self._watcher.start()
        self._emit(name, PodPhase.RUNNING)

    def adopt_pod(self, name: str, pid: int) -> None:
        """Re-attach to a live orphan of a previous master (r18 crash
        survivability): supervision continues — liveness via kill(0)
        polling, teardown via signals — WITHOUT spawning a duplicate
        worker next to the one riding out the restart.  The pod's worker
        process notices nothing: it re-registers with the new master
        through its own proxy reconnect."""
        with self._lock:
            self._adopted[name] = pid
            if self._watcher is None:
                self._watcher = threading.Thread(
                    target=self._watch, name="pod-watcher", daemon=True
                )
                self._watcher.start()
        logger.info("adopted orphan pod %s (pid %d)", name, pid)
        trace.instant("pod:adopt", cat="elastic", pod=name, pid=pid)
        self._emit(name, PodPhase.RUNNING)

    _pid_alive = staticmethod(pid_alive)

    #: SIGTERM->SIGKILL grace on delete: must exceed the worker's
    #: preemption-snapshot bound (worker.main PREEMPTION_EXIT_S = 15 s) or
    #: a scale-down would tear the snapshot it just triggered mid-write.
    #: wait() returns the moment the pod exits, so pods without state to
    #: save (PS shards, group members) still tear down in milliseconds.
    TERMINATE_GRACE_S = 20.0

    def delete_pod(self, name: str) -> None:
        with self._lock:
            proc = self._procs.pop(name, None)
            adopted_pid = self._adopted.pop(name, None)
        if proc is not None and proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=self.TERMINATE_GRACE_S)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(timeout=5)
        elif adopted_pid is not None:
            # Not our child: no wait() — SIGTERM, poll liveness through
            # the same grace the child path gets, then SIGKILL.
            self._signal_adopted(adopted_pid)
        self._emit(name, PodPhase.DELETED)

    def _signal_adopted(self, pid: int) -> None:
        import signal

        try:
            os.kill(pid, signal.SIGTERM)
        except OSError:
            return  # already gone
        deadline = time.monotonic() + self.TERMINATE_GRACE_S
        while time.monotonic() < deadline:
            if not self._pid_alive(pid):
                return
            time.sleep(0.1)
        try:
            os.kill(pid, signal.SIGKILL)
        except OSError:
            pass

    def _watch(self) -> None:
        while not self._stop.is_set():
            try:
                done = []
                lost = []
                with self._lock:
                    for name, proc in self._procs.items():
                        rc = proc.poll()
                        if rc is not None:
                            done.append((name, rc))
                    for name, _ in done:
                        del self._procs[name]
                    for name, pid in list(self._adopted.items()):
                        if not self._pid_alive(pid):
                            lost.append((name, pid))
                            del self._adopted[name]
                for name, pid in lost:
                    # Exit code unknowable (never our child): LOST, which
                    # PodManager resolves against job state.
                    logger.info(
                        "adopted pod %s (pid %d) disappeared -> %s",
                        name, pid, PodPhase.LOST,
                    )
                    self._emit(name, PodPhase.LOST)
                for name, rc in done:
                    if rc == 0:
                        phase = PodPhase.SUCCEEDED
                    elif rc == WORKER_RESTART_EXIT_CODE:
                        phase = PodPhase.RESTART
                    else:
                        phase = PodPhase.FAILED
                    # The exit code is the only forensic a silently-dying
                    # pod leaves (negative = killed by that signal); the
                    # chaos work made clear the watcher must say it.
                    logger.info("pod %s exited rc=%s -> %s", name, rc, phase)
                    self._emit(name, phase)
            except Exception:
                # The watcher is the only observer of worker exits; it must
                # survive any emit-chain error or elasticity silently dies.
                logger.exception("pod watcher iteration failed")
            time.sleep(self._poll)

    def pid(self, name: str) -> Optional[int]:
        with self._lock:
            proc = self._procs.get(name)
            if proc is not None:
                return proc.pid
            return self._adopted.get(name)

    def standby_depth(self) -> Optional[int]:
        """Live parked spares right now (the Heartbeat/JobStatus gauge);
        None when warm standby is off — "no pool" and "drained pool" must
        not read the same."""
        if not self._warm:
            return None
        with self._lock:
            return sum(1 for p, _, _ in self._standby if p.poll() is None)

    def close(self) -> None:
        self._stop.set()
        with self._lock:
            procs = list(self._procs.values())
            self._procs.clear()
            procs.extend(p for p, _, _ in self._standby)
            self._standby = []
            adopted = list(self._adopted.values())
            self._adopted.clear()
            standby_dir, self._standby_dir = self._standby_dir, None
        for proc in procs:
            if proc.poll() is None:
                proc.kill()
                self._reap(proc)
        for pid in adopted:
            if self._pid_alive(pid):
                import signal

                try:
                    os.kill(pid, signal.SIGKILL)
                except OSError:
                    pass
        if standby_dir is not None:
            import shutil

            shutil.rmtree(standby_dir, ignore_errors=True)


def render_base_pod_manifest(
    job_name: str,
    pod_name: str,
    replica_type: str,
    image: str,
    command: List[str],
    env: Dict[str, str],
) -> dict:
    """Common V1Pod scaffold for master and worker pods (labels, restart
    policy, env plumbing).  Always injects ``MY_POD_IP`` via the downward
    API: the master advertises it to workers (Master._advertise_host), and
    having it everywhere keeps the two renderers from drifting."""
    return {
        "apiVersion": "v1",
        "kind": "Pod",
        "metadata": {
            "name": pod_name,
            "labels": {
                "app": "elasticdl-tpu",
                "elasticdl-job-name": job_name,
                "elasticdl-replica-type": replica_type,
            },
        },
        "spec": {
            "restartPolicy": "Never",  # relaunch policy lives in PodManager
            "containers": [
                {
                    "name": replica_type,
                    "image": image,
                    "command": command,
                    "env": [
                        {
                            "name": "MY_POD_IP",
                            "valueFrom": {
                                "fieldRef": {"fieldPath": "status.podIP"}
                            },
                        }
                    ]
                    + [{"name": k, "value": v} for k, v in sorted(env.items())],
                }
            ],
        },
    }


def render_worker_pod_manifest(
    config: JobConfig,
    pod_name: str,
    env: Dict[str, str],
    image: str = "elasticdl-tpu:latest",
    tpu_topology: str = "2x4",
    tpu_accelerator: str = "tpu-v5-lite-podslice",
    tpu_chips_per_host: int = 4,
) -> dict:
    """A Kubernetes V1Pod-shaped dict for one TPU worker host.

    Mirrors the reference's master-rendered worker pod spec (SURVEY.md §3.1),
    retargeted at GKE TPU node pools: the ``google.com/tpu`` resource plus the
    podslice node selectors replace the reference's GPU resource requests.
    """
    manifest = render_base_pod_manifest(
        config.job_name,
        pod_name,
        "worker",
        image,
        ["python", "-m", "elasticdl_tpu.worker.main"],
        env,
    )
    manifest["spec"]["nodeSelector"] = {
        "cloud.google.com/gke-tpu-accelerator": tpu_accelerator,
        "cloud.google.com/gke-tpu-topology": tpu_topology,
    }
    manifest["spec"]["containers"][0]["resources"] = {
        "requests": {"google.com/tpu": str(tpu_chips_per_host)},
        "limits": {"google.com/tpu": str(tpu_chips_per_host)},
    }
    return manifest


def render_ps_pod_manifest(
    config: JobConfig,
    pod_name: str,
    env: Dict[str, str],
    image: str = "elasticdl-tpu:latest",
) -> dict:
    """A V1Pod dict for one PS shard (ps/main.py): CPU-only — no TPU
    resources or node selectors — with the shard's memory dominated by its
    host-tier table slice.  Cross-pod reachability relies on a headless
    service named ``<job>-ps`` governing these pods (master/main.py renders
    shard addresses as ``<pod>.<job>-ps.<namespace>:2222``)."""
    manifest = render_base_pod_manifest(
        config.job_name,
        pod_name,
        "ps",
        image,
        ["python", "-m", "elasticdl_tpu.ps.main"],
        env,
    )
    # Per-pod DNS under the headless service needs BOTH hostname and
    # subdomain on the pod spec.  The hostname is derived from the SHARD
    # slot, not the pod name: a relaunched shard gets a fresh pod name
    # (slot-gen suffix, PodManager._new_pod_locked) but must keep answering
    # at the address the master advertised to workers at job start.
    slot = env.get("ELASTICDL_WORKER_SLOT", "0")
    manifest["spec"]["hostname"] = f"{config.job_name}-ps-{slot}"
    manifest["spec"]["subdomain"] = f"{config.job_name}-ps"
    return manifest


class KubernetesPodBackend(PodBackend):
    """Drives rendered manifests through the kubernetes python client.

    Import-gated: constructing it without the ``kubernetes`` package raises —
    the manifest renderer above stays testable anywhere.  ``renderer`` picks
    the manifest shape (worker TPU pods by default; ``render_ps_pod_manifest``
    for PS shards).
    """

    def __init__(
        self,
        config: JobConfig,
        namespace: str = "default",
        renderer: Callable[..., dict] = render_worker_pod_manifest,
        **render_kwargs,
    ):
        try:
            import kubernetes  # type: ignore
        except ImportError as e:  # pragma: no cover - not installed in image
            raise RuntimeError(
                "KubernetesPodBackend requires the 'kubernetes' package; "
                "use ProcessPodBackend for local jobs"
            ) from e
        kubernetes.config.load_incluster_config()
        self._core = kubernetes.client.CoreV1Api()
        self._ns = namespace
        self._config = config
        self._renderer = renderer
        self._render_kwargs = render_kwargs
        self._stop = threading.Event()
        self._watcher = threading.Thread(
            target=self._watch, name="k8s-watcher", daemon=True
        )
        self._watcher.start()

    def start_pod(self, name: str, env: Dict[str, str]) -> None:  # pragma: no cover
        manifest = self._renderer(
            self._config, name, env, **self._render_kwargs
        )
        self._core.create_namespaced_pod(self._ns, manifest)

    def delete_pod(self, name: str) -> None:  # pragma: no cover
        self._core.delete_namespaced_pod(name, self._ns)
        self._emit(name, PodPhase.DELETED)

    def _watch(self) -> None:  # pragma: no cover — raw API calls only
        import kubernetes  # type: ignore

        watch = kubernetes.watch.Watch()
        selector = f"elasticdl-job-name={self._config.job_name}"

        def stream():
            return watch.stream(
                self._core.list_namespaced_pod,
                self._ns,
                label_selector=selector,
                timeout_seconds=30,
            )

        run_watch_loop(stream, self._emit, self._stop)

    def close(self) -> None:  # pragma: no cover
        self._stop.set()


def map_watch_event(event) -> tuple:
    """One k8s watch event -> (pod_name, PodPhase) for the slot table.

    k8s has no 'Restart' phase: a worker exiting with
    WORKER_RESTART_EXIT_CODE (multihost elastic re-join) shows as Failed —
    map it back to RESTART from the container's terminated exit code so
    membership changes don't consume the slot's relaunch budget.  Unit-
    tested against synthetic events (tests/test_pod_manager.py); the
    in-cluster path differs only in where events come from.
    """
    pod = event["object"]
    phase = pod.status.phase
    if phase == PodPhase.FAILED:
        try:
            statuses = pod.status.container_statuses or []
            term = (
                statuses[0].state.terminated
                if statuses and statuses[0].state
                else None
            )
            if term is not None and term.exit_code == WORKER_RESTART_EXIT_CODE:
                phase = PodPhase.RESTART
        except Exception:
            logger.exception(
                "could not read exit code of failed pod %s", pod.metadata.name
            )
    return pod.metadata.name, phase


def run_watch_loop(stream_factory, emit, stop, backoff_s: float = 1.0) -> None:
    """Drive watch events into ``emit`` until ``stop`` is set.

    ``stream_factory`` opens a fresh event stream each round; it raising
    (410 Gone on resourceVersion expiry, transient apiserver errors) just
    re-establishes the watch after ``backoff_s`` instead of killing the
    thread — the reference master's pod-watch loop survives the same way.
    """
    while not stop.is_set():
        try:
            for event in stream_factory():
                emit(*map_watch_event(event))
                if stop.is_set():
                    return
        except Exception:
            logger.exception("k8s watch stream failed; re-watching")
            stop.wait(backoff_s)


# racesan (r16): fleet state lives under _lock; _listeners is
# append-at-wiring (master main, before scale()) and iterated on
# watcher threads — single-op atomic by declaration, like the
# rendezvous listener list.
@racesan.instrument(atomic=("_listeners",))
class PodManager:
    """Slot-based worker fleet: start, watch, relaunch, scale.

    Each of the ``desired`` slots holds at most one live pod.  A FAILED pod is
    relaunched into its slot (fresh pod name, as k8s would) while its relaunch
    budget lasts; SUCCEEDED/DELETED pods retire their slot's current pod
    without relaunch.  ``scale(n)`` adds slots or deletes the highest ones —
    the 4→8→4 elasticity path.
    """

    #: Canonical registry filename (module constant re-exported where the
    #: wiring already has the class in hand).
    REGISTRY_FILENAME = REGISTRY_FILENAME

    def __init__(
        self,
        backend: PodBackend,
        config: JobConfig,
        worker_env: Optional[Dict[str, str]] = None,
        name_prefix: Optional[str] = None,
        state_path: Optional[str] = None,
    ):
        self._backend = backend
        self._config = config
        self._env = dict(worker_env or {})
        self._prefix = name_prefix or f"{config.job_name}-worker"
        self._lock = locksan.lock("PodManager._lock", leaf=True)  # lock-order: leaf
        self._slots: Dict[int, Optional[PodInfo]] = {}  # guarded-by: _lock
        self._by_name: Dict[str, PodInfo] = {}  # guarded-by: _lock
        # Pod reattach registry (r18 master crash survivability): the
        # per-slot (name, pid, gen, cmdline) of every live pod, persisted
        # to ``state_path`` so supervision OUTLIVES this master process —
        # a restarted master ADOPTS the still-running orphans (backend
        # adopt_pod: kill(0)-polled liveness, signal teardown) instead of
        # spawning a duplicate fleet beside the workers riding out the
        # restart.  None = no persistence (pre-r18 behavior).
        self._state_path = state_path
        self._reattach: Dict[str, dict] = self._load_registry()  # guarded-by: _lock
        # Resolves an adopted pod's unknowable exit (PodPhase.LOST): the
        # master wires servicer.job_finished here — a disappearance after
        # the job is done is a clean exit, before it is a crash.
        self._job_finished_fn: Optional[Callable[[], bool]] = None
        # Per-slot launch generation, NEVER reset (survives scale-down/up
        # cycles): every pod a slot ever gets has a unique name, so late
        # events for a retired pod can't resolve to its successor and a k8s
        # backend can't hit a name conflict with a terminating pod.
        self._slot_gen: Dict[int, int] = {}  # guarded-by: _lock
        self._desired = 0  # guarded-by: _lock
        self._listeners: List[PodListener] = []
        self._retry_timers: List[threading.Timer] = []  # guarded-by: _lock
        self._relaunch = config.relaunch_on_worker_failure
        self._max_relaunch = config.max_worker_relaunch
        backend.set_event_callback(self._on_event)

    # -- listeners (master main wires rendezvous.remove here) --

    def add_listener(self, fn: PodListener) -> None:
        self._listeners.append(fn)

    def set_job_finished_fn(self, fn: Callable[[], bool]) -> None:
        """Wire the LOST-resolution probe (see _on_event); called at
        wiring time, before any pod events flow."""
        self._job_finished_fn = fn

    # -- reattach registry (r18) --

    # recovery-path
    def _load_registry(self) -> Dict[str, dict]:
        if not self._state_path or not os.path.exists(self._state_path):
            return {}
        data = durable.read_json_tolerant(self._state_path)
        if not isinstance(data, dict):
            logger.warning(
                "unreadable pod registry %s; ignoring", self._state_path
            )
            return {}
        try:
            return {
                str(k): dict(v) for k, v in (data.get("slots") or {}).items()
            }
        except (TypeError, ValueError, AttributeError):
            logger.warning(
                "malformed pod registry %s; ignoring", self._state_path
            )
            return {}

    _proc_cmdline = staticmethod(proc_cmdline)

    # recovery-path
    @staticmethod
    def scan_registry(state_path: Optional[str]) -> dict:
        """One-shot registry liveness scan (r18): ``{"recorded": n,
        "alive": [pids], "dead": [pids]}`` with the SAME adoptability
        probe ``_adoptable_locked`` applies (zombie + cmdline-fingerprint
        guarded pid_alive) — Master's whole-job-restart decision and any
        tool read the fleet's fate through this one definition."""
        out = {"recorded": 0, "alive": [], "dead": []}
        if not state_path or not os.path.exists(state_path):
            return out
        data = durable.read_json_tolerant(state_path)
        if not isinstance(data, dict):
            return out
        try:
            slots = (data.get("slots") or {}).values()
        except AttributeError:
            return out
        for s in slots:
            if not isinstance(s, dict):
                continue
            pid = s.get("pid")
            if not isinstance(pid, int) or pid <= 0:
                continue
            out["recorded"] += 1
            bucket = (
                "alive" if pid_alive(pid, cmdline=s.get("cmdline")) else "dead"
            )
            out[bucket].append(pid)
        return out

    def _persist_registry(self) -> None:
        """Atomically persist the live-pod table.  Reads pids OUTSIDE the
        manager lock (the backend takes its own): the registry is
        advisory — a torn race loses one adoption opportunity, never
        correctness (the unmatched orphan is simply not adopted and the
        slot cold-spawns beside it only if its pid probe failed, i.e. it
        was already gone)."""
        if not self._state_path:
            return
        with self._lock:
            live = [
                (i.slot, i.name, i.relaunches, self._slot_gen.get(i.slot, 0))
                for i in self._slots.values()
                if i is not None and i.phase not in PodPhase.TERMINAL
            ]
        pid_fn = getattr(self._backend, "pid", None)
        slots = {}
        for slot, name, relaunches, gen in live:
            pid = pid_fn(name) if pid_fn is not None else None
            if pid is None:
                continue
            slots[str(slot)] = {
                "name": name, "pid": pid, "relaunches": relaunches,
                "gen": gen, "cmdline": self._proc_cmdline(pid),
            }
        try:
            # durable.atomic_publish's thread-unique temp matters HERE: the
            # watcher thread's terminal-event persist can race a
            # scale()/launch persist IN THIS PROCESS — a shared pid-only
            # temp name would let them interleave writes and os.replace
            # corrupt JSON into the registry, which the next master's scan
            # would read as "no evidence" and pick a FULL replay for a
            # genuinely dead fleet.  (It also adds the fsyncs the old
            # hand-rolled copy skipped.)
            durable.atomic_publish_json(
                self._state_path, {"slots": slots}, sort_keys=True
            )
        except OSError:
            # Advisory state: a failed write costs the NEXT master its
            # adoption shortcut, never this one its launch.
            logger.exception("pod registry write failed (%s)", self._state_path)

    def _adoptable_locked(self, entry: dict) -> bool:  # guarded-by: _lock
        if not hasattr(self._backend, "adopt_pod"):
            return False
        pid = entry.get("pid")
        if not isinstance(pid, int) or pid <= 0:
            return False
        return pid_alive(pid, cmdline=entry.get("cmdline"))

    def _notify(self, name: str, phase: str) -> None:
        for fn in self._listeners:
            try:
                fn(name, phase)
            except Exception:
                # Listeners run on backend watcher threads; see _on_event.
                logger.exception("pod listener failed for %s/%s", name, phase)

    # -- fleet control --

    def start(self, num_workers: Optional[int] = None) -> None:
        self.scale(num_workers or self._config.num_workers)

    def scale(self, n: int) -> None:
        """Grow or shrink the fleet to ``n`` worker slots."""
        if n < 0:
            raise ValueError("cannot scale below 0 workers")
        to_start: List[PodInfo] = []
        to_delete: List[str] = []
        to_adopt: List[tuple] = []
        with self._lock:
            old = self._desired
            self._desired = n
            for slot in range(old, n):  # grow
                # Reattach first (r18): a live orphan of the pre-restart
                # master fills the slot WITHOUT a duplicate spawn — the
                # worker in it is already riding out the restart on its
                # proxy reconnect.  The registry entry is one-shot; a
                # dead/reused pid falls through to a normal launch.
                entry = self._reattach.pop(str(slot), None)
                if entry is not None:
                    # Seed the slot's generation from the registry EITHER
                    # way: a dead entry falls through to a fresh launch,
                    # and reusing the dead generation's exact pod name
                    # would break the every-pod-unique-name invariant
                    # (late events for the retired pod would resolve to
                    # its unrelated successor, and the successor's worker
                    # id would collide with the dead incarnation's).
                    gen = int(entry.get("gen", 0))
                    self._slot_gen[slot] = max(
                        self._slot_gen.get(slot, -1), gen
                    )
                if entry is not None and self._adoptable_locked(entry):
                    info = PodInfo(
                        name=entry["name"], slot=slot,
                        relaunches=int(entry.get("relaunches", 0)),
                    )
                    self._slots[slot] = info
                    self._by_name[info.name] = info
                    to_adopt.append((info, int(entry["pid"])))
                    continue
                info = self._new_pod_locked(slot, relaunches=0)
                to_start.append(info)
            for slot in range(n, old):  # shrink: retire highest slots
                info = self._slots.pop(slot, None)
                if info is not None and info.phase not in PodPhase.TERMINAL:
                    to_delete.append(info.name)
        for info, pid in to_adopt:
            self._backend.adopt_pod(info.name, pid)
        for info in to_start:
            self._launch(info)
        for name in to_delete:
            self._backend.delete_pod(name)
        if to_adopt or to_start or to_delete:
            self._persist_registry()
        if n != old:
            logger.info(
                "scaled worker fleet %d -> %d%s", old, n,
                f" ({len(to_adopt)} slot(s) re-attached to live orphans)"
                if to_adopt else "",
            )

    # How many times a single pod launch is retried against backend errors
    # (transient k8s API outages, fork failures) before the failure is
    # surfaced as a budget-consuming FAILED event.  The backoff schedule
    # (1+2+4+8+16+30+30 = ~91s) outlasts a ~1-minute apiserver outage.
    MAX_START_ATTEMPTS = 8

    def _launch(self, info: PodInfo, attempt: int = 0) -> None:
        """start_pod with bounded backoff retries for the SAME PodInfo.

        A launch that throws is retried directly — NOT turned into a FAILED
        pod event — so a ~1-minute transient k8s API outage doesn't eat the
        slot's relaunch budget (and budget-free RESTART relaunches stay
        budget-free).  Only after MAX_START_ATTEMPTS does it degrade to the
        normal failure path.
        """
        with self._lock:
            if self._slots.get(info.slot) is not info:
                return  # slot was scaled away or superseded while backing off
        try:
            self._backend.start_pod(info.name, self._pod_env(info))
            with self._lock:
                info.launched_at = trace.now_s()
            self._persist_registry()
        except Exception:
            logger.exception(
                "launch of %s failed (attempt %d/%d)",
                info.name, attempt + 1, self.MAX_START_ATTEMPTS,
            )
            if attempt + 1 >= self.MAX_START_ATTEMPTS:
                self._on_event(info.name, PodPhase.FAILED)
                return
            delay = min(2.0 ** attempt, 30.0)
            timer = threading.Timer(delay, self._launch, (info, attempt + 1))
            timer.daemon = True
            with self._lock:
                # Prune timers that already fired or were cancelled so the
                # list stays bounded.  `finished` (set after run or cancel)
                # is the right predicate: is_alive() is also False for
                # appended-but-not-yet-started timers, which must stay
                # cancellable by stop().
                self._retry_timers = [
                    t for t in self._retry_timers if not t.finished.is_set()
                ]
                self._retry_timers.append(timer)
            timer.start()

    def _new_pod_locked(self, slot: int, relaunches: int) -> PodInfo:  # guarded-by: _lock
        gen = self._slot_gen.get(slot, -1) + 1
        self._slot_gen[slot] = gen
        suffix = f"-r{gen}" if gen else ""
        info = PodInfo(
            name=f"{self._prefix}-{slot}{suffix}",
            slot=slot,
            relaunches=relaunches,
        )
        self._slots[slot] = info
        self._by_name[info.name] = info
        return info

    def _pod_env(self, info: PodInfo) -> Dict[str, str]:
        env = dict(self._env)
        env.update(self._config.to_env())
        env["ELASTICDL_WORKER_ID"] = info.name
        env["ELASTICDL_WORKER_SLOT"] = str(info.slot)
        return env

    def stop(self) -> None:
        with self._lock:
            self._desired = 0
            for timer in self._retry_timers:
                timer.cancel()
            self._retry_timers.clear()
            live = [
                i.name
                for i in self._slots.values()
                if i is not None and i.phase not in PodPhase.TERMINAL
            ]
            self._slots.clear()
        for name in live:
            self._backend.delete_pod(name)
        self._backend.close()
        if self._state_path:
            # A CLEAN stop tears the fleet down — leaving the registry
            # behind would point the next master at recycled pids.
            try:
                os.remove(self._state_path)
            except OSError:
                pass

    # -- event handling --

    def _on_event(self, name: str, phase: str) -> None:
        if phase == PodPhase.LOST:
            # Adopted-orphan disappearance: the exit code is unknowable
            # (never this process's child).  After the job is finished a
            # disappearance IS the worker's clean exit; before it, treat
            # as a crash so the relaunch/requeue machinery engages.
            fn = self._job_finished_fn
            phase = (
                PodPhase.SUCCEEDED
                if fn is not None and fn()
                else PodPhase.FAILED
            )
            logger.info(
                "adopted pod %s lost -> resolved %s (exit code "
                "unknowable for a re-attached orphan)", name, phase,
            )
        relaunch_info: Optional[PodInfo] = None
        with self._lock:
            info = self._by_name.get(name)
            if info is None:
                return
            info.phase = phase
            if phase == PodPhase.RESTART:
                # Requested restart (multihost elastic re-join): relaunch
                # into the slot without touching the failure budget.
                if self._slots.get(info.slot) is info:
                    relaunch_info = self._new_pod_locked(
                        info.slot, info.relaunches
                    )
            elif phase == PodPhase.FAILED:
                in_fleet = self._slots.get(info.slot) is info
                if (
                    in_fleet
                    and self._relaunch
                    and info.relaunches < self._max_relaunch
                ):
                    relaunch_info = self._new_pod_locked(
                        info.slot, info.relaunches + 1
                    )
                elif in_fleet:
                    self._slots[info.slot] = None
                    logger.warning(
                        "pod %s failed with relaunch budget exhausted", name
                    )
            elif phase in (PodPhase.SUCCEEDED, PodPhase.DELETED):
                if self._slots.get(info.slot) is info:
                    self._slots[info.slot] = None
        if phase == PodPhase.FAILED:
            # The splice timeline's t0: the master KNOWS the pod is gone.
            # Recovery decomposes as detect -> adopt -> reformed ->
            # trained-again from these master-clock instants
            # (the dying worker's own chaos:kill instant never ships —
            # its buffer dies with it).
            trace.instant(
                "elastic:splice", cat="elastic", stage="detect", pod=name,
                slot=info.slot,
                relaunch=relaunch_info.name if relaunch_info else None,
            )
        self._notify(name, phase)
        if relaunch_info is not None:
            logger.info(
                "relaunching failed pod %s as %s (relaunch %d/%d)",
                name, relaunch_info.name,
                relaunch_info.relaunches, self._max_relaunch,
            )
            # _launch retries transient backend errors for this same PodInfo
            # without unwinding into the watcher thread (the only thread
            # observing pod events) and without consuming relaunch budget.
            self._launch(relaunch_info)
        elif phase in PodPhase.TERMINAL:
            # A retired pod must leave the reattach registry NOW: a later
            # master adopting its recycled pid would supervise a stranger.
            self._persist_registry()

    # -- introspection --

    def live_pods(self) -> List[str]:
        with self._lock:
            return sorted(
                i.name
                for i in self._slots.values()
                if i is not None and i.phase not in PodPhase.TERMINAL
            )

    def desired(self) -> int:
        with self._lock:
            return self._desired

    def pod_info(self, name: str) -> Optional[PodInfo]:
        with self._lock:
            return self._by_name.get(name)

    def launched_at(self, name: Optional[str] = None) -> Optional[float]:
        """When the launch of pod ``name`` returned (the newest launch of
        any pod without a name); None for a pod this manager never
        launched."""
        with self._lock:
            pods = (
                [self._by_name.get(name)] if name is not None
                else list(self._by_name.values())
            )
        stamps = [p.launched_at for p in pods if p and p.launched_at]
        return max(stamps) if stamps else None

    def standby_depth(self) -> Optional[int]:
        """Warm-standby pool depth, or None when the backend has no pool
        (fake/kubernetes backends, warm standby off)."""
        fn = getattr(self._backend, "standby_depth", None)
        return fn() if fn is not None else None

    def counts(self) -> Dict[str, int]:
        """Fleet-state scalars for the live metrics plane (the master's
        /metrics collector, master/main.py): desired slots, live pods, and
        the summed relaunch generations — churn made a readable number."""
        with self._lock:
            infos = [i for i in self._slots.values() if i is not None]
            return {
                "desired": self._desired,
                "live": sum(
                    1 for i in infos if i.phase not in PodPhase.TERMINAL
                ),
                "relaunches": sum(i.relaunches for i in infos),
            }

    def all_finished(self) -> bool:
        """True when every slot's pod has reached a terminal phase."""
        with self._lock:
            return all(
                i is None or i.phase in PodPhase.TERMINAL
                for i in self._slots.values()
            )
