"""PodManager unit tests via the fake backend (the reference's mock-k8s
pattern, SURVEY.md §4) plus master-orchestrated jobs: fake-fleet supervision
and a real ProcessPodBackend end-to-end run with a mid-job worker kill."""

import os
import sys
import threading
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.main import Master
from elasticdl_tpu.master.pod_manager import (
    FakePodBackend,
    PodManager,
    PodPhase,
    ProcessPodBackend,
    render_worker_pod_manifest,
)


def _manager(num_workers=4, max_relaunch=2, relaunch=True):
    backend = FakePodBackend()
    config = JobConfig(
        job_name="job",
        num_workers=num_workers,
        relaunch_on_worker_failure=relaunch,
        max_worker_relaunch=max_relaunch,
    )
    manager = PodManager(backend, config)
    return manager, backend


class TestPodManager:
    def test_start_launches_desired_pods(self):
        manager, backend = _manager(num_workers=4)
        manager.start()
        assert len(backend.running()) == 4
        assert manager.live_pods() == [f"job-worker-{i}" for i in range(4)]

    def test_failed_pod_is_relaunched_with_fresh_name(self):
        manager, backend = _manager(num_workers=2)
        manager.start()
        backend.fail_pod("job-worker-0")
        assert "job-worker-0-r1" in backend.running()
        assert len(manager.live_pods()) == 2
        backend.fail_pod("job-worker-0-r1")
        assert "job-worker-0-r2" in backend.running()

    def test_relaunch_budget_exhausted(self):
        manager, backend = _manager(num_workers=1, max_relaunch=1)
        manager.start()
        backend.fail_pod("job-worker-0")
        backend.fail_pod("job-worker-0-r1")
        assert manager.live_pods() == []
        assert manager.all_finished()

    def test_no_relaunch_when_disabled(self):
        manager, backend = _manager(num_workers=1, relaunch=False)
        manager.start()
        backend.fail_pod("job-worker-0")
        assert manager.live_pods() == []

    def test_scale_up_and_down(self):
        manager, backend = _manager(num_workers=4)
        manager.start()
        manager.scale(8)
        assert len(manager.live_pods()) == 8
        manager.scale(4)
        assert manager.live_pods() == [f"job-worker-{i}" for i in range(4)]
        # Retired pods got real delete calls, not silent forgetting.
        assert backend.pods["job-worker-7"] == PodPhase.DELETED

    def test_succeeded_pod_not_relaunched(self):
        manager, backend = _manager(num_workers=2)
        manager.start()
        backend.succeed_pod("job-worker-0")
        assert manager.live_pods() == ["job-worker-1"]

    def test_listener_sees_events(self):
        manager, backend = _manager(num_workers=2)
        events = []
        manager.add_listener(lambda name, phase: events.append((name, phase)))
        manager.start()
        backend.fail_pod("job-worker-1")
        assert ("job-worker-1", PodPhase.FAILED) in events

    def test_worker_env_carries_config_and_identity(self):
        backend = FakePodBackend()
        config = JobConfig(job_name="j", num_workers=1)
        seen = {}
        orig = backend.start_pod

        def spy(name, env):
            seen[name] = env
            orig(name, env)

        backend.start_pod = spy
        PodManager(backend, config).start()
        env = seen["j-worker-0"]
        assert env["ELASTICDL_WORKER_ID"] == "j-worker-0"
        assert "ELASTICDL_JOB_CONFIG" in env
        assert JobConfig.from_env(env).job_name == "j"


class TestPodReattach:
    """r18 master crash survivability: the pod registry lets a restarted
    master ADOPT the previous master's live worker orphans instead of
    spawning a duplicate fleet, and resolves their unknowable exit codes
    against job state."""

    @staticmethod
    def _sleep_backend(log_dir=None):
        return ProcessPodBackend(
            argv=[sys.executable, "-c", "import time; time.sleep(60)"],
            poll_interval_s=0.05,
        )

    def _config(self, n=1):
        return JobConfig(job_name="rejob", num_workers=n, max_worker_relaunch=1)

    def test_registry_persists_and_restart_adopts(self, tmp_path):
        state = str(tmp_path / "pod_registry.json")
        b1 = self._sleep_backend()
        m1 = PodManager(b1, self._config(), state_path=state)
        m1.start(1)
        pid = b1.pid("rejob-worker-0")
        assert pid is not None and os.path.exists(state)
        import json

        reg = json.load(open(state))
        assert reg["slots"]["0"]["pid"] == pid
        # "Crash": the first manager/backend go away WITHOUT delete_pod —
        # only the subprocess handle dies, the process lives on.
        b1._stop.set()
        with b1._lock:
            b1._procs.clear()  # simulate the master process dying

        events = []
        b2 = self._sleep_backend()
        m2 = PodManager(b2, self._config(), state_path=state)
        m2.add_listener(lambda name, phase: events.append((name, phase)))
        m2.start(1)
        # Adopted, not respawned: same name, same pid, RUNNING emitted.
        assert b2.pid("rejob-worker-0") == pid
        with b2._lock:
            assert b2._adopted == {"rejob-worker-0": pid}
            assert not b2._procs  # nothing spawned
        assert ("rejob-worker-0", PodPhase.RUNNING) in events
        m2.stop()
        assert not os.path.exists(state)  # clean stop clears the registry
        # stop() killed the adopted orphan too (pid_alive is zombie-aware:
        # in THIS harness the "orphan" is our own unreaped child, a case
        # production adoption never sees — real orphans reap via init).
        from elasticdl_tpu.master.pod_manager import pid_alive

        deadline = time.time() + 5
        while time.time() < deadline and pid_alive(pid):
            time.sleep(0.05)
        assert not pid_alive(pid)

    def test_dead_registry_pid_falls_through_to_spawn(self, tmp_path):
        state = str(tmp_path / "pod_registry.json")
        import json

        json.dump(
            {"slots": {"0": {"name": "rejob-worker-0-r2", "pid": 2 ** 22 + 1234,
                             "relaunches": 2, "gen": 2}}},
            open(state, "w"),
        )
        b = self._sleep_backend()
        m = PodManager(b, self._config(), state_path=state)
        m.start(1)
        with b._lock:
            assert not b._adopted
            assert len(b._procs) == 1  # normal spawn
            # The dead generation's gen still seeds the slot: the fresh
            # pod must NOT reuse the dead incarnation's exact name (late
            # events and worker-id collisions would alias to it).
            (name,) = b._procs
        assert name == "rejob-worker-0-r3"
        m.stop()

    def test_lost_resolves_failed_before_finish_succeeded_after(self, tmp_path):
        import subprocess

        state = str(tmp_path / "pod_registry.json")
        orphan = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"]
        )
        try:
            import json

            json.dump(
                {"slots": {"0": {"name": "rejob-worker-0", "pid": orphan.pid,
                                 "relaunches": 0, "gen": 0}}},
                open(state, "w"),
            )
            b = self._sleep_backend()
            m = PodManager(b, self._config(), state_path=state)
            finished = {"v": False}
            m.set_job_finished_fn(lambda: finished["v"])
            events = []
            m.add_listener(lambda name, phase: events.append((name, phase)))
            m.start(1)
            with b._lock:
                assert b._adopted == {"rejob-worker-0": orphan.pid}
            # Kill the orphan while the job is NOT finished: LOST resolves
            # to FAILED and the slot relaunches (budget charged).
            orphan.kill()
            orphan.wait()
            deadline = time.time() + 10
            while time.time() < deadline and not any(
                p == PodPhase.FAILED for _n, p in events
            ):
                time.sleep(0.05)
            assert ("rejob-worker-0", PodPhase.FAILED) in events
            deadline = time.time() + 10
            while time.time() < deadline:
                with b._lock:
                    if b._procs:  # the relaunch spawned
                        break
                time.sleep(0.05)
            info = m.pod_info("rejob-worker-0-r1")
            assert info is not None and info.relaunches == 1
            m.stop()
        finally:
            if orphan.poll() is None:
                orphan.kill()

    def test_lost_after_job_end_resolves_succeeded(self, tmp_path):
        import json
        import subprocess

        state = str(tmp_path / "pod_registry.json")
        orphan = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"]
        )
        try:
            json.dump(
                {"slots": {"0": {"name": "rejob-worker-0", "pid": orphan.pid,
                                 "relaunches": 0, "gen": 0}}},
                open(state, "w"),
            )
            b = self._sleep_backend()
            m = PodManager(b, self._config(), state_path=state)
            m.set_job_finished_fn(lambda: True)  # the job is already done
            events = []
            m.add_listener(lambda name, phase: events.append((name, phase)))
            m.start(1)
            orphan.kill()
            orphan.wait()
            # A disappearance AFTER the job finished IS the worker's
            # clean exit: SUCCEEDED, slot retired, no relaunch.
            deadline = time.time() + 10
            while time.time() < deadline and (
                ("rejob-worker-0", PodPhase.SUCCEEDED) not in events
            ):
                time.sleep(0.05)
            assert ("rejob-worker-0", PodPhase.SUCCEEDED) in events
            assert m.all_finished()
            with b._lock:
                assert not b._procs  # nothing relaunched
            m.stop()
        finally:
            if orphan.poll() is None:
                orphan.kill()


class TestPodManifest:
    def test_tpu_pod_manifest_shape(self):
        config = JobConfig(job_name="deepfm")
        manifest = render_worker_pod_manifest(
            config, "deepfm-worker-0", {"A": "1"}, tpu_chips_per_host=4
        )
        assert manifest["kind"] == "Pod"
        container = manifest["spec"]["containers"][0]
        assert container["resources"]["limits"]["google.com/tpu"] == "4"
        selector = manifest["spec"]["nodeSelector"]
        assert "cloud.google.com/gke-tpu-topology" in selector
        assert manifest["spec"]["restartPolicy"] == "Never"
        assert {"name": "A", "value": "1"} in container["env"]

    def test_ps_pod_manifest_shape(self):
        """PS shard pods: CPU-only, stable per-SLOT hostname under the
        headless <job>-ps subdomain (a relaunched shard keeps its DNS name
        even though the pod name carries a generation suffix)."""
        from elasticdl_tpu.master.pod_manager import render_ps_pod_manifest

        config = JobConfig(job_name="deepfm")
        manifest = render_ps_pod_manifest(
            config, "deepfm-ps-1-r2", {"ELASTICDL_WORKER_SLOT": "1"}
        )
        container = manifest["spec"]["containers"][0]
        assert "resources" not in container  # no TPU request
        assert "nodeSelector" not in manifest["spec"]
        assert manifest["spec"]["hostname"] == "deepfm-ps-1"
        assert manifest["spec"]["subdomain"] == "deepfm-ps"
        assert container["command"] == [
            "python", "-m", "elasticdl_tpu.ps.main"
        ]
        labels = manifest["metadata"]["labels"]
        assert labels["elasticdl-replica-type"] == "ps"


def _job_config(tmp_path, **kwargs):
    train = str(tmp_path / "train.rio")
    generate("mnist", train, 64)
    return JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=train,
        minibatch_size=16,
        num_minibatches_per_task=1,
        **kwargs,
    )


class TestMasterWithFakeFleet:
    def test_fleet_death_fails_job(self, tmp_path):
        config = _job_config(
            tmp_path, num_workers=1, max_worker_relaunch=0,
            relaunch_on_worker_failure=False,
        )
        backend = FakePodBackend()
        master = Master(config, pod_backend=backend)
        errors = []

        def run():
            try:
                master.run(poll_interval_s=0.05, reap_every_s=0.5)
            except RuntimeError as e:
                errors.append(e)

        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.2)
        backend.fail_pod(f"{config.job_name}-worker-0")
        t.join(timeout=10)
        assert not t.is_alive()
        assert errors and "terminated before the job finished" in str(errors[0])

    def test_pod_failure_bumps_membership(self, tmp_path):
        config = _job_config(tmp_path, num_workers=2)
        backend = FakePodBackend()
        master = Master(config, pod_backend=backend)
        master.pod_manager.start()
        master.rendezvous.register(f"{config.job_name}-worker-0")
        master.rendezvous.register(f"{config.job_name}-worker-1")
        v = master.rendezvous.version()
        backend.fail_pod(f"{config.job_name}-worker-1")
        assert master.rendezvous.version() > v
        # The relaunched pod re-registers itself when it comes up.
        assert f"{config.job_name}-worker-1-r1" in backend.running()
        master.shutdown()


WORKER_PY = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from elasticdl_tpu.worker.main import main
{hook}
sys.exit(main())
"""

CRASH_HOOK = """
# Crash the FIRST generation mid-task to exercise relaunch: the relaunched
# process sees the marker file and runs clean.
import elasticdl_tpu.worker.worker as W
marker = os.environ["CRASH_MARKER"]
if not os.path.exists(marker):
    open(marker, "w").close()
    _orig = W.Worker._run_training_task
    def _boom(self, task):
        os.kill(os.getpid(), 9)
    W.Worker._run_training_task = _boom
"""


def _process_backend(tmp_path, hook=""):
    script = tmp_path / "worker_entry.py"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script.write_text(WORKER_PY.format(repo=repo, hook=hook))
    return ProcessPodBackend(argv=[sys.executable, str(script)])


@pytest.mark.slow
class TestMasterProcessJob:
    def test_end_to_end_subprocess_job(self, tmp_path):
        config = _job_config(tmp_path, num_workers=2)
        master = Master(config, pod_backend=_process_backend(tmp_path))
        status = master.run(poll_interval_s=0.1)
        assert status["finished"]
        assert status["done"] == 4  # 64 records / 16-record tasks
        # model_version is the max of per-worker local step counters; with the
        # 4 tasks split across 2 workers it lands in [2, 4].
        assert 2 <= status["model_version"] <= 4

    def test_worker_crash_relaunch_completes_job(self, tmp_path):
        config = _job_config(tmp_path, num_workers=1, max_worker_relaunch=2)
        backend = _process_backend(tmp_path, hook=CRASH_HOOK)
        marker = str(tmp_path / "crashed.marker")
        os.environ["CRASH_MARKER"] = marker
        try:
            master = Master(config, pod_backend=backend)
            status = master.run(poll_interval_s=0.1)
        finally:
            os.environ.pop("CRASH_MARKER", None)
        assert os.path.exists(marker)  # the crash really happened
        assert status["finished"] and status["done"] == 4


# ---------------------------------------------------------------------------
# k8s watch-event mapping (VERDICT r3 item 8): synthetic events through the
# same mapping/loop the in-cluster watcher drives, no cluster needed.
# ---------------------------------------------------------------------------


def _fake_pod(name, phase, exit_code=None, broken=False):
    from types import SimpleNamespace as NS

    if broken:
        # attribute access explodes like a half-populated API object
        class Boom:
            @property
            def container_statuses(self):
                raise AttributeError("partial API object")

            phase = PodPhase.FAILED
        status = Boom()
    elif exit_code is None:
        status = NS(phase=phase, container_statuses=None)
    else:
        status = NS(
            phase=phase,
            container_statuses=[NS(state=NS(terminated=NS(exit_code=exit_code)))],
        )
    return {"object": NS(metadata=NS(name=name), status=status)}


def test_map_watch_event_phases():
    from elasticdl_tpu.master.pod_manager import (
        WORKER_RESTART_EXIT_CODE,
        map_watch_event,
    )

    assert map_watch_event(_fake_pod("w0", "Running")) == ("w0", PodPhase.RUNNING)
    assert map_watch_event(_fake_pod("w0", "Succeeded")) == (
        "w0", PodPhase.SUCCEEDED,
    )
    # Failed + RESTART exit code -> budget-free RESTART
    assert map_watch_event(
        _fake_pod("w1", "Failed", exit_code=WORKER_RESTART_EXIT_CODE)
    ) == ("w1", PodPhase.RESTART)
    # Failed + real failure exit code -> FAILED (consumes relaunch budget)
    assert map_watch_event(_fake_pod("w2", "Failed", exit_code=1)) == (
        "w2", PodPhase.FAILED,
    )
    # Failed with no container statuses -> FAILED
    assert map_watch_event(_fake_pod("w3", "Failed")) == ("w3", PodPhase.FAILED)
    # Half-populated API object: mapping must not raise, stays FAILED
    assert map_watch_event(_fake_pod("w4", "Failed", broken=True)) == (
        "w4", PodPhase.FAILED,
    )


def test_run_watch_loop_reestablishes_and_feeds_slots():
    """The loop survives a stream that dies mid-watch (410 Gone analogue)
    and keeps emitting; RESTART events reach the PodManager relaunch logic
    without consuming the failure budget (wired end-to-end elsewhere via
    FakePodBackend — here we pin the k8s-side mapping feeding _emit)."""
    import threading

    from elasticdl_tpu.master.pod_manager import (
        WORKER_RESTART_EXIT_CODE,
        run_watch_loop,
    )

    stop = threading.Event()
    seen = []
    rounds = []

    def stream_factory():
        rounds.append(1)
        if len(rounds) == 1:
            def first():
                yield _fake_pod("w0", "Running")
                raise RuntimeError("410 Gone")
            return first()

        def second():
            yield _fake_pod("w0", "Failed", exit_code=WORKER_RESTART_EXIT_CODE)
            stop.set()
            yield _fake_pod("w9", "Running")  # consumed; loop exits after
        return second()

    run_watch_loop(stream_factory, lambda n, p: seen.append((n, p)), stop,
                   backoff_s=0.01)
    assert ("w0", PodPhase.RUNNING) in seen
    assert ("w0", PodPhase.RESTART) in seen
    assert len(rounds) == 2


# ---------------------------------------------------------------------------
# Warm-standby spare (VERDICT r4 Next #4b): the backend parks one pre-booted
# process and hands it its worker id via the go-file at relaunch time.
# ---------------------------------------------------------------------------

STANDBY_STUB = """
import json, os, sys, time
go = os.environ.get("ELASTICDL_STANDBY_GO_FILE")
out = os.environ["STANDBY_TEST_OUT"]
if go:
    # Mirror worker.main's standby protocol: a configurable "import
    # warmup", then the atomic readiness marker adoption gates on.
    time.sleep(float(os.environ.get("STANDBY_WARMUP_S", "0")))
    with open(go + ".ready.tmp", "w") as f:
        f.write(str(os.getpid()))
    os.replace(go + ".ready.tmp", go + ".ready")
    while not os.path.exists(go):
        time.sleep(0.01)
    payload = json.loads(open(go).read())
    for k, v in payload.get("env", {}).items():
        os.environ[k] = v
    wid = payload["worker_id"]
    mode = "warm"
else:
    wid = os.environ["ELASTICDL_WORKER_ID"]
    mode = "cold"
slot = os.environ.get("ELASTICDL_WORKER_SLOT", "?")
# Atomic marker: the test polls for this file's EXISTENCE, so a plain
# open-then-write can be observed empty on a starved box.
marker = os.path.join(out, f"ran.{wid}")
with open(marker + ".tmp", "w") as f:
    f.write(f"{mode}:{os.getpid()}:{slot}")
os.replace(marker + ".tmp", marker)
time.sleep(60)  # stay 'running' like a real worker
"""


def _wait(cond, timeout=15.0, what="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def _spare_ready(backend) -> bool:
    """A parked spare exists AND has published its readiness marker (the
    adoption gate)."""
    with backend._lock:
        spares = list(backend._standby)
    return any(os.path.exists(go + ".ready") for _, go, _ in spares)


def test_warm_standby_adopted_on_relaunch(tmp_path):
    script = tmp_path / "stub.py"
    script.write_text(STANDBY_STUB)
    backend = ProcessPodBackend(
        argv=[sys.executable, str(script)], warm_standby=True
    )
    def env(name, slot, **extra):
        # Mirrors PodManager._pod_env: per-pod identity + job-static env.
        return {
            "ELASTICDL_WORKER_ID": name,
            "ELASTICDL_WORKER_SLOT": str(slot),
            "STANDBY_TEST_OUT": str(tmp_path),
            **extra,
        }

    try:
        backend.start_pod("w-0", env("w-0", 0))  # cold (no spare) + parks one
        _wait(lambda: (tmp_path / "ran.w-0").exists(), what="w-0 boot")
        assert (tmp_path / "ran.w-0").read_text().split(":") [::2] == [
            "cold", "0",
        ]
        _wait(lambda: _spare_ready(backend), what="spare parked + ready")
        spare_pid = backend._standby[0][0].pid

        # Adoption works across SLOTS (review r5: per-pod slot must ride the
        # go file, not the spawn signature) — relaunch slot 1 from the spare
        # parked by slot 0's launch.
        backend.start_pod("w-1", env("w-1", 1))
        _wait(lambda: (tmp_path / "ran.w-1").exists(), what="w-1 adoption")
        mode, pid, slot = (tmp_path / "ran.w-1").read_text().split(":")
        assert (mode, slot) == ("warm", "1") and int(pid) == spare_pid
        # A replacement spare was parked for the NEXT relaunch.
        _wait(
            lambda: len(backend._standby) == 1
            and backend._standby[0][0].pid != spare_pid,
            what="replacement spare",
        )

        # Job-static env change invalidates the spare: next launch is cold.
        backend.start_pod("w-2", env("w-2", 2, EXTRA="x"))
        _wait(lambda: (tmp_path / "ran.w-2").exists(), what="w-2 boot")
        assert (tmp_path / "ran.w-2").read_text().startswith("cold:")
        standby_dir = backend._standby_dir
        assert standby_dir is not None and os.path.isdir(standby_dir)
    finally:
        backend.close()
    # close() reaps the spares AND their scratch dir — nothing outlives
    # the job.
    assert backend._standby == []
    assert not os.path.isdir(standby_dir)


def test_dead_spare_falls_back_to_cold_spawn(tmp_path):
    """A spare that died while parked must not be adopted — the launch
    degrades to a cold spawn (spares are latency, never correctness)."""
    script = tmp_path / "stub.py"
    script.write_text(STANDBY_STUB)
    backend = ProcessPodBackend(
        argv=[sys.executable, str(script)], warm_standby=True
    )
    env = {
        "ELASTICDL_WORKER_ID": "w-0",
        "ELASTICDL_WORKER_SLOT": "0",
        "STANDBY_TEST_OUT": str(tmp_path),
    }
    try:
        backend.start_pod("w-0", env)
        _wait(lambda: _spare_ready(backend), what="spare parked + ready")
        backend._standby[0][0].kill()  # the spare dies while parked
        backend._standby[0][0].wait(timeout=10)

        env2 = dict(env, ELASTICDL_WORKER_ID="w-1", ELASTICDL_WORKER_SLOT="1")
        backend.start_pod("w-1", env2)
        _wait(lambda: (tmp_path / "ran.w-1").exists(), what="w-1 boot")
        assert (tmp_path / "ran.w-1").read_text().startswith("cold:")
        # And the pool healed itself with a fresh live spare.
        _wait(
            lambda: len(backend._standby) == 1
            and backend._standby[0][0].poll() is None,
            what="pool refilled",
        )
    finally:
        backend.close()


def test_standby_churn_two_kills_first_warm_second_cold(tmp_path):
    """Back-to-back kills against a pool of ONE: the first relaunch
    splices the parked spare in, the second (pool still refilling or
    drained) degrades to a cold spawn, and the pool refills behind both —
    spares are latency, never a correctness dependency.  The standby
    lifecycle instants (standby:spawn/adopt/refill) make the whole cycle
    attributable in a merged trace."""
    from elasticdl_tpu.common import trace

    script = tmp_path / "stub.py"
    script.write_text(STANDBY_STUB)
    backend = ProcessPodBackend(
        argv=[sys.executable, str(script)], warm_standby=True,
        standby_pool=1,
    )

    def env(name, slot):
        return {
            "ELASTICDL_WORKER_ID": name,
            "ELASTICDL_WORKER_SLOT": str(slot),
            "STANDBY_TEST_OUT": str(tmp_path),
            # A visible "import warmup": the refill spare spawned behind
            # the first adoption is NOT ready when the second relaunch
            # arrives, which is exactly the burst-beyond-the-pool case.
            "STANDBY_WARMUP_S": "1.0",
        }

    trace.configure(enabled=True)
    trace.default().clear()
    try:
        backend.start_pod("w-0", env("w-0", 0))
        backend.start_pod("w-1", env("w-1", 1))
        _wait(lambda: (tmp_path / "ran.w-0").exists(), what="w-0 boot")
        _wait(lambda: (tmp_path / "ran.w-1").exists(), what="w-1 boot")
        _wait(lambda: _spare_ready(backend), what="spare parked + ready")
        spare_pid = backend._standby[0][0].pid

        # Kill both ranks back-to-back, then relaunch both immediately —
        # the second relaunch arrives while the pool holds at most the
        # one spare the first relaunch is about to take.
        for name in ("w-0", "w-1"):
            with backend._lock:
                proc = backend._procs[name]
            proc.kill()
            proc.wait(timeout=10)
        backend.start_pod("w-0-r1", env("w-0-r1", 0))
        backend.start_pod("w-1-r1", env("w-1-r1", 1))
        _wait(lambda: (tmp_path / "ran.w-0-r1").exists(), what="w-0-r1 boot")
        _wait(lambda: (tmp_path / "ran.w-1-r1").exists(), what="w-1-r1 boot")
        first = (tmp_path / "ran.w-0-r1").read_text().split(":")
        second = (tmp_path / "ran.w-1-r1").read_text().split(":")
        # First splices the parked spare (same pid), second went cold.
        assert first[0] == "warm" and int(first[1]) == spare_pid
        assert second[0] == "cold"
        # The pool healed behind the churn.
        _wait(lambda: backend.standby_depth() == 1, what="pool refilled")

        names = [e["name"] for e in trace.default().export()]
        assert "standby:spawn" in names     # initial park
        assert "standby:adopt" in names     # the splice
        assert "standby:refill" in names    # the post-adoption top-up
        # The splice timeline's adopt stage rides the same moment.
        splices = [
            e for e in trace.default().export()
            if e["name"] == "elastic:splice"
        ]
        assert any(e["args"]["stage"] == "adopt" for e in splices)
    finally:
        trace.configure(enabled=False)
        trace.default().clear()
        backend.close()
