"""Launch one real ``elasticdl train --local`` job, watch it through the
channels the program already has, and stop it.

The launcher is the benchmark's own copy of ``chip_smoke.py:_train_job``
(PR 21), without the smoke sizes: this process never imports jax (a process
that has touched jax holds the chip), the job runs in its own session, and
nothing the job started outlives :meth:`Job.stop`.
"""

from __future__ import annotations

import glob
import json
import os
import re
import signal
import subprocess
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

#: One numbered entry per chip's IOMMU group.  The kernel gives a group back
#: seconds AFTER the process that held it has left /proc (8-10 s for the
#: four of a v5e host, 3-6 s for one, PERF.md section 6): until then the
#: next process's TPU open dies with ``Device or resource busy``.
VFIO_DIR = "/dev/vfio"
CHIPS_FREE_DEADLINE_S = 60.0

#: What the job's environment holds besides the harness's own paths.  The TPU
#: runtime pins a host buffer for its transfers when a process opens the chip;
#: at its default size that is most of an open of 5.6-11.1 s which wanders by
#: seconds from run to run on one machine, at 256 MiB the open is about 2 s
#: (PERF.md, section 6, PR 63: the noise `setup_s` was refused for).  The cells
#: move a few MB a step from the host; a cell that moves more than the buffer
#: in one transfer (a checkpoint's save) sizes it in its own data file: a
#: configuration's or a traffic mix's ``job_env`` comes after this one.
JOB_ENV = {"TPU_PREMAPPED_BUFFER_SIZE": str(256 << 20)}


class JobFailed(Exception):
    """The job cannot give a measurement (no chip, a dead worker, ...)."""


def model_params_flag(params: dict) -> str:
    """``k=v;k=v`` with JSON values: the program runs each value through
    ``json.loads``, so ``False`` must be spelled ``false``."""
    return ";".join(f"{k}={json.dumps(v, separators=(',', ':'))}" for k, v in params.items())


def job_argv(config: dict, traffic: dict, data_dir: str, work: str, extra: dict) -> list:
    flags = {
        "job_name": "bench-" + re.sub(r"[^a-z0-9]+", "-", config["name"].lower()),
        "model_def": config["model_def"],
        "model_params": model_params_flag(config["model_params"]),
        "distribution_strategy": config["distribution_strategy"],
        "training_data": data_dir,
        "minibatch_size": traffic["minibatch_size"],
        "num_minibatches_per_task": traffic["minibatches_per_task"],
        "num_epochs": traffic["num_epochs"],
        "num_workers": 1,
        "max_worker_relaunch": 0,
        "pod_log_dir": os.path.join(work, "pods"),
        "metrics_dir": os.path.join(work, "metrics"),
    }
    # A configuration's or a traffic mix's own flags; the literal ``{work}``
    # in a value is this run's directory (a checkpoint directory, say).
    for own in (config.get("job_flags", {}), traffic.get("job_flags", {})):
        flags.update({k: v.replace("{work}", work) if isinstance(v, str) else v for k, v in own.items()})
    flags.update(extra)
    argv = [sys.executable, "-m", "elasticdl_tpu.client.main", "train", "--local"]
    for key, value in flags.items():
        if isinstance(value, bool):
            value = "true" if value else "false"
        argv += [f"--{key}", str(value)]
    return argv


def _pids_in_group(pgid: int) -> list:
    pids = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            with open(f"/proc/{entry}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[2]) == pgid and fields[0] != "Z":
            pids.append(int(entry))
    return pids


def _opens(path: str) -> bool:
    try:
        os.close(os.open(path, os.O_RDWR))
    except OSError:
        return False
    return True


def _numbered_groups(vfio_dir: str) -> list:
    try:
        return [os.path.join(vfio_dir, g) for g in sorted(os.listdir(vfio_dir)) if g.isdigit()]
    except OSError:
        return []


def _groups_held(pids: list, vfio_dir: str) -> list:
    """The numbered groups under ``vfio_dir`` that one of ``pids`` has open
    (``/proc/<pid>/fd``)."""
    held = set()
    for pid in pids:
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if os.path.dirname(target) == vfio_dir.rstrip("/") and os.path.basename(target).isdigit():
                held.add(target)
    return sorted(held)


def wait_for_chips(groups: list, deadline_s: float) -> float:
    """Seconds waited until every one of ``groups`` opened (and was closed
    again at once), or until the deadline, which is said on stderr; no
    group, no wait."""
    t0 = time.time()
    while groups:
        groups = [g for g in groups if not _opens(g)]
        waited = time.time() - t0
        if groups and waited >= deadline_s:
            print(f"[bench] {groups} still busy {deadline_s:.0f} s after the job's end: going on", file=sys.stderr, flush=True)
        if not groups or waited >= deadline_s:
            return waited
        time.sleep(0.25)
    return 0.0


class Job:
    def __init__(self, argv: list, work: str, platform: str, cache_dir: str,
                 vfio_dir: str = VFIO_DIR, chips_deadline_s: float = CHIPS_FREE_DEADLINE_S,
                 job_env: dict | None = None):
        self.work = work
        self.metrics_path = os.path.join(work, "metrics", "metrics.jsonl")
        self.master_log = os.path.join(work, "master.log")
        self.probe_dir = os.path.join(work, "probe")
        for d in ("pods", "metrics", "probe"):
            os.makedirs(os.path.join(work, d), exist_ok=True)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = platform
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
        env["EDL_BENCH_PROBE_DIR"] = self.probe_dir
        env.update(JOB_ENV)
        env.update(job_env or {})
        env["PYTHONPATH"] = os.pathsep.join(
            [os.path.join(BENCH_DIR, "probe"), ROOT]
            + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
        )
        env.pop("BENCH_RUN", None)
        self._log = open(self.master_log, "w")
        self.proc = subprocess.Popen(
            argv, cwd=ROOT, env=env, stdout=self._log,
            stderr=subprocess.STDOUT, start_new_session=True,
        )
        self._requests = 0
        self.platform, self.vfio_dir, self.chips_deadline_s = platform, vfio_dir, chips_deadline_s
        #: the chips' groups the job's processes held when :meth:`stop` killed them
        self.groups_held: list = []
        #: what :meth:`wait_for_chips` polled, and the seconds it waited
        self.chips_waited_for: list = []
        self.chips_wait_s = 0.0

    # -- reading what the job writes --

    def records(self) -> list:
        """Every complete line of the master's metrics.jsonl so far."""
        try:
            with open(self.metrics_path) as f:
                text = f.read()
        except FileNotFoundError:
            return []
        out = []
        for line in text.split("\n")[: text.count("\n")]:
            if line:
                out.append(json.loads(line))
        return out

    def worker_log(self) -> str:
        text = ""
        for path in sorted(glob.glob(os.path.join(self.work, "pods", "*.log"))):
            with open(path, errors="replace") as f:
                text += f.read()
        return text

    def master_address(self) -> str:
        with open(self.master_log, errors="replace") as f:
            found = re.findall(r"master gRPC service on (\S+)", f.read())
        if not found:
            raise JobFailed("the master never logged its gRPC address")
        return found[-1]

    def job_status(self) -> dict:
        """The master's JobStatus RPC (done, duplicate_done, abandoned,
        phase_counts): what its final log line would have said."""
        sys.path.insert(0, ROOT)
        from elasticdl_tpu.common.rpc import JsonRpcClient

        client = JsonRpcClient(self.master_address())
        try:
            return client.call("JobStatus", {}, timeout_s=10.0)
        finally:
            client.close()

    def ask_probe(self, timeout_s: float = 5.0) -> dict | None:
        """One answer of the in-worker probe (probe/sitecustomize.py)."""
        self._requests += 1
        n = self._requests
        open(os.path.join(self.probe_dir, f"request.{n}"), "w").close()
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            found = glob.glob(os.path.join(self.probe_dir, f"answer.{n}.*.json"))
            if found:
                with open(found[0]) as f:
                    return json.load(f)
            time.sleep(0.05)
        return None

    # -- life cycle --

    def check_alive(self) -> None:
        # ``max_worker_relaunch 0``: a worker that cannot start (no TPU)
        # ends the master, so the job's exit is the one failure signal.
        rc = self.proc.poll()
        if rc is not None:
            raise JobFailed(f"the job exited with code {rc} before the window ended")

    def wait_for(self, predicate, timeout_s: float, what: str, poll_s: float = 0.05):
        deadline = time.time() + timeout_s
        while True:
            value = predicate()
            if value:
                return value
            self.check_alive()
            if time.time() > deadline:
                raise JobFailed(f"timed out after {timeout_s:.0f}s waiting for {what}")
            time.sleep(poll_s)

    def stop(self) -> None:
        """Kill the job's whole session and wait until every process of it
        has ended.  The kernel gives the chips back some seconds later: a
        process that opens the TPU next waits for them first
        (:meth:`wait_for_chips`)."""
        pgid = self.proc.pid
        if self.platform == "tpu":
            self.groups_held = _groups_held(_pids_in_group(pgid), self.vfio_dir)
        deadline = time.time() + 30.0
        while True:
            try:
                os.killpg(pgid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()
            if not _pids_in_group(pgid) or time.time() > deadline:
                break
            time.sleep(0.05)
        self._log.close()

    def wait_for_chips(self) -> float:
        """After :meth:`stop`, on a TPU: poll the groups the job held (every
        numbered group of the machine where none was seen held) until each
        opens again or the deadline passes; the seconds waited.  Only a run
        that starts another process on the chips (the traced run's reference
        child) calls this: an untraced run has nothing to wait for.  A
        machine without the directory (the CPU rehearsal) waits for nothing."""
        if self.platform == "tpu":
            self.chips_waited_for = self.groups_held or _numbered_groups(self.vfio_dir)
            self.chips_wait_s = wait_for_chips(self.chips_waited_for, self.chips_deadline_s)
        return self.chips_wait_s
