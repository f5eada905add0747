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
    flags.update(config.get("job_flags", {}))
    flags.update(traffic.get("job_flags", {}))
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


class Job:
    def __init__(self, argv: list, work: str, platform: str, cache_dir: str):
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
        has ended (the worker holds the chip until it has)."""
        pgid = self.proc.pid
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
