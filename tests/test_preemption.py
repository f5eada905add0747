"""Graceful preemption (SIGTERM -> snapshot -> RESTART exit -> resume).

k8s preemption delivers SIGTERM with a grace window before SIGKILL; the
worker's handler (worker.main._install_preemption_handler) snapshots the
live state when safe and exits RESTART_EXIT_CODE so the relaunch is
budget-free and resumes from the preemption step, not the last periodic
checkpoint.  This drives a REAL worker process: periodic checkpoints are
disabled, so any restorable step can only have come from the preemption
snapshot.
"""

from __future__ import annotations

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
from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(config, log_path):
    env = dict(os.environ)
    env.update(config.to_env())
    env["ELASTICDL_WORKER_ID"] = "preempt-w0"
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    with open(log_path, "w") as log:  # the child keeps its own fd
        return subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.worker.main"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=_REPO,
        )


@pytest.mark.slow
def test_sigterm_snapshots_and_resume(tmp_path):
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    path = str(tmp_path / "train.rio")
    generate("mnist", path, 256)
    shards = create_data_reader(path).create_shards(16)
    dispatcher = TaskDispatcher(shards, num_epochs=50)
    servicer = MasterServicer(dispatcher)
    server = MasterServer(servicer, port=0).start()
    procs = []
    try:
        config = JobConfig(
            model_def="mnist.model_spec",
            model_params="compute_dtype=float32",
            training_data=path,
            minibatch_size=16,
            num_epochs=50,
            master_addr=server.address,
            checkpoint_dir=str(tmp_path / "ckpt"),
            checkpoint_steps=0,  # snapshot can ONLY come from preemption
        )
        proc = _spawn(config, tmp_path / "w.log.0")
        procs.append(proc)
        deadline = time.time() + 240
        while time.time() < deadline:
            if servicer.JobStatus({})["done"] >= 2:
                break
            time.sleep(0.1)
        else:
            pytest.fail("worker never made progress")

        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=60)
        assert rc == RESTART_EXIT_CODE

        from elasticdl_tpu.common.checkpoint import CheckpointManager

        ckpt = CheckpointManager(config.checkpoint_dir)
        snap_step = ckpt.latest_step()
        assert snap_step is not None and snap_step > 0

        # Relaunch resumes FROM THE PREEMPTION SNAPSHOT and keeps training.
        done_before = servicer.JobStatus({})["done"]
        proc2 = _spawn(config, tmp_path / "w.log.1")
        procs.append(proc2)
        deadline = time.time() + 240
        while time.time() < deadline:
            if servicer.JobStatus({})["done"] > done_before:
                break
            time.sleep(0.2)
        else:
            pytest.fail("relaunch never resumed training")
        proc2.kill()
        log = (tmp_path / "w.log.1").read_text()
        assert f"joined from checkpoint step {snap_step}" in log
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        server.stop()
