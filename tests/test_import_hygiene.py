"""What a launch imports (PR 43): a library that serves an OPTIONAL
subsystem is imported where that subsystem is built, not where its module
is imported.  Every check runs a child interpreter, so that what this test
process has imported decides nothing; a child a case, shared by the
module names it is asked about."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: Roots of ``sys.modules`` no worker start may hold: checkpoint libraries
#: of a job that names no ``checkpoint_dir``, and the deep-learning
#: frameworks a TensorBoard writer would drag in.
WORKER_FORBIDDEN = ["orbax", "tensorstore", "torch", "tensorflow", "tensorboardX"]
MASTER_FORBIDDEN = ["torch", "tensorflow", "tensorboardX"]

_REPORT = """
import json, sys
def roots():
    return sorted({name.split(".")[0] for name in sys.modules})
"""

WORKER_START = _REPORT + """
import elasticdl_tpu.worker.main
print(json.dumps({"loaded": roots()}))
"""

MASTER_START = _REPORT + """
import elasticdl_tpu.master.main
from elasticdl_tpu.common.metrics import MetricsWriter
writer = MetricsWriter(sys.argv[1])  # the mirror ON, as the master builds it
writer.write("train", 1, {"loss": 2.0})
writer.close()
print(json.dumps({"loaded": roots()}))
"""

#: A whole worker: built, then run to the end of its job.  With a
#: ``checkpoint_dir`` orbax arrives with the manager, in ``Worker.__init__``
#: — before any task, never with the first save or restore; without one it
#: never arrives at all.
WORKER_JOB = _REPORT + """
import os
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker
work, checkpoints = sys.argv[1], sys.argv[2] == "checkpoints"
train = os.path.join(work, "train.rio")
generate("mnist", train, 64)
config = JobConfig(
    model_def="mnist.model_spec", model_params="compute_dtype=float32",
    training_data=train, minibatch_size=16, num_minibatches_per_task=2,
    checkpoint_dir=os.path.join(work, "ckpt") if checkpoints else "", checkpoint_steps=2,
)
reader = create_data_reader(train)
servicer = MasterServicer(TaskDispatcher(reader.create_shards(32)))
spec = load_model_spec("elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32")
loaded = lambda: "orbax.checkpoint" in sys.modules
said = {"imported": loaded()}
worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec)
said.update(built=loaded(), done_when_built=servicer.JobStatus({})["done"])
worker.run()
said.update(ran=loaded(), done=servicer.JobStatus({})["done"], saved=os.path.isdir(config.checkpoint_dir))
print(json.dumps(said))
"""


def _child(script: str, *argv: str) -> dict:
    env = dict(os.environ, PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run(
        [sys.executable, "-c", script, *argv],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def worker_start():
    return _child(WORKER_START)["loaded"]


@pytest.fixture(scope="module")
def master_start(tmp_path_factory):
    return _child(MASTER_START, str(tmp_path_factory.mktemp("metrics")))["loaded"]


@pytest.mark.parametrize("module", WORKER_FORBIDDEN)
def test_a_worker_start_leaves_out(worker_start, module):
    assert "elasticdl_tpu" in worker_start and "jax" in worker_start
    assert module not in worker_start, (
        f"importing elasticdl_tpu.worker.main imports {module}: every launch "
        "pays for it; import it where the subsystem that needs it is built"
    )


@pytest.mark.parametrize("module", MASTER_FORBIDDEN)
def test_a_master_start_with_the_mirror_on_leaves_out(master_start, module):
    assert "elasticdl_tpu" in master_start
    assert module not in master_start, (
        f"the master's start and a mirrored MetricsWriter.write import {module}"
    )


@pytest.mark.parametrize(
    "checkpoints, orbax",
    [("checkpoints", {"imported": False, "built": True, "ran": True}),
     ("none", {"imported": False, "built": False, "ran": False})],
)
def test_orbax_arrives_with_the_checkpoint_manager_before_the_first_task(tmp_path, checkpoints, orbax):
    said = _child(WORKER_JOB, str(tmp_path), checkpoints)
    assert {k: said[k] for k in orbax} == orbax
    assert said["done_when_built"] == 0 and said["done"] == 2
    assert said["saved"] == (checkpoints == "checkpoints")
