"""Checkpoint save/restore on Orbax.

Reference parity (SURVEY.md §2 #18, §5 [U]): the reference snapshots the
model every ``--checkpoint_steps`` (PS shards dump their slices; in AllReduce
mode worker-0 saves) and restores on restart — checkpoint restore is also how
an elastically re-formed job resumes.  Here Orbax saves the full TrainState
pytree — including mesh-sharded embedding tables, which Orbax reads/writes
per-shard from each device's HBM — and restores it **into any mesh shape**,
which is exactly the elastic 4->8->4 path: the checkpoint is
topology-agnostic, the restore target's shardings belong to the new mesh.

Format contract (r11): optimizer state is ALWAYS stored in the CANONICAL
layout — param-shaped leaves, never the flat dp-sharded layout of
``--optimizer_sharding`` — because the flat layout's global shapes depend
on the world size that wrote them.  Writers go through
``Trainer.host_state`` (or the jitted ``Trainer.snapshot_state`` for
group-mode collective saves); readers restore through
``Trainer.restore_template`` / ``adopt_restored``, which re-shard the
canonical leaves into whatever layout the live mesh runs.  This is what
lets a checkpoint written by a 4-way sharded job restore into an 8-way or
replicated one (tests/test_elastic.py).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

import jax

from elasticdl_tpu.common import durable, trace
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("checkpoint")

#: The published-checkpoint manifest: a tiny JSON file next to the Orbax
#: step dirs naming the newest step whose save (dense state AND host-store
#: shards) is COMPLETE.  The serving tier's checkpoint watcher keys off this
#: file — never off directory listings, which show steps mid-write.
MANIFEST_NAME = "checkpoint_manifest.json"  # durable-file


def publish_manifest(
    directory: str,
    step: int,
    code_rev: str = "",
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Atomically publish ``step`` as the newest complete checkpoint.

    The durable.atomic_publish commit: a reader (the serving watcher,
    possibly in another process) sees either the previous manifest or the
    new one, never a half-written file.  The caller must only publish
    AFTER the checkpoint itself is fully committed (Orbax wait + host-store
    snapshot): the manifest is the happens-after edge serving relies on.
    """
    path = os.path.join(directory, MANIFEST_NAME)
    payload = {
        "step": int(step),
        "code_rev": code_rev,
        "published_utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
    }
    if extra:
        payload.update(extra)
    durable.atomic_publish_json(path, payload)
    # The publish is the training->serving hand-off edge: its instant in
    # the merged trace is what publish-to-live latency is measured between
    # (pairs with the watcher's serving:hot_reload instant).
    trace.instant("ckpt:publish", cat="elastic", step=int(step))
    return path


# recovery-path
def read_manifest(directory: str) -> Optional[Dict[str, Any]]:
    """The published manifest, or None when absent/unreadable.  Tolerant by
    design (durable.read_json_tolerant): a missing or garbage manifest
    means "nothing published yet", not an error — fresh checkpoint dirs
    and pre-manifest checkpoints both look that way."""
    path = os.path.join(directory, MANIFEST_NAME)
    m = durable.read_json_tolerant(path)
    if not isinstance(m, dict) or not isinstance(m.get("step"), int):
        return None
    return m


class CheckpointManager:
    def __init__(self, directory: str, keep_max: int = 3):
        # Orbax (and the tensorstore / cloud-logging stack under it) is
        # imported HERE, where a job that checkpoints builds its manager,
        # and not with this module: seconds of every worker's start that
        # a job without ``checkpoint_dir``, and the serving watcher's
        # ``read_manifest``, never use.  The worker builds its manager
        # before the first task, so no save or restore is the first to
        # import.
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep_max, create=True, enable_async_checkpointing=True
            ),
        )

    def save(self, step: int, state: Any, wait: bool = False) -> None:
        """Async snapshot (training continues while Orbax writes)."""
        self._mgr.save(step, args=self._ocp.args.StandardSave(state))
        if wait:
            self._mgr.wait_until_finished()

    def restore(self, state_like: Any, step: Optional[int] = None) -> Any:
        """Restore into the sharding/structure of ``state_like`` (an abstract
        or concrete TrainState whose arrays carry the TARGET mesh's
        shardings — this is what makes restore-into-new-topology work)."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
            if hasattr(x, "sharding")
            else jax.ShapeDtypeStruct(x.shape, x.dtype),
            state_like,
        )
        return self._mgr.restore(
            step, args=self._ocp.args.StandardRestore(abstract)
        )

    def publish(
        self,
        step: int,
        code_rev: str = "",
        extra: Optional[Dict[str, Any]] = None,
    ) -> str:
        """Publish ``step`` for online consumers (the serving watcher) —
        AFTER draining any in-flight async save, so the manifest can never
        name a step Orbax has not finished committing.  Host-store snapshots
        must already be on disk when this is called (the worker save paths
        order it last)."""
        self._mgr.wait_until_finished()
        return publish_manifest(self.directory, step, code_rev=code_rev, extra=extra)

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> list:
        """Retained checkpoint steps, newest first (torn-checkpoint fallback
        walks these until one restores completely)."""
        return sorted(self._mgr.all_steps(), reverse=True)

    def wait(self) -> None:
        self._mgr.wait_until_finished()

    def close(self) -> None:
        self._mgr.wait_until_finished()
        self._mgr.close()
