"""Worker: the task-pulling training/eval/predict loop.

Reference parity (SURVEY.md §2 #7-9, §3.3-3.4 [U/D]): the worker registers
with the master, pulls shard tasks over RPC, runs the jitted mesh step on
each shard's minibatches, reports results/metrics, and on a membership-version
change re-forms its mesh from the latest checkpoint (the reference's elastic
Horovod retry path, §3.5).
"""


def __getattr__(name: str):
    # Lazily (PEP 562): ``python -m elasticdl_tpu.worker.main`` imports
    # this package first, and main.py's first statement is the first stamp
    # of the worker's set-up chain; the heavy imports (jax, the trainer)
    # belong after it, in ``setup:imports``.
    if name in ("DirectMasterProxy", "RpcMasterProxy", "Worker"):
        from elasticdl_tpu.worker import worker

        return getattr(worker, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
