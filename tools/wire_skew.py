"""wire_skew — the version-skew roundtrip proof (graftlint v8 / wiresan).

Runs a REAL gRPC job with a v1-masked worker against a current master:
the client arms wiresan's version mask (``GRAFT_WIRESAN_MASK`` semantics
via :func:`wiresan.set_mask`), so every outgoing request and incoming
response is stripped to exactly the fields a peer built at wire revision
1 would speak — no ``lease`` batching, no ``seq`` dedup ledger, no
``trace``/``gauge`` envelopes, no ``server_ts_us`` clock stamp.  The
additive-compat stance ("optional field, no PROTOCOL_VERSION bump",
r9/r12/r14/r18) is only real if that worker still completes the job with
ZERO wire violations and ZERO double-trains; this tool proves it
(``tests/test_wiresan.py`` holds the same ``run_skew`` in tier-1).

Usage:
    python tools/wire_skew.py [--shards N]

Exit 0 = the masked fleet completed clean; 1 = any wire violation,
undone task, double-train, or stale report.
"""

from __future__ import annotations

import argparse
import os
import sys
from typing import List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

# The mask refuses to arm unless the sanitizer is on (fail-loud stance);
# set BEFORE any rpc import so every hook in this process is live.
os.environ.setdefault("GRAFT_WIRESAN", "1")

#: The emulated peer's wire revision: the pre-r9 baseline — every field
#: added since (lease, requeue, seq, trace, gauge, phase_counts, ...) is
#: stripped both directions.
MASK_REV = 1


def run_skew(num_shards: int, log=print) -> dict:
    from elasticdl_tpu.common import wiresan
    from elasticdl_tpu.common.rpc import JsonRpcClient
    from elasticdl_tpu.data.reader import Shard
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    wiresan.reset()
    shards = [
        Shard(name=f"shard-{i}", start=i * 10, end=(i + 1) * 10)
        for i in range(num_shards)
    ]
    dispatcher = TaskDispatcher(shards)
    servicer = MasterServicer(dispatcher, rendezvous=RendezvousServer())
    server = MasterServer(servicer, port=0).start()
    verdict = {
        "mask_rev": MASK_REV,
        "shards": num_shards,
        "tasks_done": 0,
        "heartbeats": 0,
        "wire_violations": 0,
        "errors": [],
    }
    try:
        worker = JsonRpcClient(server.address)
        worker.wait_ready(10.0)
        # The v1 peer: the worker-side loop SENDS modern payloads (seq,
        # requeue, lease) and the mask strips them on the way out — the
        # proof must cover the stripping itself, not a hand-tailored old
        # payload.  Responses are masked too: a v1 worker never sees
        # tasks/entries batches or the server_ts_us stamp.
        wiresan.set_mask(MASK_REV)
        try:
            worker.call("RegisterWorker", {
                "worker_id": "w0", "proto": 2, "incarnation": "inc-1",
                "held_tasks": [],
            }, timeout_s=10.0)
            beat = worker.call(
                "Heartbeat", {"worker_id": "w0"}, timeout_s=10.0
            )
            verdict["heartbeats"] += 1
            if "server_ts_us" in beat:
                verdict["errors"].append(
                    "response mask leaked server_ts_us (since r12) to the "
                    "v1 peer"
                )
            seq = 0
            while True:
                resp = worker.call(
                    "GetTask", {"worker_id": "w0", "lease": 4},
                    timeout_s=10.0,
                )
                if "tasks" in resp:
                    verdict["errors"].append(
                        "response mask leaked the r9 'tasks' lease batch "
                        "to the v1 peer"
                    )
                task = resp.get("task")
                if task is None:
                    if resp["finished"]:
                        break
                    verdict["errors"].append(
                        "no task and not finished — the masked loop "
                        "would spin"
                    )
                    break
                seq += 1
                ack = worker.call("ReportTaskResult", {
                    "worker_id": "w0",
                    "task_id": int(task["task_id"]),
                    "success": True,
                    "task_type": str(task.get("type", "training")),
                    "seq": seq,
                    "requeue": False,
                }, timeout_s=10.0)
                if not ack.get("accepted"):
                    verdict["errors"].append(
                        f"report for task {task['task_id']} not accepted"
                    )
                verdict["tasks_done"] += 1
        finally:
            wiresan.set_mask(None)
        # The unmasked admin view settles the double-train question: the
        # masked worker sent NO seq ledger (stripped), so every report
        # had to be applied exactly once on its own merits.
        admin = JsonRpcClient(server.address)
        admin.wait_ready(10.0)
        status = admin.call("JobStatus", {}, timeout_s=10.0)
        verdict["job_status"] = {
            k: status[k]
            for k in ("todo", "doing", "done", "abandoned",
                      "duplicate_done", "stale_reports", "finished")
        }
        if status["done"] != num_shards:
            verdict["errors"].append(
                f"done={status['done']} != shards={num_shards}"
            )
        if status["duplicate_done"]:
            verdict["errors"].append(
                f"double-train: duplicate_done={status['duplicate_done']}"
            )
        if status["stale_reports"]:
            verdict["errors"].append(
                f"stale_reports={status['stale_reports']}"
            )
        if not status["finished"]:
            verdict["errors"].append("job not finished")
    except wiresan.WireSanViolation as e:
        verdict["errors"].append(f"wire violation: {e}")
    finally:
        server.stop(grace=0)
    stats = wiresan.stats()
    verdict["wiresan"] = stats
    verdict["wire_violations"] = stats["violations"]
    verdict["ok"] = not verdict["errors"] and not stats["violations"]
    log(
        f"wire_skew: mask_rev={MASK_REV} tasks_done={verdict['tasks_done']}"
        f"/{num_shards} violations={stats['violations']} "
        f"ok={verdict['ok']}"
    )
    for err in verdict["errors"]:
        log(f"wire_skew: FAIL {err}")
    return verdict


def main(argv: Optional[List[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="wire_skew", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--shards", type=int, default=8,
        help="training shards the masked worker must complete (default 8)",
    )
    args = parser.parse_args(argv)
    return 0 if run_skew(args.shards)["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
