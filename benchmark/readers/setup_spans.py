"""One quantity of the job's set-up, from the ``setup`` records the
program writes into the master's ``metrics.jsonl`` (PR 35): the master's
chain of spans (``setup:launch | shards | serve | spawn``, written once the
fleet is spawned) and the worker incarnation's (``setup:interp | imports |
register | device_open | build | init_state | first_prep | first_dispatch |
first_step``, with the index scan inside the build as the child
``setup:shards``; it rides the worker's first training report).  Each span
is ``<span>_t0`` / ``<span>_t1`` in epoch seconds on the clock that stamps
every record's ``ts``, so ``t0`` — the window's first instant,
``ctx["window"]["ts"][0]`` — closes the sum:

    t0 - the launcher's first stamp
        = master_s + index_scan_s + worker_imports_s + device_open_s
        + init_state_s + worker_build_s + compile_s + warmup_s
        + unattributed_s

``params["what"]`` names the quantity (``QUANTITIES``).  The worker's
record is the LAST one stamped before ``t0``: a relaunched incarnation
sends a chain of its own, and the one whose first report precedes the
window is the one that set the window up.  None when the run's files are
not there or a program older than PR 35 wrote no ``setup`` record.
"""

import os

import runfiles

QUANTITIES = (
    "master_s", "index_scan_s", "worker_imports_s", "device_open_s", "init_state_s",
    "worker_build_s", "compile_s", "warmup_s", "unattributed_s", "cache_served_pct",
)
#: the durations that, with ``unattributed_s``, add up to ``t0`` - the launcher's first stamp
DURATIONS = QUANTITIES[:8]


def span(record: dict, name: str) -> float:
    """Seconds of one span of a record; 0.0 for a span it does not hold."""
    t0, t1 = record.get(name + "_t0"), record.get(name + "_t1")
    return 0.0 if t0 is None or t1 is None else t1 - t0


def chains(records: list, t0: float):
    """(the master's ``setup`` record, the worker's): the last of each
    stamped no later than ``t0``; None for one that is not there."""
    master = worker = None
    for r in records:
        if r.get("kind") != "setup" or r["ts"] > t0:
            continue
        if "setup:launch_t0" in r:
            master = r
        elif "setup:first_dispatch_t1" in r:
            worker = r
    return master, worker


def quantities(master: dict, worker: dict, t0: float) -> dict:
    first = master["setup:launch_t0"]
    spawned = master["setup:spawn_t1"]
    scan_master, scan_worker = span(master, "setup:shards"), span(worker, "setup:shards")
    # a worker no pod manager launched (no interp span): from the spawn's end to its first stamp
    interp = span(worker, "setup:interp") if "setup:interp_t0" in worker else worker["setup:imports_t0"] - spawned
    out = {
        "master_s": spawned - first - scan_master,
        "index_scan_s": scan_master + scan_worker,
        "worker_imports_s": interp + span(worker, "setup:imports"),
        "device_open_s": span(worker, "setup:device_open"),
        "init_state_s": span(worker, "init_state"),
        "worker_build_s": span(worker, "setup:register") + span(worker, "setup:build") - scan_worker
        + span(worker, "setup:first_prep"),
        "compile_s": span(worker, "setup:first_dispatch"),
        "warmup_s": t0 - worker["setup:first_dispatch_t1"],
    }
    out["unattributed_s"] = (t0 - first) - sum(out[k] for k in DURATIONS)
    requests = worker.get("cache_hits", 0.0) + worker.get("cache_misses", 0.0)
    out["cache_served_pct"] = 100.0 * worker.get("cache_hits", 0.0) / requests if requests > 0 else None
    return out


def read(ctx: dict, params: dict):
    ts = (ctx.get("window") or {}).get("ts") or []
    directory = runfiles.run_dir(ctx)
    if directory is None or not ts:
        return None
    records = runfiles.read_records(os.path.join(directory, "metrics", "metrics.jsonl"))
    master, worker = chains(records, ts[0])
    if master is None or worker is None:
        return None
    return quantities(master, worker, ts[0])[params["what"]]
