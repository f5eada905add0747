"""Seconds of named PhaseTimers buckets inside the window, over the
window's wall, its tasks or its records.

``params``: ``phases`` (bucket names), ``over`` (``wall`` | ``tasks`` |
``records``), ``scale`` (100 for %, 1e3 for ms, 1e6 for us).  The buckets
are the worker's own (``common/metrics.py``); the cumulative snapshot
rides every task report into the master's metrics.jsonl.
"""


def read(ctx: dict, params: dict):
    phases = ctx["phases"]
    if not any(name in phases for name in params["phases"]):
        return None
    seconds = sum(phases.get(name, 0.0) for name in params["phases"])
    tasks = ctx["window"]["reports"] - 1
    over = {
        "wall": ctx["phases_span_s"],
        "tasks": tasks,
        "records": tasks * ctx["records_per_task"],
    }[params["over"]]
    if not over or over <= 0:
        return None
    return float(params["scale"]) * seconds / over
