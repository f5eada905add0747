"""Device-idle time of the trace that lies under named spans of the
worker's task loop, per traced task, in ms.

The idle time is the gaps between ``XLA Ops`` events from the first step
program's start to the last one's end (``params["module"]``, a pattern on
the ``XLA Modules`` events).  The spans are the program's own
(``common/trace.py``), written into the same trace while the worker's
profile window is open, so both are on one clock.  Each idle nanosecond
goes to the innermost covering span among ``params["known"]`` (the names
some metric of the cell claims); ``params["spans"]`` picks the names this
metric sums, ``"rest": true`` what lies under none of the known ones.  The
metrics of one cell that share ``known`` add up to the trace's idle time.
Absent when the trace holds no task-loop spans (``runfiles.py``).
"""

import runfiles


def read(ctx: dict, params: dict):
    path = runfiles.trace_path(ctx)
    if path is None:
        return None
    found = runfiles.idle_by_span(path, params["module"], tuple(params["known"]))
    if found is None or not found["tasks"]:
        return None
    if params.get("rest"):
        ns = found["rest"]
    else:
        ns = sum(found["under"].get(name, 0.0) for name in params["spans"])
    return ns / 1e6 / found["tasks"]
