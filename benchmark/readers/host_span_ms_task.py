"""Mean duration, in ms, of a named span of the program on named threads
of the traced run's host plane: the summed duration of the
``params["span"]`` events on the lines whose name starts with
``params["lines"]`` (``runfiles.host_lines``), over the tasks they belong
to (their distinct ``task`` stats; the events themselves where they carry
none).  With ``span`` = ``prep`` and ``lines`` = ``edl-prep_``: a task's
whole host half (read, decode, stack) on the prep-ahead threads, to hold
against the time the device takes for the same task.  None when the run
has no trace or the trace holds no such span (a program older than PR 24
writes none)."""

import runfiles


def read(ctx: dict, params: dict):
    path = runfiles.trace_path(ctx)
    if path is None:
        return None
    events = [
        e for line, found in runfiles.host_lines(path) if line.startswith(params["lines"])
        for e in found if e[2] == params["span"]
    ]
    if not events:
        return None
    tasks = {e[3]["task"] for e in events if "task" in e[3]}
    return sum(end - start for start, end, _, _ in events) / 1e6 / (len(tasks) or len(events))
