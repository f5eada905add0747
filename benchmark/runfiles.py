"""What a reader cannot get from ``ctx``: the files of the run it is called in.

``run.py`` hands a reader ``ctx`` and ``params`` only, and ``ctx`` holds
neither the run's directory nor the host planes of the trace.  The run's
directory is found from what ``ctx`` does hold: the configuration's name x
the traffic mix's name x the chip count name exactly one cell of
``BENCHMARK.json``, and ``run.py`` keeps a cell's last run under
``benchmark/.state/runs/<cell>/`` (``profile/`` and
``metrics/metrics.jsonl``).  Everything here returns None (or an empty
list) when there is nothing to read: a ``ctx`` without those keys, no cell
or more than one, no files, a trace without the program's host spans (a
program older than PR 24 writes none).  A reader then reports no metric,
never a zero.

The host's spans: while its ``--profile_dir`` window is open the worker
emits every span of ``common/trace.py`` as a ``jax.profiler``
annotation, so they are events of plane ``/host:CPU`` in the same
``*.xplane.pb`` as the device planes, on the session's one clock, one line
per thread, keyword arguments kept as event stats (``task``, ``seq``).
The TASK-LOOP thread is the line that holds the ``dispatch`` spans.
"""

from __future__ import annotations

import functools
import json
import os
import re

import clock
import xplane

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

HOST_PLANE = "/host:CPU"
LOOP_MARKER = "dispatch"


# ------------------------------------------------------- the run's files


def run_dir(ctx: dict, root: str | None = None):
    """``<root>/benchmark/.state/runs/<cell>`` of the run ``ctx`` belongs
    to; ``root`` defaults to this checkout."""
    root = root or ROOT
    try:
        key = (ctx["config"]["name"], ctx["traffic"]["name"], ctx["chips"])
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            cells = json.load(f)["workloads"]
    except (KeyError, TypeError, OSError, ValueError):
        return None
    found = [w["name"] for w in cells if (w["config"], w["traffic"], w["chips"]) == key]
    if len(found) != 1:
        return None
    path = os.path.join(root, "benchmark", ".state", "runs", found[0])
    return path if os.path.isdir(path) else None


def read_records(path: str) -> list:
    """Every complete line of a ``metrics.jsonl`` (a torn last line is
    dropped); nothing when the file is not there."""
    try:
        with open(path) as f:
            text = f.read()
    except OSError:
        return []
    return [json.loads(line) for line in text.split("\n")[: text.count("\n")] if line]


def counter_records(ctx: dict) -> list:
    """The ``counter`` records (the worker's own cumulative counters, one
    per training report, stamped by the master) between the window's first
    and last task report: the same span the report clock measures."""
    ts = (ctx.get("window") or {}).get("ts") or []
    directory = run_dir(ctx)
    if directory is None or len(ts) < 2:
        return []
    records = read_records(os.path.join(directory, "metrics", "metrics.jsonl"))
    return clock.window_records(records, "counter", ts[0], ts[-1])


def trace_path(ctx: dict):
    directory = run_dir(ctx)
    return None if directory is None else xplane.find_xplane(os.path.join(directory, "profile"))


# ------------------------------------------------------- the host's spans


def host_lines(path: str) -> list:
    """[(thread's line name, [(start_ns, end_ns, name, stats), ...] by
    start, longer first on ties)] of the host plane."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            events = [
                (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name, dict(e.stats))
                for e in line.events
            ]
            events.sort(key=lambda e: (e[0], -e[1]))
            out.append((line.name, events))
    return out


def loop_line(lines: list):
    """The task loop's events: the line with the most ``dispatch`` spans
    (other threads never dispatch); None when no line has one."""
    best, count = None, 0
    for _, events in lines:
        n = sum(1 for e in events if e[2] == LOOP_MARKER)
        if n > count:
            best, count = events, n
    return best


def innermost_segments(spans: list) -> list:
    """Disjoint ``(start, end, name)`` pieces of one thread's properly
    nested spans, each piece named after the innermost span covering it."""
    segments = []
    stack: list = []  # (end, name)
    cursor = 0.0

    def close(upto: float) -> None:
        nonlocal cursor
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > cursor:
                segments.append((cursor, end, name))
                cursor = end

    for start, end, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(start)
        if stack and start > cursor:
            segments.append((cursor, start, stack[-1][1]))
        cursor = max(cursor, start)
        stack.append((min(end, stack[-1][0]) if stack else end, name))
    close(float("inf"))
    return segments


def attribute(gaps: list, spans: list) -> dict:
    """Nanoseconds of the ``gaps`` (``(start_ns, length_ns)``) under each
    span name, every nanosecond given to the innermost covering span; key
    None holds what lies under no span.  The values add up to the gaps."""
    segments = innermost_segments(spans)
    under: dict = {None: 0.0}
    i = 0
    for start, length in sorted(gaps):
        end = start + length
        while i < len(segments) and segments[i][1] <= start:
            i += 1
        covered = 0.0
        j = i
        while j < len(segments) and segments[j][0] < end:
            seg_start, seg_end, name = segments[j]
            piece = min(end, seg_end) - max(start, seg_start)
            if piece > 0:
                under[name] = under.get(name, 0.0) + piece
                covered += piece
            j += 1
        under[None] += length - covered
    return under


def clip(gaps: list, lo: float, hi: float) -> list:
    out = []
    for start, length in gaps:
        a, b = max(start, lo), min(start + length, hi)
        if b > a:
            out.append((a, b - a))
    return out


@functools.lru_cache(maxsize=4)
def idle_by_span(path: str, module_pattern: str, known: tuple):
    """Device-idle time of one trace, put down to the task loop's spans.

    Per device plane: the gaps between ``XLA Ops`` events
    (``xplane.busy_and_gaps``) from the first step program's start to the
    last one's end (``XLA Modules`` events matching ``module_pattern``),
    each nanosecond given to the innermost covering span of the task-loop
    thread among the ``known`` names.  Averaged over the planes.  Returns
    ``{"under": {name: ns}, "rest": ns, "idle_ns", "window_ns", "tasks"}``
    (``tasks`` = step-program executions per plane), or None without a
    device plane, a step program or a task-loop line."""
    loop = loop_line(host_lines(path))
    lines = xplane.load_lines(path, (xplane.OP_LINE, xplane.MODULE_LINE))
    planes = lines[xplane.OP_LINE]
    if loop is None or not planes:
        return None
    spans = [(s, e, name) for s, e, name, _ in loop if name in known]
    under: dict = {}
    idle = window = tasks = 0.0
    for plane, events in planes.items():
        steps = [m for m in lines[xplane.MODULE_LINE].get(plane, []) if re.search(module_pattern, m[2])]
        if not steps:
            return None
        lo, hi = steps[0][0], max(m[1] for m in steps)
        gaps = clip(xplane.busy_and_gaps(events)["gaps"], lo, hi)
        for name, ns in attribute(gaps, spans).items():
            under[name] = under.get(name, 0.0) + ns
        idle += sum(length for _, length in gaps)
        window += hi - lo
        tasks += len(steps)
    n = len(planes)
    rest = under.pop(None, 0.0)
    return {
        "under": {name: ns / n for name, ns in under.items()},
        "rest": rest / n,
        "idle_ns": idle / n,
        "window_ns": window / n,
        "tasks": tasks / n,
    }


def clock_check(path: str, module_pattern: str) -> list:
    """Are the host's spans and the device's events on one clock?  The
    device runs programs in dispatch order, so the step program's
    executions on the first device plane and the task loop's ``dispatch``
    spans pair up in order FROM THE END of the trace (the executions at
    its beginning that are left over belong to tasks dispatched before the
    window opened).  For each pair: the dispatch must begin before the
    execution begins, and the ``step_wait``/``metrics`` span that settles
    the same task must end after the execution ends.  One dict per pair,
    in trace order; ``settled_after_end`` is None where the trace closed
    before the task's settle."""
    loop = loop_line(host_lines(path)) or []
    dispatches = [e for e in loop if e[2] == LOOP_MARKER]
    modules = xplane.load_lines(path, (xplane.MODULE_LINE,))[xplane.MODULE_LINE]
    if not dispatches or not modules:
        return []
    steps = [m for m in sorted(modules.items())[0][1] if re.search(module_pattern, m[2])]
    n = min(len(dispatches), len(steps))
    out = []
    for (d_start, _, _, stats), (m_start, m_end, _) in zip(dispatches[len(dispatches) - n:], steps[len(steps) - n:]):
        settles = [e[1] for e in loop if e[2] in ("step_wait", "metrics") and e[3].get("task") == stats.get("task")]
        out.append({
            "task": stats.get("task"),
            "seq": stats.get("seq"),
            "dispatch_to_start_us": (m_start - d_start) / 1e3,
            "end_to_settle_us": (max(settles) - m_end) / 1e3 if settles else None,
            "dispatch_before_start": d_start < m_start,
            "settled_after_end": (max(settles) > m_end) if settles else None,
        })
    return out
