"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to device busy
time, idle gaps, per-op time and a named kernel's time.

Read with ``jax.profiler.ProfileData`` alone (the program's
``tools/profile_step.py`` leans on the ``xprof`` converter).  A TPU device
is a plane named ``/device:TPU:<n>``; the line ``XLA Ops`` of that plane
holds one event per executed HLO op with start and duration in
nanoseconds.  Control-flow ops (``while``, ``conditional``) span the ops of
their bodies, so busy time is the UNION of the intervals, and an op's own
time is its duration less the events nested inside it.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE_RE = r"^/device:TPU:\d+$"
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(profile_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    return found[-1] if found else None


def load_lines(path: str, line_names: tuple, plane_re: str = DEVICE_PLANE_RE) -> dict:
    """One parse of the file: {line name: {plane name: [(start_ns, end_ns,
    event name), ...] sorted by start, longer first on ties}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out: dict = {name: {} for name in line_names}
    for plane in data.planes:
        if not re.search(plane_re, plane.name):
            continue
        for name in line_names:
            out[name][plane.name] = []
        for line in plane.lines:
            if line.name in out:
                out[line.name][plane.name] += [
                    (float(e.start_ns), float(e.start_ns) + float(e.duration_ns), e.name)
                    for e in line.events
                ]
        for name in line_names:
            out[name][plane.name].sort(key=lambda e: (e[0], -e[1]))
    return out


def load_op_events(path: str) -> dict:
    """{device plane name: the events of its ``XLA Ops`` line}."""
    return load_lines(path, (OP_LINE,))[OP_LINE]


def busy_and_gaps(events: list) -> dict:
    """Union of the op intervals of one device: busy ns, the span from the
    first op's start to the last op's end, and the idle gaps inside it."""
    if not events:
        return {"busy_ns": 0.0, "span_ns": 0.0, "gaps": []}
    busy = 0.0
    gaps = []
    cur_start, cur_end = events[0][0], events[0][1]
    for start, end, _ in events[1:]:
        if start > cur_end:
            busy += cur_end - cur_start
            gaps.append((cur_end, start - cur_end))
            cur_start, cur_end = start, end
        else:
            cur_end = max(cur_end, end)
    busy += cur_end - cur_start
    return {"busy_ns": busy, "span_ns": cur_end - events[0][0], "gaps": gaps}


def op_name(name: str) -> str:
    """One row of the breakdown per kind of op.  The TPU's events carry the
    whole HLO instruction (``%fusion.123 = f32[..] fusion(...)``): the row
    is the instruction's name without ``%`` and its number, and a Mosaic
    kernel (``custom_call_target="tpu_custom_call"``) says so."""
    short = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    if 'custom_call_target="tpu_custom_call"' in name:
        return "tpu_custom_call:" + short
    return short


def self_times(events: list) -> dict:
    """Own time per op name: each event's duration less the events nested
    inside it (``events`` sorted by start, longer first on ties)."""
    totals: dict = {}
    stack: list = []  # [end_ns, name, self_ns]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            _, name, own = stack.pop()
            totals[name] = totals.get(name, 0.0) + max(own, 0.0)

    for start, end, name in events:
        close(start)
        if stack:
            stack[-1][2] -= min(end, stack[-1][0]) - start
        stack.append([end, op_name(name), end - start])
    close(float("inf"))
    return totals


def kernel_events(events: list, pattern: str) -> list:
    """Durations (ns) of the events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    return [end - start for start, end, name in events if rx.search(name)]


def summarize(path: str, top: int = 10) -> dict:
    """The numbers the benchmark reports from one trace, averaged over the
    device planes in it."""
    lines = load_lines(path, (OP_LINE, MODULE_LINE))
    planes, module_events = lines[OP_LINE], lines[MODULE_LINE]
    if not planes:
        return {"devices": 0}
    n = len(planes)
    busy = span = 0.0
    ops: dict = {}
    gaps: list = []
    count = 0
    for events in planes.values():
        reduced = busy_and_gaps(events)
        busy += reduced["busy_ns"]
        span += reduced["span_ns"]
        gaps += reduced["gaps"]
        count += len(events)
        for name, ns in self_times(events).items():
            ops[name] = ops.get(name, 0.0) + ns
    gaps.sort(key=lambda g: -g[1])
    modules = [
        (name, (end - start) / 1e9)
        for events in module_events.values()
        for start, end, name in events
    ]
    # The host's spans are on another clock, so a gap can only be told by
    # where it lies: inside an executing program (the device waits on
    # itself) or between programs (the device waits for the host).
    running = [(s, e) for events in module_events.values() for s, e, _ in events]

    def where(start: float, ns: float) -> str:
        inside = any(s <= start and start + ns <= e for s, e in running)
        return "inside_program" if inside else "between_programs"

    return {
        "modules": modules,
        "devices": n,
        "events": count,
        "busy_s": busy / n / 1e9,
        "span_s": span / n / 1e9,
        "device_ops": [
            [name, ns / n / 1e9]
            for name, ns in sorted(ops.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": [[where(start, ns), ns / 1e9] for start, ns in gaps[:top]],
        "planes": planes,
    }


def step_seconds(trace: dict, module_pattern: str, steps_per_run: int):
    """Device seconds of one training step: the median duration of the
    executions of the step program (``XLA Modules`` events whose name
    matches) over the steps one execution makes.  The median, because the
    program's profile hook can open the trace while the task before the
    profiled one is still running, which cuts that execution short."""
    if not trace or not trace.get("devices") or not steps_per_run:
        return None
    runs = sorted(s for name, s in trace["modules"] if re.search(module_pattern, name))
    if not runs:
        return None
    return runs[len(runs) // 2] / steps_per_run


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the commonest event names of a trace: what to
    read by hand before writing a pattern against it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    rows = []
    for plane in data.planes:
        rows.append(f"PLANE {plane.name}")
        for line in plane.lines:
            names: dict = {}
            total = 0
            for e in line.events:
                total += 1
                key = op_name(e.name)
                acc = names.setdefault(key, [0, 0.0])
                acc[0] += 1
                acc[1] += float(e.duration_ns)
            rows.append(f"  LINE {line.name!r}: {total} events")
            for key, (cnt, ns) in sorted(names.items(), key=lambda kv: -kv[1][1])[:limit]:
                rows.append(f"    {cnt:6d} x {key[:100]}  {ns / 1e6:.3f} ms")
    return "\n".join(rows)
