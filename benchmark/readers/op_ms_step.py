"""Device time of a family of ops per training step, on the busiest chip,
in ms: the own time (duration less the events nested inside) of the
``XLA Ops`` events that match, inside the whole executions of the step
program (``params["module"]``, a pattern on the ``XLA Modules`` events),
over those executions x the steps one execution makes; the largest such
value over the device planes.

``params["pattern"]`` is matched against the event's HLO instruction text
(``"on": "op"``, the default), or against the ``jax.named_scope`` path the
op was traced under (``"on": "scope"``).  The ``*.xplane.pb`` events carry
no scope; the ``*.trace.json.gz`` the profiler writes beside it does
(``args.tf_op``, by instruction name), so a scope metric is absent where
that file is.  ``params["exclude"]`` (optional) drops events whose HLO text
matches it.  An execution cut by the trace's edge (shorter than 0.98 of the
median) is left out.  Absent when nothing matches: a program without these
ops or scopes reports no metric, never a zero.
"""

import glob
import gzip
import json
import os
import re

import runfiles
import xplane


def scopes_by_op(trace_json: str) -> dict:
    """{instruction name: scope path} from a profiler ``trace.json.gz``."""
    with gzip.open(trace_json) as f:
        events = json.load(f)["traceEvents"]
    return {e["name"]: e["args"]["tf_op"] for e in events if e.get("ph") == "X" and "tf_op" in e.get("args", {})}


def own_times(events: list) -> list:
    """[(start_ns, own_ns, name)] per event (sorted by start, longer first
    on ties): its duration less the events nested directly inside it."""
    out = []
    stack: list = []  # [end_ns, index into out]
    for start, end, name in events:
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[1] -= min(end, stack[-1][0]) - start
        out.append([start, end - start, name])
        stack.append([end, len(out) - 1])
    return out


def per_step_ms(path: str, module: str, pattern: str, steps_per_run: int, on: str = "op",
                exclude: str | None = None, whole_share: float = 0.98):
    lines = xplane.load_lines(path, (xplane.OP_LINE, xplane.MODULE_LINE))
    if not steps_per_run or not lines[xplane.OP_LINE]:
        return None
    rx, rx_out = re.compile(pattern), re.compile(exclude) if exclude else None
    scopes = None
    if on == "scope":
        found = glob.glob(os.path.join(os.path.dirname(path), "*.trace.json.gz"))
        if not found:
            return None
        scopes = scopes_by_op(found[0])
    worst = None
    for plane, events in lines[xplane.OP_LINE].items():
        runs = [m for m in lines[xplane.MODULE_LINE].get(plane, []) if re.search(module, m[2])]
        if not runs:
            continue
        lengths = sorted(end - start for start, end, _ in runs)
        whole = [(s, e) for s, e, _ in runs if e - s >= whole_share * lengths[len(lengths) // 2]]
        total, matched = 0.0, False
        for start, own, name in own_times(events):
            if not any(s <= start < e for s, e in whole) or (rx_out and rx_out.search(name)):
                continue
            text = name if scopes is None else scopes.get(name.split(" = ", 1)[0].lstrip("%"), "")
            if rx.search(text):
                total += max(own, 0.0)
                matched = True
        if matched:
            value = total / (len(whole) * steps_per_run) / 1e6
            worst = value if worst is None else max(worst, value)
    return worst


def read(ctx: dict, params: dict):
    path = runfiles.trace_path(ctx)
    if path is None:
        return None
    return per_step_ms(
        path, params["module"], params["pattern"], ctx.get("trace_steps"),
        params.get("on", "op"), params.get("exclude"),
    )
