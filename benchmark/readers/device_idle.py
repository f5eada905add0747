"""Share of the window in which the device ran nothing, in %:
1 - (steps reported in the window x device time per step) / wall.

Device time per step comes from the trace (``trace_step``), wall and steps
from the report clock, so this sees the gaps BETWEEN tasks, which the trace
cannot (the program's profile hook traces a task or two)."""

import xplane


def read(ctx: dict, params: dict):
    window = ctx["window"]
    step_s = xplane.step_seconds(ctx["trace"], params["module"], ctx["trace_steps"])
    if step_s is None or not window["span_s"]:
        return None
    return 100.0 * (1.0 - window["steps"] * step_s / window["span_s"])
