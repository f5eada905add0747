"""Device time of one training step, in ms: the median execution of the
step program (``params["module"]``, a pattern on the ``XLA Modules`` events
of the profiled tasks) over the steps of one task."""

import xplane


def read(ctx: dict, params: dict):
    step_s = xplane.step_seconds(ctx["trace"], params["module"], ctx["trace_steps"])
    return None if step_s is None else step_s * 1e3
