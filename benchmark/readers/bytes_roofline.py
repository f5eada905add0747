"""A step's share of its memory roofline, in %: the least time the chip
could take for the bytes the step needs (the configuration's cost model,
key ``params["bytes"]``) at the device's peak HBM bandwidth, over the
measured device time of a step (``trace_step``).  Bandwidth-bound by
construction: the FLOPs of such a step are far below the bytes' time."""

import xplane


def read(ctx: dict, params: dict):
    step_s = xplane.step_seconds(ctx["trace"], params["module"], ctx["trace_steps"])
    if step_s is None:
        return None
    least_s = ctx["costs"][params["bytes"]] / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / step_s
