"""A kernel family's share of its compute roofline, in %: the FLOPs its
calls need (from shapes) at the device's peak, over the summed device time
of its events in the trace.

``params["kernels"]``: a list of ``{"pattern": regex on the event name,
"units_key": cost-model key with the FLOP units one call needs}``; one
unit is ``costs[params["unit_flops"]]`` FLOPs.  A pattern that matches
nothing makes the metric absent, not zero."""

import xplane


def read(ctx: dict, params: dict):
    trace = ctx["trace"]
    if not trace or not trace.get("devices"):
        return None
    flops = seconds = 0.0
    for events in trace["planes"].values():
        for kernel in params["kernels"]:
            durations = xplane.kernel_events(events, kernel["pattern"])
            if not durations:
                return None
            seconds += sum(durations) / 1e9
            flops += len(durations) * ctx["costs"][kernel["units_key"]] * ctx["costs"][params["unit_flops"]]
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / seconds
