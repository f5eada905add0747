"""The ops traced under a ``jax.named_scope``, as a share of the compute
roofline, in %: the FLOPs a step needs of them (``costs[params["flops"]]``,
from shapes) at the device's peak, over their device time a step
(``op_ms_step``'s own-time sum over the scope, called by path, not
copied).  Absent where the scope, the trace or the profiler's
``trace.json.gz`` is: a program without the scope reports no metric."""

import os

import resolve

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx: dict, params: dict):
    ms = resolve.load_module(os.path.join(_HERE, "op_ms_step.py")).read(
        ctx, {"module": params["module"], "pattern": params["pattern"], "on": "scope"})
    if not ms:
        return None
    return 100.0 * ctx["costs"][params["flops"]] / ctx["peaks"]["bf16_flops_per_s"] / (ms / 1e3)
