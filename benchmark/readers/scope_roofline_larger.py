"""The ops traced under a ``jax.named_scope``, as a share of the roofline
that BINDS them, in %: the LARGER of the least time the chip could take for
the FLOPs a step needs of them (``costs[params["flops"]]`` at the MXU's
peak) and for the bytes it needs (``costs[params["bytes"]]`` at the peak HBM
bandwidth), both from shapes, over their device time a step
(``op_ms_step``'s own-time sum over the scope, called by path, not copied).
For work that sits near the ridge, where neither ``scope_roofline`` nor
``scope_hbm_roofline`` alone is the bound.  Absent where the scope, the
trace or the profiler's ``trace.json.gz`` is: a program without the scope
reports no metric."""

import os

import resolve

_HERE = os.path.dirname(os.path.abspath(__file__))


def read(ctx: dict, params: dict):
    ms = resolve.load_module(os.path.join(_HERE, "op_ms_step.py")).read(
        ctx, {"module": params["module"], "pattern": params["pattern"], "on": "scope"})
    if not ms:
        return None
    least_s = max(
        ctx["costs"][params["flops"]] / ctx["peaks"]["bf16_flops_per_s"],
        ctx["costs"][params["bytes"]] / ctx["peaks"]["hbm_bytes_per_s"],
    )
    return 100.0 * least_s / (ms / 1e3)
