"""``scope_roofline`` for work whose amount the ROUTING decides: the ops
traced under a ``jax.named_scope`` as a share of the compute roofline, in
%, with the FLOPs a step needs counted from what the steps really did —
``costs[params["flops_per_unit"]]`` (one computed slot's, from shapes) x
``costs[params["units_per_step"]]`` (all the slots a step routes, from
shapes) x the share of them this device computed, read off the worker's
counters (growth of ``params["counter"]`` over growth of ``params["over"]``)
— at the device's peak, over the scope's device time a step
(``op_ms_step``, called by path, not copied).

An expectation in its place (top_k x held / E slots a token) against the
time of fewer slots reads over 100 %.  The counters are cumulative and ride
every task report; the share is taken over the reports up to the window's
first — the warm-up tasks, the traced ones among them — and over the
window's own where there are fewer than two of those.  Absent where the
scope, the trace, the profiler's ``trace.json.gz`` or the counters are: a
program without them reports no metric."""

import os

import resolve
import runfiles

_HERE = os.path.dirname(os.path.abspath(__file__))


def computed_share(ctx: dict, counter: str, over: str):
    directory = runfiles.run_dir(ctx)
    ts = (ctx.get("window") or {}).get("ts") or []
    if directory is None or not ts:
        return None
    records = [
        r for r in runfiles.read_records(os.path.join(directory, "metrics", "metrics.jsonl"))
        if r.get("kind") == "counter" and counter in r and over in r
    ]
    early = [r for r in records if r["ts"] <= ts[0]]
    use = early if len(early) >= 2 else [r for r in records if ts[0] <= r["ts"] <= ts[-1]]
    if len(use) < 2 or use[-1][over] <= use[0][over]:
        return None
    return (use[-1][counter] - use[0][counter]) / (use[-1][over] - use[0][over])


def read(ctx: dict, params: dict):
    ms = resolve.load_module(os.path.join(_HERE, "op_ms_step.py")).read(
        ctx, {"module": params["module"], "pattern": params["pattern"], "on": "scope"})
    share = computed_share(ctx, params["counter"], params["over"])
    if not ms or share is None:
        return None
    flops = ctx["costs"][params["flops_per_unit"]] * ctx["costs"][params["units_per_step"]] * share
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / (ms / 1e3)
