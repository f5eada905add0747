"""Growth of one of the worker's counters inside the window: between the
first and the last ``counter`` record (``runfiles.counter_records``; the
worker's cumulative counters ride every task report into the master's
metrics.jsonl), optionally over the growth of another counter
(``params["over"]``), times ``params["scale"]``.  Absent with fewer than
two records, or when the denominator did not grow.
(``params["how"]``, where a file has it, says ``growth`` and is not read.)"""

import runfiles


def read(ctx: dict, params: dict):
    records = runfiles.counter_records(ctx)
    name = params["counter"]
    if len(records) < 2 or name not in records[0] or name not in records[-1]:
        return None
    value = records[-1][name] - records[0][name]
    over = params.get("over")
    if over is not None:
        grown = records[-1].get(over, 0.0) - records[0].get(over, 0.0)
        if grown <= 0:
            return None
        value /= grown
    return float(params["scale"]) * value
