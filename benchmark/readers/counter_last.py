"""One of the worker's counters as the window's last ``counter`` record
has it (``runfiles.counter_records``), times ``params["scale"]``.
(``params["how"]``, where a file has it, says ``last`` and is not read.)"""

import runfiles


def read(ctx: dict, params: dict):
    records = runfiles.counter_records(ctx)
    if not records or params["counter"] not in records[-1]:
        return None
    return float(params["scale"]) * records[-1][params["counter"]]
