"""Peak device memory on the fullest chip, in GiB, read inside the worker
at the window's end (``run.py:memory_peak``)."""


def read(ctx: dict, params: dict):
    peak = ctx["memory_peak_bytes"]
    return None if not peak else peak / 2**30
