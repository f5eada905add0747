"""Largest gap between consecutive task reports in the window, in ms: the
stall detector (one late task shows here before it shows in the rate)."""


def read(ctx: dict, params: dict):
    gap = ctx["window"]["gap_max_s"]
    return None if gap is None else gap * 1e3
