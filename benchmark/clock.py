"""The throughput clock: from task report to task report.

The master writes one ``train`` record per reported task into its
``metrics.jsonl``, stamped with its own clock (``ts``) and the model version
(``step``: minibatches trained so far).  The rate of a window is the work
of the records AFTER the first one in it, over ``ts_last - ts_first``:
numerator and denominator move together, so a task more or less inside the
window changes neither the rate nor its spread (PR 22 was refused for a
rate with a one-task quantum).
"""

from __future__ import annotations

import math


def window_records(records: list, kind: str, t0: float, t1: float) -> list:
    return [r for r in records if r["kind"] == kind and t0 <= r["ts"] <= t1]


def report_rate(train: list, units_per_step: float) -> dict:
    """``train``: the window's train records in file order.  Returns the
    rate in units/s with what it was made from; ``rate`` is None when the
    window holds fewer than two reports."""
    out = {
        "reports": len(train),
        "rate": None,
        "steps": 0,
        "span_s": 0.0,
        "gap_max_s": None,
        "gap_median_s": None,
        "ts": [r["ts"] for r in train],
    }
    if len(train) < 2:
        return out
    steps = train[-1]["step"] - train[0]["step"]
    span = train[-1]["ts"] - train[0]["ts"]
    gaps = sorted(b["ts"] - a["ts"] for a, b in zip(train, train[1:]))
    out.update(
        steps=steps,
        span_s=span,
        gap_max_s=gaps[-1],
        gap_median_s=gaps[len(gaps) // 2],
        rate=(steps * units_per_step / span) if span > 0 else None,
    )
    return out


def nonfinite_losses(train: list) -> int:
    return sum(1 for r in train if not math.isfinite(r.get("loss", math.nan)))


def phase_delta(phase_records: list) -> dict:
    """Seconds each PhaseTimers bucket grew between the first and the last
    ``phase`` record of the window (the snapshots are cumulative and ride
    every task report)."""
    if len(phase_records) < 2:
        return {}
    first, last = phase_records[0], phase_records[-1]
    skip = ("ts", "kind", "step")
    return {
        k: last[k] - first.get(k, 0.0)
        for k in last
        if k not in skip and isinstance(last[k], (int, float))
    }
