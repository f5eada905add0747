"""The one definition of the latency percentiles and histogram buckets
that every report of latencies uses (``tools/straggler_report.py``), on
the grid the live registry's histograms bucket on (``common/gauge.py``).
"""

from __future__ import annotations

from typing import Sequence

#: Shared log-spaced histogram bucket edges (MILLISECONDS) for
#: ``latency_stats(..., buckets=True)``.  One FIXED grid across every
#: report (straggler_report) so tail shapes are
#: comparable file to file and round to round — per-run adaptive edges
#: would make two reports' histograms incomparable.  Canonical home is
#: ``common/gauge.py`` since r14: the LIVE registry histograms bucket on
#: the same grid, so a scrape and a report agree bin-for-bin
#: (gauge.py is stdlib-only, so this import keeps this module
#: jax-free).  Re-exported here for the existing consumers.
from elasticdl_tpu.common.gauge import DEFAULT_BUCKET_EDGES_MS  # noqa: E402,F401


def latency_stats(
    samples_ms: Sequence[float], prefix: str = "", buckets=None
) -> dict:
    """p50/p99/mean/max over per-request latencies in MILLISECONDS — the
    one definition every latency consumer (straggler_report) uses, so
    percentile conventions cannot drift per tool.  Empty input returns {} (a point with zero completed requests has
    no latency distribution; callers report their error tallies instead).

    ``buckets``: True for the shared ``DEFAULT_BUCKET_EDGES_MS`` grid, or
    an explicit ascending edge sequence — adds ``{prefix}hist`` with
    ``edges_ms`` and ``counts`` (``len(edges)+1`` entries: counts[i] holds
    samples in ``(edges[i-1], edges[i]]`` with counts[0] the under-first-
    edge bin and counts[-1] the overflow), so artifacts carry the TAIL
    SHAPE, not just two percentile points.
    """
    if not samples_ms:
        return {}
    import numpy as np  # local: keep the module import jax-/numpy-free

    arr = np.asarray(samples_ms, np.float64)
    out = {
        f"{prefix}p50_ms": round(float(np.percentile(arr, 50)), 2),
        f"{prefix}p99_ms": round(float(np.percentile(arr, 99)), 2),
        f"{prefix}mean_ms": round(float(arr.mean()), 2),
        f"{prefix}max_ms": round(float(arr.max()), 2),
    }
    if buckets is not None and buckets is not False:
        edges = (
            DEFAULT_BUCKET_EDGES_MS
            if buckets is True
            else tuple(float(e) for e in buckets)
        )
        idx = np.searchsorted(np.asarray(edges, np.float64), arr, side="left")
        counts = np.bincount(idx, minlength=len(edges) + 1)
        out[f"{prefix}hist"] = {
            "edges_ms": list(edges),
            "counts": [int(c) for c in counts],
        }
    return out
