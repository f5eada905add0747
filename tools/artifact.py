"""Shared artifact writer for the tools that stamp a file under
``artifacts/`` (graftlint, crashsan_matrix, wire_skew).

The file carries counts and findings, stamped with the command line and
UTC time; speed lives in ``PERF.md`` and ``PERF_LEDGER.jsonl``.  One
definition so the write idiom — env override, directory creation,
stamping — cannot drift per tool.
"""

from __future__ import annotations

import os
import sys
import time
from typing import Callable, Optional, Sequence

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

def code_rev(repo: Optional[str] = None) -> str:
    """Commit hash of the code producing an artifact (best-effort).

    Stamped into artifacts so a reader can tell "another run of the same
    code" from "the first run of NEW code".  A dirty tree gets a "-dirty" suffix
    — uncommitted changes are NEW code under the same HEAD, and two dirty
    runs may differ from each other too, so dirty never matches anything.
    Untracked files count as dirt: a new not-yet-added module is importable
    code the committed rev does not describe (ignored files still don't
    count).  Returns "" when git is unavailable.
    """
    try:
        import subprocess

        repo = repo or _REPO_ROOT
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"],
            cwd=repo, capture_output=True, text=True, timeout=10,
        )
        if out.returncode != 0:
            return ""
        rev = out.stdout.strip()
        st = subprocess.run(
            ["git", "status", "--porcelain"],
            cwd=repo, capture_output=True, text=True, timeout=10,
        )
        if st.returncode != 0 or st.stdout.strip():
            rev += "-dirty"
        return rev
    except Exception:
        return ""


class ArtifactRun:
    """Capture ``code_rev`` at TOOL ENTRY and stamp it at write time.

    A tool whose RUN rewrites committed outputs (prior artifacts)
    dirties its own tree, so a stamp-time
    ``code_rev()`` would mark every artifact "-dirty" from the tool's OWN
    output files.  The code that produced the measurement is the tree as
    it stood on entry — construct one of these FIRST, write through it
    LAST.  A caller-supplied ``code_rev`` key in the result still wins
    (setdefault), so tools measuring a different tree can override.
    """

    def __init__(self, repo: Optional[str] = None):
        self.code_rev = code_rev(repo)

    def write(
        self,
        result: dict,
        default_name: str,
        env_var: str = "",
        path: Optional[str] = None,
        log: Optional[Callable[[str], None]] = None,
    ) -> str:
        stamped = dict(result)
        stamped.setdefault("code_rev", self.code_rev)
        return write_artifact(
            stamped, default_name, env_var=env_var, path=path, log=log
        )


#: Shared log-spaced histogram bucket edges (MILLISECONDS) for
#: ``latency_stats(..., buckets=True)``.  One FIXED grid across every
#: report (straggler_report) so tail shapes are
#: comparable file to file and round to round — per-run adaptive edges
#: would make two artifacts' histograms incomparable.  Canonical home is
#: ``common/gauge.py`` since r14: the LIVE registry histograms bucket on
#: the same grid, so a scrape and a stamped artifact agree bin-for-bin
#: (gauge.py is stdlib-only, so this import keeps the artifact path
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
                        # (graftlint's artifact path must cost milliseconds)

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


def write_artifact(
    result: dict,
    default_name: str,
    env_var: str = "",
    path: Optional[str] = None,
    log: Optional[Callable[[str], None]] = None,
) -> str:
    """Write ``result`` (+ command/utc stamp) and return the path.

    Resolution order: explicit ``path`` arg, then ``env_var`` if set in the
    environment, then ``artifacts/<default_name>`` at the repo root.  A
    bare filename (no directory part) writes to the current directory.
    """
    out = (
        path
        or (os.environ.get(env_var, "") if env_var else "")
        or os.path.join(_REPO_ROOT, "artifacts", default_name)
    )
    # Atomic since r21 (durable.atomic_publish): a tool killed mid-stamp
    # used to leave a truncated JSON file where graftlint expects a whole
    # one — an artifact must commit whole or not at all, same as any
    # durable state.
    from elasticdl_tpu.common import durable

    durable.atomic_publish_json(
        out,
        {
            **result,
            "command": " ".join(sys.argv),
            # What the writing process was AIMED at.  Most writers are
            # jax-free drivers of subprocess fleets, so this is all they
            # can know; a tool that ran steps itself also stamps the
            # backend that answered (``device``:
            # common/platform.device_summary) in ``result``.
            "jax_platforms": os.environ.get("JAX_PLATFORMS", "(unset)"),
            "utc": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        },
        indent=1,
    )
    say = log or (lambda m: print(m, file=sys.stderr, flush=True))
    say(f"artifact written to {out}")
    return out
