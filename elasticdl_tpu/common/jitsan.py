"""Runtime jit-compile sanitizer — the dynamic twin of graftlint's
jit-discipline passes (v6).

Every perf result of record assumes the jitted step compiles once and
never silently retraces: r15 pinned mask flips recompile-free, r11's
donation story assumes stable jit identity, and the serving tier promises
one compiled forward per padded batch shape.  The static passes
(``analysis/jit_discipline.py``) prove the LEXICAL picture — jit created
through the shim, bound once, no device->host materialization on the hot
path — but they cannot see a shape drift at runtime.  This module closes
that half, the locksan/racesan pattern:

- ``jax_compat.jit_compiled``/``jit_donating`` route through
  :func:`wrap` when ``GRAFT_JITSAN=1`` (tests/conftest.py arms it for
  the whole tier-1 suite).  Disabled, the wrappers return the PLAIN
  jitted function untouched — zero overhead, not even a shim frame.
- Armed, the to-be-jitted function is wrapped in a counting tracer:
  jax re-traces it exactly once per compile-cache miss, so each trace IS
  one lowering.  Counts aggregate per declared ``name=`` (the registry
  key) and per compiled-callable instance.
- A callable that lowers more times than its declared
  ``expected_variants=`` budget raises :class:`JitSanViolation` AT the
  triggering call — the silent throughput-halving retrace becomes a loud
  deterministic failure naming the site and its budget.
- Each lowering also emits a ``jit:compile`` trace instant
  (``common/trace.py`` ring — non-blocking, hot-path-legal) and the
  aggregate counts bridge into the gauge registry as
  ``edl_jit_compiles_total{fn=...}`` via
  ``gauge.install_jit_collector`` — an unexpected production retrace is
  visible in ``watch_job.py``, not just under tests.
- :func:`transfer_guard` optionally arms ``jax.transfer_guard`` around
  the worker's step dispatch (``GRAFT_JITSAN_TRANSFER_GUARD=1`` on top
  of ``GRAFT_JITSAN=1``): implicit device->host materializations inside
  the dispatch window fail loud while explicit spellings
  (``jax.device_put`` / ``jax.device_get``) stay legal — the runtime
  side of the static ``transfer-discipline`` rule's blind spots
  (values materialized through parameters, dynamic dispatch).

``GRAFT_JITSAN_DUMP=<path>`` writes the per-name stats as JSON at
process exit, for a reader of one run; ``tests/test_jitsan.py`` is what
fails when a compile count passes its budget.

Pure stdlib at import time (jax is imported only inside
:func:`transfer_guard` when armed): importable by gauge/watch tooling
that must never pay a backend init.
"""

from __future__ import annotations

import atexit
import contextlib
import json
import os
import threading
import time
from typing import Callable, Dict, Optional

from elasticdl_tpu.common import trace

__all__ = [
    "JitSanViolation", "enabled", "transfer_guard_armed", "wrap",
    "stats", "compiles", "reset", "transfer_guard", "dump_stats",
]


class JitSanViolation(AssertionError):
    """A compiled callable lowered more times than its declared
    ``expected_variants`` budget.  Raised AT the re-tracing call, so a
    shape/dtype drift is a deterministic failure at the drifting site
    instead of a silent 2x step-time regression."""


def enabled() -> bool:
    return os.environ.get("GRAFT_JITSAN", "") == "1"


def transfer_guard_armed() -> bool:
    """Arm ``jax.transfer_guard`` around step dispatch too — opt-in on
    top of the counter (compilation itself may move constants, so the
    guard is a steady-state assertion the operator arms deliberately)."""
    return enabled() and os.environ.get(
        "GRAFT_JITSAN_TRANSFER_GUARD", ""
    ) == "1"


_lock = threading.Lock()
#: name -> {"compiles", "instances", "budget"}; process-global like
#: locksan's edge table — the budget contract is per declared site name.
_names: Dict[str, dict] = {}
_dump_registered = False


class _Site:
    """One registered compiled callable: its own lowering counter against
    its own budget (two structural variants of one ``name`` are separate
    instances; each may lower ``budget`` times)."""

    __slots__ = ("name", "budget", "lowerings")

    def __init__(self, name: str, budget: int):
        self.name = name
        self.budget = budget
        self.lowerings = 0


def _register(name: str, budget: int) -> _Site:
    global _dump_registered
    site = _Site(name, budget)
    with _lock:
        rec = _names.setdefault(
            name, {"compiles": 0, "instances": 0, "budget": 0}
        )
        rec["instances"] += 1
        rec["budget"] = max(rec["budget"], budget)
        if not _dump_registered and os.environ.get("GRAFT_JITSAN_DUMP"):
            _dump_registered = True
            atexit.register(dump_stats)
    return site


def _note_lowering(site: _Site) -> None:
    with _lock:
        site.lowerings += 1
        # setdefault: reset() may have cleared the aggregates while this
        # instance (and its budget) lives on in a caller's closure.
        rec = _names.setdefault(
            site.name, {"compiles": 0, "instances": 1, "budget": site.budget}
        )
        rec["compiles"] += 1
        n_site, n_total = site.lowerings, rec["compiles"]
    # Record BEFORE judging: the over-budget lowering must be visible in
    # the trace/gauges even when the raise below kills the step.
    trace.instant("jit:compile", cat="jit", fn=site.name, n=n_total)
    if n_site > site.budget:
        raise JitSanViolation(
            f"jitsan: {site.name!r} lowered {n_site} time(s) on one "
            f"compiled callable, past its declared expected_variants="
            f"{site.budget} — a shape/dtype/static-arg drift is retracing "
            "the step (every retrace pays a full XLA compile mid-run). "
            "Stabilize the drifting input, bucket the shapes, or raise "
            "the declared budget at the jit_compiled/jit_donating site "
            "(docs/static_analysis.md, v6)."
        )


def wrap(
    jit_factory: Callable,
    fun: Callable,
    *,
    name: Optional[str] = None,
    expected_variants: int = 1,
    jit_kwargs: Optional[dict] = None,
) -> Callable:
    """Jit ``fun`` through ``jit_factory`` with lowering accounting.

    ``jit_factory`` is passed in (``jax.jit``) rather than imported so
    this module stays jax-free at import time.  The counting wrapper
    rides INSIDE the jit: jax re-traces it once per compile-cache miss,
    which is exactly the lowering count — no private cache probing."""
    import functools

    site = _register(
        name or getattr(fun, "__name__", "<jit>"),
        max(1, int(expected_variants)),
    )

    @functools.wraps(fun)
    def counted(*args, **kwargs):
        _note_lowering(site)
        return fun(*args, **kwargs)

    return jit_factory(counted, **(jit_kwargs or {}))


def stats() -> Dict[str, dict]:
    """Per-name ``{"compiles", "instances", "budget"}`` — the gauge
    collector's and artifact dump's input."""
    with _lock:
        return {name: dict(rec) for name, rec in sorted(_names.items())}


def compiles(name: str) -> int:
    """Total lowerings recorded under ``name`` (0 when never registered)
    — what the recompile-free tests assert deltas over."""
    with _lock:
        rec = _names.get(name)
        return int(rec["compiles"]) if rec else 0


def reset() -> None:
    """Forget aggregate counts (test isolation).  Per-instance budgets on
    already-wrapped callables keep their own counters — the violation
    contract is an instance property, not an aggregate one."""
    with _lock:
        _names.clear()


def transfer_guard(level: str = "disallow", when: bool = True):
    """Context manager for the worker's step-dispatch window: armed
    (:func:`transfer_guard_armed`), implicit transfers raise inside it;
    disarmed, a ``nullcontext`` — the dispatch path pays one env check.

    ``when=False`` keeps the window open even when armed — the caller's
    escape hatch for dispatch paths with a LEGITIMATE implicit transfer
    inside (the worker's host-table push materializes sparse cotangents
    mid-window by design; the runtime guard has no per-line waiver, so
    the exemption is declared at the ``with`` site instead)."""
    if not when or not transfer_guard_armed():
        return contextlib.nullcontext()
    import jax

    return jax.transfer_guard(level)


def dump_stats(path: Optional[str] = None) -> Optional[str]:
    """Write :func:`stats` as JSON to ``path`` (default: the
    ``GRAFT_JITSAN_DUMP`` env var; registered atexit when it is set).
    Returns the path written, or None when there is nowhere to write."""
    path = path or os.environ.get("GRAFT_JITSAN_DUMP")
    if not path:
        return None
    payload = stats()
    # Provenance for a reader: counts are only meaningful for the code
    # that produced them, and this module cannot reach git — the
    # wall-clock stamp can be compared against HEAD's commit time.
    payload["_meta"] = {"utc_s": time.time()}
    from elasticdl_tpu.common import durable

    durable.atomic_publish(
        path, json.dumps(payload, indent=1, sort_keys=True) + "\n"
    )
    return path
