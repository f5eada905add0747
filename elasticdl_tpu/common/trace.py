"""grafttrace — hot-path-safe structured tracing, one recorder per process.

The repo could decompose a worker's wall time (``PhaseTimers``) but not
show one training step ACROSS processes: a slow gang step was attributable
to "control grew" and nothing finer.  This module is the recording half of
the fix — a stdlib-only span recorder cheap enough to live inside
``# hot-path`` functions — and ``tools/trace_dump.py`` is the reading half
(merge every process's buffer into one Chrome-trace/Perfetto JSON).

Design constraints, in order:

- **Hot-path safe.**  Emission never blocks and never allocates beyond the
  event record itself: the buffer is a bounded ``collections.deque`` whose
  ``append`` is GIL-atomic (no lock), overwriting the OLDEST event when
  full — a tracing stall or an unbounded buffer must never be the thing
  that makes the traced job slow.  Disabled (the default), ``span()``
  returns a shared no-op context manager: two attribute reads per call
  (the ring's switch and the bridge below).
- **Stdlib only.**  The master control plane and the lint/bench tools are
  jax-free by contract (graftlint import-hygiene); the recorder rides in
  all of them.
- **Mergeable.**  Events carry wall-anchored microsecond timestamps
  (``time.time`` anchor + ``perf_counter`` offsets, so resolution is
  perf_counter's while the epoch is comparable across processes) and the
  worker ships its buffer with a measured clock offset (RPC RTT midpoint,
  see ``Worker._check_membership``), so the dump tool can align per-process
  clocks onto the master's.

API split the ``trace-discipline`` lint rule enforces:

- non-blocking ring API (legal anywhere, including ``# hot-path``):
  ``span(...)`` / ``instant(...)`` / ``TraceRecorder.add_complete``;
- export API (forbidden in ``# hot-path`` functions): ``drain_slice`` /
  ``export`` / ``chrome_events`` — draining belongs on control-plane
  boundaries (heartbeats, checkpoint reports, dump tools).

The bridge: a process that holds a profiler (the worker, while its
``--profile_dir`` window is open) installs a factory ``(name, attrs) ->
context manager`` with :func:`set_bridge`; every span is then ALSO entered
through it, so the same spans land in the profiler's own trace on the
profiler's clock, beside the device planes.  This module stays stdlib-only:
it never learns what the factory builds.  The bridge works with the ring
off, and costs nothing when it is not installed.

Per-thread nesting: spans stack per thread; each records its parent's id
and its SELF time (wall minus directly nested spans' wall) in
``args.self_us`` — the trace-side twin of ``PhaseTimers``' nested-phase
self-time arithmetic, and the tests pin that the two agree on the same
block.
"""

from __future__ import annotations

import collections
import itertools
import os
import threading
import time
from typing import Any, Dict, List, Optional

#: Default per-process ring capacity (events).  At the worker's steady
#: state (~10 spans/task) this holds hours; the serving tier's per-request
#: spans wrap sooner, which is the point of overwrite-oldest: the buffer
#: always holds the most RECENT window.
DEFAULT_CAPACITY = 65536

#: How many events one heartbeat/report ships (bounded so a control-plane
#: RPC can never balloon because tracing is on).
SHIP_BATCH = 512


class _NullSpan:
    """Shared no-op span for the disabled recorder: enter/exit do nothing,
    so a disabled hot path pays one attribute check per ``span()`` call."""

    __slots__ = ()
    span_id = 0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _BridgeSpan:
    """A span that exists only in the bridge's trace (ring off): it has no
    id, so RPC clients propagate no parent for it."""

    __slots__ = ("_cm",)
    span_id = 0

    def __init__(self, cm):
        self._cm = cm

    def __enter__(self) -> "_BridgeSpan":
        self._cm.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._cm.__exit__(*exc)
        return False


class _Span:
    """One live span: a context manager pushed on the per-thread stack."""

    __slots__ = ("_rec", "name", "cat", "attrs", "span_id", "parent_id",
                 "_t0", "_child", "_bridged")

    def __init__(self, rec: "TraceRecorder", name: str, cat: str,
                 attrs: Dict[str, Any]):
        self._rec = rec
        self.name = name
        self.cat = cat
        self.attrs = attrs
        self.span_id = 0
        self.parent_id = 0
        self._child = 0.0
        self._bridged = None

    def __enter__(self) -> "_Span":
        rec = self._rec
        bridge = rec.bridge
        if bridge is not None:
            # Outside the ring's own timing on both ends, so the ring's
            # self-time arithmetic does not see the bridge.
            self._bridged = bridge(self.name, self.attrs)
            self._bridged.__enter__()
        stack = rec._stack()
        self.span_id = next(rec._ids)
        self.parent_id = stack[-1].span_id if stack else 0
        stack.append(self)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        rec = self._rec
        elapsed = t1 - self._t0
        stack = rec._stack()
        if stack and stack[-1] is self:
            stack.pop()
        if stack:
            # Hand the full wall to the enclosing span so IT can subtract;
            # this span keeps only its self-time (PhaseTimers' arithmetic).
            stack[-1]._child += elapsed
        args = dict(self.attrs) if self.attrs else {}
        args["self_us"] = round(max(elapsed - self._child, 0.0) * 1e6, 1)
        args["span_id"] = self.span_id
        if self.parent_id:
            args["parent"] = self.parent_id
        rec.add_complete(
            self.name, self.cat,
            rec._to_us(self._t0), elapsed * 1e6, args,
        )
        if self._bridged is not None:
            self._bridged.__exit__(*exc)
        return False


class TraceRecorder:
    """Bounded ring of trace events with non-blocking append.

    Thread-safety without a lock: ``deque(maxlen=N).append`` and
    ``popleft`` are GIL-atomic in CPython, so concurrent writers interleave
    safely and a full ring drops the oldest event (each writer's retained
    events form a suffix of its own appends — pinned by tests).
    ``dropped`` is an APPROXIMATE monotonic counter (unsynchronized
    increments may lose a race); it exists to say "the window wrapped",
    not to account every event.
    """

    def __init__(self, enabled: bool = False,
                 capacity: int = DEFAULT_CAPACITY):
        self.enabled = bool(enabled)
        # (name, attrs) -> context manager, or None: see set_bridge().
        self.bridge = None
        self.capacity = int(capacity)
        self._buf: collections.deque = collections.deque(maxlen=self.capacity)
        self._local = threading.local()
        self._ids = itertools.count(1)
        self.dropped = 0
        # Wall anchor + perf_counter origin: timestamps get perf_counter's
        # resolution/monotonicity on a wall-clock epoch, so buffers from
        # different processes are alignable (after the RTT-midpoint offset).
        self._wall0 = time.time()
        self._pc0 = time.perf_counter()

    # -- clock --

    def _to_us(self, pc: float) -> float:
        return (self._wall0 + (pc - self._pc0)) * 1e6

    def now_us(self) -> float:
        """Wall-anchored monotonic timestamp in microseconds."""
        return self._to_us(time.perf_counter())

    # -- non-blocking ring API (hot-path legal) --

    def _stack(self) -> List[_Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def current_span_id(self) -> int:
        stack = getattr(self._local, "stack", None)
        return stack[-1].span_id if stack else 0

    def span(self, name: str, cat: str = "span", **attrs):
        """Context manager recording one complete ("X") event on exit."""
        if not self.enabled:
            bridge = self.bridge
            if bridge is None:
                return _NULL_SPAN
            return _BridgeSpan(bridge(name, attrs))
        return _Span(self, name, cat, attrs)

    def instant(self, name: str, cat: str = "event", **attrs) -> None:
        """One instant ("i") event — elastic control transitions live here."""
        if not self.enabled:
            return
        ev = {
            "ph": "i", "name": name, "cat": cat,
            "ts": round(self.now_us(), 1),
            "tid": threading.get_ident() & 0x7FFFFFFF,
            "s": "t",
        }
        if attrs:
            ev["args"] = attrs
        self._append(ev)

    def add_complete(self, name: str, cat: str, ts_us: float, dur_us: float,
                     args: Optional[Dict[str, Any]] = None) -> None:
        """Append one complete event (the span exit path; also usable
        directly by instrumentation that already timed itself)."""
        if not self.enabled:
            return
        ev = {
            "ph": "X", "name": name, "cat": cat,
            "ts": round(ts_us, 1), "dur": round(dur_us, 1),
            "tid": threading.get_ident() & 0x7FFFFFFF,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def _append(self, ev: dict) -> None:
        if len(self._buf) >= self.capacity:
            self.dropped += 1  # approximate: see class docstring
        self._buf.append(ev)

    # -- export API (forbidden in # hot-path functions: trace-discipline) --

    def drain_slice(self, max_events: int = SHIP_BATCH) -> List[dict]:
        """Pop up to ``max_events`` OLDEST events (the shipping path:
        bounded slices ride the heartbeat/report channel).  Safe against
        concurrent appenders; never blocks."""
        out: List[dict] = []
        for _ in range(max_events):
            try:
                out.append(self._buf.popleft())
            except IndexError:
                break
        return out

    def export(self) -> List[dict]:
        """Snapshot of the current window, oldest first (non-draining)."""
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0


class SetupChain:
    """One process's set-up as CONSECUTIVE spans on the wall epoch.

    ``mark(name)`` closes the span ``name`` from the previous stamp (the
    chain's origin for the first) to now, so the spans partition
    ``[origin, last stamp]`` by construction: no overlap, and time nobody
    named is inside whichever span the next mark closes, never a silence.
    A dozen stamps in a process's life, none in a task loop.  Seconds on
    ``now_us``'s clock: ``time.time``'s epoch at ``perf_counter``'s
    resolution, comparable across the processes of one host and with the
    ``ts`` the master stamps ``metrics.jsonl`` with.

    ``child`` records a span INSIDE the current one (the worker's index
    scan inside its build): kept beside the chain, outside the partition.
    ``extras`` are bare floats that ride with the spans (pid, counts, the
    seconds jax reported for a span's parts).  Three sinks read the same
    stamps: :meth:`flat` (the ``setup`` record of ``metrics.jsonl``),
    :meth:`emit` (``cat="setup"`` spans of the ring) and the worker's
    ``edl_setup_seconds`` gauges (``durations``).
    """

    def __init__(self, origin_s: Optional[float] = None):
        self.origin_s = now_s() if origin_s is None else float(origin_s)
        self.spans: List[tuple] = []  # (name, t0_s, t1_s), consecutive
        self.children: List[tuple] = []
        self.extras: Dict[str, float] = {}

    @property
    def last_s(self) -> float:
        return self.spans[-1][2] if self.spans else self.origin_s

    def restart(self) -> None:
        """Forget everything and start at now (a warm standby's adoption:
        what it paid while parked belongs to no job's set-up)."""
        self.__init__()

    def mark(self, name: str, at_s: Optional[float] = None) -> float:
        """Close ``name`` at ``at_s`` (now unless given; never before the
        previous stamp) and return the stamp."""
        t1 = max(now_s() if at_s is None else float(at_s), self.last_s)
        self.spans.append((name, self.last_s, t1))
        return t1

    def has(self, name: str) -> bool:
        return any(s[0] == name for s in self.spans)

    def child(self, name: str) -> "_ChildSpan":
        return _ChildSpan(self, name)

    def durations(self) -> Dict[str, float]:
        return {n: t1 - t0 for n, t0, t1 in self.spans + self.children}

    def flat(self) -> Dict[str, float]:
        """``<span>_t0`` / ``<span>_t1`` epoch seconds of every span and
        child, the extras, and whose chain it is (``pid``; ``proc_start``,
        when the kernel started the process): all floats
        (``MetricsWriter.write``)."""
        out = dict(self.extras, pid=float(os.getpid()))
        started = process_start_s()
        if started is not None:
            out["proc_start"] = started
        for name, t0, t1 in self.spans + self.children:
            out[name + "_t0"], out[name + "_t1"] = t0, t1
        return out

    def emit(self) -> None:
        """The spans as ``cat="setup"`` complete events of the process
        ring (nothing while the ring is off): ``tools/trace_dump.py``
        shows them beside everything else."""
        for name, t0, t1 in self.spans + self.children:
            _REC.add_complete(name, "setup", t0 * 1e6, (t1 - t0) * 1e6)


class _ChildSpan:
    __slots__ = ("_chain", "_name", "_t0")

    def __init__(self, chain: SetupChain, name: str):
        self._chain, self._name = chain, name

    def __enter__(self) -> "_ChildSpan":
        self._t0 = now_s()
        return self

    def __exit__(self, *exc) -> bool:
        self._chain.children.append((self._name, self._t0, now_s()))
        return False


# -- the process-global recorder ------------------------------------------

#: One recorder per process.  GRAFT_TRACE=1 enables at import (subprocess
#: workers/benches inherit the env); ``configure()`` flips it
#: programmatically (the --trace job flag, tests, tools).
_REC = TraceRecorder(
    enabled=os.environ.get("GRAFT_TRACE", "") not in ("", "0")
)


def default() -> TraceRecorder:
    return _REC


def configure(enabled: Optional[bool] = None,
              capacity: Optional[int] = None) -> TraceRecorder:
    """Reconfigure the process recorder IN PLACE (module users hold no
    reference; they call the module helpers, which read the global)."""
    if capacity is not None and capacity != _REC.capacity:
        _REC.capacity = int(capacity)
        _REC._buf = collections.deque(_REC._buf, maxlen=_REC.capacity)
    if enabled is not None:
        _REC.enabled = bool(enabled)
    return _REC


def set_bridge(factory) -> None:
    """Install (or, with None, clear) the process recorder's bridge: a
    factory ``(name, attrs) -> context manager`` entered around every span
    while it is set, ring on or off.  One plain attribute store: spans
    already open keep the bridge state they were entered with."""
    _REC.bridge = factory


def bridge_span(name: str, **attrs):
    """A span of the BRIDGE's trace alone: entered through the installed
    bridge like every other span, never written to the ring (its writer
    puts the ring's event there itself, once it knows more than the span's
    beginning did).  The shared no-op while no bridge is installed."""
    bridge = _REC.bridge
    return _NULL_SPAN if bridge is None else _BridgeSpan(bridge(name, attrs))


def name_os_thread() -> None:
    """Give the calling thread's Python name to the OS (Linux; 15 bytes).
    Python 3.12 names threads for itself only, and a profiler names a
    thread's line after the OS name — without this every pool thread's
    line reads like the main thread's.  Pool initializers call it; never
    the main thread (its OS name is the process's)."""
    try:
        import ctypes

        name = threading.current_thread().name.encode()[:15]
        ctypes.CDLL(None).prctl(15, name, 0, 0, 0)  # PR_SET_NAME
    except Exception:  # not Linux, no libc: the lines stay unnamed
        pass


def enabled() -> bool:
    return _REC.enabled


def span(name: str, cat: str = "span", **attrs):
    return _REC.span(name, cat, **attrs)


def instant(name: str, cat: str = "event", **attrs) -> None:
    _REC.instant(name, cat, **attrs)


def now_us() -> float:
    return _REC.now_us()


def now_s() -> float:
    """``now_us`` in seconds: the set-up chains' unit, and the unit of
    the ``ts`` in ``metrics.jsonl``."""
    return _REC.now_us() / 1e6


#: This process's set-up chain.  Its origin is the recorder's own anchor:
#: the moment this module was first imported, which the entry points
#: (``client/main.py``, ``worker/main.py``) make their first statement.
_SETUP = SetupChain(_REC._wall0)


def setup() -> SetupChain:
    return _SETUP


def process_start_s() -> Optional[float]:
    """When the kernel started this process, on the wall epoch, from ONE
    read of ``/proc/self/stat`` (field 22: start in clock ticks after
    boot, so 10 ms coarse); None off Linux.  Before the chain's origin by
    what the interpreter took to reach the entry point's first
    statement."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        age = time.clock_gettime(time.CLOCK_BOOTTIME) - ticks / os.sysconf(
            "SC_CLK_TCK"
        )
        return time.time() - age
    except (OSError, ValueError, IndexError, AttributeError):
        return None
