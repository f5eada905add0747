"""The stall recorder: what the task loop, its threads and the device were in
while a training report came late.

A job that loses seconds with no error and every result correct (PERF.md
section 7, ROADMAP S14 (a)) was visible only from OUTSIDE, in the gaps
between the ``ts`` of its ``train`` records.  This module reads the same
gaps from INSIDE the worker, where the cause is still there to be read:

- :class:`StallRule` calls a gap between two training reports LATE from
  the job's own pace (the median of the last gaps); a late gap is a STALL
  as far as the next reports do not catch it up (the device works through
  what was queued while the host was away, and the reports behind a late
  one come early): ``StallRecorder.on_report`` holds a late gap's record
  for one to three reports and counts what was LOST;
- :class:`StallRecorder` keeps, at every report, a mark of what is cheap to
  read and cumulative (``PhaseTimers``' seconds, CPU clocks, ``getrusage``,
  the compile listeners' and the collector's seconds), so that a stalled
  gap's record is the growth of each over that gap: the after-the-fact
  half, always complete;
- its daemon thread ``edl-watchdog`` wakes at 10 Hz and, once a report is
  later than the rule allows, samples what a record made afterwards cannot
  have: the loop thread's stack, the other threads' top frames, the RPC in
  flight and whether the device's newest output is ready.  Each sample is
  logged at WARNING at once, so a hang that never ends is in the pod's
  log; while a profiler's bridge is installed (``trace.set_bridge``) it
  holds a span ``stall`` from detection to the next report, beside the
  device planes on the session's clock;
- :func:`name_cause` names ONE cause a stall from a closed list
  (:data:`CAUSES`; the table is in docs/observability.md, "Stalls").

Nothing here runs per step; with no stall a report pays one mark (a few
clock reads and a 16-element median) and the watchdog's wake reads one
attribute.  Stdlib only: the device is reached through a callable its
owner hands in.
"""

from __future__ import annotations

import gc
import os
import resource
import statistics
import sys
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from elasticdl_tpu.common import locksan, racesan, trace
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.metrics import CRITICAL_PATH_PHASES, PhaseTimers
from elasticdl_tpu.common.rpc import IN_FLIGHT

logger = get_logger("stall")

# ---- the rule's constants, in one place ----

#: Gaps kept: the median of these is the job's pace.
HISTORY = 16
#: Nothing is judged before this many gaps are in (the first task compiles).
MIN_GAPS = 4
#: A gap is a stall when it exceeds the median by more than the larger of
#: these: seconds, and a share of the median.
MIN_EXCESS_S = 0.1
EXCESS_SHARE = 0.25
#: A late report is a LOSS only as far as the next reports do not catch it
#: up (the device works through what was queued while the host was away,
#: and the reports behind a late one come early): a late gap's record is
#: held while the gaps after it are short, for at most this many gaps, and
#: what they are short of the median by is taken off its excess.  A gap is
#: short while it lacks more than this share of the median.
CATCH_UP_GAPS = 3
SHORT_SHARE = 0.1
#: The watchdog's wake, its samples a stall (the first once a report is
#: later than the rule allows, each further one at twice the wait of the
#: one before), and the frames it keeps of the loop's stack.
WATCH_PERIOD_S = 0.1
MAX_SAMPLES = 4
STACK_FRAMES = 12

#: The causes a stall can be given, in the order they are tried: the first
#: that holds names it (``name_cause``).
CAUSES = (
    "compile", "gc", "profile_stop", "checkpoint", "ingest", "master",
    "device", "fetch", "injected", "descheduled", "unnamed",
)

#: The time of a gap that no foreground phase took: the loop between its
#: phases (a key of the record's ``phase_s.*`` beside the phases' names).
LOOP = "loop"


def allowance(median_s: float) -> float:
    """Seconds over the median that are no stall."""
    return max(MIN_EXCESS_S, EXCESS_SHARE * median_s)


class StallRule:
    """The rule: a gap is LATE when ``excess = gap - median`` exceeds
    ``allowance(median)``, the median being that of the last
    :data:`HISTORY` gaps BEFORE it.  Every gap joins the history, a late
    one too (a job that has become slower for good is, eight reports
    later, a steady job again)."""

    def __init__(self):
        self._gaps: deque = deque(maxlen=HISTORY)
        #: (median, the longest gap that is no stall) from the gaps so far.
        self.limit: Optional[tuple] = None  # gil-atomic

    def judge(self, gap_s: float) -> Optional[tuple]:
        """``(median, excess)`` when ``gap_s`` is late, else None."""
        limit = self.limit
        self._gaps.append(gap_s)
        if len(self._gaps) >= MIN_GAPS:
            median = statistics.median(self._gaps)
            self.limit = (median, median + allowance(median))
        if limit is None or gap_s <= limit[1]:
            return None
        return limit[0], gap_s - limit[0]


class _Gen2Clock:
    """Seconds this process has spent inside generation-2 collections: a
    ``gc.callbacks`` entry that returns at once for the other generations.
    One a process (``gc.callbacks`` is the process's), installed by the
    first recorder and left in place."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t0


_GEN2 = _Gen2Clock()


def _frame_str(frame) -> str:
    """``file:line:function``, the file by its last two path components."""
    path = frame.f_code.co_filename.split(os.sep)
    return f"{os.sep.join(path[-2:])}:{frame.f_lineno}:{frame.f_code.co_name}"


def name_cause(rec: Dict) -> str:
    """The ONE cause of a stall's record, by the first test that holds
    (docs/observability.md, "Stalls", has the table).  ``rec`` is the flat
    record ``StallRecorder`` builds; "the excess is X's" means that phase
    X grew, over this gap, by at least half the excess more than over the
    last gap that was no stall."""
    half = rec["excess_s"] / 2.0

    def excess_of(*phases: str) -> float:
        return sum(rec.get(f"phase_excess_s.{p}", 0.0) for p in phases)

    if rec["compile_s"] >= half:
        return "compile"
    if rec["gc2_s"] >= half:
        return "gc"
    if rec["profile_stop"]:
        return "profile_stop"
    if excess_of("checkpoint") >= half:
        return "checkpoint"
    if excess_of("prep_wait") >= half:
        return "ingest"
    if (
        excess_of("lease_wait", "control", "metrics") >= half
        and rec["rpc"] and rec["rpc_s"] >= half
    ):
        return "master"
    # The loop's thread ALONE waited for the step while the watchdog woke
    # on time; where the watchdog overslept too, the process stood still,
    # and the loop of a device-bound job is found in ``step_wait`` because
    # that is where it nearly always is.
    alone = rec["watchdog_late_s"] < half
    if excess_of("step_wait") >= half and rec["device"] == "busy" and alone:
        return "device"
    if excess_of("step_wait") >= half and rec["device"] == "ready" and alone:
        return "fetch"
    if rec["injected_s"] >= half:
        return "injected"
    if rec["loop_nivcsw"] >= 1 and rec["loadavg_1m"] > rec["cores"]:
        return "descheduled"
    # No thread ran and none burned CPU: descheduled, frozen, or the
    # sandbox's kernel stalled under every syscall at once (a call that
    # KEEPS the GIL and computes shows as ``cpu_process_s``).
    if not alone and rec["cpu_process_s"] < half:
        return "descheduled"
    return "unnamed"


def _record(
    prev: Dict, mark: Dict, base: Optional[tuple], verdict: tuple,
    samples: List[Dict],
) -> Dict:
    """The record of the stalled gap between the marks ``prev`` and
    ``mark`` (``StallRecorder._mark``), all but its ``task``, ``seq``,
    ``rpc`` and ``cause``; ``base`` is the pair of marks around the last
    gap that was no stall."""
    median, excess = verdict
    gap = mark["t"] - prev["t"]
    rec: Dict = {"gap_s": gap, "median_s": median, "excess_s": excess}
    # Which phase took it: each foreground phase's growth over the gap,
    # and that growth less the same over the last clean gap.
    for name, grown in _phase_growth(prev, mark).items():
        rec[f"phase_s.{name}"] = grown
        rec[f"phase_excess_s.{name}"] = grown
    if base is not None:
        for name, grown in _phase_growth(*base).items():
            rec[f"phase_excess_s.{name}"] -= grown
    rec["phase"] = max(
        (*CRITICAL_PATH_PHASES, LOOP), key=lambda n: rec[f"phase_excess_s.{n}"]
    )
    # Running or blocked, descheduled, paging, a neighbour.
    for key in (
        "cpu_process_s", "cpu_loop_s", "nivcsw", "majflt", "inblock",
        "loop_nivcsw", "gc2_s", "compiles", "compile_s",
        "dispatches_device_idle", "injected_s",
    ):
        rec[key] = mark[key] - prev[key]
    try:
        rec["loadavg_1m"] = os.getloadavg()[0]
    except OSError:
        rec["loadavg_1m"] = 0.0
    rec["cores"] = os.cpu_count() or 1
    # What else was alive: at either end of the gap, or begun inside it.
    rec["profile"] = mark["profile"]
    rec["profile_stop"] = bool(
        prev["profile_stopping"] or mark["profile_stopping"]
        or mark["profile_stops"] > prev["profile_stops"]
    )
    rec["saving"] = bool(prev["saving"] or mark["saving"])
    rec["prepping"] = bool(prev["prepping"] or mark["prepping"])
    # The live half: ready at every sample = the chip waited for the host;
    # at none = the chip was busy, or wedged.
    ready = [s["device_ready"] for s in samples if s["device_ready"] is not None]
    rec["device"] = (
        "" if not ready else "ready" if all(ready)
        else "busy" if not any(ready) else "mixed"
    )
    # The longest the watchdog's own wake overslept inside the gap: for so
    # long the whole interpreter (a call that kept the GIL) or the whole
    # process stood still, and not the loop's thread alone.
    rec["watchdog_late_s"] = max((s["watchdog_late_s"] for s in samples), default=0.0)
    rec["samples"] = samples
    return rec


def _phase_growth(before: Dict, after: Dict) -> Dict[str, float]:
    """Seconds each foreground phase grew by between two marks, and what
    of the time between them no phase took (``LOOP``)."""
    growth = {
        name: after["phases"].get(name, 0.0) - before["phases"].get(name, 0.0)
        for name in CRITICAL_PATH_PHASES
    }
    growth[LOOP] = after["t"] - before["t"] - sum(growth.values())
    return growth


@racesan.instrument(
    atomic=("_armed", "_samples", "_closing", "_loop_ident", "_unspanned")
)
class StallRecorder:
    """One worker's stall recorder (the module docstring has the design).

    ``probes`` returns the owner's cumulative numbers at a report
    (``compiles``, ``compile_s``, ``dispatches_device_idle``,
    ``injected_s``, ``profile_stops``) and what is alive right now
    (``profile``: the window's state, ``profile_stopping``, ``saving``,
    ``prepping``); ``device_ready`` answers, WITHOUT blocking and from the
    watchdog's thread, whether the newest dispatch's output is ready (None
    when there is none).

    Threads: the task loop calls ``start``, ``on_report``, ``taint``,
    ``close_span`` and ``stop``; ``edl-watchdog`` runs ``_watch`` alone.  They
    share four attributes, each replaced whole by its one writer and read
    racily by the other: ``_armed`` (loop -> watchdog), ``_samples``
    (appended to by the watchdog, swapped for a fresh list by the loop: a
    sample taken across the swap lands in the next record or in none),
    ``_closing`` and ``_unspanned``."""

    def __init__(
        self,
        phases: PhaseTimers,
        probes: Callable[[], Dict],
        device_ready: Callable[[], Optional[bool]],
    ):
        self._phases = phases
        self._probes = probes
        self._device_ready = device_ready
        self._rule = StallRule()
        # Everything below that the loop's side touches is touched under
        # this lock (the preemption thread makes a worker's last reports);
        # the watchdog takes it never.
        self._lock = locksan.lock("StallRecorder._lock", leaf=True)  # lock-order: leaf
        #: The three counters (worker.COUNTER_GAUGES): stalls, the seconds
        #: they LOST (their excess less what was caught up, not their
        #: gaps), and those of them no cause was found for.
        self.stalls = 0
        self.stall_s = 0.0
        self.stall_unnamed_s = 0.0
        self._loop_ident: Optional[int] = None  # gil-atomic
        self._loop_cpu_clock: Optional[int] = None
        self._prev: Optional[Dict] = None  # the mark of the last report
        self._base: Optional[tuple] = None  # the marks around the last clean gap
        self._tainted = False
        #: [record, gaps it may still be held for] of the late gap whose
        #: catch-up is under way.
        self._held: Optional[list] = None
        self._reports = 0
        #: (report count, perf_counter of that report, median, limit): what
        #: the watchdog waits against, None while nothing can be judged.
        self._armed: Optional[tuple] = None  # gil-atomic
        self._samples: List[Dict] = []  # gil-atomic
        self._closing = False  # gil-atomic
        #: The report count of the gap whose span ``close_span`` ended: the
        #: watchdog holds no span for the rest of that gap.
        self._unspanned: Optional[int] = None  # gil-atomic
        self._wake = threading.Event()
        #: Set while the watchdog holds no span.
        self._quiet = threading.Event()
        self._quiet.set()
        self._thread: Optional[threading.Thread] = None
        if _GEN2 not in gc.callbacks:
            gc.callbacks.append(_GEN2)

    # ---- the task loop's side ----

    def start(self) -> None:
        """The calling thread is the task loop: publish its open phase,
        remember its CPU clock, start the watchdog."""
        ident = threading.get_ident()
        try:
            clock = time.pthread_getcpuclockid(ident)
        except (AttributeError, OSError):  # not Linux: no thread CPU clock
            clock = None
        self._phases.watch_this_thread()
        with self._lock:
            self._loop_ident, self._loop_cpu_clock = ident, clock
            if self._thread is not None:
                return
            self._closing = False
            self._thread = thread = threading.Thread(
                target=self._watch, name="edl-watchdog", daemon=True
            )
        thread.start()

    def stop(self) -> None:
        with self._lock:
            thread, self._thread = self._thread, None
            self._closing = True
            held, self._held = self._held, None
        if held is not None:  # the loop ends before a report can carry it
            record, left = held
            logger.warning(
                "stall: the loop ended %d report(s) after a report came "
                "%.3f s late (%.3f s caught up since; cause=%s phase=%s): "
                "not settled, in no record",
                CATCH_UP_GAPS - left, record["excess_s"],
                record["recovered_s"], record["cause"], record["phase"],
            )
        if thread is not None:
            self._wake.set()
            thread.join(timeout=2.0)

    def taint(self) -> None:
        """The gap under way is not the job's pace (the dispatcher had no
        task, an evaluation ran): it is neither judged nor kept."""
        with self._lock:
            self._tainted = True
            self._armed = None

    def close_span(self, timeout_s: float = 0.5) -> None:
        """Have the watchdog close the span it holds, and wait for that:
        before the bridge goes (a span left open when its profiler stops
        is in no trace).  A stall still under way keeps its samples and
        gets no second span."""
        if not self._quiet.is_set():
            with self._lock:
                self._unspanned = self._reports
            self._wake.set()
            self._quiet.wait(timeout_s)

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return {
                "stalls": self.stalls,
                "stall_s": round(self.stall_s, 6),
                "stall_unnamed_s": round(self.stall_unnamed_s, 6),
            }

    def _mark(self, phase_times: Dict[str, float], clock: Optional[int]) -> Dict:
        usage = resource.getrusage(resource.RUSAGE_SELF)
        thread_usage = resource.getrusage(resource.RUSAGE_THREAD)
        return {
            "t": time.perf_counter(),
            "phases": phase_times,
            "cpu_process_s": time.process_time(),
            "cpu_loop_s": 0.0 if clock is None else time.clock_gettime(clock),
            "nivcsw": usage.ru_nivcsw,
            "majflt": usage.ru_majflt,
            "inblock": usage.ru_inblock,
            "loop_nivcsw": thread_usage.ru_nivcsw,
            "gc2_s": _GEN2.seconds,
            **self._probes(),
        }

    def on_report(
        self, task_id: int, seq: int, phase_times: Dict[str, float]
    ) -> Optional[Dict]:
        """Called as a SUCCESSFUL TRAINING report is made up (gaps run
        between those; its owner taints the gap for any other), before the
        report's counters are read.  Returns the record of a stall that is
        now settled (a late gap whose excess the gaps after it did not
        catch up: it ended one to ``CATCH_UP_GAPS`` reports ago), else
        None."""
        with self._lock:
            ident, clock = self._loop_ident, self._loop_cpu_clock
        if threading.get_ident() != ident:
            return None  # the preemption thread's last flush
        mark = self._mark(phase_times, clock)
        rpc_s, rpc = IN_FLIGHT.take_longest(ident)
        with self._lock:
            prev, self._prev = self._prev, mark
            samples, self._samples = self._samples, []
            tainted, self._tainted = self._tainted, False
            self._reports += 1
            judged = prev is not None and not tainted
            gap = mark["t"] - prev["t"] if judged else 0.0
            verdict = self._rule.judge(gap) if judged else None
            base = self._base
            if judged and verdict is None:
                self._base = (prev, mark)
            limit = self._rule.limit
            self._armed = (
                None if limit is None else (self._reports, mark["t"], *limit)
            )
            # The late gap held back, if any: this gap catches a part of
            # it up, or shows the loop back in step, which settles it.
            settled, held = None, self._held
            if held is not None:
                record = held[0]
                short = record["median_s"] - gap if judged and verdict is None else 0.0
                record["recovered_s"] += max(short, 0.0)
                held[1] -= 1
                if (
                    short <= SHORT_SHARE * record["median_s"]  # back in step
                    or record["recovered_s"] >= record["excess_s"]  # all made good
                    or not held[1]
                ):
                    settled, self._held = self._settle_locked(record), None
            late = None
            if verdict is not None:
                late = _record(prev, mark, base, verdict, samples)
                late.update(
                    task=task_id, seq=seq, rpc=rpc, rpc_s=rpc_s, recovered_s=0.0
                )
                late["cause"] = name_cause(late)
                self._held = [late, CATCH_UP_GAPS]
        if samples:
            self._wake.set()  # the report came: the watchdog's span ends here
        if late is not None:
            # With the ring on, the excess as a complete event: it began a
            # median after the previous report and ends now.
            trace.default().add_complete(
                "stall", "stall",
                trace.now_us() - late["excess_s"] * 1e6, late["excess_s"] * 1e6,
                {k: late[k] for k in ("cause", "phase", "task", "seq", "rpc")},
            )
            logger.warning(
                "stall: %.3f s between reports (median %.3f s, excess %.3f s) "
                "ending with task %s: cause=%s phase=%s rpc=%s device=%s",
                late["gap_s"], late["median_s"], late["excess_s"],
                late["task"], late["cause"], late["phase"],
                late["rpc"] or "-", late["device"] or "-",
            )
        return settled

    def _settle_locked(self, held: Dict) -> Optional[Dict]:  # guarded-by: _lock
        """The held late gap's record, settled: what of its excess the gaps after
        it did not catch up is LOST, and a stall when it is more than the
        allowance (counted, and returned to ride a report); a late report
        the next ones made good is none."""
        held["lost_s"] = max(held["excess_s"] - held["recovered_s"], 0.0)
        if held["lost_s"] <= allowance(held["median_s"]):
            logger.warning(
                "stall: the report of task %s came %.3f s late and the next "
                "ones caught %.3f s of it up: no stall",
                held["task"], held["excess_s"], held["recovered_s"],
            )
            return None
        self.stalls += 1
        self.stall_s += held["lost_s"]
        if held["cause"] == "unnamed":
            self.stall_unnamed_s += held["lost_s"]
        return held

    # ---- the watchdog's side ----

    def _sample(self, waited_s: float, median_s: float, late_s: float) -> Dict:
        """One live sample: made, kept (``_samples``), then logged."""
        frames = sys._current_frames()
        stack = []
        frame = frames.get(self._loop_ident)
        while frame is not None and len(stack) < STACK_FRAMES:
            stack.append(_frame_str(frame))  # innermost first
            frame = frame.f_back
        me = threading.get_ident()
        threads = {
            t.name: _frame_str(frames[t.ident])
            for t in threading.enumerate()
            if t.ident in frames and t.ident not in (me, self._loop_ident)
        }
        name, t0, task = self._phases.watched_open() or ("", None, None)
        now = time.perf_counter()
        rpc_s, rpc = IN_FLIGHT.of_thread(self._loop_ident, now)
        try:
            ready = self._device_ready()
        except Exception:  # a deleted buffer, a backend gone: not the watchdog's to raise
            ready = None
        sample = {
            "waited_s": round(waited_s, 3),
            "watchdog_late_s": round(late_s, 3),
            "phase": name,
            "phase_open_s": 0.0 if t0 is None else round(now - t0, 3),
            "task": task,
            "rpc": rpc,
            "rpc_open_s": round(rpc_s, 3),
            "device_ready": ready,
            "stack": stack,
            "threads": threads,
        }
        # Kept before it is said: the log's write lets the loop run, whose
        # report takes the samples there are.
        self._samples.append(sample)
        logger.warning(
            "stall: no training report for %.2f s (median gap %.2f s); the "
            "loop is in phase %r (task %s) for %.2f s, rpc %s, device "
            "output ready: %s, this watchdog overslept %.2f s at most; loop "
            "stack, innermost first: %s; other threads: %s",
            waited_s, median_s, name or "-", task, sample["phase_open_s"],
            rpc or "-", ready, late_s, " < ".join(stack), threads,
        )
        return sample

    def _watch(self) -> None:
        trace.name_os_thread()
        taken = 0  # samples of the gap under way
        seen = -1  # the report count that gap began with
        span = None
        late = 0.0  # the longest this thread's own wake overslept in that gap
        woke = time.perf_counter()
        while True:
            self._wake.wait(WATCH_PERIOD_S)
            self._wake.clear()
            now = time.perf_counter()
            overslept, woke = now - woke - WATCH_PERIOD_S, now
            armed = self._armed
            if span is not None and (
                self._closing or armed is None or armed[0] != seen
                or self._unspanned == seen
            ):
                span.__exit__(None, None, None)
                span = None
                self._quiet.set()
            if self._closing:
                return
            if armed is None:
                continue
            reports, t_report, median, limit = armed
            waited = now - t_report
            if reports != seen:
                seen, taken, late = reports, 0, 0.0
                overslept = min(overslept, waited)  # what of it lies in this gap
            late = max(late, overslept)
            if taken >= MAX_SAMPLES or waited <= limit * (1 << taken):
                continue
            sample = self._sample(waited, median, late)
            taken += 1
            if span is None and self._unspanned != seen:
                self._quiet.clear()
                span = trace.bridge_span(
                    "stall", phase=sample["phase"], task=sample["task"]
                )
                span.__enter__()
