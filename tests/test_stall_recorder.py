"""The stall recorder (PR 54): ``common/stall.py``'s rule, record, cause and
watchdog; what ``PhaseTimers`` and the RPC client publish for it; the
``stall`` record and the three counters of a worker's job.

Timing on a shared host: every job here is a tiny CPU job whose ordinary
gaps wander, so a case looks for the stall it MADE (a second or more, by
its cause) and never counts the stalls of a run; the clean job's rule is
set out of the host's reach instead."""

from __future__ import annotations

import gc
import glob
import os
import threading
import time

import pytest

from elasticdl_tpu import chaos
from elasticdl_tpu.common import rpc, stall, trace
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.metrics import MetricsWriter, PhaseTimers, read_metrics
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.worker.worker import COUNTER_GAUGES, DirectMasterProxy, Worker

COUNTERS = ("stalls", "stall_s", "stall_unnamed_s")

# ---------------------------------------------------------------- the rule


def test_nothing_is_judged_before_four_gaps_are_in():
    rule = stall.StallRule()
    assert [rule.judge(g) for g in (9.0, 0.3, 0.3)] == [None, None, None] and rule.limit is None
    assert rule.judge(50.0) is None  # the fourth gap itself: four are in only after it
    median, limit = rule.limit
    assert median == pytest.approx((0.3 + 9.0) / 2) and limit == pytest.approx(median * 1.25)


def test_the_excess_is_over_the_median_and_the_allowance_is_a_tenth_of_a_second_or_a_quarter():
    rule = stall.StallRule()
    for _ in range(6):
        assert rule.judge(0.30) is None
    assert rule.limit == pytest.approx((0.30, 0.40))  # max(0.1 s, 0.25 x 0.3 s)
    assert rule.judge(0.399) is None
    median, excess = rule.judge(1.80)
    assert (median, excess) == pytest.approx((0.30, 1.50))  # the EXCESS, not the gap
    slow = stall.StallRule()
    for _ in range(6):
        slow.judge(2.0)
    assert slow.limit == pytest.approx((2.0, 2.5))  # a quarter of the median, once that is the larger
    assert slow.judge(2.4) is None and slow.judge(2.6) == pytest.approx((2.0, 0.6))


def test_a_slow_but_steady_job_counts_no_stall_and_a_slower_pace_becomes_the_pace():
    rule = stall.StallRule()
    assert [rule.judge(7.5 + 0.01 * (i % 3)) for i in range(40)] == [None] * 40
    # a job that becomes three times slower for good: stalls until the median has moved, none after
    verdicts = [rule.judge(22.0) is not None for _ in range(stall.HISTORY)]
    assert verdicts[0] and not any(verdicts[stall.HISTORY // 2 + 1:])
    assert len(rule._gaps) == stall.HISTORY


# --------------------------------------------------------------- the cause


def _hand_made(**over):
    rec = {
        "excess_s": 1.0, "compile_s": 0.0, "gc2_s": 0.0, "profile_stop": False, "rpc": "", "rpc_s": 0.0,
        "device": "", "injected_s": 0.0, "loop_nivcsw": 0, "loadavg_1m": 1.0, "cores": 8,
        "watchdog_late_s": 0.0, "cpu_process_s": 0.05,
    }
    rec.update(over)
    return rec


@pytest.mark.parametrize("over, cause", [
    ({"compile_s": 0.5, "gc2_s": 0.9, "phase_excess_s.prep_wait": 1.0}, "compile"),
    ({"gc2_s": 0.5, "profile_stop": True}, "gc"),
    ({"profile_stop": True, "phase_excess_s.control": 1.0}, "profile_stop"),
    ({"phase_excess_s.checkpoint": 0.6, "phase_excess_s.prep_wait": 0.4}, "checkpoint"),
    ({"phase_excess_s.prep_wait": 0.5}, "ingest"),
    ({"phase_excess_s.lease_wait": 0.3, "phase_excess_s.control": 0.3, "rpc": "GetTask", "rpc_s": 0.6}, "master"),
    ({"phase_excess_s.metrics": 0.9, "rpc": "", "rpc_s": 0.0}, "unnamed"),  # no call in flight: not the master's
    ({"phase_excess_s.metrics": 0.9, "rpc": "Heartbeat", "rpc_s": 0.01}, "unnamed"),  # nor with a short one
    ({"phase_excess_s.step_wait": 0.9, "device": "busy"}, "device"),
    ({"phase_excess_s.step_wait": 0.9, "device": "ready"}, "fetch"),  # the chip had finished; the fetch had not returned
    ({"phase_excess_s.step_wait": 0.9, "device": "mixed"}, "unnamed"),
    ({"phase_excess_s.step_wait": 0.9, "device": ""}, "unnamed"),  # nobody looked
    ({"phase_excess_s.loop": 1.0, "injected_s": 1.5}, "injected"),
    ({"phase_excess_s.prep_wait": 1.0, "injected_s": 1.5}, "ingest"),  # the injected point's own cause comes first
    ({"loop_nivcsw": 3, "loadavg_1m": 9.5}, "descheduled"),
    ({"loop_nivcsw": 3, "loadavg_1m": 2.0}, "unnamed"),
    # the watchdog overslept too: no thread of the process ran, and none burned CPU (a v5e's natural stalls, PR 54)
    ({"phase_excess_s.step_wait": 1.0, "device": "ready", "watchdog_late_s": 1.4}, "descheduled"),
    ({"phase_excess_s.step_wait": 1.0, "device": "busy", "watchdog_late_s": 0.6}, "descheduled"),
    ({"phase_excess_s.prep_wait": 1.0, "watchdog_late_s": 1.4}, "ingest"),  # what the loop waited for comes first
    ({"phase_excess_s.loop": 1.0, "watchdog_late_s": 0.9, "cpu_process_s": 0.9}, "unnamed"),  # a call that kept the GIL and computed
    ({"loop_nivcsw": 0, "loadavg_1m": 40.0}, "unnamed"),
    ({"compile_s": 0.49, "gc2_s": 0.49, "phase_excess_s.prep_wait": 0.49}, "unnamed"),  # under half the excess each
], ids=lambda v: v if isinstance(v, str) else "")
def test_one_cause_a_stall_by_the_first_test_that_holds(over, cause):
    assert stall.name_cause(_hand_made(**over)) == cause and cause in stall.CAUSES


# ----------------------------------------- what the recorder reads from


def test_a_phase_publishes_what_its_thread_is_in_and_restores_the_enclosing_one():
    phases = PhaseTimers()
    assert phases.watched_open() is None
    seen = {}

    def other():  # a thread nobody watches publishes to itself alone
        with phases.phase("checkpoint_bg", task=99):
            seen["other"] = phases.watched_open()

    phases.watch_this_thread()
    assert phases.watched_open() is None  # outside every phase
    before = time.perf_counter()
    with phases.phase("control"):
        name, t0, task = phases.watched_open()
        assert (name, task) == ("control", None) and before <= t0 <= time.perf_counter()
        with phases.phase("lease_wait", task=7):
            assert phases.watched_open()[::2] == ("lease_wait", 7)
            t = threading.Thread(target=other)
            t.start()
            t.join(10.0)
            assert not t.is_alive() and seen["other"][::2] == ("lease_wait", 7)
        assert phases.watched_open() == (name, t0, task)  # the enclosing one, as it was
    assert phases.watched_open() is None
    with pytest.raises(KeyError):
        with phases.phase("metrics", task=1):
            raise KeyError("the way out restores too")
    assert phases.watched_open() is None
    # the timers are what they were: three foreground entries and the other thread's
    assert phases.counts() == {"lease_wait": 1, "control": 1, "checkpoint_bg": 1, "metrics": 1}


def test_the_call_in_flight_is_published_and_the_longest_is_kept_until_taken():
    calls = rpc._InFlight()
    me = threading.get_ident()
    assert calls.of_thread(me, 5.0) == (0.0, "") and calls.take_longest(me) == (0.0, "")
    with calls.of("GetTask"):
        time.sleep(0.02)
        seconds, method = calls.of_thread(me, time.perf_counter())
        assert method == "GetTask" and 0.02 <= seconds < 5.0
        assert calls.take_longest(me)[1] == "GetTask"  # still open: the longest so far
    with calls.of("Heartbeat"):
        pass
    seconds, method = calls.take_longest(me)
    assert method == "GetTask" and seconds >= 0.02 and calls.open == {}
    assert calls.take_longest(me) == (0.0, "")  # taken
    with pytest.raises(ValueError):
        with calls.of("ReportTaskResult"):
            raise ValueError("a failed call is closed too")
    assert calls.open == {} and calls.take_longest(me)[1] == "ReportTaskResult"


def test_generation_two_collections_are_timed_and_the_others_return_at_once():
    clock = stall._Gen2Clock()
    clock("start", {"generation": 0})
    clock("stop", {"generation": 0})
    clock("start", {"generation": 1})
    assert clock.seconds == 0.0 and clock._t0 == 0.0
    clock("start", {"generation": 2})
    time.sleep(0.01)
    clock("stop", {"generation": 2, "collected": 0})
    assert 0.01 <= clock.seconds < 5.0


# ------------------------------------- the recorder, driven by hand


class _Harness:
    """A recorder on real clocks and threads, with its owner's side made
    by hand: ``step()`` is one clean gap, ``report()`` ends the gap."""

    GAP_S = 0.03

    def __init__(self, monkeypatch, min_excess_s=0.25):
        # the host's own hiccups stay under the allowance; the stalls made here are well over it
        monkeypatch.setattr(stall, "MIN_EXCESS_S", min_excess_s)
        self.phases = PhaseTimers()
        self.state = {
            "compiles": 0, "compile_s": 0.0, "dispatches_device_idle": 0, "injected_s": 0.0, "profile": "none",
            "profile_stops": 0, "profile_stopping": False, "saving": False, "prepping": False,
        }
        self.ready = None
        self.recorder = stall.StallRecorder(self.phases, lambda: dict(self.state), lambda: self.ready)
        self.seq = 0

    def report(self):
        self.seq += 1
        return self.recorder.on_report(100 + self.seq, self.seq, self.phases.snapshot())

    def settled(self):
        """The late gap just reported, settled: one clean gap after it
        shows the loop back in step (nothing was caught up).  Beside busy
        neighbours the pace the recorder learnt is a little slower than a
        clean gap, which then counts as CATCHING UP and leaves the late
        gap held: one more gap, ``CATCH_UP_GAPS`` at most, is the rule's
        own answer to that (D24).  With nothing held it is one gap."""
        for _ in range(stall.CATCH_UP_GAPS):
            with self.phases.phase("step_wait", task=self.seq):
                time.sleep(self.GAP_S)
            record = self.report()
            if record is not None or self.recorder._held is None:
                break
        return record

    def warm(self, gaps=6):
        self.recorder.start()
        self.report()
        for _ in range(gaps):
            with self.phases.phase("step_wait", task=self.seq):
                time.sleep(self.GAP_S)
            assert self.report() is None
        assert self.recorder._armed is not None


@pytest.fixture()
def harness(monkeypatch):
    made = _Harness(monkeypatch)
    yield made
    made.recorder.stop()
    assert made.recorder._thread is None and not any(t.name == "edl-watchdog" for t in threading.enumerate())


def _sleeping_frame():
    time.sleep(0.7)


@pytest.fixture()
def said():
    """What the recorder logged (the repo's loggers write to stderr themselves)."""
    import logging

    lines = []
    handler = logging.Handler(level=logging.WARNING)
    handler.emit = lambda record: lines.append(record.getMessage())
    stall.logger.addHandler(handler)
    yield lines
    stall.logger.removeHandler(handler)


def test_a_stalled_gap_is_recorded_whole_with_the_live_sample_of_the_sleeping_frame(harness, said):
    harness.warm()
    harness.ready = False
    harness.state["dispatches_device_idle"] = 3
    with harness.phases.phase("step_wait", task=4242):
        _sleeping_frame()
    assert harness.report() is None  # held: the next gaps may catch it up
    assert harness.recorder.counters() == {"stalls": 0, "stall_s": 0.0, "stall_unnamed_s": 0.0}
    late_seq = harness.seq
    record = harness.settled()
    assert record is not None
    assert record["gap_s"] == pytest.approx(0.7, abs=0.2) and record["median_s"] == pytest.approx(harness.GAP_S, abs=0.03)
    assert record["excess_s"] == pytest.approx(record["gap_s"] - record["median_s"])
    assert (record["task"], record["seq"]) == (100 + late_seq, late_seq)  # the report that ended the late gap
    assert record["lost_s"] == pytest.approx(record["excess_s"] - record["recovered_s"]) and record["recovered_s"] < 0.03
    assert record["phase"] == "step_wait" and record["phase_s.step_wait"] == pytest.approx(0.7, abs=0.1)
    assert record["phase_excess_s.step_wait"] == pytest.approx(0.7 - harness.GAP_S, abs=0.1)
    assert abs(record["phase_s.loop"]) < 0.1 and record["dispatches_device_idle"] == 3
    assert sum(v for k, v in record.items() if k.startswith("phase_s.")) == pytest.approx(record["gap_s"])
    # asleep: neither the loop's thread nor the process burned the gap
    assert 0 <= record["cpu_loop_s"] < 0.3 and record["cpu_process_s"] < record["gap_s"]
    assert record["cores"] == os.cpu_count() and record["loadavg_1m"] >= 0
    assert {"nivcsw", "majflt", "inblock", "loop_nivcsw", "gc2_s", "compiles", "compile_s"} <= set(record)
    # the live half: one sample at the allowance, a second at twice it (0.28 s and 0.56 s of 0.7 s)
    samples = record["samples"]
    assert 1 <= len(samples) <= 2 and samples[0]["waited_s"] < 0.5
    assert samples[0]["phase"] == "step_wait" and samples[0]["task"] == 4242 and samples[0]["device_ready"] is False
    stack = samples[0]["stack"]
    assert len(stack) <= stall.STACK_FRAMES and stack[0].endswith(":_sleeping_frame")
    assert stack[0].startswith(os.path.join("tests", "test_stall_recorder.py:"))
    assert any(frame.endswith(":test_a_stalled_gap_is_recorded_whole_with_the_live_sample_of_the_sleeping_frame") for frame in stack)
    assert "edl-watchdog" not in samples[0]["threads"] and "MainThread" not in samples[0]["threads"]
    assert record["device"] == "busy" and record["cause"] == "device"
    # the sample was in the log BEFORE the report came, the verdict after it
    assert "no training report for" in said[0] and "_sleeping_frame" in said[0] and "phase 'step_wait' (task 4242)" in said[0]
    assert "cause=device phase=step_wait" in said[-1]
    assert harness.recorder.counters() == {"stalls": 1, "stall_s": round(record["lost_s"], 6), "stall_unnamed_s": 0.0}
    # the next gap is judged against the same pace, and is clean
    with harness.phases.phase("step_wait"):
        time.sleep(harness.GAP_S)
    assert harness.report() is None and harness.recorder.counters()["stalls"] == 1


def test_an_unnamed_stall_counts_into_the_closure_and_keeps_its_stack(harness):
    harness.warm()
    _sleeping_frame()  # in no phase, nothing grew, nobody injected it
    assert harness.report() is None
    record = harness.settled()
    # Beside busy neighbours the host may take the loop's thread off its core inside the gap, and the recorder is BUILT
    # to say so (``descheduled``: ``name_cause``'s last two tests read the host's load, not this job): either word is
    # right here; what the case holds is that no phase and no device took the blame, and that the stack is kept.
    assert record["cause"] in ("unnamed", "descheduled") and (record["phase"], record["device"]) == (stall.LOOP, "")
    assert record["samples"][0]["phase"] == "" and record["samples"][0]["stack"][0].endswith(":_sleeping_frame")
    counters = harness.recorder.counters()
    assert counters["stalls"] == 1 and counters["stall_s"] == round(record["lost_s"], 6)
    assert counters["stall_unnamed_s"] == (counters["stall_s"] if record["cause"] == "unnamed" else 0.0)


def _one_call_that_keeps_the_gil(n):
    """``sum(range(n))``: ONE call into C, so no thread of this process runs while it does.
    Returns the wall and the thread's own CPU seconds of it."""
    t0, c0 = time.perf_counter(), time.thread_time()
    sum(range(n))
    return time.perf_counter() - t0, time.thread_time() - c0


def test_a_call_that_keeps_the_gil_shows_as_the_watchdogs_own_lateness_and_a_sleep_does_not(harness, said):
    """``watchdog_late_s``: the loop's thread alone was blocked (the
    watchdog woke on time all through), or the whole interpreter stood
    still (it could not wake: a call that kept the GIL, a frozen process)."""
    # Sized by the thread's CPU time, the fastest of three: a timing of the wall beside busy neighbours counts the
    # time the thread was off its core, and a hold sized from it comes out short (D24).  0.8 s of CPU is at least
    # 0.8 s of wall, whatever the host does meanwhile.  Sized BEFORE the recorder runs: the timings are no gap of its.
    n = int(2_000_000 * 0.8 / min(_one_call_that_keeps_the_gil(2_000_000)[1] for _ in range(3)))
    harness.warm()
    with harness.phases.phase("step_wait"):
        held, burned = _one_call_that_keeps_the_gil(n)
        time.sleep(0.05)  # the watchdog wakes now, and samples
    assert held > 0.45 and harness.report() is None
    record = harness.settled()
    # the first sample could be taken only when the call returned, late by all that lay past the limit
    assert record["watchdog_late_s"] == max(s["watchdog_late_s"] for s in record["samples"])
    # ... and the loop's thread BURNED the hold: its CPU seconds over the gap are the call's own (the wall of a
    # loaded host is longer than they are, so the wall is not what they are held to)
    assert held - 0.45 < record["watchdog_late_s"] <= record["gap_s"] and record["cpu_loop_s"] > 0.9 * burned > 0.4
    assert "this watchdog overslept" in said[0]
    with harness.phases.phase("step_wait"):
        _sleeping_frame()  # asleep: the GIL is free, and the watchdog wakes ten times a second
    assert harness.report() is None
    asleep = harness.settled()
    assert asleep["samples"] and asleep["watchdog_late_s"] < record["watchdog_late_s"]


def test_what_grew_over_the_gap_names_the_cause(harness):
    harness.warm()
    harness.state["compile_s"] += 0.5
    harness.state["compiles"] += 2
    time.sleep(0.6)
    assert harness.report() is None
    harness.state["profile_stops"] += 1  # a stop begun inside the gap (the inline one)
    time.sleep(0.6)
    record = harness.report()  # a late gap behind a late gap settles it: nothing was caught up
    assert (record["cause"], record["compiles"], record["compile_s"]) == ("compile", 2, pytest.approx(0.5))
    assert record["recovered_s"] == 0.0 and record["lost_s"] == record["excess_s"]
    harness.state["profile_stopping"] = True  # alive at the gap's end
    time.sleep(0.6)
    assert harness.report()["cause"] == "profile_stop"
    harness.state["profile_stopping"] = False  # ... and so at the next gap's beginning
    time.sleep(0.6)
    assert harness.report()["cause"] == "profile_stop"
    record = harness.settled()
    assert record["cause"] == "profile_stop" and record["profile_stop"] is True
    assert harness.recorder.counters()["stalls"] == 4 and harness.recorder.counters()["stall_unnamed_s"] == 0.0


def test_a_forced_generation_two_collection_is_named_gc(harness):
    ballast = [[i] for i in range(300_000)]  # what a full collection has to walk
    harness.warm()
    t0 = time.perf_counter()
    with harness.phases.phase("metrics"):
        while time.perf_counter() - t0 < 0.6:
            gc.collect()
    assert harness.report() is None
    record = harness.settled()
    assert len(ballast) == 300_000
    assert record["cause"] == "gc" and record["gc2_s"] >= record["excess_s"] / 2
    assert record["phase"] == "metrics" and record["cpu_loop_s"] > 0.3  # running, not blocked


def test_a_tainted_gap_is_neither_judged_nor_kept_and_only_the_loop_is_judged(harness):
    harness.warm()
    pace = harness.recorder._rule.limit
    harness.recorder.taint()  # the dispatcher had no task
    assert harness.recorder._armed is None
    time.sleep(0.6)
    assert harness.report() is None and harness.recorder._rule.limit == pace
    assert harness.recorder._armed is not None and harness.recorder.counters()["stalls"] == 0
    # a report made on another thread (the preemption thread's last flush) is no gap's end
    time.sleep(0.6)
    out = []
    t = threading.Thread(target=lambda: out.append(harness.report()))
    t.start()
    t.join(10.0)
    assert out == [None] and harness.recorder.counters()["stalls"] == 0
    assert harness.report() is None and harness.settled() is not None  # the loop's own report still ends that gap


def test_the_watchdog_samples_at_most_four_times_each_at_twice_the_wait(harness, monkeypatch):
    monkeypatch.setattr(stall, "MIN_EXCESS_S", 0.05)
    monkeypatch.setattr(stall, "WATCH_PERIOD_S", 0.02)
    harness.recorder.stop()
    harness.warm()
    _, limit = harness.recorder._rule.limit
    time.sleep(limit * 20)
    assert harness.report() is None
    samples = harness.settled()["samples"]
    assert len(samples) == stall.MAX_SAMPLES
    for n, sample in enumerate(samples):
        assert limit * 2**n < sample["waited_s"] + 0.001 < limit * 2**n + 0.5


class _Annotation:
    log = []

    def __init__(self, name, attrs):
        self.name, self.attrs = name, dict(attrs)

    def __enter__(self):
        _Annotation.log.append(("enter", self.name, self.attrs, threading.current_thread().name))

    def __exit__(self, *exc):
        _Annotation.log.append(("exit", self.name, threading.current_thread().name))


def test_with_a_bridge_installed_the_stall_span_is_entered_and_left_once_on_the_watchdogs_thread(harness):
    harness.warm()
    _Annotation.log = []
    spans = lambda: [e for e in _Annotation.log if e[1] == "stall"]  # noqa: E731
    trace.set_bridge(_Annotation)
    try:
        with harness.phases.phase("prep_wait", task=31):
            time.sleep(0.7)
            assert spans() == [("enter", "stall", {"phase": "prep_wait", "task": 31}, "edl-watchdog")]
        assert harness.report() is None
        harness.recorder.close_span()  # what _profile_close does before it takes the bridge away
        assert spans()[1:] == [("exit", "stall", "edl-watchdog")]
        # settled as every other case settles a late gap (one clean gap, ``CATCH_UP_GAPS`` at most beside busy neighbours):
        # its own single gap read as CATCHING UP under load and left the record held (PR 66's sitting: the one ``F``)
        assert harness.settled()["cause"] == "ingest"
    finally:
        trace.set_bridge(None)
    assert [e[0] for e in spans()] == ["enter", "exit"]
    # the loop's own spans went through the same bridge, on the loop's thread
    assert {e[-1] for e in _Annotation.log if e[1] == "prep_wait"} == {threading.current_thread().name}
    # without a bridge the watchdog holds the shared no-op
    assert trace.bridge_span("stall", phase="x") is trace._NULL_SPAN


def test_a_window_that_closes_under_a_stall_ends_its_span_at_once_and_the_stall_gets_no_second_one(harness):
    harness.warm()
    _Annotation.log = []
    spans = lambda: [e[0] for e in _Annotation.log if e[1] == "stall"]  # noqa: E731
    trace.set_bridge(_Annotation)
    try:
        with harness.phases.phase("step_wait", task=32):
            time.sleep(0.5)
            assert spans() == ["enter"]
            harness.recorder.close_span()  # job end: the window closes while the report is still late
            assert spans() == ["enter", "exit"]
            time.sleep(0.5)  # the second sample falls here: taken, under no span
        assert spans() == ["enter", "exit"] and len(harness.recorder._samples) >= 2
        assert harness.report() is None
        assert len(harness.settled()["samples"]) >= 2
    finally:
        trace.set_bridge(None)
    assert spans() == ["enter", "exit"]


def test_with_the_ring_on_a_stall_is_one_complete_event_of_its_excess(harness):
    rec = trace.default()
    was = rec.enabled
    try:
        trace.configure(enabled=True)
        rec.clear()
        harness.warm()
        with harness.phases.phase("prep_wait"):
            time.sleep(0.6)
        assert harness.report() is None
        (event,) = [e for e in rec.export() if e["cat"] == "stall"]  # written when the late report came
        assert event["ts"] + event["dur"] == pytest.approx(trace.now_us(), abs=2e5)
        record = harness.settled()
        assert event["name"] == "stall" and event["ph"] == "X" and event["dur"] == pytest.approx(record["excess_s"] * 1e6, rel=1e-3)
        assert event["args"] == {"cause": "ingest", "phase": "prep_wait", "task": record["task"], "seq": record["seq"], "rpc": ""}
    finally:
        rec.clear()
        trace.configure(enabled=was)


def test_a_late_report_that_the_next_ones_catch_up_is_no_stall_and_a_part_caught_up_is_not_lost(monkeypatch, said):
    """The device works through what was queued while the host was away:
    the reports behind a late one come early, and only what they do not
    make good was lost (the chip's readings are in PERF.md, PR 54)."""
    slow = _Harness(monkeypatch, min_excess_s=0.1)
    slow.GAP_S = 0.2
    try:
        slow.warm(gaps=5)
        with slow.phases.phase("step_wait"):
            time.sleep(0.5)  # 0.3 s late
        assert slow.report() is None
        assert slow.report() is None  # at once: 0.2 s short of the median, caught up; still held
        assert slow.report() is None  # and again: all of it caught up, no stall
        assert slow.recorder.counters() == {"stalls": 0, "stall_s": 0.0, "stall_unnamed_s": 0.0}
        assert slow.recorder._held is None and "caught" in said[-1] and "no stall" in said[-1]
        for _ in range(3):  # the short gaps joined the history; the pace is what it was
            assert slow.settled() is None
        with slow.phases.phase("step_wait"):
            time.sleep(0.7)  # 0.5 s late
        assert slow.report() is None
        assert slow.report() is None  # 0.2 s of it caught up
        record = slow.settled()  # back in step: settled, 0.3 s lost
        assert record["excess_s"] == pytest.approx(0.5, abs=0.08) and record["recovered_s"] == pytest.approx(0.2, abs=0.08)
        assert record["lost_s"] == pytest.approx(record["excess_s"] - record["recovered_s"]) and record["lost_s"] > 0.1
        assert slow.recorder.counters() == {"stalls": 1, "stall_s": round(record["lost_s"], 6), "stall_unnamed_s": round(record["lost_s"], 6)}
    finally:
        slow.recorder.stop()


def test_a_late_gap_is_held_for_three_gaps_at_most_and_the_loops_end_says_what_it_drops(harness, said):
    harness.warm()
    time.sleep(0.6)
    assert harness.report() is None
    for left in (2, 1):  # each a little short of the median: catching up, not yet in step
        time.sleep(harness.GAP_S / 4)
        assert harness.report() is None and harness.recorder._held[1] == left
    time.sleep(harness.GAP_S / 4)
    record = harness.report()
    assert record is not None and 0 < record["recovered_s"] < 3 * harness.GAP_S
    time.sleep(0.6)
    assert harness.report() is None
    harness.recorder.stop()
    assert harness.recorder._held is None and "not settled, in no record" in said[-1]
    assert harness.recorder.counters()["stalls"] == 1


def test_a_stopped_recorder_starts_again_and_stopping_closes_an_open_span(harness):
    harness.warm()
    first = harness.recorder._thread
    trace.set_bridge(_Annotation)
    _Annotation.log = []
    try:
        time.sleep(0.5)
        harness.recorder.stop()
        assert not first.is_alive() and [e[:2] for e in _Annotation.log] == [("enter", "stall"), ("exit", "stall")]
    finally:
        trace.set_bridge(None)
    harness.recorder.start()
    assert harness.recorder._thread is not first and harness.recorder._thread.is_alive()


# --------------------------------------------------------- a worker's job


def _job(tmp_path, tasks=16, **cfg):
    train = str(tmp_path / "train.rio")
    generate("mnist", train, 16 * tasks)
    # tasks of under 0.1 s (two steps of eight examples): the stalls made below are 1.5 s, and what the tasks
    # prepped ahead can hide of one stays a small part of it on a loaded host too
    config = JobConfig(
        model_def="mnist.model_spec", model_params="compute_dtype=float32", training_data=train,
        minibatch_size=8, num_minibatches_per_task=2, prep_depth=1, **cfg,
    )
    reader = create_data_reader(train)
    dispatcher = TaskDispatcher(reader.create_shards(16))
    spec = load_model_spec("elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32")
    writer = MetricsWriter(str(tmp_path / "metrics"), tensorboard=False)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    return config, servicer, reader, spec, writer


def _run(tmp_path, devices, servicer_hook=None, **cfg):
    config, servicer, reader, spec, writer = _job(tmp_path, **cfg)
    seen = []
    report = servicer.ReportTaskResult

    def reported(req):
        seen.append(req)
        if servicer_hook is not None:
            servicer_hook(len(seen))
        return report(req)

    servicer.ReportTaskResult = reported
    try:
        worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices[:1])
        worker.run()
    finally:
        chaos.configure("")
        writer.close()
    assert not any(t.name == "edl-watchdog" for t in threading.enumerate())
    return worker, seen, read_metrics(str(tmp_path / "metrics"))


def _stalls(records, cause):
    return [r for r in records if r["kind"] == "stall" and r["cause"] == cause]


def test_a_clean_job_writes_no_stall_record_and_reports_the_three_counters_at_zero(tmp_path, devices, monkeypatch):
    monkeypatch.setattr(stall, "MIN_EXCESS_S", 60.0)  # out of a loaded host's reach: what a clean run IS
    worker, seen, records = _run(tmp_path, devices)
    counters = [r for r in records if r["kind"] == "counter"]
    assert len(seen) == len(counters) == 16 and not any("stall" in req for req in seen)
    assert not any(r["kind"] == "stall" for r in records)
    for record in counters:
        assert [record[key] for key in COUNTERS] == [0.0, 0.0, 0.0]
    assert set(COUNTERS) <= set(COUNTER_GAUGES) and "init_state_s" not in COUNTER_GAUGES
    assert not any("init_state" in key for req in seen for key in req["counters"])
    families = worker.gauges.snapshot()
    for key in COUNTERS:
        (sample,) = families[COUNTER_GAUGES[key][0]]["samples"]
        assert sample["value"] == 0.0
    assert "edl_init_state_seconds" not in families
    assert worker._stalls._rule.limit is not None and len(worker._stalls._rule._gaps) == 15


def test_a_stall_in_the_prep_is_named_ingest_with_the_sleeping_hook_on_the_prep_thread(tmp_path, devices):
    _, seen, records = _run(tmp_path, devices, chaos="stall:rank=0,point=prep,step=16,ms=1500,count=1")
    (record,) = _stalls(records, "ingest")
    assert record["phase"] == "prep_wait" and record["phase_excess_s.prep_wait"] >= record["excess_s"] / 2
    # the tasks prepped ahead hide a part of the 1.5 s (more of it the slower the host runs a task)
    assert 0.1 < record["lost_s"] <= record["excess_s"] < 1.6 and record["prepping"] is True
    sample = record["samples"][0]
    assert sample["phase"] == "prep_wait" and sample["task"] is not None
    assert any(frame.endswith(":_dispatch_prepped") for frame in sample["stack"])
    hooks = [top for name, top in sample["threads"].items() if name.startswith("edl-prep")]
    assert any(top.startswith(os.path.join("chaos", "inject.py:")) and top.endswith(":_apply") for top in hooks)
    # the record rode ONE report, one to three after the one that ended the gap (which it names), and that
    # report's counters (and every later one's) count what the gaps in between did not catch up
    (req,) = [r for r in seen if r.get("stall", {}).get("cause") == "ingest"]
    assert 1 <= req["seq"] - record["seq"] <= stall.CATCH_UP_GAPS and record["worker_id"] == "worker-0"
    assert [r["task_id"] for r in seen if r["seq"] == record["seq"]] == [record["task"]]
    assert record["lost_s"] == pytest.approx(record["excess_s"] - record["recovered_s"])
    assert req["counters"]["stalls"] >= 1 and req["counters"]["stall_s"] >= record["lost_s"] - 1e-6
    last = [r for r in records if r["kind"] == "counter"][-1]
    assert last["stalls"] == sum(r["kind"] == "stall" for r in records)
    assert last["stall_s"] == pytest.approx(sum(r["lost_s"] for r in records if r["kind"] == "stall"), abs=1e-4)
    assert last["stall_unnamed_s"] == pytest.approx(sum(r["lost_s"] for r in _stalls(records, "unnamed")), abs=1e-4)


@pytest.mark.parametrize("point", ["step", "task"])
def test_a_stall_on_the_loop_between_its_phases_is_named_injected_with_the_hook_innermost(tmp_path, devices, point):
    _, _, records = _run(tmp_path, devices, chaos=f"stall:rank=0,point={point},step=16,ms=1500,count=1")
    (record,) = _stalls(records, "injected")
    assert record["phase"] == stall.LOOP and record["injected_s"] == pytest.approx(1.5)
    assert record["phase_excess_s.loop"] >= record["excess_s"] / 2
    stack = record["samples"][0]["stack"]
    assert stack[0].startswith(os.path.join("chaos", "inject.py:")) and stack[0].endswith(":_apply")
    caller = "_dispatch_training_task" if point == "step" else "_run"
    assert [frame.rsplit(":", 1)[1] for frame in stack[:4]] == ["_apply", "fire", "hook", caller]
    assert record["samples"][0]["phase"] == ""  # between the phases


def test_a_sleeping_master_call_is_named_master_with_its_method(tmp_path, devices):
    def slow_ninth(n):
        if n == 9:
            time.sleep(1.5)

    _, _, records = _run(tmp_path, devices, servicer_hook=slow_ninth)
    (record,) = _stalls(records, "master")
    assert record["rpc"] == "ReportTaskResult" and record["rpc_s"] >= 1.5 and record["phase"] == "metrics"
    sample = record["samples"][0]
    assert sample["rpc"] == "ReportTaskResult" and sample["phase"] == "metrics" and sample["rpc_open_s"] > 0.1
    assert sample["stack"][0].endswith(":slow_ninth") and any(f.endswith(":_report_result") for f in sample["stack"])


def test_a_stall_inside_the_profile_window_is_a_span_on_the_watchdogs_line_of_the_xplane(tmp_path, devices):
    from jax.profiler import ProfileData

    prof = str(tmp_path / "prof")
    worker, _, records = _run(
        tmp_path, devices, profile_dir=prof, profile_tasks=10,
        chaos="stall:rank=0,point=step,step=14,ms=1500,count=1",
    )
    (record,) = _stalls(records, "injected")
    assert record["profile"] == "open" and worker._profile_state == "closed"
    (path,) = glob.glob(os.path.join(prof, "**", "*.xplane.pb"), recursive=True)
    found = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                found += [(line.name, e.duration_ns / 1e9) for e in line.events if e.name == "stall"]
    assert [name for name, _ in found].count("edl-watchdog") >= 1 and {name for name, _ in found} == {"edl-watchdog"}
    # from detection (a median and the allowance after the last report) to the report: inside the excess
    assert max(seconds for _, seconds in found) == pytest.approx(record["excess_s"], abs=0.45)
