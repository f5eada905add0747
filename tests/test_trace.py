"""grafttrace (common/trace.py): ring semantics, nesting, propagation,
shipping, and the merge/analysis tools.

Covers the r12 acceptance points: trace-context propagation across a REAL
gRPC round trip, ring-buffer overwrite-oldest under concurrent writers,
nested-span self-time agreeing with PhaseTimers on the same block, and
trace_dump merging two worker processes with skewed clocks.
"""

import json
import threading
import time

import pytest

from elasticdl_tpu.common import trace
from elasticdl_tpu.common.metrics import PhaseTimers
from elasticdl_tpu.common.trace import TraceRecorder


@pytest.fixture()
def recorder():
    """Enable the PROCESS recorder for a test, restoring state after (the
    module helpers and PhaseTimers read the global)."""
    was = trace.enabled()
    rec = trace.configure(enabled=True, capacity=4096)
    rec.clear()
    yield rec
    rec.clear()
    trace.configure(enabled=was)


# ---------------------------------------------------------------- recorder


def test_disabled_recorder_is_noop():
    rec = TraceRecorder(enabled=False)
    with rec.span("x", cat="t"):
        pass
    rec.instant("y")
    assert rec.export() == []


def test_span_and_instant_shapes():
    rec = TraceRecorder(enabled=True, capacity=16)
    with rec.span("work", cat="phase", k=1):
        rec.instant("tick", cat="event", n=2)
    inst, span = rec.export()
    assert inst["ph"] == "i" and inst["name"] == "tick"
    assert inst["args"]["n"] == 2
    assert span["ph"] == "X" and span["name"] == "work"
    assert span["cat"] == "phase"
    assert span["dur"] >= 0
    assert span["args"]["k"] == 1
    assert span["args"]["span_id"] > 0
    # Timestamps are wall-anchored microseconds: the instant fired inside
    # the span's window.
    assert span["ts"] <= inst["ts"] <= span["ts"] + span["dur"] + 1


def test_span_parent_nesting():
    rec = TraceRecorder(enabled=True, capacity=16)
    with rec.span("outer") as outer:
        assert rec.current_span_id() == outer.span_id
        with rec.span("inner") as inner:
            assert inner.parent_id == outer.span_id
    inner_ev, outer_ev = rec.export()
    assert inner_ev["args"]["parent"] == outer_ev["args"]["span_id"]


def test_ring_overwrites_oldest_single_thread():
    rec = TraceRecorder(enabled=True, capacity=8)
    for i in range(20):
        rec.instant("e", i=i)
    kept = [e["args"]["i"] for e in rec.export()]
    assert kept == list(range(12, 20))  # the NEWEST window, in order
    assert rec.dropped > 0


def test_ring_overwrite_oldest_under_concurrent_writers():
    """N writers x M events into a capacity-C ring: the ring holds exactly
    C events, and each writer's surviving events are a SUFFIX of its own
    append sequence (overwrite-oldest means no writer's newer event is
    dropped while its older one survives)."""
    cap, writers, per = 256, 8, 400
    rec = TraceRecorder(enabled=True, capacity=cap)

    def _write(w):
        for i in range(per):
            rec.instant("e", w=w, i=i)

    threads = [
        threading.Thread(target=_write, args=(w,)) for w in range(writers)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    events = rec.export()
    assert len(events) == cap
    by_writer = {}
    for e in events:
        by_writer.setdefault(e["args"]["w"], []).append(e["args"]["i"])
    for w, seq in by_writer.items():
        # In-order (deque append preserves per-thread order)...
        assert seq == sorted(seq), f"writer {w} out of order"
        # ...and a suffix: everything from its first survivor onward.
        assert seq == list(range(seq[0], per)), f"writer {w} not a suffix"


def test_drain_slice_bounded_and_fifo():
    rec = TraceRecorder(enabled=True, capacity=64)
    for i in range(10):
        rec.instant("e", i=i)
    first = rec.drain_slice(4)
    assert [e["args"]["i"] for e in first] == [0, 1, 2, 3]
    rest = rec.drain_slice(100)
    assert [e["args"]["i"] for e in rest] == [4, 5, 6, 7, 8, 9]
    assert rec.drain_slice(5) == []


# ------------------------------------------- PhaseTimers span integration


def test_phase_timers_emit_spans(recorder):
    timers = PhaseTimers()
    with timers.phase("prep_wait"):
        time.sleep(0.01)
    (ev,) = [e for e in recorder.export() if e["ph"] == "X"]
    assert ev["name"] == "prep_wait"
    assert ev["cat"] == "phase"
    assert ev["dur"] >= 9e3  # microseconds


def test_nested_span_self_time_agrees_with_phase_timers(recorder):
    """The trace side computes per-span SELF time with its own per-thread
    stack; PhaseTimers computes per-phase self time with ITS stack.  On a
    nested block the two independent implementations must agree."""
    timers = PhaseTimers()
    with timers.phase("control"):
        time.sleep(0.02)
        with timers.phase("lease_wait"):
            time.sleep(0.03)
        time.sleep(0.01)
    snap = timers.snapshot()
    self_us = {}
    for e in recorder.export():
        if e["ph"] == "X" and e["cat"] == "phase":
            self_us[e["name"]] = (
                self_us.get(e["name"], 0.0) + e["args"]["self_us"]
            )
    assert set(self_us) == {"control", "lease_wait"}
    for name in self_us:
        # Tolerance: the two stacks bracket each other's bookkeeping by a
        # few calls of overhead per nesting level.
        assert self_us[name] / 1e6 == pytest.approx(snap[name], abs=5e-3)
    # And the decomposition really is a partition: control's self time
    # excludes the nested lease_wait.
    assert self_us["control"] / 1e6 < 0.045


# ------------------------------------------------------------- the bridge


class _FakeAnnotation:
    """What a bridge factory builds: a context manager that remembers."""

    log = []

    def __init__(self, name, attrs):
        self.name, self.attrs = name, dict(attrs)

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.attrs))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name))
        return False


@pytest.fixture()
def bridge():
    _FakeAnnotation.log = []
    trace.set_bridge(_FakeAnnotation)
    yield _FakeAnnotation.log
    trace.set_bridge(None)


def test_span_without_ring_or_bridge_is_the_shared_noop():
    assert not trace.enabled()
    a, b = trace.span("x", task=1), trace.span("y")
    assert a is b and a.span_id == 0
    with a:
        pass


def test_bridge_gets_name_and_attrs_with_the_ring_off(bridge):
    assert not trace.enabled()
    timers = PhaseTimers()
    with timers.phase("dispatch", task=7, seq=3, step0=16):
        with trace.span("rpc:Foo", cat="rpc.client", method="Foo") as sp:
            # no ring, no id: an RPC client propagates no parent for it
            assert sp.span_id == 0
    assert bridge == [
        ("enter", "dispatch", {"task": 7, "seq": 3, "step0": 16}),
        ("enter", "rpc:Foo", {"method": "Foo"}),
        ("exit", "rpc:Foo"),
        ("exit", "dispatch"),
    ]
    assert trace.default().export() == []
    assert timers.counts() == {"dispatch": 1}
    # cleared: the shared no-op again, and nothing more reaches the bridge
    trace.set_bridge(None)
    assert trace.span("after") is trace.span("again")
    assert len(bridge) == 4


@pytest.mark.parametrize("bridged", [False, True])
def test_ring_self_time_arithmetic_is_the_same_with_a_bridge(recorder, bridged):
    """The ring's per-span self time (checked against PhaseTimers' own
    stack) does not change when every span is also entered through a
    bridge, and the bridge sees every span the ring records."""
    log = []
    if bridged:
        _FakeAnnotation.log = log
        trace.set_bridge(_FakeAnnotation)
    try:
        timers = PhaseTimers()
        with timers.phase("control"):
            time.sleep(0.02)
            with timers.phase("lease_wait", task=5):
                time.sleep(0.03)
    finally:
        trace.set_bridge(None)
    snap = timers.snapshot()
    spans = {e["name"]: e for e in recorder.export() if e["ph"] == "X"}
    assert set(spans) == {"control", "lease_wait"}
    for name, ev in spans.items():
        assert ev["args"]["self_us"] / 1e6 == pytest.approx(snap[name], abs=5e-3)
    assert spans["lease_wait"]["args"]["task"] == 5
    assert spans["lease_wait"]["args"]["parent"] == spans["control"]["args"]["span_id"]
    assert [x[:2] for x in log] == (
        [("enter", "control"), ("enter", "lease_wait"),
         ("exit", "lease_wait"), ("exit", "control")] if bridged else []
    )


def test_pool_threads_give_their_python_name_to_the_os():
    from concurrent.futures import ThreadPoolExecutor

    def comm():
        with open(f"/proc/self/task/{threading.get_native_id()}/comm") as f:
            return f.read().strip()

    with ThreadPoolExecutor(
        1, thread_name_prefix="edl-prep", initializer=trace.name_os_thread
    ) as pool:
        assert pool.submit(comm).result() == "edl-prep_0"


# ------------------------------------------------- gRPC round-trip context


def test_trace_context_propagates_over_real_grpc(recorder):
    """Client span id rides the request envelope; the servicer's rpc.server
    span names it as remote_parent — one logical RPC, linked across the
    wire."""
    from elasticdl_tpu.common.rpc import JsonRpcClient
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    servicer = MasterServicer(TaskDispatcher([]))
    server = MasterServer(servicer, port=0).start()
    client = JsonRpcClient(server.address)
    try:
        client.wait_ready(10.0)
        client.call("RegisterWorker", {"worker_id": "w0"})
        recorder.clear()
        resp = client.call("Heartbeat", {"worker_id": "w0"})
        assert resp.get("server_ts_us") is not None
        events = recorder.export()
        client_spans = [
            e for e in events
            if e["ph"] == "X" and e["cat"] == "rpc.client"
            and e["name"] == "rpc:Heartbeat"
        ]
        server_spans = [
            e for e in events
            if e["ph"] == "X" and e["cat"] == "rpc.server"
            and e["name"] == "rpc:Heartbeat"
        ]
        assert len(client_spans) == 1 and len(server_spans) == 1
        assert (
            server_spans[0]["args"]["remote_parent"]
            == client_spans[0]["args"]["span_id"]
        )
        assert client_spans[0]["args"]["deadline_s"] == 30.0
        # The server span nests INSIDE the client span's window (same
        # process here, so no clock alignment needed).
        cs, ss = client_spans[0], server_spans[0]
        assert cs["ts"] <= ss["ts"]
        assert ss["ts"] + ss["dur"] <= cs["ts"] + cs["dur"] + 1
    finally:
        client.close()
        server.stop()


def test_heartbeat_slice_shipping_and_dump(recorder):
    """Worker-shipped slices land in the master's per-worker buffer and
    come back out of DumpTrace; shipping DRAINS the worker ring."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    servicer = MasterServicer(TaskDispatcher([]))
    servicer.RegisterWorker({"worker_id": "w0"})
    recorder.clear()
    recorder.instant("e", i=1)
    recorder.instant("e", i=2)
    events = recorder.drain_slice(512)
    assert recorder.export() == []  # drained
    servicer.Heartbeat({
        "worker_id": "w0",
        "trace": {"events": events, "clock_offset_us": 123.0, "dropped": 0},
    })
    dump = servicer.DumpTrace({})
    proc = dump["processes"]["w0"]
    assert [e["args"]["i"] for e in proc["events"]] == [1, 2]
    assert proc["clock_offset_us"] == 123.0
    # Non-draining: a second dump sees the same window.
    assert len(servicer.DumpTrace({})["processes"]["w0"]["events"]) == 2


def test_departed_worker_trace_buffers_are_bounded(recorder):
    """Master-side rings of DEPARTED workers are retained (the job-end tail
    is dumped after workers exit) but capped at TRACE_DEPARTED_KEEP, most
    recently updated win — memory must track current world size, not
    historical membership."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    servicer = MasterServicer(TaskDispatcher([]))
    keep = MasterServicer.TRACE_DEPARTED_KEEP
    n = keep + 5
    for i in range(n):
        servicer.RegisterWorker({"worker_id": f"w{i}"})
        servicer.Heartbeat({
            "worker_id": f"w{i}",
            "trace": {"events": [{"ph": "i", "name": "e", "ts": float(i)}]},
        })
    # Everyone but w0 departs (w0 beat first = least recently updated of
    # the departed set).
    servicer._on_membership_change(2, ["w0"])
    with servicer._lock:
        held = set(servicer._trace_buffers)
    assert "w0" in held  # current member always kept
    assert len(held) <= keep + 1
    # The survivors among the departed are the most recently updated ones.
    assert f"w{n-1}" in held and "w1" not in held


def test_merge_skips_events_with_malformed_ts():
    from tools.trace_dump import merge

    dump = {
        "master_events": [
            {"ph": "i", "name": "ok", "ts": 5.0, "tid": 1},
            {"ph": "i", "name": "bad", "ts": None, "tid": 1},
            {"ph": "i", "name": "bad2", "ts": "later", "tid": 1},
            {"ph": "i", "name": "bad3", "ts": True, "tid": 1},
        ],
        "processes": {},
    }
    merged = merge(dump)
    names = [e["name"] for e in merged["traceEvents"] if e.get("ph") == "i"]
    assert names == ["ok"]


# ------------------------------------------------------- merge / analysis


def _mk_span(name, cat, ts, dur, tid=1, **args):
    return {
        "ph": "X", "name": name, "cat": cat, "ts": ts, "dur": dur,
        "tid": tid, "args": args,
    }


def test_trace_dump_merges_skewed_clocks(tmp_path):
    """Two worker processes with skewed clocks merge onto the master
    timeline: the same physical moment (each worker's gang boundary) lands
    at the same merged timestamp once each worker's RTT-midpoint offset is
    applied."""
    from tools.trace_dump import merge

    # Physical truth: both workers cross the gang boundary at master time
    # 1_000_000 us.  w0's clock runs 5 s behind the master, w1's 2 s ahead
    # -> their LOCAL timestamps differ by 7 s for the same moment.
    dump = {
        "master_events": [_mk_span("rpc:GetGroupTask", "rpc.server",
                                   1_000_000.0, 500.0)],
        "processes": {
            "w0": {
                "events": [_mk_span("gang_boundary", "gang",
                                    1_000_000.0 - 5_000_000.0, 400.0)],
                "clock_offset_us": 5_000_000.0,
                "dropped": 0,
            },
            "w1": {
                "events": [_mk_span("gang_boundary", "gang",
                                    1_000_000.0 + 2_000_000.0, 400.0)],
                "clock_offset_us": -2_000_000.0,
                "dropped": 0,
            },
        },
    }
    merged = merge(dump)
    spans = [
        e for e in merged["traceEvents"]
        if e.get("ph") == "X" and e["name"] == "gang_boundary"
    ]
    assert len(spans) == 2
    assert spans[0]["ts"] == pytest.approx(1_000_000.0)
    assert spans[1]["ts"] == pytest.approx(1_000_000.0)
    # Distinct integer pids with process_name metadata (Perfetto/Chrome
    # both load this shape).
    names = {
        e["pid"]: e["args"]["name"]
        for e in merged["traceEvents"]
        if e.get("ph") == "M" and e.get("name") == "process_name"
    }
    assert set(names.values()) == {"master", "w0", "w1"}
    assert all(isinstance(p, int) for p in names)
    json.dumps(merged)  # the file must serialize as-is


def test_straggler_report_skew_and_phase_stats():
    """Per-rank gang wait totals, skew, straggler identification, and
    per-phase p50/p99 (+ shared histogram) from a merged trace."""
    from tools.straggler_report import analyze

    events = [
        {"ph": "M", "pid": 1, "tid": 0, "ts": 0, "name": "process_name",
         "args": {"name": "w0"}},
        {"ph": "M", "pid": 2, "tid": 0, "ts": 0, "name": "process_name",
         "args": {"name": "w1"}},
    ]
    # w0 is the straggler: long prep, short waits.  w1 waits on it: short
    # prep, long step_wait + gang_boundary.
    for i in range(4):
        t = i * 100_000.0
        events += [
            dict(_mk_span("prep_wait", "phase", t, 80_000.0), pid=1),
            dict(_mk_span("gang_boundary", "gang", t + 80_000, 1_000.0), pid=1),
            dict(_mk_span("step_wait", "phase", t + 81_000, 4_000.0), pid=1),
            dict(_mk_span("prep_wait", "phase", t, 10_000.0), pid=2),
            dict(_mk_span("gang_boundary", "gang", t + 10_000, 5_000.0), pid=2),
            dict(_mk_span("step_wait", "phase", t + 15_000, 70_000.0), pid=2),
        ]
    report = analyze({"traceEvents": events})
    skew = report["gang_boundary_skew"]
    assert skew["straggler"] == "w0"
    assert skew["per_rank"]["w0"]["total_ms"] == pytest.approx(20.0)
    assert skew["per_rank"]["w1"]["total_ms"] == pytest.approx(300.0)
    assert skew["skew_ms"] == pytest.approx(280.0)
    w0 = report["processes"]["w0"]["phases"]
    assert w0["prep_wait"]["count"] == 4
    assert w0["prep_wait"]["p50_ms"] == pytest.approx(80.0)
    assert w0["prep_wait"]["p99_ms"] == pytest.approx(80.0)
    # The shared histogram grid rode along (tail shape, not just points).
    hist = w0["prep_wait"]["hist"]
    assert sum(hist["counts"]) == 4
    assert len(hist["counts"]) == len(hist["edges_ms"]) + 1


def test_latency_stats_histogram_buckets():
    from tools.artifact import DEFAULT_BUCKET_EDGES_MS, latency_stats

    out = latency_stats([0.05, 0.3, 3.0, 3.0, 40.0, 99999.0], buckets=True)
    hist = out["hist"]
    assert hist["edges_ms"] == list(DEFAULT_BUCKET_EDGES_MS)
    counts = hist["counts"]
    assert sum(counts) == 6
    assert counts[0] == 1          # 0.05 under the first edge
    assert counts[-1] == 1         # 99999 overflow
    edges = hist["edges_ms"]
    assert counts[edges.index(0.5)] == 1      # 0.3 in (0.2, 0.5]
    assert counts[edges.index(5.0)] == 2      # both 3.0s in (2, 5]
    assert counts[edges.index(50.0)] == 1     # 40 in (20, 50]
    assert latency_stats([], buckets=True) == {}
    # Explicit edges pass through.
    out = latency_stats([1.5], buckets=(1.0, 2.0))
    assert out["hist"]["edges_ms"] == [1.0, 2.0]
    assert out["hist"]["counts"] == [0, 1, 0]
