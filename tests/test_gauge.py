"""graftgauge (r14): registry semantics, Prometheus exposition, fleet
aggregation, heartbeat envelope compat and live endpoints.

The concurrency tests assert EXACT totals — the registry's counters back
the goodput computer, and an approximate examples-trained count would
make a live goodput ratio lie.  The bucket tests pin the live histogram
grid to ``tools/artifact.latency_stats``'s: one grid, so a scrape and a
stamped artifact bucket the same sample identically.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import pytest

from elasticdl_tpu.common import gauge
from elasticdl_tpu.common.metrics_http import MetricsHTTPServer


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_exact_under_threads(self):
        reg = gauge.Registry()
        c = reg.counter("edl_t_total", "t")
        n_threads, per = 8, 5000

        def work():
            for _ in range(per):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value() == n_threads * per

    def test_histogram_exact_under_threads(self):
        reg = gauge.Registry()
        h = reg.histogram("edl_t_ms", "t")
        n_threads, per = 6, 3000

        def work(i):
            for k in range(per):
                h.observe(float(i * per + k) % 97)

        threads = [
            threading.Thread(target=work, args=(i,))
            for i in range(n_threads)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        snap = h.snapshot()
        assert snap["count"] == n_threads * per
        assert sum(snap["counts"]) == n_threads * per

    def test_bucket_semantics_match_latency_stats(self):
        # The exact-edge cases are the ones that drift: bisect_left vs
        # searchsorted(side="left") must agree that a sample AT an edge
        # lands in the (prev, edge] bin.
        from tools.artifact import latency_stats

        samples = [0.05, 0.1, 0.11, 1.0, 2.0, 2.0001, 9999.0, 10000.0,
                   10000.1, 50000.0]
        h = gauge.Histogram()
        for s in samples:
            h.observe(s)
        stats = latency_stats(samples, buckets=True)
        assert h.snapshot()["counts"] == stats["hist"]["counts"]
        assert h.snapshot()["edges"] == stats["hist"]["edges_ms"]

    def test_shared_grid_is_the_artifact_grid(self):
        import tools.artifact as artifact

        assert artifact.DEFAULT_BUCKET_EDGES_MS is gauge.DEFAULT_BUCKET_EDGES_MS

    def test_type_conflict_raises(self):
        reg = gauge.Registry()
        reg.counter("edl_x_total")
        with pytest.raises(ValueError):
            reg.gauge("edl_x_total")

    def test_get_or_create_idempotent_and_labeled_series(self):
        reg = gauge.Registry()
        a = reg.counter("edl_x_total", labels={"w": "0"})
        b = reg.counter("edl_x_total", labels={"w": "0"})
        c = reg.counter("edl_x_total", labels={"w": "1"})
        assert a is b and a is not c

    def test_disabled_registry_is_noop_and_flippable(self):
        reg = gauge.Registry(enabled=False)
        c = reg.counter("edl_x_total")
        h = reg.histogram("edl_h_ms")
        c.inc()
        h.observe(1.0)
        assert c.value() == 0 and h.snapshot()["count"] == 0
        reg.configure(enabled=True)
        c.inc()
        h.observe(1.0)
        assert c.value() == 1 and h.snapshot()["count"] == 1

    def test_quantile_interpolates_and_bounds(self):
        h = gauge.Histogram()
        assert h.quantile(0.99) is None
        for _ in range(100):
            h.observe(1.5)  # (1.0, 2.0] bucket
        q = h.quantile(0.5)
        assert 1.0 <= q <= 2.0
        h2 = gauge.Histogram()
        h2.observe(10**6)  # overflow bucket: the last edge, a lower bound
        assert h2.quantile(0.99) == h2.edges[-1]

    def test_collector_runs_at_snapshot_and_errors_are_contained(self):
        reg = gauge.Registry()
        calls = []

        def ok():
            calls.append(1)
            reg.gauge("edl_depth").set(7.0)

        def broken():
            raise RuntimeError("boom")

        reg.add_collector(ok)
        reg.add_collector(broken)
        snap = reg.snapshot()
        assert calls and snap["edl_depth"]["samples"][0]["value"] == 7.0


# ---------------------------------------------------------------------------
# Prometheus text exposition (golden)
# ---------------------------------------------------------------------------

def test_render_prometheus_golden():
    reg = gauge.Registry()
    reg.counter("edl_examples_trained_total", "examples trained").inc(42)
    reg.gauge("edl_lease_depth", "buffered leases",
              labels={"worker": "w0"}).set(3)
    h = reg.histogram("edl_req_ms", "request wall", edges=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.render_prometheus()
    assert text == (
        "# HELP edl_examples_trained_total examples trained\n"
        "# TYPE edl_examples_trained_total counter\n"
        "edl_examples_trained_total 42\n"
        "# HELP edl_lease_depth buffered leases\n"
        "# TYPE edl_lease_depth gauge\n"
        'edl_lease_depth{worker="w0"} 3\n'
        "# HELP edl_req_ms request wall\n"
        "# TYPE edl_req_ms histogram\n"
        'edl_req_ms_bucket{le="1"} 1\n'
        'edl_req_ms_bucket{le="10"} 2\n'
        'edl_req_ms_bucket{le="+Inf"} 3\n'
        "edl_req_ms_sum 105.5\n"
        "edl_req_ms_count 3\n"
    )


def test_watch_job_parse_roundtrip():
    from tools.watch_job import parse_prometheus, render_table

    reg = gauge.Registry()
    reg.counter("edl_a_total").inc(5)
    reg.gauge("edl_b", labels={"worker": "w1"}).set(2.5)
    h = reg.histogram("edl_c_ms")
    for v in (1.5, 1.5, 300.0):
        h.observe(v)
    families = parse_prometheus(reg.render_prometheus())
    assert families["edl_a_total"]["samples"][0]["value"] == 5.0
    b = families["edl_b"]["samples"][0]
    assert b["labels"] == {"worker": "w1"} and b["value"] == 2.5
    assert families["edl_c_ms"]["type"] == "histogram"
    table = render_table(families)
    assert "edl_a_total" in table and "n=3" in table


def test_render_families_skips_malformed_remote_samples():
    # The merged fleet view renders REMOTE input: garbage shapes must be
    # skipped, never a scrape 500.
    text = gauge.render_families({
        "edl_ok": {"type": "gauge", "help": "",
                   "samples": [{"labels": {}, "value": 1.0}]},
        "edl_bad1": {"type": "gauge", "samples": [7, {"value": "x"}]},
        "edl_bad2": "not-a-dict",
        "edl_bad3": {"type": "histogram", "samples": [
            {"labels": {}, "value": {"edges": [1.0], "counts": [1]}},
        ]},  # counts != edges+1: skipped
    })
    assert "edl_ok 1" in text
    assert "edl_bad1" not in text.split("# TYPE")[0]
    assert "bucket" not in text


# ---------------------------------------------------------------------------
# fleet-view helpers
# ---------------------------------------------------------------------------

def test_merge_snapshots_labels_per_worker_and_keeps_histograms():
    r0, r1 = gauge.Registry(), gauge.Registry()
    r0.counter(gauge.EXAMPLES_TRAINED).inc(100)
    r1.counter(gauge.EXAMPLES_TRAINED).inc(50)
    r0.histogram("edl_phase_ms", labels={"phase": "dispatch"}).observe(3.0)
    merged = gauge.merge_snapshots(
        {"w0": r0.snapshot(), "w1": r1.snapshot()}
    )
    fam = merged[gauge.EXAMPLES_TRAINED]
    by_worker = {
        s["labels"]["worker"]: s["value"] for s in fam["samples"]
    }
    assert by_worker == {"w0": 100.0, "w1": 50.0}
    hist = merged["edl_phase_ms"]["samples"][0]
    assert hist["labels"] == {"phase": "dispatch", "worker": "w0"}
    text = gauge.render_families(merged)
    assert 'edl_examples_trained_total{worker="w0"} 100' in text


class TestRateWindow:
    def test_rate_over_window_and_restart_reanchor(self):
        clock = [0.0]
        rw = gauge.RateWindow(window_s=10.0, clock=lambda: clock[0])
        rw.update("w0", 0)
        clock[0] = 2.0
        rw.update("w0", 200)
        assert rw.rates() == {"w0": 100.0}
        # Counter went BACKWARDS (worker restarted): re-anchor, never a
        # negative rate.
        clock[0] = 3.0
        rw.update("w0", 10)
        assert rw.rates() == {}
        clock[0] = 4.0
        rw.update("w0", 110)
        assert rw.rates() == {"w0": 100.0}

    def test_stale_keys_drop_out(self):
        clock = [0.0]
        rw = gauge.RateWindow(window_s=5.0, clock=lambda: clock[0])
        rw.update("dead", 0)
        clock[0] = 1.0
        rw.update("dead", 100)
        clock[0] = 2.0
        rw.update("live", 0)
        clock[0] = 3.0
        rw.update("live", 10)
        clock[0] = 8.0  # "dead" silent past the window
        assert set(rw.rates()) == {"live"}
        assert rw.rate() == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# heartbeat envelope: additive compat + master aggregation
# ---------------------------------------------------------------------------

def _servicer(n_shards=4):
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    dispatcher = TaskDispatcher(
        [(i * 10, (i + 1) * 10) for i in range(n_shards)], num_epochs=1
    )
    return MasterServicer(
        dispatcher, rendezvous=RendezvousServer(heartbeat_timeout_s=30.0)
    )


def test_heartbeat_gauge_envelope_additive_compat_over_grpc():
    """Both directions of the r9/r12 additive stance over REAL gRPC: an
    old client's beat (no ``gauge`` field) passes the new server's
    schema, a new client's beat with the envelope passes too, and a
    malformed envelope degrades to ignored — never a failed heartbeat."""
    from elasticdl_tpu.common.rpc import JsonRpcClient
    from elasticdl_tpu.master.servicer import MasterServer

    servicer = _servicer()
    server = MasterServer(servicer, port=0).start()
    client = JsonRpcClient(server.address)
    try:
        client.wait_ready(10.0)
        # Old client -> new server: no envelope.
        assert "version" in client.call("Heartbeat", {"worker_id": "w0"})
        # New client -> server: real envelope banks into the fleet view.
        reg = gauge.Registry()
        reg.counter(gauge.EXAMPLES_TRAINED).inc(64)
        assert "version" in client.call(
            "Heartbeat",
            {"worker_id": "w0", "gauge": {"families": reg.snapshot()}},
        )
        assert gauge.EXAMPLES_TRAINED in servicer.fleet.fleet_snapshot()
        # Malformed envelopes: the typed schema rejects a non-dict in the
        # CALLER's frame, and a dict of garbage banks nothing — neither
        # crashes the beat.
        from elasticdl_tpu.common.rpc import SchemaError

        with pytest.raises(SchemaError):
            client.call("Heartbeat", {"worker_id": "w0", "gauge": 7})
        assert "version" in client.call(
            "Heartbeat", {"worker_id": "w0", "gauge": {"families": 9}}
        )
        # New SERVER fields are equally ignorable by old clients: the
        # response schema carries nothing gauge-shaped to strip, which is
        # the compat guarantee (nothing to misread).
    finally:
        client.close()
        server.stop()


def test_master_aggregation_two_worker_fleet():
    """Two in-process 'workers' ship envelopes on beats; the master's
    rendered view carries per-worker families, the fleet rate, goodput
    and the peak denominator."""
    servicer = _servicer()
    regs = {w: gauge.Registry() for w in ("w0", "w1")}
    counters = {
        w: r.counter(gauge.EXAMPLES_TRAINED) for w, r in regs.items()
    }
    for v0, v1 in ((100, 50), (300, 150), (500, 250)):
        counters["w0"].inc(v0)
        counters["w1"].inc(v1)
        for w, r in regs.items():
            servicer.Heartbeat(
                {"worker_id": w, "gauge": {"families": r.snapshot()}}
            )
        time.sleep(0.25)
    text = servicer.fleet.render()
    assert 'edl_examples_trained_total{worker="w0"} 900' in text
    assert 'edl_examples_trained_total{worker="w1"} 450' in text
    from tools.watch_job import parse_prometheus

    fams = parse_prometheus(text)

    def value(name):
        return fams[name]["samples"][0]["value"]

    fleet_rate = value("edl_fleet_examples_per_sec")
    assert fleet_rate > 0
    assert value("edl_fleet_examples_per_sec_peak") >= fleet_rate
    assert 0 < value("edl_goodput_under_churn") <= 1.0
    # The beats themselves registered the two workers (the rendezvous
    # revival path), so the world-size gauge reads the live membership.
    assert value("edl_world_size") == 2
    health = servicer.fleet.health()
    assert health["workers_reporting"] == ["w0", "w1"]


def test_remove_collector_unhooks_and_tolerates_absent():
    reg = gauge.Registry()
    calls = []

    def fn():
        calls.append(1)

    reg.add_collector(fn)
    reg.snapshot()
    assert len(calls) == 1
    reg.remove_collector(fn)
    reg.snapshot()
    assert len(calls) == 1
    reg.remove_collector(fn)  # already gone: no-op


def test_master_render_has_one_type_block_per_family():
    """A family living on BOTH sides of the master page (its own
    registry and the worker envelopes — edl_membership_version does)
    must render under ONE HELP/TYPE block: a spec-strict Prometheus
    parser rejects the whole scrape on a duplicate TYPE line."""
    servicer = _servicer()
    reg = gauge.Registry()
    reg.gauge("edl_membership_version", "applied membership version").set(3)
    reg.counter(gauge.EXAMPLES_TRAINED).inc(10)
    servicer.Heartbeat(
        {"worker_id": "w0", "gauge": {"families": reg.snapshot()}}
    )
    text = servicer.fleet.render()
    assert text.count("# TYPE edl_membership_version ") == 1
    # Both sides' samples survive the fold: the master's unlabeled
    # series and the worker-labeled one.
    assert "\nedl_membership_version " in text
    assert 'edl_membership_version{worker="w0"} 3' in text


def test_departed_worker_envelopes_are_bounded():
    """Dead incarnations' envelopes are pruned past DEPARTED_KEEP
    (most-recently-updated kept — the r12 departed-trace-ring stance);
    live members are never pruned."""
    from elasticdl_tpu.master.fleet_metrics import FleetMetrics

    servicer = _servicer()
    servicer.rendezvous.register("w-live")
    reg = gauge.Registry()
    reg.counter(gauge.EXAMPLES_TRAINED).inc(1)
    snap = reg.snapshot()
    servicer.fleet.record_envelope("w-live", {"families": snap})
    n_dead = FleetMetrics.DEPARTED_KEEP + 5
    for i in range(n_dead):
        servicer.fleet.record_envelope(f"w-dead-{i}", {"families": snap})
    merged = servicer.fleet.fleet_snapshot()
    workers = {
        s["labels"]["worker"]
        for s in merged[gauge.EXAMPLES_TRAINED]["samples"]
    }
    assert "w-live" in workers
    departed = workers - {"w-live"}
    assert len(departed) == FleetMetrics.DEPARTED_KEEP
    # Most-recently-updated survive: the oldest five were pruned.
    assert departed == {
        f"w-dead-{i}" for i in range(5, n_dead)
    }


def test_clear_family_drops_series_but_keeps_registration():
    reg = gauge.Registry()
    reg.gauge("edl_w", labels={"worker": "w0"}).set(5)
    reg.clear_family("edl_w")
    assert reg.snapshot(collect=False)["edl_w"]["samples"] == []
    reg.clear_family("edl_absent")  # unknown family: no-op
    # Re-registering after a clear still enforces the type.
    with pytest.raises(ValueError):
        reg.counter("edl_w")


def test_stale_per_entity_series_disappear_from_the_fleet_view():
    """A dissolved gang's lag series (and by the same mechanism a dead
    worker's rate series) must vanish from /metrics, not serve their
    last value forever."""
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    clock = [100.0]
    dispatcher = TaskDispatcher([(0, 10), (10, 20)], num_epochs=1)
    servicer = MasterServicer(dispatcher, clock=lambda: clock[0])
    with servicer._group_lock:
        servicer._group_version = 1
        servicer._gang_arrivals = {"w0": (5, 99.0), "w1": (4, 90.0)}
        servicer._gang_head = (5, 99.0)
    clock[0] = 101.0
    snap = servicer.fleet.registry.snapshot()
    assert len(snap["edl_gang_arrival_lag_seconds"]["samples"]) == 2
    # The gang dissolves (job end / reform): the lag series must go too.
    with servicer._group_lock:
        servicer._group_version = None
        servicer._gang_arrivals = {}
        servicer._gang_head = (0, None)
    snap = servicer.fleet.registry.snapshot()
    assert snap["edl_gang_arrival_lag_seconds"]["samples"] == []


def test_gang_lag_snapshot_names_the_laggard():
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    clock = [100.0]
    dispatcher = TaskDispatcher([(0, 10), (10, 20)], num_epochs=1)
    servicer = MasterServicer(dispatcher, clock=lambda: clock[0])
    servicer._group_version = 1
    with servicer._group_lock:
        servicer._gang_arrivals = {"w0": (5, 99.0), "w1": (4, 90.0)}
        servicer._gang_head = (5, 99.0)
    clock[0] = 102.0
    lag = servicer.gang_lag_snapshot()
    assert lag["w0"] == 0.0  # at the head
    # w1 trails: seconds since the HEAD arrived (now - head_t) — the
    # deadline's own clock, not now - w1's previous arrival (which would
    # read 12 s of "lag" on a healthy gang).
    assert lag["w1"] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# JSONL coexistence: one naming table, torn-line tolerance
# ---------------------------------------------------------------------------

def test_jsonl_mirror_uses_the_one_naming_table():
    servicer = _servicer()
    reg = gauge.Registry()
    reg.counter(gauge.EXAMPLES_TRAINED).inc(10)
    reg.counter(gauge.STEPS_DISPATCHED).inc(2)
    reg.counter(gauge.TASKS_DONE).inc(1)
    reg.gauge(gauge.LEASE_DEPTH).set(3)
    reg.gauge(gauge.PREP_QUEUE_DEPTH).set(1)
    reg.gauge("edl_rank").set(0)  # NOT in the table: must not leak
    reg.histogram("edl_phase_ms").observe(1.0)  # histograms never mirror
    mirror = servicer.fleet.jsonl_mirror(
        "w0", {"families": reg.snapshot()}
    )
    assert set(mirror) == set(gauge.JSONL_GAUGE_FAMILIES)
    assert mirror[gauge.EXAMPLES_TRAINED] == 10.0


def test_gauge_records_stream_to_jsonl_and_tolerate_torn_tail(tmp_path):
    from elasticdl_tpu.common.metrics import MetricsWriter, read_metrics
    from elasticdl_tpu.data.reader import Shard
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    writer = MetricsWriter(str(tmp_path), tensorboard=False)
    dispatcher = TaskDispatcher([Shard("f", 0, 10)], num_epochs=1)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    reg = gauge.Registry()
    reg.counter(gauge.EXAMPLES_TRAINED).inc(128)
    task = dispatcher.get_task("w0")
    servicer.ReportTaskResult({
        "worker_id": "w0",
        "task_id": task.task_id,
        "success": True,
        "gauge": {"families": reg.snapshot()},
    })
    writer.close()
    records = read_metrics(str(tmp_path))
    gauges = [r for r in records if r["kind"] == "gauge"]
    assert gauges and gauges[0][gauge.EXAMPLES_TRAINED] == 128.0
    assert set(gauges[0]) - {"ts", "kind", "step"} <= set(
        gauge.JSONL_GAUGE_FAMILIES
    )
    # Torn FINAL line (crash mid-append of a gauge record): dropped, the
    # earlier records still read.
    path = os.path.join(str(tmp_path), "metrics.jsonl")
    with open(path, "a") as f:
        f.write('{"ts": 1, "kind": "gauge", "edl_examples_tra')
    assert read_metrics(str(tmp_path)) == records


# ---------------------------------------------------------------------------
# scrape endpoints
# ---------------------------------------------------------------------------

def _get(address, path="/metrics", timeout=5.0):
    with urllib.request.urlopen(
        f"http://{address}{path}", timeout=timeout
    ) as r:
        return r.status, r.read().decode()


def test_metrics_http_serves_metrics_and_healthz():
    reg = gauge.Registry()
    reg.counter("edl_x_total").inc(9)
    srv = MetricsHTTPServer(
        reg.render_prometheus, health_fn=lambda: {"role": "test"}, port=0
    ).start()
    try:
        status, body = _get(srv.address)
        assert status == 200 and "edl_x_total 9" in body
        status, body = _get(srv.address, "/healthz")
        assert status == 200
        assert json.loads(body) == {"role": "test", "status": "ok"}
        with pytest.raises(urllib.error.HTTPError):
            _get(srv.address, "/nope")
    finally:
        srv.stop()


def test_maybe_start_disabled_and_bind_failure():
    from elasticdl_tpu.common.metrics_http import maybe_start

    assert maybe_start(-1, lambda: "") is None
    srv = maybe_start(0, lambda: "edl_y 1\n")
    try:
        assert srv is not None
        # A second server on the SAME fixed port fails the bind: logs and
        # returns None instead of taking the process down.
        assert maybe_start(srv.port, lambda: "") is None
    finally:
        srv.stop()


def test_endpoint_answers_while_task_loop_is_stalled(tmp_path, devices):
    """The chaos stance: a worker wedged in an injected stall must still
    answer /metrics — the scrape server runs its own daemon threads,
    never the task loop.  Scrapes are issued CONCURRENT with the stalled
    run and must all succeed."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    train = str(tmp_path / "train.rio")
    generate("mnist", train, 96)
    config = JobConfig(
        model_def="mnist.model_spec",
        training_data=train,
        minibatch_size=16,
        num_minibatches_per_task=2,
        chaos="stall:point=task,ms=400,count=2",
    )
    reader = create_data_reader(train)
    from elasticdl_tpu.master.servicer import MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    servicer = MasterServicer(TaskDispatcher(reader.create_shards(32)))
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    worker = Worker(
        config, DirectMasterProxy(servicer), reader,
        worker_id="w0", spec=spec, devices=devices,
    )
    srv = MetricsHTTPServer(worker.gauges.render_prometheus, port=0).start()
    scrapes = {"ok": 0, "fail": 0}
    stop = threading.Event()

    def scrape_loop():
        while not stop.is_set():
            try:
                status, body = _get(srv.address, timeout=2.0)
                if status == 200 and "edl_" in body:
                    scrapes["ok"] += 1
                else:
                    scrapes["fail"] += 1
            except Exception:
                scrapes["fail"] += 1
            stop.wait(0.05)

    scraper = threading.Thread(target=scrape_loop, daemon=True)
    scraper.start()
    try:
        result = worker.run()
    finally:
        stop.set()
        scraper.join(5.0)
        srv.stop()
    assert result["tasks_done"] == 3
    # The two 400 ms stalls alone guarantee many scrape windows; every
    # one must have answered.
    assert scrapes["ok"] >= 5 and scrapes["fail"] == 0
    assert worker.gauges.scalar_values(
        [gauge.EXAMPLES_TRAINED]
    )[gauge.EXAMPLES_TRAINED] == 96.0


def test_worker_families_match_the_naming_table_after_a_job(tmp_path, devices):
    """The registry families a real worker publishes cover the whole
    JSONL naming table (the coexistence assert the one-table stance
    hangs on), and the envelope payload carries them."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

    train = str(tmp_path / "train.rio")
    generate("mnist", train, 64)
    config = JobConfig(
        model_def="mnist.model_spec",
        training_data=train,
        minibatch_size=16,
        num_minibatches_per_task=2,
    )
    reader = create_data_reader(train)
    from elasticdl_tpu.master.servicer import MasterServicer

    servicer = MasterServicer(TaskDispatcher(reader.create_shards(32)))
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    worker = Worker(
        config, DirectMasterProxy(servicer), reader,
        worker_id="w0", spec=spec, devices=devices,
    )
    result = worker.run()
    assert result["tasks_done"] == 2
    # force=True: the loop's own final beat may have shipped within the
    # throttle window (the report path uses the same bypass).
    payload = worker.gauge_payload(force=True)
    assert set(gauge.JSONL_GAUGE_FAMILIES) <= set(payload["families"])
    mirror = servicer.fleet.jsonl_mirror("w0", payload)
    assert set(mirror) == set(gauge.JSONL_GAUGE_FAMILIES)
    assert mirror[gauge.EXAMPLES_TRAINED] == 64.0
    assert mirror[gauge.TASKS_DONE] == 2.0
    # The per-phase families rode along (PhaseTimers -> collector).
    fams = payload["families"]
    assert "edl_phase_seconds_total" in fams
    assert fams["edl_phase_ms"]["type"] == "histogram"


# ---------------------------------------------------------------------------
# locksan contention bridge (r16): edl_lock_acquire_total / edl_lock_wait_ms
# ---------------------------------------------------------------------------

class TestLockContentionGauges:
    def test_collector_publishes_lock_families(self):
        from elasticdl_tpu.common import locksan

        locksan.reset()
        reg = gauge.Registry()
        collector = gauge.install_lock_collector(reg)
        try:
            lk = locksan.lock("Bridge._lock")
            for _ in range(3):
                with lk:
                    pass
            snap = reg.snapshot()  # collectors run at scrape time
            acq = snap["edl_lock_acquire_total"]["samples"]
            (sample,) = [
                s for s in acq if s["labels"].get("lock") == "Bridge._lock"
            ]
            assert sample["value"] == 3.0
            hist = snap["edl_lock_wait_ms"]
            assert hist["type"] == "histogram"
            (hs,) = [
                s for s in hist["samples"]
                if s["labels"].get("lock") == "Bridge._lock"
            ]
            assert hs["value"]["count"] == 3
            # The shared grid: live scrape buckets match artifact buckets.
            assert tuple(hs["value"]["edges"]) == gauge.DEFAULT_BUCKET_EDGES_MS
            # Re-scrape overwrites with the newer cumulative totals.
            with lk:
                pass
            snap = reg.snapshot()
            (sample,) = [
                s for s in snap["edl_lock_acquire_total"]["samples"]
                if s["labels"].get("lock") == "Bridge._lock"
            ]
            assert sample["value"] == 4.0
        finally:
            reg.remove_collector(collector)
            locksan.reset()

    def test_render_and_watch_job_summary(self):
        from elasticdl_tpu.common import locksan
        from tools.watch_job import parse_prometheus, render_locks

        locksan.reset()
        reg = gauge.Registry()
        collector = gauge.install_lock_collector(reg)
        try:
            with locksan.lock("Watch._lock"):
                pass
            families = parse_prometheus(reg.render_prometheus())
            line = render_locks(families)
            assert line is not None and line.startswith("locks:")
            # Total spans every sanitized lock (the registry's own leaf
            # locks record too once stats are on) — assert presence, not
            # an exact count.
            assert "acquires=" in line
            assert "Watch._lock" in line
        finally:
            reg.remove_collector(collector)
            locksan.reset()

    def test_histogram_load_snapshot_rejects_mismatched_grid(self):
        h = gauge.Histogram()
        with pytest.raises(ValueError):
            h.load_snapshot({"edges": [1.0], "counts": [0, 0], "sum": 0.0,
                             "count": 0})
