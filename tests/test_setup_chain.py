"""The job's set-up as spans (PR 35): ``common/trace.py``'s ``SetupChain``,
the master's and the worker's marks, the ``setup`` records they leave in
``metrics.jsonl``, the gauge family and the ring's ``cat="setup"`` spans,
and the ``jax.monitoring`` seconds behind the first dispatch's parts."""

from __future__ import annotations

import os

import pytest

from elasticdl_tpu.client.main import main as cli_main
from elasticdl_tpu.common import platform, trace
from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.metrics import MetricsWriter, read_metrics
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.pod_manager import FakePodBackend, PodManager
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker

MASTER_CHAIN = ["setup:launch", "setup:shards", "setup:serve", "setup:spawn"]
WORKER_CHAIN = [
    "setup:interp", "setup:imports", "setup:register", "setup:device_open", "setup:build",
    "init_state", "setup:first_prep", "setup:first_dispatch", "setup:first_step",
]


def _spans(record: dict) -> dict:
    return {k[:-3]: (record[k], record[k[:-3] + "_t1"]) for k in record if k.endswith("_t0")}


# ------------------------------------------------------------- the chain


def test_marks_partition_the_time_from_the_origin():
    chain = trace.SetupChain(origin_s=100.0)
    assert chain.mark("a", at_s=101.0) == 101.0
    with chain.child("inside"):
        pass
    chain.mark("b", at_s=103.5)
    assert chain.mark("c", at_s=102.0) == 103.5  # never before the previous stamp
    assert chain.spans == [("a", 100.0, 101.0), ("b", 101.0, 103.5), ("c", 103.5, 103.5)]
    assert chain.has("b") and not chain.has("inside")
    flat = chain.flat()
    assert flat["a_t0"] == 100.0 and flat["b_t1"] == 103.5 and flat["inside_t0"] <= flat["inside_t1"]
    assert chain.durations()["b"] == 2.5
    assert sum(t1 - t0 for _, t0, t1 in chain.spans) == chain.last_s - chain.origin_s
    assert all(isinstance(v, float) for v in flat.values()) and flat["pid"] == os.getpid()


def test_a_chain_without_an_origin_starts_now_on_the_wall_epoch_and_restarts():
    import time

    before = time.time()
    chain = trace.SetupChain()
    chain.mark("a")
    assert before - 0.05 <= chain.origin_s <= chain.last_s <= time.time() + 0.05
    chain.extras["n"] = 1.0
    chain.restart()
    assert chain.spans == [] and chain.extras == {} and chain.origin_s >= before


def test_the_process_chain_starts_where_the_recorder_was_anchored():
    import time

    assert trace.setup() is trace.setup()
    assert trace.setup().origin_s == trace.default()._wall0
    started = trace.process_start_s()
    # the kernel started this process before any statement of it ran
    assert started is not None and started <= trace.setup().origin_s + 0.02 and started <= time.time()


def test_emit_writes_setup_spans_into_the_ring_only_while_it_is_on():
    rec = trace.default()
    was = rec.enabled
    chain = trace.SetupChain(origin_s=10.0)
    chain.mark("setup:x", at_s=12.0)
    try:
        trace.configure(enabled=False)
        rec.clear()
        chain.emit()
        assert rec.export() == []
        trace.configure(enabled=True)
        chain.emit()
        (event,) = [e for e in rec.export() if e["cat"] == "setup"]
        assert (event["name"], event["ts"], event["dur"]) == ("setup:x", 10.0e6, 2.0e6)
    finally:
        rec.clear()
        trace.configure(enabled=was)


# ------------------------------------------------- the compile's seconds


def test_the_listeners_keep_the_seconds_of_each_part_of_a_compile_request():
    stats = platform._CompileStats()
    stats.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25)
    stats.on_duration("/jax/core/compile/jaxpr_trace_duration", 0.25)
    stats.on_duration("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.125)
    stats.on_duration("/jax/compilation_cache/cache_retrieval_time_sec", 2.0)
    stats.on_duration("/jax/some/other_duration", 9.0)
    stats.on_event("/jax/compilation_cache/cache_hits")
    stats.on_duration("/jax/core/compile/backend_compile_duration", 3.0, fun_name="f")
    assert stats.phase_s == {"trace_s": 0.5, "lower_s": 0.125, "cache_load_s": 2.0}
    assert (stats.compiles, stats.compile_s, stats.hits, stats.misses) == (1, 3.0, 1, 0)
    assert stats.functions == {"f": {"cache": "hit", "s": 3.0}}


def test_compile_phase_seconds_grow_with_a_real_compile():
    import jax
    import jax.numpy as jnp

    platform.count_compiles()
    before = platform.compile_phase_seconds()
    assert set(before) == {"trace_s", "lower_s", "cache_load_s", "compile_s", "cache_hits", "cache_misses"}
    jax.jit(lambda x: jnp.sin(x) * 41.5 + 35)(jnp.ones((3, 5))).block_until_ready()
    after = platform.compile_phase_seconds()
    assert after["trace_s"] > before["trace_s"] and after["lower_s"] > before["lower_s"]
    assert after["compile_s"] > before["compile_s"]
    assert all(after[k] >= before[k] for k in before)


# ------------------------------------------------------- the pod manager


def test_a_pods_launch_is_stamped_when_the_backend_returns():
    import time

    manager = PodManager(FakePodBackend(), JobConfig(num_workers=2, training_data="x"))
    assert manager.launched_at() is None
    before = time.time()
    manager.start()
    try:
        names = manager.live_pods()
        stamps = [manager.launched_at(n) for n in names]
        assert len(names) == 2 and all(before - 0.05 <= s <= time.time() + 0.05 for s in stamps)
        assert manager.launched_at() == max(stamps)
        assert manager.launched_at("no-such-pod") is None
    finally:
        manager.stop()


# --------------------------------------------- a worker without a master


def _job(tmp_path, **cfg):
    train = str(tmp_path / "train.rio")
    generate("mnist", train, 192)
    config = JobConfig(
        model_def="mnist.model_spec", model_params="compute_dtype=float32", training_data=train,
        minibatch_size=16, num_minibatches_per_task=2, **cfg,
    )
    reader = create_data_reader(train)
    dispatcher = TaskDispatcher(reader.create_shards(32))
    spec = load_model_spec("elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32")
    return config, dispatcher, reader, spec


@pytest.mark.parametrize("pipelined", [True, False], ids=["pipelined", "synchronous"])
def test_the_chain_rides_the_first_training_report_and_no_other(tmp_path, devices, pipelined):
    """A standalone Worker (no worker.main, no master process): its chain
    starts at its constructor, rides report 1 alone, becomes ONE ``setup``
    record right after the first ``train`` record, and is then dropped."""
    config, dispatcher, reader, spec = _job(tmp_path, task_pipelining=pipelined)
    writer = MetricsWriter(str(tmp_path / "metrics"), tensorboard=False)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    seen = []
    report = servicer.ReportTaskResult
    servicer.ReportTaskResult = lambda req: (seen.append(req), report(req))[1]
    worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices[:2])
    assert worker._setup is not None
    worker.run()
    writer.close()
    assert len(seen) == 6 and "setup" in seen[0] and not any("setup" in r for r in seen[1:])
    assert worker._setup is None
    records = read_metrics(str(tmp_path / "metrics"))
    kinds = [r["kind"] for r in records]
    assert kinds.count("setup") == 1 and kinds.index("setup") == kinds.index("train") + 1
    (record,) = [r for r in records if r["kind"] == "setup"]
    spans = _spans(record)
    chain = ["setup:build", "init_state", "setup:first_prep", "setup:first_dispatch", "setup:first_step"]
    assert sorted(spans, key=lambda n: spans[n]) == chain
    for a, b in zip(chain, chain[1:]):
        assert spans[a][1] == spans[b][0], (a, b)
    assert all(t0 <= t1 for t0, t1 in spans.values())
    # The order, each pair of stamps on ONE clock: the master's ``ts`` are ``time.time()``, the chain's stamps
    # ``trace.now_s()`` (an anchor plus ``perf_counter``), and the two drift apart by tens of microseconds in a
    # process that has lived for minutes (16 us lost this line the driver's run of PR 53's tree, under -n 6).
    first_train = next(r for r in records if r["kind"] == "train")
    stamps = [r["ts"] for r in records]
    assert stamps == sorted(stamps) and first_train["ts"] <= record["ts"]
    assert spans["setup:first_dispatch"][1] == spans["setup:first_step"][0] <= spans["setup:first_step"][1]
    # who sent it, and what the first dispatch's compile was made of
    assert record["pid"] == os.getpid() and record["step"] == first_train["step"]
    assert record["compile_requests"] >= 1
    parts = {k.rsplit(".", 1)[1]: v for k, v in record.items() if k.startswith("setup:first_dispatch.")}
    assert set(parts) == {"trace_s", "lower_s", "compile_s", "cache_load_s", "restore_s"}
    assert parts["compile_s"] > 0 and parts["trace_s"] > 0 and all(v >= 0 for v in parts.values())
    # a worker handed its spec has no program store (PR 57): its step is traced, nothing restored
    assert (record["programs_restored"], record["programs_traced"], parts["restore_s"]) == (0.0, 1.0, 0.0)
    assert parts["compile_s"] <= spans["setup:first_dispatch"][1] - spans["setup:first_dispatch"][0]
    # the same stamps as gauges of the worker's registry
    family = worker.gauges.snapshot()["edl_setup_seconds"]
    by_phase = {s["labels"]["phase"]: s["value"] for s in family["samples"]}
    assert set(by_phase) == set(chain) - {"setup:first_step"} | {"setup:first_dispatch.restore_s"}
    assert by_phase.pop("setup:first_dispatch.restore_s") == 0.0
    for name, seconds in by_phase.items():
        assert seconds == pytest.approx(spans[name][1] - spans[name][0], abs=1e-6)
    assert {name: worker.gauges.snapshot()[name]["samples"][0]["value"] for name in ("edl_programs_restored", "edl_programs_traced")} == {
        "edl_programs_restored": 0.0, "edl_programs_traced": 1.0}
    # ... and none of it among the counters that ride EVERY report
    assert not any(k.startswith("setup") for r in seen for k in r["counters"])


def test_with_the_ring_on_the_chain_is_also_setup_spans_of_the_trace(tmp_path, devices):
    config, dispatcher, reader, spec = _job(tmp_path, trace=True)
    servicer = MasterServicer(dispatcher)
    rec = trace.default()
    try:
        rec.clear()
        worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices[:1])
        worker.run()
        # in this process the worker's ring and the master's are one: what was shipped is there twice
        dump = servicer.DumpTrace({})
        shipped = [e for buf in dump["processes"].values() for e in buf["events"]]
        events = shipped or dump["master_events"]
        names = [e["name"] for e in events if e.get("cat") == "setup"]
        assert names == ["setup:build", "init_state", "setup:first_prep", "setup:first_dispatch"]
    finally:
        rec.clear()
        trace.configure(enabled=False)


def test_a_failed_first_report_keeps_the_chain_for_the_next(tmp_path, devices):
    config, dispatcher, reader, spec = _job(tmp_path)
    servicer = MasterServicer(dispatcher)
    seen = []
    report = servicer.ReportTaskResult

    def flaky(req):
        seen.append(req)
        answer = report(req)
        if len(seen) == 1:
            raise RuntimeError("the answer was lost")
        return answer

    servicer.ReportTaskResult = flaky
    worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices[:1])
    worker.run()
    assert "setup" in seen[0] and "setup" in seen[1] and not any("setup" in r for r in seen[2:])


# ------------------------------------------------------ a whole local job


def test_a_local_job_leaves_two_setup_records_that_partition_its_set_up(tmp_path):
    """``elasticdl train --local``: the master in this process, one worker
    in a process of its own.  Two ``setup`` records — the master's when the
    fleet is spawned, the worker's on its first report — whose spans are
    consecutive from the launcher's first stamp to the first report, on the
    clock the ``train`` records' ``ts`` are on."""
    train_path = str(tmp_path / "train.rio")
    generate("mnist", train_path, 192)
    metrics_dir = str(tmp_path / "metrics")
    rc = cli_main([
        "train", "--local", "--job_name=setup-job", "--model_def=mnist.model_spec",
        "--model_params=compute_dtype=float32", f"--training_data={train_path}", "--minibatch_size=16",
        "--num_minibatches_per_task=2", "--num_workers=1", f"--metrics_dir={metrics_dir}",
        f"--pod_log_dir={tmp_path / 'pods'}",
    ])
    assert rc == 0
    records = read_metrics(metrics_dir)
    train = [r for r in records if r["kind"] == "train"]
    setups = [r for r in records if r["kind"] == "setup"]
    assert len(train) == 6 and len(setups) == 2
    master, worker = setups
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "setup" and kinds.index("setup", 1) == kinds.index("train") + 1
    assert list(_spans(master)) and sorted(_spans(master), key=lambda n: _spans(master)[n]) == MASTER_CHAIN
    assert sorted(_spans(worker), key=lambda n: _spans(worker)[n][0])[:1] == ["setup:interp"]
    spans = {**_spans(master), **{n: s for n, s in _spans(worker).items() if n != "setup:shards"}}
    order = MASTER_CHAIN + WORKER_CHAIN
    assert set(spans) == set(order)
    for a, b in zip(order, order[1:]):
        assert spans[a][1] == spans[b][0], (a, b, spans[a], spans[b])
    assert all(t0 <= t1 for t0, t1 in list(spans.values()) + [_spans(worker)["setup:shards"]])
    first_stamp, first_report = spans["setup:launch"][0], train[0]["ts"]
    assert sum(t1 - t0 for t0, t1 in spans.values()) == pytest.approx(first_report - first_stamp, abs=0.05)
    # one clock: each record is stamped just after its chain's last span closed
    assert 0 <= master["ts"] - spans["setup:spawn"][1] < 0.05
    assert 0 <= worker["ts"] - spans["setup:first_step"][1] < 0.05
    # the worker's index scan is inside its build; two processes, two pids
    scan, build = _spans(worker)["setup:shards"], spans["setup:build"]
    assert build[0] <= scan[0] <= scan[1] <= build[1]
    assert master["pid"] == os.getpid() and worker["pid"] not in (0.0, master["pid"])
    assert worker["proc_start"] <= spans["setup:imports"][0] + 0.02
    # the worker process imports before it registers: seconds, not microseconds
    assert spans["setup:imports"][1] - spans["setup:imports"][0] > 0.5
