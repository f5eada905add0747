"""Observability: metrics JSONL stream from train/eval reports, profiler
trace capture in the worker loop (SURVEY.md §5)."""

import glob
import json
import math
import os
import struct
import subprocess
import sys
import time

import pytest

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.common.metrics import MetricsWriter, read_metrics
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.data.synthetic import generate
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.worker.worker import DirectMasterProxy, Worker


def test_metrics_writer_roundtrip(tmp_path):
    writer = MetricsWriter(str(tmp_path), tensorboard=False)
    writer.write("train", 3, {"loss": 1.5, "accuracy": 0.5})
    writer.write("eval", 3, {"loss": 1.2})
    writer.close()
    records = read_metrics(str(tmp_path))
    assert len(records) == 2
    assert records[0]["kind"] == "train"
    assert records[0]["step"] == 3
    assert records[0]["loss"] == 1.5
    assert records[1]["kind"] == "eval"


def _crc32c(data: bytes) -> int:
    """An independent CRC-32C: ``google_crc32c`` where importable, else the
    bitwise definition (no table), pinned on the standard check vector."""
    try:
        import google_crc32c

        return google_crc32c.value(data)
    except ImportError:
        crc = 0xFFFFFFFF
        for byte in data:
            crc ^= byte
            for _ in range(8):
                crc = (crc >> 1) ^ (0x82F63B78 if crc & 1 else 0)
        return crc ^ 0xFFFFFFFF


def _masked(data: bytes) -> int:
    crc = _crc32c(data)
    return (((crc >> 15) | (crc << 17)) + 0xA282EAD8) & 0xFFFFFFFF


def _fields(buf: bytes) -> dict:
    """One protobuf message's fields by number (the five kinds the mirror
    writes; a nested message comes back as bytes): a parser that shares
    no code with the writer."""
    out, i = {}, 0
    while i < len(buf):
        field, kind = buf[i] >> 3, buf[i] & 7
        i += 1
        if kind == 0:  # varint
            value = shift = 0
            while True:
                value |= (buf[i] & 0x7F) << shift
                shift += 7
                i += 1
                if not buf[i - 1] & 0x80:
                    break
        elif kind == 2:  # length-delimited (every length here is under 128)
            value = buf[i + 1:i + 1 + buf[i]]
            i += 1 + len(value)
        else:  # fixed64 (double) / fixed32 (float)
            size, fmt = (8, "<d") if kind == 1 else (4, "<f")
            value = struct.unpack(fmt, buf[i:i + size])[0]
            i += size
        out[field] = value
    return out


def _read_events(path: str) -> list:
    """The events of a TFRecord file, each record's two checksums verified."""
    events = []
    with open(path, "rb") as f:
        data = f.read()
    while data:
        (length,) = struct.unpack("<Q", data[:8])
        assert struct.unpack("<I", data[8:12])[0] == _masked(data[:8])
        payload = data[12:12 + length]
        assert struct.unpack("<I", data[12 + length:16 + length])[0] == _masked(payload)
        events.append(_fields(payload))
        data = data[16 + length:]
    return events


def test_the_mirror_checksum_is_crc32c():
    from elasticdl_tpu.common.metrics import _CRC32C, _masked_crc32c

    assert _crc32c(b"123456789") == 0xE3069283  # the standard check vector
    assert len(_CRC32C) == 256
    for data in (b"", b"123456789", bytes(range(256)) * 3):
        assert int.from_bytes(_masked_crc32c(data), "little") == _masked(data)


def _mirror_of(tmp_path) -> str:
    writer = MetricsWriter(str(tmp_path))
    writer.write("train", 1, {"loss": 2.0})
    writer.write("eval", 300, {"auc": 0.625, "loss": 1.5})
    writer.write("counter", 300, {"compiles": 3.0}, tensorboard=False)
    writer.close()
    (events,) = glob.glob(str(tmp_path / "tensorboard" / "events*"))
    return events


#: what ``_mirror_of`` mirrored: (tag, step, value), values exact in float32
MIRRORED = [("train/loss", 1, 2.0), ("eval/auc", 300, 0.625), ("eval/loss", 300, 1.5)]


def test_metrics_writer_tensorboard(tmp_path):
    """The mirror's file IS a TensorBoard events file: TFRecord framing
    with both masked CRC-32C values right, a ``file_version`` event first,
    then one ``Event{wall_time, step, summary{value{tag, simple_value}}}``
    a mirrored scalar."""
    path = _mirror_of(tmp_path)
    assert "tfevents" in os.path.basename(path)
    first, *scalars = _read_events(path)
    assert first[3] == b"brain.Event:2" and set(first) == {1, 2, 3}
    assert abs(first[1] - time.time()) < 600
    got = []
    for event in scalars:
        assert set(event) == {1, 2, 5} and event[1] >= first[1]
        value = _fields(_fields(event[5])[1])  # Summary{1: Value{1: tag, 2: simple_value}}
        assert set(value) == {1, 2}
        got.append((value[1].decode(), event[2], value[2]))
    assert got == MIRRORED


def test_a_value_past_float32_is_mirrored_as_infinity(tmp_path):
    """A diverged loss must not raise in the master's report handler."""
    writer = MetricsWriter(str(tmp_path))
    writer.write("train", 1, {"loss": 1e39, "drift": -1e300, "nan": float("nan")})
    writer.close()
    (path,) = glob.glob(str(tmp_path / "tensorboard" / "events*"))
    values = [_fields(_fields(e[5])[1])[2] for e in _read_events(path)[1:]]
    assert values[:2] == [math.inf, -math.inf] and math.isnan(values[2])


def test_tensorboard_reads_the_mirror(tmp_path):
    """TensorBoard's own loader gives the same scalars.  In a child: the
    loader imports TensorFlow where it is installed, which this process
    (jax, several workers) is better off without."""
    pytest.importorskip("tensorboard")
    path = _mirror_of(tmp_path)
    script = (
        "import json, sys\n"
        "from tensorboard.backend.event_processing.event_file_loader import EventFileLoader\n"
        "from tensorboard.util import tensor_util\n"
        "events = list(EventFileLoader(sys.argv[1]).Load())\n"
        "out = [[v.tag, e.step, float(tensor_util.make_ndarray(v.tensor))] for e in events for v in e.summary.value]\n"
        "print(json.dumps({'version': events[0].file_version, 'scalars': out}))\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", script, path], capture_output=True, text=True, timeout=600
    )
    assert out.returncode == 0, out.stderr[-3000:]
    said = json.loads(out.stdout.strip().splitlines()[-1])
    assert said["version"] == "brain.Event:2"
    assert [tuple(s) for s in said["scalars"]] == MIRRORED


def test_a_second_mirror_of_one_second_appends_without_a_second_header(tmp_path, monkeypatch):
    monkeypatch.setattr(time, "time", lambda: 1790000000.25)
    for step in (1, 2):
        writer = MetricsWriter(str(tmp_path))
        writer.write("train", step, {"loss": 2.0})
        writer.close()
    (path,) = glob.glob(str(tmp_path / "tensorboard" / "events*"))
    events = _read_events(path)
    assert [3 in e for e in events] == [True, False, False]
    assert [e[2] for e in events] == [0, 1, 2]


def test_a_group_can_stay_out_of_the_tensorboard_mirror(tmp_path):
    class _Tb:
        seen = []

        def add_scalar(self, tag, value, step):
            self.seen.append(tag)

        def close(self):
            pass

    writer = MetricsWriter(str(tmp_path), tensorboard=False)
    writer._tb = _Tb()
    writer.write("phase", 1, {"dispatch": 2.0})
    writer.write("counter", 1, {"compiles": 3.0}, tensorboard=False)
    writer.close()
    assert _Tb.seen == ["phase/dispatch"]
    assert [r["kind"] for r in read_metrics(str(tmp_path))] == ["phase", "counter"]


def test_read_metrics_missing_dir(tmp_path):
    assert read_metrics(str(tmp_path / "nope")) == []


def test_metrics_writer_holds_one_append_handle(tmp_path):
    """One handle for the stream's life (the old idiom reopened per
    record); records are flushed so a concurrent reader sees them."""
    writer = MetricsWriter(str(tmp_path), tensorboard=False)
    f = writer._f
    writer.write("train", 1, {"loss": 1.0})
    writer.write("train", 2, {"loss": 0.5})
    assert writer._f is f  # same handle across records
    # Flushed: visible to an independent reader before close().
    assert len(read_metrics(str(tmp_path))) == 2
    writer.close()
    assert writer._f is None
    # A report racing close() reopens instead of crashing the handler.
    writer.write("train", 3, {"loss": 0.25})
    writer.close()
    assert len(read_metrics(str(tmp_path))) == 3


def test_read_metrics_tolerates_torn_final_line(tmp_path):
    writer = MetricsWriter(str(tmp_path), tensorboard=False)
    writer.write("train", 1, {"loss": 1.0})
    writer.write("train", 2, {"loss": 0.5})
    writer.close()
    path = tmp_path / "metrics.jsonl"
    # Simulate a crash mid-append: the final line is torn.
    with open(path, "a") as f:
        f.write('{"ts": 3, "kind": "tra')
    records = read_metrics(str(tmp_path))
    assert [r["step"] for r in records] == [1, 2]
    # Garbage EARLIER in the stream is corruption, not a crash tail: raise.
    lines = path.read_text().splitlines()
    lines[0] = "not json {{{"
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(Exception):
        read_metrics(str(tmp_path))


def _job(tmp_path, records=64, **cfg):
    train = str(tmp_path / "train.rio")
    val = str(tmp_path / "val.rio")
    generate("mnist", train, records)
    generate("mnist", val, 32)
    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=train,
        validation_data=val,
        minibatch_size=16,
        num_minibatches_per_task=2,
        **cfg,
    )
    reader = create_data_reader(train)
    per_task = config.minibatch_size * config.num_minibatches_per_task
    dispatcher = TaskDispatcher(reader.create_shards(per_task))
    evaluation = EvaluationService(
        create_data_reader(val).create_shards(per_task), evaluation_steps=2
    )
    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    return config, dispatcher, evaluation, reader, spec


class _MuxReader:
    def __init__(self, *readers):
        self._readers = readers

    def read_records(self, shard):
        for r in self._readers:
            if shard.name in r.sources():
                return r.read_records(shard)
        raise KeyError(shard.name)


def test_master_writes_train_and_eval_metrics(tmp_path, devices):
    config, dispatcher, evaluation, reader, spec = _job(tmp_path)
    writer = MetricsWriter(str(tmp_path / "metrics"), tensorboard=False)
    servicer = MasterServicer(
        dispatcher, evaluation=evaluation, metrics_writer=writer
    )
    val_reader = create_data_reader(str(tmp_path / "val.rio"))
    worker = Worker(
        config,
        DirectMasterProxy(servicer),
        _MuxReader(reader, val_reader),
        spec=spec,
    )
    worker.run()
    writer.close()
    records = read_metrics(str(tmp_path / "metrics"))
    kinds = {r["kind"] for r in records}
    assert "train" in kinds
    assert "eval" in kinds
    train_records = [r for r in records if r["kind"] == "train"]
    assert all("loss" in r for r in train_records)
    # eval rounds recorded once each
    eval_records = [r for r in records if r["kind"] == "eval"]
    assert len(eval_records) == evaluation.completed_rounds()


def test_phase_counts_ride_reports_into_job_status(tmp_path, devices):
    """PhaseTimers.counts() rides ReportTaskResult/ReportCheckpoint beside
    phase_times (additive optional field), and JobStatus republishes it —
    per-phase AVERAGES become computable from the same artifact that held
    only cumulative sums."""
    config, dispatcher, evaluation, reader, spec = _job(tmp_path)
    servicer = MasterServicer(dispatcher)
    worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec)
    worker.run()
    status = servicer.JobStatus({})
    counts = status["phase_counts"].get(worker.worker_id)
    times = status["phase_times"].get(worker.worker_id)
    assert counts and times
    # Counts key the same phases the seconds do, and each recorded phase
    # entered at least once — total/count is a well-defined mean.
    for name, seconds in times.items():
        assert counts.get(name, 0) >= 1, name
        assert seconds >= 0


def _train_job(tmp_path, name, devices=None, **cfg):
    """Six training tasks of two steps, metrics to ``<tmp>/<name>``."""
    work = tmp_path / name
    work.mkdir()
    config, dispatcher, _, reader, spec = _job(work, records=192, **cfg)
    writer = MetricsWriter(str(work / "metrics"), tensorboard=False)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices)
    worker.run()
    writer.close()
    return worker, servicer, read_metrics(str(work / "metrics"))


def _host_events(xplane_path, name):
    """(line name, event stats) of the ``/host:CPU`` events called ``name``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(line.name, dict(e.stats)) for e in line.events if e.name == name]
    return out


@pytest.mark.parametrize("flags", [{}, {"profile_tasks": 2, "profile_inline": True}], ids=["three_tasks_written_on_a_thread", "two_tasks_written_on_the_loop"])
def test_worker_profiler_trace(tmp_path, devices, flags):
    """``--profile_dir`` traces ``--profile_tasks`` tasks (3) from the second
    one on, with the host's spans in the same file, and leaves the loop
    alone: prep-ahead stays on and the job trains to the same losses.
    With ``--profile_inline`` the loop writes the files itself: they are
    there, whole, when the window's last task has reported, and no thread
    is left to wait for."""
    from elasticdl_tpu.common import trace

    PROFILE_TASKS = flags.get("profile_tasks", 3)
    prof = str(tmp_path / "prof")
    worker, _, records = _train_job(tmp_path, "traced", profile_dir=prof, **flags)
    traces = glob.glob(os.path.join(prof, "**", "*.xplane.pb"), recursive=True)
    assert len(traces) == 1, "expected one xplane trace from the profile window"
    assert worker._prep_ahead_eligible() and worker._pipelining_enabled()
    assert worker._profile_state == "closed" and trace.default().bridge is None
    assert worker.config.profile_tasks == PROFILE_TASKS
    if flags.get("profile_inline"):
        assert worker._profile_closer is None  # the loop wrote the files: no thread was started

    dispatches = _host_events(traces[0], "dispatch")
    # one task-loop line; the first task (seq 0, the compile) is outside
    assert len({line for line, _ in dispatches}) == 1
    seqs = [stats["seq"] for _, stats in dispatches]
    assert seqs[:PROFILE_TASKS] == list(range(1, PROFILE_TASKS + 1))
    assert len({stats["task"] for _, stats in dispatches}) == len(dispatches)
    # two steps a task: the model version before the n-th dispatch
    assert [stats["step0"] for _, stats in dispatches] == [2 * n for n in seqs]
    traced_tasks = {stats["task"] for _, stats in dispatches[:PROFILE_TASKS]}
    for name in ("prep_wait", "step_wait", "metrics"):
        tasks = {stats.get("task") for _, stats in _host_events(traces[0], name)}
        assert traced_tasks <= tasks, name
    # prep runs on a pool thread, named for the profiler
    prep_lines = {line for line, _ in _host_events(traces[0], "prep")}
    assert prep_lines and all(line.startswith("edl-prep") for line in prep_lines)

    _, _, plain = _train_job(tmp_path, "plain")
    losses = lambda recs: [(r["step"], r["loss"]) for r in recs if r["kind"] == "train"]  # noqa: E731
    assert len(losses(records)) == 6
    assert losses(records) == pytest.approx(losses(plain), rel=1e-6)


def test_a_job_shorter_than_the_profile_window_still_writes_its_trace(tmp_path, devices):
    prof = str(tmp_path / "prof")
    config, dispatcher, _, reader, spec = _job(tmp_path, profile_dir=prof)
    worker = Worker(config, DirectMasterProxy(MasterServicer(dispatcher)), reader, spec=spec)
    worker.run()
    assert glob.glob(os.path.join(prof, "**", "*.xplane.pb"), recursive=True)
    assert worker._profile_state == "closed" and worker._profile_closer is None


def test_profiler_that_cannot_start_is_logged_not_fatal(tmp_path, devices, monkeypatch):
    import jax

    def boom(*a, **k):
        raise RuntimeError("no profiler here")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    worker, _, records = _train_job(tmp_path, "job", profile_dir=str(tmp_path / "prof"))
    assert sum(r["kind"] == "train" for r in records) == 6
    assert worker._profile_state == "closed"


def test_counters_ride_every_training_report(tmp_path, devices):
    """One ``counter`` record per successful training report, cumulative
    (so non-decreasing), beside the ``phase`` record; JobStatus serves the
    newest per worker; the same five are gauges of the worker's registry."""
    from elasticdl_tpu.worker.worker import COUNTER_GAUGES

    worker, servicer, records = _train_job(tmp_path, "job")
    counters = [r for r in records if r["kind"] == "counter"]
    train = [r for r in records if r["kind"] == "train"]
    assert len(counters) == len(train) == 6
    assert [r["step"] for r in counters] == [r["step"] for r in train]
    for key in COUNTER_GAUGES:
        values = [r[key] for r in counters]
        assert values == sorted(values), key
    # a report goes out after the NEXT task's dispatch (pipelining)
    assert [r["dispatches"] for r in counters] == [2, 3, 4, 5, 6, 6]
    assert counters[-1]["compiles"] >= 1 and counters[-1]["compile_s"] > 0
    assert 0 <= counters[-1]["dispatches_device_idle"] <= 5
    assert counters[-1]["hbm_peak_bytes"] == 0  # XLA:CPU reports no memory stats
    newest = servicer.JobStatus({})["counters"][worker.worker_id]
    assert newest == {k: counters[-1][k] for k in COUNTER_GAUGES}
    families = worker.gauges.snapshot()
    for key, (family, _) in COUNTER_GAUGES.items():
        assert family in families, family


def test_a_models_step_counters_are_summed_not_reported(tmp_path, devices):
    """``ModelSpec.step_counters`` is the one declaration of a model's
    counts: the trainer sums the key over devices, the worker over steps
    into a counter of the same name (gauge ``edl_<key>_total``, the
    declared help text), and no task reports it as a metric."""
    import dataclasses

    import jax.numpy as jnp

    config, dispatcher, _, reader, spec = _job(tmp_path, records=192)
    spec = dataclasses.replace(
        spec,
        metrics=lambda out, batch, m=spec.metrics: {**m(out, batch), "widgets": jnp.float32(3.0)},
        step_counters={"widgets": "widgets a step made"},
    )
    writer = MetricsWriter(str(tmp_path / "metrics"), tensorboard=False)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    worker = Worker(config, DirectMasterProxy(servicer), reader, spec=spec, devices=devices[:2])
    worker.run()
    writer.close()
    records = read_metrics(str(tmp_path / "metrics"))
    # 3 a device a step, two devices, two steps a task, six tasks
    assert [r["widgets"] for r in records if r["kind"] == "counter"] == [12.0 * n for n in range(1, 7)]
    assert all("widgets" not in r for r in records if r["kind"] == "train")
    assert "edl_widgets_total" in worker.gauges.snapshot()


def test_eva_attentions_pairs_ride_the_reports_as_counters(tmp_path, devices):
    """EVA attention's two counts (``attentions.EVA_COUNTERS``, from the
    shapes the attention was called with) through a real worker loop: a
    ``counter`` record a task that grows by sequences x held heads x layers
    x the pairs of one sequence, gauges ``edl_eva_pairs_*_total``, and no
    task reports them as metrics."""
    from elasticdl_tpu.models import attentions
    from elasticdl_tpu.ops import eva_attention as eva_ops

    train = str(tmp_path / "train.rio")
    generate("lm", train, 16, seq_len=150, vocab=320)
    params = (
        "compute_dtype=float32;vocab_size=320;hidden_size=32;num_attention_heads=2;heads_held=1;"
        "num_hidden_layers=2;layer_types=[\"dense\",\"dense\"];intermediate_size=48;attention_class=\"eva\";"
        "window_size=64;chunk_size=8;norm_add_unit_offset=true;fp32_skip_add=true;num_pred_heads=8;seq_len=150"
    )
    config = JobConfig(
        model_def="moe_lm.model_spec", model_params=params, training_data=train,
        minibatch_size=2, num_minibatches_per_task=2,
    )
    reader = create_data_reader(train)
    dispatcher = TaskDispatcher(reader.create_shards(4))
    writer = MetricsWriter(str(tmp_path / "metrics"), tensorboard=False)
    servicer = MasterServicer(dispatcher, metrics_writer=writer)
    worker = Worker(config, DirectMasterProxy(servicer), reader, devices=devices[:1])
    assert dict(worker.spec.step_counters) == attentions.EVA_COUNTERS
    worker.run()
    writer.close()
    records = read_metrics(str(tmp_path / "metrics"))
    exact, far = eva_ops.pairs(150, 64, 8)
    a_task = 2 * 2 * 1 * 2  # steps x sequences x held heads x layers
    counters = [r for r in records if r["kind"] == "counter"]
    assert [r["eva_pairs_exact"] for r in counters] == [float(a_task * exact * n) for n in range(1, 5)]
    assert [r["eva_pairs_summary"] for r in counters] == [float(a_task * far * n) for n in range(1, 5)]
    assert all("eva_pairs_exact" not in r for r in records if r["kind"] == "train")
    families = worker.gauges.snapshot()
    for key in attentions.EVA_COUNTERS:
        assert f"edl_{key}_total" in families, key


def test_starved_dispatches_are_the_ones_that_found_the_device_idle(tmp_path, devices):
    """Synchronous mode settles every task before the next dispatch: every
    dispatch but the first finds the previous output ready.  On ONE device:
    the settle fetches one replica, and on a loaded box the other seven
    virtual devices may really still be running."""
    _, _, records = _train_job(tmp_path, "sync", devices=devices[:1], task_pipelining=False)
    last = [r for r in records if r["kind"] == "counter"][-1]
    assert last["dispatches"] == 6 and last["dispatches_device_idle"] == 5
