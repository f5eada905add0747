"""PS service tier (ps/service.py): the native host store behind gRPC.

Covers the wire codec, shard routing, numerics-vs-local-store equivalence,
checkpoint fan-out (each shard dumps its own slice), the trainer swapping in
RemoteEmbeddingStore (config.ps_addresses), and the master launching/awaiting
a real PS pod fleet end-to-end.
"""

import os

import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import HostTableIO
# Canonical public import path for the service tier classes:
from elasticdl_tpu.ps import PSClient, PSServer, RemoteEmbeddingStore  # noqa: F401
from elasticdl_tpu.ps.service import (
    PSFrameError,
    decode_frame,
    encode_frame,
    parse_ps_addresses,
    shard_of,
    snapshot_filename,
    validate_meta,
)


def _native_available() -> bool:
    from elasticdl_tpu.ps.host_store import native_lib_available

    return native_lib_available()


needs_native = pytest.mark.skipif(
    not _native_available(), reason="native lib unavailable"
)

IO = HostTableIO(
    ids_fn=lambda b: b["cat"], dim=8, optimizer="sgd", learning_rate=0.5
)


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------


def test_frame_roundtrip():
    meta = {"table": "t", "nested": {"a": [1, 2]}}
    arrays = {
        "ids": np.arange(7, dtype=np.int64),
        "rows": np.random.RandomState(0).randn(7, 8).astype(np.float32),
        "empty": np.empty((0, 3), np.float32),
    }
    meta2, arrays2 = decode_frame(encode_frame(meta, arrays))
    assert meta2 == meta
    assert set(arrays2) == set(arrays)
    for k in arrays:
        np.testing.assert_array_equal(arrays2[k], arrays[k])
        assert arrays2[k].dtype == arrays[k].dtype


def test_frame_malformed_fails_at_boundary():
    with pytest.raises(PSFrameError):
        decode_frame(b"\x01")  # too short
    with pytest.raises(PSFrameError):
        decode_frame(b"\xff\xff\xff\xff")  # header runs past payload
    good = encode_frame({"table": "t"}, {"ids": np.arange(3, dtype=np.int64)})
    with pytest.raises(PSFrameError):
        decode_frame(good[:-4])  # truncated array payload
    with pytest.raises(PSFrameError):
        validate_meta("Pull", {})  # missing required field
    with pytest.raises(PSFrameError):
        validate_meta("Pull", {"table": 3})  # wrong type
    with pytest.raises(PSFrameError):
        validate_meta("Nope", {})  # unknown method


def test_shard_of_nonnegative_for_negative_ids():
    ids = np.array([-7, -1, 0, 5, 1 << 60], dtype=np.int64)
    owner = shard_of(ids, 4)
    assert ((owner >= 0) & (owner < 4)).all()


# ---------------------------------------------------------------------------
# server + client
# ---------------------------------------------------------------------------


@pytest.fixture
def one_shard():
    server = PSServer({"t": IO}, shard=0, num_shards=1).start()
    store = RemoteEmbeddingStore("t", IO.dim, [server.address])
    store.wait_ready()
    yield server, store
    store.close()
    server.stop()


@needs_native
def test_remote_matches_local_store(one_shard):
    """Pull/push through the service == the same ops on a local store:
    deterministic per-id init plus identical server-side optimizer applies."""
    from elasticdl_tpu.ps.host_store import HostEmbeddingStore

    _, remote = one_shard
    local = HostEmbeddingStore(
        dim=IO.dim, optimizer=IO.optimizer, learning_rate=IO.learning_rate,
        init_scale=IO.init_scale,
    )
    ids = np.array([[3, 9, 3], [7, 1, 9]], dtype=np.int64)  # dups included
    np.testing.assert_array_equal(remote.pull(ids), local.pull(ids))

    grads = np.random.RandomState(1).randn(*ids.shape, IO.dim).astype(np.float32)
    remote.push_grad(ids, grads)
    local.push_grad(ids, grads)
    np.testing.assert_array_equal(remote.pull(ids), local.pull(ids))
    assert len(remote) == len(local) == 4  # distinct ids materialized


@needs_native
def test_sharded_routing_and_stats():
    """ids route by id mod n; values match a single-shard fleet exactly
    (per-id determinism makes topology invisible to the caller)."""
    servers = [
        PSServer({"t": IO}, shard=s, num_shards=2).start() for s in range(2)
    ]
    both = RemoteEmbeddingStore("t", IO.dim, [s.address for s in servers])
    solo_server = PSServer({"t": IO}, shard=0, num_shards=1).start()
    solo = RemoteEmbeddingStore("t", IO.dim, [solo_server.address])
    try:
        ids = np.array([0, 1, 2, 3, 4, 5, 6, 101], dtype=np.int64)
        np.testing.assert_array_equal(both.pull(ids), solo.pull(ids))
        g = np.random.RandomState(2).randn(ids.size, IO.dim).astype(np.float32)
        both.push_grad(ids, g)
        solo.push_grad(ids, g)
        np.testing.assert_array_equal(both.pull(ids), solo.pull(ids))
        # evens (incl. 0,2,4,6) on shard 0, odds (1,3,5,101) on shard 1
        meta0, _ = both._clients[0].call("Stats", {})
        meta1, _ = both._clients[1].call("Stats", {})
        assert meta0["tables"]["t"] == 4
        assert meta1["tables"]["t"] == 4
        assert meta0["shard"] == 0 and meta0["num_shards"] == 2
    finally:
        both.close()
        solo.close()
        for s in servers + [solo_server]:
            s.stop()


@needs_native
def test_unknown_table_and_bad_arrays_are_invalid_argument(one_shard):
    import grpc

    _, remote = one_shard
    client = remote._clients[0]
    with pytest.raises(grpc.RpcError) as e:
        client.call("Pull", {"table": "nope"}, {"ids": np.arange(2, dtype=np.int64)})
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    with pytest.raises(grpc.RpcError) as e:
        client.call("Pull", {"table": "t"}, {"ids": np.arange(2, dtype=np.int32)})
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT
    with pytest.raises(grpc.RpcError) as e:
        client.call(
            "PushGrad", {"table": "t"},
            {"ids": np.arange(2, dtype=np.int64),
             "grads": np.zeros((3, IO.dim), np.float32)},  # shape mismatch
        )
    assert e.value.code() == grpc.StatusCode.INVALID_ARGUMENT


# ---------------------------------------------------------------------------
# checkpoint fan-out
# ---------------------------------------------------------------------------


@needs_native
def test_snapshot_save_load_across_restart(tmp_path):
    """Each shard dumps its own slice; a restarted fleet restores rows
    exactly; restore_latest picks the newest COMPLETE step."""
    servers = [
        PSServer({"t": IO}, shard=s, num_shards=2).start() for s in range(2)
    ]
    store = RemoteEmbeddingStore("t", IO.dim, [s.address for s in servers])
    ids = np.arange(10, dtype=np.int64)
    g = np.random.RandomState(3).randn(ids.size, IO.dim).astype(np.float32)
    store.push_grad(ids, g)
    before = store.pull(ids)
    store.save_snapshot(str(tmp_path), step=5)
    for s in range(2):
        assert os.path.exists(
            tmp_path / "host_stores" / "5" / snapshot_filename("t", s, 2)
        )
    store.close()
    for s in servers:
        s.stop()

    fresh = [
        PSServer({"t": IO}, shard=s, num_shards=2) for s in range(2)
    ]
    assert [s.restore_latest(str(tmp_path)) for s in fresh] == [5, 5]
    for s in fresh:
        s.start()
    store2 = RemoteEmbeddingStore("t", IO.dim, [s.address for s in fresh])
    np.testing.assert_array_equal(store2.pull(ids), before)
    store2.close()
    for s in fresh:
        s.stop()


@needs_native
def test_restore_latest_skips_torn_step(tmp_path):
    """A step missing this shard's file is skipped for an older intact one;
    load(strict=True) on the torn step aborts with FAILED_PRECONDITION-level
    structured error at the client."""
    server = PSServer({"t": IO}, shard=0, num_shards=1).start()
    store = RemoteEmbeddingStore("t", IO.dim, [server.address])
    ids = np.arange(4, dtype=np.int64)
    store.push_grad(ids, np.ones((4, IO.dim), np.float32))
    rows_at_2 = store.pull(ids)
    store.save_snapshot(str(tmp_path), step=2)
    # Fabricate a TORN newer step: dir exists, shard file missing.
    os.makedirs(tmp_path / "host_stores" / "9")
    assert not store.load_snapshot(str(tmp_path), step=9, strict=False)
    with pytest.raises(FileNotFoundError):
        store.load_snapshot(str(tmp_path), step=9, strict=True)
    store.close()
    server.stop()

    fresh = PSServer({"t": IO}, shard=0, num_shards=1)
    assert fresh.restore_latest(str(tmp_path)) == 2
    fresh.start()
    store2 = RemoteEmbeddingStore("t", IO.dim, [fresh.address])
    np.testing.assert_array_equal(store2.pull(ids), rows_at_2)
    store2.close()
    fresh.stop()


@needs_native
def test_snapshot_retention_prunes_per_shard(tmp_path):
    server = PSServer({"t": IO}, shard=0, num_shards=1).start()
    store = RemoteEmbeddingStore("t", IO.dim, [server.address])
    store.pull(np.arange(3, dtype=np.int64))
    for step in (1, 2, 3, 4, 5):
        store.save_snapshot(str(tmp_path), step=step, keep_max=3)
    kept = sorted(os.listdir(tmp_path / "host_stores"))
    assert kept == ["3", "4", "5"]
    store.close()
    server.stop()


# ---------------------------------------------------------------------------
# trainer integration: remote stores via config.ps_addresses
# ---------------------------------------------------------------------------


@needs_native
def test_trainer_uses_remote_stores_and_matches_local(devices):
    """A host-tier DeepFM trained against the PS service tracks the
    local-store run bit-for-bit (same seed, same batches, same server-side
    optimizer), proving the RPC hop changes nothing numerically."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=64, embedding_dim=8, hidden=(16,),
        host_tier=True, compute_dtype="float32",
    )
    assert spec.host_io
    server = PSServer(spec.host_io, shard=0, num_shards=1).start()
    mesh = create_mesh(devices[:4])

    def run(config):
        trainer = Trainer(spec, config, mesh)
        state = trainer.init_state(jax.random.key(0))
        losses = []
        rng = np.random.RandomState(0)
        for _ in range(3):
            batch = {
                "dense": rng.rand(16, 13).astype(np.float32) * 100,
                "cat": rng.randint(0, 1 << 20, (16, 26)).astype(np.int64),
                "labels": rng.randint(0, 2, (16,)).astype(np.int32),
            }
            state, metrics = trainer.run_train_step(state, batch)
            losses.append(float(metrics["loss"]))
        return losses, trainer

    base = JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER)
    remote_cfg = JobConfig(
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        ps_addresses=server.address,
    )
    try:
        local_losses, local_trainer = run(base)
        remote_losses, remote_trainer = run(remote_cfg)
        assert remote_trainer._remote_ps and not local_trainer._remote_ps
        assert remote_losses == local_losses
        assert all(np.isfinite(remote_losses))
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# master-orchestrated end-to-end: PS pod fleet + worker subprocess
# ---------------------------------------------------------------------------

WORKER_PY = """
import os, sys
sys.path.insert(0, {repo!r})
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
from elasticdl_tpu.worker.main import main
sys.exit(main())
"""

PS_PY = """
import sys
sys.path.insert(0, {repo!r})
from elasticdl_tpu.ps.main import main
sys.exit(main())
"""


@needs_native
@pytest.mark.slow
def test_master_launches_ps_fleet_end_to_end(tmp_path):
    """`--num_ps_pods 2`: the master picks ports, launches two PS shard
    subprocesses, waits for readiness, hands workers the addresses through
    the config bus, the host-tier DeepFM job trains to completion, and the
    final checkpoint leaves every shard's slice on disk."""
    import sys as _sys

    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.main import Master
    from elasticdl_tpu.master.pod_manager import ProcessPodBackend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker_entry = tmp_path / "worker_entry.py"
    worker_entry.write_text(WORKER_PY.format(repo=repo))
    ps_entry = tmp_path / "ps_entry.py"
    ps_entry.write_text(PS_PY.format(repo=repo))

    data = str(tmp_path / "criteo.rio")
    generate("criteo", data, 64)
    config = JobConfig(
        job_name="psjob",
        model_def="deepfm.model_spec",
        model_params=(
            'buckets_per_feature=64;embedding_dim=8;hidden=[16];'
            'host_tier=true;compute_dtype="float32"'
        ),
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        training_data=data,
        minibatch_size=16,
        num_minibatches_per_task=1,
        num_workers=1,
        num_ps_pods=2,
        checkpoint_steps=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    master = Master(
        config,
        pod_backend=ProcessPodBackend(argv=[_sys.executable, str(worker_entry)]),
        ps_backend=ProcessPodBackend(argv=[_sys.executable, str(ps_entry)]),
    )
    assert len(parse_ps_addresses(config.ps_addresses)) == 2
    status = master.run(poll_interval_s=0.1)
    assert status["finished"]
    assert status["done"] == 4  # 64 records / 16-record tasks

    # Final checkpoint: BOTH shards dumped their slice of the host table.
    root = tmp_path / "ckpt" / "host_stores"
    steps = sorted(os.listdir(root), key=int)
    assert steps, "no host-store snapshot written"
    latest = root / steps[-1]
    from elasticdl_tpu.models.deepfm import HOST_FM_KEY

    for s in range(2):
        assert (latest / snapshot_filename(HOST_FM_KEY, s, 2)).exists()


@needs_native
@pytest.mark.slow
def test_two_process_world_trains_against_ps_fleet(tmp_path):
    """THE multi-process host-tier proof: two real worker processes form one
    jax.distributed world (8-device mesh) and train a host-tier DeepFM
    against a shared 2-shard PS fleet.  Exercises the per-process slice pull
    (_local_example_range), the addressable-shards-only cotangent push, and
    the rank-gated snapshot fan-out — none of which run outside a real
    multi-process world."""
    import signal
    import subprocess
    import sys as _sys
    import threading
    import time

    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    data = str(tmp_path / "criteo.rio")
    generate("criteo", data, 128)
    reader = create_data_reader(data)
    shards = reader.create_shards(32)

    dispatcher = TaskDispatcher(shards, num_epochs=2)
    rendezvous = RendezvousServer(heartbeat_timeout_s=6.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()
    stop = threading.Event()

    max_world = {"n": 0}

    def reap():
        while not stop.is_set():
            rendezvous.reap_dead()
            max_world["n"] = max(
                max_world["n"], rendezvous.membership()["world_size"]
            )
            time.sleep(0.25)

    threading.Thread(target=reap, daemon=True).start()

    # ONE source of truth for the model shape: the PS fleet builds its
    # stores from the spec parsed out of the same string the workers get.
    model_params = (
        'buckets_per_feature=64;embedding_dim=8;hidden=[16];'
        'host_tier=true;compute_dtype="float32"'
    )
    from elasticdl_tpu.common.config import _parse_kv_string
    from elasticdl_tpu.models.spec import load_model_spec

    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        **_parse_kv_string(model_params),
    )
    ps_servers = [
        PSServer(spec.host_io, shard=s, num_shards=2).start() for s in range(2)
    ]

    import socket as _socket

    coord = _socket.socket()
    coord.bind(("", 0))
    coord_port = coord.getsockname()[1]
    coord.close()

    config = JobConfig(
        model_def="deepfm.model_spec",
        model_params=model_params,
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        training_data=data,
        minibatch_size=16,
        master_addr=server.address,
        multihost=True,
        coordinator_port=coord_port,
        checkpoint_dir=str(tmp_path / "ckpt"),
        checkpoint_steps=4,
        num_epochs=2,
        ps_addresses=",".join(s.address for s in ps_servers),
    )

    def _spawn(worker_id):
        env = dict(os.environ)
        env.update(config.to_env())
        env["ELASTICDL_WORKER_ID"] = worker_id
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        log = open(tmp_path / f"{worker_id}.log", "w")
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        return subprocess.Popen(
            [_sys.executable, "-m", "elasticdl_tpu.worker.main"],
            env=env, stdout=log, stderr=subprocess.STDOUT, cwd=repo,
        )

    def _log_tail(w):
        return open(tmp_path / f"{w}.log").read()[-3000:]

    procs = {}
    relaunches = {"n": 0}
    try:
        procs.update({w: _spawn(w) for w in ("w-a", "w-b")})
        deadline = time.time() + 420
        while time.time() < deadline:
            if servicer.JobStatus({})["finished"]:
                break
            for w, p in list(procs.items()):
                rc = p.poll()
                if rc is None:
                    continue
                if rc == 0:
                    procs.pop(w)
                    continue
                fatal = (
                    "JAX distributed service detected fatal errors"
                    in _log_tail(w)
                )
                if rc == RESTART_EXIT_CODE or fatal:
                    assert relaunches["n"] < 8, (
                        f"{w} restart churn; log:\n" + _log_tail(w)
                    )
                    relaunches["n"] += 1
                    procs[w] = _spawn(w)
                else:
                    pytest.fail(f"{w} exited rc={rc}; log:\n" + _log_tail(w))
            time.sleep(0.5)
        status = servicer.JobStatus({})
        assert status["finished"], (
            f"job did not finish: {status}; logs:\n"
            + "".join(_log_tail(w) for w in ("w-a", "w-b"))
        )
        # The proof is only multi-process if the world really reached 2.
        assert max_world["n"] == 2, f"world never formed (max {max_world})"
        # Both shards served pulls and took pushes: rows materialized.
        sizes = []
        for s in ps_servers:
            meta, _ = s._stats({}, {})
            sizes.append(meta["tables"][list(spec.host_io)[0]])
        assert all(n > 0 for n in sizes), f"shard sizes {sizes}"
        # Rank 0's final checkpoint fanned a Save out: per-shard files exist.
        root = tmp_path / "ckpt" / "host_stores"
        steps = sorted(os.listdir(root), key=int)
        assert steps, "no PS snapshot written"
        key = list(spec.host_io)[0]
        for s in range(2):
            assert (root / steps[-1] / snapshot_filename(key, s, 2)).exists()
    finally:
        stop.set()
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGKILL)
        for s in ps_servers:
            s.stop()
        server.stop()


@needs_native
@pytest.mark.slow
def test_ps_pod_crash_relaunch_restores_and_job_finishes(tmp_path):
    """Chaos: SIGKILL a PS shard mid-job.  The master's relaunch policy
    restarts it on the SAME port, the relaunched pod restores its slice from
    the newest snapshot (ps/main.py), the workers' RemoteEmbeddingStore
    retry bridges the outage, and the job drains to completion."""
    import signal
    import sys as _sys
    import threading
    import time

    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.main import Master
    from elasticdl_tpu.master.pod_manager import ProcessPodBackend

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker_entry = tmp_path / "worker_entry.py"
    worker_entry.write_text(WORKER_PY.format(repo=repo))
    ps_entry = tmp_path / "ps_entry.py"
    ps_entry.write_text(PS_PY.format(repo=repo))

    data = str(tmp_path / "criteo.rio")
    generate("criteo", data, 128)
    config = JobConfig(
        job_name="pschaos",
        model_def="deepfm.model_spec",
        model_params=(
            'buckets_per_feature=64;embedding_dim=8;hidden=[16];'
            'host_tier=true;compute_dtype="float32"'
        ),
        distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
        training_data=data,
        minibatch_size=16,
        num_minibatches_per_task=1,
        num_workers=1,
        num_ps_pods=1,
        num_epochs=3,
        checkpoint_steps=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
        max_worker_relaunch=4,
    )
    ps_backend = ProcessPodBackend(argv=[_sys.executable, str(ps_entry)])
    master = Master(
        config,
        pod_backend=ProcessPodBackend(argv=[_sys.executable, str(worker_entry)]),
        ps_backend=ps_backend,
    )
    result = {}

    def run():
        result["status"] = master.run(poll_interval_s=0.1)

    t = threading.Thread(target=run, daemon=True)
    t.start()
    try:
        # Wait for the first host-store snapshot, then kill the PS shard.
        root = tmp_path / "ckpt" / "host_stores"
        deadline = time.time() + 120
        while time.time() < deadline and not (
            root.exists() and os.listdir(root)
        ):
            time.sleep(0.2)
        assert root.exists() and os.listdir(root), "no snapshot before kill"
        pid = ps_backend.pid("pschaos-ps-0")
        assert pid is not None, "PS pod not running"
        os.kill(pid, signal.SIGKILL)

        t.join(timeout=240)
        assert not t.is_alive(), "job did not finish after PS crash"
        assert result["status"]["finished"], result["status"]
        assert result["status"]["done"] == 24  # 8 tasks x 3 epochs
        # The relaunched shard really is a second generation of the slot.
        relaunched = master.ps_manager.pod_info("pschaos-ps-0-r1")
        assert relaunched is not None, "PS pod was not relaunched"
    finally:
        master.shutdown()


def test_parse_ps_addresses():
    assert parse_ps_addresses("a:1, b:2 ,,c:3") == ["a:1", "b:2", "c:3"]
    assert parse_ps_addresses("") == []


@needs_native
def test_multiprocess_host_tier_without_ps_raises(devices, monkeypatch):
    """Multi-process mesh + host tables + no PS fleet is the one illegal
    layout (each process would train divergent row copies): the constructor
    refuses with a message pointing at --num_ps_pods.  With ps_addresses
    set, the same construction succeeds with remote stores."""
    import elasticdl_tpu.parallel.trainer as trainer_mod
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh

    spec = load_model_spec(
        "elasticdl_tpu.models", "deepfm.model_spec",
        buckets_per_feature=64, embedding_dim=8, hidden=(16,),
        host_tier=True, compute_dtype="float32",
    )
    mesh = create_mesh(devices[:2])
    monkeypatch.setattr(trainer_mod, "_process_count", lambda m: 2)
    with pytest.raises(NotImplementedError, match="num_ps_pods"):
        trainer_mod.Trainer(
            spec,
            JobConfig(
                distribution_strategy=DistributionStrategy.PARAMETER_SERVER
            ),
            mesh,
        )
    server = PSServer(spec.host_io, shard=0, num_shards=1).start()
    try:
        t = trainer_mod.Trainer(
            spec,
            JobConfig(
                distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
                ps_addresses=server.address,
            ),
            mesh,
        )
        assert t._remote_ps
    finally:
        server.stop()


@needs_native
def test_concurrent_pulls_correct_under_contention(one_shard):
    """Per-table RW locking: many reader threads pulling EXISTING rows run
    concurrently with a pusher mutating other rows; every pull must return
    internally consistent rows (the pre-r4 global mutex made this trivially
    true but serialized the executor — this pins correctness of the
    concurrent path)."""
    import threading

    from elasticdl_tpu.ps.host_store import HostEmbeddingStore

    _, remote = one_shard
    read_ids = np.arange(0, 256, dtype=np.int64)
    write_ids = np.arange(1000, 1256, dtype=np.int64)
    baseline = remote.pull(read_ids)  # materialize the read set

    stop = threading.Event()
    errors = []

    def reader():
        try:
            while not stop.is_set():
                rows = remote.pull(read_ids)
                # read rows are NEVER pushed to: must equal their init values
                np.testing.assert_array_equal(rows, baseline)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=reader) for _ in range(4)]
    for t in threads:
        t.start()
    local = HostEmbeddingStore(
        dim=IO.dim, optimizer=IO.optimizer, learning_rate=IO.learning_rate,
        init_scale=IO.init_scale,
    )
    rng = np.random.RandomState(7)
    for _ in range(30):
        g = rng.randn(write_ids.size, IO.dim).astype(np.float32)
        remote.push_grad(write_ids, g)
        local.push_grad(write_ids, g)
    stop.set()
    for t in threads:
        t.join()
    assert not errors, errors[0]
    np.testing.assert_array_equal(remote.pull(write_ids), local.pull(write_ids))


@needs_native
def test_stats_reports_restored_step(tmp_path):
    """Stats surfaces restored_step; RemoteEmbeddingStore.restored_steps
    collects it fleet-wide (the torn-fleet guard's wire half)."""
    server = PSServer({"t": IO}, shard=0, num_shards=1).start()
    store = RemoteEmbeddingStore("t", IO.dim, [server.address])
    store.wait_ready()
    try:
        assert store.restored_steps() == [None]
        store.pull(np.arange(8, dtype=np.int64))
        store.save_snapshot(str(tmp_path), step=12)
        store.load_snapshot(str(tmp_path), step=12)
        assert store.restored_steps() == [12]
    finally:
        store.close()
        server.stop()


@needs_native
def test_eval_job_fails_loud_on_fresh_or_divergent_ps_fleet(tmp_path, devices):
    """ADVICE r3 medium: an evaluation job must refuse a PS fleet that
    restored nothing (fresh rows) or restored DIVERGENT steps; a training
    job error-logs and continues."""
    import jax

    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    servers = [
        PSServer({"__host__fm_table": IO}, shard=s, num_shards=2).start()
        for s in range(2)
    ]
    addrs = ",".join(s.address for s in servers)
    try:
        def make_trainer(job_type):
            spec = load_model_spec(
                "elasticdl_tpu.models",
                "deepfm.model_spec",
                buckets_per_feature=64,
                embedding_dim=IO.dim - 1,
                hidden=(8,),
                host_tier=True,
            )
            config = JobConfig(
                distribution_strategy=DistributionStrategy.PARAMETER_SERVER,
                job_type=job_type,
                ps_addresses=addrs,
            )
            return Trainer(spec, config, create_mesh(devices[:1]))

        # Fresh fleet: evaluation refuses, training proceeds.
        with pytest.raises(RuntimeError, match="no PS shard restored"):
            make_trainer("evaluation").restore_host_stores(str(tmp_path), 5)
        assert make_trainer("training").restore_host_stores(str(tmp_path), 5)

        # Divergent fleet: save a snapshot, then make only shard 0 load it.
        store = RemoteEmbeddingStore(
            "__host__fm_table", IO.dim, [s.address for s in servers]
        )
        store.wait_ready()
        store.pull(np.arange(32, dtype=np.int64))
        store.save_snapshot(str(tmp_path), step=7)
        servers[0]._load(
            {"directory": str(tmp_path), "step": 7, "strict": True}, {}
        )
        store.close()
        with pytest.raises(RuntimeError, match="divergent"):
            make_trainer("evaluation").restore_host_stores(str(tmp_path), 7)
        assert make_trainer("training").restore_host_stores(str(tmp_path), 7)
    finally:
        for s in servers:
            s.stop()
