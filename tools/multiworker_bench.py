"""Multi-worker control-plane bound (VERDICT r4 Weak #6 / Next #7).

The reference's sustained-throughput story is many workers sharing one
master; tools/bench_e2e.py measures a single in-process worker.  This tool
bounds what the CONTROL PLANE (task dispatch, result reporting, rendezvous
heartbeats, the RPC server itself) costs per worker as real worker
processes are added — on the CPU harness, so the accelerator never gates.

Method: a deliberately task-bound job — tiny model, one minibatch per task,
hundreds of tasks — so wall-clock is dominated by GetTask/ReportTaskResult
round-trips, not math.  Run the same job at fleet sizes 1/2/4 real worker
subprocesses against one embedded RPC master; report aggregate and
per-worker task rates and the scaling efficiency vs the 1-worker figure.
If the master's hot loop (SURVEY §3.2) serializes, efficiency collapses as
workers are added; numbers near 1.0 bound the per-worker overhead at
(1/rate) per task.

Two modes:

- ``--mode control`` (default): the r5 task-bound job above — the per-task
  RPC overhead bound.
- ``--mode ingest`` (r6): gang-mode INGEST e2e.  A lockstep gang of real
  worker processes (``multihost=True``, one jax.distributed world) trains
  criteo recordio through the full worker path — bulk C++ read, criteo
  decode, prefetch, fused scan, prep-ahead pipelining (group-eligible
  since r6) — and the number is examples/sec through the gang, with the
  workers' phase decomposition (common/metrics.py PhaseTimers) attached.
  The control mode deliberately starves the data path; this mode is the
  one that can see gang-mode ingest regressions at all.

Writes ONE JSON artifact per mode (the number of record — docs/perf.md
quotes the file): ``artifacts/multiworker_r05.json`` /
``artifacts/gang_ingest_r09.json`` by default.

CPU harness only: fleets of 2+ worker processes on one TPU host would
fight for the same chips (one process per chip), so there is no chip mode.

Usage: python tools/multiworker_bench.py [--mode control|ingest]
           [--fleets 1,2,4] [--tasks 96]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# FORCE cpu (not setdefault): this is a CPU harness, and an inherited
# JAX_PLATFORMS (or none, on a TPU host) would aim it at the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=1")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_fleet(n_workers: int, n_tasks: int, tmp: str, log) -> dict:
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher

    mb = 16
    path = os.path.join(tmp, "mw.rio")
    if not os.path.exists(path):
        generate("mnist", path, mb * n_tasks)
    shards = create_data_reader(path).create_shards(mb)

    dispatcher = TaskDispatcher(shards, num_epochs=1)
    rendezvous = RendezvousServer(heartbeat_timeout_s=30.0)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()

    # Per-worker ReportTaskResult timestamps via a servicer wrapper thread?
    # Simpler: poll JobStatus; per-worker split comes from task ownership.
    config = JobConfig(
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=mb,
        num_minibatches_per_task=1,
        num_epochs=1,
        master_addr=server.address,
        prefetch_depth=0,       # decode cost ~0; keep the loop RPC-bound
        fused_task_scan=False,  # per-step dispatch = max control-plane load
        checkpoint_steps=0,
    )
    env_base = dict(os.environ)
    env_base.update(config.to_env())
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    # Shared compile cache: the jitted step compiles once, every process
    # loads it — measurement starts after a warmup barrier anyway.
    env_base["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "jax_cache")

    procs = []
    logs = []
    t0 = time.perf_counter()
    for i in range(n_workers):
        env = dict(env_base)
        env["ELASTICDL_WORKER_ID"] = f"mw-{n_workers}-{i}"
        lf = open(os.path.join(tmp, f"mw{n_workers}_{i}.log"), "w")
        logs.append(lf)
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.worker.main"],
            env=env, stdout=lf, stderr=subprocess.STDOUT, cwd=_REPO_ROOT,
        ))
    # Warmup window: exclude process boot + compile from the rate by
    # timestamping from the FIRST completed task to the LAST.
    first_done = None
    deadline = time.time() + 600
    while time.time() < deadline:
        status = servicer.JobStatus({})
        if first_done is None and status["done"] > 0:
            first_done = (time.perf_counter(), status["done"])
        if status["finished"]:
            break
        time.sleep(0.05)
    t_end = time.perf_counter()
    status = servicer.JobStatus({})
    for p in procs:
        try:
            p.wait(timeout=30)
        except subprocess.TimeoutExpired:
            p.kill()
    for lf in logs:
        lf.close()
    server.stop()
    if not status["finished"]:
        raise RuntimeError(
            f"fleet {n_workers}: job not finished ({status['done']} tasks)"
        )
    t_first, done_at_first = first_done
    measured_tasks = status["done"] - done_at_first
    elapsed = t_end - t_first
    if measured_tasks <= 0 or elapsed <= 0:
        # Job finished within the first-done poll window (tiny --tasks):
        # fall back to the boot-inclusive rate rather than reporting 0 and
        # poisoning the retention baseline (review r5).
        measured_tasks = status["done"]
        elapsed = t_end - t0
    rate = measured_tasks / elapsed
    out = {
        "workers": n_workers,
        "tasks_total": status["done"],
        "tasks_measured": measured_tasks,
        "elapsed_s": round(elapsed, 3),
        "tasks_per_sec": round(rate, 2),
        "tasks_per_sec_per_worker": round(rate / n_workers, 2),
        "wall_total_s": round(t_end - t0, 2),
    }
    log(f"fleet {n_workers}: {out}")
    return out


# ---------------------------------------------------------------------------
# ingest mode: gang-mode ingest e2e (r6)
# ---------------------------------------------------------------------------

_INGEST_MB = 2048
_INGEST_MB_PER_TASK = 4
_INGEST_RECORDS_PER_TASK = _INGEST_MB * _INGEST_MB_PER_TASK


def _run_ingest_fleet(
    n_workers: int, n_tasks: int, tmp: str, log, trace_dump_raw: str = "",
) -> dict:
    """One lockstep gang of ``n_workers`` REAL worker processes training
    criteo recordio end to end; returns examples/sec through the gang plus
    the workers' phase decomposition.

    ``trace_dump_raw``: enable grafttrace on every process (workers via the
    config bus, the embedded master in-process) and save the raw DumpTrace
    response there after the job finishes — the supply side of
    tools/straggler_report.py's gang analysis."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.data.synthetic import synthetic_criteo
    from elasticdl_tpu.master.rendezvous import RendezvousServer
    from elasticdl_tpu.common.platform import free_port
    from elasticdl_tpu.master.servicer import MasterServer, MasterServicer
    from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
    from elasticdl_tpu.worker.worker import RESTART_EXIT_CODE

    path = os.path.join(tmp, "gang_criteo.rio")
    file_tasks = 4
    if not os.path.exists(path):
        synthetic_criteo(
            path, _INGEST_RECORDS_PER_TASK * file_tasks, seed=11,
            container="recordio",
        )
    reader = create_data_reader(path)
    shards = reader.create_shards(_INGEST_RECORDS_PER_TASK)
    epochs = -(-n_tasks // file_tasks)  # ceil

    dispatcher = TaskDispatcher(shards, num_epochs=epochs)
    rendezvous = RendezvousServer(heartbeat_timeout_s=60.0)
    # Symmetric gang formation: settle only once every member of the full
    # fleet has registered — an incumbent/joiner split would spend the
    # measurement window on membership restarts instead of ingest.
    rendezvous.set_expected(n_workers)
    servicer = MasterServicer(dispatcher, rendezvous=rendezvous)
    server = MasterServer(servicer, port=0).start()

    # The gang-ingest parity config: the full r6 hot path — fused scan,
    # task pipelining, prep-ahead (all group-eligible now) — on a modest
    # CPU-compilable DeepFM.  AllReduce: dense device tables, no host tier,
    # so prep-ahead stays eligible (host_io pins prep to the main thread).
    config = JobConfig(
        model_def="deepfm.model_spec",
        model_params="buckets_per_feature=4096;embedding_dim=4;"
                     "hidden=[64,64];compute_dtype=float32",
        distribution_strategy="AllReduce",
        training_data=path,
        minibatch_size=_INGEST_MB,
        num_minibatches_per_task=_INGEST_MB_PER_TASK,
        num_epochs=epochs,
        master_addr=server.address,
        multihost=n_workers > 1,
        coordinator_port=free_port(),
        fused_task_scan=True,
        task_pipelining=True,
        checkpoint_steps=0,  # checkpoint wire has its own instrument
        distributed_heartbeat_timeout_s=100.0,
        trace=bool(trace_dump_raw),
    )
    if trace_dump_raw:
        # The embedded master's own spans (rpc.server, lease lifecycle)
        # join the dump; workers enable via the config env bus.
        from elasticdl_tpu.common import trace as _trace

        _trace.configure(enabled=True)
    env_base = dict(os.environ)
    env_base.update(config.to_env())
    env_base["JAX_PLATFORMS"] = "cpu"
    env_base["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env_base["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "jax_cache")

    def _spawn(i: int):
        env = dict(env_base)
        env["ELASTICDL_WORKER_ID"] = f"gi-{n_workers}-{i}"
        lf = open(os.path.join(tmp, f"gi{n_workers}_{i}.log"), "a")
        p = subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.worker.main"],
            env=env, stdout=lf, stderr=subprocess.STDOUT, cwd=_REPO_ROOT,
        )
        lf.close()
        return p

    procs = {i: _spawn(i) for i in range(n_workers)}
    fail_budget = {i: 3 for i in range(n_workers)}
    t0 = time.perf_counter()
    first_done = None
    phase_times: dict = {}
    deadline = time.time() + 1200
    finished = False
    try:
        while time.time() < deadline:
            status = servicer.JobStatus({})
            if first_done is None and status["done"] > 0:
                first_done = (time.perf_counter(), status["done"])
            if status.get("phase_times"):
                phase_times = status["phase_times"]
            if status["finished"]:
                finished = True
                break
            for i, p in list(procs.items()):
                rc = p.poll()
                if rc is None:
                    continue
                if rc == RESTART_EXIT_CODE:
                    # Membership churn (a peer registering mid-boot): the
                    # gang contract IS restart-to-resync; relaunch
                    # budget-free, exactly as the PodManager does.
                    procs[i] = _spawn(i)
                    continue
                # Any other exit mirrors the PodManager's FAILED policy:
                # relaunch while the slot's budget lasts.  The expected
                # shape here is the coordination-runtime SIGABRT a survivor
                # takes when the gang LEADER restarts mid-formation (its
                # PJRT client hard-exits on the closed coordinator socket)
                # — churn the production pod flow absorbs, not a bench
                # failure.
                fail_budget[i] -= 1
                tail = ""
                lp = os.path.join(tmp, f"gi{n_workers}_{i}.log")
                if os.path.exists(lp):
                    tail = open(lp).read()[-2000:]
                if fail_budget[i] < 0:
                    raise RuntimeError(
                        f"gang worker {i} exited rc={rc} with relaunch "
                        f"budget exhausted; log tail:\n{tail}"
                    )
                log(
                    f"gang worker {i} exited rc={rc} "
                    f"(budget {fail_budget[i]} left); relaunching"
                )
                procs[i] = _spawn(i)
            time.sleep(0.1)
        t_end = time.perf_counter()
        status = servicer.JobStatus({})
    finally:
        # Runs on the raise paths too: surviving gang members (wedged on a
        # dead peer) and the master server must not outlive the fleet run.
        for p in procs.values():
            if finished:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
            elif p.poll() is None:
                p.kill()
        if finished and trace_dump_raw:
            # After the workers exited (their job-end trace tails shipped
            # on the final heartbeats) and before the server goes away.
            try:
                with open(trace_dump_raw, "w") as f:
                    json.dump(servicer.DumpTrace({}), f)
                log(f"raw trace dump -> {trace_dump_raw}")
            except Exception as e:  # a failed dump must not fail the bench
                log(f"trace dump failed: {e}")
        server.stop()
    if not finished:
        raise RuntimeError(
            f"gang fleet {n_workers}: job not finished "
            f"({status['done']} tasks done)"
        )
    if first_done is not None:
        t_first, done_at_first = first_done
    else:
        t_first, done_at_first = t0, 0
    measured_tasks = status["done"] - done_at_first
    elapsed = t_end - t_first
    if measured_tasks <= 0 or elapsed <= 0:
        measured_tasks, elapsed = status["done"], t_end - t0
    eps = measured_tasks * _INGEST_RECORDS_PER_TASK / elapsed
    out = {
        "workers": n_workers,
        "group_mode": n_workers > 1,
        "tasks_total": status["done"],
        "tasks_measured": measured_tasks,
        "records_per_task": _INGEST_RECORDS_PER_TASK,
        "elapsed_s": round(elapsed, 3),
        "examples_per_sec": round(eps),
        "wall_total_s": round(t_end - t0, 2),
        # Cumulative per-worker phase split (prep_wait/dispatch/step_wait/
        # metrics/checkpoint/control) — the ingest number's decomposition.
        "phase_times": phase_times,
    }
    log(f"ingest fleet {n_workers}: {out}")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=("control", "ingest"), default="control")
    ap.add_argument("--fleets", default="")
    ap.add_argument("--tasks", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    import tempfile

    log = lambda m: print(f"[mw] {m}", file=sys.stderr, flush=True)
    tmp = tempfile.mkdtemp(prefix="mw_bench_")

    if args.mode == "ingest":
        fleets = [int(x) for x in (args.fleets or "1,2").split(",")]
        n_tasks = args.tasks or 12
        results = [
            _run_ingest_fleet(n, n_tasks, tmp, log)
            for n in fleets
        ]
        artifact = {
            "metric": "gang_ingest_e2e_examples_per_sec",
            "unit": "examples/sec",
            "harness": (
                f"cpu ({os.cpu_count()} core host), 1 fake device per "
                "worker process, real jax.distributed gang"
            ),
            "config": "deepfm AllReduce, criteo recordio via C++ bulk "
                      "read + decode, fused scan + task pipelining + "
                      "prep-ahead (group-eligible since r6)",
            "fleets": results,
            "note": "group-mode ingest was unmeasurable before r6 (the "
                    "control-plane mode deliberately starves the data "
                    "path); examples/sec is gang-aggregate — lockstep "
                    "peers train the SAME tasks collectively, so the "
                    "figure does not scale with fleet size, it must "
                    "HOLD as the gang grows",
        }
        # Pipeline shape (r9): the workers run JobConfig defaults for the
        # ingest/prep/lease knobs; numbers are only comparable at equal
        # shape (same rule as bench.py's record guard).
        from elasticdl_tpu.common.config import JobConfig
        from elasticdl_tpu.data.ingest_pool import resolve_threads

        _cfg = JobConfig()
        artifact["pipeline"] = {
            "ingest_threads": resolve_threads(_cfg.ingest_threads),
            "prep_depth": _cfg.prep_depth,
            "lease_batch": _cfg.lease_batch,
            "optimizer_sharding": _cfg.optimizer_sharding,
            "donate_train_state": _cfg.donate_train_state,
        }
        from tools.artifact import write_artifact

        write_artifact(
            artifact, "gang_ingest_r09.json", env_var="GANG_INGEST_OUT",
            path=args.out or None, log=log,
        )
        print(json.dumps(artifact["fleets"]), flush=True)
        return

    fleets = [int(x) for x in (args.fleets or "1,2,4").split(",")]
    results = [_run_fleet(n, args.tasks or 96, tmp, log) for n in fleets]
    # On this 1-core host every worker shares the CPU, so per-worker rate
    # falls ~1/N by CONTENTION alone; the control-plane bound is how much
    # of the AGGREGATE rate survives as workers multiply — a serializing
    # master would drop it, a clean one holds it flat.
    base = results[0]["tasks_per_sec"]
    for r in results:
        r["aggregate_retention_vs_1w"] = round(r["tasks_per_sec"] / base, 3)
    worst = min(r["aggregate_retention_vs_1w"] for r in results)
    artifact = {
        "metric": "control_plane_task_rate",
        "unit": "tasks/sec",
        "harness": f"cpu ({os.cpu_count()} core host), 1 fake device per "
                   "worker, task-bound job (1 minibatch of 16 per task)",
        "fleets": results,
        "control_plane_overhead_bound_pct": round((1 - worst) * 100, 1),
        "note": "per-step dispatch + prefetch off: every task is pure "
                "GetTask/feed/step/ReportTaskResult; aggregate retention "
                "~1.0 = the master adds no per-worker serialization at "
                "this scale (per-worker division is meaningless under "
                "full CPU sharing)",
    }
    from tools.artifact import write_artifact

    write_artifact(
        artifact, "multiworker_r05.json", path=args.out or None, log=log
    )
    print(json.dumps(artifact["fleets"]), flush=True)


if __name__ == "__main__":
    main()
