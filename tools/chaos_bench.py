"""chaos_bench — recovery time and goodput-under-churn as numbers of record.

ROADMAP item 3 wants tail tolerance stamped "as first-class perf numbers
alongside examples/sec".  This tool drives REAL multi-worker jobs through
the full master stack (Master -> PodManager -> ProcessPodBackend worker
subprocesses, warm standby on) under a graftchaos fault plan
(chaos/inject.py), and stamps ``artifacts/CHAOS_r13.json`` with:

- **recovery_time_ms**, decomposed over the master-clock splice timeline:
  ``elastic:splice`` stage=detect (the pod watcher saw the death) ->
  stage=adopt (a warm spare took the identity) -> ``elastic:reformed``
  (every member confirmed the new membership) -> the first successful
  ``lease:report`` after the fault (trained-again).  All four instants are
  emitted IN the master process, so no cross-process clock alignment can
  blur the decomposition.
- **goodput-under-churn**: examples/sec of the faulted run divided by the
  fault-free baseline at identical shape (same data, fleet, pipeline).
- **skip accounting**: the dispatcher's per-task skip counts and the
  servicer's per-rank deadline skips (--gang_deadline_ms).
- **zero-double-train**: done == expected tasks, zero rejected late
  SUCCESS reports (TaskDispatcher's duplicate_done counter), zero
  abandoned — the explicit exactly-once check, not an assumption.

Fleets (CPU harness — chaos is a control-plane property; the fault paths
exercised are identical on chip).  Each faulted fleet has a SHAPE-MATCHED
baseline (same data, model, workers, pipeline) as its goodput
denominator:

    baseline_pool / kill    2 independent (non-gang) deepfm workers
                            sharing the dispatcher; chaos kills one
                            mid-job and the warm standby splices the
                            replacement in (worker= addressing, so the
                            relaunched incarnation cannot re-kill
                            itself).  Both share one compile cache — the
                            baseline warms it, so the kill fleet's churn
                            wall measures recovery, not XLA.
    baseline_gang / stall   a 2-rank mnist lockstep gang; chaos stalls
                            worker 0 mid-job far past --gang_deadline_ms
                            AND blacks out its RPCs from the same step.
                            The boundary skips the straggler (gang:skip,
                            skip-accounted requeue, eviction); the
                            blackout means the evicted rank can neither
                            heartbeat its way back into membership nor
                            death-push itself into a RESTART relaunch,
                            and max_worker_relaunch=0 keeps its slot
                            down — so the survivor death-pushes out of
                            the wedged collective, settles past the
                            15 s gate into a world of ONE, and drains
                            the log solo.

The stall fleet's shape is deliberate: on this box a RE-FORMED 2-process
jax.distributed world dies of timing-sensitive heap corruption in
jaxlib/gloo at its first post-(re)compile collective dispatch (the @slow
test_multihost reform churn noted since CHANGES r8 — model-independent,
worst with deepfm's embedding host paths), so any design where recovery
means "form a second multi-process world" would stamp that box flake as
recovery time.  Skip-then-degrade-to-solo needs NO second gang: initial
2-rank mnist formation is the reliably-passing tier-1 configuration, and
everything after the skip is single-process.  Gang fleets use PRIVATE
per-fleet compile caches (no world ever starts on another world's cached
collective executables — the corruption's most reliable trigger);
pool fleets share one.  Exactly-once accounting holds through all of it
either way (that is the point).

Usage:
    python tools/chaos_bench.py [--workers 2] [--tasks 8] [--fleets ...]
    python tools/chaos_bench.py --smoke     # tiny 1-worker kill+recover
                                            # (bench_all --chaos-smoke)
    python tools/chaos_bench.py --masterfail        # r18 master-kill
                                            # fleet -> MASTERFAIL_r18.json
    python tools/chaos_bench.py --masterfail-smoke  # 1-worker master
                                            # kill+restart CI check
                                            # (bench_all --masterfail-smoke)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# FORCE cpu (the multiworker_bench stance): this harness spawns several
# worker processes at once, which on a TPU host would fight for the chips
# (one process per chip), and the master is jax-free.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ARTIFACT_NAME = "CHAOS_r13.json"

#: r18 master-kill survivability artifact (``--masterfail``): the master
#: process is chaos-killed mid-job (kill:target=master,step=N fires in
#: the servicer AFTER a report is applied+journaled), the worker fleet
#: rides the outage out on the proxy reconnect WITHOUT relaunch, a fresh
#: master process replays the journal, adopts the orphan pods, and the
#: job completes exactly-once.  Decomposition on wall-anchored trace
#: instants: kill -> restart spawn -> master:replay -> worker:reconnect
#: -> first post-restart lease:handout.
MASTERFAIL_ARTIFACT = "MASTERFAIL_r18.json"

_MB = 1024
_MB_PER_TASK = 2
_RECORDS_PER_TASK = _MB * _MB_PER_TASK

#: Hard wall bound per fleet: a wedged chaos run must fail loud, not hang
#: the battery (the whole point of the subsystem is bounded tails).
FLEET_TIMEOUT_S = 900.0


def _splice_timeline(events: List[dict]) -> dict:
    """Recovery decomposition from the master-clock instants (see module
    docstring).  Returns {} when no fault was detected (baseline)."""
    detect = adopt = reformed = skip = skip_trained = None
    survivor_trained = replacement_trained = None
    relaunch = None
    for e in sorted(events, key=lambda e: e.get("ts") or 0):
        ts = e.get("ts")
        if not isinstance(ts, (int, float)):
            continue
        name = e.get("name")
        args = e.get("args") or {}
        if name == "gang:skip" and skip is None:
            skip = ts
        elif skip is not None and skip_trained is None and (
            name == "lease:report" and args.get("success")
        ):
            skip_trained = ts
        if name == "elastic:splice" and args.get("stage") == "detect":
            if detect is None:
                detect = ts
                relaunch = args.get("relaunch")
        elif detect is not None and adopt is None and (
            name == "elastic:splice" and args.get("stage") == "adopt"
        ):
            adopt = ts
        elif detect is not None and reformed is None and (
            name == "elastic:reformed"
        ):
            reformed = ts
        elif detect is not None and (
            name == "lease:report" and args.get("success")
        ):
            # Two distinct recoveries: the POOL keeps making progress (any
            # worker's next success — continuity), and the LOST CAPACITY
            # comes back (the spliced replacement's first success — the
            # recovery_time the artifact headlines).
            if survivor_trained is None:
                survivor_trained = ts
            if replacement_trained is None and relaunch and (
                args.get("worker") == relaunch
            ):
                replacement_trained = ts
    if skip is not None and (detect is None or skip <= detect):
        # Deadline-skip fleets: the straggler is EVICTED, never a FAILED
        # pod, so the timeline anchors on the gang:skip instant.  The
        # anchor is whichever fired FIRST — a skip fleet's severed
        # straggler is killed at teardown, and that post-job FAILED
        # detect is noise, not recovery (stamped as late_detect_ms so
        # the artifact shows it was seen and excluded).
        out = {"detected": detect is not None, "skipped": True}
        if skip_trained is not None:
            out["skip_to_trained_ms"] = round((skip_trained - skip) / 1e3, 1)
        if detect is not None:
            out["late_detect_ms"] = round((detect - skip) / 1e3, 1)
        return out
    if detect is None:
        return {}
    out = {"detected": True}
    if adopt is not None:
        out["detect_to_adopt_ms"] = round((adopt - detect) / 1e3, 1)
    if reformed is not None:
        out["detect_to_reformed_ms"] = round((reformed - detect) / 1e3, 1)
        if adopt is not None:
            out["adopt_to_reformed_ms"] = round((reformed - adopt) / 1e3, 1)
    if survivor_trained is not None:
        out["survivor_trained_ms"] = round(
            (survivor_trained - detect) / 1e3, 1
        )
    if replacement_trained is not None:
        out["recovery_time_ms"] = round(
            (replacement_trained - detect) / 1e3, 1
        )
        if reformed is not None:
            out["reformed_to_trained_ms"] = round(
                (replacement_trained - reformed) / 1e3, 1
            )
    return out


def _chaos_event_counts(dump: dict, pod_log_dir: str = "") -> Dict[str, int]:
    """The injection audit — a chaos artifact whose faults never fired
    measures nothing.  Two channels: chaos:*/gang:skip instants across
    every shipped trace buffer, and ``[graftchaos]`` stderr lines in the
    pod logs (``log:<kind>`` keys) — the only evidence a SEVERED process
    leaves: a kill's ring dies with it, and a drop_rpc blackout cuts the
    heartbeat channel its ring would have shipped over."""
    counts: Dict[str, int] = {}
    buffers = [dump.get("master_events") or []]
    for proc in (dump.get("processes") or {}).values():
        buffers.append(proc.get("events") or [])
    for events in buffers:
        for e in events:
            name = e.get("name", "")
            if name.startswith("chaos:") or name == "gang:skip":
                counts[name] = counts.get(name, 0) + 1
    if pod_log_dir and os.path.isdir(pod_log_dir):
        for fn in os.listdir(pod_log_dir):
            if not fn.endswith(".log"):
                continue
            try:
                with open(os.path.join(pod_log_dir, fn)) as f:
                    for line in f:
                        if line.startswith("[graftchaos] "):
                            kind = line.split()[1]
                            key = f"log:{kind}"
                            counts[key] = counts.get(key, 0) + 1
            except OSError:
                pass
    return counts


def _scrape_loop(address: str, stop, box: dict) -> None:
    """Poll ``address``'s /metrics once a second until ``stop``; bank the
    newest parsed snapshot (scalar edl_* families flattened to
    name{labels} -> value) plus ok/failed tallies.  Runs while the fleet
    is faulted ON PURPOSE: a scrape that only works on a healthy job
    proves nothing."""
    from tools.watch_job import fetch

    while not stop.is_set():
        try:
            families = fetch(address, timeout_s=2.0)
        except Exception as e:  # noqa: BLE001 — tallied; the job goes on
            box["scrapes_failed"] = box.get("scrapes_failed", 0) + 1
            box["last_error"] = f"{type(e).__name__}: {e}"
        else:
            flat = {}
            for name, fam in sorted(families.items()):
                if not name.startswith("edl_") or fam.get("type") == "histogram":
                    continue
                for s in fam["samples"]:
                    labels = ",".join(
                        f"{k}={v}" for k, v in sorted(s["labels"].items())
                    )
                    flat[f"{name}{{{labels}}}" if labels else name] = s["value"]
            box["snapshot"] = flat
            box["scrapes_ok"] = box.get("scrapes_ok", 0) + 1
        stop.wait(1.0)


def run_fleet(
    n_workers: int,
    n_tasks: int,
    tmp: str,
    log,
    label: str,
    chaos: str = "",
    warm_standby: bool = False,
    gang_deadline_ms: float = 0.0,
    model: str = "deepfm",
    multihost: bool = False,
    timeout_s: float = FLEET_TIMEOUT_S,
    cache: str = "shared",
    max_relaunch: int = 8,
) -> dict:
    """One job through the full master stack; returns goodput + accounting
    + the splice timeline (and leaves the raw dump beside the tmp data)."""
    from elasticdl_tpu.common import trace
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.common.platform import free_port
    from elasticdl_tpu.data.synthetic import generate, synthetic_criteo
    from elasticdl_tpu.master.main import Master

    if model == "deepfm":
        path = os.path.join(tmp, "chaos_criteo.rio")
        if not os.path.exists(path):
            synthetic_criteo(
                path, _RECORDS_PER_TASK * n_tasks, seed=13,
                container="recordio",
            )
        model_def = "deepfm.model_spec"
        model_params = (
            "buckets_per_feature=4096;embedding_dim=4;"
            "hidden=[64,64];compute_dtype=float32"
        )
        mb, mb_per_task = _MB, _MB_PER_TASK
    else:  # mnist: the smoke's cheap workload
        mb, mb_per_task = 16, 2
        path = os.path.join(tmp, "chaos_mnist.rio")
        if not os.path.exists(path):
            generate("mnist", path, mb * mb_per_task * n_tasks)
        model_def = "mnist.model_spec"
        model_params = "compute_dtype=float32"

    # Compile-cache policy (workers inherit the env).  Pool fleets SHARE
    # one cache — the baseline warms it, so the kill fleet's churn wall
    # measures recovery, not XLA.  Gang fleets each get a PRIVATE cache
    # (cache="fleet"): on this box a multi-process world that LOADS a
    # cached collective executable dies of heap corruption at its first
    # dispatch (the warm-cache face of the pre-existing CHANGES r8
    # multi-process flake), so no gang world may ever start on another
    # world's cache — each compiles its collectives exactly once, cold,
    # shape-matched with its baseline.
    if os.environ.get("CHAOS_NO_CACHE"):
        os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
    else:
        sub = "jax_cache" if cache == "shared" else f"jax_cache_{label}"
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, sub)
    config = JobConfig(
        job_name=f"chaos-{label}",
        model_def=model_def,
        model_params=model_params,
        distribution_strategy="AllReduce",
        training_data=path,
        minibatch_size=mb,
        num_minibatches_per_task=mb_per_task,
        num_epochs=1,
        num_workers=n_workers,
        multihost=multihost and n_workers > 1,
        coordinator_port=free_port(),
        distributed_heartbeat_timeout_s=100.0,
        # Relaunch headroom: an injected kill costs budget BY DESIGN, and
        # on this box a gang fleet's post-fault REFORMATION churns through
        # the known jaxlib segfault (module docstring) before converging
        # or degrading the world — the budget must outlast that.
        max_worker_relaunch=max_relaunch,
        warm_worker_standby=warm_standby,
        standby_pool=1,
        trace=True,
        chaos=chaos,
        gang_deadline_ms=gang_deadline_ms,
        checkpoint_steps=0,
        pod_log_dir=os.path.join(tmp, f"pods-{label}"),
        # graftgauge (r14): every process of the fleet serves /metrics on
        # an ephemeral port; the bench scrapes the MASTER's endpoint
        # mid-run (below) — the fleet-aggregated view must answer while a
        # fault is in flight, which is the whole claim.
        gauge_port=0,
    )
    # Isolate each fleet's trace window: the process recorder is global,
    # and a previous fleet's instants must not leak into this timeline.
    trace.configure(enabled=True)
    trace.default().clear()

    master = Master(config)
    result_box: dict = {}

    def _run():
        try:
            result_box["status"] = master.run()
        except Exception as e:  # surfaced after the join below
            result_box["error"] = e

    t0 = time.perf_counter()
    runner = threading.Thread(target=_run, name=f"chaos-{label}", daemon=True)
    runner.start()
    # Live mid-run scrape (r14): poll the master's /metrics every second
    # WHILE the fleet runs (including while a stall has the gang wedged —
    # the scrape server's daemon threads are the availability claim) and
    # keep the newest snapshot for the artifact.
    scrape_box: dict = {}
    scrape_stop = threading.Event()
    scraper = None
    if master.metrics_server is not None:
        scraper = threading.Thread(
            target=_scrape_loop,
            args=(master.metrics_server.address, scrape_stop, scrape_box),
            name=f"chaos-scrape-{label}", daemon=True,
        )
        scraper.start()
    runner.join(timeout=timeout_s)
    scrape_stop.set()
    if scraper is not None:
        scraper.join(timeout=5.0)
    wall = time.perf_counter() - t0
    if runner.is_alive():
        # The watchdog IS part of the experiment: a chaos run that wedges
        # has disproven the tolerance claim — tear down and fail loud.
        master.shutdown()
        runner.join(timeout=30)
        raise RuntimeError(
            f"chaos fleet {label!r} still running after {timeout_s:.0f}s "
            f"(workers={n_workers}, chaos={chaos!r})"
        )
    if "error" in result_box:
        raise RuntimeError(
            f"chaos fleet {label!r} failed: {result_box['error']}"
        ) from result_box["error"]
    status = result_box["status"]
    # The servicer outlives run() in-process: its banked worker buffers +
    # the master's own recorder are the timeline source.
    dump = master.servicer.DumpTrace({})
    with open(os.path.join(tmp, f"dump-{label}.json"), "w") as f:
        json.dump(dump, f)

    done = int(status.get("done", 0))
    eps = done * mb * mb_per_task / wall if wall > 0 else 0.0
    out = {
        "label": label,
        "workers": n_workers,
        "group_mode": bool(multihost and n_workers > 1),
        "chaos": chaos,
        "gang_deadline_ms": gang_deadline_ms,
        "warm_standby": warm_standby,
        "wall_s": round(wall, 2),
        "tasks_done": done,
        "tasks_expected": n_tasks,
        "examples_per_sec": round(eps, 1),
        "abandoned": int(status.get("abandoned", 0)),
        "skipped": int(status.get("skipped", 0)),
        "skip_counts": status.get("skip_counts") or {},
        "skipped_ranks": status.get("skipped_ranks") or {},
        "duplicate_done": int(status.get("duplicate_done", 0)),
        "chaos_events": _chaos_event_counts(
            dump, os.path.join(tmp, f"pods-{label}")
        ),
        # The newest mid-run scrape of the master's live endpoint: proof
        # the fleet view answered DURING the injected faults.
        "live_metrics": {
            "endpoint": (
                master.metrics_server.address
                if master.metrics_server is not None else None
            ),
            "scrapes_ok": scrape_box.get("scrapes_ok", 0),
            "scrapes_failed": scrape_box.get("scrapes_failed", 0),
            **(
                {"last_error": scrape_box["last_error"]}
                if "last_error" in scrape_box else {}
            ),
            "snapshot": scrape_box.get("snapshot") or {},
        },
        "recovery": _splice_timeline(dump.get("master_events") or []),
        # The explicit exactly-once verdict the artifact is judged on.
        "zero_double_train": (
            done == n_tasks
            and int(status.get("duplicate_done", 0)) == 0
            and int(status.get("abandoned", 0)) == 0
        ),
    }
    log(f"fleet {label}: {json.dumps(out)}")
    return out


def _masterfail_config(
    tmp: str, label: str, port: int, n_workers: int, n_tasks: int,
    kill_after_done: int,
):
    """One masterfail fleet's JobConfig: mnist over a REAL gRPC master on
    a FIXED port (the restarted master must answer at the address the
    riding-through workers already hold), process-backend workers, the
    journal + pod registry in checkpoint_dir, and — when kill_after_done
    > 0 — the master-kill fault armed."""
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.synthetic import generate

    mb, mb_per_task = 16, 2
    path = os.path.join(tmp, "masterfail_mnist.rio")
    if not os.path.exists(path):
        generate("mnist", path, mb * mb_per_task * n_tasks)
    return JobConfig(
        job_name=f"mfail-{label}",
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        distribution_strategy="AllReduce",
        training_data=path,
        minibatch_size=mb,
        num_minibatches_per_task=mb_per_task,
        num_epochs=1,
        num_workers=n_workers,
        master_addr=f"localhost:{port}",
        master_port=port,
        master_outage_tolerance_s=120.0,
        checkpoint_dir=os.path.join(tmp, f"ckpt-{label}"),
        checkpoint_steps=2,
        max_worker_relaunch=3,
        trace=True,
        chaos=(
            f"kill:target=master,step={kill_after_done}"
            if kill_after_done > 0 else ""
        ),
        pod_log_dir=os.path.join(tmp, f"pods-{label}"),
        gauge_port=0,
    )


def _spawn_master(config, tmp: str, label: str, generation: int):
    """One master process over the config bus (python -m master.main),
    stdout+stderr captured per generation."""
    import subprocess

    env = dict(os.environ)
    env.update(config.to_env())
    log_path = os.path.join(tmp, f"master-{label}-g{generation}.log")
    f = open(log_path, "w")
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "elasticdl_tpu.master.main"],
            env=env, stdout=f, stderr=subprocess.STDOUT,
        )
    finally:
        f.close()
    return proc, log_path


def _offline_replay_counts(config) -> dict:
    """Replay the fleet's journal IN THIS PROCESS (jax-free) — the
    bench-side proof that the WAL alone reconstructs the dispatcher."""
    from elasticdl_tpu.data.reader import create_data_reader
    from elasticdl_tpu.master import journal as journal_mod

    reader = create_data_reader(
        config.training_data, config.parsed_data_reader_params()
    )
    shards = reader.create_shards(
        config.minibatch_size * config.num_minibatches_per_task
    )
    rr = journal_mod.replay(
        os.path.join(config.checkpoint_dir, journal_mod.JOURNAL_FILENAME),
        shards,
        num_epochs=config.num_epochs,
        task_type="training",
        task_timeout_s=config.task_timeout_s,
        task_skip_budget=config.gang_skip_budget,
    )
    counts = rr.dispatcher.counts()
    counts["replayed_events"] = rr.events_applied
    counts["restarts"] = rr.restarts
    counts["torn_tail"] = rr.torn_tail
    return counts


def _masterfail_timeline(dump: dict, t_kill: float, t_spawn2: float) -> dict:
    """Decompose outage -> restart -> replay -> reconcile -> first task on
    the wall-anchored trace clocks (master:replay and lease:handout are
    master-2 instants; worker:reconnect ships from the worker with its
    RTT-midpoint offset applied when known)."""
    replay_ts = replay_ms = first_task_ts = None
    for e in dump.get("master_events") or []:
        ts, name = e.get("ts"), e.get("name")
        if not isinstance(ts, (int, float)):
            continue
        if name == "master:replay" and replay_ts is None:
            replay_ts = ts
            replay_ms = (e.get("args") or {}).get("replay_ms")
        elif (
            name == "lease:handout" and replay_ts is not None
            and first_task_ts is None and ts >= replay_ts
        ):
            first_task_ts = ts
    reconnect_ts = None
    for proc in (dump.get("processes") or {}).values():
        offset = proc.get("clock_offset_us") or 0.0
        for e in proc.get("events") or []:
            if e.get("name") == "worker:reconnect" and isinstance(
                e.get("ts"), (int, float)
            ):
                ts = e["ts"] + offset
                if reconnect_ts is None or ts < reconnect_ts:
                    reconnect_ts = ts
    out = {}
    kill_us, spawn_us = t_kill * 1e6, t_spawn2 * 1e6
    out["outage_hold_ms"] = round((spawn_us - kill_us) / 1e3, 1)
    if replay_ts is not None:
        out["spawn_to_replay_ms"] = round((replay_ts - spawn_us) / 1e3, 1)
        out["replay_ms"] = replay_ms
    if reconnect_ts is not None and replay_ts is not None:
        out["replay_to_reconnect_ms"] = round(
            (reconnect_ts - replay_ts) / 1e3, 1
        )
    if first_task_ts is not None:
        out["replay_to_first_task_ms"] = round(
            (first_task_ts - replay_ts) / 1e3, 1
        )
        out["recovery_ms"] = round((first_task_ts - kill_us) / 1e3, 1)
    return out


def run_masterfail_fleet(
    n_workers: int,
    n_tasks: int,
    tmp: str,
    log,
    label: str,
    kill_after_done: int = 0,
    outage_hold_s: float = 2.0,
    timeout_s: float = FLEET_TIMEOUT_S,
) -> dict:
    """One master-kill fleet: master in a SUBPROCESS (it must die for
    real), workers spawned by ITS PodManager (process backend) so the
    restart exercises the pod reattach registry, and this bench process
    watching from outside over the same gRPC surface the workers use.
    ``kill_after_done`` = 0 runs the fault-free baseline."""
    import json as _json

    from elasticdl_tpu.chaos.inject import CHAOS_KILL_EXIT_CODE
    from elasticdl_tpu.common.platform import free_port
    from elasticdl_tpu.common.rpc import JsonRpcClient

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "jax_cache")
    port = free_port()
    config = _masterfail_config(
        tmp, label, port, n_workers, n_tasks, kill_after_done
    )
    addr = f"localhost:{port}"
    from elasticdl_tpu.master.pod_manager import REGISTRY_FILENAME

    registry_path = os.path.join(config.checkpoint_dir, REGISTRY_FILENAME)

    t0 = time.perf_counter()
    master1, m1_log = _spawn_master(config, tmp, label, 1)
    client = JsonRpcClient(addr)
    client.wait_ready(90.0)

    def _poll_status(cli, box: dict) -> None:
        try:
            box["status"] = cli.call("JobStatus", {}, timeout_s=5.0)
        except Exception:
            pass

    def _poll_dump(cli, box: dict) -> None:
        try:
            box["dump"] = cli.call("DumpTrace", {}, timeout_s=10.0)
        except Exception:
            pass

    box: Dict[str, dict] = {}
    deadline = time.time() + timeout_s
    worker_pids: Dict[str, int] = {}
    while master1.poll() is None:
        if time.time() > deadline:
            master1.kill()
            raise RuntimeError(f"masterfail fleet {label}: master 1 wedged")
        _poll_status(client, box)
        if not worker_pids and os.path.exists(registry_path):
            from elasticdl_tpu.common import durable

            reg = durable.read_json_tolerant(registry_path, default={})
            try:
                worker_pids = {
                    v["name"]: v["pid"] for v in reg["slots"].values()
                }
            except (KeyError, TypeError, AttributeError):
                pass
        time.sleep(0.15)
    rc1 = master1.returncode
    t_kill = time.time()
    pre_kill_status = dict(box.get("status") or {})

    if kill_after_done <= 0:
        # Baseline: one master generation to completion.
        wall = time.perf_counter() - t0
        final = _offline_replay_counts(config)
        eps = (
            final["done"] * config.minibatch_size
            * config.num_minibatches_per_task / wall
            if wall > 0 else 0.0
        )
        out = {
            "label": label, "workers": n_workers, "wall_s": round(wall, 2),
            "tasks_done": final["done"], "tasks_expected": n_tasks,
            "examples_per_sec": round(eps, 1),
            "duplicate_done": final["duplicate_done"],
            "abandoned": final["abandoned"],
            "master_rc": rc1,
        }
        log(f"fleet {label}: {json.dumps(out)}")
        return out

    if rc1 != CHAOS_KILL_EXIT_CODE:
        raise RuntimeError(
            f"masterfail fleet {label}: master 1 exited rc={rc1}, expected "
            f"the chaos kill ({CHAOS_KILL_EXIT_CODE}) — see {m1_log}"
        )
    log(
        f"fleet {label}: master killed (rc={rc1}) after "
        f"done={pre_kill_status.get('done')} — replaying journal offline"
    )

    # Worker ride-through, part 1: every registered pod is still alive
    # with the master DOWN (they are riding the proxy backoff).
    orphans_alive = {
        name: _pid_alive(pid) for name, pid in worker_pids.items()
    }
    # Offline journal replay IN THE OUTAGE WINDOW: the WAL alone must
    # reconstruct the dispatcher the pre-kill JobStatus described.  The
    # kill fires at the first report whose done count reaches
    # kill_after_done (step= matches >=), but concurrent report handlers
    # can journal past it before the exiting thread's os._exit lands, and
    # the bench's last pre-kill poll can lag by in-flight reports — so
    # the invariant is a band, not equality: kill step <= replayed done
    # <= kill step + (workers - 1) in-flight handlers, and never behind
    # the last thing JobStatus showed us.
    replayed = _offline_replay_counts(config)
    replay_matches = (
        kill_after_done
        <= replayed["done"]
        <= kill_after_done + max(0, n_workers - 1)
        and replayed["done"] >= int(pre_kill_status.get("done", 0))
    )

    time.sleep(outage_hold_s)
    config2 = type(config).from_json(config.to_json())
    config2.chaos = ""  # generation 2 must not re-kill itself
    master2, m2_log = _spawn_master(config2, tmp, label, 2)
    t_spawn2 = time.time()
    client2 = JsonRpcClient(addr)
    # Readiness-wait BEFORE polling: fail-fast probes against the booting
    # master would park this fresh channel in gRPC's no-redial
    # TRANSIENT_FAILURE state (the exact pathology the worker proxy's
    # post-failure probe exists for) and every later poll would lie.
    client2.wait_ready(90.0)
    box2: Dict[str, dict] = {}
    last_dump = 0.0
    while master2.poll() is None:
        if time.time() > deadline:
            master2.kill()
            raise RuntimeError(f"masterfail fleet {label}: master 2 wedged")
        # client2, never the gen-1 channel: a poll that raced the kill
        # can park THAT channel in gRPC's no-redial TRANSIENT_FAILURE
        # state, and every later poll through it would silently fail.
        _poll_status(client2, box2)
        if time.monotonic() - last_dump > 1.0:
            _poll_dump(client2, box2)
            last_dump = time.monotonic()
        time.sleep(0.15)
    wall = time.perf_counter() - t0
    rc2 = master2.returncode
    if rc2 != 0:
        raise RuntimeError(
            f"masterfail fleet {label}: master 2 exited rc={rc2} — see "
            f"{m2_log}"
        )
    dump = box2.get("dump") or {}
    with open(os.path.join(tmp, f"dump-{label}.json"), "w") as f:
        _json.dump(dump, f)

    # Worker ride-through, part 2: the SAME worker processes finished the
    # job — no relaunch pod logs (-rN incarnations) ever appeared.
    relaunch_logs = sorted(
        fn for fn in os.listdir(config.pod_log_dir)
        if "-r" in fn and fn.endswith(".log")
    )
    final = _offline_replay_counts(config)
    status2 = box2.get("status") or {}
    eps = (
        final["done"] * config.minibatch_size
        * config.num_minibatches_per_task / wall
        if wall > 0 else 0.0
    )
    timeline = _masterfail_timeline(dump, t_kill, t_spawn2)
    out = {
        "label": label,
        "workers": n_workers,
        "kill_after_done": kill_after_done,
        "outage_hold_s": outage_hold_s,
        "wall_s": round(wall, 2),
        "tasks_done": final["done"],
        "tasks_expected": n_tasks,
        "examples_per_sec": round(eps, 1),
        "duplicate_done": final["duplicate_done"],
        "stale_reports": int(status2.get("stale_reports", 0)),
        "abandoned": final["abandoned"],
        "master_rcs": [rc1, rc2],
        "pre_kill_status": {
            k: pre_kill_status.get(k) for k in ("done", "doing", "todo")
        },
        "replay_at_kill": {
            k: replayed[k]
            for k in ("done", "doing", "todo", "replayed_events")
        },
        "replay_matches_prekill": replay_matches,
        "journal": status2.get("journal") or {},
        "worker_ride_through": {
            "pids": worker_pids,
            "alive_during_outage": orphans_alive,
            "relaunch_logs": relaunch_logs,
            "no_relaunch": not relaunch_logs and all(orphans_alive.values()),
        },
        "recovery": timeline,
        "zero_double_train": (
            final["done"] == n_tasks
            and final["duplicate_done"] == 0
            and final["abandoned"] == 0
        ),
    }
    log(f"fleet {label}: {json.dumps(out)}")
    return out


def _pid_alive(pid: int) -> bool:
    # The one shared probe (zombie- and reuse-aware): pod_manager owns it.
    from elasticdl_tpu.master.pod_manager import pid_alive

    return pid_alive(pid)


def run_masterfail_smoke(log, tmp: Optional[str] = None) -> dict:
    """Tiny master-kill+restart (bench_all --masterfail-smoke): ONE mnist
    worker, master chaos-killed once its dispatcher counts 2 done tasks,
    restarted ~2 s later — asserts the worker rode through WITHOUT
    relaunch, the journal replayed (> 0 events), and nothing trained
    twice."""
    import tempfile

    tmp = tmp or tempfile.mkdtemp(prefix="masterfail_smoke_")
    result = run_masterfail_fleet(
        1, 6, tmp, log, "smoke", kill_after_done=2, timeout_s=600.0
    )
    problems = []
    if not result["zero_double_train"]:
        problems.append(
            f"exactly-once violated: done={result['tasks_done']}/"
            f"{result['tasks_expected']}, duplicate_done="
            f"{result['duplicate_done']}, abandoned={result['abandoned']}"
        )
    if not result["worker_ride_through"]["no_relaunch"]:
        problems.append(
            "worker did not ride through: "
            f"{result['worker_ride_through']}"
        )
    if not int((result.get("journal") or {}).get("replayed_events", 0)):
        problems.append("master 2 reported no replayed journal events")
    if not result["replay_matches_prekill"]:
        problems.append(
            f"offline replay at kill time diverged: "
            f"{result['replay_at_kill']} vs pre-kill "
            f"{result['pre_kill_status']}"
        )
    result["problems"] = problems
    return result


def run_smoke(log, tmp: Optional[str] = None) -> dict:
    """Tiny kill+recover (bench_all --chaos-smoke): ONE mnist worker,
    killed by chaos at its third dispatched step, relaunched into a warm
    standby — asserts recovery completed and nothing trained twice.
    Small enough for tier-1-adjacent CI; the full gang fleets stay in the
    artifact run."""
    import tempfile

    tmp = tmp or tempfile.mkdtemp(prefix="chaos_smoke_")
    result = run_fleet(
        1, 6, tmp, log, "smoke", model="mnist",
        chaos="kill:worker=chaos-smoke-worker-0,step=3",
        warm_standby=True, timeout_s=600.0,
    )
    problems = []
    if not result["zero_double_train"]:
        problems.append(
            f"exactly-once violated: done={result['tasks_done']}/"
            f"{result['tasks_expected']}, duplicate_done="
            f"{result['duplicate_done']}, abandoned={result['abandoned']}"
        )
    if not result["recovery"].get("detected"):
        problems.append("no elastic:splice detect instant — the kill never fired?")
    if "recovery_time_ms" not in result["recovery"]:
        problems.append("no post-fault successful lease:report — never trained again")
    result["problems"] = problems
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="chaos_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument(
        "--tasks", type=int, default=24,
        help="pool-fleet tasks: long enough that the job OUTLASTS the "
        "spliced replacement's warmup, so recovery_time_ms (the "
        "replacement's first trained task) exists",
    )
    ap.add_argument(
        "--gang-tasks", type=int, default=8,
        help="gang-fleet tasks (the lockstep gang trains every task "
        "collectively, so its wall grows linearly with this)",
    )
    ap.add_argument(
        "--fleets", default="baseline_pool,kill,baseline_gang,stall",
        help="comma-separated subset of "
        "baseline_pool,kill,baseline_gang,stall",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny 1-worker kill+recover; exit 1 on any failed check",
    )
    ap.add_argument(
        "--masterfail", action="store_true",
        help="run the r18 master-kill survivability fleet instead of the "
        "r13 families: chaos-kill the master subprocess mid-job, restart "
        "it, and stamp MASTERFAIL (journal replay + worker ride-through "
        "+ outage decomposition + exactly-once)",
    )
    ap.add_argument(
        "--masterfail-smoke", action="store_true",
        help="tiny 1-worker master kill+restart; exit 1 on any failed "
        "check (bench_all --masterfail-smoke)",
    )
    ap.add_argument(
        "--masterfail-tasks", type=int, default=12,
        help="masterfail fleet tasks: enough that the job OUTLASTS the "
        "restart and the post-replay master dispatches real work",
    )
    ap.add_argument(
        "--kill-after-done", type=int, default=4,
        help="kill the master once its dispatcher counts this many done "
        "tasks (fires AFTER that report is applied+journaled)",
    )
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    log = lambda m: print(f"[chaos] {m}", file=sys.stderr, flush=True)

    # code_rev at ENTRY (tools/artifact.ArtifactRun): this tool's run
    # writes dump files and the artifact itself — the measured code is the
    # tree as it stood when the run started.
    from tools.artifact import ArtifactRun

    run = ArtifactRun()

    if args.masterfail_smoke:
        result = run_masterfail_smoke(log)
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                log(f"FAIL: {p}")
            return 1
        log(
            "PASS: master kill+restart rode through — recovery "
            f"{result['recovery'].get('recovery_ms')} ms, "
            f"{result['journal'].get('replayed_events')} journal events "
            "replayed, zero double-train, no worker relaunch"
        )
        return 0

    if args.masterfail:
        import tempfile

        tmp = tempfile.mkdtemp(prefix="masterfail_bench_")
        n = args.workers
        baseline = run_masterfail_fleet(
            n, args.masterfail_tasks, tmp, log, "baseline"
        )
        faulted = run_masterfail_fleet(
            n, args.masterfail_tasks, tmp, log, "masterkill",
            kill_after_done=args.kill_after_done,
        )
        goodput = (
            round(
                faulted["examples_per_sec"] / baseline["examples_per_sec"], 3
            )
            if baseline["examples_per_sec"] else None
        )
        artifact = {
            "metric": "master_kill_survivability",
            "harness": (
                f"cpu ({os.cpu_count()} core host), master as a killable "
                "subprocess on a fixed port, ProcessPodBackend worker "
                "subprocesses ADOPTED across the restart via the pod "
                "registry, real gRPC throughout"
            ),
            "workers": n,
            "tasks": args.masterfail_tasks,
            "kill_after_done": args.kill_after_done,
            "fleets": {"baseline": baseline, "masterkill": faulted},
            "goodput_under_restart": goodput,
            "zero_double_train": {
                "baseline": baseline["tasks_done"]
                == args.masterfail_tasks
                and baseline["duplicate_done"] == 0,
                "masterkill": faulted["zero_double_train"],
            },
            "note": (
                "kill fires in the servicer AFTER a report is applied AND "
                "journaled (the hardest crash point for exactly-once: the "
                "worker's unanswered report retries through the proxy and "
                "must dedup by seq, never double-count).  recovery_ms = "
                "kill -> first post-replay lease:handout on wall-anchored "
                "trace clocks; replay/reconnect stages from the "
                "master:replay and worker:reconnect instants.  "
                "worker_ride_through proves the SAME worker pids finished "
                "the job (registry pids alive during the outage, zero "
                "relaunch pod logs).  replay_at_kill is this bench "
                "process replaying the WAL OFFLINE in the outage window "
                "and matching it against the last pre-kill JobStatus."
            ),
        }
        run.write(
            artifact, MASTERFAIL_ARTIFACT, env_var="MASTERFAIL_OUT",
            path=args.out or None, log=log,
        )
        print(json.dumps(artifact), flush=True)
        ok = (
            faulted["zero_double_train"]
            and faulted["worker_ride_through"]["no_relaunch"]
            and faulted["replay_matches_prekill"]
        )
        return 0 if ok else 1

    if args.smoke:
        result = run_smoke(log)
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                log(f"FAIL: {p}")
            return 1
        log(
            "PASS: recovery "
            f"{result['recovery'].get('recovery_time_ms')} ms, "
            "zero double-train"
        )
        return 0

    import tempfile

    tmp = tempfile.mkdtemp(prefix="chaos_bench_")
    n = args.workers
    wanted = [f.strip() for f in args.fleets.split(",") if f.strip()]
    fleets: Dict[str, dict] = {}
    fault_step = _MB_PER_TASK * 2 + 1
    if "baseline_pool" in wanted:
        fleets["baseline_pool"] = run_fleet(
            n, args.tasks, tmp, log, "baseline-pool"
        )
    if "kill" in wanted:
        # Kill the last worker at its SECOND task boundary (step >= 1
        # fires once the first task's steps are dispatched — a later
        # threshold can miss when the pool's dynamic sharding gives the
        # target few tasks); worker= addressing keeps the relaunched -rN
        # incarnation alive, and the warm standby splices the replacement
        # in (non-gang fleet: see module docstring).
        fleets["kill"] = run_fleet(
            n, args.tasks, tmp, log, "kill",
            chaos=f"kill:worker=chaos-kill-worker-{n - 1},step=1",
            warm_standby=True,
        )
    if "baseline_gang" in wanted:
        fleets["baseline_gang"] = run_fleet(
            n, args.gang_tasks, tmp, log, "baseline-gang", multihost=True,
            model="mnist", cache="fleet",
        )
    if "stall" in wanted:
        # Sever-and-solo-drain (module docstring): stall worker 0 at a
        # mid-job task boundary for longer than the whole run can last,
        # and from the SAME step black out every RPC its process sends
        # (count=0 = unlimited; the injector's step mirror gates rpc
        # faults on worker-loop progress).  The stall freezes its
        # lockstep gang_seq while the survivor's heartbeats keep feeding
        # the boundary, so the master skips + evicts it at the deadline;
        # the blackout then keeps the evicted rank OUT — its liveness
        # beats (which would revive the membership) and its death-push
        # (which would RESTART-relaunch it into a doomed 2-world reform)
        # both die client-side as ChaosRpcDropped, swallowed by the beat
        # thread's retry loop.  max_relaunch=0: an injected fault's slot
        # must stay down (the survivor's own death-push RESTART is
        # budget-free by design, so the budget only pins the straggler).
        # worker= addressing (not rank=): post-skip rank numbers
        # reshuffle, and a relaunched -rN incarnation must never
        # re-match.  The 10 s deadline is compile-safe for mnist: both
        # ranks block in their first jit compile at the SAME seq, so
        # neither lags the head while the other advances.
        fleets["stall"] = run_fleet(
            n, args.gang_tasks, tmp, log, "stall",
            chaos=(
                f"stall:worker=chaos-stall-worker-0,point=task,"
                f"step={fault_step},ms={int(FLEET_TIMEOUT_S * 1e3)},count=1;"
                f"drop_rpc:worker=chaos-stall-worker-0,"
                f"step={fault_step},count=0"
            ),
            gang_deadline_ms=10000.0,
            multihost=True,
            model="mnist", cache="fleet", max_relaunch=0,
        )

    artifact = {
        "metric": "chaos_recovery_and_goodput_under_churn",
        "harness": (
            f"cpu ({os.cpu_count()} core host), 1 fake device per worker "
            "process, real gRPC master + PodManager(process backend, warm "
            "standby), jax.distributed gang for multi-worker fleets"
        ),
        "workers": n,
        "pool_tasks": args.tasks,
        "gang_tasks": args.gang_tasks,
        "records_per_task": _RECORDS_PER_TASK,
        "fleets": fleets,
        "note": (
            "kill recovery decomposed over master-clock instants: "
            "elastic:splice detect -> adopt -> elastic:reformed -> the "
            "spliced replacement's first successful lease:report; stall "
            "recovery is gang:skip -> first successful lease:report "
            "after the survivor degrades to a solo world (no second "
            "multi-process world is ever formed: re-formed 2-process "
            "worlds hit this box's jaxlib/gloo heap corruption — the "
            "pre-existing CHANGES r8 @slow reform churn — so the bench "
            "measures the subsystem, not the flake).  "
            "goodput_under_churn = faulted examples/sec / its "
            "shape-matched baseline.  Pool fleets share one compile "
            "cache (the baseline warms it, so the kill fleet's churn "
            "wall measures recovery, not XLA); gang fleets use private "
            "per-fleet caches (no world ever loads another world's "
            "cached collective executables) and the stall fleet's "
            "post-skip wall includes the survivor's solo re-settle + "
            "one fresh solo compile, stamped as such"
        ),
    }
    ratios = {}
    for faulted, base in (("kill", "baseline_pool"), ("stall", "baseline_gang")):
        base_eps = (fleets.get(base) or {}).get("examples_per_sec") or 0
        if faulted in fleets and base_eps:
            ratios[faulted] = round(
                fleets[faulted]["examples_per_sec"] / base_eps, 3
            )
    if ratios:
        artifact["goodput_under_churn"] = ratios
    artifact["zero_double_train"] = {
        k: v["zero_double_train"] for k, v in fleets.items()
    }
    run.write(
        artifact, ARTIFACT_NAME, env_var="CHAOS_OUT",
        path=args.out or None, log=log,
    )
    print(json.dumps(artifact), flush=True)
    return 0 if all(artifact["zero_double_train"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
