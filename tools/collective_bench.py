"""collective_bench — graftreduce's numbers of record (artifacts/COLLECT_r15.json).

Three measurement families:

- **parity** (subprocess per device count, honest XLA fake-device
  counts): flat vs hierarchical train-step param divergence after K
  identical steps (float32 reduction-order only), the subgroup
  renormalization probe — a dp-way step excluding one shard vs a
  1-device step over the surviving shards' examples — and the
  recompile-free assertion (every exclusion mask runs in ONE compiled
  program).
- **sweep** (subprocess per point): steady-state step time at 2/4/8-way
  dp, flat vs hierarchical (``--collective_local_size 2`` emulates the
  host grouping on fake CPU devices), plus the analytic per-replica
  inter-host bytes under each route
  (collectives.interhost_bytes_per_step's model — this harness has no
  real DCN to meter, and the artifact labels the bytes as modeled).
  CPU caveat, stamped into the artifact: fake-device collectives share
  one host's cores, so step-time deltas here measure the route's
  LAUNCH overhead, not the inter-host bandwidth the hierarchy exists
  to save — the bytes column is the claim, the time column is the
  non-regression guard.
- **chaos fleet** (real worker subprocess + real gRPC master +
  PodManager, the chaos_bench harness): a mid-collective stall —
  ``stall:point=collective,shard=1`` wedges one dp shard's contribution
  at the r15 in-step gate — driven through three shapes: a fault-free
  baseline, the stall with the gate OFF (``collective_deadline_ms=0``:
  the dispatch blocks for the full stall, the pre-r15 behavior), and
  the stall with the gate ON (the step completes on the subgroup at the
  deadline).  The degradation comparison is stamped against both the
  blocking path and the r13 sever-and-solo-drain number
  (CHAOS_r13.json's 25.8 s skip->trained), with the worker's
  ``edl_collective_skip_total`` observed in the MASTER's live /metrics
  scrape mid-stall (the fleet-aggregated envelope view).

Usage:
    python tools/collective_bench.py [--steps 10] [--tasks 6]
        [--stall-ms 2000] [--deadline-ms 250]
        [--families parity,sweep,chaos] [--out artifacts/COLLECT_r15.json]
    python tools/collective_bench.py --smoke   # tiny subgroup fleet
                                               # (bench_all --collective-smoke)
Env override for the artifact path: COLLECT_OUT.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, Optional

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

ARTIFACT_NAME = "COLLECT_r15.json"

#: The r13 number the in-collective path is measured against: the stall
#: fleet's sever-and-solo-drain skip->trained wall (CHAOS_r13.json).
R13_SKIP_TO_TRAINED_MS = 25800.0

FLEET_TIMEOUT_S = 600.0

DP_SWEEP = (2, 4, 8)
WARMUP = 3


def _child_env(dp: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={dp}"
    )
    return env


def _spawn(extra, dp: int, log) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--child"] + extra
    log(f"run {' '.join(extra)}")
    out = subprocess.run(
        cmd, env=_child_env(dp), capture_output=True, text=True,
        timeout=600, cwd=_REPO,
    )
    if out.returncode != 0:
        raise RuntimeError(
            f"child {extra} failed rc={out.returncode}: {out.stderr[-800:]}"
        )
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# child tasks (jax initializes inside the subprocess)
# ---------------------------------------------------------------------------


def _make_trainer(dp: int, mode: str, min_elems: int = 4096):
    import jax

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    spec = load_model_spec(
        "elasticdl_tpu.models", "mnist.model_spec", compute_dtype="float32"
    )
    cfg = JobConfig(
        collective=mode,
        collective_local_size=(2 if mode == "hierarchical" else 0),
        collective_min_elems=min_elems,
    )
    return spec, Trainer(
        spec, cfg, create_mesh(jax.devices(), num_devices=dp)
    )


def _batch(n: int, seed: int = 7):
    import numpy as np

    rng = np.random.default_rng(seed)
    return {
        "images": rng.uniform(size=(n, 28, 28, 1)).astype(np.float32),
        "labels": rng.integers(0, 10, (n,)).astype(np.int32),
    }


def child_measure(args) -> dict:
    import jax

    dp = args.dp
    _, t = _make_trainer(dp, args.mode)
    state = t.init_state(jax.random.key(0))
    n = max(args.batch // dp * dp, dp)
    batch = t.shard_batch(_batch(n))
    bytes_model = t.collective_bytes_per_step(state)
    state, m = t.train_step(state, batch)  # compile
    jax.block_until_ready(m)
    for _ in range(WARMUP):
        state, m = t.train_step(state, batch)
    jax.block_until_ready(m)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, m = t.train_step(state, batch)
    jax.block_until_ready(m)
    dt = (time.perf_counter() - t0) / args.steps
    return {
        "dp": dp,
        "mode": args.mode,
        "topology": t.collective.describe() if t.collective else "flat",
        "step_ms": round(dt * 1e3, 3),
        "examples_per_sec": round(n / dt, 1),
        "global_batch": n,
        "interhost_bytes_per_step_model": bytes_model,
        "loss": round(float(m["loss"]), 6),
    }


def child_parity(args) -> dict:
    import jax
    import numpy as np

    dp = args.dp

    def diff(a, b):
        return max(
            float(np.max(np.abs(np.asarray(x) - np.asarray(y))))
            if x.size else 0.0
            for x, y in zip(
                jax.tree.leaves(jax.device_get(a.params)),
                jax.tree.leaves(jax.device_get(b.params)),
            )
        )

    n = max(args.batch // dp * dp, dp)
    host = _batch(n)
    # flat vs hierarchical, identical steps
    _, tf_ = _make_trainer(dp, "flat")
    _, th = _make_trainer(dp, "hierarchical")
    sf = tf_.init_state(jax.random.key(0))
    sh = th.init_state(jax.random.key(0))
    for _ in range(args.steps):
        sf, _ = tf_.train_step(sf, tf_.shard_batch(host))
        sh, _ = th.train_step(sh, th.shard_batch(host))
    flat_vs_hier = diff(sf, sh)
    # renormalization: exclude the last shard vs a 1-device run over the
    # surviving shards' examples
    _, tx = _make_trainer(dp, "flat")
    sx = tx.init_state(jax.random.key(0))
    mask = [1] * (dp - 1) + [0]
    tx.set_active_contributors(mask)
    sx, mx = tx.train_step(sx, tx.shard_batch(host))
    _, t1 = _make_trainer(1, "flat")
    s1 = t1.init_state(jax.random.key(0))
    keep = n // dp * (dp - 1)
    s1, m1 = t1.train_step(s1, t1.shard_batch({k: v[:keep] for k, v in host.items()}))
    renorm = diff(sx, s1)
    # recompile-free: every mask variant through ONE compiled program
    fn = tx._train_step
    compiles_ok = True
    for m in ([0] + [1] * (dp - 1), None, [1] * (dp - 1) + [0]):
        tx.set_active_contributors(m)
        sx, _ = tx.train_step(sx, tx.shard_batch(host))
        compiles_ok = compiles_ok and tx._train_step is fn
    cache = getattr(fn, "_cache_size", lambda: None)()
    if cache is not None:
        compiles_ok = compiles_ok and cache == 1
    return {
        "dp": dp,
        "steps": args.steps,
        "hier_local_size": 2,
        "max_abs_param_diff_flat_vs_hier": flat_vs_hier,
        "max_abs_param_diff_excluded_vs_smaller_world": renorm,
        "excluded_loss": round(float(mx["loss"]), 6),
        "smaller_world_loss": round(float(m1["loss"]), 6),
        "mask_flip_recompile_free": bool(compiles_ok),
        "jit_cache_size_after_mask_flips": cache,
    }


# ---------------------------------------------------------------------------
# chaos fleet (real gRPC master + worker subprocess, 2 fake devices)
# ---------------------------------------------------------------------------


def _scrape_collectives(address: str, stop, box: dict) -> None:
    """Poll the master's /metrics, tracking the MAX observed
    edl_collective_* values — the mid-stall observability claim."""
    from tools.watch_job import fetch

    while not stop.is_set():
        try:
            families = fetch(address, timeout_s=2.0)
        except Exception as e:  # noqa: BLE001 — tallied; the job goes on
            box["scrapes_failed"] = box.get("scrapes_failed", 0) + 1
            box["last_error"] = f"{type(e).__name__}: {e}"
        else:
            box["scrapes_ok"] = box.get("scrapes_ok", 0) + 1
            for name in (
                "edl_collective_skip_total",
                "edl_collective_subgroup_size",
                "edl_collective_interhost_bytes_total",
            ):
                fam = families.get(name)
                if not fam:
                    continue
                for s in fam["samples"]:
                    key = f"{name}:max_seen"
                    box[key] = max(box.get(key, 0.0), s["value"])
                    if name == "edl_collective_subgroup_size" and s["value"]:
                        key_min = f"{name}:min_seen"
                        box[key_min] = min(
                            box.get(key_min, float("inf")), s["value"]
                        )
        stop.wait(0.2)


def _ensure_fleet_env() -> None:
    """The fleet's worker subprocesses inherit this process's env: a
    2-fake-device dp mesh per worker (without it the worker boots 1
    device and the gate disables itself — the in-step deadline needs
    two contributors), CPU only (the chaos_bench stance — a fleet of
    several worker processes must never contend for a chip).  Called by
    every fleet
    entry point: ``main`` AND ``run_smoke`` (bench_all imports the
    latter directly)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"


def run_fleet(
    n_tasks: int,
    tmp: str,
    log,
    label: str,
    chaos: str = "",
    deadline_ms: float = 0.0,
    stall_ms: float = 0.0,
    timeout_s: float = FLEET_TIMEOUT_S,
) -> dict:
    """One 1-worker job (the worker holds a 2-fake-device dp mesh)
    through the full master stack; returns wall, accounting, and the
    mid-run collective-gauge scrape."""
    _ensure_fleet_env()
    from elasticdl_tpu.common import trace
    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.data.synthetic import generate
    from elasticdl_tpu.master.main import Master

    mb, mb_per_task = 16, 2
    # Keyed by task count: fleets of different sizes (the warmup fleet is
    # deliberately short) must never share a dataset sized for the first
    # caller — a 2-task file silently turns every 6-task fleet into a
    # 2-task one.
    path = os.path.join(tmp, f"collective_mnist_{n_tasks}.rio")
    if not os.path.exists(path):
        generate("mnist", path, mb * mb_per_task * n_tasks)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(tmp, "jax_cache")
    config = JobConfig(
        job_name=f"coll-{label}",
        model_def="mnist.model_spec",
        model_params="compute_dtype=float32",
        training_data=path,
        minibatch_size=mb,
        num_minibatches_per_task=mb_per_task,
        num_epochs=1,
        num_workers=1,
        trace=True,
        chaos=chaos,
        collective_deadline_ms=deadline_ms,
        gang_skip_budget=8,
        checkpoint_steps=0,
        pod_log_dir=os.path.join(tmp, f"pods-{label}"),
        gauge_port=0,
    )
    trace.configure(enabled=True)
    trace.default().clear()
    master = Master(config)
    result_box: dict = {}

    def _run():
        try:
            result_box["status"] = master.run()
        except Exception as e:
            result_box["error"] = e

    t0 = time.perf_counter()
    runner = threading.Thread(target=_run, name=f"coll-{label}", daemon=True)
    runner.start()
    scrape_box: dict = {}
    scrape_stop = threading.Event()
    scraper = None
    if master.metrics_server is not None:
        scraper = threading.Thread(
            target=_scrape_collectives,
            args=(master.metrics_server.address, scrape_stop, scrape_box),
            name=f"coll-scrape-{label}", daemon=True,
        )
        scraper.start()
    runner.join(timeout=timeout_s)
    scrape_stop.set()
    if scraper is not None:
        scraper.join(timeout=5.0)
    wall = time.perf_counter() - t0
    if runner.is_alive():
        master.shutdown()
        runner.join(timeout=30)
        raise RuntimeError(
            f"collective fleet {label!r} still running after {timeout_s:.0f}s"
        )
    if "error" in result_box:
        raise RuntimeError(
            f"collective fleet {label!r} failed: {result_box['error']}"
        ) from result_box["error"]
    status = result_box["status"]
    done = int(status.get("done", 0))
    # The in-step wait, on phase clocks: every gate crossing — a bounded
    # deadline wait (gate armed) or a blocking inline stall (gate off) —
    # lands in the worker's ``collective_gate`` phase, so this number is
    # immune to the ±2-3 s process-spawn/scrape noise whole-fleet walls
    # carry on this box.
    gate_s = sum(
        float(p.get("collective_gate", 0.0))
        for p in (status.get("phase_times") or {}).values()
    )
    out = {
        "label": label,
        "chaos": chaos,
        "collective_deadline_ms": deadline_ms,
        "stall_ms": stall_ms,
        "wall_s": round(wall, 2),
        "gate_phase_s": round(gate_s, 3),
        "tasks_done": done,
        "tasks_expected": n_tasks,
        "abandoned": int(status.get("abandoned", 0)),
        "duplicate_done": int(status.get("duplicate_done", 0)),
        "collective_skips": status.get("collective_skips") or {},
        "live_metrics": {
            "endpoint": (
                master.metrics_server.address
                if master.metrics_server is not None else None
            ),
            **scrape_box,
        },
        "zero_double_train": (
            done == n_tasks
            and int(status.get("duplicate_done", 0)) == 0
            and int(status.get("abandoned", 0)) == 0
        ),
    }
    log(f"fleet {label}: {json.dumps(out)}")
    return out


def run_chaos_family(args, tmp: str, log) -> dict:
    """baseline / stall-with-gate-off / stall-with-gate-on, one stamped
    comparison (see module docstring)."""
    stall = (
        f"stall:point=collective,shard=1,step=3,"
        f"ms={int(args.stall_ms)},count=1"
    )
    # All three fleets share one compile cache (same model, same shapes);
    # an UNSTAMPED warmup fleet pays the XLA compiles first, so the
    # baseline — the degradation DENOMINATOR — measures steady-state
    # wall, not compilation (the chaos_bench cache stance, one step
    # further: here even the baseline must be warm).
    run_fleet(2, tmp, log, "warmup")
    fleets = {
        "baseline": run_fleet(args.tasks, tmp, log, "baseline"),
        "stall_blocking": run_fleet(
            args.tasks, tmp, log, "stall-blocking", chaos=stall,
            deadline_ms=0.0, stall_ms=args.stall_ms,
        ),
        "stall_subgroup": run_fleet(
            args.tasks, tmp, log, "stall-subgroup", chaos=stall,
            deadline_ms=args.deadline_ms, stall_ms=args.stall_ms,
        ),
    }
    base = fleets["baseline"]["wall_s"]
    blocking_gate_ms = round(fleets["stall_blocking"]["gate_phase_s"] * 1e3, 1)
    subgroup_gate_ms = round(fleets["stall_subgroup"]["gate_phase_s"] * 1e3, 1)
    skips = sum(fleets["stall_subgroup"]["collective_skips"].values())
    live = fleets["stall_subgroup"]["live_metrics"]
    return {
        "fleets": fleets,
        "stall_ms": args.stall_ms,
        "deadline_ms": args.deadline_ms,
        # The three-way degradation story, on PHASE clocks (the
        # noise-immune number — every gate crossing, blocking or
        # deadline-bounded, is accounted under the worker's
        # ``collective_gate`` phase): the blocking path pays ~the stall
        # inside the step, the subgroup path pays ~the deadline, and the
        # r13 evict-and-reform path paid 25.8 s.
        "in_step_wait_ms": {
            "blocking": blocking_gate_ms,
            "subgroup": subgroup_gate_ms,
            "r13_sever_and_solo_drain": R13_SKIP_TO_TRAINED_MS,
        },
        # Whole-fleet wall excess over the fault-free baseline — stamped
        # for context, NOT gated: a difference of ~15-20 s fleet walls
        # on a 2-core box carries ±2-3 s process-spawn/scrape noise
        # (the r12 wall-A/B stance; the phase numbers above are the
        # comparison of record).
        "wall_excess_ms_noisy": {
            "blocking": round(
                (fleets["stall_blocking"]["wall_s"] - base) * 1e3, 1
            ),
            "subgroup": round(
                (fleets["stall_subgroup"]["wall_s"] - base) * 1e3, 1
            ),
        },
        "subgroup_completed_with_skips": skips,
        "skip_observed_in_live_scrape": (
            live.get("edl_collective_skip_total:max_seen", 0) >= 1
        ),
        "checks": {
            "all_fleets_exactly_once": all(
                f["zero_double_train"] for f in fleets.values()
            ),
            "subgroup_skipped": skips >= 1,
            # The blocking fleet's in-step wait must show (most of) the
            # stall — proof the fault actually wedged a dispatch.
            "blocking_paid_the_stall": blocking_gate_ms >= args.stall_ms * 0.9,
            "subgroup_beats_blocking": subgroup_gate_ms < blocking_gate_ms,
            # Bounded by the deadline per gate pass (one pass per task,
            # +1 for the warm-in crossing), not by the stall.
            "subgroup_bounded_by_deadline": (
                subgroup_gate_ms
                <= args.deadline_ms * (args.tasks + 1)
            ),
            "subgroup_well_under_r13": (
                subgroup_gate_ms < R13_SKIP_TO_TRAINED_MS / 10
            ),
        },
    }


def run_smoke(log, tmp: Optional[str] = None) -> dict:
    """Tiny subgroup-completion check (bench_all --collective-smoke):
    one worker, one mid-collective stall, gate on — asserts the job
    completed on the subgroup (skips > 0), nothing trained twice, and
    the skip was visible in the live master scrape."""
    import tempfile

    tmp = tmp or tempfile.mkdtemp(prefix="collective_smoke_")
    result = run_fleet(
        4, tmp, log, "smoke",
        chaos="stall:point=collective,shard=1,step=2,ms=1500,count=1",
        deadline_ms=150.0, stall_ms=1500.0,
    )
    problems = []
    if not result["zero_double_train"]:
        problems.append(
            f"exactly-once violated: done={result['tasks_done']}/"
            f"{result['tasks_expected']}, duplicate_done="
            f"{result['duplicate_done']}, abandoned={result['abandoned']}"
        )
    if not sum(result["collective_skips"].values()):
        problems.append(
            "no collective_skips in JobStatus — the gate never excluded?"
        )
    if not result["live_metrics"].get("edl_collective_skip_total:max_seen"):
        problems.append(
            "edl_collective_skip_total never observed in the live scrape"
        )
    result["problems"] = problems
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="collective_bench", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--task", default="measure", choices=("measure", "parity"))
    ap.add_argument("--mode", default="flat", choices=("flat", "hierarchical"))
    ap.add_argument("--dp", type=int, default=2)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--tasks", type=int, default=6)
    ap.add_argument("--stall-ms", type=float, default=2000.0)
    ap.add_argument("--deadline-ms", type=float, default=250.0)
    ap.add_argument(
        "--families", default="parity,sweep,chaos",
        help="comma-separated subset of parity,sweep,chaos",
    )
    ap.add_argument("--smoke", action="store_true",
                    help="tiny subgroup fleet; exit 1 on any failed check")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if args.child:
        result = (
            child_parity(args) if args.task == "parity" else child_measure(args)
        )
        print(json.dumps(result), flush=True)
        return 0

    _ensure_fleet_env()
    log = lambda m: print(f"[collective] {m}", file=sys.stderr, flush=True)
    from tools.artifact import ArtifactRun

    run = ArtifactRun()

    if args.smoke:
        result = run_smoke(log)
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                log(f"FAIL: {p}")
            return 1
        log(
            "PASS: subgroup completion with "
            f"{sum(result['collective_skips'].values())} skip(s), "
            "zero double-train"
        )
        return 0

    import tempfile

    wanted = {f.strip() for f in args.families.split(",") if f.strip()}
    artifact: Dict = {
        "metric": "collective_step_time_and_straggler_degradation",
        "harness": (
            f"cpu ({os.cpu_count()} core host), XLA fake devices; "
            "hierarchical grouping emulated via --collective_local_size 2 "
            "(fake-device collectives share one host's cores, so step-time "
            "deltas measure launch overhead, not DCN bandwidth — the "
            "inter-host bytes column is the analytic model)"
        ),
        "model": "mnist dense f32",
    }
    if "parity" in wanted:
        parity = _spawn(
            ["--task", "parity", "--dp", "4",
             "--batch", str(args.batch), "--steps", str(args.steps)],
            4, log,
        )
        log(f"parity: {parity}")
        artifact["parity"] = parity
    if "sweep" in wanted:
        sweep = []
        for dp in DP_SWEEP:
            # local_size=2 cannot factor a 2-wide axis into multiple
            # hosts (n_host would be 1 → resolve_topology demotes to
            # flat); stamping that point as "hierarchical" would gate a
            # mislabeled flat-vs-flat series in bench_regress.
            modes = ("flat",) if dp <= 2 else ("flat", "hierarchical")
            for mode in modes:
                row = _spawn(
                    ["--task", "measure", "--mode", mode, "--dp", str(dp),
                     "--batch", str(args.batch), "--steps", str(args.steps)],
                    dp, log,
                )
                sweep.append(row)
                log(
                    f"dp={dp} {mode}: {row['step_ms']} ms/step, "
                    f"interhost(model) {row['interhost_bytes_per_step_model']}"
                )
        artifact["sweep"] = sweep
        by = {(r["dp"], r["mode"]): r for r in sweep}
        artifact["sweep_checks"] = {
            f"interhost_cut_dp{dp}": round(
                by[(dp, "flat")]["interhost_bytes_per_step_model"]["flat"]
                / max(
                    by[(dp, "hierarchical")]["interhost_bytes_per_step_model"][
                        "resolved"
                    ],
                    1,
                ),
                2,
            )
            for dp in DP_SWEEP
            if (dp, "hierarchical") in by
        }
    if "chaos" in wanted:
        tmp = tempfile.mkdtemp(prefix="collective_bench_")
        artifact["chaos"] = run_chaos_family(args, tmp, log)

    run.write(
        artifact, ARTIFACT_NAME, env_var="COLLECT_OUT",
        path=args.out or None, log=log,
    )
    print(json.dumps({
        k: v for k, v in artifact.items() if k in ("sweep_checks",)
    } | ({"chaos_checks": artifact["chaos"]["checks"]}
         if "chaos" in artifact else {})))
    ok = all(artifact.get("chaos", {}).get("checks", {"ok": True}).values())
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
