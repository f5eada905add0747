"""Chip benchmarks for BASELINE configs #1-#3 (VERDICT r3 item 2).

bench.py owns the flagship DeepFM number; this tool covers the other three
reproducible configs — MNIST (AllReduce), ResNet-50/CIFAR-10 (AllReduce),
Wide&Deep/Census (ParameterServer) — plus an ImageNet-shaped ResNet-50
(224x224/1000-class, 7x7/s2 stem), and reports examples/sec/chip and MFU.
The >=40% MFU target is judged on resnet50_imagenet: it is the MXU-bound
workload — CIFAR's 32x32 convs are too small to tile the systolic array.

MFU method: FLOPs per step come from XLA's own compiled cost analysis
(``compiled.cost_analysis()['flops']``) — the count of what the compiled
program actually executes, not a hand-derived estimate — divided by
measured steady-state step time and the chip's bf16 peak (looked up by the
``device_kind`` that answered, tools/artifact.PEAK_BF16_FLOPS; an unknown
chip raises).  ResNet-50 is the proof the trainer sustains MXU utilization
when FLOPs dominate; the tabular models are embedding/HBM-bound by design
and their MFU is reported for completeness, not as a target.

Usage: python tools/bench_all.py [--configs mnist,resnet50,resnet50_imagenet,wide_deep]
Prints one JSON line per config; docs/perf.md carries the committed table.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.platform import device_summary, enable_compile_cache
from tools.artifact import peak_bf16_flops

WARMUP = 5
MEASURE = 30

CONFIGS = {
    # BASELINE.json config #1: MNIST Keras functional ~ AllReduce.
    "mnist": dict(
        model_def="mnist.model_spec",
        params={},
        strategy="AllReduce",
        batch=4096,
    ),
    # Config #2: ResNet-50 on CIFAR-10, AllReduce — the BASELINE config.
    "resnet50": dict(
        model_def="cifar10_resnet.model_spec",
        params=dict(depth=50),
        strategy="AllReduce",
        batch=512,
    ),
    # ImageNet-shaped ResNet-50 (224x224, 1000 classes, 7x7/s2 stem) — the
    # honest MXU-utilization benchmark: CIFAR's 32x32 convs are too small
    # to tile the systolic array, so the >=40% MFU target is judged here.
    "resnet50_imagenet": dict(
        model_def="cifar10_resnet.model_spec",
        params=dict(
            depth=50, image_size=224, num_classes=1000, imagenet_stem=True
        ),
        strategy="AllReduce",
        batch=256,
        # Textbook training cost at the MAC=2 convention the peak is
        # quoted in: fwd ~4.1 GMACs at 224x224 = 8.2 GFLOP, x3 for
        # fwd+bwd = 24.6 GFLOP/example.  Reported alongside the
        # XLA-cost-analysis MFU as a cross-check (XLA measured ~26.7G on
        # the compiled step — same convention, plus norm/elementwise).
        analytic_flops_per_example=24.6e9,
    ),
    # Config #3: Wide&Deep on Census, ParameterServer + sharded embedding.
    "wide_deep": dict(
        model_def="wide_deep.model_spec",
        params=dict(buckets=65536),
        strategy="ParameterServer",
        batch=8192,
    ),
    # TPU-native capability extension (SURVEY §2 parallelism table: SP/CP
    # absent upstream): decoder-only transformer LM at a GPT-2-small shape
    # — the matmul-dominated workload.  remat off: the MFU bench wants the
    # no-recompute step (b=16, L=1024 activations fit HBM comfortably).
    "transformer_lm": dict(
        model_def="transformer_lm.model_spec",
        params=dict(
            vocab=32768, dim=768, n_heads=12, n_layers=12,
            seq_len=1024, max_seq=1024, remat=False,
        ),
        strategy="AllReduce",
        batch=16,
        # Per 1024-token sequence at MAC=2, fwd+bwd (x3 fwd):
        # dense blocks 6*N*L with N=12x12*768^2=84.9M -> 522 GFLOP;
        # attention 12 layers x 4L^2d x3 -> 116 GFLOP;
        # tied LM head 2LdV x3 -> 155 GFLOP  ==> ~0.79 TFLOP/example.
        # mfu_analytic_pct is the number of record for THIS config: the
        # attention runs in a Pallas kernel whose FLOPs XLA's
        # cost_analysis cannot see, so mfu_pct under-counts here.
        analytic_flops_per_example=0.79e12,
    ),
}


def _synth_batch(name: str, spec, n: int):
    import jax
    import jax.numpy as jnp

    k = jax.random.key(11)
    ks = jax.random.split(k, 3)
    if name == "mnist":
        return {
            "images": jax.random.uniform(ks[0], (n, 28, 28, 1), jnp.float32),
            "labels": jax.random.randint(ks[1], (n,), 0, 10),
        }
    if name == "resnet50":
        return {
            "images": jax.random.uniform(ks[0], (n, 32, 32, 3), jnp.float32),
            "labels": jax.random.randint(ks[1], (n,), 0, 10),
        }
    if name == "resnet50_imagenet":
        # Shapes derive from the SAME params dict the model is built from,
        # so a config edit cannot silently bench a mismatched workload.
        p = CONFIGS[name]["params"]
        size, classes = p["image_size"], p["num_classes"]
        return {
            "images": jax.random.uniform(
                ks[0], (n, size, size, 3), jnp.float32
            ),
            "labels": jax.random.randint(ks[1], (n,), 0, classes),
        }
    if name == "transformer_lm":
        p = CONFIGS[name]["params"]
        seqs = jax.random.randint(
            ks[0], (n, p["seq_len"] + 1), 0, p["vocab"]
        )
        return {
            "tokens": seqs[:, :-1],
            "labels": seqs[:, 1:],
        }
    if name == "wide_deep":
        return {
            "dense": jax.random.uniform(ks[0], (n, 5), jnp.float32, 0.0, 80.0),
            "cat": jax.random.randint(ks[1], (n, 9), 0, 1 << 30),
            "labels": jax.random.bernoulli(ks[2], 0.3, (n,)).astype(jnp.int32),
        }
    raise ValueError(name)


def bench_config(name: str, batch_override: int = 0, measure: int = MEASURE) -> dict:
    import jax

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    cfg = CONFIGS[name]
    devices = jax.devices()
    device = device_summary()
    peak = peak_bf16_flops(device)  # no TPU, or an unknown one: raise now
    n_chips = len(devices)
    batch = batch_override or cfg["batch"]
    batch = max(batch // n_chips * n_chips, n_chips)
    spec = load_model_spec(
        "elasticdl_tpu.models", cfg["model_def"], **cfg["params"]
    )
    trainer = Trainer(
        spec,
        JobConfig(distribution_strategy=cfg["strategy"]),
        create_mesh(devices),
    )
    state = trainer.init_state(jax.random.key(0))
    host_batch = jax.device_get(_synth_batch(name, spec, batch))
    sharded = trainer.shard_batch(host_batch)
    state, metrics = trainer.train_step(state, sharded)  # builds + compiles
    jax.block_until_ready(metrics)

    # FLOPs of the compiled step, from XLA's own cost analysis (AOT lower +
    # compile hits the jit cache — same shapes — so this is cheap).  Fresh
    # batch placement: the executing call may have donated the first one.
    flops = None
    try:
        sharded2 = trainer.shard_batch(host_batch)
        cost = (
            # Third arg since r15: the graftreduce subgroup mask is a
            # traced input of every train step.
            trainer._train_step.lower(
                state, sharded2, trainer._active_device()
            )
            .compile()
            .cost_analysis()
        )
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        flops = float(c.get("flops", 0.0)) or None
        sharded = sharded2
    except Exception as e:  # cost analysis is best-effort; report without MFU
        print(f"  cost_analysis unavailable: {e}", file=sys.stderr)

    for _ in range(WARMUP):
        state, metrics = trainer.train_step(state, sharded)
    jax.block_until_ready(metrics)
    t0 = time.perf_counter()
    for _ in range(measure):
        state, metrics = trainer.train_step(state, sharded)
    jax.block_until_ready(metrics)
    step_s = (time.perf_counter() - t0) / measure

    out = {
        "config": name,
        "strategy": cfg["strategy"],
        "global_batch": batch,
        "examples_per_sec_per_chip": round(batch / step_s / n_chips),
        "step_ms": round(step_s * 1e3, 2),
        "chips": n_chips,
        # What answered, observed in this process — never the env var.
        "device": device,
    }
    if flops:
        # cost_analysis() reports the PER-DEVICE executable's flops (the
        # SPMD module each chip runs), so per-chip MFU divides by step
        # time and peak only — dividing by n_chips again undercounted
        # multi-chip MFU by n (harmless on the 1-chip battery, wrong on a
        # mesh).  Verified: at global batch 8 on 8 devices the reported
        # count matches ~1 example's training flops, not 8.
        out["flops_per_step_per_device"] = flops
        out["mfu_pct"] = round(flops / step_s / peak * 100, 2)
    analytic = cfg.get("analytic_flops_per_example")
    if analytic:
        out["mfu_analytic_pct"] = round(
            analytic * (batch / n_chips) / step_s / peak * 100, 2
        )
    return out


def run_gauge_smoke() -> int:
    """The graftgauge CI check (bench_all --gauge-smoke): live endpoints
    answer mid-run with the instrumented families, watch_job renders a
    live scrape, instrumentation overhead holds the <2% budget, and the
    cross-rev trajectory gate passes non-empty.  Host-only (CPU-harness
    subprocess fleet): the smoke measures the metrics
    plane, not the accelerator."""
    import tempfile

    say = lambda m: print(f"[gauge-smoke] {m}", file=sys.stderr, flush=True)
    problems = []

    # 1. A real 1-worker job through the full master stack; chaos_bench's
    # fleet runner scrapes the master's live endpoint every second
    # mid-run and stamps the newest snapshot.
    from tools.chaos_bench import run_fleet

    tmp = tempfile.mkdtemp(prefix="gauge_smoke_")
    fleet = run_fleet(
        1, 6, tmp, say, "gauge", model="mnist", timeout_s=600.0
    )
    live = fleet.get("live_metrics") or {}
    snap = live.get("snapshot") or {}
    if not live.get("scrapes_ok"):
        problems.append(
            f"no successful mid-run scrape of the master endpoint "
            f"({live.get('last_error', 'endpoint never came up')})"
        )
    for family in ("edl_fleet_examples_per_sec", "edl_world_size",
                   "edl_dispatcher_done"):
        if family not in snap:
            problems.append(f"master family {family} missing from the "
                            f"mid-run snapshot")
    if not any(k.startswith("edl_examples_trained_total") for k in snap):
        problems.append(
            "no worker gauge envelope reached the fleet view "
            "(edl_examples_trained_total absent)"
        )

    # 2. watch_job one-shot against a LIVE endpoint (the CLI path, end to
    # end: bind, scrape, parse, render).
    from elasticdl_tpu.common import gauge
    from elasticdl_tpu.common.metrics_http import MetricsHTTPServer
    from tools.watch_job import main as watch_main

    reg = gauge.Registry()
    reg.counter("edl_smoke_total", "gauge-smoke probe").inc(3)
    probe_srv = MetricsHTTPServer(reg.render_prometheus, port=0).start()
    try:
        rc = watch_main([probe_srv.address])
    finally:
        probe_srv.stop()
    if rc != 0:
        problems.append(f"watch_job one-shot exited {rc}")

    # 3. Instrumentation + scrape overhead on the ingest A/B harness.
    from tools.ingest_bench import gauge_overhead_ab

    ab = gauge_overhead_ab(say)
    if ab["overhead_pct"] >= 2.0:
        problems.append(
            f"gauge overhead {ab['overhead_pct']}% >= 2% budget"
        )

    # 4. The cross-rev trajectory gate over the committed artifacts.
    from tools.bench_regress import run_gate

    trajectory = run_gate(log=say)
    if not trajectory["series"]:
        problems.append("bench_regress trajectory is EMPTY — the "
                        "artifact indexer found nothing")
    if not trajectory["compared"]:
        problems.append("bench_regress compared zero cross-rev pairs")
    if trajectory["regressions"]:
        problems.append(
            f"{len(trajectory['regressions'])} perf regression(s) in the "
            "committed trajectory"
        )

    result = {
        "metric": "gauge_smoke",
        "live_metrics": live,
        "fleet_tasks_done": fleet.get("tasks_done"),
        "overhead": ab,
        "trajectory_series": len(trajectory["series"]),
        "trajectory_compared": trajectory["compared"],
        "problems": problems,
    }
    from tools.artifact import write_artifact

    write_artifact(result, "GAUGE_r14.json", env_var="GAUGE_OUT", log=say)
    print(json.dumps(result), flush=True)
    if problems:
        for p in problems:
            say(f"FAIL: {p}")
        return 1
    say(
        f"PASS: {live.get('scrapes_ok')} live scrapes mid-run, overhead "
        f"{ab['overhead_pct']}% < 2%, trajectory "
        f"{len(trajectory['series'])} series / "
        f"{trajectory['compared']} compared"
    )
    return 0


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="mnist,resnet50,resnet50_imagenet,wide_deep,transformer_lm")
    ap.add_argument("--batch", type=int, default=0, help="override global batch")
    ap.add_argument("--measure", type=int, default=MEASURE)
    ap.add_argument(
        "--optshard", action="store_true",
        help="also run the sharded-optimizer bytes/step bench "
        "(tools/optshard_bench.py) after the training configs; it stamps "
        "its own OPTSHARD artifact — per-replica optimizer bytes and step "
        "time, replicated vs sharded, at 1/2/4-way dp",
    )
    ap.add_argument(
        "--serving", action="store_true",
        help="also run the serving-tier latency/QPS bench "
        "(tools/serving_bench.py) after the training configs; it stamps "
        "its own SERVE artifact — the r10 latency surface alongside "
        "examples/sec",
    )
    ap.add_argument(
        "--chaos", action="store_true",
        help="also run the chaos bench (tools/chaos_bench.py) after the "
        "training configs; it stamps its own CHAOS artifact — recovery "
        "time decomposed over the splice timeline, goodput-under-churn "
        "vs a fault-free baseline, skip accounting, and the explicit "
        "zero-double-train check",
    )
    ap.add_argument(
        "--chaos-smoke", action="store_true",
        help="run ONLY the chaos smoke: a tiny 1-worker kill+recover "
        "through the full master stack asserting recovery completes and "
        "nothing trains twice — the tier-1-adjacent CI check that the "
        "fault path works without the full gang run",
    )
    ap.add_argument(
        "--masterfail", action="store_true",
        help="also run the r18 master-kill survivability fleet "
        "(tools/chaos_bench.py --masterfail) after the training configs; "
        "it stamps its own MASTERFAIL artifact — journal replay, worker "
        "ride-through, outage decomposition, exactly-once",
    )
    ap.add_argument(
        "--masterfail-smoke", action="store_true",
        help="run ONLY the masterfail smoke: 1-worker fleet, the master "
        "chaos-killed and restarted mid-job — asserts the worker rode "
        "through WITHOUT relaunch, the journal replayed, and nothing "
        "trained twice",
    )
    ap.add_argument(
        "--collective", action="store_true",
        help="also run the graftreduce bench (tools/collective_bench.py) "
        "after the training configs; it stamps its own COLLECT artifact — "
        "flat-vs-hierarchical parity + step-time sweep at 2/4/8-way, the "
        "analytic inter-host bytes cut, and the mid-collective-stall "
        "chaos fleets (blocking vs subgroup completion)",
    )
    ap.add_argument(
        "--collective-smoke", action="store_true",
        help="run ONLY the graftreduce smoke: one worker with a 2-shard "
        "dp mesh, one mid-collective stall — asserts the in-step deadline "
        "gate completes the job on the subgroup (skips > 0, live-scrape "
        "observable) with zero double-train",
    )
    ap.add_argument(
        "--mesh2d", action="store_true",
        help="also run the 2D hybrid-mesh bench (tools/mesh2d_bench.py) "
        "after the training configs; it stamps its own MESH2D artifact — "
        "1D-vs-2D parity, step time + analytic inter-host bytes across "
        "(dp, tp) shapes, and the elastic 4x2 -> 4x1 -> 4x2 chaos reform "
        "with bit-exact moments",
    )
    ap.add_argument(
        "--mesh2d-smoke", action="store_true",
        help="run ONLY the mesh2d smoke: the 1D-vs-2D parity probe plus "
        "the chaos reform (4x2 -> 4x1 -> 4x2, bit-exact moments, "
        "exactly-once, jitsan-armed zero over-budget retraces)",
    )
    ap.add_argument(
        "--trace-smoke", action="store_true",
        help="run ONLY the grafttrace overhead smoke: the ingest bench's "
        "--trace A/B (recorder off vs on, same workload) must land under "
        "2%% throughput delta — the recorded guarantee that tracing a "
        "production job is safe (docs/observability.md)",
    )
    ap.add_argument(
        "--gauge-smoke", action="store_true",
        help="run ONLY the graftgauge smoke: a 1-worker job whose live "
        "/metrics endpoints are scraped MID-RUN (fleet view + worker "
        "families must answer), a watch_job one-shot over a live "
        "endpoint, the gauge overhead A/B (<2%% budget), and the "
        "bench_regress trajectory gate over the committed artifacts "
        "(must be non-empty and regression-free)",
    )
    args = ap.parse_args()
    if args.gauge_smoke:
        raise SystemExit(run_gauge_smoke())
    if args.masterfail_smoke:
        # CPU-harness subprocess fleet (the chaos-smoke
        # stance): the smoke measures master crash survivability — the
        # journal replay + ride-through machinery — not the accelerator.
        from tools.chaos_bench import run_masterfail_smoke

        result = run_masterfail_smoke(
            lambda m: print(
                f"[masterfail-smoke] {m}", file=sys.stderr, flush=True
            )
        )
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                print(f"[masterfail-smoke] FAIL: {p}", file=sys.stderr)
            raise SystemExit(1)
        print(
            "[masterfail-smoke] PASS: worker rode the master restart out "
            f"without relaunch, {result['journal'].get('replayed_events')} "
            "journal event(s) replayed, zero double-train",
            file=sys.stderr,
        )
        return
    if args.chaos_smoke:
        # CPU-harness subprocess fleet: the smoke measures
        # the recovery machinery, not the accelerator.
        from tools.chaos_bench import run_smoke

        result = run_smoke(
            lambda m: print(f"[chaos-smoke] {m}", file=sys.stderr, flush=True)
        )
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                print(f"[chaos-smoke] FAIL: {p}", file=sys.stderr)
            raise SystemExit(1)
        print(
            "[chaos-smoke] PASS: recovery "
            f"{result['recovery'].get('recovery_time_ms')} ms, zero "
            "double-train", file=sys.stderr,
        )
        return
    if args.collective_smoke:
        # CPU-harness subprocess fleet (the chaos-smoke stance): the smoke
        # measures the in-collective exclusion machinery, not the chip.
        from tools.collective_bench import run_smoke as collective_smoke

        result = collective_smoke(
            lambda m: print(
                f"[collective-smoke] {m}", file=sys.stderr, flush=True
            )
        )
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                print(f"[collective-smoke] FAIL: {p}", file=sys.stderr)
            raise SystemExit(1)
        print(
            "[collective-smoke] PASS: subgroup completion with "
            f"{sum(result['collective_skips'].values())} skip(s), zero "
            "double-train", file=sys.stderr,
        )
        return
    if args.mesh2d_smoke:
        # Subprocess-driven children pin their own fake device counts (the
        # optshard stance): the smoke measures the 2D re-partitioner, not
        # the chip.
        from tools.mesh2d_bench import run_smoke as mesh2d_smoke

        result = mesh2d_smoke(
            lambda m: print(f"[mesh2d-smoke] {m}", file=sys.stderr, flush=True)
        )
        print(json.dumps(result), flush=True)
        if result["problems"]:
            for p in result["problems"]:
                print(f"[mesh2d-smoke] FAIL: {p}", file=sys.stderr)
            raise SystemExit(1)
        print(
            "[mesh2d-smoke] PASS: parity "
            f"{result['parity']['max_abs_loss_diff']:.2e}, chaos "
            f"{result['chaos']['path_tp_major']} bit-exact, zero "
            "over-budget retraces", file=sys.stderr,
        )
        return
    if args.trace_smoke:
        # Host-only: the smoke measures the recorder, not
        # the accelerator, and must run on any box.
        from tools.ingest_bench import trace_overhead_ab

        result = trace_overhead_ab(
            lambda m: print(f"[trace-smoke] {m}", file=sys.stderr, flush=True)
        )
        print(json.dumps(result), flush=True)
        if result["overhead_pct"] >= 2.0:
            print(
                f"[trace-smoke] FAIL: {result['overhead_pct']}% overhead "
                ">= 2% budget", file=sys.stderr,
            )
            raise SystemExit(1)
        print(
            f"[trace-smoke] PASS: {result['overhead_pct']}% overhead "
            "< 2% budget", file=sys.stderr,
        )
        return
    # This process opens the backend itself (bench_config) and owns the
    # chip for the whole battery; the fleets further down are CPU-pinned
    # subprocess harnesses and never contend for it.
    enable_compile_cache()
    results = []
    try:
        for name in args.configs.split(","):
            result = bench_config(name.strip(), args.batch, args.measure)
            results.append(result)
            print(json.dumps(result), flush=True)
            print(f"  {name}: {result['examples_per_sec_per_chip']:,} "
                  f"ex/s/chip, {result['step_ms']} ms/step, "
                  f"MFU {result.get('mfu_pct', '?')}%", file=sys.stderr)
    finally:
        if results:  # a mid-battery flake still deposits what was measured
            from tools.artifact import write_artifact

            # A subset/experiment run must not clobber the full-table
            # number of record (it did, twice, during r5 tuning) — and
            # neither must a short --measure smoke over the full list.
            names = {n.strip() for n in args.configs.split(",")}
            full = (
                names >= set(CONFIGS)
                and not args.batch
                and args.measure == MEASURE
            )
            # Subset/smoke runs never honor the env override either — with
            # BENCH_ALL_OUT pointed at the full-table file, the override
            # would reintroduce the clobber the name split prevents.
            write_artifact(
                {"metric": "bench_all_configs", "configs": results},
                "bench_all_r05.json" if full else "bench_all_partial.json",
                env_var="BENCH_ALL_OUT" if full else "",
            )
    if args.optshard:
        from tools.optshard_bench import main as optshard_main

        # Subprocess-driven (its children pin their own fake device
        # counts), so running it after the in-process configs is safe.
        optshard_main([])
    if args.chaos:
        from tools.chaos_bench import main as chaos_main

        # Subprocess-fleet driven (the bench process itself stays
        # jax-free), so running it after the in-process configs is safe.
        chaos_main([])
    if args.masterfail:
        from tools.chaos_bench import main as chaos_main

        # Master + workers all run as subprocesses; this process only
        # watches over gRPC, so it composes with the in-process configs.
        chaos_main(["--masterfail"])
    if args.mesh2d:
        from tools.mesh2d_bench import main as mesh2d_main

        # Subprocess-driven (its children pin their own fake device
        # counts), so running it after the in-process configs is safe.
        mesh2d_main([])
    if args.collective:
        from tools.collective_bench import main as collective_main

        # Subprocess-driven sweep children + subprocess worker fleets
        # (this process never re-initializes its backend), so running it
        # after the in-process configs is safe.
        collective_main([])
    if args.serving:
        from tools.serving_bench import run_bench

        serve = run_bench([50.0, 100.0, 200.0])
        for p in serve["points"]:
            print(f"  serving @{p['offered_qps']} QPS: "
                  f"p50 {p.get('p50_ms', '—')} ms, "
                  f"p99 {p.get('p99_ms', '—')} ms ({p['errors']} errors)",
                  file=sys.stderr)
    # Cross-rev trajectory gate (r14): every battery ends by re-indexing
    # the committed artifacts (including whatever this run just stamped)
    # into artifacts/TRAJECTORY.json; a same-config metric that regressed
    # past the threshold fails the run — the perf trajectory is a gated
    # number now, not a docs/perf.md narrative.
    from tools.bench_regress import run_gate

    if run_gate()["regressions"]:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
