"""Headline benchmark: DeepFM/Criteo training throughput, examples/sec/chip
(BASELINE.json metric).

Two phases, ONE JSON line:
1. device-step: the full hybrid train step (mesh-sharded embedding tables +
   psum'd dense grads) on all available devices, synthetic pre-sharded batch,
   steady-state steps/sec — the device ceiling.
2. end-to-end (tools/bench_e2e.py): the WHOLE worker path on a real recordio
   file — master task dispatch, bulk C++ reads, C++ criteo decode, prefetch,
   pipelined device steps.  This is the headline ``value``: it is what a
   user's job sustains (VERDICT r3 Missing #1 demanded the end-to-end number
   be the one of record); the device-step figure rides along as
   ``device_step_examples_per_sec_per_chip``.

This process owns the chip for the whole run: nothing probes the device
from a child first, and a run that lands on anything but a TPU raises
instead of reporting a host number under a device metric's name.

- every phase (init / build / compile / warmup / measure) logs a timestamped
  line to stderr;
- the JSON line is emitted even on partial measurement (``"partial": true``
  with whatever phase was reached), so the driver always gets a parseable
  artifact;
- the persistent compilation cache is enabled so repeat benches skip the
  XLA compile.

``vs_baseline``: no published reference number exists (BASELINE.json
``"published": {}``; see BASELINE.md).  The denominator below is a documented
ESTIMATE of per-V100 ElasticDL DeepFM throughput implied by the north-star
target ("match 8xV100 Horovod throughput"): ~120k examples/sec/GPU for a
small DeepFM with PS-hosted embeddings.  Treat vs_baseline as relative to
that stand-in until a real number is obtainable.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.platform import device_summary, enable_compile_cache
from tools.artifact import peak_bf16_flops

# Stand-ins for the unpublished reference number (see module docstring).
# Kept SEPARATE per metric: r1-r3 compared the *device-step* figure against
# the ~120k/GPU estimate; r4 switched the headline to *end-to-end*, which in
# the reference's own story is also what a V100 job sustains (the estimate
# already includes its input pipeline), so the same stand-in applies — but a
# consumer of the old metric name must not silently read the new one
# (ADVICE r4 #4), hence the explicit ``renamed_from`` field in the output.
REFERENCE_E2E_EXAMPLES_PER_SEC_PER_CHIP = 120_000.0
REFERENCE_DEVICE_STEP_EXAMPLES_PER_SEC_PER_CHIP = 120_000.0

GLOBAL_BATCH = 8192
WARMUP_STEPS = 5
MEASURE_STEPS = 30

_state = {
    "phase": "start",
    "t0": time.time(),
    "emitted": False,
}


def _log(phase: str, msg: str = "") -> None:
    _state["phase"] = phase
    dt = time.time() - _state["t0"]
    print(f"[bench +{dt:7.1f}s] {phase}: {msg}", file=sys.stderr, flush=True)


def _code_rev() -> str:
    """Commit hash stamped into every bench artifact (tools/artifact.py
    ``code_rev``: shared with graftlint's LINT artifact so bench and lint
    trajectories key to the same revision ids).  The best-run-wins record
    guard needs it to tell "a worse run of the same code" (keep the
    record) from "the first run of NEW code" (the record must follow the
    code) — see the guard in ``_emit`` for the dirty-rev rules.
    """
    try:
        from tools.artifact import code_rev

        return code_rev(os.path.dirname(os.path.abspath(__file__)))
    except Exception:
        return ""


def _emit(
    value: float | None,
    *,
    partial: bool = False,
    error: str = "",
    extras: dict | None = None,
) -> None:
    _state["emitted"] = True
    line = {
        "metric": "deepfm_criteo_e2e_examples_per_sec_per_chip",
        # r4 renamed the headline from the device-step metric; trend lines
        # across rounds 1-3 compare against device_step_* in extras instead.
        "renamed_from": "deepfm_criteo_examples_per_sec_per_chip",
        "value": round(value, 1) if value is not None else None,
        "unit": "examples/sec/chip",
        "vs_baseline": (
            round(value / REFERENCE_E2E_EXAMPLES_PER_SEC_PER_CHIP, 3)
            if value is not None
            else None
        ),
    }
    line["code_rev"] = _code_rev()
    if extras:
        line.update(extras)
    if partial:
        line["partial"] = True
        line["phase_reached"] = _state["phase"]
    if error:
        line["error"] = error[:400]
    print(json.dumps(line), flush=True)
    # Belt: deposit the same line under artifacts/ so a battery or driver
    # run leaves a committed number-of-record file even if stdout capture
    # is lost (best-effort: the printed line is the primary channel).
    # Partials go to their OWN file — a later outage rerun must never
    # clobber a committed real number with value:null.
    try:
        from tools.artifact import write_artifact

        if partial:
            # Partials go to their OWN file and NEVER honor the env
            # override: with BENCH_OUT pointed at the committed headline,
            # an outage rerun would clobber the real number with
            # value:null — the exact hazard the name split prevents.
            write_artifact(
                line, "bench_r05_partial.json", env_var="",
                log=lambda m: None,
            )
        else:
            # Every full run is recorded (bench_r05_latest.json), but the
            # number-of-record file keeps the BEST run (ROADMAP D1 owns
            # replacing this with every-run medians).
            write_artifact(
                line, "bench_r05_latest.json", env_var="",
                log=lambda m: None,
            )
            # Compare against the SAME file the guarded write resolves to
            # (BENCH_OUT-aware) — reading the default while writing the
            # override would skip explicit-override writes entirely.
            best = os.environ.get("BENCH_OUT") or os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "artifacts", "bench_r05.json",
            )
            prev = prev_rev = None
            try:
                with open(best) as f:
                    rec = json.load(f)
                prev = rec.get("value")
                prev_rev = rec.get("code_rev")
            except Exception:
                pass
            # Best-run-wins is a SAME-REVISION, SAME-PIPELINE-CONFIG guard:
            # across runs of the same code AND the same ingest/prep/lease
            # shape it keeps the best number, but once either changes the
            # record must follow the
            # fresh run — throughput at ingest_threads=4 and at 1 are
            # different experiments, and a genuine regression must be able
            # to lower the number of record.  Unknown/missing revs or
            # pipeline stamps (old artifacts, no git) count as "different":
            # the fresh run wins.
            same_rev = (
                prev_rev is not None
                and prev_rev != ""
                # Dirty revs never match — even each other: two runs of
                # the same dirty HEAD can be running different code.
                and not prev_rev.endswith("-dirty")
                and prev_rev == line["code_rev"]
                and rec.get("pipeline") is not None
                and rec.get("pipeline") == line.get("pipeline")
            )
            if prev is None or (
                value is not None and (not same_rev or value >= prev)
            ):
                write_artifact(
                    line, "bench_r05.json", env_var="BENCH_OUT",
                    log=lambda m: None,
                )
    except Exception:
        pass


def _batch(n: int):
    # Synthetic Criteo-shaped batch; ids spread across the full hashed space.
    k = jax.random.key(7)
    k1, k2, k3 = jax.random.split(k, 3)
    return {
        "dense": jax.random.uniform(k1, (n, 13), jnp.float32, 0.0, 1000.0),
        "cat": jax.random.randint(k2, (n, 26), 0, 1 << 30),
        "labels": jax.random.bernoulli(k3, 0.25, (n,)).astype(jnp.int32),
    }


def main() -> None:
    profile_dir = os.environ.get("BENCH_PROFILE_DIR", "")
    enable_compile_cache()

    _log("init", "querying devices")
    devices = jax.devices()
    device = device_summary()
    n = len(devices)
    _log("init", json.dumps(device))
    peak = peak_bf16_flops(device)  # no TPU, or an unknown one: raise now
    batch_size = max(GLOBAL_BATCH // n * n, n)

    from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    _log("build", "constructing DeepFM trainer")
    spec = load_model_spec(
        "elasticdl_tpu.models",
        "deepfm.model_spec",
        buckets_per_feature=65536,
        embedding_dim=8,
        hidden=(400, 400),
    )
    mesh = create_mesh(devices)
    trainer = Trainer(
        spec,
        JobConfig(distribution_strategy=DistributionStrategy.PARAMETER_SERVER),
        mesh,
    )
    # "auto" is mesh-size-aware: 1-device meshes resolve to dense (local
    # gather), n>1 TPU meshes to the ragged all-to-all route.  Logged so the
    # recorded artifact names the code path it measured (VERDICT r2 Weak #1).
    _log("build", f"embedding_lookup_impl resolved to "
                  f"{trainer.ctx.embedding_impl!r} on {n} device(s)")

    _log("compile", "init_state + first train_step (XLA compile)")
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_batch(batch_size))
    state, metrics = trainer.train_step(state, batch)
    jax.block_until_ready(metrics)
    _log("compile", "done")

    try:
        _log("warmup", f"{WARMUP_STEPS} steps")
        for _ in range(WARMUP_STEPS):
            state, metrics = trainer.train_step(state, batch)
        jax.block_until_ready(metrics)

        _log("measure", f"{MEASURE_STEPS} steps @ global batch {batch_size}")
        if profile_dir:
            jax.profiler.start_trace(profile_dir)
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            state, metrics = trainer.train_step(state, batch)
        jax.block_until_ready(metrics)
        elapsed = time.perf_counter() - t0
        if profile_dir:
            jax.profiler.stop_trace()
            _log("measure", f"profile trace written to {profile_dir}")
    except Exception as e:
        # Partial result: we compiled and ran at least one step; report that.
        failed_phase = _state["phase"]
        _log("error", str(e)[:300])
        _state["phase"] = failed_phase  # keep phase_reached forensic
        _emit(None, partial=True, error=str(e))
        raise

    eps_per_chip = batch_size * MEASURE_STEPS / elapsed / n
    # MFU context: DeepFM's dense FLOPs are ~20 GFLOP/step at this batch
    # (MLP 608->400->400->1 fwd+bwd), so even a perfect step is ~1% MFU on a
    # v5e — the model is embedding-bound BY DESIGN.  The honest utilization
    # lens is the embedding traffic: per step the fused table moves ~109 MB
    # of random 128-lane rows each way (gather + scatter-add); per-op trace
    # times (a device trace; today --profile_dir + benchmark/xplane.py)
    # put those at ~1.9/2.9 ms = ~50 GB/s
    # effective random-row bandwidth, i.e. the step sits at the HBM
    # random-access floor, not a compute ceiling.
    step_ms = elapsed / MEASURE_STEPS * 1e3
    _log("device-step", f"{eps_per_chip:,.0f} examples/sec/chip "
                        f"({step_ms:.2f} ms/step)")
    # 20 GFLOP is the GLOBAL batch's dense work; per-chip MFU divides by n.
    mfu = 20e9 / n / (elapsed / MEASURE_STEPS) / peak
    _log("device-step", f"~{mfu * 100:.1f}% MFU of the "
                        f"{device['device_kind']} bf16 peak — "
                        "embedding-bound, see comment")
    extras = {
        # What answered, observed in this process — never the env var.
        "device": device,
        "device_step_examples_per_sec_per_chip": round(eps_per_chip, 1),
        "device_step_ms": round(step_ms, 3),
        # Cross-round trend line vs r1-r3, which benched this metric.
        "device_step_vs_baseline": round(
            eps_per_chip / REFERENCE_DEVICE_STEP_EXAMPLES_PER_SEC_PER_CHIP, 3
        ),
    }

    # Phase 2: end-to-end through the real worker loop (the headline).
    _log("e2e", "running the full job stack on a recordio file")
    try:
        from tools.bench_e2e import run_e2e

        e2e = run_e2e(log=lambda m: _log("e2e", m))
    except Exception as e:
        # The device-step figure is still a valid partial artifact.
        _log("e2e-error", str(e)[:300])
        _emit(None, partial=True, error=f"e2e failed: {e}", extras=extras)
        raise
    e2e_eps = e2e["e2e_examples_per_sec_per_chip"]
    extras["e2e_detail"] = {
        k: (round(v, 3) if isinstance(v, float) else v)
        for k, v in e2e.items()
        if k != "e2e_examples_per_sec_per_chip"
    }
    # Pipeline shape of record (r9, extended r11): like the link fields,
    # throughput is only comparable at equal ingest/prep/lease config AND
    # equal step shape (optimizer sharding / donation) — the record guard
    # in _emit treats a different shape as a different experiment, so a
    # sharded-optimizer run and a replicated run never compete for the one
    # record slot.
    extras["pipeline"] = {
        k: e2e[k]
        for k in (
            "ingest_threads", "prep_depth", "lease_batch",
            "optimizer_sharding", "donate_train_state",
        )
        if k in e2e
    }
    _log("done", f"end-to-end {e2e_eps:,.0f} examples/sec/chip "
                 f"(device-step ceiling {eps_per_chip:,.0f})")
    _emit(e2e_eps, extras=extras)


if __name__ == "__main__":
    try:
        main()
    except Exception as e:  # always leave a parseable artifact — exactly one
        if not _state["emitted"]:
            _emit(None, partial=True, error=f"{type(e).__name__}: {e}")
        raise
