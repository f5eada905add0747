#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

``python3 chip_smoke.py`` (no arguments, from the repo root, on a machine
with a TPU) drives the main path once, through the entry points a user
calls, at the full width of the two models the repo benches:

1. ``native_build``: rebuild ``libedl_native.so`` from ``edl_native.cc``
   (``*.so`` is not committed; a stale or foreign binary must not pass).
2. ``kernels``: one child checks the chip-only kernels directly —
   ``flash_attention`` fwd+bwd against ``attention_reference`` in bf16 at
   the bench shape and at the documented bound L=8192, asserting three
   ``tpu_custom_call`` in the lowered text (compiled, not interpreted), and
   the explicit ``impl="ragged"`` embedding lookup fwd+bwd against a plain
   gather (the real ``lax.ragged_all_to_all``, which XLA:CPU cannot run).
3. ``data``: criteo + LM recordio generated from a seed.
4. ``deepfm``: ``elasticdl train --local`` — jax-free master, one worker
   process that owns the chip(s) — DeepFM at the flagship width with
   periodic + final Orbax checkpoints.
5. ``deepfm_resume``: a second ``elasticdl train`` over the same
   ``--checkpoint_dir``; must log "joined from checkpoint step N" and
   continue.
6. ``transformer``: ``transformer_lm`` at the GPT-2-small width; on one
   chip the worker must trace attention as the compiled Pallas kernel, on
   several as the ppermute ring.

This parent never imports jax: a process that has touched jax holds the
chip, and the next one that needs it fails or hangs.  Every phase is its own
child, run one after another with ``JAX_PLATFORMS=tpu`` (a missing chip is
then jax's own hard error, and an inherited ``cpu`` cannot redirect the run).
Any failed check fails the run, non-zero, naming the phase; stdout stays
empty unless every phase passed, and then ends with the pass marker
``{"ok": true, "device": {...}}`` naming the device as the children saw it.

``--rehearsal`` runs the same control flow at tiny sizes on the CPU
(interpreted kernel, emulated ragged collective) so tier-1 can exercise it;
its output says it is a rehearsal and it never prints the pass marker.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chip_smoke_out")
NATIVE_DIR = os.path.join(ROOT, "elasticdl_tpu", "ps", "native")

#: Whole-run budget: the contract allows 1200 s, compilation included.
BUDGET_S = 1150.0

#: bf16 tolerance of the kernel-vs-reference comparison: max|delta| over the
#: tensor, relative to max|reference| (5 bf16 ulps at the tensor's scale;
#: the interpreted kernel measures ~7e-3 at small shapes).
BF16_TOL = 2e-2

MODES = {
    "chip": {
        "platform": "tpu",
        # ((B, L, H, D), heads the O(L^2) f32 reference is computed for)
        "flash": [((16, 1024, 12, 64), 12), ((1, 8192, 12, 64), 2)],
        "ragged_impl": "ragged",
        "deepfm": {
            "model_params": (
                "buckets_per_feature=65536;embedding_dim=8;hidden=[400,400]"
            ),
            "minibatch": 8192, "mb_per_task": 8, "file_tasks": 2,
            "epochs": 4, "resume_epochs": 6, "checkpoint_steps": 16,
        },
        "lm": {
            # GPT-2-small width (tools/bench_all.py "transformer_lm"), with
            # the model's default per-block rematerialisation.
            "model_params": (
                "vocab=32768;dim=768;n_heads=12;n_layers=12;seq_len=1024;"
                "max_seq=1024"
            ),
            "vocab": 32768, "seq_len": 1024,
            "minibatch": 16, "mb_per_task": 4, "file_tasks": 2, "epochs": 2,
        },
    },
    "rehearsal": {
        "platform": "cpu",
        "flash": [((1, 256, 2, 64), 2)],
        "ragged_impl": "ragged_emulated",
        "deepfm": {
            "model_params": (
                "buckets_per_feature=256;embedding_dim=8;hidden=[16]"
            ),
            "minibatch": 64, "mb_per_task": 2, "file_tasks": 2,
            "epochs": 2, "resume_epochs": 3, "checkpoint_steps": 4,
        },
        "lm": {
            "model_params": (
                "vocab=256;dim=64;n_heads=2;n_layers=2;seq_len=128;max_seq=128"
            ),
            "vocab": 256, "seq_len": 128,
            "minibatch": 8, "mb_per_task": 2, "file_tasks": 2, "epochs": 1,
        },
    },
}


class PhaseFailed(Exception):
    def __init__(self, phase: str, reason: str):
        super().__init__(f"phase={phase}: {reason}")
        self.phase = phase


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# parent: process handling
# ---------------------------------------------------------------------------


class Runner:
    """Starts each phase's child in its own session and makes sure nothing
    it started outlives the phase (a master killed at its time limit would
    otherwise orphan the worker that holds the chip)."""

    def __init__(self, mode: str):
        self.mode = mode
        self.deadline = time.monotonic() + BUDGET_S
        self.env = dict(os.environ)
        self.env["JAX_PLATFORMS"] = MODES[mode]["platform"]
        self.env["PYTHONPATH"] = ROOT + (
            os.pathsep + self.env["PYTHONPATH"]
            if self.env.get("PYTHONPATH") else ""
        )
        os.makedirs(os.path.join(OUT, "logs"), exist_ok=True)

    def run(self, phase: str, argv: list) -> str:
        """Run one child to its end; returns the path of its log (stdout +
        stderr).  Raises PhaseFailed on a non-zero exit or the deadline."""
        remaining = self.deadline - time.monotonic()
        if remaining <= 0:
            raise PhaseFailed(phase, f"no time left of the {BUDGET_S:.0f}s budget")
        log_path = os.path.join(OUT, "logs", f"{phase}.log")
        say(f"{phase}: starting ({remaining:.0f}s left)")
        with open(log_path, "w") as log:
            proc = subprocess.Popen(
                argv, cwd=ROOT, env=self.env, stdout=log,
                stderr=subprocess.STDOUT, start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=remaining)
            except subprocess.TimeoutExpired:
                rc = None
            finally:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        if rc != 0:
            tail = _tail(log_path)
            for path in sorted(glob.glob(os.path.join(OUT, phase, "pods", "*.log"))):
                tail += f"\n--- {os.path.basename(path)} ---\n" + _tail(path)
            say(f"{phase}: log tail\n{tail}")
            raise PhaseFailed(
                phase,
                "timed out (killed)" if rc is None else f"child exited {rc}",
            )
        return log_path


def _read(path: str) -> str:
    with open(path, errors="replace") as f:
        return f.read()


def _tail(path: str, n: int = 4000) -> str:
    return _read(path)[-n:]


# ---------------------------------------------------------------------------
# parent: phases
# ---------------------------------------------------------------------------


def phase_native_build() -> dict:
    """Force a rebuild from source: the library is built by ``make`` on
    first use and trusted by mtime, which says nothing about a binary that
    rode in with a copied tree."""
    t0 = time.monotonic()
    try:
        subprocess.run(
            ["make", "-s", "-B", "-C", NATIVE_DIR],
            check=True, capture_output=True, text=True,
        )
    except (OSError, subprocess.CalledProcessError) as e:
        raise PhaseFailed(
            "native_build", f"{e}: {getattr(e, 'stderr', '')}"
        ) from e
    lib = os.path.join(NATIVE_DIR, "libedl_native.so")
    if not os.path.exists(lib):
        raise PhaseFailed("native_build", f"{lib} missing after make")
    return {
        "phase": "native_build", "rebuilt": True,
        "bytes": os.path.getsize(lib),
        "wall_s": round(time.monotonic() - t0, 2),
    }


def phase_kernels(runner: Runner) -> dict:
    result_path = os.path.join(OUT, "kernels.json")
    t0 = time.monotonic()
    runner.run("kernels", [
        sys.executable, os.path.abspath(__file__), "--child", "kernels",
        "--mode", runner.mode, "--result", result_path,
    ])
    with open(result_path) as f:
        result = json.load(f)
    result["wall_s"] = round(time.monotonic() - t0, 2)
    result["device"] = _require_device("kernels", result["device"], runner.mode)
    return result


def phase_data(runner: Runner) -> dict:
    t0 = time.monotonic()
    runner.run("data", [
        sys.executable, os.path.abspath(__file__), "--child", "data",
        "--mode", runner.mode,
    ])
    return {
        "phase": "data",
        "files": {
            os.path.basename(p): os.path.getsize(p)
            for p in sorted(glob.glob(os.path.join(OUT, "data", "*.rio")))
        },
        "wall_s": round(time.monotonic() - t0, 2),
    }


def _train_job(
    runner: Runner, phase: str, sizes: dict, epochs: int, model_def: str,
    strategy: str, data: str, checkpoint_dir: str = "",
) -> tuple:
    """One ``elasticdl train --local`` and everything the parent can learn
    about it from channels that already exist: the master's final status
    line, the worker's pod log, the master's metrics stream, the
    checkpoint directory.  Returns (phase result, worker log text)."""
    work = os.path.join(OUT, phase)
    pods, metrics = os.path.join(work, "pods"), os.path.join(work, "metrics")
    argv = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train", "--local",
        "--job_name", f"smoke-{phase.replace('_', '-')}",
        "--model_def", model_def,
        "--model_params", sizes["model_params"],
        "--distribution_strategy", strategy,
        "--training_data", data,
        "--minibatch_size", str(sizes["minibatch"]),
        "--num_minibatches_per_task", str(sizes["mb_per_task"]),
        "--num_epochs", str(epochs),
        "--num_workers", "1",
        "--pod_log_dir", pods,
        "--metrics_dir", metrics,
    ]
    if checkpoint_dir:
        argv += [
            "--checkpoint_dir", checkpoint_dir,
            "--checkpoint_steps", str(sizes["checkpoint_steps"]),
            "--keep_checkpoint_max", "64",
        ]
    t0 = time.monotonic()
    master_log = runner.run(phase, argv)
    wall_s = time.monotonic() - t0

    expected_tasks = sizes["file_tasks"] * epochs
    status = _last_json(phase, _read(master_log), r"job finished: (\{.*\})")
    if status["done"] != expected_tasks or status["duplicate_done"] != 0:
        raise PhaseFailed(
            phase,
            f"done={status['done']} (expected {expected_tasks}), "
            f"duplicate_done={status['duplicate_done']}, "
            f"abandoned={status['abandoned']}",
        )
    worker_log = "".join(
        _read(p) for p in sorted(glob.glob(os.path.join(pods, "*.log")))
    )
    boot = _last_json(phase, worker_log, r"worker \S+ device: (\{.*\})")
    device = _require_device(phase, boot, runner.mode)
    if not boot["native_lib"]:
        raise PhaseFailed(
            phase, "the worker ran without the native library (Python "
            "decoders, ~80x slower)"
        )
    finished = _last_json(phase, worker_log, r"worker \S+ finished: (\{.*\})")
    train = []
    with open(os.path.join(metrics, "metrics.jsonl")) as f:
        for line in f:
            record = json.loads(line)
            if record["kind"] == "train":
                train.append(record)
    losses = [r["loss"] for r in train]
    if len(losses) != expected_tasks or not all(map(math.isfinite, losses)):
        raise PhaseFailed(
            phase, f"expected {expected_tasks} finite task losses, got {losses}"
        )
    cache = finished["compile_cache"]
    if not cache["dir"] or cache["hits"] + cache["misses"] == 0:
        raise PhaseFailed(phase, f"compile cache not in use: {cache}")
    memory = re.findall(r"device bytes in use after init: (\[.*\])", worker_log)
    result = {
        "phase": phase,
        "device": device,
        "native_lib": boot["native_lib"],
        "tasks_done": status["done"],
        "steps": finished["step"],
        "loss_first_task": losses[0],
        "loss_last_task": losses[-1],
        "wall_s": round(wall_s, 2),
        "compile_s": cache["backend_compile_s"],
        "steady_s": round(train[-1]["ts"] - train[0]["ts"], 2),
        "steady_tasks": len(train) - 1,
        "compile_cache": {
            "dir": cache["dir"], "hits": cache["hits"],
            "misses": cache["misses"],
            # The five costliest compiles and how the cache served each.
            "slowest": dict(sorted(
                cache["functions"].items(), key=lambda kv: -kv[1]["s"]
            )[:5]),
        },
        "device_bytes_in_use_after_init": (
            json.loads(memory[-1]) if memory else None
        ),
    }
    return result, worker_log


def phase_deepfm(runner: Runner, resume_from: dict | None = None) -> dict:
    sizes = MODES[runner.mode]["deepfm"]
    phase = "deepfm_resume" if resume_from else "deepfm"
    epochs = sizes["resume_epochs"] if resume_from else sizes["epochs"]
    ckpt = os.path.join(OUT, "deepfm_ckpt")
    result, log = _train_job(
        runner, phase, sizes, epochs, "deepfm.model_spec", "ParameterServer",
        os.path.join(OUT, "data", "criteo.rio"), checkpoint_dir=ckpt,
    )

    routes = set(re.findall(r"embedding lookup route: (\w+)", log))
    device = result["device"]
    # One device: auto means the local gather (dense's n=1 path).  Several
    # chips: anything but the ragged all-to-all is the hidden fallback.
    want = "ragged" if device["platform"] == "tpu" and device["count"] > 1 else "dense"
    if routes != {want}:
        raise PhaseFailed(
            phase, f"embedding route resolved to {sorted(routes)} on "
            f"{device['count']} {device['platform']} device(s); expected {want!r}"
        )
    result["embedding_route"] = want

    steps_per_task = sizes["mb_per_task"]
    start = resume_from["steps"] if resume_from else 0
    final = start + steps_per_task * sizes["file_tasks"] * epochs
    if result["steps"] != final:
        raise PhaseFailed(phase, f"final step {result['steps']}, expected {final}")
    on_disk = sorted(
        int(d) for d in os.listdir(ckpt) if d.isdigit()
    )
    every = sizes["checkpoint_steps"]
    expected = sorted(
        {s for s in range(every, final + 1, every) if s > start} | {final}
    )
    missing = [s for s in expected if s not in on_disk]
    if missing:
        raise PhaseFailed(
            phase, f"checkpoint step dirs missing: {missing} (on disk {on_disk})"
        )
    result["checkpoints_on_disk"] = on_disk

    joined = [int(s) for s in re.findall(r"joined from checkpoint step (\d+)", log)]
    if resume_from:
        if joined != [resume_from["steps"]]:
            raise PhaseFailed(
                phase, f"resume did not join from checkpoint step "
                f"{resume_from['steps']} (log says {joined})"
            )
        result["joined_from_checkpoint_step"] = joined[0]
    elif joined:
        raise PhaseFailed(phase, f"a fresh job joined from a checkpoint: {joined}")
    return result


def phase_transformer(runner: Runner) -> dict:
    sizes = MODES[runner.mode]["lm"]
    result, log = _train_job(
        runner, "transformer", sizes, sizes["epochs"],
        "transformer_lm.model_spec", "AllReduce",
        os.path.join(OUT, "data", "lm.rio"),
    )
    paths = set(re.findall(r"attention path: ([\w-]+)", log))
    device = result["device"]
    if device["count"] > 1:
        want = "xla-ring"
    elif device["platform"] == "tpu":
        want = "pallas-compiled"
    else:
        want = "xla-reference"  # rehearsal only: _require_device refuses a CPU chip run
    if paths != {want}:
        raise PhaseFailed(
            "transformer", f"attention traced as {sorted(paths)} on "
            f"{device['count']} {device['platform']} device(s); expected {want!r}"
        )
    result["attention_path"] = want
    final = sizes["mb_per_task"] * sizes["file_tasks"] * sizes["epochs"]
    if result["steps"] != final:
        raise PhaseFailed(
            "transformer", f"final step {result['steps']}, expected {final}"
        )
    return result


def _last_json(phase: str, text: str, pattern: str) -> dict:
    found = re.findall(pattern, text)
    if not found:
        raise PhaseFailed(phase, f"no log line matches {pattern!r}")
    return json.loads(found[-1])


def _require_device(phase: str, device: dict, mode: str) -> dict:
    """The device a phase's own process reported (platform, device_kind,
    count, jax version), refused unless it is the platform this mode runs
    on."""
    want = MODES[mode]["platform"]
    if device["platform"] != want:
        raise PhaseFailed(
            phase, f"ran on platform {device['platform']!r} "
            f"({device['device_kind']}), not {want!r}"
        )
    return {k: device[k] for k in ("platform", "device_kind", "count", "jax")}


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------


def child_data(mode: str) -> None:
    """Criteo + LM recordio from a seed (numpy only; touches no backend)."""
    from elasticdl_tpu.data.synthetic import synthetic_criteo, synthetic_lm

    out = os.path.join(OUT, "data")
    os.makedirs(out, exist_ok=True)
    d = MODES[mode]["deepfm"]
    synthetic_criteo(
        os.path.join(out, "criteo.rio"),
        d["minibatch"] * d["mb_per_task"] * d["file_tasks"],
        seed=11, container="recordio",
    )
    lm = MODES[mode]["lm"]
    synthetic_lm(
        os.path.join(out, "lm.rio"),
        lm["minibatch"] * lm["mb_per_task"] * lm["file_tasks"],
        seed=13, seq_len=lm["seq_len"], vocab=lm["vocab"],
    )


def child_kernels(mode: str, result_path: str) -> None:
    """The chip-only kernels, checked directly in the process that owns the
    device.  Any failed comparison raises; the parent sees the exit code."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.common.jax_compat import shard_map
    from elasticdl_tpu.common.platform import (
        compile_cache_stats,
        device_summary,
        enable_compile_cache,
    )
    from elasticdl_tpu.ops.embedding import (
        ParallelContext,
        embedding_lookup,
        pack_table,
    )
    from elasticdl_tpu.ops.flash_attention import flash_attention
    from elasticdl_tpu.ops.ring_attention import attention_reference
    from elasticdl_tpu.parallel.mesh import create_mesh

    enable_compile_cache()
    device = device_summary()  # the parent refuses the wrong platform
    on_chip = device["platform"] == "tpu"
    f32 = jnp.float32

    def rel_err(got, ref) -> float:
        got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
        if not np.all(np.isfinite(got)):
            raise RuntimeError("non-finite values out of the kernel")
        return float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))

    flash = []
    for shape, ref_heads in MODES[mode]["flash"]:
        keys = jax.random.split(jax.random.key(0), 4)
        q, k, v = (
            jax.random.normal(key, shape, f32).astype(jnp.bfloat16)
            for key in keys[:3]
        )
        w = jax.random.normal(keys[3], shape, f32)  # cotangent of the output

        def loss(q, k, v):
            out = flash_attention(q, k, v, True)
            return jnp.sum(out.astype(f32) * w), out

        def ref_loss(q, k, v, h=ref_heads):
            # Heads are independent, so the O(L^2) f32 oracle runs on a
            # head slice where all heads would not fit next to the kernel.
            out = attention_reference(
                *(x[:, :, :h].astype(f32) for x in (q, k, v)), causal=True
            )
            return jnp.sum(out * w[:, :, :h]), out

        t0 = time.perf_counter()
        lowered = jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        ).lower(q, k, v)
        custom_calls = lowered.as_text().count("tpu_custom_call")
        if on_chip and custom_calls != 3:
            raise RuntimeError(
                f"flash {shape}: {custom_calls} tpu_custom_call in the "
                "lowering, expected 3 (fwd, dq, dkv) — not the compiled kernel"
            )
        compiled = lowered.compile()
        t1 = time.perf_counter()
        (_, out), grads = jax.block_until_ready(compiled(q, k, v))
        t2 = time.perf_counter()
        (_, ref_out), ref_grads = jax.jit(
            jax.value_and_grad(ref_loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)
        errs = {"out": rel_err(out[:, :, :ref_heads], ref_out)}
        for name, got, ref in zip(("dq", "dk", "dv"), grads, ref_grads):
            errs[name] = rel_err(
                got[:, :, :ref_heads], ref[:, :, :ref_heads]
            )
        if max(errs.values()) > BF16_TOL:
            raise RuntimeError(
                f"flash {shape}: kernel vs reference {errs} exceeds {BF16_TOL}"
            )
        flash.append({
            "shape": list(shape), "dtype": "bfloat16", "causal": True,
            "tpu_custom_calls": custom_calls, "reference_heads": ref_heads,
            "max_rel_err": {k_: round(e, 5) for k_, e in errs.items()},
            "tolerance": BF16_TOL,
            "compile_s": round(t1 - t0, 2), "first_run_s": round(t2 - t1, 3),
        })

    # The ragged route on a mesh over every device (n=1 honours an explicit
    # "ragged" for exactly this purpose): fwd + custom_vjp bwd vs a gather.
    impl = MODES[mode]["ragged_impl"]
    mesh = create_mesh(jax.devices())
    axis = mesh.axis_names[0]
    rows, dim, n_ids = 2048, 16, 64  # ids land on every shard of the mesh
    table = jax.random.normal(jax.random.key(0), (rows, dim), f32)
    ids = jax.random.randint(jax.random.key(1), (n_ids,), 0, rows)
    cot = jax.random.normal(jax.random.key(2), (n_ids, dim))
    ctx = ParallelContext(
        axis_name=axis, sharded_embeddings=True, embedding_impl=impl
    )

    def fwd_bwd(t, i, c):
        def lookup_loss(tt):
            return jnp.sum(embedding_lookup(tt, i, ctx, dim=dim) * c)

        val, grad = jax.value_and_grad(lookup_loss)(t)
        return val[None], grad

    place = lambda a: jax.device_put(a, NamedSharding(mesh, P(axis)))  # noqa: E731
    lowered = jax.jit(shard_map(
        fwd_bwd, mesh=mesh, in_specs=(P(axis), P(axis), P(axis)),
        out_specs=(P(axis), P(axis)), check_vma=False,
    )).lower(place(pack_table(table, dim)), place(ids), place(cot))
    ragged_ops = lowered.as_text().count("ragged_all_to_all")
    if on_chip and ragged_ops == 0:
        raise RuntimeError("no ragged_all_to_all in the lowering")
    vals, grad = lowered.compile()(
        place(pack_table(table, dim)), place(ids), place(cot)
    )
    val = jnp.sum(vals)  # one partial per device
    exp_val, exp_grad = jax.value_and_grad(
        lambda t: jnp.sum(jnp.take(t, ids, axis=0) * cot)
    )(table)
    np.testing.assert_allclose(float(val), float(exp_val), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(grad).reshape(-1, dim)[:rows], np.asarray(exp_grad),
        rtol=1e-5, atol=1e-6,
    )

    cache = compile_cache_stats()
    result = {
        "phase": "kernels",
        "device": device,
        "flash_attention": flash,
        "ragged_lookup": {
            "impl": impl, "mesh_devices": int(mesh.devices.size),
            "ragged_all_to_all_ops": ragged_ops, "rtol": 1e-5,
        },
        "compile_s": cache["backend_compile_s"],
        "compile_cache": {
            k_: cache[k_] for k_ in ("dir", "hits", "misses")
        },
    }
    with open(result_path, "w") as f:
        json.dump(result, f)


# ---------------------------------------------------------------------------
# entry
# ---------------------------------------------------------------------------


def run(mode: str) -> int:
    if not os.path.isdir(os.path.join(ROOT, "elasticdl_tpu")):
        raise PhaseFailed(
            "layout", f"{ROOT} holds chip_smoke.py but not the repository"
        )
    shutil.rmtree(OUT, ignore_errors=True)
    runner = Runner(mode)
    results = []

    def done(result: dict) -> dict:
        say(f"{result['phase']}: ok {json.dumps(result)}")
        results.append(result)
        return result

    done(phase_native_build())
    done(phase_kernels(runner))
    done(phase_data(runner))
    first = done(phase_deepfm(runner))
    done(phase_deepfm(runner, resume_from=first))
    done(phase_transformer(runner))

    devices = {
        json.dumps(r["device"], sort_keys=True)
        for r in results if "device" in r
    }
    if len(devices) != 1:
        raise PhaseFailed("summary", f"phases disagree on the device: {devices}")
    device = next(r["device"] for r in results if "device" in r)
    for result in results:
        print(json.dumps(result), flush=True)
    summary = {
        "device": {
            "platform": device["platform"],
            "kind": device["device_kind"],
            "count": device["count"],
        },
    }
    if mode == "chip":
        print(json.dumps({"ok": True, **summary}), flush=True)
    else:
        print(json.dumps({
            "rehearsal": True,
            "note": "CPU rehearsal of the control flow at tiny sizes; "
                    "says nothing about the chip",
            **summary,
        }), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearsal", action="store_true",
        help="tiny sizes on the CPU; exercises the control flow, never "
             "prints the pass marker",
    )
    ap.add_argument("--child", choices=("kernels", "data"), help=argparse.SUPPRESS)
    ap.add_argument("--mode", choices=tuple(MODES), help=argparse.SUPPRESS)
    ap.add_argument("--result", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "kernels":
        child_kernels(args.mode, args.result)
        return 0
    if args.child == "data":
        child_data(args.mode)
        return 0
    try:
        return run("rehearsal" if args.rehearsal else "chip")
    except PhaseFailed as e:
        say(f"FAILED {e}")
        return 1


if __name__ == "__main__":
    sys.exit(main())
