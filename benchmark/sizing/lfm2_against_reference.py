#!/usr/bin/env python3
"""LFM2-8B-A1B's share (``lfm2_8b_a1b_ep4_l5``) at its published widths on the
chip, outside any timed window.

- ``--ops``: step 0's table.  The double-gated short convolution alone at the
  cell's shape ``[4, 8192, 2048]`` (bfloat16 operands, 3 taps), median of
  seven: the forward and the forward + gradient (of all three operands and the
  taps) on the Pallas kernel pair and on the XLA chain (the path is asked for
  by name: ``ops/short_conv._gated`` under each), beside what the needed bytes
  (8 an element forward, 14 backward: ``costs/lfm2_flops.py``) take at the
  chip's peak HBM bandwidth.
- ``--checks N [--controls all|none|a,b] [--own_step a,b]``: the
  configuration's checks on N seeds of tokens, read as the benchmark's
  reference child reads them (``lfm2_8b_a1b_ep4_l5_reference.py``:
  ``system_of_the_checks`` against ``reference_of_the_checks``), sound and
  under each control the reference names (``CONTROLS``), each judged by
  ``benchmark/run.py``'s ``reference_problems`` against the limits in the
  configuration's file.  Where the limits and the readings in that file come
  from.

    chiprun -- python3 benchmark/sizing/lfm2_against_reference.py --ops --checks 3

Prints one JSON object and writes it to ``chiprun_out/lfm2_against_reference.json``.
It decides nothing: PERF.md and the configuration's file hold the readings
and the limits drawn from them.  ``--rehearsal benchmark/rehearsal/lfm2_job.json``
is its CPU dry run at the rehearsal's sizes.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
from resolve import Bench, load_module  # noqa: E402

CONFIG = "lfm2_8b_a1b_ep4_l5"
#: controls that nothing but the train step can catch run their own (the others read the forward checks alone,
#: which the configuration's file names for them; ``--own_step`` adds to these)
OWN_STEP = ("no_weight_decay", "state_unchanged")


def timed(fn, *args, repeats: int = 7) -> float:
    """Median seconds of ``fn(*args)``, compiled and warm, each run ended by
    ``block_until_ready``."""
    import jax

    jax.block_until_ready(fn(*args))
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        out.append(time.perf_counter() - t0)
    return statistics.median(out)


def op_table(p: dict, batch: int, costs: dict, hbm_bytes_per_s: float) -> dict:
    """Milliseconds of the double-gated short convolution at the cell's shape,
    on the kernel pair and on the XLA chain, forward and forward + gradient."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import short_conv as conv_ops
    from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_XLA_REFERENCE

    seq, d, taps = int(p["seq_len"]), int(p["hidden_size"]), int(p["conv_L_cache"])
    keys = jax.random.split(jax.random.key(0), 4)
    b, c, z = (jax.random.normal(key, (batch, seq, d), jnp.bfloat16) for key in keys[:3])
    w = jax.random.uniform(keys[3], (taps, d), jnp.float32, -taps ** -0.5, taps ** -0.5)
    elements = batch * seq * d
    table = {"needed_fwd_ms_at_peak": 1e3 * 8 * elements / hbm_bytes_per_s, "needed_fwd_bwd_ms_at_peak": 1e3 * 22 * elements / hbm_bytes_per_s,
             "elements_a_layer": elements, "bytes_a_step_all_layers": costs["gated_conv_bytes_per_step"]}
    paths = {"kernels": PATH_PALLAS_COMPILED, "xla_chain": PATH_XLA_REFERENCE}
    if jax.default_backend() != "tpu":
        del paths["kernels"]
    for name, path in paths.items():
        op = lambda b, c, z, w, path=path: conv_ops._gated(b, c, z, w, path)  # noqa: E731
        square = lambda b, c, z, w: jnp.sum(op(b, c, z, w).astype(jnp.float32) ** 2)  # noqa: E731
        fwd = 1e3 * timed(jax.jit(op), b, c, z, w)
        every = 1e3 * timed(jax.jit(jax.grad(square, argnums=(0, 1, 2, 3))), b, c, z, w)
        table[name] = {"fwd_ms": fwd, "fwd_bwd_ms": every, "bwd_ms": every - fwd,
                       "fwd_hbm_pct": 100 * table["needed_fwd_ms_at_peak"] / fwd, "fwd_bwd_hbm_pct": 100 * table["needed_fwd_bwd_ms_at_peak"] / every}
        print(name, table[name], flush=True)
    return table


def check_table(config: dict, ref, batch: int, seeds: list, controls: tuple, own_step: tuple = OWN_STEP) -> dict:
    """The configuration's checks, read as the benchmark's reference child
    reads them, on each seed's minibatch: the system as it is, then under
    each control of ``ref.CONTROLS``; every reading judged by
    ``benchmark/run.py``'s ``reference_problems`` against the limits in the
    configuration's file.  A control that leaves the train step alone (one
    not in ``own_step``) reads the forward checks only (the step's readings
    are the sound ones)."""
    import gc

    import jax

    import run  # benchmark/run.py: the judge

    p = config["model_params"]
    out: dict = {name: [] for name in ("sound",) + controls}
    for seed in seeds:
        toks = np.random.default_rng(seed).integers(0, int(p["vocab_size"]), (batch, int(p["seq_len"]) + 1), dtype=np.int32)
        tokens, labels = toks[:, :-1], toks[:, 1:]
        sound = ref.system_of_the_checks(config, tokens, labels)
        reference = ref.reference_of_the_checks(p, sound["weights"], tokens, labels, to_host=True)
        for name in out:
            t0 = time.time()
            system = sound if name == "sound" else ref.system_of_the_checks(config, tokens, labels, name, train=name in own_step)
            got = ref.readings_of(system, reference)
            judged = {"loss": reference["loss"], "checks": dict(got)}
            limits = {k: v for k, v in config.get("checks", {}).items() if k in got and v.get("limit") is not None}
            problems = run.reference_problems(judged, reference["loss"], config["reference_tolerance"], limits)
            out[name].append({"seed": seed, "readings": got, "correct": not problems, "problems": problems,
                              "losses": {"reference": reference["loss"], "train_step": system.get("trained", {}).get("loss")}})
            print(seed, name, f"{time.time() - t0:.0f} s", "correct" if not problems else f"NOT correct: {problems}",
                  {k: float(f"{v:.4g}") for k, v in got.items()}, flush=True)
            if name != "sound":
                # a control's trainer, taps and executables: the host has 40 GiB for eight systems of 508 M parameters
                del system
                ref._system.cache_clear()
                jax.clear_caches()
                gc.collect()
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--checks", type=int, default=0, help="read the configuration's checks on this many seeds")
    ap.add_argument("--controls", default="all", help="'all', 'none' or the controls' names, comma-separated")
    ap.add_argument("--own_step", default="", help="controls that run their own train step besides OWN_STEP, comma-separated")
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--rehearsal", default="")
    args = ap.parse_args()

    bench = Bench(ROOT)
    config = bench.config(CONFIG)
    traffic = bench.traffic(bench.cell("lfm2_job")["traffic"])
    if args.rehearsal:
        with open(args.rehearsal) as f:
            override = json.load(f)
        config["model_params"].update(override["model_params"])
        traffic.update(override["traffic"])
    p, batch = config["model_params"], int(traffic["minibatch_size"])

    import jax

    ref = load_module(bench.reference_path(CONFIG))
    d = jax.devices()[0]
    result = {"device": {"platform": d.platform, "kind": d.device_kind}, "model_params": p, "sequences": batch}
    if args.ops:
        costs = bench.costs(config["costs"]).compute(config, traffic)
        peaks = bench.peaks(d.device_kind) if d.platform == "tpu" else {"hbm_bytes_per_s": float("nan")}
        result["ops"] = op_table(p, batch, costs, peaks["hbm_bytes_per_s"])
    if args.checks:
        controls = {"all": ref.CONTROLS, "none": ()}.get(args.controls, tuple(args.controls.split(",")))
        jax.config.update("jax_default_matmul_precision", "highest")  # as the reference child sets it
        own_step = OWN_STEP + tuple(filter(None, args.own_step.split(",")))
        result["checks"] = check_table(config, ref, batch, [args.seed + 104729 * i for i in range(args.checks)], controls, own_step)
    print(json.dumps(result))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "lfm2_against_reference.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
