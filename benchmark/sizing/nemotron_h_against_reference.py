#!/usr/bin/env python3
"""Nemotron 3 Super's share (``nemotron3_super_tp4_ep64_l11``) at its
published widths on the chip, outside any timed window.

- ``--ops``: step 0's table.  The new ops at the cell's shapes (one
  8192-token sequence), forward and forward + backward, median of seven: the
  chunked scan ``[1, 8192, 32, 64]`` over 2 groups of state 128
  (``ops/ssm.ssm_scan``), the convolution + silu and the gated group norm,
  the router at 22 of 512 (``ops/moe.route``), the latent expert layer (8 of
  512 held, two matrices: ``ops/moe.expert_ffn``).
- ``--checks N [--controls all|none|a,b]``: the configuration's checks on N
  seeds of tokens, read as the benchmark's reference child reads them
  (``nemotron3_super_tp4_ep64_l11_reference.py``: ``system_of_the_checks``
  against ``reference_of_the_checks``), sound and under each control the
  reference names (``CONTROLS``), each judged by ``benchmark/run.py``'s
  ``reference_problems`` against the limits in the configuration's file.
  Where the limits and the readings in that file come from.

    chiprun -- python3 benchmark/sizing/nemotron_h_against_reference.py --ops --checks 3

Prints one JSON object and writes it to ``chiprun_out/nemotron_h_against_reference.json``.
It decides nothing: PERF.md and the configuration's file hold the readings
and the limits drawn from them.  ``--rehearsal benchmark/rehearsal/nemotron3_job.json``
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

CONFIG = "nemotron3_super_tp4_ep64_l11"
#: controls whose fault is in the train step too (the others read the forward checks alone)
OWN_STEP = ("bfloat16_decay", "no_carried_state", "all_bfloat16", "no_weight_decay", "state_unchanged")


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


def op_table(p: dict, batch: int) -> dict:
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import ssm as ssm_ops

    seq, heads, width, state = int(p["seq_len"]), int(p["mamba_heads_held"]), int(p["mamba_head_dim"]), int(p["ssm_state_size"])
    groups, chunk = heads * int(p["n_groups"]) // int(p["mamba_num_heads"]), int(p["chunk_size"])
    d, latent, f = int(p["hidden_size"]), int(p["moe_latent_size"]), int(p["moe_intermediate_size"])
    experts, held, top_k = int(p["num_experts"]), int(p["experts_held"]), int(p["num_experts_per_tok"])
    inner, conv_dim = heads * width, heads * width + 2 * groups * state
    ks = iter(jax.random.split(jax.random.key(0), 16))
    bf = lambda *shape: jax.random.normal(next(ks), shape, jnp.bfloat16)  # noqa: E731
    x, b, c = bf(batch, seq, heads, width), bf(batch, seq, groups, state), bf(batch, seq, groups, state)
    dt = jax.nn.softplus(jax.random.normal(next(ks), (batch, seq, heads)) - 4.0)
    a = -jnp.exp(jax.random.uniform(next(ks), (heads,), minval=0.0, maxval=jnp.log(16.0)))
    tokens, lat = bf(batch * seq, d), bf(batch * seq, latent)
    wg = 0.02 * jax.random.normal(next(ks), (d, experts))
    routing = moe.route(tokens, wg, top_k, scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=5.0)
    w_up, w_down = 0.02 * bf(held, latent, f), 0.02 * bf(held, f, latent)
    square = lambda fn: (lambda *args: jnp.sum(fn(*args).astype(jnp.float32) ** 2))  # noqa: E731
    forms = {
        "ssm_scan": (lambda x, dt, a, b, c: ssm_ops.ssm_scan(x, dt, a, b, c, jnp.ones_like(a), chunk=chunk), (x, dt, a, b, c)),
        "conv_silu": (lambda t, w, bias: jax.nn.silu(ssm_ops.causal_conv(t, w, bias)),
                      (bf(batch, seq, conv_dim), 0.5 * jax.random.normal(next(ks), (4, conv_dim)), jnp.zeros((conv_dim,)))),
        "gated_group_norm": (lambda y, z, g: ssm_ops.gated_group_norm(y, z, g, groups, 1e-5),
                             (bf(batch, seq, inner), bf(batch, seq, inner), jnp.ones((inner,)))),
        "route_22_of_512": (lambda u, wg: moe.route(u, wg, top_k, scoring_func="sigmoid", norm_topk_prob=True,
                                                    routed_scaling_factor=5.0).weights, (tokens, wg)),
        "latent_experts_8_of_512": (lambda u, w, w_up, w_down: moe.expert_ffn(
            u, routing.choices, w, None, w_up, w_down, n_experts=experts, lo=0)[0], (lat, routing.weights, w_up, w_down)),
    }
    table = {}
    for name, (fn, args) in forms.items():
        row = {}
        for what, program in (("fwd_ms", jax.jit(fn)),
                              ("fwd_bwd_ms", jax.jit(jax.grad(square(fn), argnums=tuple(range(len(args))))))):
            try:
                row[what] = 1e3 * timed(program, *args)
            except Exception as e:  # noqa: BLE001 — a form that does not fit is a finding, not a failure
                row[what] = None
                row[what + "_error"] = str(e).splitlines()[0][:300]
        table[name] = row
        print(name, row, flush=True)
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
            limits = {k: v for k, v in config.get("checks", {}).items() if k in got}
            problems = run.reference_problems(judged, reference["loss"], config["reference_tolerance"], limits)
            out[name].append({"seed": seed, "readings": got, "correct": not problems, "problems": problems,
                              "losses": {"reference": reference["loss"], "train_step": system.get("trained", {}).get("loss")}})
            print(seed, name, f"{time.time() - t0:.0f} s", "correct" if not problems else f"NOT correct: {problems}",
                  {k: float(f"{v:.4g}") for k, v in got.items()}, flush=True)
            if name != "sound":
                # a control's trainer, taps and executables: the host has 40 GiB for eight systems of 773.6 M parameters
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
    ap.add_argument("--ops", action="store_true")
    ap.add_argument("--rehearsal", default="")
    args = ap.parse_args()

    bench = Bench(ROOT)
    config = bench.config(CONFIG)
    batch = 1
    if args.rehearsal:
        with open(args.rehearsal) as f:
            override = json.load(f)
        config["model_params"].update(override["model_params"])
        batch = int(override["traffic"]["minibatch_size"])
    p = config["model_params"]

    import jax

    ref = load_module(bench.reference_path(CONFIG))
    d = jax.devices()[0]
    result = {"device": {"platform": d.platform, "kind": d.device_kind}, "model_params": p, "sequences": batch}
    if args.ops:
        result["ops"] = op_table(p, batch)
    if args.checks:
        controls = {"all": ref.CONTROLS, "none": ()}.get(args.controls, tuple(args.controls.split(",")))
        jax.config.update("jax_default_matmul_precision", "highest")  # as the reference child sets it
        result["checks"] = check_table(config, ref, batch, [args.seed + 104729 * i for i in range(args.checks)], controls)
    print(json.dumps(result))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "nemotron_h_against_reference.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
