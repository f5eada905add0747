#!/usr/bin/env python3
"""Kimi Linear's share (``kimi_linear_48b_a3b_ep32_l5``) at its published
widths on the chip, outside any timed window.

- ``--ops``: step 0's table.  The new op and its glue at the cell's shapes
  (one 8192-token sequence), forward and forward + backward, median of seven:
  the chunked gated delta rule ``[1, 8192, 32, 128]`` (``ops/delta_rule.delta_rule``,
  chunk 64), one convolution + silu, the gated norm a head, the router at 8
  of 256 (``ops/moe.route``), the expert layer (8 of 256 held:
  ``ops/moe.expert_ffn``).
- ``--checks N [--controls all|none|a,b] [--own_step a,b]``: the
  configuration's checks on N seeds of tokens, read as the benchmark's
  reference child reads them (``kimi_linear_48b_a3b_ep32_l5_reference.py``:
  ``system_of_the_checks`` against ``reference_of_the_checks``), sound and
  under each control the reference names (``CONTROLS``), each judged by
  ``benchmark/run.py``'s ``reference_problems`` against the limits in the
  configuration's file.  Where the limits and the readings in that file come
  from.

    chiprun -- python3 benchmark/sizing/kimi_linear_against_reference.py --ops --checks 3

Prints one JSON object and writes it to ``chiprun_out/kimi_linear_against_reference.json``.
It decides nothing: PERF.md and the configuration's file hold the readings
and the limits drawn from them.  ``--rehearsal benchmark/rehearsal/kimi_linear_job.json``
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

CONFIG = "kimi_linear_48b_a3b_ep32_l5"
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


def op_table(p: dict, batch: int) -> dict:
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.linear_attention import l2norm
    from elasticdl_tpu.ops import delta_rule as delta_ops
    from elasticdl_tpu.ops import moe
    from elasticdl_tpu.ops import ssm as ssm_ops

    kda = p["linear_attn_config"]
    seq, heads, hd = int(p["seq_len"]), int(kda["num_heads"]), int(kda["head_dim"])
    d, f = int(p["hidden_size"]), int(p["moe_intermediate_size"])
    experts, held, top_k = int(p["num_experts"]), int(p["experts_held"]), int(p["num_experts_per_token"])
    inner = heads * hd
    ks = iter(jax.random.split(jax.random.key(0), 16))
    bf = lambda *shape: jax.random.normal(next(ks), shape, jnp.bfloat16)  # noqa: E731
    q, k, v = l2norm(bf(batch, seq, heads, hd)) * hd ** -0.5, l2norm(bf(batch, seq, heads, hd)), bf(batch, seq, heads, hd)
    a = -jnp.exp(jax.random.uniform(next(ks), (heads,), minval=0.0, maxval=jnp.log(16.0)))
    g = a[:, None] * jax.nn.softplus(jax.random.normal(next(ks), (batch, seq, heads, hd)) - 4.0)
    beta = jax.nn.sigmoid(jax.random.normal(next(ks), (batch, seq, heads)))
    tokens = bf(batch * seq, d)
    wg = 0.02 * jax.random.normal(next(ks), (d, experts))
    keys = dict(scoring_func="sigmoid", norm_topk_prob=True, routed_scaling_factor=float(p["routed_scaling_factor"]))
    routing = moe.route(tokens, wg, top_k, **keys)
    w_gate, w_up, w_down = 0.02 * bf(held, d, f), 0.02 * bf(held, d, f), 0.02 * bf(held, f, d)
    square = lambda fn: (lambda *args: jnp.sum(fn(*args).astype(jnp.float32) ** 2))  # noqa: E731
    forms = {
        "delta_rule": (lambda q, k, v, g, beta: delta_ops.delta_rule(q, k, v, g, beta, chunk=64), (q, k, v, g, beta)),
        "conv_silu": (lambda t, w, bias: jax.nn.silu(ssm_ops.causal_conv(t, w, bias)),
                      (bf(batch, seq, inner), 0.5 * jax.random.normal(next(ks), (4, inner)), jnp.zeros((inner,)))),
        "gated_head_norm": (lambda o, z, gain: delta_ops.gated_head_norm(o, z, gain, 1e-5),
                            (bf(batch, seq, heads, hd), bf(batch, seq, heads, hd), jnp.ones((hd,)))),
        "route_8_of_256": (lambda u, wg: moe.route(u, wg, top_k, **keys).weights, (tokens, wg)),
        "experts_8_of_256": (lambda u, w, w_gate, w_up, w_down: moe.expert_ffn(
            u, routing.choices, w, w_gate, w_up, w_down, n_experts=experts, lo=0)[0], (tokens, routing.weights, w_gate, w_up, w_down)),
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
                # a control's trainer, taps and executables: the host has 40 GiB for nine systems of 602 M parameters
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
        own_step = OWN_STEP + tuple(filter(None, args.own_step.split(",")))
        result["checks"] = check_table(config, ref, batch, [args.seed + 104729 * i for i in range(args.checks)], controls, own_step)
    print(json.dumps(result))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kimi_linear_against_reference.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
