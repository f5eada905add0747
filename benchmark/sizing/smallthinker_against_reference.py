#!/usr/bin/env python3
"""SmallThinker-21BA3B's share (``smallthinker_21b_a3b_ep8_l8``) at its
published widths on the chip, outside any timed window.

- ``--ops``: step 0's tables.  (1) The flash kernels alone at the cell's shape
  ``[1, 16384, 28, 128]`` under the window of 4096 keys and full causal, median
  of seven: the forward, the forward + dQ (the gradient of q alone: XLA drops
  the other backward kernel, whose results nothing reads) and the forward +
  dK/dV, from which dQ and dK/dV alone follow by subtraction; beside them what
  the needed work (the pairs inside the windows; the causal half) would take
  at the chip's peak, the pairs a pass multiplies (``window_pairs_computed``)
  and the largest error of o, dq, dk, dv against the float32 masked softmax
  computed in blocks of queries on the kernels' OWN operands (the reference's
  ``masked_attention``).  (2) ONE expert layer forward + backward at
  ``[16384, 2560]``, k = 6, 8 of 64 held, width 768, relu-gated, under each
  tiling of ``--tilings`` (``ops/moe.GMM_TILING`` as it is first).
- ``--checks N [--controls all|none|a,b] [--own_step a,b]``: the
  configuration's checks on N seeds of tokens, read as the benchmark's
  reference child reads them (``smallthinker_21b_a3b_ep8_l8_reference.py``:
  ``system_of_the_checks`` against ``reference_of_the_checks``), sound and
  under each control the reference names (``CONTROLS``), each judged by
  ``benchmark/run.py``'s ``reference_problems`` against the limits in the
  configuration's file.  Where the limits and the readings in that file come
  from.
- ``--slots``: the model's forward pass on one seed's tokens: each layer's
  slots by expert (``spec.apply(...)["router_slots"]``): the held experts'
  share, the fullest held expert over the mean.

    chiprun -- python3 benchmark/sizing/smallthinker_against_reference.py --ops --checks 3

Prints one JSON object and writes it to ``chiprun_out/smallthinker_against_reference.json``.
It decides nothing: PERF.md and the configuration's file hold the readings
and the limits drawn from them.  ``--rehearsal benchmark/rehearsal/smallthinker_job.json``
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

CONFIG = "smallthinker_21b_a3b_ep8_l8"
CELL = "smallthinker_job"
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


def flash_table(p: dict, ref, batch: int, costs: dict, peak_flops: float) -> dict:
    """Milliseconds of the three flash kernels at the cell's shape, under the
    window and full causal, and their largest errors against the float32
    masked softmax in blocks on their own operands."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import flash_attention as flash_ops
    from elasticdl_tpu.ops.ring_attention import _local_attention

    seq, heads, hd, window = int(p["seq_len"]), int(p["num_attention_heads"]), int(p["head_dim"]), int(p["sliding_window_size"])
    q, k, v = (jax.random.normal(key, (batch, seq, heads, hd), jnp.bfloat16) for key in jax.random.split(jax.random.key(0), 3))
    weigh = jax.random.normal(jax.random.key(1), (batch, seq, heads, hd), jnp.float32)
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    table = {}
    for name, w, unit in (("window", window, costs["window_unit_flops"]), ("full", None, costs["flash_unit_flops"])):
        attend = lambda q, k, v, w=w: _local_attention(q, k, v, True, window=w)  # noqa: E731
        square = lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32) ** 2)  # noqa: E731
        fwd = 1e3 * timed(jax.jit(attend), q, k, v)
        with_dq = 1e3 * timed(jax.jit(jax.grad(square, argnums=0)), q, k, v)
        with_dkv = 1e3 * timed(jax.jit(jax.grad(square, argnums=(1, 2))), q, k, v)
        every = 1e3 * timed(jax.jit(jax.grad(square, argnums=(0, 1, 2))), q, k, v)
        table[name] = {
            "fwd_ms": fwd, "dq_ms": with_dq - fwd, "dkv_ms": with_dkv - fwd, "fwd_bwd_ms": every,
            "needed_fwd_ms_at_peak": 1e3 * unit * costs["window_fwd_units"] / peak_flops,
            "needed_fwd_bwd_ms_at_peak": 1e3 * unit * (costs["window_fwd_units"] + costs["window_bwd_units"]) / peak_flops,
            "pairs_computed_a_head": flash_ops.window_pairs_computed(seq, w) if w else None,
            "pairs_needed_a_head": costs["pairs_window"] if w else costs["pairs_full"],
        }
        # against the float32 masked softmax in blocks of queries, on the same bfloat16 operands: o and the gradients of sum(o * weigh)
        # (the kernels OUTSIDE the reference's ``highest`` precision: Mosaic refuses a bfloat16 product traced under it)
        got = jax.jit(jax.value_and_grad(lambda q, k, v: jnp.sum(f32(attend(q, k, v)) * weigh), argnums=(0, 1, 2)))(q, k, v)
        o_got = jax.jit(attend)(q, k, v)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(jax.value_and_grad(
                lambda q, k, v: jnp.sum(ref.masked_attention(q, k, v, w or 0) * weigh), argnums=(0, 1, 2)))(f32(q), f32(k), f32(v))
            o_want = jax.jit(lambda q, k, v: ref.masked_attention(q, k, v, w or 0))(f32(q), f32(k), f32(v))
        off = lambda a, b: float(jnp.max(jnp.abs(f32(a) - b)) / jnp.max(jnp.abs(b)))  # noqa: E731
        table[name]["max_error"] = {"o": off(o_got, o_want), **{n: off(a, b) for n, a, b in zip(("dq", "dk", "dv"), got[1], want[1])}}
        print(name, table[name], flush=True)
    return table


def expert_table(p: dict, batch: int, tilings: list) -> dict:
    """Milliseconds of ONE expert layer (route + ``expert_ffn``, relu-gated), forward and forward + backward, at the
    cell's shape under each tiling of the grouped matmul."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import moe

    tokens, d, f = batch * int(p["seq_len"]), int(p["hidden_size"]), int(p["moe_ffn_hidden_size"])
    n_experts, held, k = int(p["moe_num_primary_experts"]), int(p.get("experts_held") or p["moe_num_primary_experts"]), int(p["moe_num_active_primary_experts"])
    keys = jax.random.split(jax.random.key(2), 5)
    u = jax.random.normal(keys[0], (tokens, d), jnp.bfloat16)
    wg = 0.02 * jax.random.normal(keys[1], (d, n_experts), jnp.float32)
    w_gate, w_up = (0.02 * jax.random.normal(key, (held, d, f), jnp.float32).astype(jnp.bfloat16) for key in keys[2:4])
    w_down = (0.02 * jax.random.normal(keys[4], (held, f, d), jnp.float32)).astype(jnp.bfloat16)

    def layer(u, w_gate, w_up, w_down):
        r = moe.route(u, wg, k, norm_topk_prob=True)
        y, _, given = moe.expert_ffn(u, r.choices, r.weights, w_gate, w_up, w_down, n_experts=n_experts, lo=int(p.get("first_expert_held", 0)), activation="relu")
        return jnp.sum(y.astype(jnp.float32) ** 2), given

    table, was = {}, moe.GMM_TILING
    for tiling in tilings:
        moe.GMM_TILING = tuple(tiling)
        jax.clear_caches()
        try:
            fwd = 1e3 * timed(jax.jit(layer), u, w_gate, w_up, w_down)
            both = 1e3 * timed(jax.jit(jax.grad(lambda *a: layer(*a)[0], argnums=(0, 1, 2, 3))), u, w_gate, w_up, w_down)
            given = jax.jit(layer)(u, w_gate, w_up, w_down)[1]
            table[",".join(map(str, tiling))] = {"fwd_ms": fwd, "fwd_bwd_ms": both, "rows_first_tier": int(given.first), "rows_second_tier": int(given.second)}
        except Exception as e:  # noqa: BLE001 — a tiling Mosaic refuses is a row of the table
            table[",".join(map(str, tiling))] = {"error": str(e)[:300]}
        print("tiling", tiling, table[",".join(map(str, tiling))], flush=True)
    moe.GMM_TILING = was
    jax.clear_caches()
    return table


def slots_table(config: dict, seed: int, batch: int) -> list:
    """Each layer's slots by expert on one seed's tokens, through the model's own forward pass at the job's dtypes."""
    import jax

    from elasticdl_tpu.models.spec import load_model_spec

    p = config["model_params"]
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    toks = np.random.default_rng(seed).integers(0, int(p["vocab_size"]), (batch, int(p["seq_len"]) + 1), dtype=np.int32)
    params = jax.jit(spec.init)(jax.random.key(0))
    slots = np.asarray(jax.jit(lambda w, b: spec.apply(w, b)["router_slots"])(params, {"tokens": toks[:, :-1], "labels": toks[:, 1:]}))
    lo, held = int(p.get("first_expert_held", 0)), int(p.get("experts_held") or slots.shape[1])
    rows = []
    for i, s in enumerate(slots):
        mine = s[lo:lo + held]
        rows.append({"layer": i, "held_share_pct": float(100 * mine.sum() / s.sum()), "held_max_over_mean": float(mine.max() / max(mine.mean(), 1e-9)),
                     "all_max_over_mean": float(s.max() / s.mean())})
        print(rows[-1], flush=True)
    return rows


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
                # a control's trainer, taps and executables: the host has 40 GiB for twelve systems of 644 M parameters
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
    ap.add_argument("--slots", action="store_true")
    ap.add_argument("--tilings", default="512,1024,1024;512,1280,1280;512,512,512;512,1280,768;512,640,1280",
                    help="grouped-matmul tilings (rows,contraction,columns) of --ops' expert table, ';'-separated")
    ap.add_argument("--rehearsal", default="")
    args = ap.parse_args()

    bench = Bench(ROOT)
    config = bench.config(CONFIG)
    traffic = bench.traffic(bench.cell(CELL)["traffic"])
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
        peaks = bench.peaks(d.device_kind) if d.platform == "tpu" else {"bf16_flops_per_s": float("nan")}
        result["flash"] = flash_table(p, ref, batch, costs, peaks["bf16_flops_per_s"])
        result["experts"] = expert_table(p, batch, [[int(x) for x in t.split(",")] for t in args.tilings.split(";")])
    if args.slots:
        result["slots"] = slots_table(config, args.seed, batch)
    if args.checks:
        controls = {"all": ref.CONTROLS, "none": ()}.get(args.controls, tuple(args.controls.split(",")))
        jax.config.update("jax_default_matmul_precision", "highest")  # as the reference child sets it
        own_step = OWN_STEP + tuple(filter(None, args.own_step.split(",")))
        result["checks"] = check_table(config, ref, batch, [args.seed + 104729 * i for i in range(args.checks)], controls, own_step)
    print(json.dumps(result))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "smallthinker_against_reference.json"), "w") as f:
        json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
