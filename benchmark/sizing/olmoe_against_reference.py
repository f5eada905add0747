#!/usr/bin/env python3
"""OLMoE at its published widths on the chip, outside any timed window: the
system (``moe_lm.model_spec``, bfloat16 compute, flash and grouped-matmul
kernels) against the plain float32 reference on ONE seeded minibatch from
the same weights — logits, per-expert slot counts, the three loss terms,
and the gradient's norm per parameter group.  Then the reference once more
in the precision below the configuration's (the same weights rounded to
bfloat16, so bfloat16 activations, router logits and head), forward only:
which of the loss terms and slot counts tell that precision from float32
(``reference_in_bfloat16``).  And the router ALONE, where its precision can
be seen: what the model's ``apply`` hands ``ops/moe.route`` and gets back,
against float64, beside the same with the operands rounded to bfloat16 on
their way to the op (``router_logits``, by the reference file's
``router_checks``: what ``correct``'s two checks read in every traced run;
``--router_seeds 12 --router_only`` reads only that, on a dozen seeds of
tokens, which is where the checks' limits come from).

    chiprun -- python3 benchmark/sizing/olmoe_against_reference.py [--seed N] [--sequences 4]

Prints one JSON object and writes it to ``chiprun_out/olmoe_against_reference.json``.
It checks nothing and times nothing: PERF.md holds the reading and the
limits drawn from it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402
from resolve import Bench, load_module  # noqa: E402

GROUPS = {
    "attention": ("wq", "wk", "wv", "wo"), "router": ("router",),
    "experts": ("w_gate", "w_up", "w_down"), "head": ("head",), "embedding": ("tok_emb",),
    "norms": ("attn_norm", "q_norm", "k_norm", "ffn_norm", "norm_f"),
}


def group_of(path) -> str:
    leaf = path[-1].key
    return next(g for g, names in GROUPS.items() if leaf in names)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--sequences", type=int, default=4)
    ap.add_argument("--router_seeds", type=int, default=1, help="read the router alone on this many seeds")
    ap.add_argument("--router_only", action="store_true", help="stop after the router's readings")
    ap.add_argument("--rehearsal", default="", help="a rehearsal file whose model_params replace the widths (CPU dry run)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.spec import load_model_spec

    bench = Bench(ROOT)
    config = bench.config("olmoe_1b_7b_l1")
    p = config["model_params"]
    if args.rehearsal:
        with open(args.rehearsal) as f:
            p.update(json.load(f)["model_params"])
    seq, vocab = int(p["seq_len"]), int(p["vocab_size"])
    toks = np.random.default_rng(args.seed).integers(0, vocab, (args.sequences, seq + 1)).astype(np.int32)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = spec.init(jax.random.key(0))
    pairs = toks[:, :-1].size * len(params["blocks"])  # (layer, token) pairs: the routers' shares are over these

    # -- the routers as the MODEL runs them, against float64, and the same with the operands rounded to bfloat16 on
    # their way to the op (the precision below the configuration's) -- the reference file's ``router_checks``: what
    # ``correct``'s two checks read in every traced run
    reference = load_module(bench.reference_path("olmoe_1b_7b_l1"))
    top_k = int(p["num_experts_per_tok"])
    router_logits = {"slots": int(toks[:, :-1].size * top_k), "seeds": {}}
    sound = reference.routers_of_the_model(spec)
    lower = reference.routers_of_the_model(spec, lambda u, wg: (u.astype(jnp.bfloat16), wg.astype(jnp.bfloat16)))
    for seed in [args.seed + 1000003 * i for i in range(args.router_seeds)]:
        batch = np.random.default_rng(seed).integers(0, vocab, (args.sequences, seq + 1)).astype(np.int32)
        router_logits["seeds"][str(seed)] = {
            "system": reference.router_checks(sound(params, batch[:, :-1], batch[:, 1:]), params, top_k),
            "bfloat16": reference.router_checks(lower(params, batch[:, :-1], batch[:, 1:]), params, top_k),
        }
    if args.router_only:
        print(json.dumps({"device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}, "router_logits": router_logits}))
        return

    # -- the system, on the whole minibatch, as the train step computes it --
    def system(params, tokens, labels):
        batch = {"tokens": tokens, "labels": labels}
        out = spec.apply(params, batch, train=True)
        return spec.loss(out, batch), (spec.metrics(out, batch), out["logits"], out["router"]["f"])

    (_, (metrics, logits, f)), grads = jax.jit(jax.value_and_grad(system, has_aux=True))(params, toks[:, :-1], toks[:, 1:])
    sys_logits = np.asarray(logits)
    sys_counts = np.asarray(f) * pairs  # [k, E] slots by choice rank and expert
    sys_terms = {k: float(metrics[k]) for k in ("loss", "ce", "lb_loss", "z_loss")}
    sys_grads = jax.tree.map(np.asarray, grads)
    del logits, grads, metrics

    # -- the reference, a sequence at a time (its own micro-batching) --
    jax.config.update("jax_default_matmul_precision", "highest")
    _, loss_terms = reference.build(p)
    shares = jax.jit(lambda params, t, l: loss_terms(params, t, l)["f_sum"])
    parts = [toks[i : i + 1] for i in range(args.sequences)]
    counts = sum(np.asarray(shares(params, part[:, :-1], part[:, 1:])) for part in parts)
    f_all = jnp.asarray(counts / pairs)

    def micro(params, t, l):
        terms = loss_terms(params, t, l, f_all)
        return terms["loss"], terms

    grad_fn = jax.jit(jax.value_and_grad(micro, has_aux=True))
    ref_terms = dict.fromkeys(sys_terms, 0.0)
    ref_grads, worst, err2, ref2, ref_max = None, 0.0, 0.0, 0.0, 0.0
    for i, part in enumerate(parts):
        (_, terms), g = grad_fn(params, part[:, :-1], part[:, 1:])
        for k in ref_terms:
            ref_terms[k] += float(terms[k]) / len(parts)
        want = np.asarray(terms["logits"])[0]
        diff = sys_logits[i] - want
        worst, ref_max = max(worst, float(np.abs(diff).max())), max(ref_max, float(np.abs(want).max()))
        err2, ref2 = err2 + float(np.sum(diff.astype(np.float64) ** 2)), ref2 + float(np.sum(want.astype(np.float64) ** 2))
        g = jax.tree.map(lambda a: np.asarray(a) / len(parts), g)
        ref_grads = g if ref_grads is None else jax.tree.map(np.add, ref_grads, g)

    # -- the reference in the precision below: its one code path, bfloat16 weights --
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)
    low_counts = sum(np.asarray(shares(low, part[:, :-1], part[:, 1:])) for part in parts)
    low_f = jnp.asarray(low_counts / pairs)
    terms_fn = jax.jit(lambda params, t, l: {k: v for k, v in loss_terms(params, t, l, low_f).items() if k in sys_terms})
    low_terms = dict.fromkeys(sys_terms, 0.0)
    for part in parts:
        for k, v in terms_fn(low, part[:, :-1], part[:, 1:]).items():
            low_terms[k] += float(v) / len(parts)
    del low

    norms = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(sys_grads), jax.tree.leaves(ref_grads)):
        entry = norms.setdefault(group_of(path), {"system2": 0.0, "reference2": 0.0, "difference2": 0.0})
        a, b = a.astype(np.float64), b.astype(np.float64)
        entry["system2"] += float(np.sum(a * a))
        entry["reference2"] += float(np.sum(b * b))
        entry["difference2"] += float(np.sum((a - b) ** 2))
    per_expert_sys, per_expert_ref = sys_counts.sum(0), counts.sum(0)
    result = {
        "device": {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind},
        "seed": args.seed, "sequences": args.sequences, "tokens": int(toks[:, :-1].size),
        "logits": {"max_abs_difference": worst, "max_abs_reference": ref_max, "relative_l2": (err2 / ref2) ** 0.5},
        "slots": {
            "total": int(per_expert_ref.sum()),
            "moved_to_another_expert": float(np.abs(per_expert_sys - per_expert_ref).sum() / 2),
            "moved_by_rank_and_expert": float(np.abs(sys_counts - counts).sum() / 2),
            "fullest_expert": [float(per_expert_sys.max()), float(per_expert_ref.max())],
            "emptiest_expert": [float(per_expert_sys.min()), float(per_expert_ref.min())],
        },
        "terms": {k: {"system": sys_terms[k], "reference": ref_terms[k], "relative": abs(sys_terms[k] - ref_terms[k]) / abs(ref_terms[k])} for k in sys_terms},
        "router_logits": router_logits,
        "reference_in_bfloat16": {
            "terms": {k: {"value": low_terms[k], "relative": abs(low_terms[k] - ref_terms[k]) / abs(ref_terms[k])} for k in sys_terms},
            "slots_moved_to_another_expert": float(np.abs(low_counts.sum(0) - per_expert_ref).sum() / 2),
            "slots_moved_by_rank_and_expert": float(np.abs(low_counts - counts).sum() / 2),
        },
        "gradient_norms": {
            g: {"system": e["system2"] ** 0.5, "reference": e["reference2"] ** 0.5, "relative_l2_of_difference": (e["difference2"] / e["reference2"]) ** 0.5}
            for g, e in norms.items()
        },
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "olmoe_against_reference.json"), "w") as out:
        json.dump(result, out, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
