#!/usr/bin/env python3
"""kanana-2's share (``kanana2_30b_a3b_ep8_l5``) at its published widths on
the chip, outside any timed window: the system (``moe_lm.model_spec``,
bfloat16 compute, the flash kernels with a rotary part, grouped matmuls over
the held experts) against the plain float32 reference on ONE seeded
minibatch from the same weights — the loss, the logits, the slots every
expert of every router was sent, and the gradient's norm per parameter
group.  Then the reference once more in the precision below the
configuration's (the same weights rounded to bfloat16), forward only: what
of the loss and the slots tells that precision from float32.  And the
routers ALONE, where their precision can be seen: what the model's ``apply``
hands ``ops/moe.route`` and gets back, against float64, beside the same with
the operands rounded to bfloat16 on their way to the op — the program's
compute rounded one precision down, which must fail the configuration's
``checks`` (``--router_seeds 12 --router_only`` reads only that, on a dozen
seeds of tokens: where the checks' limits come from).  A twin of
``olmoe_against_reference.py``.

    chiprun -- python3 benchmark/sizing/kanana2_against_reference.py [--seed N] [--sequences 2]

Prints one JSON object and writes it to ``chiprun_out/kanana2_against_reference.json``.
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

CONFIG = "kanana2_30b_a3b_ep8_l5"
GROUPS = {
    "attention": ("wq", "wkv_a", "wkv_b", "wo"), "router": ("router",), "bias": ("router_bias",),
    "shared": ("ws_gate", "ws_up", "ws_down"), "head": ("head",), "embedding": ("tok_emb",),
    "norms": ("attn_norm", "kv_norm", "ffn_norm", "norm_f"),
}


def group_of(path, leaf) -> str:
    name = path[-1].key
    if name in ("w_gate", "w_up", "w_down"):
        return "experts" if leaf.ndim == 3 else "dense"
    return next(g for g, names in GROUPS.items() if name in names)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=2147483777)
    ap.add_argument("--sequences", type=int, default=2)
    ap.add_argument("--router_seeds", type=int, default=1, help="read the routers alone on this many seeds")
    ap.add_argument("--router_only", action="store_true", help="stop after the routers' readings")
    ap.add_argument("--rehearsal", default="", help="a rehearsal file whose model_params replace the widths (CPU dry run)")
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import optax

    from elasticdl_tpu.models.spec import load_model_spec

    bench = Bench(ROOT)
    config = bench.config(CONFIG)
    p = config["model_params"]
    if args.rehearsal:
        with open(args.rehearsal) as f:
            p.update(json.load(f)["model_params"])
    seq, vocab = int(p["seq_len"]), int(p["vocab_size"])
    toks = np.random.default_rng(args.seed).integers(0, vocab, (args.sequences, seq + 1)).astype(np.int32)
    spec = load_model_spec("elasticdl_tpu.models", config["model_def"], **p)
    params = spec.init(jax.random.key(0))
    device = {"platform": jax.devices()[0].platform, "kind": jax.devices()[0].device_kind}

    # -- the routers as the MODEL runs them, against float64, and the same with the operands rounded to bfloat16 on
    # their way to the op: what ``correct``'s two checks read in every traced run
    reference = load_module(bench.reference_path(CONFIG))
    top_k = int(p["num_experts_per_tok"])
    router_logits = {"choices_a_layer": int(toks[:, :-1].size * top_k), "seeds": {}}
    sound = reference.routers_of_the_model(spec)
    lower = reference.routers_of_the_model(spec, lambda u, wg: (u.astype(jnp.bfloat16), wg.astype(jnp.bfloat16)))
    for seed in [args.seed + 1000003 * i for i in range(args.router_seeds)]:
        batch = np.random.default_rng(seed).integers(0, vocab, (args.sequences, seq + 1)).astype(np.int32)
        router_logits["seeds"][str(seed)] = {
            "system": reference.router_checks(sound(params, batch[:, :-1], batch[:, 1:]), params, top_k),
            "bfloat16": reference.router_checks(lower(params, batch[:, :-1], batch[:, 1:]), params, top_k),
        }
    if args.router_only:
        print(json.dumps({"device": device, "router_logits": router_logits}))
        return

    # -- the system, on the whole minibatch, as the train step computes it --
    def system(params, tokens, labels):
        batch = {"tokens": tokens, "labels": labels}
        out = spec.apply(params, batch, train=True)
        return spec.loss(out, batch), (out["logits"], out["router_slots"], out["moe_counters"])

    (loss, (logits, slots, counters)), grads = jax.jit(jax.value_and_grad(system, has_aux=True))(params, toks[:, :-1], toks[:, 1:])
    sys_loss, sys_logits, sys_slots = float(loss), np.asarray(logits), np.asarray(slots)
    sys_counters = {k: float(v) for k, v in counters.items()}
    sys_grads = jax.tree.map(np.asarray, grads)
    del logits, grads, slots

    # -- the reference, a sequence at a time (its own micro-batching) --
    jax.config.update("jax_default_matmul_precision", "highest")
    forward = reference.build(p)

    def micro(params, t, l):
        logits, slots = forward(params, t)
        return optax.softmax_cross_entropy_with_integer_labels(logits, l).mean(), (logits, slots)

    grad_fn = jax.jit(jax.value_and_grad(micro, has_aux=True))
    parts = [toks[i : i + 1] for i in range(args.sequences)]
    ref_loss, ref_slots, ref_grads, worst, err2, ref2, ref_max = 0.0, 0.0, None, 0.0, 0.0, 0.0, 0.0
    for i, part in enumerate(parts):
        (loss, (want, slots)), g = grad_fn(params, part[:, :-1], part[:, 1:])
        ref_loss += float(loss) / len(parts)
        ref_slots = ref_slots + np.asarray(slots)
        want = np.asarray(want)[0]
        diff = sys_logits[i] - want
        worst, ref_max = max(worst, float(np.abs(diff).max())), max(ref_max, float(np.abs(want).max()))
        err2, ref2 = err2 + float(np.sum(diff.astype(np.float64) ** 2)), ref2 + float(np.sum(want.astype(np.float64) ** 2))
        g = jax.tree.map(lambda a: np.asarray(a) / len(parts), g)
        ref_grads = g if ref_grads is None else jax.tree.map(np.add, ref_grads, g)
    del g

    # -- the reference in the precision below: its one code path, bfloat16 weights, forward only --
    low = jax.tree.map(lambda a: a.astype(jnp.bfloat16), params)

    @jax.jit
    def low_fn(params, t, l):
        loss, (_, slots) = micro(params, t, l)
        return loss, slots

    low_loss, low_slots = 0.0, 0.0
    for part in parts:
        loss, slots = low_fn(low, part[:, :-1], part[:, 1:])
        low_loss, low_slots = low_loss + float(loss) / len(parts), low_slots + np.asarray(slots)
    del low

    norms = {}
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(sys_grads), jax.tree.leaves(ref_grads)):
        entry = norms.setdefault(group_of(path, a), {"system2": 0.0, "reference2": 0.0, "difference2": 0.0})
        a, b = a.astype(np.float64), b.astype(np.float64)
        entry["system2"] += float(np.sum(a * a))
        entry["reference2"] += float(np.sum(b * b))
        entry["difference2"] += float(np.sum((a - b) ** 2))
    held, lo = int(p.get("experts_held") or p["num_experts"]), int(p.get("first_expert_held", 0))
    result = {
        "device": device, "seed": args.seed, "sequences": args.sequences, "tokens": int(toks[:, :-1].size),
        "loss": {"system": sys_loss, "reference": ref_loss, "relative": abs(sys_loss - ref_loss) / abs(ref_loss)},
        "logits": {"max_abs_difference": worst, "max_abs_reference": ref_max, "relative_l2": (err2 / ref2) ** 0.5},
        "slots": {
            "total": float(ref_slots.sum()),
            "moved_to_another_expert": float(np.abs(sys_slots - ref_slots).sum() / 2),
            "held_share": [float(sys_slots[:, lo:lo + held].sum() / sys_slots.sum()), float(ref_slots[:, lo:lo + held].sum() / ref_slots.sum())],
            "fullest_expert_of_a_layer": [float(sys_slots.max()), float(ref_slots.max())],
            "emptiest_expert_of_a_layer": [float(sys_slots.min()), float(ref_slots.min())],
            "counters": sys_counters,
        },
        "router_logits": router_logits,
        "reference_in_bfloat16": {
            "loss": {"value": low_loss, "relative": abs(low_loss - ref_loss) / abs(ref_loss)},
            "slots_moved_to_another_expert": float(np.abs(low_slots - ref_slots).sum() / 2),
        },
        "gradient_norms": {
            g: {"system": e["system2"] ** 0.5, "reference": e["reference2"] ** 0.5,
                "relative_l2_of_difference": (e["difference2"] / e["reference2"]) ** 0.5 if e["reference2"] else None}
            for g, e in norms.items()
        },
    }
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "kanana2_against_reference.json"), "w") as out:
        json.dump(result, out, indent=1)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
