"""Backward-path (table-gradient) formulation experiments on the live chip.

The round-3 profile (docs/perf.md) puts the embedding scatter-add at 2.85 ms
— 42% of the DeepFM step — at ~13 ns per touched row, op-rate-bound: measured
at THIS file's table, P = 106,496 physical rows (54.5 MB).  The cost a row
is not a constant of the op: into the benchmark's 1.31 GB table the same
scatter-add is 74 ns a row, and from ``ops/embedding.SWEEP_MIN_ROWS`` rows
on the program builds the buffer by a sorted merge sweep instead
(``ops/table_grad.py``; PERF.md, PR 27, has both at every size).  This
tool measures candidate reformulations of JUST the backward table-grad
computation, trace-derived like tools/gather_experiments.py:

- ``baseline``      — what ships: unsorted scatter-add of [N,128] rows.
- ``sorted_flags``  — sort ids, permute grad rows (a gather — measured 5x
  cheaper per row than scatter), segment-sum duplicate runs, then
  scatter-add with ``indices_are_sorted=True`` +  ``unique_indices=True`` so
  XLA can use a monotonic lowering.
- ``sort_only``     — just the argsort + permute + segment-sum, no scatter:
  isolates the pipeline overhead from the sorted-scatter win.
- ``scatter_sorted_presorted`` — the sorted+unique scatter-add alone on
  ALREADY sorted unique indices: the upper bound of the sorted lowering.

Each variant is profiled in its own trace dir; per-op device times printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.platform import enable_compile_cache  # noqa: E402
from tools.gather_experiments import trace_total_device_us  # noqa: E402

# jax globals populated by _init_jax() (same lazy pattern as
# gather_experiments): module import stays cheap and chip-free for
# --help/lint paths; function bodies resolve the names at call time.
jax = None
jnp = None
lax = None


def _init_jax() -> None:
    global jax, jnp, lax
    if jax is not None:
        return
    import jax as _jax
    import jax.numpy as _jnp
    from jax import lax as _lax

    jax, jnp, lax = _jax, _jnp, _lax

B, F = 8192, 26
N = B * F                 # 212,992 touched rows per step
BUCKETS = 65536
V = F * BUCKETS
DIM = 8
PACK = 128 // DIM
P = V // PACK             # 106,496 physical rows
W = 128


def _scatter_rows(table, rows_idx, updates, sorted_unique: bool):
    """scatter-add ``updates`` [N, W] into ``table`` [P, W] at rows_idx."""
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=(1,),
        inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0,),
    )
    return lax.scatter_add(
        table,
        rows_idx[:, None].astype(jnp.int32),
        updates,
        dnums,
        indices_are_sorted=sorted_unique,
        unique_indices=sorted_unique,
        mode=lax.GatherScatterMode.FILL_OR_DROP,
    )


def baseline(ids, grads):
    zeros = jnp.zeros((P, W), jnp.float32)
    return _scatter_rows(zeros, ids, grads, sorted_unique=False)


def _sorted_segments(ids, grads):
    """argsort ids, permute grad rows, segment-sum equal-id runs.

    Returns (unique-ish row ids [N], summed rows [N, W]) where duplicate
    positions hold zeros and a sentinel row id P (dropped by FILL_OR_DROP) —
    static shapes, no host round-trip.
    """
    order = jnp.argsort(ids)
    sids = ids[order]
    srows = grads[order]                       # the 0.5ms-class gather
    # Run boundaries: position i starts a new run when sids[i] != sids[i-1].
    first = jnp.concatenate(
        [jnp.ones((1,), bool), sids[1:] != sids[:-1]]
    )
    # Segment-sum via inclusive cumsum differencing: css[i] = sum rows[0..i];
    # for a run ending at j (last position before next run or N-1), the run
    # sum = css[j] - css[start-1].  Take per-run sums at run STARTS.
    css = jnp.cumsum(srows, axis=0)
    # Run ends via the "next run's start - 1" trick.  Padding slots fill
    # with N (NOT N-1): a fill of N-1 would masquerade as a real start at
    # the last position and clip the LAST run's end to N-2, silently
    # dropping the final sorted row from its segment sum.
    start_pos = jnp.nonzero(first, size=N, fill_value=N)[0]       # [N] padded
    n_runs = jnp.sum(first.astype(jnp.int32))
    next_start = jnp.concatenate([start_pos[1:], jnp.array([N])])
    end_pos = jnp.clip(next_start - 1, 0, N - 1)
    safe_start = jnp.minimum(start_pos, N - 1)
    run_sums = css[end_pos] - jnp.where(
        (safe_start == 0)[:, None], 0.0, css[jnp.maximum(safe_start - 1, 0)]
    )
    run_rows = sids[safe_start]
    # Mask padded run slots (beyond n_runs) to sentinel P -> dropped.
    valid = jnp.arange(N) < n_runs
    run_rows = jnp.where(valid, run_rows, P)
    run_sums = jnp.where(valid[:, None], run_sums, 0.0)
    return run_rows, run_sums


def sorted_flags(ids, grads):
    rows, sums = _sorted_segments(ids, grads)
    zeros = jnp.zeros((P, W), jnp.float32)
    return _scatter_rows(zeros, rows, sums, sorted_unique=True)


def sort_only(ids, grads):
    rows, sums = _sorted_segments(ids, grads)
    return rows.astype(jnp.float32).sum() + sums.sum()


def scatter_sorted_presorted(ids, grads):
    # ids pre-sorted & unique by construction at call site.
    zeros = jnp.zeros((P, W), jnp.float32)
    return _scatter_rows(zeros, ids, grads, sorted_unique=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--variants",
        default="baseline,sorted_flags,sort_only,scatter_sorted_presorted",
    )
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--outbase", default="/tmp/sexp")
    args = ap.parse_args()
    _init_jax()
    enable_compile_cache()
    from elasticdl_tpu.common.jax_compat import jit_compiled

    print(f"devices: {jax.devices()}", file=sys.stderr)

    kids = jax.random.randint(jax.random.key(1), (N,), 0, V) // PACK
    kids = kids.astype(jnp.int32)
    grads = jax.random.normal(jax.random.key(2), (N, W))
    # presorted unique indices for the upper-bound variant
    presorted = (jnp.arange(N, dtype=jnp.int32) * P) // N

    fns = {
        "baseline": (baseline, kids),
        "sorted_flags": (sorted_flags, kids),
        "sort_only": (sort_only, kids),
        "scatter_sorted_presorted": (scatter_sorted_presorted, presorted),
    }
    results = {}
    for name in args.variants.split(","):
        fn, ids = fns[name]
        # graftlint: allow[jit-stability] bench main runs once per process; one fresh compile per measured scatter variant IS the experiment
        step = jit_compiled(fn, name=f"scatter_experiments.{name}")
        try:
            t0 = time.perf_counter()
            out = step(ids, grads)
            jax.block_until_ready(out)
            compile_s = time.perf_counter() - t0
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:300]}",
                  file=sys.stderr)
            continue
        for _ in range(2):
            out = step(ids, grads)
        jax.block_until_ready(out)
        out_dir = f"{args.outbase}_{name}"
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        for _ in range(args.steps):
            out = step(ids, grads)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        stats = trace_total_device_us(out_dir)
        dev_ms = stats["total_us"] / args.steps / 1000
        results[name] = dev_ms
        print(f"== {name}: device {dev_ms:.2f} ms/step (compile {compile_s:.1f}s)",
              file=sys.stderr)
        top = sorted(stats["per_op"].items(), key=lambda kv: -kv[1][1])[:6]
        for opname, (occ, us) in top:
            print(f"     {us/args.steps/1000:9.3f} ms  x{int(occ/args.steps):>7} "
                  f" {opname[:90]}", file=sys.stderr)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
