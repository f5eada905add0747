"""Compare embedding gather/scatter formulations on the live chip via
trace-derived per-op device times (kernel time comes from the device trace,
not from host wall-clock around a dispatch).

Each variant computes forward lookup + backward table-grad for the DeepFM
shape: ids [8192, 26] into a 1.7M-row table, dim 8.  We profile each variant
in its own trace dir and report total device time per step.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.platform import enable_compile_cache  # noqa: E402

B, F = 8192, 26
BUCKETS = 65536
V = F * BUCKETS          # 1,703,936
DIM = 8
PACK = 128 // DIM        # 16 logical rows per 128-lane physical row

# jax globals are populated by _init_jax(): importing this module must stay
# cheap and chip-free — scatter_experiments imports it just for
# trace_total_device_us, and --help/lint paths must never pay (or hang on)
# a backend init.  Function bodies resolve these names at CALL time, so
# everything below works unchanged once main() has run _init_jax().
jax = None
jnp = None
lax = None
_GATHER_DNUMS = None


def _init_jax() -> None:
    global jax, jnp, lax, _GATHER_DNUMS
    if jax is not None:
        return
    import jax as _jax
    import jax.numpy as _jnp
    from jax import lax as _lax

    jax, jnp, lax = _jax, _jnp, _lax
    _GATHER_DNUMS = lax.GatherDimensionNumbers(
        offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,)
    )


def flat_lookup(flat, ids):
    """Current design: 1-D flat table, per-row slice gather (FILL_OR_DROP)."""
    starts = (ids.reshape(-1, 1) * DIM).astype(jnp.int32)
    out = lax.gather(flat, starts, _GATHER_DNUMS, slice_sizes=(DIM,),
                     mode=lax.GatherScatterMode.FILL_OR_DROP,
                     fill_value=jnp.nan)
    return out.reshape(B, F, DIM)


def take2d_clip(table2d, ids):
    """2-D [V, 8] take, clip mode."""
    return jnp.take(table2d, ids, axis=0, mode="clip")


def take2d_fill(table2d, ids):
    """2-D [V, 8] take, fill (FILL_OR_DROP) mode."""
    return jnp.take(table2d, ids, axis=0, mode="fill", fill_value=jnp.nan)


def onehot_matmul(table3d, ids):
    """Per-feature one-hot matmul: [B, BUCKETS] @ [BUCKETS, DIM] on the MXU.

    table3d: [F, BUCKETS, DIM].  ids are global (feature-offset) ids.
    """
    local = ids - jnp.arange(F)[None, :] * BUCKETS          # [B, F]
    oh = jax.nn.one_hot(local, BUCKETS, dtype=jnp.bfloat16)  # [B, F, BUCKETS]
    out = jnp.einsum("bfv,fvd->bfd", oh, table3d.astype(jnp.bfloat16))
    return out.astype(jnp.float32)


def packed_lookup_width(packed, ids, width):
    """Packed rows of an arbitrary element width (dtype from the table):
    gather full physical rows, lane-select.  width=128 f32 is the shipped
    layout; bf16 at width 128 halves bytes/row (256B), bf16 at width 256
    keeps 512B rows with double pack."""
    pack = width // DIM
    hi = ids // pack
    lo = ids % pack
    rows = jnp.take(packed, hi.reshape(-1), axis=0)        # [B*F, width]
    rows = rows.reshape(B * F, pack, DIM)
    sel = jax.nn.one_hot(lo.reshape(-1), pack, dtype=rows.dtype)
    out = jnp.einsum("npd,np->nd", rows, sel)
    return out.reshape(B, F, DIM)


def _packed_table(key, width, dtype=None):
    # dtype default resolved at call time (module import is jax-free).
    dtype = jnp.float32 if dtype is None else dtype
    rows = V // (width // DIM)
    return jax.random.normal(key, (rows, width)).astype(dtype)


VARIANTS = {
    "flat": (lambda key: jax.random.normal(key, (V * DIM,)), flat_lookup),
    "take2d_clip": (lambda key: jax.random.normal(key, (V, DIM)), take2d_clip),
    "take2d_fill": (lambda key: jax.random.normal(key, (V, DIM)), take2d_fill),
    "packed": (
        lambda key: _packed_table(key, 128),
        lambda t, ids: packed_lookup_width(t, ids, 128),
    ),
    "packed_bf16_w128": (
        lambda key: _packed_table(key, 128, jnp.bfloat16),
        lambda t, ids: packed_lookup_width(t, ids, 128),
    ),
    "packed_bf16_w256": (
        lambda key: _packed_table(key, 256, jnp.bfloat16),
        lambda t, ids: packed_lookup_width(t, ids, 256),
    ),
    "onehot": (lambda key: jax.random.normal(key, (F, BUCKETS, DIM)), onehot_matmul),
}


def trace_total_device_us(out_dir: str) -> dict:
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    from xprof.convert import raw_to_tool_data as rtd
    data, _ = rtd.xspace_to_tool_data([paths[-1]], "framework_op_stats", {})
    if isinstance(data, bytes):
        data = data.decode()
    tbl = json.loads(data)[0]
    cols = [c['label'] for c in tbl['cols']]
    i_name, i_tot = cols.index('Operation Name'), cols.index('Total self-time (us)')
    i_occ = cols.index('#Occurrences')
    per_op = {}
    total = 0.0
    for r in tbl['rows']:
        vals = [c.get('v') for c in r['c']]
        name = vals[i_name]
        if name == 'IDLE':
            continue
        per_op[name] = (vals[i_occ], vals[i_tot])
        total += vals[i_tot]
    return {"total_us": total, "per_op": per_op}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--outbase", default="/tmp/gexp")
    args = ap.parse_args()
    _init_jax()
    enable_compile_cache()
    from elasticdl_tpu.common.jax_compat import jit_compiled

    print(f"devices: {jax.devices()}", file=sys.stderr)

    key = jax.random.key(0)
    ids = jax.random.randint(jax.random.key(1), (B, F), 0, BUCKETS) \
        + jnp.arange(F)[None, :] * BUCKETS
    ids = ids.astype(jnp.int32)

    results = {}
    for name in args.variants.split(","):
        init, fn = VARIANTS[name]
        table = init(key)

        def loss(t):
            out = fn(t, ids)
            return jnp.sum(out * out)

        # graftlint: allow[jit-stability] bench main runs once per process; one fresh compile per measured lookup variant IS the experiment
        step = jit_compiled(
            jax.grad(loss), name=f"gather_experiments.{name}"
        )
        try:
            t0 = time.perf_counter()
            g = step(table)
            jax.block_until_ready(g)
            compile_s = time.perf_counter() - t0
        except Exception as e:
            print(f"{name}: FAILED {type(e).__name__}: {str(e)[:200]}",
                  file=sys.stderr)
            continue
        for _ in range(2):
            g = step(table)
        jax.block_until_ready(g)
        out_dir = f"{args.outbase}_{name}"
        os.makedirs(out_dir, exist_ok=True)
        jax.profiler.start_trace(out_dir)
        t0 = time.perf_counter()
        for _ in range(args.steps):
            g = step(table)
        jax.block_until_ready(g)
        wall = (time.perf_counter() - t0) / args.steps
        jax.profiler.stop_trace()
        stats = trace_total_device_us(out_dir)
        dev_ms = stats["total_us"] / args.steps / 1000
        results[name] = dev_ms
        print(f"== {name}: device {dev_ms:.2f} ms/step  (wall {wall*1e3:.2f} "
              f"ms, compile {compile_s:.1f}s)", file=sys.stderr)
        top = sorted(stats["per_op"].items(), key=lambda kv: -kv[1][1])[:6]
        for opname, (occ, us) in top:
            print(f"     {us/args.steps/1000:9.3f} ms  x{int(occ/args.steps):>7} "
                  f" {opname[:90]}", file=sys.stderr)
    print(json.dumps(results))


if __name__ == "__main__":
    main()
