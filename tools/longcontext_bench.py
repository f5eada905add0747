"""Long-context single-chip capability bench — trains the GPT-2-small-shape
transformer at increasing sequence lengths on ONE chip and records the
longest that fits plus its throughput.

What makes the long lengths possible: per-block rematerialization plus the
Pallas flash-attention kernel (ops/flash_attention.py).  Measured split of
credit at L=8192 (2026-07-31): remat alone lets the XLA attention path
squeeze b=2 through — its O(L^2) score tensors ([b,12,8192,8192] f32 =
6.4 GB at b=2) become per-block transients — but b=4 OOMs there, while the
flash path (attention memory O(L*D)) runs it; at L=1024 the same kernel is
what made global batch 32 fit at all (19 GB of saved probability tensors
gone).  Beyond one chip's HBM, ring-attention sequence parallelism
(ops/ring_attention.py) shards L over the mesh; that path is
CPU-mesh-tested (tests/test_ring_attention.py) since this environment has
one physical chip.

Steps settle via the loss fetch, so ``tokens_per_s`` is host wall-clock
around whole steps.  Each length row ALSO records trace-derived device
self-time (``device_step_ms`` / ``device_tokens_per_s``, from the xplane
trace, as benchmark/xplane.py reduces one).  Not measured on current code.

Usage: python tools/longcontext_bench.py [--lengths 2048,4096,8192]
One JSON line per length; artifact: artifacts/longcontext_r05.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common.platform import enable_compile_cache


def _trace_device_step_ms(out_dir: str, steps: int):
    """Per-step device self-time (ms) from the xplane trace; None when the
    trace toolchain is unavailable (the wall numbers still emit — device
    time is the better instrument, not a new hard dependency)."""
    try:
        from tools.gather_experiments import trace_total_device_us

        return trace_total_device_us(out_dir)["total_us"] / steps / 1000.0
    except Exception as e:  # noqa: BLE001 — best-effort instrumentation
        print(f"[longcontext] trace parse unavailable: {str(e)[:200]}",
              file=sys.stderr)
        return None


def bench_length(seq: int, batch: int, steps: int = 5) -> dict:
    import jax
    import numpy as np

    from elasticdl_tpu.common.config import JobConfig
    from elasticdl_tpu.models.spec import load_model_spec
    from elasticdl_tpu.parallel.mesh import create_mesh
    from elasticdl_tpu.parallel.trainer import Trainer

    spec = load_model_spec(
        "elasticdl_tpu.models", "transformer_lm.model_spec",
        vocab=32768, dim=768, n_heads=12, n_layers=12,
        seq_len=seq, max_seq=seq, remat=True,
    )
    trainer = Trainer(
        spec, JobConfig(distribution_strategy="AllReduce"),
        create_mesh(jax.devices()),
    )
    try:
        state = trainer.init_state(jax.random.key(0))
        seqs = jax.random.randint(jax.random.key(1), (batch, seq + 1), 0, 32768)
        b = trainer.shard_batch({"tokens": seqs[:, :-1], "labels": seqs[:, 1:]})
        state, m = trainer.train_step(state, b)
        # Settle the warmup via a fetch — block_until_ready returns before
        # execution completes on this backend (see module docstring).
        np.asarray(jax.device_get(m["loss"]))
        # Wall timing runs UNTRACED — live xplane collection inflates wall
        # time, and these fields must stay comparable to the untraced r5
        # walls the artifact series quotes.
        t0 = time.perf_counter()
        for _ in range(steps):
            state, m = trainer.train_step(state, b)
        # settles all steps
        loss = float(np.asarray(jax.device_get(m["loss"])))
        dt = (time.perf_counter() - t0) / steps
        # Device self-time from a SEPARATE traced set of steps (trace
        # overhead lands on wall, not on device self-time, so the traced
        # steps measure the same thing).
        # Fresh dir per run: trace_total_device_us parses the newest
        # xplane under it, and a stale file from a previous invocation
        # would silently stamp the OLD run's device time into this row
        # if this run's trace fails to flush.
        import shutil

        trace_dir = f"/tmp/longcontext_trace_L{seq}"
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        tracing = True
        try:
            jax.profiler.start_trace(trace_dir)
        except Exception:  # a live outer trace or missing profiler support
            tracing = False
        traced_ok = True
        try:
            if tracing:
                for _ in range(steps):
                    state, m = trainer.train_step(state, b)
                np.asarray(jax.device_get(m["loss"]))  # settle before stop
        except Exception as e:  # noqa: BLE001 — degrade, don't discard
            # The traced re-run can fail where the untraced steps passed
            # (xplane collection adds device-memory/overhead pressure, and
            # near-OOM lengths are exactly where this runs): the wall row
            # already measured above must not be thrown to the outer OOM
            # handler — degrade to wall-only for this length.
            print(f"[longcontext] traced re-run failed, wall-only row: "
                  f"{str(e)[:200]}", file=sys.stderr)
            traced_ok = False
        finally:
            # Stop on the failure path too (an OOM row is expected data):
            # a trace left live would poison the NEXT length's wall numbers
            # with collection overhead and make its start_trace fail,
            # silently dropping every later device_step_ms.
            if tracing:
                try:
                    jax.profiler.stop_trace()
                except Exception:
                    pass
        dev_ms = (
            _trace_device_step_ms(trace_dir, steps)
            if tracing and traced_ok else None
        )
        row = {
            "seq_len": seq, "batch": batch, "ok": True,
            "step_ms": round(dt * 1e3, 1),
            "tokens_per_s": round(batch * seq / dt),
            "loss": round(loss, 3),
        }
        # Device self-time rides beside the wall numbers.
        if dev_ms is not None:
            row["device_step_ms"] = round(dev_ms, 1)
            if dev_ms > 0:
                row["device_tokens_per_s"] = round(
                    batch * seq / (dev_ms / 1e3)
                )
        return row
    except Exception as e:  # noqa: BLE001 — OOM is a data point here
        msg = str(e)
        oom = "memory" in msg.lower() or "hbm" in msg.lower()
        return {
            "seq_len": seq, "batch": batch, "ok": False,
            "error": "OOM" if oom else msg[:200],
        }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lengths", default="2048,4096,8192")
    # b=4 is the committed artifact's configuration AND the credit-split
    # claim (XLA+remat fits b=2 but OOMs b=4; flash runs b=4).
    ap.add_argument("--batch", type=int, default=4)
    args = ap.parse_args()
    enable_compile_cache()
    results = []
    try:
        for seq in (int(s) for s in args.lengths.split(",")):
            r = bench_length(seq, args.batch)
            results.append(r)
            print(json.dumps(r), flush=True)
    finally:
        if results:
            from tools.artifact import write_artifact

            write_artifact(
                {
                    "metric": "longcontext_single_chip",
                    "model": "transformer_lm 12L/768d/12h vocab 32768, "
                             "remat + pallas flash attention",
                    "lengths": results,
                },
                "longcontext_r05.json", env_var="LONGCONTEXT_OUT",
            )


if __name__ == "__main__":
    main()
