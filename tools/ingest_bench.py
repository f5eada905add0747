"""Host-side ingest stage costs — as a committed artifact (VERDICT r4
Weak #2 / Next #5: the r4 stage table lived only as prose in docs/perf.md).

Measures, per MINIBATCH-record criteo batch on this host:
- recordio bulk range read (``read_records_packed``: one read + slice-by-8
  CRC verify in C++ — the worker's ``_read_records`` fast path);
- raw decode (``criteo_feed``: C++ parse to f32/i32, 160 B/example wire);
- preprocessed decode (``criteo_feed_pre``: hash bucketing + log1p pushed
  into the C++ parse, u16/f16/u8 — 79 B/example wire);
- read + pre decode combined (the training hot path's host share).

Pure host work — runs identically on the CPU harness and the TPU host.
Writes ONE JSON artifact (default ``artifacts/ingest_stages_r05.json``);
docs/perf.md quotes the file.

``--threads N`` (r9) switches to the parallel-ingest sweep: the worker's
chunked read+decode path (data/ingest_pool.py — minibatch-aligned
sub-chunks, bulk C++ range read + preprocessed criteo decode per chunk,
ordered reassembly) measured at pool widths 1, 2, ..., N (powers of two
plus N), reporting host-side examples/sec and speedup vs the 1-thread
serial path.  Artifact: ``artifacts/INGEST_r09.json``.

Usage: python tools/ingest_bench.py [--threads N] [--out PATH]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# FORCE cpu (not setdefault): this is a CPU harness, and an inherited
# JAX_PLATFORMS (or none, on a TPU host) would aim it at the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MINIBATCH = 8192
BATCHES = 16          # distinct shards measured (cold page cache effects
REPEATS = 3           # amortized); best-of-REPEATS per stage.
BUCKETS = 65536


def _time(fn, *args):
    best = float("inf")
    out = None
    for _ in range(REPEATS):
        t0 = time.perf_counter()
        out = fn(*args)
        best = min(best, time.perf_counter() - t0)
    return best, out


def _wire_bytes(batch: dict) -> int:
    import numpy as np

    return sum(np.asarray(v).nbytes for v in batch.values())


def _chunked_task(reader, path, pool, start: int, task_records: int,
                  phases=None) -> None:
    """ONE worker-shaped ingest task: minibatch-aligned chunk plan + pooled
    bulk read + preprocessed decode — Worker._prep_fused_host's hot path
    minus the stacking.  The width sweep and the trace-overhead A/B both
    measure THIS (one definition, so neither can silently drift onto a
    different workload than the other claims comparability with);
    ``phases`` wraps each chunk decode in the PhaseTimers accounting
    boundary the A/B needs (the boundary that doubles as a trace span)."""
    import contextlib

    from elasticdl_tpu.data.codecs import criteo_feed_pre
    from elasticdl_tpu.data.ingest_pool import plan_chunks
    from elasticdl_tpu.data.reader import Shard

    def _decode_chunk(span):
        ctx = (
            phases.phase("decode_parallel")
            if phases is not None
            else contextlib.nullcontext()
        )
        with ctx:
            recs = reader.read_records_packed(Shard(path, span[0], span[1]))
            return criteo_feed_pre(recs, BUCKETS)

    chunks = plan_chunks(start, start + task_records, MINIBATCH, pool.threads)
    pool.map_ordered(_decode_chunk, chunks)


def _thread_sweep(max_threads: int, out: str, log) -> None:
    """Parallel-ingest sweep: the worker's chunked read+decode
    (``_chunked_task``) at pool widths 1..max_threads over task-sized
    ranges (the e2e shard size), with per-width examples/sec and speedup
    vs serial — comparable to the r5 ``host_side_examples_per_sec``."""
    from elasticdl_tpu.data.ingest_pool import IngestPool
    from elasticdl_tpu.data.reader import create_data_reader
    from tools.bench_e2e import _dataset

    task_records = MINIBATCH * 8  # the e2e bench's records-per-task
    path = _dataset()
    reader = create_data_reader(path)
    log(f"dataset {path} ({os.path.getsize(path) >> 20} MiB), "
        f"{task_records}-record tasks, host cores: {os.cpu_count()}")

    widths = sorted({1, *(
        w for w in (2, 4, 8, 16) if w < max_threads
    ), max_threads})
    n_tasks = 8
    rows = []
    for width in widths:
        pool = IngestPool(width)
        best = float("inf")
        try:
            for _ in range(REPEATS):
                t0 = time.perf_counter()
                for b in range(n_tasks):
                    _chunked_task(
                        reader, path, pool, b * task_records, task_records
                    )
                best = min(best, time.perf_counter() - t0)
        finally:
            pool.shutdown()
        eps = task_records * n_tasks / best
        rows.append({
            "threads": width,
            "examples_per_sec": round(eps, 1),
            "ms_per_task": round(best / n_tasks * 1e3, 3),
        })
        log(f"threads={width}: {eps:,.0f} examples/sec host-side")
    base = rows[0]["examples_per_sec"]
    for r in rows:
        r["speedup_vs_1"] = round(r["examples_per_sec"] / base, 3)
    artifact = {
        "metric": "parallel_ingest_host_examples_per_sec",
        "unit": f"examples/sec, {task_records}-record criteo tasks "
                f"(read_records_packed + criteo_feed_pre per chunk, best "
                f"of {REPEATS} x {n_tasks} tasks)",
        "host_cpu_count": os.cpu_count(),
        "sweep": rows,
        "note": "speedup ceiling is min(threads, host cores): the chunk "
                "decode is CPU-bound GIL-releasing C++, so a 2-core "
                "harness tops out near 2x regardless of pool width",
    }
    from tools.artifact import write_artifact

    write_artifact(artifact, "INGEST_r09.json", path=out, log=log)
    print(json.dumps(rows), flush=True)


def trace_overhead_ab(log=None) -> dict:
    """The --trace overhead measurement on the ingest workload (chunk plan
    + pooled read/decode, every chunk inside a ``PhaseTimers`` phase — the
    accounting boundary that doubles as a trace span when the recorder is
    on).  Two numbers come back:

    - ``overhead_pct`` — the ASSERTABLE bound: events-per-run counted from
      the real traced workload x per-event cost measured in isolation
      (100k-rep microbench), over the measured run wall.  Deterministic
      arithmetic over stable measurements.
    - ``ab_delta_pct`` — the raw interleaved wall-clock A/B, recorded for
      transparency.  On this shared 2-core box the run-to-run weather is
      +/-10-25% (co-tenant CPU steal; even process_time swings with cache
      pollution) while the true effect is ~0.1%, so the raw delta is a
      weather report — measured and stamped, never asserted on.

    The smoke gate (<2%, asserted by bench_all --trace-smoke and stamped
    into TRACE_r12.json) is what makes "--trace on a production job is
    safe" a recorded number instead of a hope."""
    log = log or (lambda m: print(f"[ingest] {m}", file=sys.stderr, flush=True))
    import time as _time

    from elasticdl_tpu.common import trace
    from elasticdl_tpu.common.metrics import PhaseTimers
    from elasticdl_tpu.data.ingest_pool import IngestPool
    from elasticdl_tpu.data.reader import create_data_reader
    from tools.bench_e2e import _dataset

    task_records = MINIBATCH * 8
    n_tasks = 6
    path = _dataset()
    reader = create_data_reader(path)
    pool = IngestPool(min(2, os.cpu_count() or 1))
    phases = PhaseTimers()

    def _run_once() -> float:
        t0 = _time.perf_counter()
        for b in range(n_tasks):
            with phases.phase("prep_wait"):
                _chunked_task(
                    reader, path, pool, b * task_records, task_records,
                    phases=phases,
                )
            # The control-plane event load of one task boundary (lease/
            # report instants) rides along so the accounting covers
            # instants too, not just phase spans.
            trace.instant("bench:task", cat="lease", task=b)
        return _time.perf_counter() - t0

    was_enabled = trace.enabled()
    try:
        _run_once()  # warm the page cache outside every measurement
        # Traced run: count the REAL event load and the wall it rode on.
        trace.configure(enabled=True, capacity=65536)
        trace.default().clear()
        traced_wall = _run_once()
        events = trace.default().export()
        n_spans = sum(1 for e in events if e.get("ph") == "X")
        n_instants = len(events) - n_spans
        # Interleaved wall A/B (best-of per arm), recorded as-is.
        best_off = float("inf")
        best_on = traced_wall
        for _ in range(3):
            trace.configure(enabled=False)
            best_off = min(best_off, _run_once())
            trace.configure(enabled=True)
            trace.default().clear()
            best_on = min(best_on, _run_once())
        # Primitive costs, isolated: 100k span enter/exits and instants.
        n = 100_000
        t0 = _time.perf_counter()
        for _ in range(n):
            with trace.span("x", cat="bench"):
                pass
        span_ns = (_time.perf_counter() - t0) / n * 1e9
        t0 = _time.perf_counter()
        for _ in range(n):
            trace.instant("x", cat="bench")
        instant_ns = (_time.perf_counter() - t0) / n * 1e9
        trace.default().clear()
    finally:
        trace.configure(enabled=was_enabled)
        pool.shutdown()
    event_cost_s = (n_spans * span_ns + n_instants * instant_ns) / 1e9
    overhead_pct = event_cost_s / traced_wall * 100.0
    ab_delta_pct = (best_on - best_off) / best_off * 100.0
    out = {
        "overhead_pct": round(overhead_pct, 4),
        "events_per_run": len(events),
        "spans_per_run": n_spans,
        "instants_per_run": n_instants,
        "run_wall_s": round(traced_wall, 4),
        "span_ns": round(span_ns, 1),
        "instant_ns": round(instant_ns, 1),
        "examples_per_sec_trace_on": round(
            task_records * n_tasks / best_on, 1
        ),
        "examples_per_sec_trace_off": round(
            task_records * n_tasks / best_off, 1
        ),
        "ab_delta_pct": round(ab_delta_pct, 2),
        "ab_note": "raw interleaved wall A/B on a shared box: +/-10-25% "
                   "co-tenant weather over a ~0.1% true effect — recorded "
                   "for transparency; overhead_pct (event count x measured "
                   "per-event cost over run wall) is the assertable bound",
        "workload": f"{n_tasks} x {task_records}-record criteo tasks, "
                    f"chunked read+decode on a {pool.threads}-thread pool; "
                    "spans via PhaseTimers phases + one instant per task",
    }
    log(f"trace overhead: {len(events)} events/run x "
        f"({span_ns:.0f} ns/span, {instant_ns:.0f} ns/instant) over "
        f"{traced_wall*1e3:.0f} ms = {overhead_pct:.4f}% "
        f"(raw wall A/B {ab_delta_pct:+.2f}%, weather-dominated)")
    return out


def gauge_overhead_ab(log=None) -> dict:
    """The graftgauge overhead measurement on the ingest workload — the
    trace_overhead_ab method applied to the r14 metrics plane (same
    workload definition, same assertable-bound arithmetic, same <2%
    budget):

    - the workload runs with a live ``gauge.Registry`` wired into
      ``PhaseTimers`` (every phase entry observes into the per-phase
      histogram) plus the worker-shaped hot-path counter updates (one
      examples inc + one steps inc per task — Worker._dispatch_batches'
      sites);
    - ``overhead_pct`` = updates-per-run counted from the real
      instrumented workload x per-update cost measured in isolation
      (100k-rep microbench), PLUS one scrape per second
      (``render_prometheus`` wall x 1 Hz — a Prometheus-typical cadence),
      over the measured run wall;
    - the raw interleaved wall A/B is stamped for transparency and never
      asserted on (the co-tenant-weather caveat in trace_overhead_ab).
    """
    log = log or (lambda m: print(f"[ingest] {m}", file=sys.stderr, flush=True))
    import time as _time

    from elasticdl_tpu.common import gauge
    from elasticdl_tpu.common.metrics import PhaseTimers
    from elasticdl_tpu.data.ingest_pool import IngestPool
    from elasticdl_tpu.data.reader import create_data_reader
    from tools.bench_e2e import _dataset

    task_records = MINIBATCH * 8
    n_tasks = 6
    path = _dataset()
    reader = create_data_reader(path)
    pool = IngestPool(min(2, os.cpu_count() or 1))

    def _run_once(phases, g_examples, g_steps) -> float:
        t0 = _time.perf_counter()
        for b in range(n_tasks):
            with phases.phase("prep_wait"):
                _chunked_task(
                    reader, path, pool, b * task_records, task_records,
                    phases=phases,
                )
            # The worker task loop's own hot-path counter sites, one task
            # boundary's worth (examples + steps + task done).
            g_examples.inc(task_records)
            g_steps.inc(task_records // MINIBATCH)
        return _time.perf_counter() - t0

    try:
        reg = gauge.Registry()
        phases_on = PhaseTimers(gauges=reg)
        g_examples = reg.counter(gauge.EXAMPLES_TRAINED)
        g_steps = reg.counter(gauge.STEPS_DISPATCHED)
        _run_once(phases_on, g_examples, g_steps)  # warm the page cache
        warm_counts = sum(phases_on.counts().values())
        gauged_wall = _run_once(phases_on, g_examples, g_steps)
        # Updates per run, from the instrumented run itself: every phase
        # entry observed into a histogram, plus the two counter incs per
        # task.  PhaseTimers counts are CUMULATIVE — diff against the
        # warm run's tally or the per-run number doubles.
        n_observes = sum(phases_on.counts().values()) - warm_counts
        n_incs = 2 * n_tasks
        # Interleaved wall A/B (best-of per arm), recorded as-is.
        phases_off = PhaseTimers()
        off_c = gauge.Counter(enabled=False)
        best_off, best_on = float("inf"), gauged_wall
        for _ in range(3):
            best_off = min(best_off, _run_once(phases_off, off_c, off_c))
            best_on = min(
                best_on, _run_once(phases_on, g_examples, g_steps)
            )
        # Primitive costs, isolated.
        n = 100_000
        hist = reg.histogram("edl_phase_ms", labels={"phase": "prep_wait"})
        t0 = _time.perf_counter()
        for _ in range(n):
            hist.observe(1.0)
        observe_ns = (_time.perf_counter() - t0) / n * 1e9
        ctr = reg.counter(gauge.EXAMPLES_TRAINED)
        t0 = _time.perf_counter()
        for _ in range(n):
            ctr.inc()
        inc_ns = (_time.perf_counter() - t0) / n * 1e9
        # Scrape cost: one full render (collectors + every family), the
        # per-scrape price an operator's 1 Hz poll pays.
        t0 = _time.perf_counter()
        for _ in range(50):
            reg.render_prometheus()
        scrape_ms = (_time.perf_counter() - t0) / 50 * 1e3
    finally:
        pool.shutdown()
    update_cost_s = (n_observes * observe_ns + n_incs * inc_ns) / 1e9
    scrape_hz = 1.0
    overhead_pct = (
        update_cost_s / gauged_wall + scrape_ms / 1e3 * scrape_hz
    ) * 100.0
    ab_delta_pct = (best_on - best_off) / best_off * 100.0
    out = {
        "overhead_pct": round(overhead_pct, 4),
        "updates_per_run": n_observes + n_incs,
        "observes_per_run": n_observes,
        "incs_per_run": n_incs,
        "run_wall_s": round(gauged_wall, 4),
        "observe_ns": round(observe_ns, 1),
        "inc_ns": round(inc_ns, 1),
        "scrape_ms": round(scrape_ms, 3),
        "scrape_hz_assumed": scrape_hz,
        "ab_delta_pct": round(ab_delta_pct, 2),
        "ab_note": "raw interleaved wall A/B on a shared box — weather-"
                   "dominated, recorded for transparency; overhead_pct "
                   "(update count x measured per-update cost + 1 Hz "
                   "scrape render, over run wall) is the assertable "
                   "bound (the trace_overhead_ab method)",
        "workload": f"{n_tasks} x {task_records}-record criteo tasks, "
                    f"chunked read+decode on a {pool.threads}-thread "
                    "pool; histogram observes via PhaseTimers phases + 2 "
                    "counter incs per task; scrape = full "
                    "render_prometheus",
    }
    log(f"gauge overhead: {n_observes + n_incs} updates/run x "
        f"({observe_ns:.0f} ns/observe, {inc_ns:.0f} ns/inc) + "
        f"{scrape_ms:.2f} ms/scrape @1 Hz over {gauged_wall*1e3:.0f} ms "
        f"= {overhead_pct:.4f}% (raw wall A/B {ab_delta_pct:+.2f}%, "
        "weather-dominated)")
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--out", default=""
    )
    ap.add_argument(
        "--threads", type=int, default=0,
        help="run the parallel-ingest sweep up to this pool width "
             "(stamps artifacts/INGEST_r09.json) instead of the serial "
             "stage breakdown",
    )
    ap.add_argument(
        "--trace-ab", action="store_true",
        help="run the --trace overhead A/B (recorder off vs on over the "
             "chunked ingest workload) and print the result JSON",
    )
    ap.add_argument(
        "--gauge-ab", action="store_true",
        help="run the graftgauge overhead A/B (registry + scrape over "
             "the chunked ingest workload) and print the result JSON",
    )
    args = ap.parse_args()
    log = lambda m: print(f"[ingest] {m}", file=sys.stderr, flush=True)

    if args.gauge_ab:
        result = gauge_overhead_ab(log)
        if args.out:
            from tools.artifact import write_artifact

            write_artifact(
                {"metric": "gauge_overhead_ingest_ab", **result},
                "gauge_ab_r14.json", path=args.out, log=log,
            )
        print(json.dumps(result), flush=True)
        return

    if args.trace_ab:
        result = trace_overhead_ab(log)
        if args.out:
            from tools.artifact import write_artifact

            write_artifact(
                {"metric": "trace_overhead_ingest_ab", **result},
                "trace_ab_r12.json", path=args.out, log=log,
            )
        print(json.dumps(result), flush=True)
        return

    if args.threads > 0:
        _thread_sweep(
            args.threads,
            args.out or os.path.join(_REPO_ROOT, "artifacts",
                                     "INGEST_r09.json"),
            log,
        )
        return
    args.out = args.out or os.path.join(
        _REPO_ROOT, "artifacts", "ingest_stages_r05.json"
    )

    from elasticdl_tpu.data.codecs import criteo_feed, criteo_feed_pre
    from elasticdl_tpu.data.reader import Shard, create_data_reader
    from tools.bench_e2e import _dataset

    path = _dataset()
    reader = create_data_reader(path)
    log(f"dataset {path} ({os.path.getsize(path) >> 20} MiB)")

    read_s = dec_raw_s = dec_pre_s = combo_s = 0.0
    raw_bytes = pre_bytes = 0
    for b in range(BATCHES):
        shard = Shard(name=path, start=b * MINIBATCH, end=(b + 1) * MINIBATCH)
        t, records = _time(reader.read_records_packed, shard)
        read_s += t
        t, raw = _time(criteo_feed, records)
        dec_raw_s += t
        t, pre = _time(criteo_feed_pre, records, BUCKETS)
        dec_pre_s += t
        t, _ = _time(
            lambda s: criteo_feed_pre(reader.read_records_packed(s), BUCKETS),
            shard,
        )
        combo_s += t
        raw_bytes, pre_bytes = _wire_bytes(raw), _wire_bytes(pre)

    n = BATCHES
    per_batch = lambda s: round(s / n * 1e3, 3)  # ms per 8192-record batch
    artifact = {
        "metric": "ingest_stage_ms_per_batch",
        "unit": f"ms per {MINIBATCH}-record criteo batch (best of "
                f"{REPEATS}, mean over {BATCHES} shards)",
        "stages": {
            "recordio_range_read_ms": per_batch(read_s),
            "decode_raw_ms": per_batch(dec_raw_s),
            "decode_pre_ms": per_batch(dec_pre_s),
            "read_plus_pre_decode_ms": per_batch(combo_s),
        },
        "derived": {
            "decode_pre_us_per_record": round(
                dec_pre_s / n / MINIBATCH * 1e6, 3
            ),
            "host_side_examples_per_sec": round(
                MINIBATCH / (combo_s / n), 1
            ),
            "wire_bytes_per_example_raw": raw_bytes // MINIBATCH,
            "wire_bytes_per_example_pre": pre_bytes // MINIBATCH,
        },
    }
    from tools.artifact import write_artifact

    write_artifact(artifact, "ingest_stages_r05.json", path=args.out, log=log)
    print(json.dumps({**artifact["stages"], **artifact["derived"]}),
          flush=True)


if __name__ == "__main__":
    main()
