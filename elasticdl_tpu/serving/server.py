"""The online serving tier: micro-batched inference gRPC over a model-zoo
model, with hot-id embedding caching and zero-drop checkpoint hot reload.

ROADMAP item 4: everything before r10 was training-side; this server is the
"serve heavy traffic" half.  The pieces compose rather than duplicate:

- **Forward**: the trainer's own jitted predict step
  (``parallel/trainer.build_predict_step``) over a serving mesh — one
  compiled program per declared batch BUCKET (the micro-batcher pads every
  flush to a bucket shape, and jitsan budgets exactly that many variants),
  using the model's ``predict`` inference entry
  (models/spec.ModelSpec.predict) so clients get probabilities, not
  training logits.
- **Micro-batching**: serving/micro_batcher.MicroBatcher —
  deadline-or-full flush, zero-padded to the smallest ``batch_buckets``
  size that fits, priority lanes (online vs bulk, weighted admission,
  shed-bulk-first), per-request fan-back.  The r9 amortization trick
  (many small requests, one hot-path crossing) applied to inference.
- **Sparse features**: host-tier tables pull through
  serving/embedding_cache.HotIdEmbeddingCache layered in front of the PS
  host store (``ps/host_store.py`` locally, ``ps/service.py`` for a PS
  fleet) via ``Trainer.wrap_host_stores`` — hits are a dict walk, only the
  cold tail pays the RPC.
- **Hot reload**: serving/checkpoint_watcher.CheckpointWatcher polls the
  published manifest (``common/checkpoint.publish_manifest`` — atomic, so
  a half-written checkpoint is unobservable).  The restore runs on the
  watcher thread CONCURRENT with serving; the cutover is one reference
  swap under a leaf lock plus a cache invalidation.  In-flight flushes
  hold the snapshot they started with — no request is ever dropped or
  drained for a reload (tests/test_serving.py reloads under traffic).

Wire contract: JSON-over-gRPC like the master (``common/rpc.py``
SERVING_SCHEMAS — Predict / ModelInfo).  Online requests are a handful of
examples, so JSON beats dragging the PS binary-frame codec in; bulk
offline scoring belongs to predict-mode training jobs, not this tier.
"""

from __future__ import annotations

import time
from concurrent import futures
from typing import Any, Dict, Optional, Sequence, Tuple

import grpc
import numpy as np

from elasticdl_tpu.common import gauge as gaugelib
from elasticdl_tpu.common import locksan
from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.rpc import (
    SERVING_SCHEMAS,
    SERVING_SERVICE_NAME,
    SchemaError,
    make_generic_handler,
)
from elasticdl_tpu.serving.checkpoint_watcher import CheckpointWatcher
from elasticdl_tpu.serving.embedding_cache import HotIdEmbeddingCache
from elasticdl_tpu.serving.micro_batcher import (
    DEFAULT_LANE,
    LANES,
    MASK_KEY,
    MicroBatcher,
)

logger = get_logger("serving.server")

#: Feature keys of the model's example batch that are NOT client features.
_NON_FEATURE_KEYS = ("labels", MASK_KEY)


def _listify(outputs: Any) -> Any:
    """Flush outputs -> JSON-ready nested lists, leaf-wise for dict-shaped
    model outputs (the shapes micro_batcher._slice_outputs fans back)."""
    if isinstance(outputs, dict):
        return {k: _listify(v) for k, v in outputs.items()}
    return np.asarray(outputs).tolist()


class _LiveModel:
    """One immutable serving snapshot: the unit the hot reload swaps.
    Requests in flight keep the instance they were handed — the swap can
    never tear a half-old/half-new forward."""

    __slots__ = ("step", "state")

    def __init__(self, step: int, state: Any):
        self.step = step
        self.state = state


class ServingServer:
    """Micro-batched prediction service over one model-zoo model.

    ``checkpoint_dir``: a training job's checkpoint directory.  The newest
    PUBLISHED step loads at startup (fresh-initialized weights otherwise —
    logged loudly, legitimate for smoke tests) and the watcher hot-reloads
    every subsequent publish.  ``ps_addresses``: host-tier tables pull from
    that PS fleet (the live online store); empty = in-process host store.
    """

    def __init__(
        self,
        spec: Any,
        checkpoint_dir: str = "",
        ps_addresses: str = "",
        max_batch: int = 64,
        max_delay_ms: float = 5.0,
        cache_rows: int = 1 << 20,
        poll_interval_s: float = 0.5,
        port: int = 0,
        max_workers: int = 16,
        seed: int = 0,
        gauges: Optional[gaugelib.Registry] = None,
        gauge_port: int = -1,
        target_p99_ms: float = 100.0,
        batch_buckets: Optional[Sequence[int]] = None,
        bulk_weight: float = 0.25,
        max_queue_rows: Optional[int] = None,
    ):
        import jax

        from elasticdl_tpu.parallel.mesh import create_mesh
        from elasticdl_tpu.parallel.trainer import Trainer

        self.spec = spec
        self.max_batch = max_batch
        self.max_delay_ms = max_delay_ms
        config = JobConfig(
            job_type="prediction",
            ps_addresses=ps_addresses,
            checkpoint_dir=checkpoint_dir,
            distribution_strategy=(
                DistributionStrategy.PARAMETER_SERVER
                if spec.embedding_tables
                else DistributionStrategy.ALLREDUCE
            ),
        )
        # One-device serving replica: an online replica scales by running
        # MORE replicas behind a load balancer, not by sharding one
        # request's forward over a mesh (batch 64 cannot feed 8 chips).
        # Mesh-sharded-table models still restore fine: the padded table
        # shapes are mesh-size-invariant (trainer.pad_embedding_tables).
        self.trainer = Trainer(spec, config, create_mesh([jax.devices()[0]]))
        # jitsan (v6, bucketed r19): the padded-shape buckets this replica
        # serves.  Each flush zero-pads to the smallest bucket that holds
        # its real rows (micro_batcher), the jitted predict step retraces
        # once per bucket, and the declared budget IS the bucket count — so
        # an accidental extra compile (a shape leaking past the batcher's
        # padding) still fails loud, while intended buckets never trip the
        # retrace sanitizer.
        self._shape_buckets = tuple(
            sorted(set(int(b) for b in (batch_buckets or ())) | {max_batch})
        )
        self.trainer.jit_budgets["predict_step"] = len(self._shape_buckets)
        # Hot-id cache in front of every host-tier store (no-op for models
        # without host tables).
        self._caches: Dict[str, HotIdEmbeddingCache] = {}

        def _wrap(key, store):
            cache = HotIdEmbeddingCache(store, capacity=cache_rows, name=key)
            self._caches[key] = cache
            return cache

        self.trainer.wrap_host_stores(_wrap)

        # The restore template: a freshly initialized state carries the
        # exact tree structure/shapes/shardings every checkpoint of this
        # model has — and doubles as the fresh-serve state when no
        # checkpoint exists yet.
        self._template = self.trainer.init_state(jax.random.key(seed))
        self._ckpt = None
        self._state_lock = locksan.lock("ServingServer._state_lock", leaf=True)  # lock-order: leaf
        self._live = _LiveModel(-1, self._template)  # guarded-by: _state_lock
        self._reloads = 0  # guarded-by: _state_lock
        self._last_swap_ms = 0.0  # guarded-by: _state_lock
        self._last_load_s = 0.0  # guarded-by: _state_lock
        self._requests = 0  # guarded-by: _state_lock
        self._watcher: Optional[CheckpointWatcher] = None
        if checkpoint_dir:
            from elasticdl_tpu.common.checkpoint import (
                CheckpointManager,
                read_manifest,
            )

            self._ckpt = CheckpointManager(checkpoint_dir)
            manifest = read_manifest(checkpoint_dir)
            if manifest is not None:
                self._reload(int(manifest["step"]), manifest)
            else:
                # Pre-manifest checkpoints (or none at all): fall back to
                # Orbax's newest step once, loudly.  The watcher still keys
                # strictly off the manifest from here on.
                step = self._ckpt.latest_step()
                if step is not None:
                    logger.warning(
                        "no published manifest under %s; serving Orbax "
                        "latest step %d (publish manifests for atomic "
                        "reload)", checkpoint_dir, step,
                    )
                    self._reload(int(step), {})
                else:
                    logger.warning(
                        "no checkpoint under %s: serving FRESHLY "
                        "INITIALIZED weights", checkpoint_dir,
                    )
            with self._state_lock:
                loaded = self._live.step
            self._watcher = CheckpointWatcher(
                checkpoint_dir, self._reload, poll_interval_s, name=spec.name,
                initial_step=None if loaded < 0 else loaded,
            )
        else:
            logger.warning(
                "serving without --checkpoint_dir: fresh weights, no hot "
                "reload (smoke/bench mode)"
            )

        # Client-facing feature template (dtype/shape contract, ModelInfo).
        example = spec.example_batch(max_batch) if spec.example_batch else None
        if example is None:
            raise ValueError(
                f"model {spec.name!r} declares no example_batch; the serving "
                "tier needs it for the feature template"
            )
        self._features = {
            k: np.asarray(v)
            for k, v in example.items()
            if k not in _NON_FEATURE_KEYS
        }
        self._batcher = MicroBatcher(
            self._run_batch,
            self._features,
            max_batch=max_batch,
            max_delay_ms=max_delay_ms,
            name=spec.name,
            batch_buckets=self._shape_buckets,
            bulk_weight=bulk_weight,
            # The batcher's bounded queue must be THE queue: size the gRPC
            # handler pool (max_workers) at or above the expected in-flight
            # request count, or excess load parks invisibly in the
            # executor — unmeasured by the latency histogram and unshed by
            # the admission bounds, which blinds the fleet autoscaler's
            # two pressure signals.
            max_queue_rows=max_queue_rows,
        )

        # graftgauge (r14): the replica's live metrics — request counter +
        # per-request latency histogram updated on the # hot-path handler
        # (O(1): gauge-discipline), everything else (batcher fill/shed,
        # cache hit rate, reload counter, the p99-vs-target SLO ratio)
        # collected from the existing stats() surfaces at scrape time.
        # ``target_p99_ms`` is the operator's SLO line: the endpoint serves
        # the live p99/target ratio so a blowout reads as a number > 1.0.
        self.target_p99_ms = float(target_p99_ms)
        self.gauges = gauges if gauges is not None else gaugelib.default()
        self._g_requests = self.gauges.counter(
            "edl_serving_requests_total", "Predict requests answered"
        )
        # Per-lane latency histograms: the SLO (p99 / slo_ratio gauges, and
        # the fleet autoscaler's windowed-p99 signal) is defined over the
        # ONLINE lane only — bulk latency is throughput traffic and must
        # not pollute the knee signal that adds replicas.
        self._g_request_ms = {
            lane: self.gauges.histogram(
                "edl_serving_request_ms",
                "per-request wall inside the Predict handler (parse + "
                "queue + flush + fan-back), by priority lane",
                labels={"lane": lane},
            )
            for lane in LANES
        }
        self._g_lane_requests = {
            lane: self.gauges.counter(
                "edl_serving_lane_requests_total",
                "Predict requests answered, by priority lane",
                labels={"lane": lane},
            )
            for lane in LANES
        }
        self.gauges.add_collector(self._collect_gauges)
        self._gauge_port = gauge_port
        self._metrics_server = None

        self._server = grpc.server(futures.ThreadPoolExecutor(max_workers))
        self._server.add_generic_rpc_handlers(
            (
                make_generic_handler(
                    SERVING_SERVICE_NAME,
                    {"Predict": self._predict, "ModelInfo": self._model_info},
                    SERVING_SCHEMAS,
                ),
            )
        )
        self.port = self._server.add_insecure_port(f"[::]:{port}")
        # Same loud-bind contract as PSServer: an advertised port that
        # silently rebinds serves nothing while looking healthy.
        if self.port == 0 or (port and self.port != port):
            raise RuntimeError(
                f"serving server failed to bind port {port} (got {self.port})"
            )

    # ---- model lifecycle ----

    def warmup(self) -> float:
        """Compile the forward at EVERY serving batch bucket (one padded
        zero batch per bucket through the real path) so the first request
        of any bucket pays RPC + forward, not RPC + XLA compile — and so
        the full jitsan variant budget is spent here, loudly, rather than
        one retrace at a time under live traffic.  Returns the total
        warmup wall seconds."""
        t0 = time.perf_counter()
        for bucket in self._shape_buckets:
            batch = {
                k: np.zeros((bucket,) + t.shape[1:], t.dtype)
                for k, t in self._batcher._template.items()
            }
            batch[MASK_KEY] = np.zeros((bucket,), np.float32)
            self._run_batch(batch, 0)
        return time.perf_counter() - t0

    def _reload(self, step: int, manifest: Dict[str, Any]) -> None:
        """Load checkpoint ``step`` and swap it live (the watcher callback).

        The expensive half — Orbax read + device placement — happens on the
        CALLING thread against a private state object while serving
        continues on the old snapshot.  The live path is touched only by
        the reference swap + cache invalidation at the end (microseconds,
        stamped in ModelInfo as ``last_swap_ms``)."""
        t0 = time.perf_counter()
        state = self._ckpt.restore(self._template, step=step)
        load_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        with self._state_lock:
            self._live = _LiveModel(step, state)
        # AFTER the swap: a pull that lands between swap and invalidate
        # caches NEW-era rows, which are valid; rows cached before the
        # swap are dropped here, and in-flight fetches from the old
        # generation are insert-blocked by the generation guard.
        for cache in self._caches.values():
            cache.invalidate()
        swap_ms = (time.perf_counter() - t1) * 1e3
        with self._state_lock:
            self._reloads += 1
            self._last_swap_ms = swap_ms
            self._last_load_s = load_s
        logger.info(
            "serving step %d live (load %.2fs off-path, swap %.3fms)",
            step, load_s, swap_ms,
        )

    # ---- request path ----

    def _parse_features(self, features: Dict[str, Any]) -> Dict[str, np.ndarray]:
        """Client JSON -> typed numpy per the model template.  Violations
        raise SchemaError: the handler surfaces them as structured
        FAILED_PRECONDITION at the boundary, never a KeyError mid-flush."""
        out: Dict[str, np.ndarray] = {}
        n = None
        for key, tmpl in self._features.items():
            if key not in features:
                raise SchemaError(
                    f"Predict: missing feature {key!r} "
                    f"(model {self.spec.name} expects {sorted(self._features)})"
                )
            try:
                arr = np.asarray(features[key], dtype=tmpl.dtype)
            except (TypeError, ValueError) as e:
                raise SchemaError(
                    f"Predict: feature {key!r} not convertible to "
                    f"{tmpl.dtype}: {e}"
                ) from e
            if arr.ndim == tmpl.ndim - 1:
                arr = arr[None]  # single example without the batch dim
            if arr.ndim != tmpl.ndim or arr.shape[1:] != tmpl.shape[1:]:
                raise SchemaError(
                    f"Predict: feature {key!r} has shape {arr.shape}, "
                    f"expected [n{''.join(f', {d}' for d in tmpl.shape[1:])}]"
                )
            if n is None:
                n = arr.shape[0]
            elif arr.shape[0] != n:
                raise SchemaError(
                    f"Predict: feature {key!r} carries {arr.shape[0]} "
                    f"examples but earlier features carry {n}"
                )
            out[key] = arr
        if not 1 <= (n or 0) <= self.max_batch:
            raise SchemaError(
                f"Predict: {n} examples; must be 1..{self.max_batch}"
            )
        return out

    # hot-path: the per-request gRPC handler — parse, enqueue, park on the
    # flush fan-back; never a device touch (the flusher owns the forward)
    def _predict(self, req: Dict[str, Any]) -> Dict[str, Any]:
        t0 = time.perf_counter()
        lane = req.get("lane", DEFAULT_LANE)
        if lane not in LANES:
            raise SchemaError(
                f"Predict: unknown priority lane {lane!r}; expected one "
                f"of {list(LANES)}"
            )
        features = self._parse_features(req["features"])
        handle = self._batcher.submit(features, lane=lane)
        outputs, meta = handle.result(timeout_s=30.0)
        with self._state_lock:
            self._requests += 1
        self._g_requests.inc()
        self._g_lane_requests[lane].inc()
        self._g_request_ms[lane].observe((time.perf_counter() - t0) * 1e3)
        return {
            "outputs": _listify(outputs),
            "model": self.spec.name,
            "step": meta.get("step", -1),
        }

    def _run_batch(self, batch: Dict[str, np.ndarray], n_real: int) -> Tuple[Any, Dict]:
        """The flusher's runner: ONE jitted forward of the padded batch on
        the serving snapshot current at flush time.  Holding the snapshot
        as a local is the zero-drop reload mechanism: a concurrent swap
        retargets the NEXT flush, never this one."""
        with self._state_lock:
            live = self._live
        import jax

        out = self.trainer.run_predict_step(live.state, batch)
        return jax.device_get(out), {"step": live.step}

    def _collect_gauges(self) -> None:
        """Scrape-time collector (gauge-discipline: never the request
        path): batcher/cache/reload state re-published from the stats()
        surfaces, plus the goodput/SLO gauges — live p99 estimated from
        the request histogram on the shared bucket grid, served beside the
        operator's target as a ratio (> 1.0 = the SLO is blown NOW)."""
        g = self.gauges
        stats = self._batcher.stats()
        g.gauge("edl_serving_queue_depth", "requests parked in the "
                "micro-batcher").set(float(stats["queued"]))
        g.gauge("edl_serving_shed_overload", "requests shed at the "
                "queue-row bound").set(float(stats["shed_overload"]))
        g.gauge("edl_serving_expired", "requests expired at flush time"
                ).set(float(stats["expired"]))
        # Per-lane shed/expiry attribution (r19 satellite): the autoscaler
        # and the SLO dashboard must tell bulk shed (by design under the
        # shed-bulk-first policy) from online shed (a capacity red alert).
        for lane, ls in stats["lanes"].items():
            g.counter(
                "edl_serving_shed_total",
                "requests shed at admission or evicted, by priority lane",
                labels={"lane": lane},
            ).set_total(float(ls["shed"]))
            g.counter(
                "edl_serving_expired_total",
                "requests expired at flush time, by priority lane",
                labels={"lane": lane},
            ).set_total(float(ls["expired"]))
            g.gauge(
                "edl_serving_lane_queued_rows",
                "rows parked in the micro-batcher, by priority lane",
                labels={"lane": lane},
            ).set(float(ls["queued_rows"]))
        for bucket, n in stats["flushes_by_bucket"].items():
            g.counter(
                "edl_serving_bucket_flushes_total",
                "flushes per padded batch bucket (bucketed compiles)",
                labels={"bucket": bucket},
            ).set_total(float(n))
        served = stats["rows_served"]
        g.gauge(
            "edl_serving_batch_fill_ratio",
            "real rows / flushed rows (padding waste is 1 - this)",
        ).set(served / (served + stats["rows_padded"])
              if served + stats["rows_padded"] else 0.0)
        for key, cache in self._caches.items():
            cs = cache.stats()
            hits, misses = cs["hits"], cs["misses"]
            g.gauge(
                "edl_serving_cache_hit_ratio",
                "hot-id embedding cache hit rate",
                labels={"table": key},
            ).set(hits / (hits + misses) if hits + misses else 0.0)
            g.gauge(
                "edl_serving_cache_rows", "cached rows",
                labels={"table": key},
            ).set(float(cs["size"]))
        with self._state_lock:
            step, reloads = self._live.step, self._reloads
        g.gauge("edl_serving_step", "live model step").set(float(step))
        g.gauge("edl_serving_reloads", "hot reloads performed").set(
            float(reloads)
        )
        # The SLO gauges track the ONLINE lane: bulk is throughput traffic
        # whose latency is not what the autoscaler protects.
        p99 = self._g_request_ms["online"].quantile(0.99)
        if p99 is not None:
            g.gauge(
                "edl_serving_p99_ms",
                "live online-lane request p99 (bucket-grid estimate)",
            ).set(p99)
            g.gauge(
                "edl_serving_p99_target_ms", "operator SLO target"
            ).set(self.target_p99_ms)
            g.gauge(
                "edl_serving_slo_ratio",
                "live p99 over the target — > 1.0 means the SLO is "
                "blown right now",
            ).set(p99 / self.target_p99_ms if self.target_p99_ms else 0.0)

    def _model_info(self, req: Dict[str, Any]) -> Dict[str, Any]:
        with self._state_lock:
            step = self._live.step
            reloads = self._reloads
            last_swap_ms = self._last_swap_ms
            last_load_s = self._last_load_s
            requests = self._requests
        return {
            "model": self.spec.name,
            "step": step,
            "max_batch": self.max_batch,
            "max_delay_ms": self.max_delay_ms,
            "batch_buckets": list(self._shape_buckets),
            "features": {
                k: {"dtype": str(v.dtype), "example_shape": list(v.shape[1:])}
                for k, v in self._features.items()
            },
            "requests": requests,
            "reloads": reloads,
            "last_swap_ms": round(last_swap_ms, 3),
            "last_load_s": round(last_load_s, 3),
            "batcher": self._batcher.stats(),
            "cache": {k: c.stats() for k, c in self._caches.items()},
        }

    # ---- lifecycle ----

    @property
    def address(self) -> str:
        return f"localhost:{self.port}"

    @property
    def metrics_address(self) -> Optional[str]:
        """host:port of the live /metrics endpoint (after start(); None
        when gauge_port < 0 or the bind failed)."""
        return (
            self._metrics_server.address
            if self._metrics_server is not None else None
        )

    def start(self) -> "ServingServer":
        self._server.start()
        if self._watcher is not None:
            self._watcher.start()
        # The scrape endpoint runs its own daemon threads — a replica
        # wedged past its knee must still answer /metrics (the whole
        # point of serving the SLO ratio live).
        from elasticdl_tpu.common.metrics_http import maybe_start

        self._metrics_server = maybe_start(
            self._gauge_port,
            self.gauges.render_prometheus,
            health_fn=lambda: {"role": "serving", "model": self.spec.name},
            registry=self.gauges,
        )
        logger.info(
            "serving %s on port %d (max_batch %d, deadline %.1fms)",
            self.spec.name, self.port, self.max_batch, self.max_delay_ms,
        )
        return self

    def wait(self) -> None:
        self._server.wait_for_termination()

    def stop(self, grace: float = 1.0) -> None:
        if self._metrics_server is not None:
            self._metrics_server.stop()
            self._metrics_server = None
        # Unhook from the (possibly process-shared) registry: a stopped
        # replica must neither keep publishing its frozen stats nor be
        # pinned in memory by the registry's collector reference.
        self.gauges.remove_collector(self._collect_gauges)
        if self._watcher is not None:
            self._watcher.stop()
        # grpc's stop() is non-blocking (it returns an Event); WAIT the
        # grace window out before closing the batcher, or a handler that
        # was admitted pre-stop would hit BatcherClosed at submit() and
        # fail a request the grace period promised to finish.
        self._server.stop(grace).wait(grace + 5.0)
        self._batcher.close()
