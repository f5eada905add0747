"""Job configuration — the cross-process "config bus".

The reference (ElasticDL) uses a layered argparse flag set
(``elasticdl/python/common/args.py`` [U: mount empty at survey time]) that the
client validates, the master re-parses, and the master serializes into worker /
PS pod command lines.  We keep the same pattern with one typed dataclass that
(a) parses from the same flag names the reference exposes
(``--distribution_strategy``, ``--model_zoo``, ``--model_def``,
``--minibatch_size``, ...), and (b) round-trips losslessly through a JSON
environment variable so the master can hand it to worker pods.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
from typing import Any, Dict, List, Optional


class DistributionStrategy:
    """Mirrors the reference's --distribution_strategy values.

    In the TPU rebuild both strategies compile to a single jitted step over a
    mesh; the difference is how *sparse* parameters are laid out:

    - ALLREDUCE: all params replicated, grads pmean'd over the ``dp`` axis.
    - PARAMETER_SERVER: embedding tables row-sharded over the mesh (the
      HBM-resident "parameter server"), dense params replicated + pmean.
      Lookups are collective (all_gather ids + reduce_scatter vectors)
      instead of the reference's gRPC pull/push.
    - LOCAL: single device, no collectives (reference's Local mode).
    """

    LOCAL = "Local"
    ALLREDUCE = "AllReduce"
    PARAMETER_SERVER = "ParameterServer"

    ALL = (LOCAL, ALLREDUCE, PARAMETER_SERVER)


@dataclasses.dataclass
class JobConfig:
    """All knobs for one training/evaluation/prediction job."""

    # --- model zoo contract (reference: --model_zoo / --model_def) ---
    model_zoo: str = "elasticdl_tpu.models"
    model_def: str = "mnist.model_spec"
    model_params: str = ""  # free-form "k=v;k=v" forwarded to the model fn

    # --- job identity / mode ---
    job_name: str = "elasticdl-job"
    job_type: str = "training"  # training | evaluation | prediction
    distribution_strategy: str = DistributionStrategy.ALLREDUCE

    # --- data (reference: --training_data / --validation_data etc.) ---
    training_data: str = ""
    validation_data: str = ""
    prediction_data: str = ""
    prediction_outputs: str = ""  # dir for predict-mode outputs (.npy per task)
    data_reader_params: str = ""
    # Decoded batches prepared ahead of the device step by a background
    # thread (data/prefetch.py) — the tf.data-pipeline role of the
    # reference's ingest (SURVEY §2 #14).  0 disables (strict alternation:
    # decode, step, decode, ...); the default keeps the host decoding while
    # the TPU computes, bounding host memory at ``depth`` extra batches.
    prefetch_depth: int = 2
    # Whole-task fused dispatch: all of a task's full minibatches run as ONE
    # jitted lax.scan — one decode, one H2D transfer, one dispatch per task
    # (per-step dispatch cost ~half the step wall-clock on the retired
    # backend's remote-attached chip; docs/perf.md).  Its own knob: r4
    # gated this on ``prefetch_depth > 0``, so the debugging setting
    # ``--prefetch_depth=0`` silently reverted the worker to per-step
    # dispatch (VERDICT r4 Weak #4).  Off = per-step dispatch (per-step
    # metrics visibility, smaller transfers — a debugging mode).
    fused_task_scan: bool = True
    # Task-level pipelining (single-worker-process mode): overlap the
    # previous task's metrics fetch + report with this task's dispatched
    # steps.  Formerly also coupled to --prefetch_depth; same fix.
    task_pipelining: bool = True
    # Parallel ingest (r9, data/ingest_pool.py): a task's record range is
    # split into minibatch-aligned sub-chunks read+decoded concurrently on
    # a bounded thread pool (the C++ codec and recordio read release the
    # GIL), reassembled in order so the stacked batch is bit-identical to
    # the serial path.  0 = auto (host cores, capped at 4); 1 = serial
    # (the pre-r9 path, byte for byte).  Only engages on readers declaring
    # thread_safe_ranges.
    ingest_threads: int = 0
    # Prep-ahead pipeline depth: up to this many leased tasks have their
    # host half (read + decode + stack) in flight concurrently while
    # earlier tasks' device work streams.  1 = the r6 one-slot behavior.
    # Each in-flight prep holds one task's stacked host batch in memory.
    prep_depth: int = 2
    # Batched task leases: GetTask/GetGroupTask may hand out up to this
    # many tasks per RPC (one control-plane RTT amortized over the batch);
    # the worker buffers the extras locally and returns unstarted ones to
    # the master on preemption or membership change.  1 = one task per
    # RPC (the pre-r9 wire behavior).
    lease_batch: int = 4

    # --- schedule ---
    minibatch_size: int = 64
    num_epochs: int = 1
    num_minibatches_per_task: int = 8  # shard granularity, as in the reference
    max_steps: int = 0  # 0 = until tasks exhausted
    evaluation_steps: int = 0  # 0 = eval at epoch end only
    learning_rate: float = 1e-3

    # --- cluster shape ---
    # (The reference's --use_tpu flag is intentionally absent: the platform
    # comes from the environment/driver, so the flag could not change
    # behavior here, and dead flags lie.)
    num_workers: int = 1
    # PS pods for the HOST tier (ps/service.py): 0 = host-tier tables live in
    # an in-process store on the (single) worker host; n > 0 = the master
    # launches n PS service pods and every table partitions by id mod n
    # across them — required for host-tier tables on multi-process meshes.
    # Mesh-sharded (HBM) tables never use PS pods; they shard over the whole
    # mesh by construction (ops/embedding.py).
    num_ps_pods: int = 0
    # Async parameter-server mode (the reference's --use_async): host-tier
    # row pulls for the next minibatch overlap the in-flight device step,
    # reading rows one un-applied push stale (bounded staleness 1).  False =
    # sync-by-version (every pull sees every prior push).  Only host-tier
    # tables are affected: mesh-sharded tables and dense params live inside
    # the jitted step and are always exact.
    use_async: bool = False
    # Staleness bound for --use_async: up to this many steps' host-tier
    # pushes may be outstanding when a pull happens (1 = the classic
    # async-PS window).  Deeper bounds hide more host RPC latency behind
    # device steps at the cost of staler rows.  The trade is not measured
    # on current code, so the default stays at the least-stale depth.
    async_staleness: int = 1
    # host:port list of the PS shards, comma-separated, in shard order.  Set
    # by the master onto the worker pod env; settable by hand to point
    # workers at an externally managed PS fleet.
    ps_addresses: str = ""
    # How the master launches workers: "process" (local subprocesses),
    # "kubernetes" (GKE TPU pods), or "fake" (tests).  The reference's
    # equivalent choice is implicit in running on k8s at all.
    pod_backend: str = "process"
    worker_image: str = "elasticdl-tpu:latest"  # pod image (kubernetes backend)
    namespace: str = "default"
    # Host workers use to reach the master service.  Empty = auto: localhost
    # for local backends, this pod's IP (MY_POD_IP downward API) or FQDN for
    # the kubernetes backend.
    master_advertise_host: str = ""
    # Multi-host: workers advertise their host and join a jax.distributed
    # world (rank 0 hosts the coordination service on this port) so one mesh
    # spans every worker's chips.  Leave False for single-host jobs.
    multihost: bool = False
    coordinator_port: int = 8476
    # jax.distributed coordination-service peer-death detection bound.
    # Governs how long a survivor blocked in a collective on a dead peer
    # waits before aborting into the RESTART/re-join path (JAX's own
    # default is 100 s, which was most of a re-rendezvous on the CPU
    # harness).  30 s tolerates heartbeat starvation on oversubscribed
    # hosts; dedicated TPU hosts can drop to 10 s (docs/perf.md).
    distributed_heartbeat_timeout_s: float = 30.0
    # Master->survivor death push: the liveness-heartbeat thread polls the
    # master's membership, and when a gang peer has DEPARTED while the main
    # thread stays wedged in a blocked collective for this grace window, the
    # process force-exits RESTART immediately instead of waiting out
    # --distributed_heartbeat_timeout_s (the avoidable middle of a
    # re-rendezvous; Worker.death_watch_tick documents the exact
    # conditions).  <= 0 disables the push.  1.5 s: long enough for an
    # unblocked main thread to hit its per-task membership check first,
    # short enough to beat the coordination-heartbeat abort by 25x.
    death_push_grace_s: float = 1.5
    # Hierarchical mesh (parallel/mesh.py): > 1 builds a 2-D (dp, ep) mesh
    # whose outer dp axis strides across hosts/slices — gradient psums ride
    # DCN, but embedding tables shard over the inner ep axis so the
    # latency-sensitive ragged all-to-all stays on ICI within a slice.
    # 1 (default) keeps the flat 1-D mesh.  Must divide the device count
    # (elastic resizes that break divisibility fall back to 1-D).
    dcn_data_parallelism: int = 1
    # Hybrid-parallel mesh (r20, parallel/mesh.py): > 1 builds the 2-D
    # (dp, tp) mesh — models declaring a tensor_sharding plan split their
    # weight matrices over the inner tp axis (Megatron column/row splits)
    # and the batch shards over the outer dp axis.  This is the CONFIGURED
    # tensor-parallel degree; elastic reform resolves the legal shape for
    # the live device count (resolve_2d_shape: dp shrinks first, tp only
    # degrades along its divisor chain when fewer than tp devices remain).
    # Mutually exclusive with dcn_data_parallelism > 1.
    tensor_parallelism: int = 1

    # --- collectives (r15, parallel/collectives.py — graftreduce) ---
    # How gradient/metric reductions run over the data-parallel axis:
    #   flat         — one all-replica collective per reduction (pre-r15);
    #   hierarchical — big leaves reduce intra-host first (reduce-scatter
    #                  over the cheap hop), then inter-host over the
    #                  1/n_local residue, then re-gather locally — cutting
    #                  inter-host bytes by the local fan-in.  Falls back
    #                  to flat when the mesh presents no (host, local)
    #                  factorization (single host and no
    #                  --collective_local_size override);
    #   auto         — hierarchical exactly when the mesh's real process
    #                  grouping (or the override) factors the axis.
    # Flat-vs-hierarchical parity is float reduction order only
    # (tests/test_collectives.py holds it).
    collective: str = "auto"
    # Pin (or, on the CPU harness, emulate) the intra-host fan-in: how
    # many consecutive positions of the dp axis count as one host's
    # local group.  0 = derive from the mesh's process grouping
    # (parallel/mesh.dp_factorization).  Must divide the axis size.
    collective_local_size: int = 0
    # Leaves smaller than this many elements always reduce with ONE flat
    # collective — a scalar's three hierarchical launches cost more than
    # the inter-host bytes they save.
    collective_min_elems: int = 4096
    # In-step (in-collective) straggler deadline, milliseconds.  > 0 arms
    # the worker's collective gate (single-process meshes): each dp
    # shard's host-side contribution must be ready within this bound or
    # the step dispatches WITHOUT it — the shard's weight in the
    # subgroup mask drops to 0, every mean renormalizes over the
    # survivors (sum/|G'|), and the exclusion is charged against the
    # same bounded skip accounting as the r13 task-boundary deadline
    # (gang_skip_budget consecutive exclusions of one shard escalate to
    # waiting it out, so a dead contributor surfaces as a visible stall,
    # never silent data loss).  The exclusion mask is an INPUT to the
    # jitted step: changing the excluded set never recompiles.  0 =
    # disabled (a stalled contributor blocks the dispatch, pre-r15).
    collective_deadline_ms: float = 0.0

    # --- elasticity ---
    relaunch_on_worker_failure: bool = True
    max_worker_relaunch: int = 3
    # Process backend only: keep one pre-booted spare worker parked (python
    # + jax + framework imports already paid) that a relaunch
    # adopts by writing its worker id to a go-file — the boot-tail half of
    # the re-rendezvous cut (docs/perf.md).  Costs one idle interpreter's
    # memory; off by default.
    warm_worker_standby: bool = False

    # --- checkpoint (reference: --checkpoint_steps / --checkpoint_dir) ---
    checkpoint_steps: int = 0
    checkpoint_dir: str = ""
    keep_checkpoint_max: int = 3

    # --- master / control plane ---
    master_addr: str = ""  # host:port of the master gRPC service
    # Port the master gRPC service binds (0 = ephemeral).  A FIXED port is
    # what makes a master restart a blip instead of a job failure (r18):
    # workers ride out the outage re-dialing the address they already
    # hold, so the relaunched master must answer at the same one.
    master_port: int = 0
    # Per-call deadline on every worker->master RPC (RpcMasterProxy).  Was
    # a hardcoded 60 s before r18; jobs with huge trace envelopes or slow
    # control planes tune it here.
    master_call_timeout_s: float = 60.0
    # Master-outage ride-through budget (r18): on a transport-level
    # failure (UNAVAILABLE — the master is down/restarting) the worker's
    # proxy retries the call under the shared exponential-backoff-with-
    # jitter helper for up to this many seconds of outage, holding its
    # buffered leases and in-flight prep, then re-registers + reconciles
    # when the master answers again.  Exceeding the budget is a terminal
    # error (the task loop fails loud).  0 disables the ride-through
    # (pre-r18 behavior: first UNAVAILABLE surfaces immediately).
    master_outage_tolerance_s: float = 120.0
    task_timeout_s: float = 600.0
    # How long the master waits after the job finishes for workers to exit on
    # their own (they are writing final checkpoints — orbax + host-tier
    # snapshots); the teardown then proceeds regardless.  Raise for jobs
    # whose final snapshot is large.
    shutdown_grace_s: float = 120.0

    # --- observability ---
    log_level: str = "INFO"
    # grafttrace (common/trace.py): per-process span recorder for the
    # cross-process structured trace.  Workers emit spans for every
    # PhaseTimers phase, RPC boundary, gang wait and elastic transition,
    # ship bounded slices to the master on the heartbeat/report channel,
    # and tools/trace_dump.py merges a live job's buffers into one
    # Perfetto-loadable file (docs/observability.md).  Off by default.
    # What the ring costs a job on the chip has not been measured; the
    # same spans written into a --profile_dir window cost nothing the
    # report clock can see (PERF.md section 6, PR 24).
    trace: bool = False
    # Ring capacity (events) of the per-process trace buffer; oldest events
    # are overwritten, so the buffer always holds the most recent window.
    trace_buffer_events: int = 65536
    # graftgauge (r14, common/gauge.py + common/metrics_http.py): every
    # process of the job — master, workers, PS shards — serves a live
    # Prometheus-text /metrics (+ /healthz JSON) scrape endpoint when
    # this is >= 0.  0 = bind an ephemeral port (the only collision-safe
    # choice on a shared config bus: two workers on one host cannot
    # share a fixed port) — each process logs its bound address as a
    # "[graftgauge] serving /metrics on ..." pod-log line, the same
    # discovery channel the chaos bench uses for its audit lines.  > 0 =
    # bind exactly that port (single-process-per-host deployments).
    # -1 (default) = no endpoint; the registry still records (its cost
    # is the point: one leaf-lock add per update, measured on the ingest
    # A/B harness — docs/observability.md), so flipping the endpoint on
    # is purely additive.
    gauge_port: int = -1
    # worker: a jax.profiler trace (device planes AND the host's spans of
    # common/trace.py, on one clock) of --profile_tasks consecutive
    # training tasks, from the second dispatch on (the first compiles).
    # The traced tasks are prepped, dispatched, settled and reported like
    # any other; the file is written off the task loop.
    profile_dir: str = ""
    profile_tasks: int = 3
    # Collect and write the trace ON the task loop when the window closes:
    # the loop stands still meanwhile, and the files are whole before the
    # next task reports.  For a job that may be killed sooner after the
    # window than the writer's thread needs (20 s for a step of 12,000
    # device ops: PERF.md section 6, PR 40); off, the loop goes on and the
    # files appear when the thread is done.
    profile_inline: bool = False
    metrics_dir: str = ""  # master: JSONL + TensorBoard scalar stream
    # Process backend: capture each worker pod's stdout+stderr to
    # {pod_log_dir}/{pod-name}.log (the local analog of kubectl logs; pod
    # names are unique per incarnation, so one file per life).  "" =
    # inherit the master's stdio.
    pod_log_dir: str = ""
    # Spares kept parked when --warm_worker_standby: 1 covers a lone
    # relaunch; a peer-death recovery relaunches TWO processes (the dead
    # pod + the survivor's RESTART), so multihost fleets that want the
    # whole recovery warm use 2.  Each spare holds one idle interpreter.
    standby_pool: int = 1

    # --- tail tolerance / fault injection (r13, chaos/inject.py) ---
    # graftchaos plan: scheduled faults (kill rank-k at step N, stall a
    # prep, drop/delay a master RPC, delay a PS pull) delivered through
    # no-op-when-disabled hook points in the worker, the RPC client and
    # the PS service — docs/robustness.md documents the plan grammar.
    # Rides the config bus so worker/PS pods inherit it; the GRAFT_CHAOS
    # env var arms processes the bus does not reach.  "" = disabled
    # (bit-exact no-op: one attribute check per hook crossing).
    chaos: str = ""
    # Deadline-bounded gang boundary (master-side, lockstep mode only):
    # when a rank lags the gang's newest lockstep seq by more than this
    # many milliseconds, the master SKIPS the straggler — its in-flight
    # gang tasks requeue with bounded skip accounting (gang_skip_budget)
    # and the rank is evicted so the gang re-forms without waiting out
    # the full task/heartbeat timeouts (OptiReduce's timeout-bounded
    # collective, done at the boundary this architecture owns).  The
    # evicted rank restarts and rejoins the next reform; nothing is
    # trained twice or lost (dispatcher skip accounting, proven by
    # test).  0 = disabled (the pre-r13 wait-forever boundary).
    gang_deadline_ms: float = 0.0
    # How many times one task may be deadline-skipped before a further
    # skip is charged like a FAILURE (retry budget -> poison-abandon): a
    # shard that deterministically stalls a rank must not ping-pong the
    # gang through skip-reform cycles forever.
    gang_skip_budget: int = 2

    # --- optimizer state layout (parallel/trainer.py) ---
    # ZeRO-style cross-replica sharding of the optimizer update: every
    # param-shaped optimizer-state leaf for a REPLICATED (dense) param is
    # partitioned over the data-parallel mesh axis (flattened and
    # zero-padded to divisibility), the train step reduce-scatters dense
    # grads, applies the optax update on each replica's 1/dp shard only,
    # and all-gathers the fresh params — all inside the one jitted XLA
    # program.  Cuts per-replica optimizer HBM by ~dp and removes the
    # redundant full weight update every replica used to compute
    # ("Automatic Cross-Replica Sharding of Weight Update", PAPERS.md).
    #   replicated — every replica holds full state (pre-r11 behavior);
    #   sharded    — always shard (dp > 1 meshes; dp == 1 is a no-op);
    #   auto       — shard when the replicated dense optimizer state would
    #                exceed --optimizer_sharding_auto_mb per replica.
    # Mesh-sharded embedding tables are unaffected either way: their
    # optimizer slots already co-shard with the table rows.  Checkpoints
    # are written in the canonical (unsharded) layout in every mode, so
    # they restore into any world size and either mode.
    optimizer_sharding: str = "replicated"
    optimizer_sharding_auto_mb: float = 64.0
    # Donate the train-state buffers into the jitted train step so XLA
    # reuses them for the output state (halves peak state memory; the
    # donated-input discipline TrainLoopError documents).  Off = a
    # debugging mode: failed steps keep their input state alive at the
    # cost of a second resident copy.
    donate_train_state: bool = True

    # --- precision ---
    compute_dtype: str = "bfloat16"  # MXU-native; params stay f32

    # --- sharded embedding lookup route (ops.embedding) ---
    # auto = ragged all-to-all on TPU meshes, dense (all_gather+psum_scatter)
    # on CPU; ragged_emulated exists for CPU tests of the ragged routing.
    embedding_lookup_impl: str = "auto"

    def validate(self) -> None:
        if self.distribution_strategy not in DistributionStrategy.ALL:
            raise ValueError(
                f"--distribution_strategy must be one of "
                f"{DistributionStrategy.ALL}, got {self.distribution_strategy!r}"
            )
        if self.minibatch_size <= 0:
            raise ValueError("--minibatch_size must be positive")
        if self.num_minibatches_per_task <= 0:
            raise ValueError("--num_minibatches_per_task must be positive")
        if self.job_type not in ("training", "evaluation", "prediction"):
            raise ValueError(f"unknown job_type {self.job_type!r}")
        if self.pod_backend not in ("process", "kubernetes", "fake"):
            raise ValueError(
                f"--pod_backend must be process|kubernetes|fake, got "
                f"{self.pod_backend!r}"
            )
        if self.num_ps_pods < 0:
            raise ValueError("--num_ps_pods cannot be negative")
        if self.prefetch_depth < 0:
            raise ValueError("--prefetch_depth cannot be negative")
        if self.ingest_threads < 0:
            raise ValueError("--ingest_threads cannot be negative (0 = auto)")
        if self.prep_depth < 1:
            raise ValueError("--prep_depth must be >= 1")
        if self.lease_batch < 1:
            raise ValueError("--lease_batch must be >= 1")
        if self.async_staleness < 1:
            raise ValueError("--async_staleness must be >= 1")
        if self.dcn_data_parallelism < 1:
            raise ValueError("--dcn_data_parallelism must be >= 1")
        if self.tensor_parallelism < 1:
            raise ValueError("--tensor_parallelism must be >= 1")
        if self.tensor_parallelism > 1 and self.dcn_data_parallelism > 1:
            raise ValueError(
                "--tensor_parallelism and --dcn_data_parallelism are "
                "mutually exclusive (no 3-D mesh)"
            )
        # Kept in sync with parallel.collectives.MODES (asserted by
        # tests); not imported from there so this module stays jax-free.
        if self.collective not in ("flat", "hierarchical", "auto"):
            raise ValueError(
                f"--collective must be flat|hierarchical|auto, got "
                f"{self.collective!r}"
            )
        if self.collective_local_size < 0:
            raise ValueError(
                "--collective_local_size cannot be negative (0 = derive "
                "from the mesh's process grouping)"
            )
        if self.collective_min_elems < 1:
            raise ValueError("--collective_min_elems must be >= 1")
        if self.collective_deadline_ms < 0:
            raise ValueError("--collective_deadline_ms cannot be negative")
        if self.optimizer_sharding not in ("replicated", "sharded", "auto"):
            raise ValueError(
                f"--optimizer_sharding must be replicated|sharded|auto, got "
                f"{self.optimizer_sharding!r}"
            )
        if self.optimizer_sharding_auto_mb <= 0:
            raise ValueError("--optimizer_sharding_auto_mb must be positive")
        if self.trace_buffer_events < 1:
            raise ValueError("--trace_buffer_events must be >= 1")
        if self.gauge_port < -1:
            raise ValueError(
                "--gauge_port must be -1 (off), 0 (ephemeral) or a port"
            )
        if self.chaos:
            # Parse-validate HERE (jax-free, stdlib): a typo'd fault plan
            # must fail the job submission, not silently never fire and
            # let a chaos run report tolerance it never exercised.
            from elasticdl_tpu.chaos.inject import parse_plan

            parse_plan(self.chaos)
        if self.master_port < 0:
            raise ValueError("--master_port must be 0 (ephemeral) or a port")
        if self.master_call_timeout_s <= 0:
            raise ValueError("--master_call_timeout_s must be positive")
        if self.master_outage_tolerance_s < 0:
            raise ValueError(
                "--master_outage_tolerance_s cannot be negative (0 = no "
                "ride-through)"
            )
        if self.gang_deadline_ms < 0:
            raise ValueError("--gang_deadline_ms cannot be negative")
        if self.gang_skip_budget < 0:
            raise ValueError("--gang_skip_budget cannot be negative")
        # Kept in sync with ops.embedding.LOOKUP_IMPLS (asserted by tests);
        # not imported from there so this module stays jax-free (the master
        # control plane and pod manager must run without jax).
        impls = ("auto", "ragged", "ragged_emulated", "dense")
        if self.embedding_lookup_impl not in impls:
            raise ValueError(
                f"--embedding_lookup_impl must be one of {impls}, got "
                f"{self.embedding_lookup_impl!r}"
            )

    # -- serialization: the config bus between master and worker pods --

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), sort_keys=True)

    @classmethod
    def from_json(cls, payload: str) -> "JobConfig":
        raw = json.loads(payload)
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in raw.items() if k in known})

    def to_env(self) -> Dict[str, str]:
        return {"ELASTICDL_JOB_CONFIG": self.to_json()}

    @classmethod
    def from_env(cls, environ: Optional[Dict[str, str]] = None) -> "JobConfig":
        environ = os.environ if environ is None else environ
        payload = environ.get("ELASTICDL_JOB_CONFIG")
        if not payload:
            raise KeyError("ELASTICDL_JOB_CONFIG not set")
        return cls.from_json(payload)

    def parsed_model_params(self) -> Dict[str, Any]:
        return _parse_kv_string(self.model_params)

    def parsed_data_reader_params(self) -> Dict[str, Any]:
        return _parse_kv_string(self.data_reader_params)


def _parse_kv_string(spec: str) -> Dict[str, Any]:
    """Parse the reference-style "key=value;key=value" param strings."""
    out: Dict[str, Any] = {}
    for item in filter(None, (s.strip() for s in spec.split(";"))):
        if "=" not in item:
            raise ValueError(f"malformed param {item!r}, expected key=value")
        key, value = (part.strip() for part in item.split("=", 1))
        try:
            out[key] = json.loads(value)
        except json.JSONDecodeError:
            if value in ("True", "False", "None"):
                # A Python literal is no JSON: kept as a string it would be
                # truthy whatever it spells ("host_tier=False" turned the
                # host tier ON).
                spelled = "null" if value == "None" else value.lower()
                raise ValueError(
                    f"param {key}={value}: values are JSON, spell it {spelled}"
                ) from None
            out[key] = value
    return out


def build_arg_parser() -> argparse.ArgumentParser:
    """Argparse surface mirroring the reference client's flag names."""
    parser = argparse.ArgumentParser(prog="elasticdl", add_help=True)
    for field in dataclasses.fields(JobConfig):
        flag = "--" + field.name
        if field.type == "bool" or isinstance(field.default, bool):
            parser.add_argument(
                flag,
                type=lambda v: str(v).lower() in ("1", "true", "yes"),
                default=field.default,
            )
        else:
            parser.add_argument(flag, type=type(field.default), default=field.default)
    return parser


def parse_args(argv: Optional[List[str]] = None) -> JobConfig:
    namespace = build_arg_parser().parse_args(argv)
    config = JobConfig(**vars(namespace))
    config.validate()
    return config
