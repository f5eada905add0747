"""The model contract: what a model-zoo entry must provide.

Reference parity: ElasticDL loads a user module from ``--model_zoo`` /
``--model_def`` and expects ``custom_model()`` (a Keras model), ``loss``,
``optimizer``, ``feed`` plus optional ``eval_metrics_fn`` [U — upstream
contract; fork mount was empty at survey time].  Here the same roles are pure
functions over pytrees so the whole step jits:

- ``init(rng) -> params``                 ~ custom_model() variable creation
- ``apply(params, batch, train) -> out``  ~ model.call
- ``loss(out, batch) -> scalar``          ~ loss
- ``metrics(out, batch) -> dict``         ~ eval_metrics_fn
- ``optimizer``                           ~ optimizer (optax)
- ``feed(records) -> batch``              ~ feed / dataset_fn
- ``predict(params, batch) -> outputs``   ~ predict-mode / serving outputs
  (client-ready values, e.g. probabilities; defaults to apply(train=False))
- ``embedding_tables``                    ~ elasticdl.layers.Embedding usage:
  names of params that are sparse embedding tables, which the
  ParameterServer strategy shards row-wise over the mesh.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

Params = Any  # pytree
Batch = Any  # pytree of arrays


@dataclasses.dataclass(frozen=True)
class EmbeddingTableSpec:
    """Declares one mesh-sharded embedding table inside the param pytree.

    ``path`` addresses the table array in the params pytree (tuple of keys).
    The table is **div-sharded** by row over the mesh's embedding axis: with
    ``n`` shards and padded vocab ``V'``, shard ``i`` owns contiguous rows
    ``[i*V'/n, (i+1)*V'/n)`` (GSPMD's natural layout of a global array — see
    ``elasticdl_tpu.ops.embedding``).  This plays the role of the reference
    PS's partitioned embedding KV store; load balance across shards is
    irrelevant here because the collective lookup does uniform masked work on
    every device regardless of the id distribution.
    """

    path: Tuple[str, ...]
    vocab_size: int
    dim: int


@dataclasses.dataclass(frozen=True)
class HostTableIO:
    """One HOST-TIER embedding table: rows live in the native C++ store
    (``ps/host_store.HostEmbeddingStore``) on the worker host, not in HBM —
    the reference's external-PS tier, for tables too large for the mesh.

    Per step the trainer pulls the batch's rows (``ids_fn`` computes the ids
    host-side in numpy, matching the model's on-device id math bit-for-bit),
    injects them into the batch under the table's key, differentiates the
    jitted step with respect to the injected array, and pushes the sparse
    cotangents back; the store applies its own optimizer per distinct id
    with duplicates pre-accumulated (IndexedSlices semantics, server-side —
    SURVEY.md §2 #10).
    """

    ids_fn: Callable[[Batch], Any]  # numpy batch -> numpy ids [b, F]
    dim: int
    optimizer: str = "adagrad"
    learning_rate: float = 0.01
    init_scale: float = 0.05
    # Sequence-parallel models ONLY: declares that ids_fn returns per-TOKEN
    # ids [b, S(, ...)] whose dim 1 is the model's sequence dim, so the
    # injected rows legally shard with the sequence.  Without the
    # declaration a [b, F]-shaped table under SP would silently
    # feature-slice — the trainer refuses instead (parallel/trainer.py).
    per_token: bool = False


@dataclasses.dataclass(frozen=True)
class Adam:
    """Plain Adam, declared: ``optax.adam(learning_rate, b1, b2, eps)`` and
    nothing else (no weight decay, no clipping, no schedule), as numbers the
    trainer can read.  A ``ModelSpec`` given one as its ``optimizer`` holds
    the optax transformation built from it there and the record itself as
    ``adam`` — what lets the trainer apply a swept table's update inside
    the merge sweep (ops/table_grad.sweep_adam) instead of calling optax on
    that leaf."""

    learning_rate: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8

    def transformation(self):
        import optax

        return optax.adam(self.learning_rate, b1=self.b1, b2=self.b2, eps=self.eps)


@dataclasses.dataclass
class ModelSpec:
    name: str
    init: Callable[..., Params]  # (rng) -> params
    apply: Callable[..., Any]  # (params, batch, train=bool) -> outputs
    loss: Callable[[Any, Batch], Any]  # (outputs, batch) -> scalar
    metrics: Callable[[Any, Batch], Dict[str, Any]]
    optimizer: Any  # optax.GradientTransformation, or an Adam record (above)
    feed: Optional[Callable[[Sequence[bytes]], Batch]] = None
    embedding_tables: List[EmbeddingTableSpec] = dataclasses.field(
        default_factory=list
    )
    # Host-tier tables: batch key -> HostTableIO.  The model's apply reads
    # the injected vectors from the batch under the key instead of looking
    # up a params table.
    host_io: Dict[str, "HostTableIO"] = dataclasses.field(default_factory=dict)
    # Which batch dimension the mesh axis shards: 0 = data parallelism
    # (examples, the default), 1 = sequence/context parallelism (each device
    # holds every example's [S/n] chunk — ring attention territory).  Leaves
    # with ndim <= batch_shard_dim (e.g. per-example masks under SP)
    # replicate on a 1-D mesh; on hierarchical (dp, ep) meshes they follow
    # the example dim's dp sharding (trainer._batch_spec_for).
    batch_shard_dim: int = 0
    # Tensor-parallel sharding plan (r20, the 2D ``(dp, tp)`` mesh): a
    # callable ``(params) -> tree`` matching the params structure whose
    # leaves are the int dim each weight shards over the ``tp`` axis
    # (Megatron column/row splits) or None for replicated leaves.  The
    # trainer uses it to lay params AND their optimizer moments out on
    # the tp axis; None (the default) means the model is tp-oblivious
    # and only ever runs on 1-D / (dp, ep) meshes.
    tensor_sharding: Optional[Callable[[Params], Any]] = None
    # Example batch (tiny) for compile checks / shape inference.
    example_batch: Optional[Callable[[int], Batch]] = None
    # Inference entry point (the serving tier's forward, and predict-mode
    # jobs): ``(params, batch, ctx=...) -> per-example outputs`` ready for a
    # client — e.g. sigmoid probability for the binary tabular models,
    # class probabilities for mnist — instead of raw training logits.
    # None = serve ``apply(params, batch, train=False)`` outputs as-is.
    # Jitted inside build_predict_step, so the transform is free on device.
    predict: Optional[Callable[..., Any]] = None
    # Keys of ``metrics`` that are COUNTS of what a step did on a device (a
    # routed model's slots, say), not model metrics, each with the help
    # text of its gauge ``edl_<key>_total``.  This is their one
    # declaration: the train step sums them over the devices instead of
    # averaging, the worker sums them over steps into counters of the same
    # name and never reports them as a task's metrics; evaluation drops
    # them.
    step_counters: Mapping[str, str] = dataclasses.field(default_factory=dict)
    # The model's own rule for parameters that no gradient moves (a
    # router's correction bias, say): ``(params, out) -> params``, given the
    # parameters as the optimizer left them and the step's ``apply`` output,
    # inside the jitted train step, after the optimizer's update.  The
    # model builds its ``optimizer`` so that it leaves those leaves alone
    # (no gradient reaches them; it masks them out of any weight decay),
    # and sums over the mesh inside ``apply`` whatever the rule reads, so
    # that every replica makes the same move.
    after_update: Optional[Callable[[Params, Any], Params]] = None
    # The model rematerialises its blocks through ``ops/remat.plan``: the
    # trainer then resolves ``ParallelContext.remat_keep_bytes`` (what the
    # blocks may keep of their activations) from the device's memory before
    # it traces the model, and holds the compiled step to the device.
    rematerialises: bool = False
    # The Adam record ``optimizer`` was declared as, if it was.
    adam: Optional[Adam] = dataclasses.field(default=None, init=False)
    # ``(model_zoo, model_def, params)`` where :func:`load_model_spec` made
    # this spec: the parameters a key of the worker's program store digests
    # (common/program_store.py).  None for a spec built any other way, a
    # ``dataclasses.replace`` of a loaded one included: its step is traced.
    loaded_with: Optional[Tuple[str, str, Mapping[str, Any]]] = dataclasses.field(default=None, init=False)

    def __post_init__(self):
        if isinstance(self.optimizer, Adam):
            self.adam = self.optimizer
            self.optimizer = self.adam.transformation()


def load_model_spec(model_zoo: str, model_def: str, **params: Any) -> ModelSpec:
    """Load ``model_spec`` from a zoo module.

    ``model_def`` is "module.function" relative to the ``model_zoo`` package,
    mirroring the reference's ``--model_zoo``/``--model_def`` resolution.
    """
    module_name, _, fn_name = model_def.rpartition(".")
    if not module_name:
        raise ValueError(
            f"--model_def must look like 'module.function', got {model_def!r}"
        )
    module = importlib.import_module(f"{model_zoo}.{module_name}")
    fn = getattr(module, fn_name)
    spec = fn(**params)
    if not isinstance(spec, ModelSpec):
        raise TypeError(f"{model_def} returned {type(spec)}, expected ModelSpec")
    spec.loaded_with = (model_zoo, model_def, dict(params))
    return spec


def load_model_spec_for_job(config: Any) -> ModelSpec:
    """Load the model for a JobConfig, plumbing job-level knobs.

    ``--learning_rate`` / ``--compute_dtype`` flags are forwarded to the model
    fn when it accepts them; explicit ``--model_params`` entries win (same
    precedence the reference gives model-module definitions over defaults).
    """
    import inspect

    params: dict = {}
    module_name, _, fn_name = config.model_def.rpartition(".")
    module = importlib.import_module(f"{config.model_zoo}.{module_name}")
    accepted = inspect.signature(getattr(module, fn_name)).parameters
    if "learning_rate" in accepted:
        params["learning_rate"] = config.learning_rate
    if "compute_dtype" in accepted:
        params["compute_dtype"] = config.compute_dtype
    params.update(config.parsed_model_params())
    return load_model_spec(config.model_zoo, config.model_def, **params)
