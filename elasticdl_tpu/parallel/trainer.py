"""Trainers: the reference's AllReduceTrainer / PS worker path as ONE jitted
step over a mesh.

Reference parity ([D: BASELINE.json north_star]; sources unverifiable — mount
empty at survey time):

- ``AllReduceTrainer.train_minibatch`` (tf.GradientTape fwd/bwd +
  ``hvd.allreduce(grads)`` + local apply) becomes a shard_map'd function:
  local fwd/bwd on each device's batch shard, ``lax.psum`` of gradients over
  the ``dp`` mesh axis, optax update — all inside one XLA program, so the
  allreduce overlaps/fuses with the backward pass instead of being a separate
  NCCL launch.
- The PS worker path (pull dense params / pull_embedding_vectors, local step,
  push_gradients) becomes the *same* step with embedding tables row-sharded
  over the mesh (see ``elasticdl_tpu.ops.embedding``); "pull" is the
  collective lookup's all_gather/psum_scatter, "push" is its AD transpose.
  The hybrid DeepFM mode (PS embeddings + allreduce dense) is therefore just
  two partition specs inside one step.

Gradient math: each device computes ``loss_local_mean / n_devices``; dense
grads are ``psum``'d (=> grad of the global batch mean), while sharded-table
grads come out of the collective transpose already globally summed, so they
are left alone.  The two paths are consistent without rescaling.
"""

from __future__ import annotations

import dataclasses
import functools
import inspect
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import struct
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common import log_utils, program_store, trace
from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.metrics import HIST_PREFIX
from elasticdl_tpu.common.platform import compile_phase_seconds, device_bytes_limit
from elasticdl_tpu.models.spec import EmbeddingTableSpec, ModelSpec
from elasticdl_tpu.parallel import collectives as coll
logger = get_logger("trainer")
from elasticdl_tpu.ops.embedding import (
    ParallelContext,
    logical_rows,
    pack_table,
    resolve_impl,
    route_taps,
    sweeps,
    table_shape,
)
from elasticdl_tpu.ops import remat
from elasticdl_tpu.ops.table_grad import sweep_adam

from elasticdl_tpu.common.jax_compat import jit_compiled, jit_donating, shard_map


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any


class TrainLoopError(RuntimeError):
    """A step failed mid-run of ``run_train_steps``.

    The jitted step DONATES its input state, so after a failure neither the
    caller's original state nor (possibly) the failing call's input still
    backs real buffers.  ``state`` carries the newest state whose buffers
    are verifiably alive (the last successful step's output), or None when
    nothing usable survives — the worker then rebuilds from the checkpoint
    instead of retrying tasks against deleted buffers forever (the pre-r4
    failure mode: one failed step wedged every subsequent task)."""

    def __init__(self, state: Optional["TrainState"], cause: BaseException):
        super().__init__(str(cause))
        self.state = state


def _state_alive(state: Optional["TrainState"]) -> bool:
    if state is None:
        return False
    try:
        return not any(
            getattr(leaf, "is_deleted", lambda: False)()
            for leaf in jax.tree.leaves(state)
        )
    except Exception:  # pragma: no cover - defensive
        return False


def _process_count(mesh: Mesh) -> int:
    """Distinct host processes owning this mesh's devices (1 = single-host)."""
    return len({d.process_index for d in mesh.devices.flat})


def sp_batch_spec(axes: Tuple[str, ...], d: int) -> P:
    """PartitionSpec for a sequence-parallel leaf of ndim > d: the sequence
    dim ``d`` shards over the INNER axis; on hierarchical meshes the example
    dim additionally shards over the outer axes.  One definition shared by
    input batch sharding and predict output sharding so the two layouts
    cannot drift apart."""
    outer = axes[:-1]
    lead = ((outer,) + (None,) * (d - 1)) if outer else (None,) * d
    return P(*lead, axes[-1])


def batch_leaf_spec(axes: Tuple[str, ...], d: int) -> P:
    """The spec for a batch-shaped leaf of ndim > d under either layout —
    the single selector used by input sharding, predict outputs, and host
    cotangents, so the three cannot drift apart."""
    return P(axes) if d == 0 else sp_batch_spec(axes, d)


def _path_keys(path) -> Tuple[str, ...]:
    keys = []
    for entry in path:
        if hasattr(entry, "key"):
            keys.append(str(entry.key))
        elif hasattr(entry, "name"):
            keys.append(str(entry.name))
        elif hasattr(entry, "idx"):
            keys.append(str(entry.idx))
        else:  # pragma: no cover
            keys.append(str(entry))
    return tuple(keys)


def _tp_dim_leaves(params: Any, tp_dims: Any) -> List[Optional[int]]:
    """Flatten a ``ModelSpec.tensor_sharding`` plan against the params
    structure, keeping the plan's None leaves (``flatten_up_to`` stops at
    the params' leaf positions, where a plain ``tree_flatten`` would
    swallow None as an empty subtree)."""
    treedef = jax.tree_util.tree_structure(params)
    if tp_dims is None:
        return [None] * treedef.num_leaves
    return treedef.flatten_up_to(tp_dims)


def params_partition_specs(
    params: Any,
    tables: List[EmbeddingTableSpec],
    axis_name: str,
    sharded: bool,
    tp_dims: Any = None,
    tp_axis: Optional[str] = None,
):
    """Partition-spec tree for params: tables row-sharded, tensor-parallel
    leaves (``tp_dims`` — the model's tensor_sharding plan, used only on a
    2D mesh where ``tp_axis`` is set) sharded on their declared dim over
    the tp axis, the rest replicated."""
    table_paths = {t.path for t in tables} if sharded else set()
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    dims = (
        _tp_dim_leaves(params, tp_dims)
        if tp_axis is not None
        else [None] * len(paths_leaves)
    )
    specs = []
    for (path, leaf), d in zip(paths_leaves, dims):
        if _path_keys(path) in table_paths:
            specs.append(P(axis_name))
        elif d is not None:
            ndim = len(getattr(leaf, "shape", ()))
            if not 0 <= d < ndim:
                raise ValueError(
                    f"tensor_sharding dim {d} out of range for param "
                    f"{_path_keys(path)} with {ndim} dims"
                )
            entry: List[Any] = [None] * ndim
            entry[d] = tp_axis
            specs.append(P(*entry))
        else:
            specs.append(P())
    return jax.tree_util.tree_unflatten(treedef, specs)


class _OptShard:
    """Per-param shard-plan entry (a deliberately UNREGISTERED class, so a
    plan tree treats it as one pytree leaf): how this dense param's
    optimizer slots lay out over the data-parallel axis.  The canonical
    param-shaped leaf flattens to [size], zero-pads to [padded] (the
    smallest multiple of the shard count — the ``pad_embedding_tables``
    move applied to the flat vector), and shards over the dp axis so each
    replica holds [padded / dp]."""

    __slots__ = ("shape", "size", "padded")

    def __init__(self, shape: Tuple[int, ...], size: int, padded: int):
        self.shape = shape
        self.size = size
        self.padded = padded

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"_OptShard(shape={self.shape}, size={self.size}, padded={self.padded})"


#: Plan marker for leaves the dp-sharding leaves alone: mesh-sharded
#: embedding tables, and (r20) tensor-parallel weight shards — in both
#: cases the optimizer slots already co-shard with the param, so the
#: ZeRO flatten/scatter must not touch them.
_OPT_KEEP = "keep"


def opt_shard_plan(
    params: Any,
    tables: List[EmbeddingTableSpec],
    sharded_embeddings: bool,
    n_shards: int,
    tp_dims: Any = None,
) -> Any:
    """Params-structured tree of ``_OptShard`` entries (dense replicated
    leaves) and ``_OPT_KEEP`` markers (mesh-sharded table leaves, and
    tensor-parallel leaves when ``tp_dims`` carries the model's plan on a
    2D mesh — their moments co-shard over ``tp``, so ZeRO's dp scatter
    skips them and their grads take the plain dp psum)."""
    table_paths = {t.path for t in tables} if sharded_embeddings else set()
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
    dims = _tp_dim_leaves(params, tp_dims)
    entries = []
    for (path, leaf), d in zip(paths_leaves, dims):
        if _path_keys(path) in table_paths or d is not None:
            entries.append(_OPT_KEEP)
            continue
        shape = tuple(np.shape(leaf))
        size = int(np.prod(shape)) if shape else 1
        padded = -(-size // n_shards) * n_shards
        entries.append(_OptShard(shape, size, padded))
    return jax.tree_util.tree_unflatten(treedef, entries)


def opt_state_partition_specs(
    optimizer: optax.GradientTransformation,
    params: Any,
    param_specs: Any,
    shard_plan: Any = None,
    shard_axis: Optional[str] = None,
):
    """Partition specs for optax state: param-shaped leaves (momenta etc.)
    inherit their param's spec — co-sharding table optimizer slots with the
    table rows, as the reference's per-PS-pod Go optimizer state does.

    With a ``shard_plan`` (ZeRO-style mode), dense param-shaped leaves are
    stored flat [padded] and partition over ``shard_axis`` instead; table
    leaves keep their co-sharded spec, non-param leaves stay replicated."""
    state_shapes = jax.eval_shape(optimizer.init, params)
    if shard_plan is None:
        return optax.tree_map_params(
            optimizer,
            lambda _, spec: spec,
            state_shapes,
            param_specs,
            transform_non_params=lambda _: P(),
        )
    return optax.tree_map_params(
        optimizer,
        lambda _, spec, entry: (
            P(shard_axis) if isinstance(entry, _OptShard) else spec
        ),
        state_shapes,
        param_specs,
        shard_plan,
        transform_non_params=lambda _: P(),
    )


def _pad_flat(x, entry: _OptShard):
    """Canonical (param-shaped) -> flat zero-padded [padded], on device."""
    v = jnp.reshape(x, (-1,))
    if entry.padded != entry.size:
        v = jnp.concatenate(
            [v, jnp.zeros((entry.padded - entry.size,), v.dtype)]
        )
    return v


def _tree_psum_except(tree: Any, skip_paths, axes, skip_axes, topo=None):
    """psum ``tree`` over ``axes``, except leaves at ``skip_paths`` which
    psum over ``skip_axes`` only (empty = left alone).

    Dense grads sum over every mesh axis; sharded-table grads come out of
    the collective lookup's transpose already summed WITHIN the embedding
    axis, so on a hierarchical mesh they still need the data-parallel axes'
    contribution (each dp replica saw different examples) — but psum'ing
    them over the embedding axis too would multiply the gradient by its
    size.  ``topo`` routes big dense leaves over the graftreduce
    hierarchical path (parallel/collectives.py)."""

    def maybe_psum(path, leaf):
        if _path_keys(path) in skip_paths:
            return coll.psum(leaf, skip_axes, topo) if skip_axes else leaf
        return coll.psum(leaf, axes, topo)

    return jax.tree_util.tree_map_with_path(maybe_psum, tree)


def _with_leaves(tree: Any, at: Sequence[int], values: Sequence[Any]) -> Any:
    """``tree`` with its ``at``-th leaves (flattening order) replaced."""
    leaves, treedef = jax.tree.flatten(tree)
    for i, value in zip(at, values):
        leaves[i] = value
    return treedef.unflatten(leaves)


def _tables_the_sweep_updates(spec: ModelSpec, ctx: ParallelContext, params: Any,
                              batch: Any, could: Sequence[int]):
    """Of the ``could``-th leaves of ``params``, those one apply looks up
    exactly once: (their positions, a zero carrier each: ops/embedding.py,
    "Handing the update rows over").  From a counting trace of the apply
    that computes nothing."""
    if not could:
        return [], ()
    leaves = jax.tree.leaves(params)
    with route_taps(hand_over=[leaves[i] for i in could]) as taps:
        jax.eval_shape(lambda: spec.apply(params, batch, train=True, ctx=ctx))
    slots = [slot for slot, _ in taps.handed]
    once = sorted((s, shape) for s, shape in taps.handed if slots.count(s) == 1)
    return (
        [could[slot] for slot, _ in once],
        tuple(jnp.zeros(shape.shape, shape.dtype) for _, shape in once),
    )


def _adam_update_with_fused_tables(
    spec: ModelSpec, params: Any, opt_state: Any, grads: Any,
    at: Sequence[int], updates: Sequence[Tuple[Any, Any]],
):
    """``spec.optimizer.update`` + ``apply_updates`` where the ``at``-th
    leaves are tables updated by the merge sweep itself: optax sees a
    one-row placeholder in their place (``grads`` already holds it) and so
    counts the step once and updates every other leaf; ``updates`` is a
    table's ``(physical ids [N], update rows [N, W])``.  The state keeps
    its pytree, shapes and dtypes."""
    adam, *rest = opt_state
    assert isinstance(adam, optax.ScaleByAdamState), type(adam)
    grad_leaves = jax.tree.leaves(grads)

    def small(tree):
        return _with_leaves(tree, at, [grad_leaves[i] for i in at])

    steps, (counted, *rest) = spec.optimizer.update(
        grads, (adam._replace(mu=small(adam.mu), nu=small(adam.nu)), *rest),
        small(params),
    )
    tables, mus, nus = (
        [jax.tree.leaves(tree)[i] for i in at] for tree in (params, adam.mu, adam.nu)
    )
    rule = dataclasses.asdict(spec.adam)
    tables, mus, nus = zip(*(
        sweep_adam(table, mu, nu, counted.count, ids, rows, **rule)
        for table, mu, nu, (ids, rows) in zip(tables, mus, nus, updates)
    ))
    new_params = _with_leaves(
        optax.apply_updates(small(params), steps), at, tables
    )
    counted = counted._replace(
        mu=_with_leaves(counted.mu, at, mus), nu=_with_leaves(counted.nu, at, nus)
    )
    return new_params, (counted, *rest)


def pad_embedding_tables(params: Any, tables: List[EmbeddingTableSpec]) -> Any:
    """Bring each declared table into the padded lane-packed [P, pack*dim]
    layout (see ops.embedding docstring), so shapes are stable across every
    mesh size.  Tables already in that shape pass through; plain [V, dim] or
    flat [V*dim] user tables are packed and zero-padded."""
    if not tables:
        return params
    by_path = {t.path: t for t in tables}

    def pad(path, leaf):
        t = by_path.get(_path_keys(path))
        if t is None:
            return leaf
        target = table_shape(t.vocab_size, t.dim)
        if leaf.ndim == 2 and leaf.shape == target:
            return leaf
        packed = pack_table(leaf, t.dim)
        if packed.shape[1] != target[1] or packed.shape[0] > target[0]:
            raise ValueError(
                f"table {t.path}: shape {leaf.shape} packs to {packed.shape}, "
                f"incompatible with the declared vocab {t.vocab_size} x dim "
                f"{t.dim} (padded shape {target})"
            )
        if packed.shape[0] < target[0]:
            # Leaf holds fewer rows than the declared vocab (e.g. a user
            # table built for the raw vocab): zero-pad up to the target.
            packed = jnp.concatenate(
                [packed, jnp.zeros((target[0] - packed.shape[0], target[1]),
                                   packed.dtype)]
            )
        return packed

    return jax.tree_util.tree_map_with_path(pad, params)


#: What a compiled step leaves free of a device's ``bytes_limit``: the line
#: it is held to is the limit less this (14.25 of a v5e's 15.75 GiB).  The
#: runtime's own peak reads 0.13 GiB over the compiler's sum, and a program
#: at the limit's edge fails to load beside the checkpoint copy of its state.
REMAT_HEADROOM = 3 * 2**29
#: What the choice aims under the line by (the estimate of a step with
#: nothing kept is within 0.1 GiB of the compiler's account, and what is kept
#: cost gpt2m_job's step 0.14 GiB more than its bytes), and what is taken off
#: the aim again, beside the overshoot, when a compiled step reads over the
#: line all the same and is compiled once more with less kept.
REMAT_REFIT_MARGIN = 2**28
#: The loss's share of a step's peak, in the logits' bytes: the float32
#: logits, and their gradient in the compute type (gpt2m_job's compiled step
#: with nothing kept: PERF.md section 6, PR 38).
_LOGITS_HELD = 1.5
#: A block's backward holds its recomputed sites and their cotangents.
_BLOCK_HELD = 2.0


@dataclasses.dataclass
class KeepPlan:
    """What a model's rematerialised blocks may keep on one device
    (``ops/remat.py``): ``line`` is what the compiled step may occupy, ``aim``
    what the choice is made for (the line, less what an earlier compile read
    over it).  The rest is filled when the step is traced: the step's
    estimated bytes with nothing kept, the budget that leaves
    (``ParallelContext.remat_keep_bytes``), and the sites' bytes."""

    line: int
    aim: int
    estimate: int = 0
    budget: int = 0
    tagged: int = 0
    kept: int = 0


def _resolve_keep_budget(spec: ModelSpec, ctx: ParallelContext, plan: KeepPlan, state, batch):
    """``ctx`` with the bytes this device's blocks may keep, and the abstract
    traces of the blocks that were made to find them (the step's own trace
    reuses them).  The budget is what ``plan.aim`` leaves over an estimate of
    the step with nothing kept, from what can be observed before the model
    is traced: the state's bytes (parameters and optimizer moments; a
    gradient is consumed by its leaf's update as it is born) and the model's
    own activations off one abstract trace of it (every block's inputs, the
    loss's working set and one block's backward).  The estimate is within
    0.1 GiB of the compiler's account of both LM cells' steps with nothing
    kept (PERF.md section 6, PR 38)."""
    with remat.survey(shapes_only=True) as held:
        out = jax.eval_shape(lambda p, b: spec.apply(p, b, train=True, ctx=ctx), state.params, batch)
    # XLA's schedule has the loss's tensors alive while the last block's
    # backward begins: the two working sets add up.
    layer_bytes = [sum(s.nbytes for s in sites) for sites in held.layers]
    loss_held = _LOGITS_HELD * remat.nbytes(out)
    block_held = _BLOCK_HELD * max(layer_bytes, default=0)
    plan.estimate = int(remat.nbytes(state) + remat.nbytes(batch) + held.block_input_bytes + loss_held + block_held)
    plan.budget = max(plan.aim - plan.estimate, 0) if held.layers else 0
    if plan.budget and block_held > loss_held:
        # Where a block's backward, not the loss, is the step's peak, what
        # the LAST block keeps is what its backward would hold recomputed
        # anyway: it costs the peak nothing (evabyte_job: everything kept
        # compiles to 0.70 GiB under its bytes), and the chooser's ties go
        # to the last layer first.
        plan.budget += layer_bytes[-1]
    return dataclasses.replace(ctx, remat_keep_bytes=plan.budget), held.traces


#: The ``JobConfig`` fields NO traced code reads, each with why: every other
#: field is in a program store key (``Trainer._program_key``; a field added
#: to ``JobConfig`` is in until it is named here,
#: ``tests/test_program_store.py``).  Observability is out so that a traced
#: (profiled) launch and a plain one share one entry; an address, a port or a
#: path of this launch is out so that a RELAUNCH finds its program.
CONFIG_FIELDS_NO_PROGRAM_READS = (
    ("job_name", "a label of the job's pods and logs"),
    ("training_data", "where records come from: what the program sees of them is the batch, whose shapes are in the key"),
    ("validation_data", "as training_data"),
    ("prediction_data", "as training_data"),
    ("prediction_outputs", "where predict-mode outputs are written, on the host"),
    ("checkpoint_dir", "where the host writes the state"),
    ("master_addr", "this launch's address of the master"),
    ("master_port", "the master's own bind"),
    ("master_advertise_host", "the master's own address"),
    ("coordinator_port", "the jax.distributed world's port: the world's shape is in the key by devices and processes"),
    ("log_level", "observability"),
    ("trace", "observability: the span ring records on the host"),
    ("trace_buffer_events", "observability: the ring's size"),
    ("gauge_port", "observability: the /metrics endpoint"),
    ("profile_dir", "observability: the profiler's window wraps dispatches, it changes none"),
    ("profile_tasks", "observability: the window's length"),
    ("profile_inline", "observability: which thread stops the profiler"),
    ("metrics_dir", "observability: the master's metrics.jsonl"),
    ("pod_log_dir", "observability: the pods' logs"),
)
_NOT_IN_A_KEY = frozenset(name for name, _ in CONFIG_FIELDS_NO_PROGRAM_READS)


def _plain(v: Any) -> Any:
    """``v`` as a program store key digests it: JSON with nothing in it that
    differs between two processes for the same thing (a function by its
    name, never by its address; an object with no ``repr`` of its own by its
    attributes)."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, dict) or type(v).__name__ == "mappingproxy":
        return {str(k): _plain(x) for k, x in sorted(v.items(), key=lambda kv: str(kv[0]))}
    if isinstance(v, (list, tuple, set, frozenset)) and not isinstance(v, P):
        return [_plain(x) for x in (sorted(v, key=str) if isinstance(v, (set, frozenset)) else v)]
    if dataclasses.is_dataclass(v) and not isinstance(v, type):
        return [type(v).__name__, {f.name: _plain(getattr(v, f.name)) for f in dataclasses.fields(v)}]
    if callable(v):
        return f"{getattr(v, '__module__', '?')}.{getattr(v, '__qualname__', type(v).__name__)}"
    if type(v).__repr__ is object.__repr__:
        return [type(v).__name__, _plain(getattr(v, "__dict__", {}))]
    return repr(v)


class _RestoredStep:
    """A program restored from the store, in its cache slot until its FIRST
    call has returned: the slot then holds the ``Compiled`` itself.  A first
    call that raises before it has consumed its arguments (a layout the
    ``Compiled`` refuses, an executable the runtime cannot run) drops the
    entry and puts the traced step in the slot; one that has consumed them
    raises, as a jitted step's would."""

    def __init__(self, trainer: "Trainer", cache: Dict, slot: Any, key: str, compiled: Any, traced: Callable):
        self.trainer, self.cache, self.slot, self.key = trainer, cache, slot, key
        self.compiled, self.traced = compiled, traced

    def __call__(self, *args):
        try:
            out = self.compiled(*args)
        except Exception as e:  # noqa: BLE001 - whatever the first call raises, the traced step is the answer
            if not _state_alive(args[0]):
                raise
            self.trainer.programs.discard(self.key, f"its first call failed ({type(e).__name__}: {str(e)[:200]})")
            step = self.cache[self.slot] = self.traced(args)
            return step(*args)
        self.cache[self.slot] = self.compiled
        return out


def compiled_bytes(compiled) -> int:
    """What a compiled program occupies on a device, by the compiler's own
    account: arguments + outputs - aliased + temporaries."""
    ma = compiled.memory_analysis()
    return int(
        ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes
    )


class Trainer:
    """Builds and runs jitted train/eval steps for a ModelSpec over a mesh."""

    def __init__(
        self, spec: ModelSpec, config: JobConfig, mesh: Mesh,
        programs: Optional[program_store.ProgramStore] = None,
    ):
        self.spec = spec
        self.config = config
        self.mesh = mesh
        #: Where this trainer's compiled train steps are kept and looked for
        #: (common/program_store.py): the WORKER's trainer has one, and a
        #: relaunch restores its step instead of tracing it.  None, for
        #: everybody else: every step is traced (:meth:`_train_program`).
        self.programs = programs
        #: Train steps this trainer restored from the store, and those it
        #: traced (with or without one).
        self.programs_restored = 0
        self.programs_traced = 0
        # (key, compiled, host side) of the steps traced with a store,
        # written once the first dispatch is out (:meth:`_store_traced`).
        self._unstored: List[Tuple[str, Any, Dict[str, Any]]] = []
        self._adopt_mesh_axes(mesh)
        self.sharded_embeddings = (
            config.distribution_strategy == DistributionStrategy.PARAMETER_SERVER
            and bool(spec.embedding_tables)
        )
        self.ctx = self._make_ctx()
        self._state_specs = None
        self.keep_plan: Optional[KeepPlan] = None
        #: Wall seconds of the last ``init_state``.
        self.init_state_s = 0.0
        # ZeRO-style optimizer-state shard plan (opt_shard_plan) — set by
        # shard_state once the mode resolves against this mesh; None =
        # replicated layout.
        # Rebuilt only on the task loop (set_mesh / shard_state); the
        # preemption thread's snapshot_state reads the current refs.
        self._opt_plan = None  # single-writer: main
        self._snapshot_fn = None  # single-writer: main
        # Per-batch-structure step caches (see _structured); _train_step
        # keeps pointing at the most recently used build (profiling tools).
        self._train_steps: Dict = {}
        self._eval_steps: Dict = {}
        self._predict_steps: Dict = {}
        self._train_step = None
        self._eval_step = None
        self._predict_step = None
        # jitsan (v6) compile budgets: how many times each built step may
        # LOWER per compiled callable.  Per-step shapes are fixed by the
        # wrap-padding contract, so the per-step budgets are 1 — the
        # fixed-shape promise of docs/perf.md, now enforced at runtime.
        # The scan variants lower once per distinct task length T (full
        # tasks share one T; the job's remainder task adds a second), so
        # they carry headroom instead of a false alarm.  The serving
        # tier overrides predict_step to its padded-shape bucket count.
        # Written only at construction/serving-setup time; the step
        # builders read it.
        self.jit_budgets: Dict[str, int] = {
            "train_step": 1,
            # The tp-sharded train step of the 2D (dp, tp) mesh (r20):
            # a 2D reform re-lowers exactly once like any other reform,
            # and shape-preserving reforms add zero recompiles — same
            # fixed-shape promise, separate declaration so the 2D path's
            # budget is pinned by name (tests/test_mesh2d.py).
            "train_step_2d": 1,
            "train_scan": 4,
            "eval_step": 1,
            "eval_scan": 4,
            "predict_step": 1,
            "snapshot_state": 1,
        }
        # Host-tier tables (spec.host_io): rows live in the native C++ store
        # — in-process on this host (single-process meshes), or behind the
        # gRPC PS service tier when the job runs PS pods (config.ps_addresses
        # — ps/service.py).  The trainer pulls/injects per step and pushes
        # the sparse cotangents back (models/spec.HostTableIO).
        # Replaced wholesale on restore (task loop only); background
        # checkpoint threads read the dict reference atomically.
        self._host_stores: Dict[str, Any] = {}  # single-writer: main
        self._remote_ps = False
        if spec.host_io:
            if spec.batch_shard_dim != 0:
                # Single-process SP works for PER-TOKEN tables (ids [B, S]:
                # the injected rows legally shard with the sequence — the
                # HostTableIO.per_token declaration is the contract; a
                # [B, F]-shaped table would silently feature-slice).
                # Multi-process SP would additionally need per-PROCESS
                # slicing of the sharded dim, which _local_example_range
                # only does for the example dim.
                not_per_token = [
                    k for k, io in spec.host_io.items()
                    if not getattr(io, "per_token", False)
                ]
                if not_per_token:
                    raise NotImplementedError(
                        "host-tier tables under sequence parallelism must "
                        "declare per_token=True (ids [B, S]); table(s) "
                        f"{not_per_token} do not"
                    )
                if _process_count(mesh) > 1:
                    raise NotImplementedError(
                        "host-tier tables with sequence parallelism are "
                        "single-process only; multi-process meshes need "
                        "per-token process slicing"
                    )
            addrs = [
                a.strip()
                for a in getattr(config, "ps_addresses", "").split(",")
                if a.strip()
            ]
            if addrs:
                # Shared PS service fleet: the only legal host-tier layout
                # for multi-process meshes (a per-process store would train
                # divergent row copies), and async-PS semantics throughout.
                from elasticdl_tpu.ps.service import RemoteEmbeddingStore

                self._remote_ps = True
                self._host_stores = {
                    key: RemoteEmbeddingStore(key, io.dim, addrs)
                    for key, io in spec.host_io.items()
                }
            elif _process_count(mesh) > 1:
                raise NotImplementedError(
                    "host-tier embedding tables on a multi-process mesh need "
                    "the PS service tier: run with --num_ps_pods > 0 (or set "
                    "--ps_addresses to an external PS fleet)"
                )
            else:
                from elasticdl_tpu.ps.host_store import HostEmbeddingStore

                self._host_stores = {
                    key: HostEmbeddingStore(
                        dim=io.dim,
                        optimizer=io.optimizer,
                        learning_rate=io.learning_rate,
                        init_scale=io.init_scale,
                    )
                    for key, io in spec.host_io.items()
                }

    def _adopt_mesh_axes(self, mesh: Mesh) -> None:
        """Axis roles for 1-D and hierarchical meshes.

        Embedding tables (and the collective lookup / ring attention) always
        use the LAST axis.  Batch layout by model:

        - data-parallel models (batch_shard_dim=0): the example dim shards
          over EVERY axis jointly.
        - sequence-parallel models (batch_shard_dim=1): on a 1-D mesh the
          sequence dim shards over the single axis (examples replicated); on
          a hierarchical ``("dp", "ep")`` mesh examples shard over the outer
          dp axis and the sequence over the inner ICI axis — data
          parallelism across hosts (DCN sees only the grad psum) with the
          ring attention's ppermutes confined to ICI within a slice.

        The graftreduce topology (r15) re-resolves here too: the outer
        axis's (host, local) factorization is a property of THIS mesh, so
        every elastic reform re-derives it, and the subgroup mask resets
        to all-active (contributor count is mesh-shaped).

        Tensor-parallel models (spec.tensor_sharding, r20) on a 2D
        ``(dp, tp)`` mesh: ``tp_axis`` names the inner model axis and
        ``reduce_axes`` drops it — the tp axis carries ONLY the model's
        in-block activation all-reduces; loss/metric/gradient reductions
        run over ``dp`` alone (tp ranks hold the same examples, and the
        custom-VJP pair in collectives.py leaves replicated-param grads
        already complete per rank).  On a 1-D mesh the same model runs
        dense and ``reduce_axes == batch_axes`` as always — that IS the
        2D->1D re-partition target.
        """
        self.batch_axes = tuple(mesh.axis_names)
        self.axis_name = mesh.axis_names[-1]  # embedding/sequence axis
        from elasticdl_tpu.parallel.mesh import MODEL_AXIS

        self.tp_axis = (
            MODEL_AXIS
            if self.spec.tensor_sharding is not None
            and self.batch_axes[-1] == MODEL_AXIS
            else None
        )
        self.reduce_axes = tuple(
            a for a in self.batch_axes if a != self.tp_axis
        )
        self.collective = coll.resolve_topology(
            mesh,
            self.reduce_axes,
            mode=getattr(self.config, "collective", coll.AUTO),
            local_size=int(getattr(self.config, "collective_local_size", 0)),
            min_elems=int(
                getattr(self.config, "collective_min_elems", coll.DEFAULT_MIN_ELEMS)
            ),
        )
        # Subgroup-mask contributors are EXAMPLE shards, never sequence
        # slices or tensor-parallel ranks: a data-parallel model
        # (batch_shard_dim=0) shards examples over every REDUCE axis, so
        # each dp position is a contributor (a 2D mesh's tp ranks hold
        # pieces of the same weights and must never be excluded alone); a
        # sequence-parallel model shards examples over the OUTER axes
        # only — its inner-axis slices hold pieces of the SAME examples,
        # and excluding one slice of an example would train on a tensor
        # no dataset produced.  On a 1-D sequence-parallel mesh there is
        # no example sharding at all: one contributor, exclusion
        # unsupported (the worker's gate self-disables at n <= 1).
        self.contributor_axes = (
            self.reduce_axes
            if self.spec.batch_shard_dim == 0
            else self.batch_axes[:-1]
        )
        self._active_np = np.ones(
            coll.contributor_count(mesh, self.contributor_axes), np.float32
        )
        self._active_dev = None

    # ---- graftreduce subgroup participation (r15) ----

    def num_contributors(self) -> int:
        """Subgroup-mask slots: one per EXAMPLE shard of this mesh
        (row-major over ``contributor_axes``) — the worker's collective
        gate addresses exclusions by this index."""
        return int(self._active_np.size)

    def active_contributors(self) -> np.ndarray:
        """The current 0/1 participation mask (host copy)."""
        return np.array(self._active_np)

    def set_active_contributors(self, active=None) -> None:
        """Set the subgroup mask for subsequent train steps.  ``None``
        restores all-active.  The mask is a traced INPUT to the jitted
        step, so this never recompiles — the whole point of in-collective
        exclusion is that it costs data movement, not a recompile (pinned
        by test).  All-zero masks are rejected: a collective over an
        empty subgroup has no mean to renormalize."""
        n = self.num_contributors()
        if active is None:
            mask = np.ones(n, np.float32)
        else:
            mask = np.asarray(active, np.float32).reshape(-1)
            if mask.size != n:
                raise ValueError(
                    f"active mask has {mask.size} slots, mesh has {n} "
                    "contributors"
                )
            if not mask.any():
                raise ValueError("cannot exclude every contributor")
        if np.array_equal(mask, self._active_np):
            return
        self._active_np = mask
        self._active_dev = None

    def _active_device(self):
        """The mask as a replicated device array (built lazily, cached
        until the mask or mesh changes — the steady state costs one
        reference read per step)."""
        if self._active_dev is None:
            sh = NamedSharding(self.mesh, P())
            self._active_dev = jax.tree.leaves(
                self._place_global(self._active_np, sh)
            )[0]
        return self._active_dev

    def collective_bytes_per_step(self, state: TrainState) -> Dict[str, int]:
        """Analytic per-replica inter-host bytes of one step's dense-grad
        all-reduce under this mesh's resolved topology vs the flat route
        (collectives.interhost_bytes_per_step's model; the live
        ``edl_collective_interhost_bytes_total`` counter advances by
        ``resolved`` per step)."""
        table_paths = (
            {t.path for t in self.spec.embedding_tables}
            if self.sharded_embeddings
            else set()
        )
        tp = (
            int(self.mesh.shape[self.tp_axis])
            if self.tp_axis is not None
            else 1
        )
        dims = _tp_dim_leaves(
            state.params,
            self.spec.tensor_sharding(state.params)
            if self.tp_axis is not None and self.spec.tensor_sharding
            else None,
        )
        sizes = []
        for (path, leaf), d in zip(
            jax.tree_util.tree_flatten_with_path(state.params)[0], dims
        ):
            if _path_keys(path) in table_paths:
                continue
            elems = coll.leaf_elems(leaf)
            if d is not None:
                # Tensor-parallel leaf: each rank reduces only its LOCAL
                # shard's grad over dp — 1/tp of the leaf rides the wire.
                elems = -(-elems // tp)
            sizes.append(elems)
        # The grad reduce runs over the dp axes only (reduce_axes): on the
        # 2D mesh the (dp x tp) product never all-reduces as one axis —
        # that is the bytes the 2D layout exists to not move.
        n = coll.contributor_count(self.mesh, self.reduce_axes)
        return {
            "flat": coll.interhost_bytes_per_step(sizes, n, None),
            "resolved": coll.interhost_bytes_per_step(sizes, n, self.collective),
        }

    def _make_ctx(self) -> ParallelContext:
        # Resolve "auto" against the MESH's platform (not the default
        # backend): tests build CPU meshes in a process whose default backend
        # may be TPU, and the ragged-all-to-all HLO only exists on TPU.  The
        # mesh size matters too: a 1-device axis resolves to dense, whose n=1
        # path is a plain local gather (VERDICT r2 Weak #1 — ragged at n=1
        # paid the full routing machinery with zero peers).
        platform = self.mesh.devices.flat[0].platform
        # Tables shard over the LAST axis only; that is the size the
        # collective lookup sees (a hierarchical mesh's dp axis never
        # carries embedding traffic).
        axis_size = self.mesh.shape[self.axis_name]
        impl = resolve_impl(
            self.config.embedding_lookup_impl, platform, axis_size=axis_size
        )
        if self.sharded_embeddings:
            # "auto" silently means dense anywhere but multi-chip TPU; the
            # log names the route this mesh will actually trace.
            logger.info(
                "embedding lookup route: %s (requested %s; platform=%s, "
                "axis %s size %d)",
                impl, self.config.embedding_lookup_impl, platform,
                self.axis_name, axis_size,
            )
        return ParallelContext(
            axis_name=self.axis_name,
            sharded_embeddings=self.sharded_embeddings,
            embedding_impl=impl,
            tp_axis=self.tp_axis,
        )

    # ---- elastic re-formation ----

    def set_mesh(self, mesh: Mesh) -> None:
        """Adopt a re-formed mesh (elastic join/leave) and drop compiled
        steps/specs so the next call re-lowers for the new topology.  The
        caller must then re-place state with ``shard_state`` — typically
        after an Orbax restore on the new membership (see master.rendezvous).
        """
        self.mesh = mesh
        self._adopt_mesh_axes(mesh)
        self.ctx = self._make_ctx()
        self._state_specs = None
        self._opt_plan = None
        self._snapshot_fn = None
        self._train_steps = {}
        self._eval_steps = {}
        self._predict_steps = {}
        self._train_step = None
        self._eval_step = None
        self._predict_step = None

    # ---- state management ----

    def init_state(self, rng: jax.Array) -> TrainState:
        """Fresh params and optimizer state, born in this mesh's layout:
        ONE jitted program whose outputs carry the shardings
        ``shard_state`` would give them, so a device only ever computes
        and holds its own shard (a row-sharded table larger than one
        chip's memory initialises; nothing is built whole on device 0).
        jax's counter-based threefry makes the values independent of the
        number of devices."""
        # The worker's set-up chain stamps the same call as its
        # ``init_state`` span (metric ``setup_init_state_s``).
        t0 = time.monotonic()
        with trace.span("init_state"):
            state = jax.block_until_ready(self._init_program(rng)(rng))
        self.init_state_s = time.monotonic() - t0
        self._log_table_layout(state)
        return state

    def _init_program(self, rng: Any) -> Callable:
        """The jitted ``rng -> TrainState`` of :meth:`init_state`; adopts
        this mesh's layout for the model's shapes on the way (``rng`` may
        be a ShapeDtypeStruct: tests compile this for a described chip)."""

        def init_params(rng):
            return pad_embedding_tables(
                self.spec.init(rng), self.spec.embedding_tables
            )

        shardings = self._adopt_layout(jax.eval_shape(init_params, rng))
        plan = self._opt_plan

        def init(rng):
            params = init_params(rng)
            opt_state = self.spec.optimizer.init(params)
            if plan is not None:
                opt_state = self._opt_map(
                    lambda leaf, entry: _pad_flat(leaf, entry)
                    if isinstance(entry, _OptShard) else leaf,
                    opt_state, plan,
                )
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params, opt_state=opt_state,
            )

        return jit_compiled(
            init, name="trainer.init_state", out_shardings=shardings
        )

    def _log_table_layout(self, state: TrainState) -> None:
        """One line per mesh-sharded table: what a chip holds of it."""
        if not self.sharded_embeddings:
            return
        shards = int(self.mesh.shape[self.axis_name])
        for t in self.spec.embedding_tables:
            leaf = state.params
            for key in t.path:
                leaf = leaf[key]
            moments = sum(
                1 for m in jax.tree.leaves(state.opt_state)
                if m.shape == leaf.shape
            )
            gib = leaf.nbytes / shards / 2**30
            logger.info(
                "embedding table %s: %d logical rows of %d floats (padded "
                "to %d), row-sharded %d ways: %.3f GiB of rows a chip, "
                "%.3f GiB of optimizer moments a chip",
                "/".join(t.path), t.vocab_size, t.dim,
                logical_rows(leaf, t.dim), shards, gib, moments * gib,
            )

    def state_specs(self) -> TrainState:
        if self._state_specs is None:
            raise RuntimeError("call init_state/shard_state first")
        return self._state_specs

    # ---- optimizer-state sharding (ZeRO over the data-parallel axis) ----

    def _opt_shard_axis(self) -> str:
        """The axis optimizer state shards over: the OUTER (data-parallel)
        mesh axis — ``dp`` on both the flat 1-D mesh and the hierarchical
        ``(dp, ep)`` mesh."""
        return self.batch_axes[0]

    def _opt_shard_count(self) -> int:
        return int(self.mesh.shape[self._opt_shard_axis()])

    def _opt_map(self, fn, opt_state: Any, *rest: Any) -> Any:
        """Map ``fn(opt_leaf, *rest_leaves)`` over the PARAM-SHAPED leaves
        of an optax state (momenta etc.), passing non-param leaves (step
        counts) through untouched.  ``rest`` trees are params-structured."""
        return optax.tree_map_params(
            self.spec.optimizer,
            fn,
            opt_state,
            *rest,
            transform_non_params=lambda x: x,
        )

    def _resolve_opt_sharding(self, params: Any, plan: Any) -> bool:
        """Whether THIS mesh runs the sharded optimizer: the config knob,
        re-resolved per mesh adoption (an elastic resize can change the
        answer in ``auto`` mode — the canonical host layout bridges)."""
        mode = getattr(self.config, "optimizer_sharding", "replicated")
        if mode not in ("sharded", "auto") or self._opt_shard_count() <= 1:
            return False
        if mode == "sharded":
            return True
        shapes = jax.eval_shape(self.spec.optimizer.init, params)
        sizes = self._opt_map(
            lambda leaf, entry: (
                int(leaf.size) * leaf.dtype.itemsize
                if isinstance(entry, _OptShard)
                else 0
            ),
            shapes,
            plan,
        )
        per_replica = sum(
            s for s in jax.tree.leaves(sizes) if isinstance(s, int)
        )
        threshold = float(
            getattr(self.config, "optimizer_sharding_auto_mb", 64.0)
        ) * (1 << 20)
        return per_replica >= threshold

    def _opt_canonical(self, opt_state: Any, params: Any) -> Any:
        """Bring every param-shaped optimizer leaf to the CANONICAL
        (param-shaped) layout, from EITHER layout.  A flat leaf is always
        ``[data, zero-pad]`` regardless of which shard count padded it, so
        ``reshape(-1)[:size]`` recovers the data bit-for-bit — this is
        what lets a 4->8->4 resize redistribute existing moments instead
        of re-initializing them, and what makes checkpoints topology- and
        mode-agnostic."""

        def canon(leaf, p):
            shape = tuple(np.shape(p))
            if tuple(np.shape(leaf)) == shape:
                return leaf
            size = int(np.prod(shape)) if shape else 1
            return np.reshape(np.reshape(np.asarray(leaf), -1)[:size], shape)

        return self._opt_map(canon, opt_state, params)

    def _opt_flat_host(self, opt_state: Any, plan: Any) -> Any:
        """Canonical -> flat-padded host layout per the plan (pure numpy
        data movement; zero-pad mirrors ``pad_embedding_tables``)."""

        def flat(leaf, entry):
            if not isinstance(entry, _OptShard):
                return leaf
            v = np.reshape(np.asarray(leaf), -1)
            if entry.padded != entry.size:
                v = np.concatenate(
                    [v, np.zeros((entry.padded - entry.size,), v.dtype)]
                )
            return v

        return self._opt_map(flat, opt_state, plan)

    def host_state(self, state: TrainState) -> TrainState:
        """Device -> host state in the CANONICAL layout (param-shaped
        optimizer leaves) regardless of the live device layout.  This is
        the ONE representation checkpoints store and elastic reforms
        bridge through: save it anywhere, restore it into any world size,
        either optimizer_sharding mode."""
        state = jax.device_get(state)
        return state.replace(
            opt_state=self._opt_canonical(state.opt_state, state.params)
        )

    def opt_state_bytes_per_device(self, state: TrainState) -> Dict[str, int]:
        """Per-device resident optimizer-state bytes of a PLACED state —
        the number the sharded mode exists to cut (replicated leaves count
        their full copy on every device).  Keys are device ids as strings;
        bench/tests assert on ``max``."""
        per: Dict[str, int] = {}
        for leaf in jax.tree.leaves(state.opt_state):
            if not hasattr(leaf, "addressable_shards"):
                continue
            for shard in leaf.addressable_shards:
                key = str(shard.device.id)
                per[key] = per.get(key, 0) + int(shard.data.nbytes)
        return per

    def _adopt_layout(self, params: Any) -> TrainState:
        """Resolve this mesh's layout for ``params`` (arrays or shapes):
        sets the optimizer shard plan and ``state_specs`` and returns the
        matching tree of shardings."""
        tp_dims = (
            self.spec.tensor_sharding(params)
            if self.tp_axis is not None and self.spec.tensor_sharding
            else None
        )
        p_specs = params_partition_specs(
            params,
            self.spec.embedding_tables,
            self.axis_name,
            self.sharded_embeddings,
            tp_dims=tp_dims,
            tp_axis=self.tp_axis,
        )
        plan = opt_shard_plan(
            params,
            self.spec.embedding_tables,
            self.sharded_embeddings,
            self._opt_shard_count(),
            tp_dims=tp_dims,
        )
        self._opt_plan = (
            plan if self._resolve_opt_sharding(params, plan) else None
        )
        o_specs = opt_state_partition_specs(
            self.spec.optimizer,
            params,
            p_specs,
            shard_plan=self._opt_plan,
            shard_axis=self._opt_shard_axis(),
        )
        self._state_specs = TrainState(step=P(), params=p_specs, opt_state=o_specs)
        return jax.tree.map(
            lambda s: NamedSharding(self.mesh, s), self._state_specs
        )

    def shard_state(self, state: TrainState) -> TrainState:
        """Place (or re-place, after a mesh re-formation) state on the mesh.

        Accepts optimizer state in EITHER layout (canonical param-shaped,
        or the flat dp-sharded layout of any PREVIOUS mesh): leaves are
        first canonicalized, then laid out for THIS mesh per the resolved
        optimizer_sharding mode — so an elastic 4->8->4 resize
        REDISTRIBUTES existing Adam/Adagrad moments instead of rebuilding
        them."""
        shardings = self._adopt_layout(state.params)
        opt_state = self._opt_canonical(state.opt_state, state.params)
        if self._opt_plan is not None:
            opt_state = self._opt_flat_host(opt_state, self._opt_plan)
        state = state.replace(opt_state=opt_state)
        procs = {d.process_index for d in self.mesh.devices.flat}
        if len(procs) <= 1:
            return jax.device_put(state, shardings)
        # Multi-process mesh: device_put cannot target non-addressable
        # devices.  Every process holds the same host-side state (init is
        # deterministic from the shared seed; restores read the same
        # checkpoint), so each fills in its own addressable shards.

        def place(x, sh):
            arr = np.asarray(jax.device_get(x))
            return jax.make_array_from_callback(arr.shape, sh, lambda i: arr[i])

        return jax.tree.map(place, state, shardings)

    def restore_template(self, state: TrainState) -> TrainState:
        """``state_like`` for CheckpointManager.restore.  Checkpoints store
        the CANONICAL optimizer layout (host_state), so in sharded mode
        the live flat leaves are swapped for param-shaped REPLICATED
        targets; replicated mode passes the live state straight through
        (restore lands directly in the mesh shardings, as before)."""
        if self._opt_plan is None:
            return state

        def target(leaf, entry):
            if not isinstance(entry, _OptShard):
                return leaf
            return jax.ShapeDtypeStruct(
                entry.shape,
                leaf.dtype,
                sharding=NamedSharding(self.mesh, P()),
            )

        return state.replace(
            opt_state=self._opt_map(target, state.opt_state, self._opt_plan)
        )

    def adopt_restored(self, state: TrainState) -> TrainState:
        """Lay a just-restored checkpoint back into the live layout: a
        no-op in replicated mode; in sharded mode each canonical
        (replicated) optimizer leaf is flattened, padded and placed over
        the shard axis — every process placing only its own addressable
        shards, so this works in multi-process worlds too."""
        if self._opt_plan is None:
            return state
        sh = NamedSharding(self.mesh, P(self._opt_shard_axis()))
        single = _process_count(self.mesh) <= 1
        # ONE definition of the canonical->flat padding rule (_opt_flat_host;
        # np.asarray only touches addressable replicas — the restore target
        # is replicated); this method adds just the placement.
        flat = self._opt_flat_host(state.opt_state, self._opt_plan)

        def place(leaf, entry):
            if not isinstance(entry, _OptShard):
                return leaf
            v = np.asarray(leaf)
            if single:
                return jax.device_put(v, sh)
            return jax.make_array_from_callback(v.shape, sh, lambda i, v=v: v[i])

        return state.replace(
            opt_state=self._opt_map(place, flat, self._opt_plan)
        )

    # hot-path: dispatch-only by design — ONE jitted device-side copy per
    # checkpoint boundary, no transfers or collectives on the caller
    # jit-boundary: returns device buffers fresh off the compiled copy
    def snapshot_state(self, state: TrainState) -> TrainState:
        """ONE jitted device-side copy of the live state in the CANONICAL
        layout: fresh buffers no later step can donate (copying the live
        state on the host would race donation), optimizer leaves
        param-shaped so even group-mode collective Orbax saves — which
        stream device arrays straight to disk — write the topology-
        agnostic checkpoint format.  Dispatch-only: the caller pays a
        dispatch RTT, never a drain."""
        if self._snapshot_fn is None:
            plan = self._opt_plan

            def snap(s):
                s = jax.tree.map(jnp.copy, s)
                if plan is None:
                    return s

                def canon(leaf, entry):
                    if not isinstance(entry, _OptShard):
                        return leaf
                    return jnp.reshape(
                        jnp.reshape(leaf, (-1,))[: entry.size], entry.shape
                    )

                return s.replace(
                    opt_state=self._opt_map(canon, s.opt_state, plan)
                )

            # graftlint: allow[shared-state] idempotent jit memo: a racing rebuild costs one duplicate compile of the same function, and either reference is valid
            self._snapshot_fn = jit_compiled(
                snap, name="trainer.snapshot_state",
                expected_variants=self.jit_budgets["snapshot_state"],
            )
        return self._snapshot_fn(state)

    def _batch_spec_for(self, leaf) -> P:
        """PartitionSpec for one batch leaf.

        Data-parallel models (batch_shard_dim=0): the example dim shards
        over every REDUCE axis jointly — each device holds B/total
        examples; on a tensor-parallel 2D mesh that means over ``dp``
        only, REPLICATED along ``tp`` (every tp rank of a dp row works
        the same examples through its weight shard).

        Sequence-parallel models (batch_shard_dim=1): the sequence dim
        shards over the inner axis; on hierarchical meshes the example dim
        additionally shards over the outer (dp) axes (sp_batch_spec).
        Leaves WITHOUT a sequence dim (per-example masks) replicate on a
        1-D mesh but must follow the example-dim sharding on hierarchical
        meshes — a replicated [B] mask against dp-sharded [B/dp, S/ep]
        tokens would weight the wrong examples."""
        d = self.spec.batch_shard_dim
        if d == 0:
            return P(self.reduce_axes)
        if getattr(leaf, "ndim", 0) > d:
            return batch_leaf_spec(self.batch_axes, d)
        outer = self.batch_axes[:-1]
        if outer and getattr(leaf, "ndim", 0) >= 1:
            return P(outer)
        return P()

    def batch_specs(self, batch: Any):
        return jax.tree.map(self._batch_spec_for, batch)

    def shard_batch(self, batch: Any) -> Any:
        """Place a GLOBAL batch on the mesh, sharded on the model's
        ``batch_shard_dim`` (examples for DP, sequence for SP).

        Single-process meshes device_put directly.  Multi-process meshes
        (jax.distributed worlds) cannot device_put onto non-addressable
        devices; every process feeds the same deterministic global batch and
        contributes its own slice via
        ``jax.make_array_from_process_local_data`` (SURVEY.md §3.5).
        """
        for leaf in jax.tree.leaves(batch):
            spec = self._batch_spec_for(leaf)
            for dim, part in enumerate(spec):
                if part is None:
                    continue
                names = part if isinstance(part, tuple) else (part,)
                k = 1
                for nm in names:
                    k *= self.mesh.shape[nm]
                if leaf.shape[dim] % k != 0:
                    raise ValueError(
                        f"batch dimension {dim} of size {leaf.shape[dim]} "
                        f"not divisible by its mesh axes {names} (size {k})"
                    )
        shardings = jax.tree.map(
            lambda x: NamedSharding(self.mesh, self._batch_spec_for(x)), batch
        )
        return self._place_global(batch, shardings)

    def _place_global(self, batch: Any, shardings: Any) -> Any:
        """Place GLOBAL host data under per-leaf shardings: device_put on
        single-process meshes; on multi-process meshes every process holds
        the same global data and contributes its own slice via
        ``jax.make_array_from_process_local_data``."""
        procs = {d_.process_index for d_ in self.mesh.devices.flat}
        if len(procs) <= 1:
            return jax.device_put(batch, shardings)

        def to_global(x, sh):
            x = np.asarray(x)
            spec_dims = [i for i, s in enumerate(sh.spec) if s is not None]
            if not spec_dims:  # replicated leaf: full copy from each process
                return jax.make_array_from_process_local_data(sh, x, x.shape)
            dd = spec_dims[0]
            # This process's contiguous slice range along the sharded dim:
            # the union of its addressable devices' index slices.
            idx_map = sh.addressable_devices_indices_map(x.shape)
            starts = [s[dd].start or 0 for s in idx_map.values()]
            stops = [
                x.shape[dd] if s[dd].stop is None else s[dd].stop
                for s in idx_map.values()
            ]
            take = [slice(None)] * x.ndim
            take[dd] = slice(min(starts), max(stops))
            return jax.make_array_from_process_local_data(
                sh, x[tuple(take)], x.shape
            )

        return jax.tree.map(to_global, batch, shardings)

    # ---- host-tier pull/push (spec.host_io) ----

    def _is_multiprocess(self) -> bool:
        return _process_count(self.mesh) > 1

    def _local_example_range(self, n_examples: int) -> Tuple[int, int]:
        """This process's contiguous [lo, hi) slice of the batch dimension
        under the data-parallel sharding (union of its addressable devices'
        index slices)."""
        sh = NamedSharding(self.mesh, P(self.reduce_axes))
        idx_map = sh.addressable_devices_indices_map((n_examples,))
        starts = [s[0].start or 0 for s in idx_map.values()]
        stops = [
            n_examples if s[0].stop is None else s[0].stop
            for s in idx_map.values()
        ]
        return min(starts), max(stops)

    def _inject_host_rows(self, batch: Any) -> Tuple[Any, Dict[str, Any]]:
        ids = {k: io.ids_fn(batch) for k, io in self.spec.host_io.items()}
        injected = dict(batch)
        multi = self._is_multiprocess()
        for key, table_ids in ids.items():
            if multi:
                # Pull only this process's example slice from the PS fleet;
                # shard_batch's make_array_from_process_local_data reads
                # exactly that slice of the global-shaped buffer, so the
                # zero rows elsewhere are never consumed.
                table_ids = np.asarray(table_ids)
                lo, hi = self._local_example_range(table_ids.shape[0])
                local = self._host_stores[key].pull(table_ids[lo:hi])
                buf = np.zeros(
                    (table_ids.shape[0],) + local.shape[1:], np.float32
                )
                buf[lo:hi] = local
                injected[key] = buf
            else:
                injected[key] = self._host_stores[key].pull(table_ids)
        return injected, ids

    def _push_host_grads(self, ids: Dict[str, Any], host_grads: Dict[str, Any]):
        """Push the step's sparse cotangents into the host-tier stores.

        Materializing ``host_grads`` (np.asarray) BLOCKS on the step that
        produced them — this is the synchronization point the async driver
        (run_train_steps) moves past the next batch's pull.
        """
        multi = self._is_multiprocess()
        for key, grads in host_grads.items():
            # The store applies its server-side optimizer per distinct id,
            # duplicates pre-accumulated (the reference PS's IndexedSlices
            # apply, in C++ — ps/native/edl_native.cc).  Multi-process
            # worlds: each process pushes its OWN example slice (the only
            # shards it can address); duplicates are pre-accumulated within
            # a process's push but land as separate optimizer applies when
            # the same id appears on two processes — the reference's
            # per-worker async push has exactly these semantics.
            if multi:
                id_arr = np.asarray(ids[key])
                part_ids = []
                part_grads = []
                for shard in grads.addressable_shards:
                    part_ids.append(id_arr[shard.index[0]])
                    part_grads.append(np.asarray(shard.data))
                self._host_stores[key].push_grad(
                    np.concatenate(part_ids), np.concatenate(part_grads)
                )
            else:
                self._host_stores[key].push_grad(ids[key], np.asarray(grads))

    # jit-boundary: state/metrics come back undisposed off the jitted step
    def run_train_step(self, state: TrainState, batch: Any):
        """Full training step from a HOST batch: host-tier pull -> shard ->
        jitted step -> sparse cotangent push.  Without host tables this is
        just shard+step."""
        if not self.spec.host_io:
            return self.train_step(state, self.shard_batch(batch))
        injected, ids = self._inject_host_rows(batch)
        state, metrics, host_grads = self.train_step(
            state, self.shard_batch(injected)
        )
        self._push_host_grads(ids, host_grads)
        return state, metrics

    # jit-boundary: state/metrics come back undisposed off the jitted step
    def run_train_steps(
        self,
        state: TrainState,
        batches,
        use_async: bool = False,
        pre_sharded: bool = False,
    ):
        """Train over an iterable of HOST batches.

        ``pre_sharded=True``: the batches are ALREADY device-placed (the
        worker's prefetch thread ran ``shard_batch``, overlapping the H2D
        transfer with the in-flight device step).  Only legal
        without host-tier tables: host injection needs the host batch.

        ``use_async=False``: the synchronous loop — each batch's pull sees
        every prior push (sync-by-version PS semantics).

        ``use_async=True`` (host-tier tables only): the reference's async-PS
        mode (SURVEY §2 #9 "async or sync-by-version") as a software
        pipeline — batch ``n+1``'s row pull (host RPC) is issued BEFORE
        blocking on batch ``n``'s cotangents, overlapping the pull with the
        device step still in flight.  The pull therefore reads rows that are
        one un-applied push stale, exactly the bounded-staleness contract of
        an async parameter server; dense params stay exact (they live in the
        jitted step).  With a single batch the pipeline degenerates to the
        synchronous order, so short tasks are bit-identical to sync.

        Returns (state, [metrics per batch]).
        """
        if pre_sharded and self.spec.host_io:
            raise ValueError(
                "pre_sharded batches are incompatible with host-tier "
                "tables (the host pull needs the host batch)"
            )
        metrics_out = []
        last_good: Optional[TrainState] = None  # newest verified-alive state
        try:
            if pre_sharded or not self.spec.host_io or not use_async:
                step = self.train_step if pre_sharded else self.run_train_step
                for batch in batches:
                    state, metrics = step(state, batch)
                    metrics_out.append(metrics)
                    last_good = state
                return state, metrics_out
            # Staleness bound D = config.async_staleness: up to D steps'
            # pushes may be outstanding when a pull happens, letting D
            # host-tier RPC round-trips hide behind device steps (depth 1 =
            # the reference's classic async-PS window, and the default:
            # deeper bounds are not measured on current code).
            from collections import deque

            depth = self.config.async_staleness
            pending: deque = deque()  # (ids, host_grads) of in-flight steps
            for batch in batches:
                injected, ids = self._inject_host_rows(batch)
                while len(pending) >= depth:
                    self._push_host_grads(*pending.popleft())
                state, metrics, host_grads = self.train_step(
                    state, self.shard_batch(injected)
                )
                pending.append((ids, host_grads))
                metrics_out.append(metrics)
                last_good = state
            while pending:
                self._push_host_grads(*pending.popleft())
            return state, metrics_out
        except Exception as e:
            # The failed call may have consumed (donated) its input state;
            # surface the newest state that still backs live buffers so the
            # caller can continue instead of wedging on deleted arrays.
            raise TrainLoopError(
                last_good if _state_alive(last_good) else None, e
            ) from e

    # jit-boundary: metrics come back undisposed off the jitted step
    def run_eval_step(self, state: TrainState, batch: Any):
        if self.spec.host_io:
            batch, _ = self._inject_host_rows(batch)
        return self.eval_step(state, self.shard_batch(batch))

    # jit-boundary: outputs come back undisposed off the jitted step
    def run_predict_step(self, state: TrainState, batch: Any):
        if self.spec.host_io:
            batch, _ = self._inject_host_rows(batch)
        return self.predict_step(state, self.shard_batch(batch))

    def save_host_stores(self, directory: str, step: int, keep_max: int = 3) -> None:
        """Snapshot host-tier stores alongside the Orbax checkpoint, pruning
        old step snapshots like Orbax's own retention does (host tables are
        the multi-GB case — unbounded snapshots would exhaust the volume)."""
        if not self._host_stores:
            return
        if self._remote_ps:
            # PS fleet: each shard dumps its own slice atomically and prunes
            # its own old files (ps/service.PSServer._save) — the worker only
            # fans the request out.  ONE fan-out total: a Save request makes
            # a shard snapshot EVERY table it serves, so looping over stores
            # (all views of the same fleet) would rewrite identical files
            # len(host_io) times per checkpoint.  Callers rank-gate this in
            # multi-process worlds (worker._maybe_checkpoint) so shards save
            # once per step.
            next(iter(self._host_stores.values())).save_snapshot(
                directory, step, keep_max=keep_max
            )
            return
        root = os.path.join(directory, "host_stores")
        d = os.path.join(root, str(step))
        os.makedirs(d, exist_ok=True)
        from elasticdl_tpu.common import durable

        for key, store in self._host_stores.items():
            # Atomic per-file commit: a crash mid-write must leave either no
            # snapshot (restore falls back to an older step) or a complete
            # one — never a truncated file that poisons every relaunch.
            final = os.path.join(d, f"{key}.bin")
            tmp = durable.tmp_path(final)
            store.save(tmp)
            durable.atomic_replace(tmp, final)
        steps = sorted(
            (int(s) for s in os.listdir(root) if s.isdigit()), reverse=True
        )
        for old in steps[max(keep_max, 1):]:
            import shutil

            shutil.rmtree(os.path.join(root, str(old)), ignore_errors=True)

    def restore_host_stores(
        self, directory: str, step: int, strict: bool = True
    ) -> bool:
        """Load host-tier snapshots for ``step``.  ``strict`` (default)
        raises FileNotFoundError when the spec has host tables but the
        snapshot is missing — silently continuing would pair restored dense
        params with freshly re-initialized embeddings (a torn checkpoint)."""
        if not self._host_stores:
            return False
        if self._remote_ps:
            # Async-PS semantics: the PS fleet outlives worker restarts, so
            # an elastic re-join does NOT roll the host tier back to the
            # checkpoint step (pushed gradients are never un-applied — the
            # reference PS behaves identically).  PS pods restore their own
            # slices from the newest complete snapshot when THEY (re)start
            # (ps/main.py).  The worker still VERIFIES fleet consistency
            # here: shards restore independently, so a crash can leave them
            # on different steps — and an evaluation/prediction job whose
            # fleet restored nothing would silently score freshly
            # initialized rows.  Fail those loud; training re-joins
            # error-log and continue (bounded-staleness tolerance).
            steps = next(iter(self._host_stores.values())).restored_steps()
            distinct = set(steps)
            scoring = self.config.job_type in ("evaluation", "prediction")
            if distinct == {None}:
                # Whole fleet fresh: fine mid-training (rows accumulated
                # since job start live only in memory until the first
                # snapshot), fatal when scoring a trained model.
                if scoring:
                    raise RuntimeError(
                        f"{self.config.job_type} job: no PS shard restored "
                        "any snapshot — refusing to score freshly "
                        "initialized embedding rows"
                    )
                return True
            if len(distinct) > 1:
                msg = (
                    f"PS shards restored divergent steps {steps} — the "
                    "fleet mixes model versions"
                )
                if scoring:
                    raise RuntimeError(msg)
                logger.error("%s; continuing (async-PS training tolerance)", msg)
            return True
        paths = {
            key: os.path.join(directory, "host_stores", str(step), f"{key}.bin")
            for key in self._host_stores
        }
        missing = [p for p in paths.values() if not os.path.exists(p)]
        if missing:
            # Validate BEFORE mutating any store: a partial load would pair
            # some tables' checkpoint rows with others' live/fresh rows.
            if strict:
                raise FileNotFoundError(
                    f"host store snapshot missing for step {step}: "
                    f"{missing[0]} (torn checkpoint — dense state and host "
                    "rows must restore together)"
                )
            # non-strict: load whatever exists (in-process resize keeps live
            # rows for the rest)
            loaded = False
            for key, path in paths.items():
                if os.path.exists(path):
                    self._host_stores[key].load(path)
                    loaded = True
            return loaded
        try:
            for key, path in paths.items():
                self._host_stores[key].load(path)
        except (IOError, ValueError) as e:
            # A corrupt file detected mid-load leaves earlier stores mutated;
            # re-initialize them all so a fallback to an older step (or a
            # fresh start) never mixes rows from a torn step.
            from elasticdl_tpu.ps.host_store import HostEmbeddingStore

            self._host_stores = {
                key: HostEmbeddingStore(
                    dim=io.dim,
                    optimizer=io.optimizer,
                    learning_rate=io.learning_rate,
                    init_scale=io.init_scale,
                )
                for key, io in self.spec.host_io.items()
            }
            raise FileNotFoundError(
                f"host store snapshot for step {step} is unreadable ({e}); "
                "stores re-initialized"
            ) from e
        return True

    def wrap_host_stores(self, wrap) -> None:
        """Layer a decorator over every host-tier store — the serving tier
        interposes its hot-id LRU cache this way (serving/embedding_cache).
        ``wrap(key, store)`` must return a pull-compatible object (same
        ``pull``/``dim`` surface); training paths additionally need
        ``push_grad``/``save``/``load`` if they run through the wrapper."""
        self._host_stores = {
            key: wrap(key, store) for key, store in self._host_stores.items()
        }

    # ---- step builders ----

    # Built steps cache by the BATCH TREE STRUCTURE, not just lazily once:
    # shard_map in_specs are a structural prefix of the batch, and batches
    # of one job legitimately differ in structure (a wrap-padded tail adds
    # ``__mask__``).  A single cached step built from the first batch then
    # blows up on the tail's pytree (found by test_partial_tail_batch).
    # jit still handles shape/dtype retraces within a structure.

    def _structured(self, cache: Dict, build, batch: Any, fit_args=None, **kwargs):
        key = self._slot(batch, fit_args)
        fn = cache.get(key)
        if fn is None:
            make = functools.partial(
                build,
                self.spec,
                self.mesh,
                self.ctx,
                self.state_specs(),
                batch_specs=self.batch_specs(batch),
                batch_axes=self.batch_axes,
                **kwargs,
            )
            fn = cache[key] = make() if fit_args is None else self._train_program(cache, key, make, fit_args)
        return fn

    def _slot(self, batch: Any, fit_args) -> Any:
        """A step cache's key for ``batch``: its tree structure, and for a
        train step of a trainer with a program store its leaves' shapes too
        (a restored program serves ONE set of shapes, where a jitted step
        retraces: each set gets a slot, and a store entry, of its own)."""
        key = jax.tree.structure(batch)
        if self.programs is not None and fit_args is not None:
            key = (key, tuple((leaf.shape, str(leaf.dtype)) for leaf in jax.tree.leaves(batch)))
        return key

    def _train_build_kwargs(self) -> Dict[str, Any]:
        """The build_train_step kwargs shared by the per-step and scan
        variants: the optimizer shard plan for this mesh and the donation
        knob — one definition so the two step shapes cannot drift."""
        return dict(
            opt_shard=self._opt_plan,
            opt_shard_axis=self._opt_shard_axis(),
            donate=bool(getattr(self.config, "donate_train_state", True)),
            collective=self.collective,
        )

    def _new_keep_plan(self, over: int = 0) -> Optional[KeepPlan]:
        """The byte budget of a model that rematerialises its blocks, from
        the memory of this mesh's devices; None (nothing kept, the step as
        it always was) for every other model and wherever the backend
        reports no memory (off the TPU).  ``over``: what an earlier compile
        of the step read over the line."""
        if not self.spec.rematerialises:
            return None
        limit = device_bytes_limit(self.mesh.devices.flat)
        if limit is None:
            return None
        line = limit - REMAT_HEADROOM
        #: the newest plan, filled when its step is traced (logs and tests read it)
        self.keep_plan = KeepPlan(line=line, aim=line - REMAT_REFIT_MARGIN - over)
        return self.keep_plan

    def _held_to_the_line(self, make: Callable, args: Tuple, compile_anyway: bool = False) -> Tuple[Callable, Any]:
        """The train step ``make(keep_plan=...)`` builds, for its first
        call's ``args``, and the ``Compiled`` of its last compile here (None
        where there was none).  Where the model is given a :class:`KeepPlan`
        the step is compiled here and held to the plan's line: while the
        compiler's own account of it reads over the line and something is
        kept, it is made again with the overshoot and a margin off the aim.
        The last compile is the one the first call would have made: the
        call finds it.  ``compile_anyway``: also a step without a plan is
        compiled here, for the program store to keep."""
        plan, compiles, gib = self._new_keep_plan(), 0, 2.0**30
        step = make(keep_plan=plan)
        compiled = step.lower(*args).compile() if compile_anyway and plan is None else None
        while plan is not None:
            compiled = step.lower(*args).compile()
            total = compiled_bytes(compiled)
            compiles += 1
            logger.info(
                "rematerialised blocks keep %.3f of %.3f GiB tagged (budget %.3f): the step compiled to "
                "%.3f GiB against a line of %.3f (estimated with nothing kept: %.3f); compile %d",
                plan.kept / gib, plan.tagged / gib, plan.budget / gib, total / gib, plan.line / gib,
                plan.estimate / gib, compiles,
            )
            if total <= plan.line or not plan.kept:
                break
            plan = self._new_keep_plan(over=total - plan.aim)
            step = make(keep_plan=plan)
        return step, compiled

    def _train_program(self, cache: Dict, slot: Any, make: Callable, args: Tuple) -> Callable:
        """The train step for ``args``, in the cache slot it is for.  A
        trainer WITHOUT a program store traces it
        (:meth:`_held_to_the_line`), and so does one whose spec does not
        say what it was loaded with.  With a store: the stored program of
        this key if there is one, called as the jitted step is (same
        arguments, same donation, same outputs), with what its trace left
        on the host (the keep plan, the lines it logged) put back; else the
        traced step, whose compiled program is written to the store once
        the first dispatch is out (:meth:`_store_traced`)."""
        store, t0 = self.programs, time.monotonic()
        key = None if store is None else self._program_key(make, args)
        if key is None:
            self.programs_traced += 1
            return self._held_to_the_line(make, args)[0]
        found = store.restore(key, list(self.mesh.devices.flat))
        store.spent(time.monotonic() - t0)
        if found is not None:
            compiled, host = found
            self.programs_restored += 1
            if host.get("keep_plan") is not None:
                self.keep_plan = KeepPlan(**host["keep_plan"])
            log_utils.replay(host.get("said", ()))
            return _RestoredStep(self, cache, slot, key, compiled, functools.partial(self._traced_for_the_store, key, make))
        return self._traced_for_the_store(key, make, args)

    def _traced_for_the_store(self, key: str, make: Callable, args: Tuple) -> Callable:
        """Today's path, with what the store will keep of it noted: the
        step is compiled here whatever its model (the first call finds that
        compile, as a rematerialising model's always did)."""
        self.programs_traced += 1
        hits = compile_phase_seconds()["cache_hits"]
        with log_utils.capture() as said:
            step, compiled = self._held_to_the_line(make, args, compile_anyway=True)
        if compile_phase_seconds()["cache_hits"] > hits and self.mesh.devices.flat[0].platform == "cpu":
            # XLA:CPU serializes an executable it LOADED (a compile the
            # persistent cache served) without its object code: restored in
            # another process it fails inside its first execution, after
            # the state is donated (jax 0.9.0).  Only what this process
            # compiled is kept there.
            logger.info("the compile cache served the train step's compile: on the CPU it is not kept in the program store")
            return step
        plan = self.keep_plan if self.spec.rematerialises else None
        host = {"keep_plan": None if plan is None else dataclasses.asdict(plan), "said": list(said)}
        self._unstored.append((key, compiled, host))
        return step

    def _store_traced(self) -> None:
        """After a dispatch: hand the programs traced since the last one to
        the store's writer thread (nothing here waits for the disk)."""
        unstored, self._unstored = self._unstored, []
        for key, compiled, host in unstored:
            self.programs.save_later(key, compiled, host)

    def _program_key(self, make: Callable, args: Tuple) -> Optional[str]:
        """The program store's key of the train step ``make`` builds for
        ``args``: a digest of everything that step's program depends on,
        from what can be read BEFORE anything is traced.  When in doubt a
        thing is in: a stale hit is a silently wrong program, a needless
        miss costs one trace.  None where the spec does not say what it was
        loaded with (``ModelSpec.loaded_with``): no key, no store."""
        spec = self.spec
        if spec.loaded_with is None:
            return None
        zoo, model_def, params = spec.loaded_with
        module = inspect.getmodule(spec.apply)
        devices = list(self.mesh.devices.flat)
        client = devices[0].client

        def described(tree):
            return [str(jax.tree.structure(tree))] + [
                (tuple(leaf.shape), str(leaf.dtype), str(getattr(leaf, "sharding", None)))
                for leaf in jax.tree.leaves(tree)
            ]

        return program_store.key_of({
            # the code: the package, and the model's module where a zoo outside it holds it
            "source": program_store.source_digest(also=(getattr(module, "__file__", "") or "",)),
            "environment": program_store.environment(client),
            # the devices and their layout
            "devices": [(d.id, d.process_index, d.device_kind) for d in devices],
            "process": (jax.process_index(), jax.process_count()),
            "mesh": (tuple(self.mesh.axis_names), tuple(self.mesh.devices.shape)),
            "bytes_limit": device_bytes_limit(devices),
            "keep_line": (REMAT_HEADROOM, REMAT_REFIT_MARGIN),
            # the arguments: trees, shapes, dtypes, shardings
            "arguments": described(args),
            # the model
            "model": (zoo, model_def, _plain(params)),
            # what the spec declares beside its functions (the optimizer is made from the parameters)
            "spec": {
                f.name: _plain(getattr(spec, f.name))
                for f in dataclasses.fields(spec)
                if f.name not in ("optimizer", "loaded_with", "feed", "example_batch")
            },
            # the job
            "config": {
                f.name: getattr(self.config, f.name)
                for f in dataclasses.fields(self.config)
                if f.name not in _NOT_IN_A_KEY
            },
            # the trainer's own choices for this mesh, and the step's variant (make's keywords:
            # host keys, the optimizer's shard plan, donation, the collective's topology, scan or step)
            "context": (_plain(self.ctx), self.batch_axes, self.reduce_axes, self.contributor_axes, self.sharded_embeddings),
            "build": (_plain(make.func), _plain(make.args[3]), _plain(make.keywords)),
        })

    # jit-boundary: returns device buffers fresh off the compiled step
    def build_train_step(self, state: Any, batch: Any) -> Callable:
        """The jitted train step for ``batch``'s structure, built once.  Only
        the SHAPES of ``state`` and ``batch`` are read (arrays or
        ``ShapeDtypeStruct``s): where the model's rematerialised blocks keep
        by a byte budget the step is compiled here, against the line
        (:meth:`_held_to_the_line`), so a caller that knows the shapes early
        can pay that compile on a thread of its own (the benchmark's
        reference child does, while the device is busy)."""
        self._train_step = self._structured(
            self._train_steps, build_train_step, batch,
            fit_args=(state, batch, self._active_device()),
            host_keys=tuple(sorted(self.spec.host_io)),
            variant_budget=self.jit_budgets[
                "train_step_2d" if self.tp_axis is not None else "train_step"
            ],
            **self._train_build_kwargs(),
        )
        return self._train_step

    def train_step(self, state: TrainState, batch: Any):
        out = self.build_train_step(state, batch)(state, batch, self._active_device())
        if self._unstored:
            self._store_traced()
        return out

    def shard_stacked_batch(self, stacked: Any) -> Any:
        """Place a HOST batch of stacked minibatches ([T, mb, ...] per leaf)
        on the mesh in ONE transfer, sharded per STEP (leading scan dim
        replicated, batch dims sharded as usual)."""
        shardings = jax.tree.map(
            lambda x, o: NamedSharding(
                self.mesh, P(None, *self._batch_spec_for(o))
            ),
            stacked,
            self._one_step_shapes(stacked),
        )
        return self._place_global(stacked, shardings)

    @staticmethod
    def _one_step_shapes(stacked: Any):
        """ShapeDtypeStructs of a single step of a stacked [T, ...] batch —
        the shape basis for scan-variant specs (shared by
        shard_stacked_batch / train_scan / eval_scan so the three cannot
        drift)."""
        return jax.eval_shape(
            lambda t: jax.tree.map(lambda v: v[0], t), stacked
        )

    def _scanned(self, cache: Dict, build, stacked: Any, fit_args=None, **kwargs):
        """Scan-variant twin of _structured: build (or fetch) the fused
        lax.scan step for this stacked batch's tree structure."""
        key = ("scan", self._slot(stacked, fit_args))
        fn = cache.get(key)
        if fn is None:
            make = functools.partial(
                build,
                self.spec,
                self.mesh,
                self.ctx,
                self.state_specs(),
                batch_specs=self.batch_specs(self._one_step_shapes(stacked)),
                batch_axes=self.batch_axes,
                scan_steps=True,
                **kwargs,
            )
            fn = cache[key] = make() if fit_args is None else self._train_program(cache, key, make, fit_args)
        return fn

    # jit-boundary: returns device buffers fresh off the compiled scan
    def train_scan(self, state: TrainState, stacked: Any):
        """All T steps of a task in one jitted lax.scan (one dispatch, one
        compiled program — see build_train_step(scan_steps=True)).
        ``stacked``: device batch from shard_stacked_batch.  Returns
        (state, metrics dict of [T]-stacked scalars)."""
        self._train_step = self._scanned(
            self._train_steps, build_train_step, stacked,
            fit_args=(state, stacked, self._active_device()), host_keys=(),
            variant_budget=self.jit_budgets["train_scan"],
            **self._train_build_kwargs(),
        )
        out = self._train_step(state, stacked, self._active_device())
        if self._unstored:
            self._store_traced()
        return out

    # jit-boundary: returns device metrics fresh off the compiled step
    def eval_step(self, state: TrainState, batch: Any) -> Dict[str, jax.Array]:
        self._eval_step = self._structured(
            self._eval_steps, build_eval_step, batch,
            variant_budget=self.jit_budgets["eval_step"],
        )
        return self._eval_step(state, batch)

    # jit-boundary: returns device metrics fresh off the compiled scan
    def eval_scan(self, state: TrainState, stacked: Any):
        """All T eval steps of a task in one jitted lax.scan (see
        build_eval_step(scan_steps=True)).  Returns a metrics dict of
        [T]-stacked leaves; the caller weights per-chunk as usual."""
        self._eval_step = self._scanned(
            self._eval_steps, build_eval_step, stacked,
            variant_budget=self.jit_budgets["eval_scan"],
        )
        return self._eval_step(state, stacked)

    # jit-boundary: returns device outputs fresh off the compiled step
    def predict_step(self, state: TrainState, batch: Any):
        self._predict_step = self._structured(
            self._predict_steps, build_predict_step, batch,
            variant_budget=self.jit_budgets["predict_step"],
        )
        return self._predict_step(state, batch)


def build_train_step(
    spec: ModelSpec,
    mesh: Mesh,
    ctx: ParallelContext,
    state_specs: TrainState,
    host_keys: Sequence[str] = (),
    batch_specs: Any = None,
    batch_axes: Optional[Tuple[str, ...]] = None,
    scan_steps: bool = False,
    opt_shard: Any = None,
    opt_shard_axis: Optional[str] = None,
    donate: bool = True,
    collective: Any = None,
    variant_budget: int = 1,
    keep_plan: Optional[KeepPlan] = None,
) -> Callable:
    """The jitted train step ``(state, batch, active) -> ...``.  With
    ``host_keys`` (host-tier tables), the step ALSO differentiates with
    respect to those injected batch arrays and returns their cotangents as
    a third output, batch-sharded — the device-side half of the
    pull/step/push cycle (Trainer.run_train_step).

    ``active`` is the graftreduce subgroup mask (r15): a replicated
    ``[n_contributors]`` float32 vector of 0/1 participation weights, one
    per data-parallel shard.  Every contribution — the loss term, and via
    the chain rule every dense AND sparse gradient — scales by this
    shard's weight before any reduction, and every mean divides by the
    ACTIVE count (``sum/|G'|``), so an excluded straggler's shard drops
    out exactly and the survivors' math renormalizes.  With the all-ones
    default the spelling is bit-identical to the pre-r15 step (×1.0 is
    exact; ``psum`` of ones is exactly ``n``).  The mask is a traced
    input: changing the excluded set never recompiles.

    ``collective`` is the resolved graftreduce topology
    (collectives.CollectiveTopology or None): big dense-grad reductions
    route hierarchically (intra-host reduce-scatter, inter-host residue
    psum, local gather), scalars stay flat.

    ``batch_axes`` lists every mesh axis the batch shards over (defaults to
    just the embedding axis — the 1-D mesh).  Reductions of loss/metrics/
    dense grads run over all of them; sharded-table grads get only the
    NON-embedding axes' psum (their transpose already summed within the
    embedding axis).  On a tensor-parallel 2D mesh (``ctx.tp_axis``, r20)
    the tp axis is dropped from every reduction here: tp ranks see the
    same examples, the model's own f/g collectives already complete
    replicated-leaf grads per rank, and tp-sharded leaves' grads ARE the
    local shard's — summing any of it over tp would double-count.

    ``scan_steps=True``: the function takes STACKED batches ([T, ...] per
    leaf, T = steps) and runs all T steps inside one ``lax.scan`` — ONE
    dispatch and one host round-trip per task instead of per minibatch.
    Per-step dispatch cost ~half the step wall-clock on the retired
    backend's remote-attached chip (docs/perf.md); fusing the task's
    steps into a single XLA program removes it, and is the idiomatic XLA
    training-loop shape besides (static trip count, donated carry).
    Caller passes ``batch_specs`` of ONE step; specs gain a leading None
    (scan) dim here.  Incompatible with host-tier tables (their pull/push
    is host work between steps).
    """
    axis = ctx.axis_name
    assert axis is not None
    axes = tuple(batch_axes) if batch_axes else (axis,)
    if ctx.tp_axis is not None:
        axes = tuple(a for a in axes if a != ctx.tp_axis)
    dcn_axes = tuple(a for a in axes if a != axis)
    # Paths of sharded-table grads (params-relative): the collective
    # lookup's transpose sums them within the embedding axis already.
    grad_skip = {t.path for t in spec.embedding_tables} if ctx.sharded_embeddings else set()

    # ZeRO-style sharded weight update (``opt_shard`` is the trainer's
    # opt_shard_plan tree).  Instead of every replica psum'ing full dense
    # grads and redundantly computing the full optax update, dense grads
    # are REDUCE-SCATTERED over the shard axis, the update runs on each
    # replica's 1/dp flat shard (against its matching param slice and its
    # resident 1/dp optimizer-state shard), and the fresh updates are
    # all-gathered back — same math, 1/dp of the optimizer memory and
    # update FLOPs per replica.  Table leaves (_OPT_KEEP) keep the
    # existing co-sharded path untouched.
    if opt_shard is not None:
        shard_axis = opt_shard_axis or axes[0]
        n_shards = int(mesh.shape[shard_axis])
        other_axes = tuple(a for a in axes if a != shard_axis)

        def sharded_update(state: TrainState, grads):
            idx = lax.axis_index(shard_axis)

            def combine_grad(entry, g):
                if not isinstance(entry, _OptShard):
                    # Sharded-table grad: already summed within the
                    # embedding axis by the collective transpose.
                    return coll.psum(g, dcn_axes, collective) if dcn_axes else g
                if other_axes:
                    g = coll.psum(g, other_axes, collective)
                return coll.psum_scatter(
                    _pad_flat(g, entry), shard_axis,
                    scatter_dimension=0, tiled=True,
                )

            def shard_param(entry, p):
                if not isinstance(entry, _OptShard):
                    return p  # table leaf: already the local row shard
                k = entry.padded // n_shards
                return lax.dynamic_slice_in_dim(
                    _pad_flat(p, entry), idx * k, k
                )

            def expand_update(entry, u):
                if not isinstance(entry, _OptShard):
                    return u
                full = lax.all_gather(u, shard_axis, axis=0, tiled=True)
                return jnp.reshape(full[: entry.size], entry.shape)

            g_dom = jax.tree.map(combine_grad, opt_shard, grads)
            p_dom = jax.tree.map(shard_param, opt_shard, state.params)
            updates, opt_state = spec.optimizer.update(
                g_dom, state.opt_state, p_dom
            )
            updates = jax.tree.map(expand_update, opt_shard, updates)
            params = optax.apply_updates(state.params, updates)
            return params, opt_state

    # Wrap-padded training tails: the worker marks real rows in
    # ``__mask__`` (exactly as eval does); padded duplicates then carry
    # ZERO loss — hence zero gradient, dense and sparse alike — and the
    # cross-device combine weights each shard by its REAL count:
    # psum(local_masked_mean * count) / psum(count).  Without a mask the
    # math reduces to the old equal-shards /n + psum form bit-for-bit.
    # Loss fns without a mask parameter (user models) train on the padded
    # batch as before.
    wants_mask = "mask" in inspect.signature(spec.loss).parameters
    wants_metric_mask = "mask" in inspect.signature(spec.metrics).parameters

    # Exclusion slots are EXAMPLE shards (Trainer.contributor_axes): all
    # axes for data-parallel models, the outer axes for sequence-parallel
    # ones — an inner-axis sequence slice shares its examples with its
    # row and must never be excluded alone.
    contrib_axes = tuple(axes) if spec.batch_shard_dim == 0 else tuple(axes[:-1])

    def sweep_applies(path, leaf) -> bool:
        """Whether this leaf's update COULD be applied by the merge sweep
        itself (ops/table_grad.sweep_adam) instead of through a gradient
        buffer and optax: read at trace time from what is declared and
        what the leaf is — the declared plain Adam, unsharded optimizer
        state, a table the sweep serves, a gradient no other replica adds
        to.  (local_step adds: looked up exactly once in the step.)"""
        if spec.adam is None or opt_shard is not None or leaf.ndim != 2:
            return False
        summed_over = dcn_axes if _path_keys(path) in grad_skip else axes
        return sweeps(leaf) and all(mesh.shape[a] == 1 for a in summed_over)

    def local_step(state: TrainState, batch, active):
        # This shard's 0/1 subgroup weight (graftreduce r15): scales the
        # loss BEFORE autodiff, so every gradient — dense psum'd, table
        # transpose-summed, host cotangent — carries the exclusion via
        # the chain rule; no per-leaf masking can drift from the loss.
        # Constant per contributor, so sequence-parallel slices of one
        # example row scale uniformly; psum over ALL axes then counts
        # each contributor once per inner slice in both numerator and
        # denominator — the renormalization cancels exactly.
        w = (
            coll.contributor_weight(active, contrib_axes)
            if contrib_axes
            else active[0]  # SP 1-D mesh: one contributor, always active
        )
        n_active = jnp.maximum(coll.psum(w, axes), 1.0)
        batch = dict(batch)
        mask = batch.pop("__mask__", None) if wants_mask else None
        host_in = {k: batch.pop(k) for k in host_keys}
        if mask is not None:
            # Real-example count of THIS shard, zeroed when excluded: the
            # renormalized total is the active shards' real examples.
            count = jnp.sum(mask.astype(jnp.float32)) * w
            total = jnp.maximum(coll.psum(count, axes), 1e-12)

        # Tables whose update rows the lookup hands over (ops/embedding.py)
        # for the merge sweep to apply: ``fused_at`` their positions among
        # the params' leaves.  Differentiation sees a one-row placeholder
        # in their place and a zero carrier each, whose cotangent is the
        # update rows.
        leaves = jax.tree_util.tree_leaves_with_path(state.params)
        could = [i for i, (path, leaf) in enumerate(leaves) if sweep_applies(path, leaf)]
        fused_at, carriers = _tables_the_sweep_updates(
            spec, ctx, state.params, {**batch, **host_in}, could
        )
        fused_tables = [leaves[i][1] for i in fused_at]

        # What the model's rematerialised blocks may keep on this device is
        # resolved here, before the model is traced (ops/remat.py).
        model_ctx, block_traces = ctx, None
        if keep_plan is not None:
            model_ctx, block_traces = _resolve_keep_budget(spec, ctx, keep_plan, state, {**batch, **host_in})

        def loss_fn(params, host_embs, carriers):
            merged = dict(batch)
            merged.update(host_embs)
            params = _with_leaves(params, fused_at, fused_tables)
            with route_taps(fused_tables, carriers) as taps:
                out = spec.apply(params, merged, train=True, ctx=model_ctx)
            aux = (
                out,
                sum(taps.rows_received) if taps.rows_received else None,
                tuple(map(sum, zip(*taps.table_grad))),
                [ids for _, ids in sorted(taps.handed, key=lambda h: h[0])],
            )
            if mask is not None:
                # count/total are constants w.r.t. params; the psum above
                # traces fine under grad.
                return spec.loss(out, merged, mask=mask) * count / total, aux
            return spec.loss(out, merged) * w / n_active, aux

        with remat.survey(traces=block_traces) as held:
            (loss, (out, rows_received, table_grad, handed_ids)), (
                grads, host_grads, handed_rows
            ) = jax.value_and_grad(loss_fn, argnums=(0, 1, 2), has_aux=True)(
                _with_leaves(
                    state.params, fused_at,
                    [jnp.zeros((1,) + t.shape[1:], t.dtype) for t in fused_tables],
                ),
                host_in, carriers,
            )
        loss = coll.psum(loss, axes)
        if opt_shard is not None:
            params, opt_state = sharded_update(state, grads)
        else:
            grads = _tree_psum_except(
                grads, grad_skip, axes, dcn_axes, collective
            )
            if fused_at:
                params, opt_state = _adam_update_with_fused_tables(
                    spec, state.params, state.opt_state, grads, fused_at,
                    list(zip(handed_ids, handed_rows)),
                )
            else:
                updates, opt_state = spec.optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)
        if spec.after_update is not None:
            # Leaves the optimizer leaves alone, moved by the model's rule.
            params = spec.after_update(params, out)
        # Histogram metrics (streaming AUC, common/metrics.HIST_PREFIX) are
        # EVAL machinery — per-minibatch training AUC is noise, and the
        # reference computes AUC only in evaluation — so the train step
        # drops them before the collective mean.
        if mask is not None and wants_metric_mask:
            raw = spec.metrics(out, batch, mask=mask)
            metrics = {
                k: coll.psum(v * count, axes) / total
                for k, v in raw.items()
                if not k.startswith(HIST_PREFIX) and k not in spec.step_counters
            }
        else:
            raw = spec.metrics(out, batch)
            metrics = {
                k: coll.psum(v * w, axes) / n_active
                for k, v in raw.items()
                if not k.startswith(HIST_PREFIX) and k not in spec.step_counters
            }
        for k in spec.step_counters:
            # Counts of what each device did, not means: summed.
            metrics[k] = coll.psum(raw[k] * w, axes)
        metrics["loss"] = loss
        if held.layers:
            # What the rematerialised blocks tagged and kept on a device
            # (the worker sums both into its STEP_COUNTERS).
            metrics["remat_bytes_tagged"] = coll.psum(jnp.float32(held.tagged_bytes) * w, axes)
            metrics["remat_bytes_kept"] = coll.psum(jnp.float32(held.kept_bytes) * w, axes)
            if keep_plan is not None:
                keep_plan.tagged, keep_plan.kept = held.tagged_bytes, held.kept_bytes
        if rows_received is not None:
            # The ragged route's load: table rows this shard served in the
            # step, on the fullest shard and on average (the worker sums
            # both into its STEP_COUNTERS instead of reporting them).
            rows = rows_received.astype(jnp.float32)
            metrics["route_rows_recv_max"] = lax.pmax(rows, axes)
            metrics["route_rows_recv_mean"] = coll.psum(
                rows, axes
            ) / coll.contributor_count(mesh, axes)
        if table_grad:
            # Update rows the tables' gradients were offered in the step,
            # those delivered by a sorted merge sweep (ops/table_grad.py),
            # and those of them whose table the sweep updated itself: all
            # or none, a table at a time.
            rows, swept, fused = (
                coll.psum(x.astype(jnp.float32), axes) for x in table_grad
            )
            metrics["table_grad_rows"] = rows
            metrics["table_grad_rows_swept"] = swept
            metrics["table_grad_rows_fused"] = fused
        new_state = TrainState(step=state.step + 1, params=params, opt_state=opt_state)
        if host_keys:
            # Per-example cotangents of the global-mean loss, batch-sharded;
            # NOT psum'd (each example's grad lives on its own shard).
            return new_state, metrics, host_grads
        return new_state, metrics

    if scan_steps:
        if host_keys:
            raise ValueError("scan_steps is incompatible with host-tier tables")

        def local_scan(state: TrainState, batches, active):
            # The mask is scan-invariant: one exclusion set per task
            # dispatch (the worker's gate runs at the task boundary).
            def body(carry, one):
                return local_step(carry, one, active)

            return lax.scan(body, state, batches)

        one_step_specs = batch_specs if batch_specs is not None else P(axis)
        stacked_specs = jax.tree.map(
            lambda s: P(None, *s),
            one_step_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        mapped = shard_map(
            local_scan,
            mesh=mesh,
            in_specs=(state_specs, stacked_specs, P()),
            out_specs=(state_specs, P()),
            check_vma=False,
        )
        if donate:
            return jit_donating(
                mapped, name="trainer.train_scan",
                expected_variants=variant_budget,
            )
        return jit_compiled(
            mapped, name="trainer.train_scan",
            expected_variants=variant_budget,
        )

    out_specs: Tuple = (state_specs, P())
    if host_keys:
        # Host cotangents mirror the injected leaf's batch layout
        # (batch_leaf_spec — the same selector as input sharding).
        host_spec = batch_leaf_spec(axes, spec.batch_shard_dim)
        out_specs = (state_specs, P(), {k: host_spec for k in host_keys})
    mapped = shard_map(
        local_step,
        mesh=mesh,
        in_specs=(
            state_specs,
            batch_specs if batch_specs is not None else P(axis),
            P(),
        ),
        out_specs=out_specs,
        check_vma=False,
    )
    if donate:
        return jit_donating(
            mapped, name="trainer.train_step", expected_variants=variant_budget
        )
    return jit_compiled(
        mapped, name="trainer.train_step", expected_variants=variant_budget
    )


def build_predict_step(
    spec: ModelSpec,
    mesh: Mesh,
    ctx: ParallelContext,
    state_specs: TrainState,
    batch_specs: Any = None,
    batch_axes: Optional[Tuple[str, ...]] = None,
    variant_budget: int = 1,
) -> Callable:
    """Per-example model outputs, batch-sharded in and out (the reference's
    predict mode, SURVEY.md §2 #1 'predict').  Models with a ``predict``
    entry (models/spec.ModelSpec.predict) serve client-ready values (e.g.
    probabilities); the rest serve raw ``apply(train=False)`` outputs."""
    axis = ctx.axis_name
    assert axis is not None

    def local_predict(state: TrainState, batch):
        # Tensor-parallel meshes: outputs are replicated along tp (the
        # model's final tp_all_reduce completes them on every rank), so
        # the dp-only out_spec below reassembles the global batch.
        # Serving batches ride with a padding mask the model must not see
        # (``__mask__`` is the micro-batcher's fan-back bookkeeping) —
        # mirror local_eval's pop.
        batch = dict(batch)
        batch.pop("__mask__", None)
        if spec.predict is not None:
            return spec.predict(state.params, batch, ctx=ctx)
        return spec.apply(state.params, batch, train=False, ctx=ctx)

    d = spec.batch_shard_dim
    axes = tuple(batch_axes) if batch_axes else (axis,)
    if ctx.tp_axis is not None:
        axes = tuple(a for a in axes if a != ctx.tp_axis)
    # Per-example outputs mirror the input batch layout (batch_leaf_spec —
    # the same selector as input sharding and host cotangents).
    out_spec = batch_leaf_spec(axes, d)
    mapped = shard_map(
        local_predict,
        mesh=mesh,
        in_specs=(state_specs, batch_specs if batch_specs is not None else P(axis)),
        out_specs=out_spec,
        check_vma=False,
    )
    return jit_compiled(
        mapped, name="trainer.predict_step", expected_variants=variant_budget
    )


def build_eval_step(
    spec: ModelSpec,
    mesh: Mesh,
    ctx: ParallelContext,
    state_specs: TrainState,
    batch_specs: Any = None,
    batch_axes: Optional[Tuple[str, ...]] = None,
    scan_steps: bool = False,
    variant_budget: int = 1,
) -> Callable:
    axis = ctx.axis_name
    assert axis is not None
    axes = tuple(batch_axes) if batch_axes else (axis,)
    if ctx.tp_axis is not None:
        # Metrics reduce over dp only — each tp rank computes identical
        # metrics from its replicated logits and examples.
        axes = tuple(a for a in axes if a != ctx.tp_axis)
    # Tail-chunk correctness: the worker wrap-pads the last eval chunk to the
    # static minibatch size and marks real rows in ``__mask__``.  Metrics
    # functions that accept a mask compute means over real examples only;
    # the cross-device aggregate is psum(local_mean * local_count) /
    # psum(local_count), exact under uneven per-device real counts.  Metrics
    # without a mask parameter (user models) fall back to plain pmean over
    # the padded batch.
    wants_mask = "mask" in inspect.signature(spec.metrics).parameters

    def local_eval(state: TrainState, batch):
        batch = dict(batch)
        mask = batch.pop("__mask__", None)
        out = spec.apply(state.params, batch, train=False, ctx=ctx)
        if mask is not None and wants_mask:
            metrics = spec.metrics(out, batch, mask=mask)
            count = jnp.sum(mask.astype(jnp.float32))
            total = jnp.maximum(coll.psum(count, axes), 1e-12)
            return {
                k: coll.psum(v * count, axes) / total
                for k, v in metrics.items()
            }
        return {
            k: coll.pmean(v, axes)
            for k, v in spec.metrics(out, batch).items()
            if k not in spec.step_counters
        }

    if scan_steps:
        # Stacked [T, ...] batches, all T eval steps in one lax.scan — the
        # eval-side twin of the fused training task (one dispatch per eval
        # task).  Masked tails stay outside the scan (the worker evals them
        # as one extra step), so the scanned chunks are all full-size and
        # the per-chunk metric weighting stays host-side as before.
        def local_eval_scan(state: TrainState, batches):
            def body(carry, batch):
                return carry, local_eval(state, batch)

            _, metrics = lax.scan(body, 0, batches)
            return metrics

        one_step_specs = batch_specs if batch_specs is not None else P(axis)
        stacked_specs = jax.tree.map(
            lambda s: P(None, *s),
            one_step_specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        mapped = shard_map(
            local_eval_scan,
            mesh=mesh,
            in_specs=(state_specs, stacked_specs),
            out_specs=P(),
            check_vma=False,
        )
        return jit_compiled(
            mapped, name="trainer.eval_scan", expected_variants=variant_budget
        )

    mapped = shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(state_specs, batch_specs if batch_specs is not None else P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return jit_compiled(
        mapped, name="trainer.eval_step", expected_variants=variant_budget
    )
