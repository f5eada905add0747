"""Mesh-sharded embedding lookup — the TPU-native replacement for the
reference's gRPC parameter-server embedding path (``elasticdl.layers.
Embedding`` pulling vectors / pushing IndexedSlices grads over gRPC
[D: BASELINE.json north_star]; reference sources unverifiable, mount empty at
survey time).

Design (static shapes, XLA/ICI-friendly — see SURVEY.md §7 item 5):

- **Lane-packed storage.**  A table of ``V'`` logical rows × ``dim`` is
  stored as a 2-D array ``[V'/pack, pack*stride]`` where ``stride`` is the
  next power of two ≥ ``dim`` (dead lanes zero-filled) and ``pack =
  128 // stride``: ``pack`` logical rows share one exactly-128-lane physical
  row, so every gather/scatter touches whole lane-aligned vregs.  The
  power-of-two stride matters: a dim-9 table packed at its natural width 126
  measured a 3x slower gather than the same data at width 128 on v5e.  This
  formulation is
  what XLA:TPU vectorizes.  Per-op device times of the r3 session (a
  backend that is gone; 8192×26 ids into a 1.7M-row dim-8 table = 106,496
  physical rows, 54.5 MB, v5e): the packed row gather 0.53 ms and its
  transpose scatter-add 2.75 ms — versus **370 ms / 728 ms** for the same
  shapes stored flat 1-D and gathered as ``dim``-element slices, which XLA
  lowers to a *serial per-row while loop* (212,992 iterations/step at ~2-3
  µs each; this was round 2's entire ~200x throughput gap).  An unpacked
  2-D ``[V, 8]`` table vectorizes too but wastes 15/16 of each vreg on the
  scatter (18.2 ms); a one-hot-matmul lookup costs 20 ms of MXU time.  bf16
  rows do NOT help: at that table size the scatter-add is op-rate-bound
  (~13 ns/row whether the physical row is 256 B or 512 B — 2.97 ms bf16 vs
  2.75 ms f32), so tables stay f32 (docs/perf.md).  The scatter-add's cost
  a row is NOT a constant, though: re-measured on current code (PERF.md,
  PR 27, step 0) the same 212,992 rows cost 2.8 ms into 54 MB, 3.9 ms into
  654 MB and 18.8 ms into the benchmark's 1.31 GB table (74 ns a row in the
  step), which is why a big table's cotangent is built by the sorted merge
  sweep instead (``SWEEP_MIN_ROWS``, ``ops/table_grad.py``).
- Lookup of logical row ``i`` reads physical row ``i // pack`` (one 128-lane
  gather) and selects lane group ``i % pack`` with a tiny one-hot einsum;
  the AD transpose expands cotangents back to 128-lane rows (einsum
  transpose) and scatter-adds whole physical rows — or, for a big f32 table
  on a TPU, sorts them and merges them into the buffer in one sequential
  sweep (``sweeps``: read from the table's shape, no flag).
- The table is **physical-row-sharded** over the mesh axis: ``V'`` is padded
  so the physical row count divides every power-of-two mesh size up to 256,
  and shard ``i`` owns logical rows ``[i*V'/n, (i+1)*V'/n)`` — GSPMD's
  natural div-sharding of dim 0, so the same array is addressable both
  outside shard_map (one logical array, e.g. for Orbax) and inside (the
  local row range).

Two collective lookup implementations, selected at trace time:

- ``ragged`` (default on multi-chip TPU) — the north-star **ragged
  all-to-all** route: sort local ids by owner shard, exchange
  per-destination counts (n² int32), ``all_gather`` every chip's sorted
  ids (``n·L`` int32: the size of the owner's slots either way), mask the
  slots that belong to other owners, lane-packed gather locally,
  ``lax.ragged_all_to_all`` the vectors straight back, unsort.  The ids do
  NOT go by ``ragged_all_to_all``: the op splits its operand by rows of
  the leading dimension and the TPU gives each row a 128-lane tile, so an
  ``int32[L]`` of ids arrives as ``s32[n·L, 1, 128]`` (109 MB for 0.85 MB
  at the benchmark's size) and reading lane 0 back out of it costs a
  sixth of the step (PERF.md, PR 55).  A chunk stays where the all-gather
  lands it (row k of the owner's ``[n, L]`` slots, at its place in sender
  k's sorted list), so the two float legs' offsets are those places and
  nothing is packed or searched.  Each vector crosses ICI exactly once, so
  per-device vector traffic is ~``B_local·dim`` (id-distribution
  dependent), independent of mesh size.  XLA:CPU does not implement the
  ``ragged-all-to-all`` HLO, so tests run the two float legs through a
  dense all_gather emulation of the collective (``ragged_emulated``) that
  is semantically equivalent by construction; the plan, the ids' leg, the
  mask and the unsort are the chip's own code.
- ``dense`` (CPU fallback; also the n=1 degenerate) — ``all_gather`` every
  device's ids, masked lane-packed gather over the full global id list, then
  ``psum_scatter`` a ``[n·B_local, dim]`` array so each device receives its
  own rows.  Simple and always available, but the psum_scatter moves
  ~``(n-1)·B_local·dim`` per device — ~(n−1)× the ragged route's vector
  volume — so it loses badly at pod scale.

``auto`` resolves per (platform, mesh size): a 1-device axis always takes
the local-gather short-circuit (paying ragged's sort/bincount machinery with
zero peers was a measured 28% tax in round 2 — VERDICT r2 Weak #1); n>1 on
TPU takes ``ragged``; CPU takes ``dense``.

Backward (both impls): the cotangents retrace the forward route back to the
owner shard and scatter-add into its local rows (whole-physical-row
scatter-add — the transpose of the packed gather — or the merge sweep that
builds the same buffer), with duplicate ids
correctly accumulated — the moral equivalent of the reference's server-side
IndexedSlices apply.  The ragged impl does this through a ``custom_vjp`` (the
ragged collective has no AD rule): the saved routing metadata is replayed,
vectors flow requester→owner, and the owner applies the same masked
scatter-add.

Handing the update rows over (PR 29).  A trainer that applies a table's
update itself (``ops/table_grad.sweep_adam``: dense Adam inside the merge
sweep) needs the table's update as ``(physical row [N], cotangent row
[N, 128])`` and not as a table-shaped cotangent.  It names such tables when
it opens :func:`route_taps` (``hand_over``: the very arrays its params hold,
recognised by identity, so models keep calling ``embedding_lookup(table,
ids, ctx, dim)`` unaware) and gives each a zero "carrier" of the gathered
rows' shape.  The lookup's gather then runs under a ``custom_vjp``
(``_take_rows_handed``; on the ragged route ``_ragged_lookup`` itself)
whose backward returns the rows' cotangent as the CARRIER's and none for
the table, and appends the physical row of each to ``taps.handed``; on the
ragged route both are what the OWNER holds after ``route_bwd_vectors`` and
the lane select's transpose, so nothing new crosses the interconnect.  Why
a carrier: a cotangent has to have the shape of some primal input, and the
update rows have the shape of none; a zero input that the forward never
reads costs nothing (XLA drops it) and stays inside JAX's rules, where
smuggling the rows out of the backward through a side channel would not.
Its shape is not known before the apply is traced, so the trainer traces
the apply once more under ``jax.eval_shape`` with no carriers given (the
counting trace: ``taps.handed`` then holds each lookup's carrier shape, and
tells a table looked up twice, which is not fused, from one looked up once).

Fail-loud OOV contract (both impls): an id outside the padded global vocab
comes back as a NaN row — never a silently wrong or zero row.  In the ragged
impl this is structural: the junk id routes to a clamped owner whose local
row range it misses, the fill-mode gather fills NaN, and the NaN rides back
to the requester; its cotangent is dropped on the same grounds.

Optimizer state for the table is co-sharded automatically because optax maps
leaf-wise (each shard's Adam moments live next to its rows — like the
reference's per-PS-pod Go optimizer state).
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend.random import threefry2x32_p

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.ops.table_grad import sweep_table_grad

# TPU vreg lane count: physical rows are packed to (at most) this many lanes.
LANES = 128

# Pad physical row counts to a multiple of this so the padded table
# div-shards over every power-of-two mesh size up to a v5e-256 pod; table
# shapes then stay identical across elastic resizes (4->8->4 never reshapes
# params or optimizer state).
PHYSICAL_ROW_MULTIPLE = 256

# Physical rows from which, on a TPU, the table cotangent of a row gather is
# built by the sorted merge sweep (ops/table_grad.py) instead of the AD
# transpose's zeros + scatter-add.  N = 212,992 update rows on a v5e (PERF.md,
# PR 27, step 0): the two tie at 1.28 M rows (3.8 against 3.9 ms), XLA's
# scatter-add then falls off a cliff between 1.54 M and 1.79 M rows (4.2 ->
# 18.2 ms) while the sweep grows with the bytes it writes (5.0 ms at 2.56 M).
SWEEP_MIN_ROWS = 3 << 19

# HBM guard for auto host-tier promotion: a table whose padded storage plus
# Adam moments (3x) would crowd a v5e's 16 GiB HBM (shared with activations
# and the dense model) belongs on the host tier (ps/host_store) instead of
# the mesh.  Per-DEVICE cost is bytes/n at mesh size n; the guard is
# conservative for n=1 (the single-chip bench/dev case).
HOST_TIER_GUARD_BYTES = 4 << 30


def table_bytes(vocab_size: int, dim: int, itemsize: int = 4) -> int:
    """Padded lane-packed storage bytes for one table (excl. optimizer)."""
    rows, width = table_shape(vocab_size, dim)
    return rows * width * itemsize


def exceeds_hbm_guard(vocab_size: int, dim: int, num_devices: int = 0) -> bool:
    """True when the PER-DEVICE share of table + 2 Adam moments exceeds
    HOST_TIER_GUARD_BYTES.  The table row-shards over the whole mesh, so a
    table that crowds one chip can be fine on a pod — ``num_devices``
    defaults to the current backend's device count (1 on a lone chip)."""
    if num_devices <= 0:
        num_devices = jax.device_count()
    return 3 * table_bytes(vocab_size, dim) > HOST_TIER_GUARD_BYTES * num_devices


#: Lookup implementations (ParallelContext.embedding_impl / config flag).
IMPL_AUTO = "auto"
IMPL_RAGGED = "ragged"
IMPL_RAGGED_EMULATED = "ragged_emulated"  # tests: same routing, dense collective
IMPL_DENSE = "dense"
LOOKUP_IMPLS = (IMPL_AUTO, IMPL_RAGGED, IMPL_RAGGED_EMULATED, IMPL_DENSE)


@dataclasses.dataclass(frozen=True)
class ParallelContext:
    """Trace-time description of how the current step is parallelized.

    Passed by the trainer into ``ModelSpec.apply`` so embedding ops know
    whether tables are mesh-sharded (ParameterServer strategy) or replicated
    (AllReduce/Local).  ``axis_name`` is the mesh axis the step runs under
    (None when not inside shard_map).  ``embedding_impl`` picks the sharded
    lookup route; ``auto`` resolves per (platform, mesh size) — the trainer
    resolves it before tracing via :func:`resolve_impl`.  ``tp_axis`` names
    the tensor-parallel mesh axis on a 2D ``(dp, tp)`` mesh (r20) — models
    with a ``tensor_sharding`` plan switch their apply to the column/row
    -split path when it is set; None everywhere else.  ``remat_keep_bytes``
    is what a model's rematerialised blocks may keep of their activations
    (``ops/remat.py``): the trainer resolves it from the device's memory
    before it traces the model; 0 (off the TPU, always) keeps nothing.
    """

    axis_name: Optional[str] = None
    sharded_embeddings: bool = False
    embedding_impl: str = IMPL_AUTO
    tp_axis: Optional[str] = None
    remat_keep_bytes: int = 0


def row_stride(dim: int) -> int:
    """Lane stride a logical row occupies in packed storage.

    The next power of two >= dim (so the 128-lane physical row divides into
    whole strides) for dim <= 128, else the next multiple of 128.  Keeping
    the physical width exactly lane-aligned matters: a dim-9 table packed at
    its natural width 126 (14 rows x 9) measured a 3x slower gather than the
    same data at width 128 (8 rows x stride 16) on v5e — dead lanes are
    cheaper than misalignment.
    """
    if dim <= 0:
        raise ValueError(f"embedding dim must be positive, got {dim}")
    if dim >= LANES:
        return ((dim + LANES - 1) // LANES) * LANES
    stride = 1
    while stride < dim:
        stride *= 2
    return stride


def row_pack(dim: int) -> int:
    """Logical rows per 128-lane physical row (1 when dim >= 128)."""
    return max(1, LANES // row_stride(dim))


def pad_vocab(vocab_size: int, dim: int = LANES) -> int:
    """Padded logical vocab: the smallest multiple of pack*PHYSICAL_ROW_MULTIPLE
    >= vocab_size, so the packed table's physical rows divide every
    power-of-two mesh size up to 256."""
    multiple = row_pack(dim) * PHYSICAL_ROW_MULTIPLE
    return ((vocab_size + multiple - 1) // multiple) * multiple


def table_shape(vocab_size: int, dim: int) -> Tuple[int, int]:
    """Packed storage shape [physical_rows, pack*stride] for a padded vocab."""
    pack = row_pack(dim)
    return pad_vocab(vocab_size, dim) // pack, pack * row_stride(dim)


def _pack_geometry(width: int, dim: int) -> Tuple[int, int]:
    """(pack, stride) for a table of physical width ``width`` holding
    ``dim``-sized logical rows.  ``width == dim`` is the plain un-packed
    case; otherwise the stride is :func:`row_stride`'s canonical value."""
    if width == dim:
        return 1, dim
    stride = row_stride(dim)
    if width % stride:
        raise ValueError(
            f"table width {width} is not a multiple of the canonical "
            f"stride {stride} for dim {dim}"
        )
    return width // stride, stride


def init_table(rng: jax.Array, vocab_size: int, dim: int, scale: float = 0.01):
    """A freshly initialized lane-packed [P, pack*dim] table."""
    return jax.random.normal(rng, table_shape(vocab_size, dim)) * scale


def _normal_at(rng: jax.Array, hi: jax.Array, lo: jax.Array) -> jax.Array:
    """The float32 values ``jax.random.normal(rng, shape)`` holds at the
    row-major positions ``hi * 2**32 + lo`` of ``shape``.  Under
    ``jax_threefry_partitionable`` every element's bits are threefry of
    its own 64-bit position, so any subset, in any layout, on any number
    of devices, can be drawn without the array that holds them all; the
    bits-to-normal recipe is jax.random's own (mantissa bits to [1, 2),
    shifted to (-1, 1), ``sqrt(2) * erf_inv``)."""
    k1, k2 = jax.random.key_data(rng)
    b1, b2 = threefry2x32_p.bind(k1, k2, hi, lo)
    one = np.float32(1).view(np.uint32)
    floats = lax.bitcast_convert_type(
        ((b1 ^ b2) >> np.uint32(9)) | one, jnp.float32
    ) - np.float32(1)
    low = np.nextafter(np.float32(-1), np.float32(0))
    u = jnp.maximum(low, floats * (np.float32(1) - low) + low)
    return np.float32(np.sqrt(2)) * lax.erf_inv(u)


def _flat_position(row: jax.Array, col: jax.Array, width: int):
    """``row * width + col`` (uint32 arrays, ``width`` < 2**16) as the two
    uint32 halves ``(hi, lo)`` of the 64-bit product: with ``row = a16 *
    2**16 + b16`` it is ``(a16 * width) * 2**16 + (b16 * width + col)``,
    each factor below 2**32."""
    a = (row >> np.uint32(16)) * np.uint32(width)
    b = (row & np.uint32(0xFFFF)) * np.uint32(width) + col
    lo = (a << np.uint32(16)) + b
    hi = (a >> np.uint32(16)) + (lo < b).astype(jnp.uint32)
    return hi, lo


def normal_packed_table(
    rng: jax.Array, vocab_size: int, dim: int,
    live_dim: Optional[int] = None, scale: float = 0.01,
) -> jax.Array:
    """A lane-packed [P, pack*stride] table born packed: logical row ``r``
    holds ``jax.random.normal(rng, (vocab_size, live_dim))[r] * scale`` in
    its first ``live_dim`` lanes (default ``dim``); the other lanes of the
    stride and the padding rows are zero.  Every element is drawn from its
    own counter (:func:`_normal_at`), so there is no [vocab, stride] array
    with a padded minor dimension on the way (on a TPU that one is 8 x the
    table), and under ``jit(..., out_shardings=rows over the mesh)`` each
    device computes its own rows only; the values do not depend on the
    number of devices.  (Run op by op the bits are exactly those of the
    expression above; inside a jit XLA fuses the two constant factors and
    some values land one ulp away.)"""
    live_dim = live_dim or dim
    if not 0 < live_dim <= dim < 1 << 16:
        raise ValueError(f"need 0 < live_dim <= dim < 65536, got {live_dim}, {dim}")
    if pad_vocab(vocab_size, dim) >= 1 << 32:
        raise ValueError(f"vocab {vocab_size} does not fit a 32-bit row counter")
    rows, width = table_shape(vocab_size, dim)
    stride = row_stride(dim)
    p = lax.broadcasted_iota(jnp.uint32, (rows, width), 0)
    lane = lax.broadcasted_iota(jnp.uint32, (rows, width), 1)
    row = p * np.uint32(width // stride) + lane // np.uint32(stride)
    col = lane % np.uint32(stride)
    hi, lo = _flat_position(row, col, live_dim)
    live = (col < live_dim) & (row < vocab_size)
    return jnp.where(live, _normal_at(rng, hi, lo) * np.float32(scale), 0.0)


def pack_table(table: jax.Array, dim: int) -> jax.Array:
    """Convert a plain [V, dim] (or flat [V*dim]) table into the padded
    lane-packed [P, pack*stride] layout.  Rows past V and lanes past dim
    zero-fill."""
    if table.ndim == 1:
        if table.shape[0] % dim:
            raise ValueError(
                f"flat table of {table.shape[0]} elements is not a multiple "
                f"of dim {dim}"
            )
        table = table.reshape(-1, dim)
    if table.ndim != 2 or table.shape[1] != dim:
        raise ValueError(
            f"expected a [V, {dim}] or flat [V*{dim}] table, got {table.shape}"
        )
    rows, width = table_shape(table.shape[0], dim)
    stride = row_stride(dim)
    pack = width // stride
    padded = rows * pack
    if table.shape[0] < padded:
        table = jnp.concatenate(
            [table, jnp.zeros((padded - table.shape[0], dim), table.dtype)]
        )
    if stride > dim:
        table = jnp.concatenate(
            [table, jnp.zeros((padded, stride - dim), table.dtype)], axis=-1
        )
    return table.reshape(rows, width)


def unpack_table(table: jax.Array, dim: int) -> jax.Array:
    """The [V', dim] logical view of a lane-packed table (padding included)."""
    _, stride = _pack_geometry(table.shape[1], dim)
    return table.reshape(-1, stride)[:, :dim]


def logical_rows(table: jax.Array, dim: int) -> int:
    """Number of logical rows a packed [P, pack*stride] table holds."""
    pack, _ = _pack_geometry(table.shape[1], dim)
    return table.shape[0] * pack


def _physical_rows(num_physical: int, flat_ids: jax.Array, pack: int):
    """(physical row, lane group) of each logical id; an id outside the
    table (either sign) goes to physical row ``num_physical``, which
    :func:`_take_rows` NaN-fills and every table gradient drops."""
    # jnp.take wraps NEGATIVE indices NumPy-style before the bounds check,
    # so a bare -1 would silently read the last row: mark OOB explicitly.
    oob = (flat_ids < 0) | (flat_ids >= num_physical * pack)
    if pack == 1:
        return jnp.where(oob, num_physical, flat_ids), None
    hi = jnp.where(oob, num_physical, flat_ids // pack)
    lo = jnp.where(oob, 0, flat_ids - (flat_ids // pack) * pack)
    return hi, lo


def _select_lanes(rows: jax.Array, lo, pack: int, stride: int, dim: int):
    """[N, dim]: lane group ``lo`` of each physical row (linear in ``rows``;
    a NaN row stays NaN: NaN * 0 == NaN)."""
    if pack == 1:
        return rows[:, :dim]
    rows = rows.reshape(rows.shape[0], pack, stride)
    sel = jax.nn.one_hot(lo, pack, dtype=rows.dtype)
    return jnp.einsum("nps,np->ns", rows, sel)[:, :dim]


def gather_rows(table: jax.Array, ids: jax.Array, dim: Optional[int] = None,
                hand: Optional["_Hand"] = None):
    """Logical rows ``ids`` of a lane-packed table as ``ids.shape + (dim,)``.

    ``table`` is ``[P, pack*dim]`` (``dim`` defaults to the full width, i.e. a
    plain ``[V, dim]`` table is the ``pack == 1`` case).  Whole-physical-row
    gather + one-hot lane select; its AD transpose is a whole-physical-row
    scatter-add, built for a big table on a TPU by the sorted merge sweep
    (:func:`sweeps`).  Out-of-range ids (either sign) fill with NaN (floats) so
    id-generation bugs surface immediately instead of silently training on a
    clamped row; the fill-mode transpose likewise drops OOB cotangents.
    ``hand``: the table's hand-over slot of an open :func:`route_taps`, or
    None (module docstring, "Handing the update rows over").
    """
    P, W = table.shape
    if dim is None:
        dim = W
    pack, stride = _pack_geometry(W, dim)
    fill = jnp.nan if jnp.issubdtype(table.dtype, jnp.floating) else 0
    hi, lo = _physical_rows(P, ids.reshape(-1), pack)
    out = _select_lanes(_take_rows(table, hi, fill, hand), lo, pack, stride, dim)
    return out.reshape(ids.shape + (dim,))


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def sweeps(table: jax.Array) -> bool:
    """Whether the cotangent of a row gather from ``table`` is built by the
    merge sweep: read from the table's shape and the platform, at trace
    time.  Everything else keeps the AD transpose of ``jnp.take``."""
    rows, width = table.shape
    return (
        rows >= SWEEP_MIN_ROWS and width == LANES
        and table.dtype == jnp.float32 and _on_tpu()
    )


def _take_rows(table: jax.Array, idx: jax.Array, fill, hand: Optional["_Hand"] = None):
    """Physical rows ``idx`` (in ``[0, P]``; ``P`` fills) of ``table``."""
    if hand is not None:
        carrier = hand.carrier(idx.shape[0], table)
        if carrier is not None:
            idx = idx.astype(jnp.int32)
            hand.deliver(idx)
            return _take_rows_handed(table, idx, carrier)
    if sweeps(table):
        return _take_rows_swept(table, idx.astype(jnp.int32), table.shape[0])
    return jnp.take(table, idx, axis=0, mode="fill", fill_value=fill)


@partial(jax.custom_vjp, nondiff_argnums=(2,))
def _take_rows_swept(table, idx, num_rows: int):
    return jnp.take(table, idx, axis=0, mode="fill", fill_value=jnp.nan)


def _take_rows_swept_fwd(table, idx, num_rows: int):
    return _take_rows_swept(table, idx, num_rows), idx


def _take_rows_swept_bwd(num_rows: int, idx, g):
    with jax.named_scope("table_grad"):
        table_bar = sweep_table_grad(idx, g, num_rows)
    return table_bar, np.zeros(idx.shape, jax.dtypes.float0)


_take_rows_swept.defvjp(_take_rows_swept_fwd, _take_rows_swept_bwd)


@jax.custom_vjp
def _take_rows_handed(table, idx, carrier):
    """The gather of a table whose update rows are handed over: the rows'
    cotangent comes back as ``carrier``'s, the table gets none."""
    return jnp.take(table, idx, axis=0, mode="fill", fill_value=jnp.nan)


def _take_rows_handed_fwd(table, idx, carrier):
    return _take_rows_handed(table, idx, carrier), idx


def _take_rows_handed_bwd(idx, g):
    return None, np.zeros(idx.shape, jax.dtypes.float0), g


_take_rows_handed.defvjp(_take_rows_handed_fwd, _take_rows_handed_bwd)


def embedding_lookup(
    table: jax.Array,
    ids: jax.Array,
    ctx: ParallelContext,
    dim: Optional[int] = None,
) -> jax.Array:
    """Look up ``ids`` in ``table``.

    ``table`` is 2-D lane-packed ``[P, pack*dim]`` (build with
    :func:`init_table` / :func:`pack_table`; a plain ``[V, dim]`` table is
    the ``pack == 1`` case and needs no ``dim``).  In sharded mode (inside
    shard_map) the array is this device's physical-row range of the padded
    global table and the lookup is collective, as described in the module
    docstring.

    ids may have any shape; output has shape ``ids.shape + (dim,)``.
    """
    if table.ndim != 2:
        raise ValueError(
            f"table must be 2-D lane-packed [P, pack*stride] (got shape "
            f"{table.shape}); convert flat tables with pack_table()"
        )
    if dim is None:
        dim = table.shape[1]
    _pack_geometry(table.shape[1], dim)  # raises on inconsistent width/dim

    hand = _hand_of(table)
    if not (ctx.sharded_embeddings and ctx.axis_name):
        _tap_lookup(table, ids, dim, hand)
        return gather_rows(table, ids, dim, hand)
    impl = resolve_impl(ctx.embedding_impl)
    # n=1 degenerates to a local gather (dense short-circuits it); an
    # EXPLICIT ragged request is still honored so the real op can be
    # smoke-tested on a single chip.
    if impl == IMPL_DENSE or (
        axis_size(ctx.axis_name) == 1 and impl == IMPL_RAGGED_EMULATED
    ):
        return _dense_lookup(table, ids, ctx.axis_name, dim, hand)
    # The owner's side of the route gathers n * L rows (worst-case skew).
    carrier = (
        None if hand is None
        else hand.carrier(axis_size(ctx.axis_name) * ids.size, table)
    )
    out, rows_received, rows_in_range, physical = _ragged_lookup(
        table, ids, carrier, ctx.axis_name, dim, impl == IMPL_RAGGED_EMULATED
    )
    if carrier is not None:
        hand.deliver(physical)
    if _TAPS.open is not None:
        _TAPS.open.rows_received.append(rows_received)
    _tap_table_grad(table, rows_in_range, hand)
    return out


@dataclasses.dataclass
class LookupTaps:
    """What :func:`route_taps` collects, one entry a lookup traced: int32
    scalars of the enclosing trace."""

    #: ragged route: rows THIS shard received.
    rows_received: list = dataclasses.field(default_factory=list)
    #: every route: (update rows the table's cotangent is offered — the
    #: looked-up ids inside the table —, those of them that reach the table
    #: sorted, by a merge sweep: all or none, :func:`sweeps`, and those of
    #: them that are handed over instead of becoming a cotangent).
    table_grad: list = dataclasses.field(default_factory=list)
    #: The tables (the very arrays the opener's params hold) whose lookups
    #: hand their update rows over, and one zero carrier each, by position
    #: (None: the counting trace, which only records ``handed``).
    hand_over: tuple = ()
    carriers: Optional[tuple] = None
    #: One ``(position in hand_over, x)`` a lookup of such a table: ``x`` is
    #: the carrier's ShapeDtypeStruct in the counting trace, and the int32
    #: physical row [N] of each update row (``P`` = none) once carriers are
    #: given; the rows themselves are the carrier's cotangent.
    handed: list = dataclasses.field(default_factory=list)


class _Taps(threading.local):
    open: Optional[LookupTaps] = None


_TAPS = _Taps()


@contextlib.contextmanager
def route_taps(hand_over=(), carriers=None):
    """Trace-time tap on the lookups: while open (on this thread), every
    ``embedding_lookup`` traced appends to the yielded :class:`LookupTaps` —
    how the train step gets the route's load balance, the table gradient's
    path and (``hand_over``, ``carriers``) a table's update rows without
    the model's apply returning them."""
    taps = LookupTaps(hand_over=tuple(hand_over), carriers=carriers)
    prev, _TAPS.open = _TAPS.open, taps
    try:
        yield taps
    finally:
        _TAPS.open = prev


@dataclasses.dataclass(frozen=True)
class _Hand:
    """A handed-over table's slot in the open taps."""

    taps: LookupTaps
    slot: int

    @property
    def fusing(self) -> bool:
        return self.taps.carriers is not None

    def carrier(self, rows: int, table: jax.Array):
        """The zero [rows, W] array whose cotangent the gathered rows'
        becomes; None in the counting trace, which notes its shape."""
        if self.fusing:
            return self.taps.carriers[self.slot]
        shape = jax.ShapeDtypeStruct((rows, table.shape[1]), table.dtype)
        self.taps.handed.append((self.slot, shape))
        return None

    def deliver(self, physical_ids: jax.Array) -> None:
        self.taps.handed.append((self.slot, physical_ids))


def _hand_of(table: jax.Array) -> Optional[_Hand]:
    taps = _TAPS.open
    if taps is not None:
        for slot, handed in enumerate(taps.hand_over):
            if handed is table:
                return _Hand(taps, slot)
    return None


def _rows_in_range(table: jax.Array, ids: jax.Array, dim: int) -> jax.Array:
    inside = (ids >= 0) & (ids < logical_rows(table, dim))
    return jnp.sum(inside, dtype=jnp.int32)


def _tap_table_grad(table: jax.Array, rows: jax.Array, hand: Optional[_Hand] = None) -> None:
    if _TAPS.open is not None:
        none = jnp.zeros_like(rows)
        _TAPS.open.table_grad.append((
            rows,
            rows if sweeps(table) else none,
            rows if hand is not None and hand.fusing else none,
        ))


def _tap_lookup(table: jax.Array, ids: jax.Array, dim: int, hand: Optional[_Hand] = None) -> None:
    """Tap a plain ``gather_rows(table, ids, dim)``; counts only while a
    tap is open."""
    if _TAPS.open is not None:
        _tap_table_grad(table, _rows_in_range(table, ids, dim), hand)


def resolve_impl(
    impl: str, platform: Optional[str] = None, axis_size: Optional[int] = None
) -> str:
    """Resolve ``auto`` to a concrete impl for (platform, mesh size).

    A 1-device axis means dense (whose n=1 path is a plain local gather) —
    paying the ragged route's sort/bincount/collective machinery with zero
    peers to shard over was a measured 28% step tax in round 2.  XLA:CPU has
    no ragged-all-to-all HLO, so auto means dense there too; multi-chip TPU
    means the ragged route.  Explicit impls pass through untouched.
    """
    if impl not in LOOKUP_IMPLS:
        raise ValueError(f"unknown embedding lookup impl {impl!r}")
    if impl != IMPL_AUTO:
        return impl
    if axis_size == 1:
        return IMPL_DENSE
    platform = platform or jax.default_backend()
    return IMPL_RAGGED if platform == "tpu" else IMPL_DENSE


# ---------------------------------------------------------------------------
# dense route: all_gather ids -> masked local gather -> psum_scatter vectors
# ---------------------------------------------------------------------------


def _dense_lookup(local_table: jax.Array, ids: jax.Array, axis_name: str, dim: int,
                  hand: Optional[_Hand] = None):
    # Trace-time import: a module-level one closes the ops -> parallel ->
    # ops cycle (parallel/__init__ pulls the trainer, which needs this
    # module mid-initialization) whenever ops is imported first.
    from elasticdl_tpu.parallel import collectives

    n = axis_size(axis_name)
    my_shard = lax.axis_index(axis_name)
    rows_local = logical_rows(local_table, dim)

    ids_shape = ids.shape
    flat_ids = ids.reshape(-1)
    bad = (flat_ids < 0) | (flat_ids >= n * rows_local)
    if n == 1:
        _tap_lookup(local_table, flat_ids, dim, hand)
        out = gather_rows(local_table, flat_ids, dim, hand)  # NaN-fills OOB itself
        return out.reshape(ids_shape + (dim,))

    # [n * local_ids] — every device's flat id list.
    all_ids = lax.all_gather(flat_ids, axis_name).reshape(-1)

    owner = all_ids // rows_local
    local_row = all_ids - owner * rows_local
    mine = owner == my_shard
    safe_row = jnp.where(mine, local_row, 0)
    # Every gathered id is offered as an update row: the others' as zeros.
    _tap_table_grad(local_table, jnp.int32(all_ids.shape[0]), hand)
    vectors = jnp.where(mine[:, None], gather_rows(local_table, safe_row, dim, hand), 0)

    # Route each device its own block, summing over shards (one nonzero each).
    vectors = vectors.reshape(n, -1, dim)
    out = collectives.psum_scatter(
        vectors, axis_name, scatter_dimension=0, tiled=False
    )
    # Fail-loud OOV: an id owned by NO shard summed to zeros above; surface
    # it as NaN to match gather_rows' single-device contract.
    out = jnp.where(bad[:, None], jnp.nan, out)
    return out.reshape(ids_shape + (dim,))


# ---------------------------------------------------------------------------
# ragged route: sort by owner -> all_gather ids, mask -> local gather ->
# ragged all-to-all vectors back -> unsort        (custom_vjp: retrace route)
# ---------------------------------------------------------------------------


def _ragged_collective(operand, output, in_off, send, out_off, recv, axis_name,
                       emulate: bool):
    """``lax.ragged_all_to_all`` or a semantically-identical dense emulation.

    The route's two float legs (vectors back, cotangents out).  The
    emulation exists because XLA:CPU lacks the ragged-all-to-all HLO: it
    all_gathers every device's operand and offset metadata, then each device
    assembles its output buffer position-by-position from the senders' chunks
    — exactly the op's documented placement semantics (chunk ``j`` of device
    ``k``'s operand, ``[in_off[j], +send[j])``, lands in device ``j``'s output
    at ``[out_off[j], +send[j])``).  O(n·len(output)) masks — test-only.
    """
    if not emulate:
        return lax.ragged_all_to_all(
            operand, output,
            in_off.astype(jnp.int32), send.astype(jnp.int32),
            out_off.astype(jnp.int32), recv.astype(jnp.int32),
            axis_name=axis_name,
        )
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    ops = lax.all_gather(operand, axis_name)          # [n, L, ...]
    IN = lax.all_gather(in_off, axis_name)            # [n, n] sender-major
    SE = lax.all_gather(send, axis_name)              # [n, n]
    OUT = lax.all_gather(out_off, axis_name)          # [n, n]
    L_out = output.shape[0]
    pos = jnp.arange(L_out)
    # For sender k, its chunk to me sits at my [OUT[k,me], +SE[k,me]).
    start = OUT[:, me][:, None]                       # [n, 1]
    size = SE[:, me][:, None]
    src0 = IN[:, me][:, None]
    inside = (pos[None, :] >= start) & (pos[None, :] < start + size)  # [n, L_out]
    k_of = jnp.argmax(inside, axis=0)                 # sender for each position
    valid = jnp.any(inside, axis=0)
    src = src0[k_of, 0] + pos - start[k_of, 0]
    flat_src = k_of * ops.shape[1] + jnp.clip(src, 0, ops.shape[1] - 1)
    picked = ops.reshape((-1,) + ops.shape[2:])[flat_src]
    mask = valid.reshape((-1,) + (1,) * (output.ndim - 1))
    return jnp.where(mask, picked, output)


def _routing_plan(ids: jax.Array, axis_name: str, rows_local: int):
    """Per-device routing metadata for the ragged route.

    Returns (perm, sorted_ids, send_sizes, in_off, recv_sizes, chunk_off).
    ``S[k, j]`` (how many ids device k sends to shard j) is shared via one
    tiny [n, n] int32 all_gather, and every offset, both directions, is a
    row or a column of its exclusive cumsum along j: ``in_off[j]`` is where
    my ids for shard j start in MY sorted list, ``chunk_off[k]`` where
    sender k's ids for ME start in ITS sorted list (k's ``in_off`` for me).
    Nothing is packed on the owner's side: a chunk keeps, in the owner's
    [n, L] slots, the place it has in its sender's list, so these two are
    all the plan there is.
    """
    n = axis_size(axis_name)
    me = lax.axis_index(axis_name)
    # Junk ids get a clamped owner; their original value then misses that
    # owner's row range and NaN-fills (fail-loud OOV, see module docstring).
    owner = jnp.clip(ids // rows_local, 0, n - 1)
    perm = jnp.argsort(owner)
    sorted_ids = ids[perm]
    send_sizes = jnp.bincount(owner, length=n).astype(jnp.int32)
    in_off = _exclusive_cumsum(send_sizes)
    S = lax.all_gather(send_sizes, axis_name)          # [n, n]
    recv_sizes = S[:, me]
    before_me = (jnp.arange(n) < me)[None, :]
    chunk_off = jnp.sum(jnp.where(before_me, S, 0), axis=1).astype(jnp.int32)
    return perm, sorted_ids, send_sizes, in_off, recv_sizes, chunk_off


def _exclusive_cumsum(x: jax.Array) -> jax.Array:
    return jnp.concatenate(
        [jnp.zeros((1,), x.dtype), jnp.cumsum(x)[:-1].astype(x.dtype)]
    )


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _ragged_lookup(local_table, ids, carrier, axis_name: str, dim: int, emulate: bool):
    """(vectors ``ids.shape + (dim,)``, rows this shard received, those of
    them inside its row range: int32, the physical row [n * L] of each row
    it gathered).  ``carrier``: None, or the zero [n * L, W] array that
    takes the gathered rows' cotangent in the table's place."""
    out, _ = _ragged_lookup_fwd(local_table, ids, carrier, axis_name, dim, emulate)
    return out


# The route's parts carry ``jax.named_scope``s (forward ``route_plan``,
# ``route_ids``, ``route_gather``, ``route_vectors``, ``route_unsort``;
# backward ``route_bwd_sort``, ``route_bwd_vectors``, ``route_bwd_scatter``)
# so a device trace can put each op's time down to its part.
def _ragged_lookup_fwd(local_table, ids, carrier, axis_name: str, dim: int, emulate: bool):
    n = axis_size(axis_name)
    rows_local = logical_rows(local_table, dim)
    ids_shape = ids.shape
    flat_ids = ids.reshape(-1)
    L = flat_ids.shape[0]

    with jax.named_scope("route_plan"):
        perm, sorted_ids, send, in_off, recv, chunk_off = _routing_plan(
            flat_ids, axis_name, rows_local
        )
    # ids -> owners: every chip's sorted list, whole, by one all_gather
    # (why not a ragged leg: module docstring).  Sender k's chunk for me
    # stays where it lands, [chunk_off[k], + recv[k]) of row k; the rest of
    # the n * L slots (worst-case skew: every shard's batch hits my rows)
    # belongs to other owners and reads -1 = OOB = a NaN row if ever read.
    with jax.named_scope("route_ids"):
        all_sorted = lax.all_gather(sorted_ids, axis_name)          # [n, L]
        slot = lax.broadcasted_iota(jnp.int32, (n, L), 1)
        mine = (slot >= chunk_off[:, None]) & (slot < (chunk_off + recv)[:, None])
        recv_ids = jnp.where(mine, all_sorted, -1).reshape(-1)
    with jax.named_scope("route_gather"):
        local_rows = recv_ids - lax.axis_index(axis_name) * rows_local
        vecs = gather_rows(local_table, local_rows, dim)   # [n*L, dim], NaN on OOB
        rows_in_range = _rows_in_range(local_table, local_rows, dim)
        physical, lane = _physical_rows(
            local_table.shape[0], local_rows,
            _pack_geometry(local_table.shape[1], dim)[0],
        )

    # vectors -> requesters: requester j's rows sit in row j of my slots
    # where its ids landed, and go back to where j's sorted block for me
    # starts, which is the same chunk_off[j].
    with jax.named_scope("route_vectors"):
        vec_buf = jnp.zeros((L, dim), vecs.dtype)
        sorted_out = _ragged_collective(
            vecs, vec_buf, jnp.arange(n) * L + chunk_off, recv, chunk_off, send,
            axis_name, emulate,
        )
    with jax.named_scope("route_unsort"):
        inv = jnp.zeros_like(perm).at[perm].set(jnp.arange(L))
        out = sorted_out[inv].reshape(ids_shape + (dim,))
    residuals = (perm, send, in_off, recv, local_rows, local_table.shape,
                 ids_shape, None if carrier is None else (lane,))
    return (out, jnp.sum(recv), rows_in_range, physical.astype(jnp.int32)), residuals


def _ragged_lookup_bwd(axis_name: str, dim: int, emulate: bool, residuals, g):
    (perm, send, in_off, recv, local_rows, table_shape_, ids_shape,
     handed) = residuals
    g = g[0]  # the row counts and the row numbers are integers: no cotangent
    n = axis_size(axis_name)
    L = perm.shape[0]
    # Cotangents go the ids' way (requester -> owner): sorted by owner, each
    # chunk into row ``me`` of its owner's slots at the place it has in my
    # sorted list, which is where its ids sit there; then whole-physical-row
    # scatter-add into the local shard.  The slots between chunks stay zero
    # and hold local_rows < 0 (OOB), so the fill-mode transpose drops them —
    # as it drops junk-id cotangents.
    with jax.named_scope("route_bwd_sort"):
        g_sorted = g.reshape(L, dim)[perm]
    with jax.named_scope("route_bwd_vectors"):
        g_buf = jnp.zeros((n * L, dim), g_sorted.dtype)
        g_at_owner = _ragged_collective(
            g_sorted, g_buf, in_off, send,
            lax.axis_index(axis_name) * L + in_off, recv, axis_name, emulate,
        )
    ids_bar = np.zeros(ids_shape, jax.dtypes.float0)
    with jax.named_scope("route_bwd_scatter"):
        if handed is not None:
            # Handed over: the lane select's transpose only; whole physical
            # rows leave as the carrier's cotangent.
            (lane,) = handed
            pack, stride = _pack_geometry(table_shape_[1], dim)
            _, pull = jax.vjp(
                lambda rows: _select_lanes(rows, lane, pack, stride, dim),
                jnp.zeros((n * L, table_shape_[1]), g_at_owner.dtype),
            )
            (rows_bar,) = pull(g_at_owner)
            return None, ids_bar, rows_bar
        zeros = jnp.zeros(table_shape_, g_at_owner.dtype)
        _, pull = jax.vjp(lambda t: gather_rows(t, local_rows, dim), zeros)
        (table_bar,) = pull(g_at_owner)
    return table_bar, ids_bar, None


_ragged_lookup.defvjp(_ragged_lookup_fwd, _ragged_lookup_bwd)
