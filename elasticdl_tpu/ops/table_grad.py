"""The dense table gradient of a row gather, built by one sorted merge
sweep instead of XLA's scatter-add — into a buffer (:func:`merge_sweep`), or,
where the table's rule is plain dense Adam, straight into the table and its
moments (:func:`merge_sweep_adam`), the buffer never written.

``zeros([P, W]).at[ids].add(rows)`` is, on a TPU, a serial read-modify-write
of HBM per update row, and each one pays a whole random access into the
buffer: 74 ns a row into 1.31 GB (PERF.md, section 5) where the gather of
the same rows costs 8.  The buffer has to be written once anyway (today:
the zeroing).  Sorting the update rows by destination lets that one
sequential write deliver them on its way:

1. XLA: sort the ids (carrying their positions), permute the update rows
   into that order, and find where each tile of ``TILE`` buffer rows starts
   in the sorted list (``searchsorted``).  None of it depends on ``P``.
2. One Pallas kernel over the ``P / TILE`` tiles.  Each grid step builds
   its tile in VMEM from the (contiguous, because sorted) slice of update
   rows that belongs to it, and the pipeline writes the tile out: every
   buffer row is written exactly once, sequentially, and no HBM row is
   read back.  The adds run on the MXU as ``onehot[TILE, CHUNK] @
   rows[CHUNK, W]`` with ``onehot = (tile's row numbers == chunk's ids)``,
   every factor a bfloat16 piece that is EXACT, as many products a chunk
   as what the kernel can see of its input needs (PR 68):

   * f32 rows are split into three bfloat16 pieces that add up to them
     exactly, so every product is exact, the sum of the pieces of ONE row
     is that row to the bit, and duplicates accumulate in f32 (DeepFM's
     table gradient; three products);
   * bfloat16 rows ARE their one exact piece: one product, chunk buffers
     of half the bytes, the tile summed in an f32 scratch and rounded once
     into a bfloat16 output (``ops/moe.py``: the transpose of a gather of
     token rows — a float32 copy of the rows would be cast, permuted and
     read at twice the bytes to feed two products of zeros);
   * bfloat16 rows with a float32 WEIGHT a row (``sum_j [id_j == r] w_j
     rows_j``, ``ops/moe.py``'s sum of the experts' results into their
     tokens): the weight is sorted with its id and travels as its bits, a
     second line under the chunk's ids; ITS three exact pieces stand where
     the one-hot's ones stood (``where(onehot, w_piece, 0) @ rows``), so
     every product is exact and the sum is f32 — nothing is rounded that
     ``f32(rows) * w`` would not round, and no f32 array of the rows'
     shape exists (step 0, PERF.md, PR 68: forming ``f32(rows) * w`` on
     the chunk in VMEM and splitting it costs the VPU some twenty passes
     over [CHUNK, W] where this costs six over [TILE, CHUNK]).  F32 rows
     with a weight take it in XLA ahead of the sort (their three pieces
     times the weight's three would be nine products).

The slice of a tile starts anywhere in the sorted list, so it is read in
whole chunks of ``CHUNK`` rows from the chunk that holds its first row on:
rows of a neighbouring tile that come along match no row number of this
tile and add nothing.  The first two chunks are ordinary pipelined block
inputs (their block index is read from the prefetched offsets, and a block
whose index did not move is not fetched again); a tile with more update
rows than they cover — hot ids — reads the rest in a double-buffered loop
of its own.

One difference from the scatter-add: a non-finite update row reaches every
row of its tile (``0 * nan`` in the matmul), not only its own.  A step
that produces one is lost either way.

**The update in the same pass** (PR 29).  The dense Adam update that follows
reads that buffer back and sweeps p, m and v: with the buffer's write that
is eight table-sized passes over HBM a step, at the roofline of its bytes.
The kernel already holds every gradient tile in VMEM, in row order, exactly
once — so :func:`merge_sweep_adam` also takes the tile's p, m and v as
pipelined blocks, applies ``optax.adam``'s arithmetic to EVERY row of it on
the VPU, in f32 and in optax's order of operations (a row with g = 0 and
live moments still moves; a tile with no update row is swept like any
other: skipping it would be a different optimizer), and stores the three
back through ``input_output_aliases``, in place.  Six passes, one kernel,
no ``[P, W]`` temporary: 12.3 ms against 17.0 for the pair at 2.56 M rows
on a v5e, 24.1 against 32.8 at 5.11 M (PERF.md, PR 29, step 0).  The merge
is the same code (:func:`_merge_tile`), so m and v come out equal to the
pair's to the bit; p to the last bit or two (Mosaic's divide and square
root against XLA's fusion).  Who may use it is the trainer's decision
(``parallel/trainer.py``: the declared plain Adam, one lookup of the table
a step, no other replica adding to its gradient); :func:`merge_sweep`
serves every other rule, and a table under ``SWEEP_MIN_ROWS`` keeps the
AD transpose.
"""

from __future__ import annotations

from functools import partial, reduce
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Buffer rows a grid step builds, and update rows a chunk holds (PERF.md,
# PR 27, step 0: the sizes measured on a v5e).
TILE = 1024
CHUNK = 128


def _use_interpret() -> bool:
    # Off the TPU the kernel runs in the Pallas interpreter, as
    # ops/flash_attention.py's do: slow, exact, one code path under test.
    return jax.default_backend() != "tpu"


def _exact_bf16_pieces(rows):
    """Three bfloat16 arrays whose f32 sum is ``rows`` (f32) exactly: 24
    bits of mantissa, 8 at a time."""
    hi = rows.astype(jnp.bfloat16)
    rest = rows - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _merge_tile(offs, ids_a, rows_a, ids_b, rows_b, ids_any, rows_any,
                acc, ids_buf, rows_buf, sems, *, tile: int, chunk: int):
    """``acc[...]`` (a [tile, W] f32 block in VMEM) = the update rows of
    grid step ``program_id(0)``'s tile, added up at its row numbers.  The
    whole kernel of :func:`merge_sweep` (``acc`` its output block) and the
    first half of :func:`merge_sweep_adam`'s."""
    t = pl.program_id(0)
    first, end = offs[t], offs[t + 1]
    k0 = first // chunk
    row_numbers = t * tile + lax.broadcasted_iota(jnp.int32, (tile, chunk), 0)

    def merged(ids, rows):
        """[tile, W]: the chunk's rows added up at this tile's row numbers,
        as one-hot products whose every factor is an exact bfloat16 piece."""
        weighted = ids.shape[0] == 2  # the weights' bits ride under the ids
        hit = row_numbers == (ids[:1] if weighted else ids)
        if weighted:
            # the weight's three pieces stand where the one-hot's ones stood
            weight = lax.bitcast_convert_type(ids[1:], jnp.float32)
            lhs = [jnp.where(hit, piece.astype(jnp.float32), 0.0).astype(jnp.bfloat16)
                   for piece in _exact_bf16_pieces(weight)]
            rhs = [rows] * 3
        elif rows.dtype == jnp.bfloat16:
            lhs, rhs = [hit.astype(jnp.bfloat16)], [rows]  # its own one piece
        else:
            lhs, rhs = [hit.astype(jnp.bfloat16)] * 3, _exact_bf16_pieces(rows)
        return reduce(jnp.add, [
            jnp.dot(one_hot, piece, preferred_element_type=jnp.float32)
            for one_hot, piece in zip(lhs, rhs)
        ])

    acc[...] = merged(ids_a[...], rows_a[...])

    @pl.when(end > (k0 + 1) * chunk)
    def _():
        acc[...] += merged(ids_b[...], rows_b[...])

    extra = jnp.maximum(0, (end - (k0 + 1) * chunk - 1) // chunk)

    def copies(slot, k):
        return (
            pltpu.make_async_copy(ids_any.at[k], ids_buf.at[slot], sems.at[0, slot]),
            pltpu.make_async_copy(
                rows_any.at[pl.ds(pl.multiple_of(k * chunk, chunk), chunk)],
                rows_buf.at[slot], sems.at[1, slot],
            ),
        )

    @pl.when(extra > 0)
    def _():
        for copy in copies(0, k0 + 2):
            copy.start()

        def body(i, carry):
            slot = i % 2

            @pl.when(i + 1 < extra)
            def _():
                for copy in copies(1 - slot, k0 + 3 + i):
                    copy.start()

            for copy in copies(slot, k0 + 2 + i):
                copy.wait()
            acc[...] += merged(ids_buf[slot], rows_buf[slot])
            return carry

        lax.fori_loop(0, extra, body, 0)


def _merge_tile_rounded(offs, *refs, tile: int, chunk: int):
    """:func:`_merge_tile` for a sum that leaves in a narrower dtype than it
    is made in (bfloat16 rows, no weight): built in a float32 scratch,
    rounded ONCE on its way into the output block."""
    *chunks, out, acc, ids_buf, rows_buf, sems = refs
    _merge_tile(offs, *chunks, acc, ids_buf, rows_buf, sems, tile=tile, chunk=chunk)
    out[...] = acc[...].astype(out.dtype)


def _apply_kernel(offs, bias, ids_a, rows_a, ids_b, rows_b, ids_any, rows_any,
                  p_in, m_in, v_in, p_out, m_out, v_out,
                  grad, ids_buf, rows_buf, sems, *, tile: int, chunk: int,
                  step_size: float, b1: float, b2: float, eps: float):
    _merge_tile(offs, ids_a, rows_a, ids_b, rows_b, ids_any, rows_any,
                grad, ids_buf, rows_buf, sems, tile=tile, chunk=chunk)
    # optax.adam's update of EVERY row of the tile, in optax's order of
    # operations (scale_by_adam, then scale(-learning_rate), then
    # apply_updates): a row with g = 0 and live moments still moves.
    g = grad[...]
    m = (1 - b1) * g + b1 * m_in[...]
    v = (1 - b2) * (g * g) + b2 * v_in[...]
    m_out[...] = m
    v_out[...] = v
    p_out[...] = p_in[...] + step_size * ((m / bias[0]) / (jnp.sqrt(v / bias[1]) + eps))


def sort_updates(ids: jax.Array, rows: jax.Array, num_rows: int,
                 weights: Optional[jax.Array] = None, *,
                 tile: int = TILE, chunk: int = CHUNK):
    """The XLA half: (offsets [tiles + 1], sorted ids [n_pad], rows in that
    order [n_pad, W]), ``n_pad`` a whole number of chunks (padded with the
    filler id ``num_rows``).  ``offsets[t]`` is where tile ``t`` starts in
    the sorted list; the last one counts the rows that are not dropped.
    ``weights`` (f32 [N]: the sum is of ``weights[j] * rows[j]``) go through
    the same sort and come back as their BITS under the ids, [2, n_pad] int32
    — one block and one copy a chunk in the kernel — where the rows are
    bfloat16; float32 rows take their weight here (their three pieces times
    the weight's three would be nine products a chunk)."""
    n, width = rows.shape
    if weights is not None and rows.dtype != jnp.bfloat16:
        rows, weights = rows * weights[:, None].astype(rows.dtype), None
    n_pad = -(-n // chunk) * chunk
    if n_pad > n:
        ids = jnp.concatenate([ids, jnp.full((n_pad - n,), num_rows, ids.dtype)])
        rows = jnp.concatenate([rows, jnp.zeros((n_pad - n, width), rows.dtype)])
    slots = lax.iota(jnp.int32, n_pad)
    if weights is None:
        sorted_ids, order = lax.sort_key_val(ids, slots)
        ids_out = sorted_ids
    else:
        bits = lax.bitcast_convert_type(jnp.pad(weights.astype(jnp.float32), (0, n_pad - n)), jnp.int32)
        sorted_ids, order, bits = lax.sort((ids, slots, bits), num_keys=1)
        ids_out = jnp.stack([sorted_ids, bits])
    tiles = -(-num_rows // tile)
    bounds = jnp.minimum(lax.iota(jnp.int32, tiles + 1) * tile, num_rows)
    offsets = jnp.searchsorted(sorted_ids, bounds).astype(jnp.int32)
    return offsets, ids_out, rows[order]


def _chunk_operands(sorted_ids, sorted_rows, chunk: int):
    """What both kernels read the sorted update rows through: (operands,
    their specs, the scratch of the hot tiles' loop).  The first two chunks
    of a tile are pipelined blocks, the rest stay in HBM."""
    n_pad, width = sorted_rows.shape
    chunks = n_pad // chunk
    if sorted_ids.ndim == 1:
        ids3 = sorted_ids.reshape(chunks, 1, chunk)
    else:  # [2, n_pad], the weights' bits under the ids: a chunk's two lines together
        ids3 = sorted_ids.reshape(2, chunks, chunk).swapaxes(0, 1)
    lines = ids3.shape[1]

    def block(step):
        """Specs of the (ids, rows) blocks ``step`` chunks after the one
        that holds the tile's first update row."""
        def at(t, offs):
            return jnp.minimum(offs[t] // chunk + step, chunks - 1)
        return (  # ``*_``: merge_sweep_adam prefetches a second scalar array
            pl.BlockSpec((None, lines, chunk), lambda t, offs, *_: (at(t, offs), 0, 0)),
            pl.BlockSpec((chunk, width), lambda t, offs, *_: (at(t, offs), 0)),
        )

    specs = [
        *block(0), *block(1),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, lines, chunk), jnp.int32),
        pltpu.VMEM((2, chunk, width), sorted_rows.dtype),
        pltpu.SemaphoreType.DMA((2, 2)),
    ]
    return (ids3, sorted_rows) * 3, specs, scratch


def merge_sweep(offsets: jax.Array, sorted_ids: jax.Array, sorted_rows: jax.Array,
                num_rows: int, *, tile: int = TILE, chunk: int = CHUNK,
                interpret: Optional[bool] = None) -> jax.Array:
    """The kernel half: the [num_rows, W] buffer from :func:`sort_updates`'
    three outputs (same ``tile`` and ``chunk``), in the rows' dtype, or in
    float32 where they came with weights."""
    if interpret is None:
        interpret = _use_interpret()
    width = sorted_rows.shape[1]
    operands, specs, scratch = _chunk_operands(sorted_ids, sorted_rows, chunk)
    out_dtype = sorted_rows.dtype if sorted_ids.ndim == 1 else jnp.float32
    if out_dtype == jnp.float32:
        kernel = _merge_tile  # acc = the output block
    else:
        kernel, scratch = _merge_tile_rounded, [pltpu.VMEM((tile, width), jnp.float32), *scratch]
    return pl.pallas_call(
        partial(kernel, tile=tile, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((num_rows, width), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-num_rows // tile),),
            in_specs=specs,
            out_specs=pl.BlockSpec((tile, width), lambda t, offs: (t, 0)),
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(offsets, *operands)


def merge_sweep_adam(
    offsets: jax.Array, sorted_ids: jax.Array, sorted_rows: jax.Array,
    table: jax.Array, mu: jax.Array, nu: jax.Array, bias: jax.Array, *,
    learning_rate: float, b1: float, b2: float, eps: float,
    tile: int = TILE, chunk: int = CHUNK, interpret: Optional[bool] = None,
):
    """:func:`merge_sweep` that keeps each gradient tile in VMEM and applies
    ``optax.adam``'s dense update to the same tile of ``table``, ``mu`` and
    ``nu`` (f32 [num_rows, W]) in place of writing it out: returns the three
    updated, aliased onto the three given.  ``bias`` is f32 [2], the step's
    two bias corrections ``1 - b**count`` (they change with the step, so
    they travel through SMEM; the rule's own numbers are compiled in)."""
    if interpret is None:
        interpret = _use_interpret()
    num_rows, width = table.shape
    operands, specs, scratch = _chunk_operands(sorted_ids, sorted_rows, chunk)
    tile_spec = pl.BlockSpec((tile, width), lambda t, *_: (t, 0))
    state = jax.ShapeDtypeStruct(table.shape, table.dtype)
    first = 2 + len(operands)  # p, m, v follow the two prefetched scalars
    return pl.pallas_call(
        partial(_apply_kernel, tile=tile, chunk=chunk,
                step_size=-learning_rate, b1=b1, b2=b2, eps=eps),
        out_shape=(state,) * 3,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-num_rows // tile),),
            in_specs=[*specs, tile_spec, tile_spec, tile_spec],
            out_specs=(tile_spec,) * 3,
            scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32), *scratch],
        ),
        input_output_aliases={first: 0, first + 1: 1, first + 2: 2},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(offsets, bias, *operands, table, mu, nu)


def sweep_table_grad(
    ids: jax.Array, rows: jax.Array, num_rows: int,
    weights: Optional[jax.Array] = None, *,
    tile: int = TILE, chunk: int = CHUNK, interpret: Optional[bool] = None,
) -> jax.Array:
    """``zeros([num_rows, W]).at[ids].add(rows, mode="drop")`` for float32
    or bfloat16 ``rows`` [N, W] and int32 ``ids`` [N] in ``[0, num_rows]``
    (``num_rows`` itself, the filler, is dropped).  Float32 rows: to the bit
    where ids are distinct, to f32 summation order where they repeat.
    Bfloat16 rows: summed in float32, rounded once to bfloat16.  With
    ``weights`` (f32 [N]) the sum is of ``weights[j] * rows[j]``, float32
    whatever the rows are: of bfloat16 rows every product is exact."""
    return merge_sweep(
        *sort_updates(ids, rows, num_rows, weights, tile=tile, chunk=chunk),
        num_rows, tile=tile, chunk=chunk, interpret=interpret,
    )


def sweep_adam(
    table: jax.Array, mu: jax.Array, nu: jax.Array, count: jax.Array,
    ids: jax.Array, rows: jax.Array, *,
    learning_rate: float, b1: float, b2: float, eps: float,
    tile: int = TILE, chunk: int = CHUNK, interpret: Optional[bool] = None,
):
    """One ``optax.adam(learning_rate, b1, b2, eps)`` step on ``table`` (f32
    [P, W]) whose dense gradient is ``zeros([P, W]).at[ids].add(rows,
    mode="drop")``, without that gradient ever being an array: ``(table,
    mu, nu)`` after the step.  ``count`` is the step's own count (optax's
    ``count`` AFTER its increment).  The sort runs under the scope
    ``table_grad``, the kernel under ``table_apply``."""
    with jax.named_scope("table_grad"):
        sorted_updates = sort_updates(ids, rows, table.shape[0], tile=tile, chunk=chunk)
    with jax.named_scope("table_apply"):
        # optax.tree.bias_correction's own expression, for the same bits.
        bias = jnp.stack([1 - b1**count, 1 - b2**count]).astype(table.dtype)
        return merge_sweep_adam(
            *sorted_updates, table, mu, nu, bias,
            learning_rate=learning_rate, b1=b1, b2=b2, eps=eps,
            tile=tile, chunk=chunk, interpret=interpret,
        )
