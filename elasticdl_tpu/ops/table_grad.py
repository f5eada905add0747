"""The dense table-gradient buffer of a row gather, built by one sorted
merge sweep instead of XLA's scatter-add.

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
   rows[CHUNK, W]`` with ``onehot = (tile's row numbers == chunk's ids)``;
   the f32 rows are split into three bfloat16 pieces that add up to them
   exactly, so every product is exact, the sum of the pieces of ONE row is
   that row to the bit, and duplicates accumulate in f32.

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
"""

from __future__ import annotations

from functools import partial
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


def _sweep_kernel(offs, ids_a, rows_a, ids_b, rows_b, ids_any, rows_any,
                  out, ids_buf, rows_buf, sems, *, tile: int, chunk: int):
    t = pl.program_id(0)
    first, end = offs[t], offs[t + 1]
    k0 = first // chunk
    row_numbers = t * tile + lax.broadcasted_iota(jnp.int32, (tile, chunk), 0)

    def merged(ids, rows):
        """[tile, W]: the chunk's rows added up at this tile's row numbers."""
        onehot = (row_numbers == ids).astype(jnp.bfloat16)
        hi, mid, lo = (
            jnp.dot(onehot, piece, preferred_element_type=jnp.float32)
            for piece in _exact_bf16_pieces(rows)
        )
        return (hi + mid) + lo

    out[...] = merged(ids_a[...], rows_a[...])

    @pl.when(end > (k0 + 1) * chunk)
    def _():
        out[...] += merged(ids_b[...], rows_b[...])

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
            out[...] += merged(ids_buf[slot], rows_buf[slot])
            return carry

        lax.fori_loop(0, extra, body, 0)


def sort_updates(ids: jax.Array, rows: jax.Array, num_rows: int, *,
                 tile: int = TILE, chunk: int = CHUNK):
    """The XLA half: (offsets [tiles + 1], sorted ids [n_pad], rows in that
    order [n_pad, W]), ``n_pad`` a whole number of chunks (padded with the
    filler id ``num_rows``).  ``offsets[t]`` is where tile ``t`` starts in
    the sorted list; the last one counts the rows that are not dropped."""
    n, width = rows.shape
    n_pad = -(-n // chunk) * chunk
    if n_pad > n:
        ids = jnp.concatenate([ids, jnp.full((n_pad - n,), num_rows, ids.dtype)])
        rows = jnp.concatenate([rows, jnp.zeros((n_pad - n, width), rows.dtype)])
    sorted_ids, order = lax.sort_key_val(ids, lax.iota(jnp.int32, n_pad))
    tiles = -(-num_rows // tile)
    bounds = jnp.minimum(lax.iota(jnp.int32, tiles + 1) * tile, num_rows)
    offsets = jnp.searchsorted(sorted_ids, bounds).astype(jnp.int32)
    return offsets, sorted_ids, rows[order]


def merge_sweep(offsets: jax.Array, sorted_ids: jax.Array, sorted_rows: jax.Array,
                num_rows: int, *, tile: int = TILE, chunk: int = CHUNK,
                interpret: Optional[bool] = None) -> jax.Array:
    """The kernel half: the [num_rows, W] buffer from :func:`sort_updates`'
    three outputs (same ``tile`` and ``chunk``)."""
    if interpret is None:
        interpret = _use_interpret()
    n_pad, width = sorted_rows.shape
    chunks = n_pad // chunk
    ids3 = sorted_ids.reshape(chunks, 1, chunk)

    def block(step):
        """Specs of the (ids, rows) blocks ``step`` chunks after the one
        that holds the tile's first update row."""
        def at(t, offs):
            return jnp.minimum(offs[t] // chunk + step, chunks - 1)
        return (
            pl.BlockSpec((None, 1, chunk), lambda t, offs: (at(t, offs), 0, 0)),
            pl.BlockSpec((chunk, width), lambda t, offs: (at(t, offs), 0)),
        )

    return pl.pallas_call(
        partial(_sweep_kernel, tile=tile, chunk=chunk),
        out_shape=jax.ShapeDtypeStruct((num_rows, width), sorted_rows.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(-(-num_rows // tile),),
            in_specs=[
                *block(0), *block(1),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tile, width), lambda t, offs: (t, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, 1, chunk), jnp.int32),
                pltpu.VMEM((2, chunk, width), sorted_rows.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
            ],
        ),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(offsets, ids3, sorted_rows, ids3, sorted_rows, ids3, sorted_rows)


def sweep_table_grad(
    ids: jax.Array, rows: jax.Array, num_rows: int, *,
    tile: int = TILE, chunk: int = CHUNK, interpret: Optional[bool] = None,
) -> jax.Array:
    """``zeros([num_rows, W]).at[ids].add(rows, mode="drop")`` for f32
    ``rows`` [N, W] and int32 ``ids`` [N] in ``[0, num_rows]`` (``num_rows``
    itself, the filler, is dropped): to the bit where ids are distinct, to
    f32 summation order where they repeat."""
    return merge_sweep(
        *sort_updates(ids, rows, num_rows, tile=tile, chunk=chunk),
        num_rows, tile=tile, chunk=chunk, interpret=interpret,
    )
