"""One short-convolution chain of Kimi Delta Attention as a Pallas kernel pair:
``y = l2norm_a_head(silu(causal depthwise conv(t, taps)))`` (the norm where a
head width is given), forward and gradient, each ONE read of its operands and
ONE write of its results: the float32 pre-activation, its silu and the head's
statistics live and die in VMEM.  ``ops/short_conv.py`` owns what is around
them (the ``custom_vjp``, which path a call takes, the contract) and says what
the chain is; ``ops/ssm.causal_conv`` is the convolution in XLA.

Operands: ``t`` [B, L, C] (the operand's type), ``taps`` [K, C] float32.  A
grid step is one block of ``rows`` positions by ``cols`` channels (whole heads)
of one sequence.  The convolution reads the K - 1 positions BEFORE a block and
the gradient also the K - 1 AFTER it: each kernel takes the ``HALO`` rows on
that side as a second small block of the same array (zeros before a
sequence's start and after its end: a row of the batch never sees its
neighbour), so the big block is read once.  Inside a step the block is upcast
once into a float32 scratch with its halo and worked through ``tile`` rows at
a time: a tap's operand is a load at a row offset (a number the compiler
knows), the head's sum of squares a lane reduction a row.

Forward, a tile:

    pre = sum_j taps[j] x[r - (K - 1) + j] ;  s = pre sigmoid(pre)
    y = s rsqrt(sum_head s^2 + eps)            (or y = s)

Gradient, from ``t`` and the cotangent ``g`` of ``y``: the same again for the
block's rows AND the K - 1 after it, then

    ds = rs (g - y sum_head(g y))              (or ds = g);  rs = rsqrt(sum_head s^2 + eps)
    dpre = ds sig (1 + pre (1 - sig))          -> a float32 scratch
    dtaps[j] = sum_r dpre[r] x[r - (K - 1) + j]   over the block's own rows: a partial sum a block
    dt[r] = sum_j taps[j] dpre[r + (K - 1) - j]

Precision is the chain's: float32 multiply-adds, silu, statistics and every
gradient factor; ONE downcast of ``y`` and of ``dt`` to the operand's type;
the taps' partial sums float32 (``ops/short_conv.py`` adds them).

Contract (``ops/short_conv.outside_conv_contract``): C whole multiples of 128
and of the head width, the head width whole multiples of 128, L whole
multiples of ``HALO``, K - 1 <= ``HALO``.

The SECOND chain (``gated`` / ``gated_grads``: ``ops/short_conv.gated_conv``)
is a double-gated convolution over THREE operands a position, ``y = C * conv(B
* z, taps)``, on the same blocks, halo and launch: the product ``p = B z`` is
made in float32 straight into the scratch (the rows before the block from the
two operands' halos), the convolution is ``_pre_of``'s, and there is no
activation.  Its gradient, from the operands and ``g``:

    c = conv(p) ;  dC = g c ;  dc = g C                   dc also for the K - 1 rows AFTER the block (the halos of g and C)
    dtaps[j] = sum_r dc[r] p[r - (K - 1) + j]             the block's own rows: a partial sum a block
    dp[r] = sum_j taps[j] dc[r + (K - 1) - j] ;  dB = dp z ;  dz = dp B

four reads (``B``, ``C``, ``z``, ``g``) and three writes of [B, L, C] arrays.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: rows of a block's neighbour a kernel reads beside it: one bfloat16 tile of sublanes
HALO = 16
#: positions and channels a grid step, positions a pass through the registers (the chip's sweep: PERF.md section 6, PR 53)
_ROWS, _COLS, _TILE = 512, 512, 128
_VMEM_LIMIT = 32 * 2**20
_F32 = jnp.float32


def _block(length: int, channels: int, unit: int):
    """``(rows, cols)`` of a grid step: the most rows up to ``_ROWS`` (whole
    halos) that divide ``length``, the most whole ``unit``s of channels up to
    ``_COLS`` (one unit where it is wider) that divide ``channels``."""
    rows = next(r for r in range(min(_ROWS, length), 0, -HALO) if length % r == 0)
    cols = next(c for c in range(max(_COLS // unit, 1) * unit, 0, -unit) if channels % c == 0)
    return rows, cols


def _tiles(rows: int, tile: int):
    """``(start, size)`` of the passes over a block's ``rows``."""
    return [(r, min(tile, rows - r)) for r in range(0, rows, tile)]


def _pre_of(ext, w_ref, at: int, size: int):
    """``(the K shifted operands, the pre-activation)`` [size, cols] float32 of
    the rows that start at row ``at`` of the block (``ext`` holds the block
    from row ``HALO`` on)."""
    taps = w_ref.shape[0]
    xs = [ext[pl.ds(HALO + at - (taps - 1) + j, size), :] for j in range(taps)]
    pre = xs[0] * w_ref[0:1, :]
    for j in range(1, taps):
        pre += xs[j] * w_ref[j:j + 1, :]
    return xs, pre


def _heads(cols: int, head_dim: Optional[int]):
    return [slice(h, h + head_dim) for h in range(0, cols, head_dim)] if head_dim else [slice(0, cols)]


def _chain_kernel(before_ref, t_ref, w_ref, y_ref, ext, *, head_dim: Optional[int], eps: float, tile: int):
    rows, cols = t_ref.shape
    ext[0:HALO, :] = jnp.where(pl.program_id(1) > 0, before_ref[...].astype(_F32), 0.0)
    ext[HALO:HALO + rows, :] = t_ref[...].astype(_F32)
    for at, size in _tiles(rows, tile):
        _, pre = _pre_of(ext, w_ref, at, size)
        s = pre * jax.nn.sigmoid(pre)
        for head in _heads(cols, head_dim):
            y = s[:, head]
            if head_dim:
                y = y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + eps)
            y_ref[at:at + size, head] = y.astype(y_ref.dtype)


def _grads_kernel(before_ref, t_ref, after_ref, g_ref, g_after_ref, w_ref, dt_ref, dw_ref, ext, dpre,
                  *, head_dim: Optional[int], eps: float, tile: int):
    rows, cols = t_ref.shape
    taps = w_ref.shape[0]
    inside = pl.program_id(1) < pl.num_programs(1) - 1  # a block after this one: the sequence goes on
    ext[0:HALO, :] = jnp.where(pl.program_id(1) > 0, before_ref[...].astype(_F32), 0.0)
    ext[HALO:HALO + rows, :] = t_ref[...].astype(_F32)
    ext[HALO + rows:, :] = jnp.where(inside, after_ref[...].astype(_F32), 0.0)
    of_taps = [jnp.zeros((8, cols), _F32) for _ in range(taps)]
    # the block's rows and the K - 1 after them (a whole halo: its other rows cost nothing to speak of)
    for at, size in _tiles(rows, tile) + [(rows, HALO)]:
        own = at < rows
        g = (g_ref[at:at + size, :] if own else jnp.where(inside, g_after_ref[...], 0.0)).astype(_F32)
        xs, pre = _pre_of(ext, w_ref, at, size)
        sig = jax.nn.sigmoid(pre)
        s = pre * sig
        slope = sig * (1.0 + pre * (1.0 - sig))
        for head in _heads(cols, head_dim):
            ds = g[:, head]
            if head_dim:
                sh = s[:, head]
                rs = lax.rsqrt(jnp.sum(sh * sh, axis=-1, keepdims=True) + eps)
                y = sh * rs
                ds = rs * (ds - y * jnp.sum(ds * y, axis=-1, keepdims=True))
            dpre[at:at + size, head] = ds * slope[:, head]
        if own:
            d = dpre[at:at + size, :]
            for j in range(taps):  # sublane groups added vreg on vreg; the last eight rows become one below
                of_taps[j] += jnp.sum((d * xs[j]).reshape(size // 8, 8, cols), axis=0)
    for j in range(taps):
        dw_ref[j:j + 1, :] = jnp.sum(of_taps[j], axis=0, keepdims=True)
    for at, size in _tiles(rows, tile):
        dt = dpre[pl.ds(at + taps - 1, size), :] * w_ref[0:1, :]
        for j in range(1, taps):
            dt += dpre[pl.ds(at + taps - 1 - j, size), :] * w_ref[j:j + 1, :]
        dt_ref[at:at + size, :] = dt.astype(dt_ref.dtype)


def _specs(t, rows: int, cols: int):
    """The BlockSpecs of one ``t``-shaped array: ``(the block, the HALO rows
    before it, the HALO rows after it)`` — the neighbours clamped into the
    sequence (the kernels put zeros where there is none)."""
    per, last = rows // HALO, t.shape[1] // HALO - 1
    return (pl.BlockSpec((None, rows, cols), lambda b, i, j: (b, i, j)),
            pl.BlockSpec((None, HALO, cols), lambda b, i, j: (b, jnp.maximum(i * per - 1, 0), j)),
            pl.BlockSpec((None, HALO, cols), lambda b, i, j: (b, jnp.minimum((i + 1) * per, last), j)))


def _launch(kernel, name: str, block, taps, in_specs, operands, out_specs, out_shape, scratch, ops_an_element: int, interpret: bool):
    t = operands[0]
    bsz, length, channels = t.shape
    rows, cols = block
    compiled = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        kernel, name=name, grid=(bsz, length // rows, channels // cols),
        in_specs=[*in_specs, pl.BlockSpec(taps.shape[:1] + (cols,), lambda b, i, j: (0, j))],
        out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=ops_an_element * t.size, transcendentals=2 * t.size,
            bytes_accessed=sum(x.size * x.dtype.itemsize for x in (*operands, *jax.tree.leaves(out_shape)))),
        **compiled,
    )(*operands, taps.astype(_F32))


def chain(t, taps, *, head_dim: Optional[int], eps: float, interpret: bool):
    """``y`` [B, L, C] in ``t``'s type from ``t`` [B, L, C] and ``taps`` [K, C]
    (module docstring)."""
    rows, cols = _block(t.shape[1], t.shape[2], head_dim or LANES)
    block, before, _ = _specs(t, rows, cols)
    kernel = functools.partial(_chain_kernel, head_dim=head_dim, eps=eps, tile=min(_TILE, rows))
    return _launch(kernel, "kda_conv_chain", (rows, cols), taps, [before, block], (t, t), block, jax.ShapeDtypeStruct(t.shape, t.dtype),
                   [pltpu.VMEM((HALO + rows, cols), _F32)], 24, interpret)


def chain_grads(t, taps, g, *, head_dim: Optional[int], eps: float, interpret: bool):
    """``chain`` transposed: ``(dt [B, L, C] in t's type, the taps' gradient a
    block of rows [B, L / rows, K, C] float32)`` from the operands and the
    cotangent ``g`` [B, L, C] of ``y``."""
    bsz, length, channels = t.shape
    rows, cols = _block(length, channels, head_dim or LANES)
    block, before, after = _specs(t, rows, cols)
    kernel = functools.partial(_grads_kernel, head_dim=head_dim, eps=eps, tile=min(_TILE, rows))
    of_taps = jax.ShapeDtypeStruct((bsz, length // rows, taps.shape[0], channels), _F32)
    return _launch(kernel, "kda_conv_chain_grads", (rows, cols), taps, [before, block, after, block, after], (t, t, t, g, g),
                   [block, pl.BlockSpec((None, None, taps.shape[0], cols), lambda b, i, j: (b, i, 0, j))],
                   [jax.ShapeDtypeStruct(t.shape, t.dtype), of_taps],
                   [pltpu.VMEM((HALO + rows + HALO, cols), _F32), pltpu.VMEM((rows + HALO, cols), _F32)], 60, interpret)


def _fill_product(ext, b_before_ref, b_ref, z_before_ref, z_ref):
    """``p = b z`` in float32 into ``ext``: the HALO rows before the block (zeros at a sequence's start), then the block."""
    rows = b_ref.shape[0]
    ext[0:HALO, :] = jnp.where(pl.program_id(1) > 0, b_before_ref[...].astype(_F32) * z_before_ref[...].astype(_F32), 0.0)
    ext[HALO:HALO + rows, :] = b_ref[...].astype(_F32) * z_ref[...].astype(_F32)


def _gated_kernel(b_before_ref, b_ref, z_before_ref, z_ref, c_ref, w_ref, y_ref, ext, *, tile: int):
    rows, _ = b_ref.shape
    _fill_product(ext, b_before_ref, b_ref, z_before_ref, z_ref)
    for at, size in _tiles(rows, tile):
        _, conv = _pre_of(ext, w_ref, at, size)
        y_ref[at:at + size, :] = (c_ref[at:at + size, :].astype(_F32) * conv).astype(y_ref.dtype)


def _gated_grads_kernel(b_before_ref, b_ref, z_before_ref, z_ref, c_ref, c_after_ref, g_ref, g_after_ref, w_ref,
                        db_ref, dc_ref, dz_ref, dw_ref, ext, dconv, *, tile: int):
    rows, cols = b_ref.shape
    taps = w_ref.shape[0]
    inside = pl.program_id(1) < pl.num_programs(1) - 1  # a block after this one: the sequence goes on
    _fill_product(ext, b_before_ref, b_ref, z_before_ref, z_ref)
    # the convolution's cotangent on the K - 1 rows after the block (a whole halo), which the block's last rows fed
    dconv[rows:, :] = jnp.where(inside, g_after_ref[...].astype(_F32) * c_after_ref[...].astype(_F32), 0.0)
    of_taps = [jnp.zeros((8, cols), _F32) for _ in range(taps)]
    for at, size in _tiles(rows, tile):
        g = g_ref[at:at + size, :].astype(_F32)
        xs, conv = _pre_of(ext, w_ref, at, size)
        dc_ref[at:at + size, :] = (g * conv).astype(dc_ref.dtype)
        d = g * c_ref[at:at + size, :].astype(_F32)
        dconv[at:at + size, :] = d
        for j in range(taps):  # sublane groups added vreg on vreg; the last eight rows become one below
            of_taps[j] += jnp.sum((d * xs[j]).reshape(size // 8, 8, cols), axis=0)
    for j in range(taps):
        dw_ref[j:j + 1, :] = jnp.sum(of_taps[j], axis=0, keepdims=True)
    for at, size in _tiles(rows, tile):
        dp = dconv[pl.ds(at + taps - 1, size), :] * w_ref[0:1, :]
        for j in range(1, taps):
            dp += dconv[pl.ds(at + taps - 1 - j, size), :] * w_ref[j:j + 1, :]
        db_ref[at:at + size, :] = (dp * z_ref[at:at + size, :].astype(_F32)).astype(db_ref.dtype)
        dz_ref[at:at + size, :] = (dp * b_ref[at:at + size, :].astype(_F32)).astype(dz_ref.dtype)


def gated(b, c, z, taps, *, interpret: bool):
    """``y = c * conv(b * z, taps)`` [B, L, C] in ``b``'s type from three
    operands [B, L, C] and ``taps`` [K, C] (module docstring, the second chain)."""
    rows, cols = _block(b.shape[1], b.shape[2], LANES)
    block, before, _ = _specs(b, rows, cols)
    kernel = functools.partial(_gated_kernel, tile=min(_TILE, rows))
    return _launch(kernel, "gated_conv_chain", (rows, cols), taps, [before, block, before, block, block], (b, b, z, z, c), block,
                   jax.ShapeDtypeStruct(b.shape, b.dtype), [pltpu.VMEM((HALO + rows, cols), _F32)], 4 + 2 * taps.shape[0], interpret)


def gated_grads(b, c, z, taps, g, *, interpret: bool):
    """``gated`` transposed: ``(db, dc, dz [B, L, C] in the operands' type, the
    taps' gradient a block of rows [B, L / rows, K, C] float32)`` from the
    operands and the cotangent ``g`` [B, L, C] of ``y``."""
    bsz, length, channels = b.shape
    rows, cols = _block(length, channels, LANES)
    block, before, after = _specs(b, rows, cols)
    kernel = functools.partial(_gated_grads_kernel, tile=min(_TILE, rows))
    like = jax.ShapeDtypeStruct(b.shape, b.dtype)
    of_taps = jax.ShapeDtypeStruct((bsz, length // rows, taps.shape[0], channels), _F32)
    return _launch(kernel, "gated_conv_chain_grads", (rows, cols), taps, [before, block, before, block, block, after, block, after],
                   (b, b, z, z, c, c, g, g),
                   [block, block, block, pl.BlockSpec((None, None, taps.shape[0], cols), lambda b, i, j: (b, i, 0, j))],
                   [like, like, like, of_taps],
                   [pltpu.VMEM((HALO + rows, cols), _F32), pltpu.VMEM((rows + HALO, cols), _F32)], 12 + 6 * taps.shape[0], interpret)
