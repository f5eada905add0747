"""The SAME-SUB-BLOCK part of ``ops/delta_rule._masks_of`` as a Pallas kernel
pair: the ``[SUB, SUB, dk]`` decay factors ``exp(G_r - G_i)`` of a sub-block,
their products with ``k_i`` and the gradient's three factors live and die in
VMEM; HBM sees the operands, the two ``[C, C]`` masks and the three gradients
only.  ``ops/delta_rule.py`` owns everything around them (the summed
log-decays, the cross-sub-block products, the solve, the recurrence over the
chunks, the ``custom_vjp``, which path a call takes) and says what each
kernel computes.  The kernels CONSUME the sums ``cum``: they sum no decay,
carry no state and solve nothing.

Operands are the free views ``[N, dk]`` of ``[.., C, dk]`` (N = every (chunk,
head) pair's C positions one after the other; ``q``, ``k`` in the operands'
type, ``cum`` float32), the masks ``[N, C]`` float32: row p holds ``M[r, i]``
of its chunk in lane i, the block diagonal of ``[C, C]`` written directly
(zero off the sub-blocks and above the diagonal).

A grid step takes up to ``_ROWS`` positions and works through them 128 at a
time (two chunks of 64).  A group's three ``[128, dk]`` tiles are upcast and
TRANSPOSED once: channels in the sublanes, positions in the lanes.  Then the
pair (r, i) of a sub-block is addressed by its OFFSET ``d = r - i`` in 0 ..
SUB - 1, not by its column: position p's partner is a lane ROTATION by d
(one XLU op a vreg, whatever p), the pairs that would leave the sub-block are
exactly the lanes with ``p % SUB < d`` (masked BEFORE the ``exp``: those
differences are positive; a rotation's wrap-around lands only there), and the
sum over the channels is a sum over sublanes: VPU adds, no lane reduction and
no MXU product that would round a float32 factor.  Per offset d and slab of
``_SLAB`` channels, forward:

    e = exp(G[p] - G[p - d] + (0 if p % SUB >= d else -inf)) ;  f = e * k[p - d]
    kk[p, p - d] = sum_c k[p] f ;  qk[p, p - d] = sum_c q[p] f

and backward, with ``Gk = g_kk[p, p - d]``, ``Gq = g_qk[p, p - d]`` a lane each:

    g_q[p] += Gq f ;  g_k[p] += Gk f ;  h = (Gk k[p] + Gq q[p]) e ;  g_k[p - d] += h
    z = h k[p - d] ;  g_cum[p] += z ;  g_cum[p - d] -= z

(offsets and slabs are unrolled: a rotation by a number the compiler knows is
one XLU op, by one it does not a dozen, and a slab in a loop of its own
drains the pipes, a quarter of a kernel's time; a slab's operands and its
three gradient accumulators stay in registers over the sixteen offsets).  The
gradient kernel takes ``h`` to its partner's lane in a PASS OF ITS OWN, from a
VMEM stash: a rotation of what the arithmetic has just made, in between that
arithmetic, cost ten cycles a vreg on the chip, in a pass after it next to
nothing (PERF.md section 6, PR 49's step 0); there ``g_cum[p - d] -= z`` is
``-h[p] k[p - d]`` seen from p - d, so ``h`` alone travels.  What the offsets
give is ``D[d, p] = M[p, p - d]``, sixteen rows of 128 lanes a group and
mask; ``[C, C]`` rows come from it by ONE transpose and a rotation of row r
by r (``pltpu.roll`` with a stride), and the gradient kernel reads its two
cotangents through the inverse, on the transposed side (Mosaic's strided
rotation takes no negative stride: a position's own sub-block's sixteen rows
are selected and rotated over the sublanes a set bit at a time).

Precision is ``ops/delta_rule.py``'s: the differences, the ``exp``, every
factor and the channel sum float32, the mask before the ``exp``; the
gradients of ``q`` and ``k`` rounded once to the operands' type.

Contract (``ops/delta_rule.outside_mask_contract``): ``dk`` whole multiples
of 128, the (padded) chunk 16, 32, 64 or 128 (whole sub-blocks of 16 and
whole chunks a 128-lane group).  N is padded to whole groups with positions
that change nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
#: positions a grid step (eight groups of 128); fewer where N is smaller
_ROWS = 1024
#: channels a slab: [_SLAB, 128] float32 is four vregs an array
_SLAB = 32
_VMEM_LIMIT = 32 * 2**20
_F32 = jnp.float32


def _transposed(ref, rows):
    """``ref[rows]`` [128, dk] as float32 [dk, 128]: channels in the sublanes."""
    return ref[rows, :].astype(_F32).T


def _outside(sub: int):
    """[sub, _SLAB, 128] float32: row d is 0 where offset d pairs a lane with
    a position of its own sub-block (``p % sub >= d``) and -inf elsewhere:
    ADDED to a difference before the ``exp``, it is the mask."""
    shape = (sub, _SLAB, LANES)
    inside = lax.broadcasted_iota(jnp.int32, shape, 2) % sub >= lax.broadcasted_iota(jnp.int32, shape, 0)
    return jnp.where(inside, 0.0, -jnp.inf).astype(_F32)


def _factors(c, k, outside, d: int):
    """``(e, k[p - d])`` of one slab at offset ``d`` >= 1: ``e = exp(G[p] -
    G[p - d])`` where the pair is inside a sub-block, 0 elsewhere (masked
    BEFORE the exp: a difference that leaves the sub-block is finite, and
    -inf once the mask is added)."""
    return jnp.exp(c - pltpu.roll(c, d, 1) + outside[d]), pltpu.roll(k, d, 1)


def _summed(x):
    """[_SLAB, 128] -> [8, 128]: a slab's channels added vreg on vreg."""
    return jnp.sum(x.reshape(_SLAB // 8, 8, LANES), axis=0)


def _masks_kernel(q_ref, k_ref, cum_ref, kk_ref, qk_ref, qt, kt, ct, outside, part, diag, *, chunk: int, sub: int):
    dk = q_ref.shape[1]
    outside[...] = _outside(sub)
    diag[...] = jnp.zeros_like(diag)  # rows 2 sub.. stay zero: the transpose's other lanes
    lane, row = (lax.broadcasted_iota(jnp.int32, (chunk, chunk), axis) for axis in (1, 0))
    own = lane // sub == row // sub  # [r, i] of a chunk: i in r's sub-block

    def group(g, _):
        rows = pl.ds(pl.multiple_of(g * LANES, LANES), LANES)
        qt[...], kt[...], ct[...] = _transposed(q_ref, rows), _transposed(k_ref, rows), _transposed(cum_ref, rows)
        part[...] = jnp.zeros_like(part)

        def slab(s, _):
            at = pl.ds(pl.multiple_of(s * _SLAB, _SLAB), _SLAB)
            q, k, c = qt[at, :], kt[at, :], ct[at, :]
            for d in range(sub):  # unrolled: a rotation by a number the compiler knows is one XLU op, by one it does not a dozen
                if d:
                    e, partner = _factors(c, k, outside, d)
                    f = e * partner
                else:  # the pair (p, p): e = 1
                    f = k
                # rows sub - 1 - d (kk) and 2 sub - 1 - d (qk) of ``diag``: M[p, p - d], a lane a position
                for row, x in ((sub - 1 - d, k), (2 * sub - 1 - d, q)):
                    part[row] += _summed(x * f)
            return _

        # unrolled: as a loop each slab drains the pipes (16,507 tokens/s/chip in the cell for 16,904: PERF.md, PR 49)
        lax.fori_loop(0, dk // _SLAB, slab, None, unroll=True)
        diag[0:2 * sub, :] = jnp.sum(part[...], axis=1)
        by_position = diag[...].T  # [p, j]: kk[p, p - (sub - 1 - j)] in lane j < sub, qk's in lane sub + j
        for t in range(0, LANES, chunk):
            for first, out_ref in ((0, kk_ref), (sub, qk_ref)):
                # row r of a chunk: lane first + j -> lane i = j + r - (sub - 1)
                rolled = pltpu.roll(by_position[t:t + chunk, :], LANES - (sub - 1) - first, 1, stride=1, stride_axis=0)
                # a row's other mask lands in the lanes after (kk's) or before (qk's) its own sub-block's: off the block diagonal
                out_ref[pl.ds(pl.multiple_of(g * LANES + t, chunk), chunk), :] = jnp.where(own, rolled[:, :chunk], 0.0)
        return _

    lax.fori_loop(0, q_ref.shape[0] // LANES, group, None)


def _grads_kernel(q_ref, k_ref, cum_ref, g_kk_ref, g_qk_ref, dq_ref, dk_ref, dcum_ref,
                  qt, kt, ct, outside, wide, of_kk, of_qk, stash, aq, ak, ac, *, chunk: int, sub: int):
    dk = q_ref.shape[1]
    outside[...] = _outside(sub)
    wide[...] = jnp.zeros_like(wide)  # lanes chunk.. stay zero: the transpose's other rows
    lane = lax.broadcasted_iota(jnp.int32, (sub, LANES), 1)
    short = sub - 1 - lane % sub  # sub - 1 - r % sub, a lane a position

    def group(g, _):
        rows = pl.ds(pl.multiple_of(g * LANES, LANES), LANES)
        qt[...], kt[...], ct[...] = _transposed(q_ref, rows), _transposed(k_ref, rows), _transposed(cum_ref, rows)
        for src, by_offset in ((g_kk_ref, of_kk), (g_qk_ref, of_qk)):
            # the masks kernel's last step, inverted, on the transposed cotangent [i, p]: a position keeps its own
            # sub-block's rows, and row i % sub goes to row j = i % sub - r % sub + (sub - 1) (a rotation over the
            # sublanes by sub - 1 - r % sub, a lane each: a set bit at a time; what wraps around is a pair above the
            # diagonal, which e = 0 takes out below)
            wide[:, 0:chunk] = src[rows, :]
            by_row = wide[...].T
            x = by_row[0:sub, :]
            for at in range(sub, chunk, sub):
                x = jnp.where((lane % chunk) // sub == at // sub, by_row[at:at + sub, :], x)
            for bit in (1 << b for b in range((sub - 1).bit_length())):
                x = jnp.where(short & bit != 0, pltpu.roll(x, bit, 0), x)
            by_offset[...] = x  # row j: the cotangent of M[p, p - (sub - 1 - j)], a lane a position
        def slab(s, _):
            at = pl.ds(pl.multiple_of(s * _SLAB, _SLAB), _SLAB)
            q, k, c = qt[at, :], kt[at, :], ct[at, :]
            of_k, of_q = of_kk[sub - 1:sub, :], of_qk[sub - 1:sub, :]  # [1, 128]: the pair (p, p), e = 1
            g_q, g_k, g_c = of_q * k, 2.0 * of_k * k + of_q * q, jnp.zeros_like(c)
            for d in range(1, sub):  # unrolled, as the masks kernel's
                of_k, of_q = of_kk[sub - 1 - d:sub - d, :], of_qk[sub - 1 - d:sub - d, :]
                e, partner = _factors(c, k, outside, d)
                f = e * partner
                h = (of_k * k + of_q * q) * e  # the partner's k's gradient, at p
                g_q, g_k, g_c = g_q + of_q * f, g_k + of_k * f, g_c + h * partner
                stash[d] = h
            # ... and taken to the partner's lane in a pass of its own: a rotation of what the arithmetic has just made,
            # in between that arithmetic, cost ten cycles a vreg on the chip (PERF.md section 6, PR 49's step 0)
            for d in range(1, sub):
                back = pltpu.roll(stash[d], LANES - d, 1)  # p -> p - d
                g_k, g_c = g_k + back, g_c - back * k  # the difference's gradient at the partner: -h k[p - d], seen from p - d
            aq[at, :], ak[at, :], ac[at, :] = g_q, g_k, g_c
            return _

        lax.fori_loop(0, dk // _SLAB, slab, None, unroll=True)  # as the masks kernel's
        dq_ref[rows, :] = aq[...].T.astype(dq_ref.dtype)
        dk_ref[rows, :] = ak[...].T.astype(dk_ref.dtype)
        dcum_ref[rows, :] = ac[...].T
        return _

    lax.fori_loop(0, q_ref.shape[0] // LANES, group, None)


def _launch(kernel, name: str, operands, widths, out_dtypes, scratch, ops_a_pair: int, sub: int, interpret: bool):
    """One call over ``operands`` [N, width]: a grid step ``rows`` positions
    of every operand and output; ``ops_a_pair`` VPU operations a (position,
    offset, channel) for the scheduler's estimate."""
    n, dk = operands[0].shape
    rows = next(r for r in (_ROWS, _ROWS // 2, _ROWS // 4, LANES) if n % r == 0)
    out_shape = [jax.ShapeDtypeStruct((n, w), t) for w, t in zip(widths, out_dtypes)]
    block = lambda width: pl.BlockSpec((rows, width), lambda i: (i, 0))  # noqa: E731
    pairs = n * sub * dk
    compiled = {} if interpret else {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",), vmem_limit_bytes=_VMEM_LIMIT)}
    return pl.pallas_call(
        kernel, name=name, grid=(n // rows,), in_specs=[block(t.shape[1]) for t in operands],
        out_specs=[block(w) for w in widths], out_shape=out_shape, scratch_shapes=scratch, interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=ops_a_pair * pairs, transcendentals=pairs,
            bytes_accessed=sum(t.size * t.dtype.itemsize for t in (*operands, *out_shape))),
        **compiled,
    )(*operands)


def _tiles(dk: int, count: int):
    return [pltpu.VMEM((dk, LANES), _F32) for _ in range(count)]


def _whole_groups(t):
    short = -t.shape[0] % LANES
    return jnp.pad(t, ((0, short), (0, 0))) if short else t


def masks(q, k, cum, *, chunk: int, sub: int, interpret: bool):
    """``(kk, qk)`` [N, chunk] float32 from ``q``, ``k`` [N, dk] and the sums
    ``cum`` [N, dk] float32 (module docstring)."""
    n, dk = k.shape
    operands = tuple(_whole_groups(t) for t in (q, k, cum))
    scratch = _tiles(dk, 3) + [pltpu.VMEM((sub, _SLAB, LANES), _F32), pltpu.VMEM((2 * sub, 8, LANES), _F32), pltpu.VMEM((LANES, LANES), _F32)]
    kk, qk = _launch(functools.partial(_masks_kernel, chunk=chunk, sub=sub), "kda_sub_block_masks", operands,
                     (chunk, chunk), (_F32, _F32), scratch, 6, sub, interpret)
    return kk[:n], qk[:n]


def mask_grads(q, k, cum, g_kk, g_qk, *, chunk: int, sub: int, interpret: bool):
    """``masks`` transposed: ``(g_q, g_k [N, dk] in the operands' type, g_cum
    [N, dk] float32)`` from the operands and the masks' cotangents [N,
    chunk] float32 (what lies outside a sub-block or above the diagonal is
    not read)."""
    n, dk = k.shape
    operands = tuple(_whole_groups(t) for t in (q, k, cum, g_kk, g_qk))
    scratch = (_tiles(dk, 3) + [pltpu.VMEM((sub, _SLAB, LANES), _F32), pltpu.VMEM((LANES, LANES), _F32)] + [pltpu.VMEM((sub, LANES), _F32)] * 2
               + [pltpu.VMEM((sub, _SLAB, LANES), _F32)] + _tiles(dk, 3))
    grads = _launch(functools.partial(_grads_kernel, chunk=chunk, sub=sub), "kda_sub_block_mask_grads", operands,
                    (dk, dk, dk), (q.dtype, k.dtype, _F32), scratch, 16, sub, interpret)
    return tuple(t[:n] for t in grads)
