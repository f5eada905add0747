"""The arithmetic INSIDE a chunk of ``ops/ssm.ssm_scan`` as Pallas kernels:
a chunk's decay mask, its ``C B^T``, the masked weights and the ``[Q, Q]``
gradient of the weights live and die in VMEM; HBM sees the operands, ``y``,
the chunks' states and the gradients only.  ``ops/ssm.py`` owns everything
around them (the summed log-decays, the recurrence over the chunks, the
``custom_vjp``, which path a call takes) and says what each kernel computes.

One grid step is one chunk of Q positions of one group of R heads (every
axis parallel: nothing is carried from a step to the next):

- ``x``, ``y`` and ``y``'s gradient as ``[Q, R * P]`` slabs of the free view
  ``[B, L, H * P]`` (lane-dense where one head's ``[Q, 64]`` is half a
  tile), ``B`` and ``C`` as ``[Q, N]`` blocks of ``[B, L, G * N]``;
- what is a number a (head, position) — ``dt``, the summed log-decays —
  float32 in BOTH layouts ``[R, Q]`` and ``[Q, R]`` of ``[B, n, G, ., .]``,
  so that neither a row nor a column of a ``[Q, Q]`` mask asks for a
  transpose in the kernel;
- the group's states ``[R, P, N]`` float32 of ``[B, n, G, R, P, N]``.

A step transposes its slab once and works on ``[R * P, Q]``: a head's number
a position is then a row broadcast over the head's P sublanes, a sum over a
head's width is a sum over sublanes, the products that read or make a state
are ONE ``[R * P, .]`` matmul for the group's heads, and a head's ``[P, Q]
[Q, Q]`` product fills the MXU's 128 columns.  ``C B^T`` is formed once a
step and reused by its R heads, which are an unrolled loop.

Precision is ``ops/ssm.py``'s: matmul operands in ``x``'s type, float32
accumulation; the log-decays, their differences, the ``exp``, the decay
factors and the states float32; the mask applied BEFORE the ``exp``; the
weights rounded to ``x``'s type once, ahead of their product.

Contract (``ops/ssm.outside_contract``): Q and N whole multiples of 128,
``R * P`` a multiple of 128, P a multiple of 8.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: A step of the gradient kernel holds some twenty [R * P, Q] float32
#: arrays (512 KiB each at R * P = 1024) beside its double-buffered blocks.
_VMEM_LIMIT = 64 * 2**20

_NT = (((1,), (1,)), ((), ()))  # a [M, K] by b [N, K] -> [M, N]
_TN = (((0,), (0,)), ((), ()))  # a [K, M] by b [K, N] -> [M, N]
_F32 = jnp.float32


def _transposed(ref):
    """A ``[Q, R * P]`` slab as float32 ``[R * P, Q]``."""
    return ref[...].astype(_F32).T


def _scaled(t, scale):
    """``t`` [R * P, Q] times ``scale`` [R, Q], a head's row over its P sublanes."""
    heads = scale.shape[0]
    return (t.reshape(heads, -1, t.shape[1]) * scale[:, None, :]).reshape(t.shape)


def _summed(t, heads: int):
    """``t`` [R * P, Q] summed over each head's P sublanes: [R, Q]."""
    return jnp.sum(t.reshape(heads, -1, t.shape[1]), axis=1)


def _dot(a, b, dims=None):
    if dims is None:
        return jnp.dot(a, b, preferred_element_type=_F32)
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _states_kernel(slab_ref, scale_ref, mat_ref, out_ref):
    """``out[r] = (slab_r * scale_r)^T mat``: a chunk's own end state from
    ``x``, ``exp(cum_end - cum) dt`` and ``B``; what ``y`` asks of its start
    state from ``y``'s gradient, ``exp(cum)`` and ``C``."""
    scaled = _scaled(_transposed(slab_ref), scale_ref[...]).astype(slab_ref.dtype)
    out_ref[...] = _dot(scaled, mat_ref[...]).reshape(out_ref.shape)


def _outputs_kernel(x_ref, dt_ref, cum_ref, cum_qr_ref, b_ref, c_ref, starts_ref, y_ref, acc_ref):
    """``y = ((C B^T) o L) (x dt) + exp(cum) (C S_0^T)``, transposed: rows
    are (head, column of the head), lanes the chunk's positions."""
    heads, chunk = dt_ref.shape
    dtype, width = x_ref.dtype, x_ref.shape[1] // heads
    cum, c = cum_ref[...], c_ref[...]
    xdt = _scaled(_transposed(x_ref), dt_ref[...]).astype(dtype)
    cb = _dot(b_ref[...], c, _NT)  # [s, q]
    starts = starts_ref[...].reshape(heads * width, -1).astype(dtype)
    acc_ref[...] = _scaled(_dot(starts, c, _NT), jnp.exp(cum))
    causal = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) <= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    for r in range(heads):
        rows = slice(r * width, (r + 1) * width)
        seg = cum[r:r + 1, :] - cum_qr_ref[:, r:r + 1]  # [s, q]: from s through q
        # masked BEFORE the exp: above the diagonal the sum is positive and large
        weights = (cb * jnp.exp(jnp.where(causal, seg, -jnp.inf))).astype(dtype)
        acc_ref[rows, :] += _dot(xdt[rows], weights)
    y_ref[...] = acc_ref[...].T.astype(dtype)


def _grads_kernel(x_ref, g_ref, dt_ref, cum_ref, cum_qr_ref, b_ref, c_ref, starts_ref, g_next_ref,
                  dx_ref, db_ref, dc_ref, ddt_ref, dcum_ref, dcum_qr_ref, acc_ref):
    """The chunk's own gradients from ``y``'s gradient ``g`` and the gradient
    ``g_next`` of the state it hands on (``decay S_0 + ends``): ``dx``; ``dB``
    and ``dC`` summed over the group's heads; ``dt``'s as it enters directly
    and the summed log-decays' (``dcum`` [R, Q] plus ``dcum_qr`` [Q, R]: what
    comes out of a sum over lanes is a column), all float32."""
    heads, chunk = dt_ref.shape
    dtype, width = x_ref.dtype, x_ref.shape[1] // heads
    dt, cum, b, c = dt_ref[...], cum_ref[...], b_ref[...], c_ref[...]
    ecum = jnp.exp(cum)
    till_end = jnp.exp(cum[:, chunk - 1:] - cum)
    to_end = till_end * dt
    x_t, g_t = _transposed(x_ref), _transposed(g_ref)
    xdt = _scaled(x_t, dt).astype(dtype)
    xw = _scaled(x_t, to_end).astype(dtype)
    starts, g_next = starts_ref[...].reshape(heads * width, -1), g_next_ref[...].reshape(heads * width, -1)
    starts_lo, g_next_lo = starts.astype(dtype), g_next.astype(dtype)

    # the state's part of y: exp(cum) (C S_0^T)
    carried = _dot(starts_lo, c, _NT)  # [R * P, q]
    dcum = ecum * _summed(g_t * carried, heads)
    weighted = _scaled(g_t, ecum).astype(dtype)
    dc = _dot(weighted, starts_lo, _TN)  # [q, N]

    # the state handed on: exp(cum_end) S_0 + (x to_end)^T B
    dxw = _dot(g_next_lo, b, _NT)  # [R * P, s]
    db = _dot(xw, g_next_lo, _TN)  # [s, N]
    dto_end = _summed(dxw * x_t, heads)
    ddt = dto_end * till_end
    through_end = dto_end * to_end
    of_decay = jnp.sum(_summed(g_next * starts, heads), axis=1, keepdims=True)  # [R, 1]
    at_end = jnp.sum(through_end, axis=1, keepdims=True) + ecum[:, chunk - 1:] * of_decay
    last = lax.broadcasted_iota(jnp.int32, (heads, chunk), 1) == chunk - 1
    dcum = dcum - through_end + jnp.where(last, at_end, 0.0)

    # inside the chunk: ((C B^T) o L) (x dt), a head at a time
    cb = _dot(c, b, _NT)  # [q, s]
    causal = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    g_lo = g_t.astype(dtype)
    dcb = jnp.zeros((chunk, chunk), _F32)
    rows_of, cols_of = [], []
    for r in range(heads):
        rows = slice(r * width, (r + 1) * width)
        seg = cum_qr_ref[:, r:r + 1] - cum[r:r + 1, :]  # [q, s]: from s through q
        decays = jnp.exp(jnp.where(causal, seg, -jnp.inf))  # masked BEFORE the exp
        weights = cb * decays
        dweights = _dot(g_ref[:, rows], xdt[rows])  # [q, s]
        acc_ref[rows, :] = _dot(g_lo[rows], weights.astype(dtype))  # d(x dt) [P, s]
        dcb = dcb + dweights * decays
        dseg = dweights * weights
        rows_of.append(jnp.sum(dseg, axis=1, keepdims=True))  # [q, 1]
        cols_of.append(jnp.sum(dseg, axis=0, keepdims=True))  # [1, s]
    dxdt = acc_ref[...]
    dcum_qr_ref[...] = jnp.concatenate(rows_of, axis=1)
    dcum_ref[...] = dcum - jnp.concatenate(cols_of, axis=0)
    ddt_ref[...] = ddt + _summed(dxdt * x_t, heads)
    dx_ref[...] = (_scaled(dxdt, dt) + _scaled(dxw, to_end)).T.astype(dtype)
    dcb = dcb.astype(dtype)
    dc_ref[...] = dc + _dot(dcb, b)
    db_ref[...] = db + _dot(dcb, c, _TN)


class _Plan:
    """Grid and BlockSpecs of the calls for one shape: ``x`` [B, L, H * P],
    ``b`` [B, L, G * N], chunks of ``chunk``."""

    def __init__(self, x, b, heads: int, groups: int, chunk: int, interpret: bool):
        bsz, length, inner = x.shape
        self.n, self.r, self.q, self.p, self.k = length // chunk, heads // groups, chunk, inner // heads, b.shape[2] // groups
        self.bsz, self.groups, self.dtype, self.interpret = bsz, groups, x.dtype, interpret
        self.grid = (bsz, self.n, groups)
        at = lambda i, j, g: (i, j, g)  # noqa: E731
        within = lambda *tail: pl.BlockSpec((None, None, None) + tail, lambda i, j, g: (i, j, g) + (0,) * len(tail))  # noqa: E731
        self.slab = pl.BlockSpec((None, chunk, self.r * self.p), at)
        self.mat = pl.BlockSpec((None, chunk, self.k), at)
        self.rq, self.qr, self.state = within(self.r, chunk), within(chunk, self.r), within(self.r, self.p, self.k)
        self.positions = bsz * length

    def shape(self, spec, dtype=_F32):
        """The whole array a block of ``spec`` is a (chunk, group) of."""
        if spec is self.slab or spec is self.mat:
            return jax.ShapeDtypeStruct((self.bsz, self.n * self.q, self.groups * spec.block_shape[-1]), dtype)
        return jax.ShapeDtypeStruct((self.bsz, self.n, self.groups) + tuple(spec.block_shape[3:]), dtype)

    def cost(self, cb: int, products: int, states: int, operands, out_shape):
        """What a call executes, in ``ops/ssm.scan_flops``' terms a position:
        ``cb`` products of the size of ``C B^T`` a group, ``products`` of a
        head's ``[Q, Q] [Q, P]``, ``states`` of a head's ``[P, N]`` with a
        position's row; an ``exp`` a (head, row, column) where it has a mask."""
        heads = self.r * self.groups
        flops = 2 * self.positions * (cb * self.q * self.k * self.groups + products * self.q * self.p * heads + states * self.p * self.k * heads)
        return pl.CostEstimate(
            flops=flops, transcendentals=self.positions * self.q * heads * bool(products),
            bytes_accessed=sum(t.size * t.dtype.itemsize for t in (*operands, *jax.tree.leaves(out_shape))),
        )

    def launch(self, kernel, name: str, in_specs, out_specs, out_shape, cost, scratch=()):
        compiled = {} if self.interpret else {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=_VMEM_LIMIT)}
        return pl.pallas_call(
            kernel, name=name, grid=self.grid, in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
            scratch_shapes=list(scratch), cost_estimate=cost, interpret=self.interpret, **compiled,
        )


def chunk_states(slab, scale, mat, *, chunk: int, interpret: bool):
    """``[B, n, G, R, P, N]`` float32: per chunk and head ``(slab scale)^T
    mat`` — ``slab`` [B, L, H * P], ``scale`` [B, n, G, R, Q] float32,
    ``mat`` [B, L, G * N]."""
    groups, heads = scale.shape[2], scale.shape[2] * scale.shape[3]
    plan = _Plan(slab, mat, heads, groups, chunk, interpret)
    operands, out_shape = (slab, scale, mat), plan.shape(plan.state)
    return plan.launch(
        _states_kernel, "ssm_chunk_states", [plan.slab, plan.rq, plan.mat], plan.state, out_shape,
        plan.cost(0, 0, 1, operands, out_shape),
    )(*operands)


def chunk_outputs(x, dt, cum, cum_qr, b, c, starts, *, chunk: int, interpret: bool):
    """``y`` [B, L, H * P] in ``x``'s type from ``x`` [B, L, H * P], ``dt``
    and the summed log-decays ``cum`` [B, n, G, R, Q] (and ``cum_qr`` [B, n,
    G, Q, R]), ``b``, ``c`` [B, L, G * N] and the states at the chunks'
    starts [B, n, G, R, P, N] float32."""
    groups, heads = dt.shape[2], dt.shape[2] * dt.shape[3]
    plan = _Plan(x, b, heads, groups, chunk, interpret)
    operands, out_shape = (x, dt, cum, cum_qr, b, c, starts), plan.shape(plan.slab, x.dtype)
    return plan.launch(
        _outputs_kernel, "ssm_chunk_outputs",
        [plan.slab, plan.rq, plan.rq, plan.qr, plan.mat, plan.mat, plan.state], plan.slab, out_shape,
        plan.cost(1, 1, 1, operands, out_shape), scratch=[pltpu.VMEM((plan.r * plan.p, chunk), _F32)],
    )(*operands)


def chunk_grads(x, g, dt, cum, cum_qr, b, c, starts, g_next, *, chunk: int, interpret: bool):
    """The transpose of a chunk's arithmetic (``_grads_kernel``): ``(dx [B,
    L, H * P] in x's type, dB, dC [B, L, G * N] float32, dt's direct
    gradient and the summed log-decays' [B, n, G, R, Q], the latter's other
    part [B, n, G, Q, R])``."""
    groups, heads = dt.shape[2], dt.shape[2] * dt.shape[3]
    plan = _Plan(x, b, heads, groups, chunk, interpret)
    operands = (x, g, dt, cum, cum_qr, b, c, starts, g_next)
    out_specs = [plan.slab, plan.mat, plan.mat, plan.rq, plan.rq, plan.qr]
    out_shape = [plan.shape(plan.slab, x.dtype)] + [plan.shape(spec) for spec in out_specs[1:]]
    return plan.launch(
        _grads_kernel, "ssm_chunk_grads",
        [plan.slab, plan.slab, plan.rq, plan.rq, plan.qr, plan.mat, plan.mat, plan.state, plan.state],
        out_specs, out_shape, plan.cost(3, 2, 4, operands, out_shape),
        scratch=[pltpu.VMEM((plan.r * plan.p, chunk), _F32)],
    )(*operands)
