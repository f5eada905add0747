"""The state-space mixer's three ops (Mamba-2, arXiv:2405.21060): the
selective scan in its CHUNKED form with a backward, the causal depthwise
convolution ahead of it and the gated norm over groups after it.

The scan.  H heads of width P in G groups (head h in group ``h // (H /
G)``), a state of N columns a head; per position t of a sequence, from a
zero state at the sequence's start:

    a_t = exp(dt_t * A)                               A [H] < 0, dt_t [H] > 0: a decay in (0, 1) a head
    S_t = a_t S_{t-1} + dt_t x_t B_t^T                S [H, P, N]; x_t [H, P]; B_t, C_t [G, N]
    y_t = S_t C_t + D x_t

``ssm_scan`` computes it a chunk of ``chunk`` positions at a time (the
paper's state-space duality): with ``cum_t`` the log-decays summed from the
chunk's start through t,

    inside a chunk:    y_t  = sum_{s <= t} exp(cum_t - cum_s) (C_t . B_s) dt_s x_s      the masked ``C B^T`` product
    a chunk's own end: S'   = sum_s exp(cum_end - cum_s) dt_s x_s B_s^T
    across chunks:     S_c+1 = exp(cum_end) S_c + S'_c                                  L / chunk steps of [H, P, N]
    the state's part:  y_t += exp(cum_t) S_c C_t

under ONE ``custom_vjp`` whose residuals are the operands and the states at
the chunks' starts only ([B, L / chunk, H, P, N] float32: 64 MiB a layer at
L = 8192, 32 heads of 64 x 128); the backward runs the same recurrence in
reverse over the states' gradients and differentiates the chunks' own
arithmetic again from the operands (nothing of size [chunk, chunk] or [L, H,
P, N] outlives a pass).  The matmuls take the operands' type (bfloat16 in a
job) and accumulate in float32; the log-decays, their sums, the decay
factors and the carried state are float32 whatever the operands are: a
sequence's state is a product of L factors near 1.

What runs where.  The arithmetic INSIDE a chunk (``_chunk_ends``,
``_chunk_outputs`` and their transposes) has two implementations of the one
algorithm, chosen by what a call can observe (``scan_path``; no flag):

- the Pallas kernels of ``ops/ssm_kernels.py`` on a TPU inside their
  contract (``outside_contract``: ``chunk`` and N whole multiples of 128, a
  group's ``R * P`` lanes a multiple of 128, P whole sublanes; the published
  widths are inside): a chunk's decay mask, its ``C B^T``, the masked weights
  and the ``[Q, Q]`` gradient of the weights never leave VMEM (as XLA einsums
  they were 134 MB a layer and pass through HBM: PERF.md section 6, PR 40
  and PR 44);
- the XLA einsums everywhere else: off the TPU (every CPU test and
  rehearsal), chunks of 16, any width that is not whole lanes.

Both call the SAME two seams around the chunks, forward and backward, and
the kernels must keep calling them: ``_log_decays(dt, a, chunk)`` (XLA,
float32; the kernels take the sums as an operand) and ``_carry(ends, decay,
first, reverse)`` (the 64-step recurrence over the chunks, XLA; between the
kernel that makes the chunks' end states and the one that reads the start
states, and in reverse between the two backward kernels).  The benchmark's
controls swap exactly these two by module attribute
(``benchmark/configs/nemotron3_super_tp4_ep64_l11_reference.py:286-297``,
``faults``: ``bfloat16_decay`` / ``all_bfloat16`` round the sums,
``no_carried_state`` zeroes the start states) and ``correct`` is only as
good as their bite: a kernel that summed the log-decays itself, or carried
the state in VMEM scratch across a sequential grid axis (fewer bytes:
PERF.md section 7), would disarm them in silence.  The seam's transpose is
``jax.vjp`` of the seam, so a patched seam is differentiated as patched.

Scopes: ``ssm_scan`` (forward and backward, either path), ``ssm_conv``,
``ssm_norm`` (XLA).  Each op has a plain ``*_reference`` (the scan position
by position) that the tests hold it to.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.ops import remat, ssm_kernels
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE, announce_path


class Aux(NamedTuple):
    """What ``ssm_scan(with_aux=True)`` hands out beside ``y`` (no gradient
    flows through either): the state after the last position and every
    position's log-decay summed from its chunk's start (the float32 island
    the benchmark's ``ssm_decay`` check reads)."""

    state: jax.Array  # [B, H, P, N] float32
    log_decay: jax.Array  # [B, L, H] float32


def scan_flops(batch: int, length: int, heads: int, width: int, groups: int, state: int, chunk: int) -> int:
    """FLOPs of one forward pass of the chunked scan, from shapes: ``C B^T``
    a group, the masked product with x, the chunks' end states and the
    states' part of the outputs, a head each."""
    positions = batch * length
    return 2 * positions * (chunk * state * groups + chunk * width * heads + 2 * width * state * heads)


def _by_chunks(t, chunk: int):
    return t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:])


def _log_decays(dt, a, chunk: int):
    """[B, n, Q, H] float32: each position's log-decay summed from its
    chunk's start (inclusive)."""
    # NOT ``jnp.cumsum``: on the TPU it lowers to a product with a triangle of
    # ones at the default (bfloat16) precision, which read 1e-3 of the largest
    # sum against float64 where float32 adds read 1e-7 (my chip run, PR 40).
    return lax.associative_scan(jnp.add, _by_chunks(dt.astype(jnp.float32) * a.astype(jnp.float32), chunk), axis=2)


def _grouped(t, groups: int):
    """[B, n, Q, H, ...] -> [B, n, Q, G, H / G, ...]."""
    return t.reshape(*t.shape[:3], groups, t.shape[3] // groups, *t.shape[4:])


def _chunk_ends(x, dt, a, b, chunk: int):
    """Each chunk's own end state [B, n, G, R, P, N] float32 (from a zero
    state at its start) and its whole decay [B, n, G, R] float32."""
    groups = b.shape[2]
    cum = _log_decays(dt, a, chunk)
    to_end = jnp.exp(cum[:, :, -1:] - cum) * _by_chunks(dt.astype(jnp.float32), chunk)  # [B, n, Q, H]
    xw = _grouped((_by_chunks(x, chunk).astype(jnp.float32) * to_end[..., None]).astype(x.dtype), groups)
    ends = jnp.einsum("bnsgrp,bnsgk->bngrpk", xw, _by_chunks(b, chunk), preferred_element_type=jnp.float32)
    return ends, _grouped(jnp.exp(cum[:, :, -1:]), groups)[:, :, 0]


def _chunk_outputs(x, dt, a, b, c, starts, chunk: int):
    """``y`` [B, L, H, P] in ``x``'s type from the states at the chunks'
    starts ``starts`` [B, n, G, R, P, N] float32."""
    groups, (bsz, length, heads, width) = b.shape[2], x.shape
    cum = _grouped(_log_decays(dt, a, chunk), groups)  # [B, n, Q, G, R]
    xdt = _grouped((_by_chunks(x, chunk).astype(jnp.float32) * _by_chunks(dt.astype(jnp.float32), chunk)[..., None]).astype(x.dtype), groups)
    bc, cc = _by_chunks(b, chunk), _by_chunks(c, chunk)
    cb = jnp.einsum("bnqgk,bnsgk->bngqs", cc, bc, preferred_element_type=jnp.float32)
    cum_q = jnp.moveaxis(cum, 2, -1)  # [B, n, G, R, Q]
    seg = cum_q[..., :, None] - cum_q[..., None, :]  # [B, n, G, R, Q, S]: from s through q
    causal = lax.iota(jnp.int32, chunk)[:, None] >= lax.iota(jnp.int32, chunk)[None, :]
    # masked BEFORE the exp: above the diagonal the sum is positive and large
    weights = (cb[:, :, :, None] * jnp.exp(jnp.where(causal, seg, -jnp.inf))).astype(x.dtype)
    y = jnp.einsum("bngrqs,bnsgrp->bnqgrp", weights, xdt, preferred_element_type=jnp.float32)
    carried = jnp.einsum("bnqgk,bngrpk->bnqgrp", cc, starts.astype(x.dtype), preferred_element_type=jnp.float32)
    y = y + carried * jnp.exp(cum)[..., None]
    return y.astype(x.dtype).reshape(bsz, length, heads, width)


def _carry(ends, decay, first, reverse: bool = False):
    """The recurrence over the chunks: ``S_c+1 = decay_c S_c + ends_c`` from
    ``first``; returns (the states at the chunks' starts [B, n, ...], the
    state after the last).  ``reverse``: from the last chunk down, for the
    states' gradients (``G_c = decay_c G_c+1 + ends_c``; then "starts" are
    the ``G_c+1`` each chunk sees).  A loop of n steps: as an associative
    scan (log2(n) levels of slices and pads of the whole array) it cost 9 ms
    more a step of ``nemotron3_job`` (PERF.md section 6, PR 40)."""
    def step(state, chunk):
        end, dec = chunk
        return dec[..., None, None] * state + end, state

    last, starts = lax.scan(step, first, (jnp.moveaxis(ends, 1, 0), jnp.moveaxis(decay, 1, 0)), reverse=reverse)
    return jnp.moveaxis(starts, 0, 1), last


def outside_contract(x, b, chunk: int) -> str:
    """Why a scan of ``x`` [B, L, H, P] and ``b`` [B, L, G, N] is outside
    the kernels' contract (``""``: inside): their tiles are whole lanes
    (ops/ssm_kernels.py)."""
    (heads, width), (groups, state) = x.shape[2:], b.shape[2:]
    per = heads // groups
    if chunk % 128 or state % 128:
        return f"chunk {chunk} and state {state} are not whole multiples of 128"
    if per * width % 128 or width % 8:
        return f"a group's {per} heads of {width} are not whole lanes of 128 in whole sublanes of 8"
    return ""


def scan_path(x, b, chunk: int, interpret: Optional[bool] = None):
    """Which path ``ssm_scan`` takes for ``x`` and ``b`` (their shapes are
    read), from what the code can observe: ``(one of ring_attention's
    PATH_*, why not the kernels)``.  The kernels compiled on a TPU inside
    their contract, the einsums everywhere else; ``interpret`` given
    (tests): the kernels, in the Pallas interpreter or compiled."""
    outside = outside_contract(x, b, chunk)
    if interpret is not None:
        if outside:
            raise ValueError(f"the scan's kernels were asked for outside their contract: {outside}")
        return (PATH_PALLAS_INTERPRET if interpret else PATH_PALLAS_COMPILED), ""
    backend = jax.default_backend()
    why_not = f"backend={backend}" if backend != "tpu" else outside
    return (PATH_XLA_REFERENCE if why_not else PATH_PALLAS_COMPILED), why_not


def _kernel_operands(dt, a, groups: int, chunk: int):
    """What the kernels read of ``dt`` and of the seam's log-decays: ``dt``
    and the sums [B, n, G, R, Q] float32, and the sums again [B, n, G, Q, R]
    (a [Q, Q] mask's rows and columns, neither transposed in a kernel)."""
    by_group = lambda t: jnp.moveaxis(_grouped(t, groups), 2, 3)  # noqa: E731 — [B, n, Q, H] -> [B, n, G, Q, R]
    cum_qr = by_group(_log_decays(dt, a, chunk))
    return jnp.swapaxes(by_group(_by_chunks(dt.astype(jnp.float32), chunk)), -1, -2), jnp.swapaxes(cum_qr, -1, -2), cum_qr


def _lanes(t):
    return t.reshape(t.shape[0], t.shape[1], -1)


def _forward_kernels(x, dt, a, b, c, chunk: int, interpret: bool):
    """``_chunk_ends``, ``_carry``, ``_chunk_outputs`` with the chunks'
    arithmetic in ops/ssm_kernels.py; the two seams are called as the XLA
    path calls them (module docstring)."""
    dt_rq, cum, cum_qr = _kernel_operands(dt, a, b.shape[2], chunk)
    to_end = jnp.exp(cum[..., -1:] - cum) * dt_rq
    ends = ssm_kernels.chunk_states(_lanes(x), to_end, _lanes(b), chunk=chunk, interpret=interpret)
    starts, last = _carry(ends, jnp.exp(cum[..., -1]), jnp.zeros_like(ends[:, 0]))
    y = ssm_kernels.chunk_outputs(_lanes(x), dt_rq, cum, cum_qr, _lanes(b), _lanes(c), starts, chunk=chunk, interpret=interpret)
    return y.reshape(x.shape), last, starts


def _forward(x, dt, a, b, c, chunk: int, keep: bool = False, interpret: Optional[bool] = None):
    path, why_not = scan_path(x, b, chunk, interpret)
    announce_path(path, x, True, f"ssm_scan groups={b.shape[2]} state={b.shape[3]} chunk={chunk}" + f"; {why_not}" * bool(why_not))
    if path == PATH_XLA_REFERENCE:
        ends, decay = _chunk_ends(x, dt, a, b, chunk)
        starts, last = _carry(ends, decay, jnp.zeros_like(ends[:, 0]))
        y = _chunk_outputs(x, dt, a, b, c, starts, chunk)
    else:
        y, last, starts = _forward_kernels(x, dt, a, b, c, chunk, path == PATH_PALLAS_INTERPRET)
    # A save site (ops/remat.py): a rematerialised block that keeps the
    # outputs and the chunks' states runs no second forward of the scan.
    work = scan_flops(x.shape[0], x.shape[1], x.shape[2], x.shape[3], b.shape[2], b.shape[3], chunk)
    y, starts = remat.site("ssm_scan_out", work, y, starts, keep=keep)
    return y, last, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _scan(x, dt, a, b, c, chunk, keep, interpret=None):
    y, last, _ = _forward(x, dt, a, b, c, chunk, interpret=interpret)
    return y, last


def _scan_fwd(x, dt, a, b, c, chunk, keep, interpret):
    y, last, starts = _forward(x, dt, a, b, c, chunk, keep, interpret)
    return (y, last), (x, dt, a, b, c, starts)


def _chunk_grads_kernels(x, dt, a, b, c, starts, g_y, g_next, operands, chunk: int, interpret: bool):
    """The chunks' own arithmetic transposed (the einsums' ``jax.vjp(chunks,
    ...)``) by ops/ssm_kernels.py, from ``_kernel_operands``' ``operands``.
    The transpose of the seam ``_log_decays`` is ``jax.vjp`` of the seam
    itself: a patched seam is differentiated as patched."""
    dt_rq, cum, cum_qr = operands
    dx, db, dc, ddt_rq, dcum, dcum_qr = ssm_kernels.chunk_grads(
        _lanes(x), _lanes(g_y), dt_rq, cum, cum_qr, _lanes(b), _lanes(c), starts, g_next, chunk=chunk, interpret=interpret)
    by_position = lambda t: jnp.moveaxis(t, 2, 3).reshape(dt.shape[0], -1, chunk, dt.shape[2])  # noqa: E731 — [B, n, G, Q, R] -> [B, n, Q, H]
    ddt, da = jax.vjp(lambda dt, a: _log_decays(dt, a, chunk), dt, a)[1](by_position(dcum_qr + jnp.swapaxes(dcum, -1, -2)))
    ddt = ddt + by_position(jnp.swapaxes(ddt_rq, -1, -2)).reshape(dt.shape).astype(dt.dtype)
    return dx.reshape(x.shape), ddt, da, db.astype(b.dtype).reshape(b.shape), dc.astype(c.dtype).reshape(c.shape)


def _backward_kernels(x, dt, a, b, c, starts, g_y, g_last, chunk: int, interpret: bool):
    """``_scan_bwd``'s three steps with the chunks' arithmetic in
    ops/ssm_kernels.py, around the same ``_carry``."""
    operands = _kernel_operands(dt, a, b.shape[2], chunk)
    cum = operands[1]
    from_y = ssm_kernels.chunk_states(_lanes(g_y), jnp.exp(cum), _lanes(c), chunk=chunk, interpret=interpret)
    g_next, _ = _carry(from_y, jnp.exp(cum[..., -1]), g_last.astype(jnp.float32), reverse=True)
    return _chunk_grads_kernels(x, dt, a, b, c, starts, g_y, g_next, operands, chunk, interpret)


def _scan_bwd(chunk, keep, interpret, res, grads):
    x, dt, a, b, c, starts = res
    g_y, g_last = grads
    groups = b.shape[2]
    path, _ = scan_path(x, b, chunk, interpret)
    with jax.named_scope("ssm_scan"):
        # Three steps on either path: what y asks of the chunks' start
        # states, that carried back over the chunks (``_carry`` in reverse),
        # the chunks' own arithmetic transposed.  The kernels' path
        # (``_backward_kernels``) is the first and the third as Pallas
        # calls around the same ``_carry``; the einsums' is below.
        if path != PATH_XLA_REFERENCE:
            return _backward_kernels(x, dt, a, b, c, starts, g_y, g_last, chunk, path == PATH_PALLAS_INTERPRET)
        # What y asks of the state at each chunk's start ...
        cum = _grouped(_log_decays(dt, a, chunk), groups)
        weighted = (_grouped(_by_chunks(g_y, chunk), groups).astype(jnp.float32) * jnp.exp(cum)[..., None]).astype(x.dtype)
        from_y = jnp.einsum("bnqgrp,bnqgk->bngrpk", weighted, _by_chunks(c, chunk), preferred_element_type=jnp.float32)
        # ... carried back over the chunks: each chunk sees the gradient of
        # the state it hands on.
        _, decay = _chunk_ends(x, dt, a, b, chunk)
        g_next, _ = _carry(from_y, decay, g_last.astype(jnp.float32), reverse=True)

        def chunks(x, dt, a, b, c):
            # the chunks' own arithmetic, again: every chunk from its start state
            ends, decay = _chunk_ends(x, dt, a, b, chunk)
            return _chunk_outputs(x, dt, a, b, c, starts, chunk), decay[..., None, None] * starts + ends

        return jax.vjp(chunks, x, dt, a, b, c)[1]((g_y, g_next))


_scan.defvjp(_scan_fwd, _scan_bwd)


def ssm_scan(x, dt, a, b, c, d: Optional[jax.Array] = None, *, chunk: int = 128, with_aux: bool = False):
    """The selective scan (module docstring): ``x`` [B, L, H, P], ``dt`` [B,
    L, H] (positive: after the softplus), ``a`` [H] (negative), ``b``, ``c``
    [B, L, G, N], ``d`` [H] or None -> ``y`` [B, L, H, P] in ``x``'s type.
    Each row of the batch is one sequence from a zero state.  L must be
    whole chunks.  ``with_aux``: ``(y, Aux)``."""
    bsz, length, heads, _ = x.shape
    groups = b.shape[2]
    if length % chunk:
        raise ValueError(f"the chunked scan needs L in whole chunks of {chunk}, got L = {length}")
    if heads % groups or b.shape != c.shape or dt.shape != (bsz, length, heads):
        raise ValueError(f"{heads} heads in {groups} groups, dt {dt.shape}, B {b.shape}, C {c.shape}: shapes do not agree")
    with jax.named_scope("ssm_scan"):
        # ``remat.kept``: asked here, while the primal is traced (as the flash kernels ask)
        y, last = _scan(x, dt, a, b, c, chunk, remat.kept("ssm_scan_out"))
        if d is not None:
            y = (y.astype(jnp.float32) + d.astype(jnp.float32)[:, None] * x.astype(jnp.float32)).astype(x.dtype)
        if not with_aux:
            return y
        log_decay = _log_decays(lax.stop_gradient(dt), lax.stop_gradient(a), chunk).reshape(bsz, length, heads)
        return y, Aux(lax.stop_gradient(last).reshape(bsz, heads, *last.shape[-2:]), log_decay)


def ssm_scan_reference(x, dt, a, b, c, d=None):
    """The recurrence position by position, in the operands' precision
    promoted to float32: ``(y [B, L, H, P], the last state [B, H, P, N])``."""
    f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
    x, dt, a, b, c = f32(x), f32(dt), f32(a), f32(b), f32(c)
    per = x.shape[2] // b.shape[2]
    b, c = jnp.repeat(b, per, axis=2), jnp.repeat(c, per, axis=2)  # a head's own B and C

    def step(state, at):
        x_t, dt_t, b_t, c_t = at  # [B, H, P], [B, H], [B, H, N], [B, H, N]
        state = jnp.exp(dt_t * a)[..., None, None] * state + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return state, jnp.einsum("bhpn,bhn->bhp", state, c_t)

    first = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:], jnp.float32)
    last, y = lax.scan(step, first, tuple(jnp.moveaxis(t, 1, 0) for t in (x, dt, b, c)))
    y = jnp.moveaxis(y, 0, 1)
    return (y if d is None else y + f32(d)[:, None] * x), last


@jax.custom_vjp
def causal_conv(x, w, bias):
    """Causal depthwise convolution over the sequence: ``y_t = bias + sum_j
    w[j] x_{t - (K - 1) + j}`` a channel, zeros before the sequence's start;
    ``x`` [B, L, C], ``w`` [K, C], ``bias`` [C].  K shifted multiply-adds in
    float32, one downcast."""
    taps, length = w.shape[0], x.shape[1]
    with jax.named_scope("ssm_conv"):
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
        y = bias.astype(jnp.float32) + sum(w[j].astype(jnp.float32) * padded[:, j:j + length] for j in range(taps))
        return y.astype(x.dtype)


def _conv_fwd(x, w, bias):
    return causal_conv(x, w, bias), (x, w, bias[:0])


def _conv_bwd(res, g):
    x, w, like = res
    taps, length = w.shape[0], x.shape[1]
    with jax.named_scope("ssm_conv"):
        # the same sum on the flipped taps, and a reduction a tap
        ahead = jnp.pad(g, ((0, 0), (0, taps - 1), (0, 0))).astype(jnp.float32)
        dx = sum(w[j].astype(jnp.float32) * ahead[:, taps - 1 - j:taps - 1 - j + length] for j in range(taps))
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0))).astype(jnp.float32)
        g32 = g.astype(jnp.float32)
        dw = jnp.stack([jnp.sum(g32 * padded[:, j:j + length], axis=(0, 1)) for j in range(taps)])
        return dx.astype(x.dtype), dw.astype(w.dtype), jnp.sum(g32, axis=(0, 1)).astype(like.dtype)


causal_conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv_reference(x, w, bias):
    """The convolution a position at a time (float32)."""
    taps, length = w.shape[0], x.shape[1]
    x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    rows = []
    for t in range(length):
        seen = [w[j] * x[:, t - (taps - 1) + j] for j in range(taps) if t - (taps - 1) + j >= 0]
        rows.append(bias.astype(jnp.float32) + sum(seen))
    return jnp.stack(rows, axis=1)


def gated_group_norm(y, z, gain, groups: int, eps: float):
    """``rmsnorm`` over each GROUP's channels of ``y * silu(z)``, times a
    gain a channel (the gate BEFORE the norm): ``y``, ``z`` [..., C], ``gain``
    [C].  Statistics and arithmetic in float32, one downcast."""
    with jax.named_scope("ssm_norm"):
        v = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        by_group = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
        by_group = by_group * lax.rsqrt(jnp.mean(jnp.square(by_group), axis=-1, keepdims=True) + eps)
        return (by_group.reshape(v.shape) * gain.astype(jnp.float32)).astype(y.dtype)


def gated_group_norm_reference(y, z, gain, groups: int, eps: float):
    """The same a group at a time (float32)."""
    y, z, gain = (t.astype(jnp.float32) for t in (y, z, gain))
    v = y * (z / (1.0 + jnp.exp(-z)))
    size = v.shape[-1] // groups
    parts = []
    for g in range(groups):
        part = v[..., g * size:(g + 1) * size]
        parts.append(part / jnp.sqrt(jnp.mean(part * part, axis=-1, keepdims=True) + eps))
    return jnp.concatenate(parts, axis=-1) * gain
