"""The gated delta rule with a decay per key CHANNEL (Kimi Delta Attention,
arXiv:2510.26692; the delta rule of arXiv:2406.06484 / 2412.06464 with a
diagonal gate) in its CHUNKED form with a backward, and the gated norm a head
that follows it.

The rule.  H heads, a state ``S`` [dk, dv] a head, zero at a sequence's
start; per position t, with ``alpha_t = exp(g_t)`` in (0, 1)^dk (``g`` the
log-decay, <= 0) and ``beta_t`` in (0, 1):

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T ;  o_t = S_t^T q_t
        = Diag(alpha_t) S_{t-1} + k_t u_t^T ,   u_t = beta_t (v_t - S_{t-1}^T (alpha_t * k_t))

``delta_rule`` computes it a chunk of ``chunk`` positions at a time.  With
``G_r`` the log-decays summed from the chunk's start through r (``Gamma_r =
exp(G_r)``, a channel each) and ``S_0`` the state at the chunk's start:

    A[r, i] = beta_r sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])      (i < r, else 0)
    (I + A) [W | Y] = Diag(beta) [K * Gamma | V]                   the unit-lower-triangular SOLVE a chunk
    U = Y - W S_0                                                  (U's rows are the u_r)
    P[r, i] = sum_c q_r[c] k_i[c] exp(G_r[c] - G_i[c])             (i <= r, else 0)
    o_r = (q_r * Gamma_r)^T S_0 + sum_i P[r, i] u_i
    S_C = Diag(Gamma_C) S_0 + sum_i (k_i * exp(G_C - G_i)) u_i^T   across chunks: AFFINE in S_0 with a matrix
                                                                   (U depends on S_0): two [C, dk] x [dk, dv]
                                                                   products a head and step, L / chunk steps

**The decay mask cannot be factored over a whole chunk.**  ``exp(G_r - G_i)``
as ``Gamma_r / Gamma_i`` needs ``1 / Gamma_i``, and a channel's log-decay is
``-exp(A_log) softplus(.)``, as low as -16 a position at the published ranges
and lower in a trained model: summed over 64 positions it leaves float32's
exponent (``exp(88.7)`` is the largest) and the quotient is ``0 / 0`` or
``inf``.  So differences are formed against a REFERENCE POINT A SUB-BLOCK of
``SUB`` = 16 positions, ``R_I`` = the sums at the last position BEFORE
sub-block I (0 for the first), and NO exponent is ever positive:

- rows r in sub-block I against columns i in an EARLIER sub-block: ``G_r -
  G_i = (G_r - R_I) + (R_I - G_i)``, both <= 0 (the sums only fall): the row
  factor ``x_r * exp(G_r - R_I)`` [C, dk] against the column factor ``k_i *
  exp(R_I - G_i)`` [C / SUB, C, dk] (one a row sub-block; masked BEFORE the
  exp where i is not earlier), an MXU product a sub-block row.  A factor that
  underflows to 0 is right: the product is smaller still;
- rows and columns of the SAME sub-block: the differences themselves,
  ``exp(G_r - G_i)`` for i <= r, a [SUB, SUB, dk] array a sub-block (16 x the
  size of k), summed over the channels on the VPU.  This is the choice FLA's
  kernels make too.  Both passes scan a sequence ``GROUP`` chunks at a time
  (below), so neither holds a sequence's column factors or differences at
  once.

A chunk that is not whole sub-blocks is padded to them with positions that
change nothing (k = q = v = 0, beta = 0, log-decay 0).

Precision.  The log-decays, their sums, every decay factor, the solve and the
carried state are float32 whatever the operands are (a float32 island, as
``ops/ssm.py``'s: a sequence's state is a product of L factors near 1); the
products take the operands' type (bfloat16 in a job) and accumulate in
float32.  The state's decay across a chunk is an elementwise float32 multiply,
never part of a matrix product.

ONE ``custom_vjp``: residuals are the operands and the states at the chunks'
starts ([B, L / chunk, H, dk, dv] float32: 256 MiB a layer at L = 8192, 32
heads of 128 x 128); the backward carries the states' gradients back over the
chunks (the same recurrence, transposed) and differentiates the chunks' own
arithmetic again from the operands.  Both passes are ONE ``lax.scan`` over
groups of ``GROUP`` chunks, the state (or its gradient) the scan's carry: a
step builds its group's masks and solves, calls ``_carry`` over the group's
chunks and computes the group's outputs (or gradients), so that nothing of a
whole sequence's size but the operands, the outputs and that residual is ever
alive (the op's backward at the published shapes compiles to 0.6 GiB of
temporaries; with every chunk at once it was 3.2).

Seams.  Three functions of their own that the benchmark's controls swap by
module attribute and that every kernel must keep calling:
``_log_decays(g, chunk)`` (the sums; ``bfloat16_decay`` rounds them),
``_carry(ends, decay, left, right, first, reverse)`` (the recurrence over the
chunks, forward and — transposed — backward; ``no_carried_state`` zeroes the
start states) and ``_solve(a, rhs)`` (``no_delta_correction`` returns ``rhs``:
``T = Diag(beta)``, plain gated linear attention).  ``_solve`` is ``(I +
a)^-1 rhs`` by BLOCKED FORWARD SUBSTITUTION in float32 (``_inverse``: the
``SUB``-row diagonal blocks the masks already have row by row, the block rows
over them by products that keep float32's digits, then ONE product with the
right-hand sides) under a ``custom_vjp`` of its own (two products from the
inverse and the solution); XLA's general ``triangular_solve`` was a third of
the scope's time on the chip (PR 48) and is the tests' reference now.  There
is ONE implementation: ``_chunk_parts`` looks ``_solve`` up in the module at
call time, forward and inside ``_rule_bwd``'s ``jax.vjp``, so the control
still takes the solve out of both passes.  A kernel that summed the decays
itself, solved by itself or kept the state in VMEM across a sequential grid
axis would disarm the controls in silence (``ops/ssm.py`` has the same
warning).  The ONE kernel pair there is (below) stands clear of all three: it
CONSUMES ``cum``, the sums ``_log_decays`` made (rounded sums give rounded
masks: the control bites through it), and hands its masks to the same
``_solve`` and ``_carry``.

What runs where.  The SAME-SUB-BLOCK differences inside ``_masks_of`` have
two implementations of the one arithmetic, chosen by what a call can observe
(``mask_path``; no flag):

- the Pallas kernel pair of ``ops/delta_rule_kernels.py`` on a TPU inside its
  contract (``outside_mask_contract``: ``dk`` whole multiples of 128, the
  padded chunk 16, 32, 64 or 128; the published widths are inside), joined by
  ONE ``custom_vjp`` (``_same_sub_block_kernels``) whose residuals are the
  operands: forward ``(q, k, cum) -> (kk, qk)`` written as the block diagonal
  of [C, C], backward ``(q, k, cum, g_kk, g_qk) -> (g_q, g_k, g_cum)`` with
  the exponent computed again in VMEM.  As XLA fusions the [SUB, SUB, dk]
  float32 factors went through HBM, 134 MB a scan step and several times over
  in the backward: 71 ms of a 534 ms step (PERF.md section 6, PR 49);
- ``_same_sub_block`` (XLA) and its product onto the diagonal everywhere
  else: off the TPU (every CPU test and rehearsal), any width that is not
  whole lanes.

Everything else is XLA products and multiply-adds on either path: the sums,
the masks' cross-sub-block products, the solve, the carry, the outputs.  A
sequence that is not whole chunks takes the STEPWISE path
(``delta_rule_reference`` under AD), which ``rule_path`` says and the part
counts (``kda_positions_chunked``; ``kda_positions_mask_kernel`` counts the
kernels' share through ``mask_path``, and ONE ``attention path:`` line a
distinct call says which).  Scopes: ``kda_scan`` (forward and backward) and,
nested in it, ``kda_mask`` around ``_masks_of``'s arithmetic in both passes;
``gated_head_norm`` is traced under the caller's scope.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.ops import delta_rule_kernels, remat
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE, announce_path

#: positions a sub-block: the reference points of the decay mask (module docstring)
SUB = 16
#: chunks a step of the op's scan over a sequence: the masks' column factors (C / SUB x the size of k), the
#: same-sub-block differences ([SUB, SUB, dk] a sub-block: SUB x), the solves and their cotangents of so many
#: chunks are alive together, and no more
GROUP = 8
PATH_CHUNKED, PATH_STEPWISE = "chunked", "stepwise"


class Aux(NamedTuple):
    """What ``delta_rule(with_aux=True)`` hands out beside ``o`` (no gradient
    flows through either): the state after the last position and every
    position's log-decay summed from its chunk's start (the float32 island
    the benchmark's ``kda_decay`` check reads)."""

    state: jax.Array  # [B, H, dk, dv] float32
    log_decay: jax.Array  # [B, L, H, dk] float32


def rule_flops(batch: int, length: int, heads: int, dk: int, dv: int, chunk: int) -> int:
    """FLOPs of one forward pass of the chunked form, from shapes: a head and
    position the two masks' products (A and P: 2 x chunk x dk each), the solve
    (chunk x (dk + dv): the triangle's half of 2 x chunk x chunk x (dk + dv) a
    chunk), ``W S_0`` and the state's read-out (2 x dk x dv each), ``P U`` (2
    x chunk x dv) and the chunk's end state (2 x dk x dv)."""
    return batch * length * heads * (4 * chunk * dk + chunk * (dk + dv) + 2 * chunk * dv + 6 * dk * dv)


def rule_path(length: int, chunk: int):
    """``(PATH_CHUNKED | PATH_STEPWISE, why not chunked)`` for a sequence of
    ``length`` positions: the chunked form needs whole chunks."""
    if length % chunk:
        return PATH_STEPWISE, f"L = {length} is not whole chunks of {chunk}"
    return PATH_CHUNKED, ""


def _by_chunks(t, chunk: int, sub: int):
    """[B, L, H, ...] -> [B, n, H, C', ...], C' = ``chunk`` padded with zeros
    to whole sub-blocks."""
    t = jnp.moveaxis(t.reshape(t.shape[0], t.shape[1] // chunk, chunk, *t.shape[2:]), 2, 3)
    short = -chunk % sub
    return jnp.pad(t, [(0, 0)] * 3 + [(0, short)] + [(0, 0)] * (t.ndim - 4)) if short else t


def _log_decays(g, chunk: int):
    """[B, n, Q, H, dk] float32: each position's log-decay summed from its
    chunk's start (inclusive), a channel each."""
    # NOT ``jnp.cumsum``: on the TPU it lowers to a product with a triangle of
    # ones at the default (bfloat16) precision (ops/ssm._log_decays, PR 40).
    by_chunk = g.astype(jnp.float32).reshape(g.shape[0], g.shape[1] // chunk, chunk, *g.shape[2:])
    return lax.associative_scan(jnp.add, by_chunk, axis=2)


def _product(x, y):
    """A float32 product that keeps float32's digits on the MXU (the TPU's
    default rounds a float32 operand to bfloat16)."""
    return jnp.matmul(x, y, precision=lax.Precision.HIGHEST)


def _inverse(a):
    """``(I + a)^-1`` [..., C, C] float32 for ``a`` strictly lower triangular
    (what lies on or above the diagonal is not read), by blocks of ``sub`` =
    min(SUB, C) rows.  Each diagonal block ``I + N`` by FORWARD SUBSTITUTION
    row by row (row r of the inverse is ``e_r - N[r, :r] X[:r]``), every block
    of the operand at once and in the minor dimension (the lanes), so that a
    step is a few full-width multiply-adds: ``sub - 1`` small steps.  Then the
    block rows one after the other, ``T[I, :I] = -D_I (a[I, :I] T[:I, :I])``:
    two products a block row.  NOT the nilpotent product ``(I - a)(I +
    a^2)(I + a^4)...``: where keys repeat its powers reach 1e17 at 64 rows."""
    size = a.shape[-1]
    sub = min(SUB, size)
    diagonal = jnp.stack([a[..., at:at + sub, at:at + sub] for at in range(0, size, sub)], axis=-3)  # [.., s, sub, sub]
    by_block = jnp.moveaxis(diagonal.reshape(-1, sub, sub), 0, -1)  # [r, j, blocks]
    eye = jnp.eye(sub, dtype=a.dtype)
    rows = [jnp.broadcast_to(eye[0][:, None], by_block.shape[1:])]
    for r in range(1, sub):
        rows.append(eye[r][:, None] - jnp.sum(by_block[r, :r, None, :] * jnp.stack(rows), axis=0))
    inverses = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(diagonal.shape)
    t = inverses[..., 0, :, :]  # the inverse of the leading [at, at] of I + a
    for at in range(sub, size, sub):
        d = inverses[..., at // sub, :, :]
        row = -_product(d, _product(a[..., at:at + sub, :at], t))
        t = jnp.concatenate([jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, sub)]), jnp.concatenate([row, d], axis=-1)], axis=-2)
    return t


@jax.custom_vjp
def _solve(a, rhs):
    """``(I + a)^-1 rhs`` for ``a`` [..., C, C] strictly lower triangular
    (what lies on or above the diagonal is not read) and ``rhs`` [..., C, n],
    float32: ``_inverse`` (blocked forward substitution) and ONE product.
    Its gradients are two products from the inverse and the solution, not a
    derivative through the substitution's steps."""
    return _solve_fwd(a, rhs)[0]


def _solve_fwd(a, rhs):
    t = _inverse(a)
    x = _product(t, rhs)
    return x, (t, x)


def _solve_bwd(res, g_x):
    t, x = res
    g_rhs = _product(jnp.swapaxes(t, -1, -2), g_x)  # (I + a)^-T g_x
    return -jnp.tril(_product(g_rhs, jnp.swapaxes(x, -1, -2)), -1), g_rhs


_solve.defvjp(_solve_fwd, _solve_bwd)


def _carry(ends, decay, left, right, first, reverse: bool = False):
    """The recurrence over the chunks, ``S_c+1 = decay_c * S_c - left_c^T
    (right_c S_c) + ends_c`` from ``first``: ``ends`` [B, n, H, dk, dv] and
    ``decay`` [B, n, H, dk] float32, ``left`` / ``right`` [B, n, H, C, dk] in
    the operands' type.  Returns (the states at the chunks' starts [B, n,
    ...], the state after the last).  Forward ``left`` is ``k * exp(G_C - G)``
    and ``right`` is W; ``reverse``: from the last chunk down with the two
    exchanged, for the states' gradients (then "starts" are the gradient each
    chunk sees of the state it hands on).  The state is float32; each product
    reads it in the operands' type and accumulates in float32."""
    def step(state, of_chunk):
        end, dec, lt, rt = of_chunk
        inner = jnp.einsum("bhck,bhkv->bhcv", rt, state.astype(rt.dtype), preferred_element_type=jnp.float32)
        back = jnp.einsum("bhck,bhcv->bhkv", lt, inner.astype(lt.dtype), preferred_element_type=jnp.float32)
        return dec[..., None] * state - back + end, state

    by_step = tuple(jnp.moveaxis(t, 1, 0) for t in (ends, decay, left, right))
    last, starts = lax.scan(step, first, by_step, reverse=reverse)
    return jnp.moveaxis(starts, 0, 1), last


class _Parts(NamedTuple):
    """A chunk's own arithmetic (nothing here reads a state): [B, n, H, ...]."""

    w: jax.Array  # [.., C, dk] operands' type: T (K * Gamma)
    y: jax.Array  # [.., C, dv] float32: T V
    p: jax.Array  # [.., C, C] operands' type: the masked q-k products
    q_in: jax.Array  # [.., C, dk] operands' type: q * Gamma
    k_out: jax.Array  # [.., C, dk] operands' type: k * exp(G_C - G)
    decay: jax.Array  # [.., dk] float32: Gamma_C


def _same_sub_block(q, k, cum):
    """The two masks inside each sub-block from the differences themselves:
    ``q``, ``k``, ``cum`` [..., s, SUB, dk] float32 -> ``(sum_c k_r k_i
    exp(G_r - G_i), sum_c q_r k_i exp(G_r - G_i))`` [..., s, SUB, SUB], i <= r
    (masked BEFORE the exp: above the diagonal the difference is positive)."""
    sub = cum.shape[-2]
    seen = lax.iota(jnp.int32, sub)[:, None] >= lax.iota(jnp.int32, sub)[None, :]
    factor = jnp.exp(jnp.where(seen[:, :, None], cum[..., :, None, :] - cum[..., None, :, :], -jnp.inf)) * k[..., None, :, :]
    return jnp.sum(k[..., :, None, :] * factor, -1), jnp.sum(q[..., :, None, :] * factor, -1)


def outside_mask_contract(k, chunk: int) -> str:
    """Why the same-sub-block masks of ``k`` [..., dk] in chunks of ``chunk``
    are outside the kernels' contract (``""``: inside): a position's channels
    are whole lanes and a 128-lane group whole chunks of whole sub-blocks
    (ops/delta_rule_kernels.py)."""
    size = chunk + -chunk % min(SUB, chunk)  # the padded chunk
    if k.shape[-1] % 128:
        return f"dk = {k.shape[-1]} is not whole multiples of 128"
    if size % SUB or 128 % size:
        return f"the padded chunk {size} is not 16, 32, 64 or 128"
    return ""


def mask_path(k, chunk: int, interpret: Optional[bool] = None):
    """Which path ``_masks_of`` takes for the same-sub-block differences of
    ``k`` [..., dk] (its width is read) in chunks of ``chunk``, from what the
    code can observe: ``(one of ring_attention's PATH_*, why not the
    kernels)``.  The kernels compiled on a TPU inside their contract,
    ``_same_sub_block`` in XLA everywhere else; ``interpret`` given (tests):
    the kernels, in the Pallas interpreter or compiled."""
    outside = outside_mask_contract(k, chunk)
    if interpret is not None:
        if outside:
            raise ValueError(f"the mask kernels were asked for outside their contract: {outside}")
        return (PATH_PALLAS_INTERPRET if interpret else PATH_PALLAS_COMPILED), ""
    backend = jax.default_backend()
    why_not = f"backend={backend}" if backend != "tpu" else outside
    return (PATH_XLA_REFERENCE if why_not else PATH_PALLAS_COMPILED), why_not


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _same_sub_block_kernels(q, k, cum, interpret: bool):
    """``_same_sub_block`` by ops/delta_rule_kernels.py, ONTO the diagonal:
    ``q``, ``k`` [..., C, dk] (the operands' type) and ``cum`` [..., C, dk]
    float32 -> the two masks [..., C, C] float32, zero outside the
    sub-blocks' diagonal blocks.  The residuals are the operands: nothing of
    [SUB, SUB, dk] size reaches HBM in either pass."""
    return _same_sub_block_fwd(q, k, cum, interpret)[0]


def _flat(t):
    """[..., C, width] -> the kernels' [N, width]: a free view."""
    return t.reshape(-1, t.shape[-1])


def _same_sub_block_fwd(q, k, cum, interpret):
    size = cum.shape[-2]  # whole sub-blocks of SUB (``outside_mask_contract``)
    masks = delta_rule_kernels.masks(_flat(q), _flat(k), _flat(cum), chunk=size, sub=SUB, interpret=interpret)
    return tuple(t.reshape(*cum.shape[:-1], size) for t in masks), (q, k, cum)


def _same_sub_block_bwd(interpret, res, grads):
    cum = res[2]
    of_operands = delta_rule_kernels.mask_grads(*map(_flat, (*res, *grads)), chunk=cum.shape[-2], sub=SUB, interpret=interpret)
    return tuple(t.reshape(cum.shape) for t in of_operands)


_same_sub_block_kernels.defvjp(_same_sub_block_fwd, _same_sub_block_bwd)


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _masks_of(q, k, cum, sub: int):
    """``(sum_c k_r k_i exp(G_r - G_i), sum_c q_r k_i exp(G_r - G_i))`` [B, n,
    H, C, C] float32 for i <= r (0 elsewhere) from ``q``, ``k`` [B, n, H, C,
    dk] and the sums ``cum`` [.., C, dk] float32, by the module docstring's
    reference point a sub-block.  Rematerialised: a gradient holds its
    operands, not the column factors (C / SUB x the size of k) or the
    same-sub-block differences (SUB x; on the kernels' path they never exist
    outside VMEM).  Scope ``kda_mask`` (under ``kda_scan``, both passes)."""
    with jax.named_scope("kda_mask"):
        lead, (size, dk) = cum.shape[:3], cum.shape[3:]
        n_sub = size // sub
        f32 = lambda t: t.astype(jnp.float32)  # noqa: E731
        blocks = lambda t: t.reshape(*lead, n_sub, sub, dk)  # noqa: E731
        # R_I: the sums at the last position before sub-block I
        before = jnp.concatenate([jnp.zeros_like(cum[..., :1, :]), cum[..., sub - 1:size - 1:sub, :]], axis=-2)  # [.., s, dk]
        rows = jnp.exp(blocks(cum) - before[..., :, None, :])  # exp(G_r - R_I) <= 1
        earlier = (lax.iota(jnp.int32, size)[None, :] // sub) < lax.iota(jnp.int32, n_sub)[:, None]  # [s, C]: column i before sub-block I
        columns = jnp.exp(jnp.where(earlier[:, :, None], before[..., :, None, :] - cum[..., None, :, :], -jnp.inf))  # [.., s, C, dk]
        columns = (f32(k)[..., None, :, :] * columns).astype(k.dtype)
        across = lambda x: jnp.einsum(  # noqa: E731 — a sub-block row's [SUB, dk] x [dk, C]
            "...src,...sic->...sri", (blocks(f32(x)) * rows).astype(k.dtype), columns, preferred_element_type=jnp.float32).reshape(*lead, size, size)
        path, _ = mask_path(k, size)
        if path == PATH_XLA_REFERENCE:
            kk, qk = _same_sub_block(blocks(f32(q)), blocks(f32(k)), blocks(cum))
            onto_diagonal = lambda t: jnp.einsum("...sri,st->...srti", t, jnp.eye(n_sub, dtype=jnp.float32)).reshape(*lead, size, size)  # noqa: E731
            kk, qk = onto_diagonal(kk), onto_diagonal(qk)
        else:  # the kernels write the block diagonal of [C, C] themselves
            kk, qk = _same_sub_block_kernels(q, k, cum, path == PATH_PALLAS_INTERPRET)
        return across(k) + kk, across(q) + qk


def _chunk_parts(q, k, v, g, beta, chunk: int) -> _Parts:
    sub = min(SUB, chunk)
    dt = k.dtype
    qc, kc, vc = (_by_chunks(t, chunk, sub) for t in (q, k, v))
    bc = _by_chunks(beta.astype(jnp.float32), chunk, sub)  # [B, n, H, C']
    cum = jnp.moveaxis(_log_decays(g, chunk), 2, 3)  # [B, n, H, C, dk]
    short = -chunk % sub
    if short:  # the padding's log-decay is 0: the sums stay at the chunk's last
        cum = jnp.pad(cum, [(0, 0)] * 3 + [(0, short), (0, 0)], mode="edge")
    size = cum.shape[3]
    kk, qk = _masks_of(qc, kc, cum, sub)
    strictly = lax.iota(jnp.int32, size)[:, None] > lax.iota(jnp.int32, size)[None, :]
    a = jnp.where(strictly, bc[..., None] * kk, 0.0)
    gamma = jnp.exp(cum)
    k32 = kc.astype(jnp.float32)
    rhs = bc[..., None] * jnp.concatenate([k32 * gamma, vc.astype(jnp.float32)], axis=-1)
    solved = _solve(a, rhs)
    dk = kc.shape[-1]
    return _Parts(
        w=solved[..., :dk].astype(dt), y=solved[..., dk:], p=qk.astype(dt),
        q_in=(qc.astype(jnp.float32) * gamma).astype(dt),
        k_out=(k32 * jnp.exp(cum[..., -1:, :] - cum)).astype(dt),
        decay=gamma[..., -1, :],
    )


def _new_values(parts: _Parts, starts):
    """U = Y - W S_0 [B, n, H, C, dv] float32."""
    return parts.y - jnp.einsum("bnhck,bnhkv->bnhcv", parts.w, starts.astype(parts.w.dtype), preferred_element_type=jnp.float32)


def _outputs(parts: _Parts, starts, chunk: int, dtype):
    """``o`` [B, L, H, dv] in ``dtype`` from the states at the chunks' starts."""
    dt = parts.w.dtype
    u = _new_values(parts, starts).astype(dt)
    o = jnp.einsum("bnhck,bnhkv->bnhcv", parts.q_in, starts.astype(dt), preferred_element_type=jnp.float32)
    o = o + jnp.einsum("bnhri,bnhiv->bnhrv", parts.p, u, preferred_element_type=jnp.float32)
    o = jnp.moveaxis(o[:, :, :, :chunk], 2, 3)  # [B, n, C, H, dv], the padding dropped
    return o.reshape(o.shape[0], -1, *o.shape[3:]).astype(dtype)


def _ends(parts: _Parts):
    """What a chunk adds to the state whatever its start: ``(k * exp(G_C -
    G))^T Y`` [B, n, H, dk, dv] float32."""
    return jnp.einsum("bnhck,bnhcv->bnhkv", parts.k_out, parts.y.astype(parts.k_out.dtype), preferred_element_type=jnp.float32)


def _next_states(parts: _Parts, starts):
    """Each chunk's end state from its start state (one step of ``_carry``,
    every chunk at once)."""
    u = _new_values(parts, starts).astype(parts.k_out.dtype)
    return parts.decay[..., None] * starts + jnp.einsum("bnhck,bnhcv->bnhkv", parts.k_out, u, preferred_element_type=jnp.float32)


def _group_of(n: int) -> int:
    """Chunks a group: the largest divisor of ``n`` chunks under ``GROUP``."""
    return next(d for d in range(min(GROUP, n), 0, -1) if n % d == 0)


def _by_groups(t, size: int):
    """[B, n x size, ...] -> [n, B, size, ...]: a ``lax.scan``'s steps."""
    return jnp.moveaxis(t.reshape(t.shape[0], t.shape[1] // size, size, *t.shape[2:]), 1, 0)


def _whole(t):
    """``_by_groups``' inverse: [n, B, size, ...] -> [B, n x size, ...]."""
    return jnp.moveaxis(t, 0, 1).reshape(t.shape[1], -1, *t.shape[3:])


def _forward(q, k, v, g, beta, chunk: int, keep: bool = False):
    """``(o, the last state, the states at the chunks' starts)``: ONE scan
    over groups of ``GROUP`` chunks, a step the group's own arithmetic, the
    seam ``_carry`` over its chunks from the state handed on, its outputs: no
    array of a whole sequence's masks, solves or end states exists."""
    group = _group_of(q.shape[1] // chunk)

    def step(state, of_group):
        parts = _chunk_parts(*of_group, chunk)
        starts, last = _carry(_ends(parts), parts.decay, parts.k_out, parts.w, state)
        return last, (_outputs(parts, starts, chunk, v.dtype), starts)

    first = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), jnp.float32)
    last, (o, starts) = lax.scan(step, first, tuple(_by_groups(t, group * chunk) for t in (q, k, v, g, beta)))
    o, starts = _whole(o), _whole(starts)
    # A save site (ops/remat.py): a rematerialised block that keeps the
    # outputs and the chunks' states runs no second forward of the op.
    work = rule_flops(q.shape[0], q.shape[1], q.shape[2], q.shape[3], v.shape[3], chunk)
    o, starts = remat.site("kda_scan_out", work, o, starts, keep=keep)
    return o, last, starts


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule(q, k, v, g, beta, chunk, keep):
    o, last, _ = _forward(q, k, v, g, beta, chunk)
    return o, last


def _rule_fwd(q, k, v, g, beta, chunk, keep):
    o, last, starts = _forward(q, k, v, g, beta, chunk, keep)
    return (o, last), (q, k, v, g, beta, starts)


def _rule_bwd(chunk, keep, res, grads):
    q, k, v, g, beta, starts = res
    g_o, g_last = grads
    group = _group_of(starts.shape[1])

    def step(g_state, of_group):
        # Three steps a group, as ops/ssm's a sequence: what o asks of the
        # chunks' start states, that carried back over the group's chunks
        # from the gradient of the state the group hands on (``_carry``
        # transposed: reverse, its two factors exchanged), the chunks' own
        # arithmetic transposed.  The group's forward is made once.
        *operands, s, g_out = of_group

        def chunks(q, k, v, g, beta, s):
            # the chunks' own arithmetic, again: every chunk from its start state
            parts = _chunk_parts(q, k, v, g, beta, chunk)
            return (_outputs(parts, s, chunk, v.dtype), _next_states(parts, s)), parts

        (_, handed_on), transpose, parts = jax.vjp(chunks, *operands, s, has_aux=True)
        from_o = transpose((g_out, jnp.zeros_like(handed_on)))[-1]
        g_next, g_first = _carry(from_o, parts.decay, parts.w, parts.k_out, g_state, reverse=True)
        return g_first, transpose((g_out, g_next))[:-1]

    with jax.named_scope("kda_scan"):
        xs = tuple(_by_groups(t, group * chunk) for t in (q, k, v, g, beta)) + (_by_groups(starts, group), _by_groups(g_o, group * chunk))
        _, of_operands = lax.scan(step, g_last.astype(jnp.float32), xs, reverse=True)
        return tuple(_whole(t) for t in of_operands)


_rule.defvjp(_rule_fwd, _rule_bwd)


def delta_rule(q, k, v, g, beta, *, chunk: int = 64, with_aux: bool = False):
    """The gated delta rule (module docstring): ``q``, ``k`` [B, L, H, dk]
    (``q`` already scaled), ``v`` [B, L, H, dv], ``g`` [B, L, H, dk] (the
    log-decay a channel, <= 0), ``beta`` [B, L, H] -> ``o`` [B, L, H, dv] in
    ``v``'s type.  Each row of the batch is one sequence from a zero state.
    A sequence that is not whole chunks takes the stepwise path
    (``rule_path``).  ``with_aux``: ``(o, Aux)``."""
    bsz, length, heads, dk = k.shape
    if q.shape != k.shape or g.shape != k.shape or v.shape[:3] != k.shape[:3] or beta.shape != (bsz, length, heads):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}: shapes do not agree")
    with jax.named_scope("kda_scan"):
        if rule_path(length, chunk)[0] == PATH_STEPWISE:
            o, last = delta_rule_reference(q, k, v, g, beta)
            o = o.astype(v.dtype)
            cum_chunk = length  # one chunk: the sums from the sequence's start
        else:
            path, why_not = mask_path(k, chunk)  # what ``_masks_of`` will ask, of the same width and chunk
            announce_path(path, k, True, f"kda_mask chunk={chunk}" + f"; {why_not}" * bool(why_not))
            # ``remat.kept``: asked here, while the primal is traced (as ops/ssm asks)
            o, last = _rule(q, k, v, g, beta, chunk, remat.kept("kda_scan_out"))
            cum_chunk = chunk
        if not with_aux:
            return o
        log_decay = _log_decays(lax.stop_gradient(g), cum_chunk).reshape(g.shape)
        return o, Aux(lax.stop_gradient(last), log_decay)


def delta_rule_reference(q, k, v, g, beta):
    """The recurrence position by position, in the operands' precision
    promoted to float32: ``(o [B, L, H, dv], the last state [B, H, dk, dv])``."""
    q, k, v, g, beta = (t.astype(jnp.float32) for t in (q, k, v, g, beta))

    def step(state, at):
        q_t, k_t, v_t, g_t, b_t = at  # [B, H, dk] x 2, [B, H, dv], [B, H, dk], [B, H]
        state = jnp.exp(g_t)[..., None] * state
        u_t = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t))
        state = state + k_t[..., :, None] * u_t[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    first = jnp.zeros(k.shape[:1] + k.shape[2:] + v.shape[-1:], jnp.float32)
    last, o = lax.scan(step, first, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), last


def gated_head_norm(o, gate, gain, eps: float):
    """``rmsnorm`` over each HEAD's channels of ``o`` [..., H, dv], times a
    gain shared by the heads [dv], THEN times ``sigmoid(gate)`` (the norm
    BEFORE the gate: not ``ops/ssm.gated_group_norm``, which gates with silu
    and then norms).  Statistics and arithmetic in float32, one downcast."""
    o32 = o.astype(jnp.float32)
    normed = o32 * lax.rsqrt(jnp.mean(jnp.square(o32), axis=-1, keepdims=True) + eps) * gain.astype(jnp.float32)
    return (normed * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(o.dtype)


def gated_head_norm_reference(o, gate, gain, eps: float):
    """The same a head at a time (float32)."""
    o, gate, gain = (t.astype(jnp.float32) for t in (o, gate, gain))
    heads = []
    for h in range(o.shape[-2]):
        part = o[..., h, :]
        heads.append(part / jnp.sqrt(jnp.mean(part * part, axis=-1, keepdims=True) + eps) * gain / (1.0 + jnp.exp(-gate[..., h, :])))
    return jnp.stack(heads, axis=-2)
