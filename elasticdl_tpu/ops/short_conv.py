"""The short-convolution chains of the token mixers, each as ONE op.  ``conv``
is the causal depthwise convolution of ``ops/ssm.causal_conv`` without a bias
(``y_t = sum_j taps[j] t_{t - (K - 1) + j}`` a channel, zeros before the
sequence's start); operands [B, L, C], ``taps`` [K, C]; each row of the batch
is one sequence.  Float32 multiply-adds and statistics, ONE downcast a result.

    short_conv(t, taps, head_dim) = l2norm_a_head(silu(conv(t, taps)))     (no ``head_dim``: silu(conv(t, taps)))
    gated_conv(b, c, z, taps)     = c * conv(b * z, taps)                  (NO activation: two gates around the convolution)

**The first** (a linear-attention layer's prelude to its scan; ``l2norm(x) = x
rsqrt(sum(x^2) + eps)`` over each head's ``head_dim`` channels) is
ops/short_conv_kernels.py's pair under ONE ``custom_vjp`` whose
residuals are its operands: forward ``(t, taps) -> y``, backward ``(t, taps,
g) -> (dt, dtaps)`` with the pre-activation, the silu and the norm computed
again in VMEM, each one read of the [B, L, C] arrays and one write.  As XLA
fusions under a ``jax.checkpoint`` of its own a chain ran three times a step
over float32 [L, C] intermediates and its convolution's gradient was four
shifted passes and four whole-array reductions (``ops/ssm._conv_bwd``): 35 of
a 67 ms scope, and 60 ms of pads, copies and reshapes around it (PERF.md
section 6, PR 52 and PR 53).

Which path a call takes is ``conv_path``'s to say, from what the code can
observe: the kernels compiled on a TPU inside their contract
(``outside_conv_contract``), the caller's XLA chain everywhere else (every CPU
test and rehearsal; a width that is not whole lanes; a length that is not
whole halos).  The CALLER branches on it (``models/linear_attention.py``: its
XLA chain is ``ops/ssm.causal_conv`` under its own ``jax.checkpoint``, as it
was), counts by it and logs it: this op is the kernels' side only.  Scope
``kda_conv``, both passes, under the caller's.

**The second** (``gated_conv``: a layer whose mixer IS the convolution, with
no attention and no recurrence; ``models/gated_conv.py``) reads THREE operands
a position and writes three gradients, and owns BOTH its paths under ONE
``custom_vjp`` whose residuals are its operands: the XLA chain
(``gated_conv_chain``: ``ops/ssm.causal_conv`` between two float32 products;
its backward is the chain's ``jax.vjp`` from the operands, which is what a
``jax.checkpoint`` of its own would do) and the kernel pair ``gated`` /
``gated_grads`` (forward one read of the three operands and one write; the
gradient from the operands and ``g``, with ``p = b z`` and ``conv(p)`` computed
again in VMEM, the rows after a block read as a halo).  ``gated_path`` says
which, as ``conv_path`` does and by the same contract without a head
(``outside_gated_contract``); the op logs ONE ``attention path:`` line a
distinct call with the reason.  Scope ``gated_conv``, both passes of either
path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import short_conv_kernels as kernels
from elasticdl_tpu.ops import ssm as ssm_ops
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE, announce_path

L2_EPS = 1e-6


def outside_conv_contract(t, taps, head_dim: Optional[int]) -> str:
    """Why the chain of ``t`` [B, L, C] under ``taps`` [K, C] is outside the
    kernels' contract (``""``: inside): channels in whole lanes and whole
    heads of whole lanes, a length in whole halos (a block's rows), the taps'
    reach inside one halo (ops/short_conv_kernels.py)."""
    length, channels = t.shape[-2:]
    if t.ndim != 3:
        return f"t {t.shape} is not [B, L, C]"
    if channels % kernels.LANES:
        return f"C = {channels} is not whole multiples of {kernels.LANES}"
    if head_dim is not None and (head_dim % kernels.LANES or channels % head_dim):
        return f"a head of {head_dim} is not whole multiples of {kernels.LANES} that divide C = {channels}"
    if length % kernels.HALO:
        return f"L = {length} is not whole multiples of {kernels.HALO}"
    if taps.shape[0] - 1 > kernels.HALO:
        return f"K - 1 = {taps.shape[0] - 1} reaches past a halo of {kernels.HALO}"
    return ""


def conv_path(t, taps, head_dim: Optional[int], interpret: Optional[bool] = None):
    """Which path the chain of ``t`` under ``taps`` takes (their shapes are
    read), from what the code can observe: ``(one of ring_attention's PATH_*,
    why not the kernels)``.  The kernels compiled on a TPU inside their
    contract, the caller's XLA chain everywhere else; ``interpret`` given
    (tests): the kernels, in the Pallas interpreter or compiled."""
    return _path_of(outside_conv_contract(t, taps, head_dim), interpret, "the convolution chain's")


def _path_of(outside: str, interpret: Optional[bool], whose: str):
    """``(path, why not the kernels)`` of a chain that is ``outside`` its
    kernels' contract for that reason (``""``: inside)."""
    if interpret is not None:
        if outside:
            raise ValueError(f"{whose} kernels were asked for outside their contract: {outside}")
        return (PATH_PALLAS_INTERPRET if interpret else PATH_PALLAS_COMPILED), ""
    backend = jax.default_backend()
    why_not = f"backend={backend}" if backend != "tpu" else outside
    return (PATH_XLA_REFERENCE if why_not else PATH_PALLAS_COMPILED), why_not


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _chain(t, taps, head_dim: Optional[int], interpret: bool):
    return _chain_fwd(t, taps, head_dim, interpret)[0]


def _chain_fwd(t, taps, head_dim, interpret):
    with jax.named_scope("kda_conv"):
        return kernels.chain(t, taps, head_dim=head_dim, eps=L2_EPS, interpret=interpret), (t, taps)


def _chain_bwd(head_dim, interpret, res, g):
    t, taps = res
    with jax.named_scope("kda_conv"):
        dt, of_taps = kernels.chain_grads(t, taps, g, head_dim=head_dim, eps=L2_EPS, interpret=interpret)
        return dt, jnp.sum(of_taps, axis=(0, 1)).astype(taps.dtype)


_chain.defvjp(_chain_fwd, _chain_bwd)


def short_conv(t, taps, head_dim: Optional[int] = None, *, interpret: bool = False):
    """The chain by the kernels (module docstring): ``t`` [B, L, C], ``taps``
    [K, C] -> [B, L, C] in ``t``'s type, the l2norm a head of ``head_dim``
    channels where one is given.  Outside the contract it raises: a caller
    asks ``conv_path`` first."""
    conv_path(t, taps, head_dim, interpret)  # asked for by name: raises outside the contract
    return _chain(t, taps, head_dim, interpret)


def outside_gated_contract(b, c, z, taps) -> str:
    """Why ``c * conv(b * z, taps)`` is outside the kernels' contract (``""``:
    inside): three operands of one shape and type, and the first chain's
    contract without a head (``outside_conv_contract``)."""
    if not (b.shape == c.shape == z.shape and b.dtype == c.dtype == z.dtype):
        return f"the operands differ: {b.shape} {b.dtype}, {c.shape} {c.dtype}, {z.shape} {z.dtype}"
    return outside_conv_contract(b, taps, None)


def gated_path(b, c, z, taps, interpret: Optional[bool] = None):
    """Which path the double-gated convolution of these operands takes (their
    shapes are read), as ``conv_path`` says it: ``(one of ring_attention's
    PATH_*, why not the kernels)``."""
    return _path_of(outside_gated_contract(b, c, z, taps), interpret, "the gated convolution's")


def gated_conv_chain(b, c, z, taps):
    """The XLA chain: ``ops/ssm.causal_conv`` between two products, float32
    from the operands to the ONE downcast of the result."""
    f32 = jnp.float32
    p = b.astype(f32) * z.astype(f32)
    conv = ssm_ops.causal_conv(p, taps.astype(f32), jnp.zeros((p.shape[-1],), f32))
    return (c.astype(f32) * conv).astype(b.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _gated(b, c, z, taps, path: str):
    return _gated_fwd(b, c, z, taps, path)[0]


def _gated_fwd(b, c, z, taps, path):
    with jax.named_scope("gated_conv"):
        if path == PATH_XLA_REFERENCE:
            return gated_conv_chain(b, c, z, taps), (b, c, z, taps)
        return kernels.gated(b, c, z, taps, interpret=path == PATH_PALLAS_INTERPRET), (b, c, z, taps)


def _gated_bwd(path, res, g):
    b, c, z, taps = res
    with jax.named_scope("gated_conv"):
        if path == PATH_XLA_REFERENCE:
            return jax.vjp(gated_conv_chain, b, c, z, taps)[1](g)
        db, dc, dz, of_taps = kernels.gated_grads(b, c, z, taps, g, interpret=path == PATH_PALLAS_INTERPRET)
        return db, dc, dz, jnp.sum(of_taps, axis=(0, 1)).astype(taps.dtype)


_gated.defvjp(_gated_fwd, _gated_bwd)


def gated_conv(b, c, z, taps, *, interpret: Optional[bool] = None):
    """``c * conv(b * z, taps)`` (module docstring, the second chain): three
    operands [B, L, C] and ``taps`` [K, C] -> [B, L, C] in the operands' type,
    by the path ``gated_path`` names for them (``interpret`` given: the
    kernels, which raise outside their contract).  Returns ``(y, whether the
    kernels computed it)``."""
    path, why_not = gated_path(b, c, z, taps, interpret)
    announce_path(path, b, True, f"gated_conv taps={taps.shape[0]}" + f"; {why_not}" * bool(why_not))
    return _gated(b, c, z, taps, path), path != PATH_XLA_REFERENCE
