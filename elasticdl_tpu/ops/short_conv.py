"""A short-convolution chain of a linear-attention layer as ONE op:

    short_conv(t, taps, head_dim) = l2norm_a_head(silu(conv(t, taps)))     (no ``head_dim``: silu(conv(t, taps)))

``conv`` the causal depthwise convolution of ``ops/ssm.causal_conv`` without a
bias (``y_t = sum_j taps[j] t_{t - (K - 1) + j}`` a channel, zeros before the
sequence's start), ``l2norm(x) = x rsqrt(sum(x^2) + eps)`` over each head's
``head_dim`` channels.  ``t`` [B, L, C], ``taps`` [K, C]; each row of the
batch is one sequence.  Float32 multiply-adds, silu and statistics, ONE
downcast to ``t``'s type.

The op is ops/short_conv_kernels.py's pair under ONE ``custom_vjp`` whose
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
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import short_conv_kernels as kernels
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE

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
    outside = outside_conv_contract(t, taps, head_dim)
    if interpret is not None:
        if outside:
            raise ValueError(f"the convolution chain's kernels were asked for outside their contract: {outside}")
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
