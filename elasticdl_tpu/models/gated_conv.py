"""``lfm2_moe``'s convolution part of a ``moe_lm`` layer (``models/parts.py``:
what a part is): a layer with NO attention and NO recurrence, whose operator
is two elementwise gates around a short causal depthwise convolution
(``transformers``' ``Lfm2MoeShortConv``); ``u`` the normed stream, d wide:

    (B, C, z) = split3(u W_in)              W_in [d, 3d], no bias
    p = B * z ; c_t = sum_j taps[j] * p_{t - (K - 1) + j}     causal, depthwise, ``taps`` = ``conv_L_cache`` taps a
                                            channel, zeros before the sequence's start, no bias
    part = (C * c) W_out                    W_out [d, d]; NO activation anywhere in the operator

Each of the three products is made by its own column block of ``W_in`` (a
slice of the WEIGHT, as ``models/attentions.LatentAttention`` does): born
[B, L, d] as the op reads them, with no split of an activation, and the op's
three gradients are the three products' cotangents with no concatenation.  A
sharded sequence is refused: the taps' reach before a shard's start lives on
the shard before it.

Scopes: ``gconv_proj`` (the three products of ``W_in`` and ``W_out``: save
sites, ``ops/remat.py``), ``gated_conv`` (the op, both passes of either path:
``ops/short_conv.gated_conv`` says which and logs it).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.models.parts import Draws, Part
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops import short_conv as conv_ops

#: The convolution layers' counts a step reports (``ModelSpec.step_counters``;
#: gauges ``edl_gconv_positions*_total``): what the traffic asks of the
#: operator, from the shapes it was called with, and the part of it the Pallas
#: kernel pair computed, each layer's counted where the op is called, by what
#: the op itself said it ran (read by ``gconv_kernel_pct.gsc``: the second over
#: the first).
GCONV_COUNTERS = {
    "gconv_positions": "positions the double-gated short convolutions were computed at, from the shapes they were "
    "called with, summed over convolution layers, training steps and devices",
    "gconv_positions_kernel": "those of them ops/short_conv_kernels.py's pair computed (ops/short_conv.gated_path: a TPU, "
    "channels in whole lanes, L whole halos; the others took the XLA chain over ops/ssm.causal_conv), summed likewise",
}


@dataclasses.dataclass(frozen=True)
class GatedShortConv(Part):
    """The double-gated short convolution, ``taps`` taps a channel.  ``gconv_in``
    and ``gconv_out`` are drawn normal, the taps uniform(+-``taps``^-1/2) (a
    depthwise ``Conv1d``'s own init).  ``bias``: the convolution's (no cell
    runs one: refused)."""

    taps: int = 3
    bias: bool = False

    counters = GCONV_COUNTERS

    def __post_init__(self):
        if self.bias:
            raise ValueError("conv_bias true (a bias a channel on the short convolution) is not supported: no cell runs it")

    def init(self, draw: Draws, d: int):
        return {
            "gconv_in": draw.normal((d, 3 * d)),
            "gconv_taps": draw.uniform((self.taps, d), -self.taps ** -0.5, self.taps ** -0.5),
            "gconv_out": draw.normal((d, d)),
        }

    def apply(self, u, blk, positions, axis, cast):
        if axis is not None and axis_size(axis) > 1:
            raise ValueError("a gated short convolution over a sharded sequence is not supported: the taps' reach before a shard's start lives on the shard before it")
        bsz, l, d = u.shape
        with jax.named_scope("gconv_proj"):
            # the three products are save sites (ops/remat.py), each by its own column block of the weight
            w_in = cast(blk["gconv_in"])
            b, c, z = (remat_lib.product(f"gconv_{name}", u, w_in[:, i * d:(i + 1) * d]) for i, name in enumerate("bcz"))
        y, by_kernels = conv_ops.gated_conv(b, c, z, blk["gconv_taps"])
        # counted where the op is called, by what the op said it ran
        counts = {name: jnp.float32(bsz * l * part) for name, part in zip(GCONV_COUNTERS, (1, by_kernels))}
        with jax.named_scope("gconv_proj"):
            return y @ cast(blk["gconv_out"]), counts
