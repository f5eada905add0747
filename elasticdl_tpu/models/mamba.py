"""``nemotron_h``'s state-space part of a ``moe_lm`` layer (``models/parts.py``:
what a part is): Mamba-2's mixer (``ops/ssm``), H heads of P in G groups,
state N, over the HELD heads and their groups; ``u`` the normed stream:

    (z, xBC, dt) = u W_in                  columns (H P | H P + 2 G N | H) of the held heads and groups
    (x, B, C) = silu(conv(xBC))            causal, depthwise, ``conv_kernel`` taps and a bias a channel
    dt = softplus(dt + dt_bias) ; a_t = exp(-exp(A_log) dt_t)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T ; y_t = S_t C_t + D x_t      S_0 = 0 at each sequence's start
    part = rmsnorm over each GROUP's channels of (y * silu(z)), times a gain, then W_out

What the heads that are not held would add is left out.  A sharded sequence
is refused: the state at a shard's start lives on the shard before it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.models.parts import Draws, Part
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops import ssm as ssm_ops

#: The state-space layers' counts a step reports (``ModelSpec.step_counters``;
#: gauges ``edl_ssm_positions*_total``): what the traffic asks of the scan,
#: from shapes (the operator's measure of scanned work), and the part of it
#: the scan's kernels took, each layer's counted where its scan is called
#: (read by ``ssm_scan_kernel_pct.ssm``: the second over the first).
SSM_COUNTERS = {
    "ssm_positions": "(head, position) pairs the state-space scans advanced a state over, from the "
    "shapes they were called with, summed over layers, training steps and devices",
    "ssm_positions_kernel": "those of them whose chunks the scan's Pallas kernels computed (ops/ssm_kernels.py: "
    "on a TPU inside their contract, decided when the step is traced), summed likewise",
}


@dataclasses.dataclass(frozen=True)
class MambaMixer(Part):
    """Mamba-2's mixer over ``heads`` HELD heads of ``head_dim`` in
    ``groups`` groups, the scan in chunks of ``chunk``.  ``ssm_in`` and
    ``ssm_out`` are drawn normal (``ssm_out`` scaled by ``into_stream``:
    ``rescale_prenorm_residual``), ``A_log`` = log uniform(1, 16),
    ``dt_bias`` the inverse softplus of a log-uniform draw in ``dt_range``
    (min, max, floor), ``D`` = 1; the taps and their bias
    uniform(+-``conv_kernel``^-1/2)."""

    heads: int
    head_dim: int
    groups: int
    state: int
    conv_kernel: int
    chunk: int
    eps: float
    dt_range: Tuple[float, float, float]
    into_stream: float = 1.0

    counters = SSM_COUNTERS

    def init(self, draw: Draws, d: int):
        heads, inner, taps = self.heads, self.heads * self.head_dim, self.conv_kernel
        conv_dim = inner + 2 * self.groups * self.state
        lo, hi, floor = self.dt_range
        dt = jnp.maximum(jnp.exp(draw.uniform((heads,), jnp.log(lo), jnp.log(hi))), floor)
        return {
            "ssm_in": draw.normal((d, inner + conv_dim + heads)),
            "conv_w": draw.uniform((taps, conv_dim), -taps ** -0.5, taps ** -0.5),
            "conv_b": draw.uniform((conv_dim,), -taps ** -0.5, taps ** -0.5),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "A_log": jnp.log(draw.uniform((heads,), 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "ssm_norm": jnp.ones((inner,), jnp.float32),
            "ssm_out": draw.normal((inner, d), self.into_stream),
        }

    def apply(self, u, blk, positions, axis, cast):
        if axis is not None and axis_size(axis) > 1:
            raise ValueError("a state-space layer over a sharded sequence is not supported: the state at a shard's start lives on the shard before it")
        b, l, _ = u.shape
        heads, groups, state, inner = self.heads, self.groups, self.state, self.heads * self.head_dim
        conv_dim = inner + 2 * groups * state
        with jax.named_scope("ssm_proj"):
            # Each part is multiplied by its own column block of the published
            # matrix (a slice of the WEIGHT, as latent attention's); z and xBC
            # are save sites (ops/remat.py).
            w_in = blk["ssm_in"]
            z = remat_lib.product("ssm_z", u, cast(w_in[:, :inner]))
            xbc = remat_lib.product("ssm_xbc", u, cast(w_in[:, inner:inner + conv_dim]))
            dt = (u @ cast(w_in[:, inner + conv_dim:])).astype(jnp.float32)
        xbc = ssm_ops.causal_conv(xbc, blk["conv_w"], blk["conv_b"])
        with jax.named_scope("ssm_conv"):
            xbc = jax.nn.silu(xbc)
            x = xbc[..., :inner].reshape(b, l, heads, inner // heads)
            bm = xbc[..., inner:inner + groups * state].reshape(b, l, groups, state)
            cm = xbc[..., inner + groups * state:].reshape(b, l, groups, state)
            dt = jax.nn.softplus(dt + blk["dt_bias"])
        y = ssm_ops.ssm_scan(x, dt, -jnp.exp(blk["A_log"]), bm, cm, blk["D"], chunk=self.chunk)
        # counted where the scan is called, from what it was called with
        by_kernels = ssm_ops.scan_path(x, bm, self.chunk)[0] != ssm_ops.PATH_XLA_REFERENCE
        counts = {"ssm_positions": jnp.float32(b * l * heads), "ssm_positions_kernel": jnp.float32(b * l * heads * by_kernels)}
        y = ssm_ops.gated_group_norm(y.reshape(b, l, inner), z, blk["ssm_norm"], groups, self.eps)
        with jax.named_scope("ssm_proj"):
            return y @ cast(blk["ssm_out"]), counts
