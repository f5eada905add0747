"""``kimi_linear``'s linear-attention part of a ``moe_lm`` layer
(``models/parts.py``: what a part is): Kimi Delta Attention (arXiv:2510.26692;
``ops/delta_rule``), H heads of dk = dv = ``head_dim``; ``u`` the normed
stream:

    q, k = l2norm(silu(conv(u Wq))), l2norm(silu(conv(u Wk)))   a head; v = silu(conv(u Wv))
                                               (causal, depthwise, ``conv_kernel`` taps, no bias, one
                                               convolution each; l2norm(x) = x / sqrt(sum(x^2) + 1e-6))
    g_t  = -exp(A_log) * softplus((u Wf_a) Wf_b + dt_bias)      [H, dk]: log alpha_t, a decay a CHANNEL,
                                               low rank through ``head_dim``; A_log a head
    beta = sigmoid(u Wb)                                        [H]
    S_t  = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T ; o_t = S_t^T (q_t dk^-1/2)
    part = (rmsnorm_per_head(o, gain) * sigmoid((u Wg_a) Wg_b)) Wo      gain [dv], shared by the heads

No projection carries a bias.  A sharded sequence is refused: the state at a
shard's start lives on the shard before it.

Scopes: ``kda_proj`` (the seven projections and ``Wo``), ``kda_glue`` (the
three chains conv -> silu -> l2norm, the decay's softplus, the gated norm a
head), ``kda_scan`` (the op, forward and backward).  A chain is ONE op where
``ops/short_conv.conv_path`` says the kernels take it (a TPU, whole lanes and
heads: its ``kda_conv`` nests under ``kda_glue``, both passes) and the XLA
chain everywhere else (``ops/ssm.causal_conv``, whose own ``ssm_conv`` nests
under it, silu, l2norm, under a ``jax.checkpoint`` of its own); ``apply``
asks, counts (``kda_positions_conv_kernel``) and logs ONE ``attention path:``
line a distinct chain.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.models.parts import Draws, Part
from elasticdl_tpu.ops import delta_rule as delta_ops
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops import short_conv as conv_ops
from elasticdl_tpu.ops import ssm as ssm_ops
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE, announce_path

#: The linear-attention layers' counts a step reports (``ModelSpec.step_counters``;
#: gauges ``edl_kda_positions*_total``): what the traffic asks of the op, from
#: the shapes it was called with, the part of it the chunked form took
#: (``benchmark/metrics/kda_chunked_pct.kda.json`` reads the pair), the part
#: of THAT whose same-sub-block decay masks the Pallas kernels computed, and
#: the part of the first whose three convolution chains the Pallas kernels
#: computed (``kda_mask_kernel_pct.kda`` / ``kda_conv_kernel_pct.kda`` since PR 63).  Each is counted in
#: ``apply``, where the op is called, through the very function the op asks
#: (``rule_path``, ``mask_path``, ``conv_path``), of the very operands: a
#: constant re-derived elsewhere would read 100 whatever ran.
KDA_COUNTERS = {
    "kda_positions": "(head, position) pairs the gated delta rule advanced a state over, from the "
    "shapes it was called with, summed over layers, training steps and devices",
    "kda_positions_chunked": "those of them the op's chunked form computed (ops/delta_rule.rule_path: a sequence "
    "of whole chunks; the others took the stepwise fallback), summed likewise",
    "kda_positions_mask_kernel": "those of the chunked ones whose same-sub-block decay masks ops/delta_rule_kernels.py "
    "computed (ops/delta_rule.mask_path: a TPU, dk whole lanes, a chunk that divides 128; the others took the XLA "
    "differences), summed likewise",
    "kda_positions_conv_kernel": "those of kda_positions whose three convolution chains (conv, silu, l2norm of q, k, v) "
    "ops/short_conv_kernels.py computed (ops/short_conv.conv_path: a TPU, whole lanes and heads, L whole halos; the "
    "others took the XLA chain over ops/ssm.causal_conv), summed likewise",
}
L2_EPS = conv_ops.L2_EPS


def l2norm(x):
    """``x / sqrt(sum(x^2) + 1e-6)`` over the last axis, float32 statistics, one downcast."""
    x32 = x.astype(jnp.float32)
    return (x32 * lax.rsqrt(jnp.sum(jnp.square(x32), axis=-1, keepdims=True) + L2_EPS)).astype(x.dtype)


def _conv_silu(t, taps):
    """``silu(conv(t))``: the causal depthwise convolution (``ops/ssm.causal_conv``, no bias) and its activation."""
    return jax.nn.silu(ssm_ops.causal_conv(t, taps, jnp.zeros((t.shape[-1],), jnp.float32)))


_short_conv = jax.checkpoint(_conv_silu)


@functools.partial(jax.checkpoint, static_argnums=(2,))
def _short_conv_l2(t, taps, head_dim: int):
    """``l2norm(silu(conv(t)))``, the norm a head of ``head_dim`` of the last axis."""
    y = _conv_silu(t, taps)
    return l2norm(y.reshape(*y.shape[:-1], -1, head_dim)).reshape(y.shape)


def _chain(t, taps, head_dim=None):
    """One chain of the glue, ``l2norm(silu(conv(t)))`` a head of ``head_dim``
    (none: ``silu(conv(t))``), by the path ``conv_path`` names for these very
    operands: ``(the chain's output, whether the kernels computed it)``.  The
    kernels' op holds what the XLA chain's ``jax.checkpoint`` holds, the
    chain's input."""
    path, why_not = conv_ops.conv_path(t, taps, head_dim)
    announce_path(path, t, True, f"kda_conv taps={taps.shape[0]} norm={head_dim}" + f"; {why_not}" * bool(why_not))
    if path == PATH_XLA_REFERENCE:
        return (_short_conv(t, taps) if head_dim is None else _short_conv_l2(t, taps, head_dim)), False
    return conv_ops.short_conv(t, taps, head_dim, interpret=path == PATH_PALLAS_INTERPRET), True


@jax.checkpoint
def _log_decay(pre, dt_bias):
    """``softplus(pre + dt_bias)`` in float32 (the decay a channel before its head's rate)."""
    return jax.nn.softplus(pre.astype(jnp.float32) + dt_bias)


@dataclasses.dataclass(frozen=True)
class KimiDeltaAttention(Part):
    """Kimi Delta Attention over ``heads`` heads of ``head_dim`` (keys and
    values alike), the op in chunks of ``chunk``.  Matrices are drawn normal,
    ``A_log`` = log uniform(1, 16) a head, ``dt_bias`` [H x dk] the inverse
    softplus of a log-uniform draw in ``dt_range`` (min, max, floor), the
    taps uniform(+-``conv_kernel``^-1/2), the norm's gain 1."""

    heads: int
    head_dim: int
    conv_kernel: int
    eps: float
    chunk: int = 64
    dt_range: Tuple[float, float, float] = (0.001, 0.1, 1e-4)

    counters = KDA_COUNTERS

    def init(self, draw: Draws, d: int):
        heads, hd, taps = self.heads, self.head_dim, self.conv_kernel
        inner = heads * hd
        lo, hi, floor = self.dt_range
        made = {f"kda_w{name}": draw.normal((d, inner)) for name in "qkv"}
        made.update({f"kda_conv_{name}": draw.uniform((taps, inner), -taps ** -0.5, taps ** -0.5) for name in "qkv"})
        dt = jnp.maximum(jnp.exp(draw.uniform((inner,), jnp.log(lo), jnp.log(hi))), floor)
        made.update({
            "A_log": jnp.log(draw.uniform((heads,), 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "kda_wf_a": draw.normal((d, hd)), "kda_wf_b": draw.normal((hd, inner)),
            "kda_wb": draw.normal((d, heads)),
            "kda_wg_a": draw.normal((d, hd)), "kda_wg_b": draw.normal((hd, inner)),
            "kda_norm": jnp.ones((hd,), jnp.float32),
            "kda_wo": draw.normal((inner, d)),
        })
        return made

    def apply(self, u, blk, positions, axis, cast):
        if axis is not None and axis_size(axis) > 1:
            raise ValueError("a linear-attention layer over a sharded sequence is not supported: the state at a shard's start lives on the shard before it")
        b, l, _ = u.shape
        heads, hd = self.heads, self.head_dim
        by_head = lambda t: t.reshape(b, l, heads, hd)  # noqa: E731
        with jax.named_scope("kda_proj"):
            # the wide products are save sites (ops/remat.py)
            q, k, v = (remat_lib.product(f"kda_{name}", u, cast(blk[f"kda_w{name}"])) for name in "qkv")
            decay = (u @ cast(blk["kda_wf_a"])) @ cast(blk["kda_wf_b"])
            beta = (u @ cast(blk["kda_wb"])).astype(jnp.float32)
            gate = remat_lib.product("kda_gate", u @ cast(blk["kda_wg_a"]), cast(blk["kda_wg_b"]))
        with jax.named_scope("kda_glue"):
            # Each chain of the glue holds its bfloat16 input for the gradient (inside the layer's rematerialisation)
            # and computes its float32 intermediates again, not eight [L, H x dk] float32 arrays
            chains = (("q", q, hd), ("k", k, hd), ("v", v, None))
            (q, k, v), by_conv_kernels = zip(*(_chain(t, blk[f"kda_conv_{name}"], norm) for name, t, norm in chains))
            q, k, v = by_head(q) * hd ** -0.5, by_head(k), by_head(v)
            g = by_head(_log_decay(decay, blk["dt_bias"])) * -jnp.exp(blk["A_log"])[:, None]
            beta = jax.nn.sigmoid(beta)
        o = delta_ops.delta_rule(q, k, v, g, beta, chunk=self.chunk)
        # counted where the op is called, from what it was called with
        chunked = delta_ops.rule_path(l, self.chunk)[0] == delta_ops.PATH_CHUNKED
        by_kernel = chunked and delta_ops.mask_path(k, self.chunk)[0] != delta_ops.PATH_XLA_REFERENCE
        parts = (1, chunked, by_kernel, all(by_conv_kernels))
        counts = {name: jnp.float32(b * l * heads * part) for name, part in zip(KDA_COUNTERS, parts)}
        with jax.named_scope("kda_glue"):
            o = jax.checkpoint(delta_ops.gated_head_norm, static_argnums=(3,))(o, by_head(gate), blk["kda_norm"], self.eps)
        with jax.named_scope("kda_proj"):
            return o.reshape(b, l, heads * hd) @ cast(blk["kda_wo"]), counts
