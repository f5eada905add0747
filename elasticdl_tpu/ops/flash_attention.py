"""Pallas TPU flash attention — the hot-op kernel for the transformer LM.

The XLA blockwise path (ops/ring_attention.py) materializes [B, H, Lq, Lk]
f32 score/prob tensors in HBM — ~800 MB per layer at the MFU-bench shape
(b=16, h=12, L=1024) — which HBM bandwidth, not the MXU, then bounds.  This
kernel tiles queries over a Pallas grid, keeps the whole K/V block resident
in VMEM (256 KB at L=1024 lane-padded — far under the ~16 MB/core budget),
and never writes an O(L^2) tensor to HBM: scores live in VMEM per q-tile.

Scope: exact (non-ring) causal/full self-attention — the single-device and
dp-only configurations, and the n=1 degenerate ring.  The n>1 sequence-
parallel ring keeps the XLA streaming-softmax path: its per-device L is
already sharded n-fold, so the O(L^2) HBM pressure this kernel removes
drops quadratically exactly when the ring turns on.

Layouts: public API takes the model layout [B, L, H, D]; kernels run on
[B*H, L, Dp] with the head dim lane-padded to 128 (D=64 at the GPT-2-small
shape; the MXU is 128 wide, so zero-padding costs nothing the idle lanes
were not already wasting).  Per-query vectors (logsumexp, the backward's
delta) use a tile-legal [BH, n_q, 8, TQ] layout — row 0 carries the data —
because Mosaic requires the last two block dims be (8k, 128k).

Training runs through a custom_vjp (standard flash backward: save out +
logsumexp, recompute probabilities per tile; dq recomputes its own softmax
stats since it re-derives full score rows anyway).

VMEM bound: whole-K/V residency asserts L <= 8192.  No ``compiler_params``
are set, so Mosaic's default 16 MiB scoped-VMEM limit applies; the L=8192
forward (double-buffered whole K and V, a [128, 8192] f32 score tile, its
exp and the mask iotas) compiles under it for v5e
(``tests/test_chip_lowering.py`` compiles the bench shape and this bound
ahead of time against libtpu, no chip needed), while the same kernel at
L=16384 asks for 23.94 MiB and is refused.  Longer sequences are what
sequence parallelism is for.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.ring_attention import (
    PATH_PALLAS_COMPILED,
    PATH_PALLAS_INTERPRET,
    announce_path,
)

# q rows per grid program: one MXU face; f32 (8,128) and bf16 (16,128) min
# tiles both divide it.
_TQ = 128
_LANE = 128     # head-dim lane padding target
_SUB = 8        # sublane rows in the vector layout (row 0 is the data)
_MAX_L = 8192   # whole-K/V-in-VMEM bound (see module docstring)


def _use_interpret() -> bool:
    # Any non-TPU backend (CPU tests/dryruns, GPU, METAL, …) runs the
    # kernel in interpreter mode — slow but exact, keeping one code path
    # under test everywhere.  Gating on "not tpu" rather than "cpu":
    # ``supports()`` passes wherever the op is mathematically valid, and a
    # compiled Pallas-TPU lowering on a non-TPU backend fails in Mosaic
    # after that check has already admitted the op.
    return jax.default_backend() != "tpu"


def _causal_mask(qi, lk: int):
    """[TQ, lk] bool: query global row >= key global col."""
    q_pos = qi * _TQ + jax.lax.broadcasted_iota(jnp.int32, (_TQ, lk), 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, (_TQ, lk), 1)
    return q_pos >= k_pos


def _dot(a, b, dims):
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32
    )


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, causal, scale):
    qi = pl.program_id(1)
    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    s = _dot(q, k, ((1,), (1,))) * scale          # [TQ, Lk] f32, VMEM-only
    if causal:
        s = jnp.where(_causal_mask(qi, k.shape[0]), s, -jnp.inf)
    m = jnp.max(s, axis=-1)                       # [TQ]
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)   # all-masked row guard
    p = jnp.exp(s - safe_m[:, None])
    l = jnp.sum(p, axis=-1)
    o = _dot(p.astype(q.dtype), v, ((1,), (0,)))  # [TQ, Dp] f32
    o_ref[0] = (o / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0, 0, :] = safe_m + jnp.log(jnp.maximum(l, 1e-30))


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, delta_ref, dq_ref, *, causal,
               scale):
    qi = pl.program_id(1)
    q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
    delta = delta_ref[0, 0, 0, :]                 # [TQ] f32
    # Recompute softmax stats: this kernel derives full score rows anyway,
    # so the lse residual is not needed here.
    s = _dot(q, k, ((1,), (1,))) * scale
    if causal:
        s = jnp.where(_causal_mask(qi, k.shape[0]), s, -jnp.inf)
    m = jnp.max(s, axis=-1)
    safe_m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(s - safe_m[:, None])
    p = p / jnp.maximum(jnp.sum(p, axis=-1), 1e-30)[:, None]
    dp = _dot(do, v, ((1,), (1,)))                # [TQ, Lk]
    ds = p * (dp - delta[:, None])
    dq_ref[0] = (_dot(ds.astype(q.dtype), k, ((1,), (0,))) * scale).astype(
        dq_ref.dtype
    )


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, causal, scale, n_q):
    ki = pl.program_id(1)
    k, v = k_ref[0], v_ref[0]                     # [TK, Dp] (TK == TQ)
    tk = k.shape[0]
    # ROLLED loop over q tiles (fori_loop, buffers reused): an unrolled
    # Python loop at n_q=64 (L=8192) accumulated per-iteration [TK, TQ]
    # temporaries on Mosaic's VMEM stack past the 16 MB budget.  Per-query
    # vectors are read by dynamic sublane index from the [n_q, 8, TQ]
    # resident block.  Under causal masking, q tiles strictly above the
    # diagonal (qi < ki) contribute nothing — lax.cond skips their three
    # dots at runtime, reclaiming ~half the backward's key-side FLOPs.

    def body(qi, acc):
        dk, dv = acc
        q = q_ref[0, pl.ds(qi * _TQ, _TQ)]        # [TQ, Dp]
        do = do_ref[0, pl.ds(qi * _TQ, _TQ)]
        lse = lse_ref[0, qi, 0, :]                # [TQ] f32
        delta = delta_ref[0, qi, 0, :]

        def _contrib():
            st = _dot(k, q, ((1,), (1,))) * scale   # [TK, TQ]
            pt = jnp.exp(st - lse[None, :])
            if causal:
                k_pos = ki * _TQ + jax.lax.broadcasted_iota(
                    jnp.int32, (tk, _TQ), 0
                )
                q_pos = qi * _TQ + jax.lax.broadcasted_iota(
                    jnp.int32, (tk, _TQ), 1
                )
                pt = jnp.where(q_pos >= k_pos, pt, 0.0)
            dv_c = _dot(pt.astype(q.dtype), do, ((1,), (0,)))
            dpt = _dot(v, do, ((1,), (1,)))         # [TK, TQ]
            dst = pt * (dpt - delta[None, :])
            dk_c = _dot(dst.astype(q.dtype), q, ((1,), (0,))) * scale
            return dk + dk_c, dv + dv_c

        if causal:
            return jax.lax.cond(qi >= ki, _contrib, lambda: (dk, dv))
        return _contrib()

    dk0 = jnp.zeros((tk, k.shape[1]), jnp.float32)
    dk, dv = jax.lax.fori_loop(0, n_q, body, (dk0, jnp.zeros_like(dk0)))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _to_kernel_layout(x):
    """[B, L, H, D] -> [B*H, L, Dp] with the head dim lane-padded."""
    b, l, h, d = x.shape
    x = x.transpose(0, 2, 1, 3).reshape(b * h, l, d)
    if d < _LANE:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, _LANE - d)))
    return x


def _from_kernel_layout(x, b, h, d):
    x = x[..., :d]
    return x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3)


def _vec4(x_bh_lq, n_q):
    """[BH, Lq] f32 -> tile-legal [BH, n_q, 8, TQ] with data in row 0."""
    bh = x_bh_lq.shape[0]
    r = x_bh_lq.reshape(bh, n_q, 1, _TQ)
    return jnp.concatenate(
        [r, jnp.zeros((bh, n_q, _SUB - 1, _TQ), x_bh_lq.dtype)], axis=2
    )


def supports(q, k, v) -> bool:
    """True when these shapes are inside the kernel's contract (callers use
    this to fall back to the XLA path instead of tripping _check)."""
    b, lq, h, d = q.shape
    return bool(
        lq % _TQ == 0
        and lq <= _MAX_L
        and d <= _LANE
        and k.shape == q.shape
        and v.shape == q.shape
    )


def _check(q, k, v):
    if not supports(q, k, v):
        raise ValueError(
            f"flash_attention supports self-attention with L a multiple of "
            f"{_TQ}, L <= {_MAX_L}, head_dim <= {_LANE}; got q{q.shape} "
            f"k{k.shape} v{v.shape} (use ops.ring_attention's XLA path)"
        )


def _specs(lq, n_q):
    tile = pl.BlockSpec(
        (1, _TQ, _LANE), lambda bh, i: (bh, i, 0), memory_space=pltpu.VMEM
    )
    whole = pl.BlockSpec(
        (1, lq, _LANE), lambda bh, i: (bh, 0, 0), memory_space=pltpu.VMEM
    )
    vec_tile = pl.BlockSpec(
        (1, 1, _SUB, _TQ), lambda bh, i: (bh, i, 0, 0),
        memory_space=pltpu.VMEM,
    )
    vec_whole = pl.BlockSpec(
        (1, n_q, _SUB, _TQ), lambda bh, i: (bh, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    return tile, whole, vec_tile, vec_whole


def _fwd_impl(q, k, v, causal):
    _check(q, k, v)
    b, lq, h, d = q.shape
    scale = d**-0.5
    qk, kk, vk = (_to_kernel_layout(x) for x in (q, k, v))
    bh, n_q = b * h, lq // _TQ
    tile, whole, vec_tile, _ = _specs(lq, n_q)
    interpret = _use_interpret()
    if interpret:
        announce_path(
            PATH_PALLAS_INTERPRET, q, causal,
            f"backend={jax.default_backend()}",
        )
    else:
        announce_path(PATH_PALLAS_COMPILED, q, causal)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale),
        grid=(bh, n_q),
        in_specs=[tile, whole, whole],
        out_specs=[tile, vec_tile],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, _LANE), q.dtype),
            jax.ShapeDtypeStruct((bh, n_q, _SUB, _TQ), jnp.float32),
        ],
        interpret=interpret,
    )(qk, kk, vk)
    # Residuals are saved UNPADDED: the lane padding is pure zeros and the
    # backward re-pads in O(L*D) — at d=64 the padded copies would hold 2x
    # the bytes across every layer of a remat-off forward, material next to
    # the batch-32 HBM margin this kernel exists to widen.
    res = (
        qk[..., :d], kk[..., :d], vk[..., :d], o[..., :d], lse, b, h, d
    )
    return _from_kernel_layout(o, b, h, d), res


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q, k, v, causal=False):
    """Exact (non-ring) attention, [B, L, H, D] -> [B, L, H, D]."""
    return _fwd_impl(q, k, v, causal)[0]


def _fa_fwd(q, k, v, causal):
    return _fwd_impl(q, k, v, causal)


def _fa_bwd(causal, res, g):
    qs, ks, vs, os_, lse, b, h, d = res
    pad = ((0, 0), (0, 0), (0, _LANE - d)) if d < _LANE else None
    qk, kk, vk, o = (
        (jnp.pad(x, pad) if pad else x) for x in (qs, ks, vs, os_)
    )
    bh, lq, _ = qk.shape
    scale = d**-0.5
    n_q = lq // _TQ
    gk = _to_kernel_layout(g)
    # delta = rowsum(dO * O) in f32 — O(L*D) precompute, standard flash bwd.
    delta = _vec4(
        jnp.sum(gk.astype(jnp.float32) * o.astype(jnp.float32), axis=-1),
        n_q,
    )
    tile, whole, vec_tile, vec_whole = _specs(lq, n_q)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale),
        grid=(bh, n_q),
        in_specs=[tile, whole, whole, tile, vec_tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((bh, lq, _LANE), qk.dtype),
        interpret=_use_interpret(),
    )(qk, kk, vk, gk, delta)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, causal=causal, scale=scale, n_q=n_q
        ),
        grid=(bh, n_q),
        in_specs=[whole, tile, tile, whole, vec_whole, vec_whole],
        out_specs=[tile, tile],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, _LANE), qk.dtype),
            jax.ShapeDtypeStruct((bh, lq, _LANE), vk.dtype),
        ],
        interpret=_use_interpret(),
    )(qk, kk, vk, gk, lse, delta)
    return tuple(_from_kernel_layout(x, b, h, d) for x in (dq, dk, dv))


flash_attention.defvjp(_fa_fwd, _fa_bwd)
