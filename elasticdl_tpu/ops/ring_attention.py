"""Ring attention — sequence/context parallelism over the device mesh.

The reference framework predates long-context models (its models are
tabular/CNN — SURVEY.md §2 parallelism table: SP/CP absent), but this
rebuild treats long-context as first-class: sequences too long for one
device's HBM shard along a ``sp`` mesh axis, and attention runs blockwise
while key/value blocks rotate around the ring via ``lax.ppermute`` —
compute on the current block overlaps the ICI transfer of the next, so the
ring costs ~one extra block of latency, not a full all-gather of K/V.

Math: classic streaming-softmax (flash-style) accumulation.  Each step
processes one K/V block against the local Q block, carrying a running
row-max ``m``, normalizer ``l``, and unnormalized output ``o``; exact to
fp error regardless of block order.  Causal masking uses global positions
(device rank × block length + offset), so the sharded result equals the
unsharded lower-triangular mask.

All collectives are XLA ``ppermute`` on the mesh axis (ICI), differentiable
(transpose is the reverse rotation), so the same code path trains.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("ops.attention")

#: The attention implementations a trace can land on, as logged by
#: :func:`announce_path` (``chip_smoke.py`` refuses a transformer job whose
#: worker log names the wrong one).
PATH_PALLAS_COMPILED = "pallas-compiled"
PATH_PALLAS_INTERPRET = "pallas-interpret"
PATH_XLA_REFERENCE = "xla-reference"
PATH_XLA_RING = "xla-ring"


def announce_path(path: str, q, causal: bool, why: str = "") -> None:
    """Log which attention implementation a trace chose — once per distinct
    line per process, since a 12-layer model traces the same choice dozens
    of times per lowering.  The choices themselves are silent by design
    (tests need the off-TPU fallbacks); this line is what makes a run that
    took one visible."""
    _log_once(
        f"attention path: {path} (q={tuple(q.shape)} {q.dtype} "
        f"causal={causal}{'; ' + why if why else ''})"
    )


@functools.lru_cache(maxsize=None)
def _log_once(line: str) -> None:
    logger.info(line)


def _rotate(x: jax.Array, axis_name: str) -> jax.Array:
    n = axis_size(axis_name)
    return lax.ppermute(x, axis_name, [(j, (j + 1) % n) for j in range(n)])


def _scores(q, k, q_rot, k_rot):
    """[B, H, Lq, Lk] scaled scores: ``q . k`` and, where a rotary part is
    given (``q_rot`` [B, Lq, H, R], ``k_rot`` [B, Lk, R]: ONE key for every
    head — latent attention), ``+ q_rot . k_rot``, over the root of the
    whole width."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if q_rot is None:
        return scores * q.shape[-1] ** -0.5
    scores += jnp.einsum("bqhr,bkr->bhqk", q_rot, k_rot)
    return scores * (q.shape[-1] + q_rot.shape[-1]) ** -0.5


def attention_reference(
    q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = False,
    q_rot: Optional[jax.Array] = None, k_rot: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain full attention ([B, L, H, D] layout) — the numerics oracle.
    Under a ``window`` (causal) position p sees the keys ``p - window < j
    <= p``."""
    scores = _scores(q, k, q_rot, k_rot)
    if causal:
        lq, lk = scores.shape[-2], scores.shape[-1]
        mask = jnp.tril(jnp.ones((lq, lk), bool), lk - lq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((lq, lk), bool), lk - lq - window)
        scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _local_attention(q, k, v, causal: bool, q_rot=None, k_rot=None, window=None) -> jax.Array:
    """Exact single-shard attention: the Pallas flash kernel on TPU (no
    O(L^2) HBM tensors — at the MFU-bench shape the XLA path's saved
    probability tensors alone are ~19 GB at b=32, the difference between
    OOM and 2x the batch), the XLA oracle elsewhere (CPU tests/dryruns;
    the kernel itself is oracle-tested in interpret mode in
    tests/test_flash_attention.py)."""
    from elasticdl_tpu.ops.flash_attention import (
        flash_attention,
        outside_contract,
    )

    backend = jax.default_backend()
    if backend != "tpu":
        why_not = f"backend={backend}"
    else:
        why_not = outside_contract(q, k, v, q_rot, k_rot, window)
        if not why_not:
            return flash_attention(q, k, v, causal, q_rot, k_rot, window)
        why_not = f"outside the flash kernels' contract: {why_not}"
    if q_rot is not None:
        why_not += f" rotary={q_rot.shape[-1]}"
    if window is not None:
        why_not += f" window={window}"
    announce_path(PATH_XLA_REFERENCE, q, causal, why_not)
    return attention_reference(q, k, v, causal, q_rot, k_rot, window)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: Optional[str] = None,
    causal: bool = False,
    q_rot: Optional[jax.Array] = None,
    k_rot: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Blockwise attention with K/V ring rotation over ``axis_name``.

    Inputs are the LOCAL sequence shards ``[B, L_local, H, D]`` (inside
    shard_map over the ``sp`` axis); the output is the local shard of the
    full-attention result.  With ``axis_name=None`` (or outside shard_map)
    it degrades to exact single-device attention.  ``q_rot`` [B, L_local,
    H, R] and ``k_rot`` [B, L_local, R] add a second product to the score
    (:func:`_scores`); the shared key rides the ring with K and V.
    ``window`` (``causal`` only): position p sees the keys ``p - window < j
    <= p``; over a sequence that IS sharded it raises (the ring is not
    taught which blocks a window lets it skip).
    """
    if window is not None and not causal:
        raise ValueError("a window is a causal call's")
    if axis_name is None:
        return _local_attention(q, k, v, causal, q_rot, k_rot, window)

    n = axis_size(axis_name)
    if n == 1:
        # Degenerate ring (1-device mesh under shard_map): exact local
        # attention, flash-kernelled on TPU.
        return _local_attention(q, k, v, causal, q_rot, k_rot, window)
    if window is not None:
        raise ValueError("attention under a window over a sharded sequence is not supported: the ring visits every block")
    announce_path(PATH_XLA_RING, q, causal, f"n={n}")
    my = lax.axis_index(axis_name)
    b, lq, h, d = q.shape
    lk = k.shape[1]
    q_pos = my * lq + jnp.arange(lq)  # global positions of local queries
    f32 = lambda x: None if x is None else x.astype(jnp.float32)  # noqa: E731

    def accumulate(acc, src, k_blk, v_blk, kr_blk):
        o, m, l = acc
        scores = _scores(q, k_blk, q_rot, kr_blk)
        if causal:
            kv_pos = src * lk + jnp.arange(lk)
            mask = q_pos[:, None] >= kv_pos[None, :]  # [lq, lk]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_blk = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        # Fully-masked rows keep m=-inf; guard the exp against inf-inf.
        safe_m = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(scores - safe_m[..., None])
        corr = jnp.where(jnp.isneginf(m), 0.0, jnp.exp(m - safe_m))
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, v_blk)
        return o_new, m_new, l_new

    # Block 0 is the locally-held K/V; the scan then performs exactly n-1
    # rotations (rotate-then-accumulate), so no transferred block is wasted.
    o0 = jnp.zeros((b, h, lq, d), jnp.float32)
    m0 = jnp.full((b, h, lq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, lq), jnp.float32)
    blocks = (f32(k), f32(v), f32(k_rot))
    acc = accumulate((o0, m0, l0), my, *blocks)

    def step(carry, i):
        acc, blocks = carry
        blocks = jax.tree.map(lambda x: _rotate(x, axis_name), blocks)
        acc = accumulate(acc, (my - i) % n, *blocks)
        return (acc, blocks), None

    if n > 1:
        (acc, _), _ = lax.scan(step, (acc, blocks), jnp.arange(1, n))
    o, m, l = acc
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # [B, Lq, H, D]
