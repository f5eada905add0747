"""The attentions of ``moe_lm``'s layers (``models/parts.py``: what a part
is), with ``rmsnorm(x, g) = x * rsqrt(mean(x^2) + eps) * g`` and ``a`` the
normed residual stream:

OLMoE's (:class:`QKNormAttention`):

    q, k, v = a Wq, a Wk, a Wv                            (no bias, no clip)
    q   = rmsnorm(q, q_norm) ; k = rmsnorm(k, k_norm)     (over ALL heads' columns, before the split)
    q, k = rope(q), rope(k)                               (per head, rotate-half pairing (i, i + hd/2), theta)
    part = causal_attention(q, k, v) Wo                   (scores / sqrt(hd))

DeepSeek-V3's (:class:`LatentAttention`: ``transformers``' ``DeepseekV3``
modules, ``q_lora_rank`` null), H heads, ``nope`` / ``rot`` / ``v`` =
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``:

    q      = a Wq                 -> [T, H, nope + rot] = (q_nope, q_rot)
    (c, k_rot) = a Wkv_a          -> c [T, kv_lora_rank], k_rot [T, rot]: ONE rotary key for all heads
    (k_nope, v) = rmsnorm(c, kv_norm) Wkv_b   -> [T, H, nope], [T, H, v]
    q_rot, k_rot = rope(q_rot), rope(k_rot)   (the rot columns only; ``rope_interleave``: pairs (2i, 2i + 1);
                                              NOT under ``mla_use_nope`` (``kimi_linear``): the rot columns and the
                                              shared key stay, unturned, and so do the scores' width and scale)
    s_h    = (q_nope_h . k_nope_h + q_rot_h . k_rot) * (nope + rot)^-0.5 ; causal softmax ; o_h = p_h v_h
    part   = o Wo

EvaByte's (:class:`EvaAttention`), ``held`` of the published heads here (one
chip's share under head parallelism: wq / wk / wv ``[d, held * hd]``, wo
``[held * hd, d]``; what the other heads would add to ``o Wo`` is left out):

    q, k = rope(a Wq), rope(a Wk) ; v = a Wv   (no QK-norm)
    part = eva_attention(q, k, v, eva_phi, eva_mu) Wo      (``ops/eva_attention``: exact inside the
                                               query's ``window_size``, one learned summary a
                                               ``chunk_size`` of every earlier window, ONE softmax)

``nemotron_h``'s (:class:`GroupedQueryAttention`), no position signal:

    q = a Wq (``q_heads`` heads of ``head_dim``), k, v = a Wk, a Wv (``kv_heads`` heads), the HELD heads;
    query head h reads key/value head h // (heads / kv heads); causal softmax of q k / sqrt(head_dim); part = o Wo

``afmoe``'s (:class:`GatedWindowAttention`: Trinity), H query heads over G key/value heads of ``head_dim``;
a layer is ``sliding_attention`` (``window`` = ``sliding_window`` keys) or ``full_attention`` (``window`` 0):

    q = a Wq [H, hd] ; k, v = a Wk, a Wv [G, hd] ; z = a Wz [H x hd]      (no bias; z the output's gate)
    q = rmsnorm(q, q_norm) ; k = rmsnorm(k, k_norm)       (a norm A HEAD over its hd columns, ONE gain [hd] for all heads)
    sliding layers only: q, k = rope(q), rope(k)          (rotate-half over the whole head; a FULL layer has NO position signal)
    query head h reads key/value head h // (H / G) ; scores / sqrt(hd) ; softmax over the keys j <= p (full) or
    p - window < j <= p (sliding: ``window`` keys, the query's own included)
    part = (o * sigmoid(z)) Wo
    ``lfm2_moe``'s attention layers are this part WITHOUT the gate (``gate`` false: no Wz, part = o Wo) and with the rotary
    turn on a FULL layer (``rotary`` true): fewer key/value heads, a norm a head, THEN the turn, every earlier key
    ``smallthinker``'s are it without the gate AND without the norm a head (``head_norm`` false: no q_norm, no k_norm), a
    group of SEVEN query heads a key/value head (28 over 4), the turn on the sliding layers alone (theta 1.5e6), a FULL
    layer with no position signal: q = a Wq ; k, v = a Wk, a Wv ; part = o Wo

``KeyeVL2``'s (:class:`IndexedSparseAttention`: DeepSeek-V3.2-Exp's sparse attention under ``sa_config``), H query
heads over G key/value heads of ``head_dim``, an INDEXER of J heads E wide over ONE index key a position, ``topk`` keys a query:

    q = a Wq [H, hd] ; k, v = a Wk, a Wv [G, hd]          (no bias)
    q = rmsnorm(q, q_norm) ; k = rmsnorm(k, k_norm)       (a norm A HEAD, ONE gain [hd] each) ; q, k = rope(q), rope(k)
    a_I = stop_gradient(a)                                (the LM loss reaches NO parameter of the indexer)
    qI = rope(a_I WqI) [J, E] ; kI = rope(layernorm(a_I WkI)) [E] ; w = (a_I Ww) * J^-1/2 * E^-1/2 [J]   (w float32)
    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])        (float32; s <= t)
    S_t = the min(t + 1, topk) keys s <= t of largest I[t, s], ties to the lower index        (EXACT: ``ops/sparse_select``)
    o[t, h] = softmax over s in S_t of (q[t, h] . k[s, h // (H / G)] / sqrt(hd)) applied to v   ; part = o Wo
    L_I = mean_t KL(p[t, .] || softmax_{S_t}(I[t, .])),  p = stop_gradient(sum_h a[t, h, .]) L1-normalised over S_t
          (the part's stat ``indexer_loss``: ``moe_lm`` adds the layers' to the loss; its gradient reaches WqI, WkI, Ww and
          the layernorm alone)

Over a sharded sequence the first two and ``nemotron_h``'s run over the ring
(``ops/ring_attention``) with global rotary positions; EVA refuses one, and
so does a window (the ring visits every block), and so does an indexer (a query's selection ranges over every shard's keys).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.jax_compat import axis_size
from elasticdl_tpu.models.parts import Draws, Part, rms_norm
from elasticdl_tpu.ops import eva_attention as eva_ops
from elasticdl_tpu.ops import flash_attention as flash_ops
from elasticdl_tpu.ops import remat as remat_lib
from elasticdl_tpu.ops import sparse_select
from elasticdl_tpu.ops.ring_attention import PATH_XLA_REFERENCE, announce_path, ring_attention

#: EVA attention's counts a step reports (``ModelSpec.step_counters``: summed
#: over devices by the trainer and over steps by the worker; gauges
#: ``edl_eva_pairs_*_total``): what the TRAFFIC asks of the attention, a
#: function of the shapes it was called with and of nothing the kernels do.
EVA_COUNTERS = {
    "eva_pairs_exact": "(query, key) pairs of a query's own window that the steps' queries were "
    "scored against, from the shapes the attention was called with, summed over heads, layers, "
    "training steps and devices",
    "eva_pairs_summary": "(query, chunk summary) pairs of earlier windows, summed likewise",
}


#: What a model of window and full attention layers asks of its attention a
#: step (gauges ``edl_attn_pairs_*_total``), from the shapes it was called
#: with; the third also from the flash kernels' plan for those shapes.
WINDOW_COUNTERS = {
    "attn_pairs_window": "(query, key) pairs inside the queries' windows (a sequence of L under a window of W: W (W + 1) / 2 + "
    "(L - W) W a head) that the sliding-attention layers' queries were scored against, from the shapes the attention was called "
    "with, summed over heads, layers, training steps and devices",
    "attn_pairs_full": "(query, key) pairs at or before the query (L (L + 1) / 2 a head) of the full-attention layers, summed likewise",
    "attn_pairs_window_computed": "(query, key) pairs that a pass of the flash kernels multiplies for the sliding-attention "
    "layers: the visited (block, block) pairs' sub-tiles as ops/flash_attention's plan cuts them, the mean of its three kernels "
    "(forward, dQ, dK/dV), summed likewise; equals attn_pairs_window for a kernel that multiplies nothing the mask hides",
}

#: What a learned sparse attention asks and does a step (gauges ``edl_dsa_*_total``): the first, second and fourth from
#: the shapes the attention was called with, the third from the selection the step made.
DSA_COUNTERS = {
    "dsa_pairs_causal": "(query, key) pairs at or before the query (L (L + 1) / 2 a head) that the indexer scores, summed over "
    "attention heads, layers, training steps and devices",
    "dsa_pairs_needed": "(query, key) pairs the selection keeps (sum over queries of min(t + 1, topk) a head): what the attention "
    "needs, summed likewise",
    "dsa_pairs_computed": "(query, key) pairs the attention really multiplied in ONE pass: the (block, block) pairs of the masked "
    "kernels' plan that hold a selected key (every pair where the XLA path runs), summed likewise; what block skipping saves "
    "is dsa_pairs_causal's blocks less this",
    "dsa_rows_selecting": "queries with more earlier keys than topk (t >= topk: the selection drops some), summed over layers, "
    "training steps and devices",
}

#: What attention under the rule of diffusion over blocks asks and does a step (gauges ``edl_bd_pairs_*_total``): the first from
#: the shapes the attention was called with, the second also from the kernels' plan for those shapes.
BD_COUNTERS = {
    "bd_pairs_needed": "(query, key) pairs the rule of diffusion over blocks holds over a sequence's noisy and clean copies "
    "(L^2 + g L a head at block length g), summed over attention heads, layers, training steps and devices",
    "bd_pairs_computed": "(query, key) pairs that a pass of the kernels under the rule multiplies: the visited (block, block) "
    "pairs' sub-tiles as ops/flash_attention's walk cuts them, the mean of its three kernels (every pair of the doubled "
    "sequence, 4 L^2 a head, where the XLA path runs), summed likewise",
}

#: how the indexer's input is cut off the LM loss (looked up HERE by the part: the references' control swaps it)
_constant = jax.lax.stop_gradient


def _qk_norm(x, scale, eps):
    """OLMoE's QK-norm: over ALL of the projection's columns (every head's),
    before the split into heads — not a norm per head."""
    return rms_norm(x, scale, eps)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary positions on ``x`` [B, L, H, hd]: element ``i`` of a head is
    paired with ``i + hd/2`` and the pair turned by ``positions * theta^
    (-2i/hd)``.  Float32 arithmetic, one downcast."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]  # [L, half]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def _rotary_columns(w: jax.Array, interleave: bool) -> jax.Array:
    """The rotary output columns of a projection ``w`` [..., rot] in the
    order :func:`rope` pairs them, (i, i + rot/2).  A model that pairs
    (2i, 2i + 1) (``rope_interleave``) has its even columns moved to the
    first half here, on the WEIGHT: the same permutation of q_rot and k_rot
    leaves every score as it is, and no activation is shuffled."""
    if not interleave:
        return w
    # a transpose, whose gradient is a transpose (two strided slices'
    # gradient is a scatter of rows, one at a time on the TPU)
    pairs = w.reshape(w.shape[:-1] + (w.shape[-1] // 2, 2))
    return jnp.swapaxes(pairs, -1, -2).reshape(w.shape)


@dataclasses.dataclass(frozen=True)
class QKNormAttention(Part):
    """OLMoE's: three projections, whole-width QK-norm, rotate-half rope
    over the whole head."""

    n_heads: int
    theta: float
    eps: float

    def init(self, draw: Draws, d: int):
        return {
            "wq": draw.normal((d, d)), "wk": draw.normal((d, d)), "wv": draw.normal((d, d)),
            "wo": draw.normal((d, d)),
            "q_norm": jnp.ones((d,), jnp.float32),
            "k_norm": jnp.ones((d,), jnp.float32),
        }

    def apply(self, a, blk, positions, axis, cast):
        b, l, dim = a.shape
        q = _qk_norm(a @ cast(blk["wq"]), blk["q_norm"], self.eps)
        k = _qk_norm(a @ cast(blk["wk"]), blk["k_norm"], self.eps)
        v = a @ cast(blk["wv"])
        heads = lambda t: t.reshape(b, l, self.n_heads, dim // self.n_heads)  # noqa: E731
        q, k = rope(heads(q), positions, self.theta), rope(heads(k), positions, self.theta)
        att = ring_attention(q, k, heads(v), axis_name=axis, causal=True)
        return att.reshape(b, l, dim) @ cast(blk["wo"]), None


@dataclasses.dataclass(frozen=True)
class LatentAttention(Part):
    """DeepSeek-V3's (module docstring): keys and values through a latent
    ``rank`` wide, ``rot`` rotary columns a head of q against ONE shared
    rotary key.  Each projection is multiplied by its own column block of
    the published matrix (a slice of the WEIGHT): every product is then born
    in the layout the attention kernels read, [B, L, H * width], with no
    slice of an activation in between."""

    n_heads: int
    rank: int
    nope: int
    rot: int
    v: int
    theta: float
    eps: float
    interleave: bool
    rotary: bool = True

    def init(self, draw: Draws, d: int):
        # The published shapes: a head's columns of wq are (nope | rot),
        # of wkv_b (nope | v); wkv_a's are (the latent | the rotary key).
        return {
            "wq": draw.normal((d, self.n_heads * (self.nope + self.rot))),
            "wkv_a": draw.normal((d, self.rank + self.rot)),
            "kv_norm": jnp.ones((self.rank,), jnp.float32),
            "wkv_b": draw.normal((self.rank, self.n_heads * (self.nope + self.v))),
            "wo": draw.normal((self.n_heads * self.v, d)),
        }

    def apply(self, a, blk, positions, axis, cast):
        b, l, dim = a.shape
        n_heads, rank, nope = self.n_heads, self.rank, self.nope
        with jax.named_scope("mla_proj"):
            wq = blk["wq"].reshape(dim, n_heads, -1)
            wkv_b = blk["wkv_b"].reshape(rank, n_heads, -1)
            columns = lambda w: cast(w.reshape(w.shape[0], -1))  # noqa: E731
            heads = lambda t: t.reshape(b, l, n_heads, -1)  # noqa: E731
            q = heads(a @ columns(wq[..., :nope]))
            q_rot = heads(a @ columns(_rotary_columns(wq[..., nope:], self.interleave)))
            c = rms_norm(a @ cast(blk["wkv_a"][:, :rank]), blk["kv_norm"], self.eps)
            k_rot = a @ cast(_rotary_columns(blk["wkv_a"][:, rank:], self.interleave))
            k, v = heads(c @ columns(wkv_b[..., :nope])), heads(c @ columns(wkv_b[..., nope:]))
            if self.rotary:
                q_rot = rope(q_rot, positions, self.theta)
                k_rot = rope(k_rot[:, :, None, :], positions, self.theta)[:, :, 0]
        att = ring_attention(q, k, v, axis_name=axis, causal=True, q_rot=q_rot, k_rot=k_rot)
        with jax.named_scope("mla_proj"):
            return att.reshape(b, l, -1) @ cast(blk["wo"]), None


@dataclasses.dataclass(frozen=True)
class EvaAttention(Part):
    """EvaByte's: three projections onto the ``held`` heads, rotate-half
    rope over the whole head, ``ops/eva_attention``."""

    held: int
    head_dim: int
    theta: float
    window: int
    chunk: int

    counters = EVA_COUNTERS

    def init(self, draw: Draws, d: int):
        held, hd = self.held, self.head_dim
        return {
            "wq": draw.normal((d, held * hd)), "wk": draw.normal((d, held * hd)),
            "wv": draw.normal((d, held * hd)), "wo": draw.normal((held * hd, d)),
            "eva_phi": jnp.zeros((held, hd), jnp.float32),
            "eva_mu": jnp.zeros((held, hd), jnp.float32),
        }

    def apply(self, a, blk, positions, axis, cast):
        if axis is not None and axis_size(axis) > 1:
            raise ValueError("EVA attention over a sharded sequence is not supported: the summaries of earlier windows live on other shards")
        b, l, _ = a.shape
        held, hd = self.held, self.head_dim
        heads = lambda t: t.reshape(b, l, held, hd)  # noqa: E731
        with jax.named_scope("eva_proj"):
            # save sites (ops/remat.py): each projection as the attention reads it
            wq, wk, wv = cast(blk["wq"]), cast(blk["wk"]), cast(blk["wv"])
            q = remat_lib.site("q", 2 * a.size * wq.shape[1], rope(heads(a @ wq), positions, self.theta))
            k = remat_lib.site("k", 2 * a.size * wk.shape[1], rope(heads(a @ wk), positions, self.theta))
            v = heads(remat_lib.product("v", a, wv))
        att = eva_ops.eva_attention(q, k, v, blk["eva_phi"], blk["eva_mu"], window=self.window, chunk=self.chunk)
        with jax.named_scope("eva_proj"):
            return att.reshape(b, l, held * hd) @ cast(blk["wo"]), None

    def shape_counts(self, batch: int, length: int):
        exact, far = eva_ops.pairs(length, self.window, self.chunk)  # a head's, of one sequence
        return {"eva_pairs_exact": batch * self.held * exact, "eva_pairs_summary": batch * self.held * far}


@dataclasses.dataclass(frozen=True)
class GroupedQueryAttention(Part):
    """Attention whose key/value heads are fewer than its query heads
    (query head h reads key/value head ``h // group``), over the HELD heads;
    no position signal.  The key/value heads are REPEATED to the queries'
    ahead of the attention (PERF.md section 7: the flash kernels' contract
    wants as many; one layer in eleven).  ``into_stream`` scales ``wo``'s
    draw (``rescale_prenorm_residual``)."""

    q_heads: int
    kv_heads: int
    head_dim: int
    into_stream: float = 1.0

    def init(self, draw: Draws, d: int):
        q, kv = self.q_heads * self.head_dim, self.kv_heads * self.head_dim
        return {
            "wq": draw.normal((d, q)), "wk": draw.normal((d, kv)),
            "wv": draw.normal((d, kv)), "wo": draw.normal((q, d), self.into_stream),
        }

    def apply(self, u, blk, positions, axis, cast):
        b, l, _ = u.shape
        with jax.named_scope("attn_proj"):
            heads = lambda t: t.reshape(b, l, -1, self.head_dim)  # noqa: E731
            q, k, v = (heads(remat_lib.product(name, u, cast(blk["w" + name]))) for name in ("q", "k", "v"))
            group = self.q_heads // self.kv_heads
            if group > 1:
                k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        att = ring_attention(q, k, v, axis_name=axis, causal=True)
        with jax.named_scope("attn_proj"):
            return att.reshape(b, l, -1) @ cast(blk["wo"]), None


@dataclasses.dataclass(frozen=True)
class GatedWindowAttention(Part):
    """``afmoe``'s (module docstring): ``q_heads`` query heads over
    ``kv_heads`` key/value heads, a norm a head on q and k, a sigmoid gate on
    the output; under a ``window`` (> 0: a sliding layer) the keys a query
    sees are its own and the ``window - 1`` before it and q, k take the
    rotary turn, without one (0: a full layer) neither.  The key/value heads
    are REPEATED to the queries' ahead of the attention, as
    :class:`GroupedQueryAttention` does (the flash kernels' contract).
    ``gate`` false: no ``wz`` and no gate (``lfm2_moe``); ``rotary``: whether
    q, k take the turn (None: as ``afmoe`` has it, the sliding layers alone).
    ``block_length`` > 0 (``sdar_moe``): the stream is a DOUBLED sequence, the
    noisy copy's L rows and then the clean copy's, ``positions`` as the model
    hands them (``0..L-1`` twice), and the keys a query sees follow the rule of
    diffusion over blocks (:func:`block_diffusion_attention`), not the causal one.
    ``into_stream`` scales ``wo``'s draw.  ``head_norm`` false (``smallthinker``):
    q and k pass NO norm a head and the part owns no ``q_norm`` / ``k_norm``.
    ``product_sites`` false: the q, k, v products are NOT save sites
    (``ops/remat.py``), the attention's output alone is — for a step whose
    memory is its tight side, as :class:`IndexedSparseAttention`'s (``smallthinker``:
    the trainer's estimate reads this step 3 GiB under the compiler's account,
    so the budget would keep every product and the first compile land over the
    line; the output is what the chooser ranks first, PERF.md section 6, PR 69)."""

    q_heads: int
    kv_heads: int
    head_dim: int
    window: int
    theta: float
    eps: float
    gate: bool = True
    rotary: Optional[bool] = None
    block_length: int = 0
    into_stream: float = 1.0
    head_norm: bool = True
    product_sites: bool = True

    @property
    def counters(self):
        """A full layer counts its causal pairs alone (a model of full layers only reports nothing of a window); a layer
        under the rule of diffusion over blocks the rule's."""
        if self.block_length:
            return BD_COUNTERS
        return WINDOW_COUNTERS if self.window else {"attn_pairs_full": WINDOW_COUNTERS["attn_pairs_full"]}

    def init(self, draw: Draws, d: int):
        q, kv = self.q_heads * self.head_dim, self.kv_heads * self.head_dim
        made = {"wq": draw.normal((d, q)), "wk": draw.normal((d, kv)), "wv": draw.normal((d, kv))}
        if self.gate:
            made["wz"] = draw.normal((d, q))
        made["wo"] = draw.normal((q, d), self.into_stream)
        if self.head_norm:
            made.update({"q_norm": jnp.ones((self.head_dim,), jnp.float32), "k_norm": jnp.ones((self.head_dim,), jnp.float32)})
        return made

    def apply(self, u, blk, positions, axis, cast):
        b, l, _ = u.shape
        with jax.named_scope("attn_proj"):
            heads = lambda t: t.reshape(b, l, -1, self.head_dim)  # noqa: E731
            # save sites (ops/remat.py): each product as the glue reads it
            product = remat_lib.product if self.product_sites else (lambda name, a, w: a @ w)
            q, k, v = (heads(product(name, u, cast(blk["w" + name]))) for name in ("q", "k", "v"))
            z = remat_lib.product("attn_gate", u, cast(blk["wz"])) if self.gate else None
        with jax.named_scope("attn_glue"):
            if self.head_norm:
                q, k = rms_norm(q, blk["q_norm"], self.eps), rms_norm(k, blk["k_norm"], self.eps)
            if bool(self.window) if self.rotary is None else self.rotary:
                q, k = rope(q, positions, self.theta), rope(k, positions, self.theta)
            group = self.q_heads // self.kv_heads
            if group > 1:
                k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
        computed = None
        if self.block_length:
            if axis is not None and axis_size(axis) > 1:
                raise ValueError("diffusion over blocks over a sharded sequence is not supported: a noisy query's clean keys live on other shards")
            att, pairs = block_diffusion_attention(q, k, v, self.block_length)
            computed = {"bd_pairs_computed": jnp.float32(b * self.q_heads * pairs)}
        else:
            att = ring_attention(q, k, v, axis_name=axis, causal=True, window=self.window or None)
        if self.gate:
            with jax.named_scope("attn_glue"):
                att = (att.reshape(b, l, -1) * jax.nn.sigmoid(z.astype(jnp.float32))).astype(att.dtype)
        with jax.named_scope("attn_proj"):
            return att.reshape(b, l, -1) @ cast(blk["wo"]), computed

    def shape_counts(self, batch: int, length: int):
        every = batch * self.q_heads
        if self.block_length:       # ``length``: a copy's L
            return {"bd_pairs_needed": every * (length * length + self.block_length * length)}
        if not self.window or self.window >= length:
            return {"attn_pairs_full": every * (length * (length + 1) // 2)}
        w = self.window
        computed = flash_ops.window_pairs_computed(length, w)
        return {"attn_pairs_window": every * (w * (w + 1) // 2 + (length - w) * w), "attn_pairs_window_computed": every * computed}


def block_diffusion_attention_reference(q, k, v, block_length: int):
    """Softmax attention of the doubled sequence ``q``, ``k``, ``v`` [B, 2 L, H, D] (the noisy copy's rows, then the
    clean copy's) under the rule as a dense mask (``ops/flash_attention.bd_mask``), in XLA."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * q.shape[-1] ** -0.5
    scores = jnp.where(flash_ops.bd_mask(q.shape[1] // 2, block_length)[None, None], scores, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1).astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def block_diffusion_attention(q, k, v, block_length: int):
    """``(o, (query, key) pairs a head that one pass multiplies)``: the kernels under the rule on the TPU inside
    their contract (``ops/flash_attention.bd_flash_attention``), XLA's dense product under the mask elsewhere."""
    backend = jax.default_backend()
    why_not = f"backend={backend}" if backend != "tpu" else flash_ops.bd_outside_contract(q, k, v, block_length)
    if why_not:
        announce_path(PATH_XLA_REFERENCE, q, False, f"{why_not} rule=block_diffusion g={block_length}")
        with jax.named_scope("bd_attn"):
            return block_diffusion_attention_reference(q, k, v, block_length), float(q.shape[1] * q.shape[1])
    return flash_ops.bd_flash_attention(q, k, v, block_length), flash_ops.bd_pairs_computed(q.shape[1] // 2, block_length)


def layer_norm(x, gain, bias, eps):
    """LayerNorm over the last axis: statistics and arithmetic in float32, ONE downcast."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    return ((x32 - mean) * jax.lax.rsqrt(var + eps) * gain + bias).astype(x.dtype)


def selected_attention_reference(q, k, v, mask):
    """Softmax attention over the keys ``mask`` [B, L, L] keeps of each query's row, in XLA: ``(o [B, L, H, D],
    lse [B * H, 1, L] float32)`` as ``ops/flash_attention.masked_flash_attention`` returns them."""
    b, l, h, d = q.shape
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * d ** -0.5
    scores = jnp.where((mask != 0)[:, None], scores, -jnp.inf)
    lse = jax.nn.logsumexp(scores, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", jnp.exp(scores - lse[..., None]).astype(v.dtype), v, preferred_element_type=jnp.float32)
    return o.astype(q.dtype), lse.reshape(b * h, 1, l)


def selected_attention(q, k, v, mask, counts=None):
    """``(o, lse, (query, key) pairs a head that one pass multiplies)``: the masked flash kernels on the TPU inside their
    contract (``ops/flash_attention``), XLA's dense product under the mask elsewhere.  ``counts``: the selection's
    second result, from which the kernels' block summary is summed."""
    backend = jax.default_backend()
    why_not = f"backend={backend}" if backend != "tpu" else flash_ops.masked_outside_contract(q, k, v, mask)
    if why_not:
        announce_path(PATH_XLA_REFERENCE, q, True, f"{why_not} mask=int8")
        with jax.named_scope("dsa_attn"):
            return (*selected_attention_reference(q, k, v, mask), jnp.float32(q.shape[0] * q.shape[1] * q.shape[1]))
    rows = flash_ops.masked_rows(q.shape[1])
    with jax.named_scope("dsa_attn"):
        summary = flash_ops.block_summary(mask, rows, counts)
    o, lse = flash_ops.masked_flash_attention(q, k, v, mask, summary)
    return o, lse, jnp.sum((summary > 0).astype(jnp.float32)) * (rows * rows)


@dataclasses.dataclass(frozen=True)
class IndexedSparseAttention(Part):
    """``KeyeVL2``'s (module docstring): grouped-query attention with a norm a head and the rotary turn, over the
    ``topk`` keys a learned indexer (``index_heads`` heads ``index_dim`` wide over ONE index key a position) selects
    for each query, ``chunk`` query rows at a time.  The key/value heads are REPEATED to the queries' ahead of the
    attention, as :class:`GroupedQueryAttention` does.  The indexer reads a stop-gradient of the stream and is trained
    by its own loss (the part's stat ``indexer_loss``).  ``into_stream`` scales ``wo``'s draw (the family's
    ``moe_lm.KEYE_VL2_INTO_STREAM``, where the reason is)."""

    q_heads: int
    kv_heads: int
    head_dim: int
    theta: float
    eps: float
    index_heads: int
    index_dim: int
    topk: int
    chunk: int
    into_stream: float = 1.0

    counters = DSA_COUNTERS

    def init(self, draw: Draws, d: int):
        q, kv, e = self.q_heads * self.head_dim, self.kv_heads * self.head_dim, self.index_dim
        return {
            "wq": draw.normal((d, q)), "wk": draw.normal((d, kv)), "wv": draw.normal((d, kv)), "wo": draw.normal((q, d), self.into_stream),
            "q_norm": jnp.ones((self.head_dim,), jnp.float32), "k_norm": jnp.ones((self.head_dim,), jnp.float32),
            "idx_wq": draw.normal((d, self.index_heads * e)), "idx_wk": draw.normal((d, e)), "idx_ww": draw.normal((d, self.index_heads)),
            "idx_norm": jnp.ones((e,), jnp.float32), "idx_norm_bias": jnp.zeros((e,), jnp.float32),
        }

    def indexer(self, u, blk, positions, cast):
        """``(qI [B, L, J, E], kI [B, L, E], w [B, L, J] float32)`` from the stream ``u`` (its gradient stopped by the caller)."""
        b, l, _ = u.shape
        with jax.named_scope("dsa_index"):
            qi = rope((u @ cast(blk["idx_wq"])).reshape(b, l, self.index_heads, self.index_dim), positions, self.theta)
            ki = layer_norm(u @ cast(blk["idx_wk"]), blk["idx_norm"], blk["idx_norm_bias"], self.eps)
            ki = rope(ki[:, :, None, :], positions, self.theta)[:, :, 0]
            w = jnp.dot(u, cast(blk["idx_ww"]), preferred_element_type=jnp.float32) * (self.index_heads ** -0.5 * self.index_dim ** -0.5)
        return qi, ki, w

    def apply(self, u, blk, positions, axis, cast):
        if axis is not None and axis_size(axis) > 1:
            raise ValueError("learned sparse attention over a sharded sequence is not supported: a query's selection ranges over every shard's keys")
        b, l, _ = u.shape
        with jax.named_scope("attn_proj"):
            heads = lambda t: t.reshape(b, l, -1, self.head_dim)  # noqa: E731
            # NOT save sites: a kept byte of this step costs its peak 1.5 bytes (PERF.md section 7, `keye_vl2_job` (f)), and
            # the three products are the cheapest thing here to make again (2.5 ms a layer); the selection, the
            # attention's output and the indexer's loss's gradients (``ops/sparse_select.indexer_loss``) are the sites
            q, k, v = (heads(u @ cast(blk["w" + name])) for name in ("q", "k", "v"))
        with jax.named_scope("attn_glue"):
            q, k = rms_norm(q, blk["q_norm"], self.eps), rms_norm(k, blk["k_norm"], self.eps)
            q, k = rope(q, positions, self.theta), rope(k, positions, self.theta)
            group = self.q_heads // self.kv_heads
            wide_k, wide_v = (jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)) if group > 1 else (k, v)
        qi, ki, w = self.indexer(_constant(u), blk, positions, cast)
        mask, counts = sparse_select.select(qi, ki, w, self.topk, self.chunk)
        # a save site (ops/remat.py) of a new kind: integer, with no gradient, and worth the score products over the
        # causal pairs and the selection's 32 passes over every pair, each of which moves a pair's 4 bytes through
        # HBM: the time of 960 MXU FLOPs on a v5e (197 TFLOP/s over 819 GB/s), which is what the chooser ranks by
        mask, counts = remat_lib.site("dsa_mask", b * l * l * (self.index_heads * self.index_dim + 32 * 960), mask, counts)
        o, lse, computed = selected_attention(q, wide_k, wide_v, mask, counts)
        loss = sparse_select.indexer_loss(qi, ki, w, q, k, lse, mask, self.chunk)
        with jax.named_scope("attn_proj"):
            y = o.reshape(b, l, -1) @ cast(blk["wo"])
        return y, {"indexer_loss": loss, "dsa_pairs_computed": self.q_heads * computed}

    def shape_counts(self, batch: int, length: int):
        reach = min(self.topk, length)
        needed = reach * (reach + 1) // 2 + (length - reach) * reach
        every = batch * self.q_heads
        return {
            "dsa_pairs_causal": every * (length * (length + 1) // 2), "dsa_pairs_needed": every * needed,
            "dsa_rows_selecting": batch * max(length - self.topk, 0),
        }
