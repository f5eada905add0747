"""A learned indexer's selection of keys (DeepSeek-V3.2-Exp's sparse
attention, as ``Keye-VL-2.0``'s ``sa_config`` sizes it): the index score of
every earlier key, the EXACT top-k of each query's row, and the indexer's
own loss.  The attention over the selected keys is
``ops/flash_attention.masked_flash_attention``.

With J index heads E wide over ONE index key a position, for query t and
key s <= t (float32):

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

- :func:`index_scores`: a block of query rows against all keys, ``-inf``
  after the query's own position; a ``custom_vjp`` of its own (the loss
  below calls its gradient kernels directly).  On the TPU three Pallas kernels
  (the score; its gradient to qI and w; its gradient to kI): J products
  contracted over a 128-lane group that holds ``128 // E`` heads, each head
  with the other heads' lanes zeroed against the key tiled to the group
  (``ops/flash_attention._only``'s way), weighed and summed in VMEM — the
  [J, rows, L] per-head scores never reach HBM.  Elsewhere XLA einsums.
- :func:`select`: S_t = the ``min(t + 1, k)`` keys of largest I[t, .], ties
  to the lower index (``lax.top_k``'s rule), as an int8 mask [B, L, L].  The
  k-th largest of a row is found by RADIX SELECT over the order-preserving
  unsigned image of the float32 scores: 32 compare-and-count passes build
  the threshold a bit at a time, and only where a row has more scores equal
  to its threshold than it may take does a second search (over the index,
  ``ceil(log2 L)`` passes) find how many of them, lowest index first.  No
  sort, no approximation: ``tests/test_keye_vl2.py`` holds it against
  ``lax.top_k`` on short rows, on ties (relu makes exact zeros) and across
  chunks.
- :func:`indexer_loss`: ``mean_t KL(p[t, .] || softmax_{S_t}(I[t, .]))``
  with ``p`` the attention's probabilities summed over the heads and
  L1-normalised over S_t, a constant; its gradient reaches the scores alone
  (``softmax_{S_t}(I) - p`` on the selected set).  ``p``'s head sum is one
  more pass over the pairs from the attention's saved logsumexps
  (:func:`head_summed_probs`: a Pallas kernel on the TPU, the head the
  grid's last axis and the [rows, keys] sum resident in VMEM).  The loss is
  a scalar, so it walks the pairs ONCE a step: a ``custom_vjp`` whose
  forward rule makes, in each chunk, the scores, the probabilities, the KL's
  rows AND the rows' gradient to the scores, handed at once to the score's
  gradient kernels; the three gradients (to qI, kI, w: 35.7 MB a layer at L
  = 16,384 where a chunk's scores alone are 33.5) are the residuals, the
  save site ``dsa_index_grads`` of a rematerialised block (``ops/remat.py``),
  and the backward multiplies them by the loss's cotangent.  Kept, the
  block's backward walks nothing; not kept, its recomputation runs the rule
  once.  A forward nobody differentiates computes the loss and no gradient.

Everything walks the queries ``chunk`` rows at a time (``q_chunk_size``),
so that one chunk's [rows, L] float32 arrays are alive at a time: a layer a
step launches the score kernel twice (the selection, the loss), its
gradient pair once and the head sum once.  Scopes: ``dsa_index`` (the score
products, forward and backward), ``dsa_select`` (threshold, ties, mask),
``dsa_loss`` (the head-summed probabilities and the KL).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import remat
from elasticdl_tpu.ops.flash_attention import _LANE, _NN, _NT, _TN, _use_interpret
from elasticdl_tpu.ops.ring_attention import PATH_PALLAS_COMPILED, PATH_PALLAS_INTERPRET, PATH_XLA_REFERENCE, announce_path


def _dot(a, b, dims):
    # the default precision whatever the caller's ``jax.default_matmul_precision``: the operands are bfloat16, whose
    # products are exact in the float32 they are summed in, and Mosaic refuses a bfloat16 operand under ``highest``
    return lax.dot_general(a, b, (dims, ((), ())), precision=lax.Precision.DEFAULT, preferred_element_type=jnp.float32)


def kernels_outside_contract(qi, length: int) -> str:
    """Why the indexer's shapes are outside the Pallas kernels' contract,
    ``""`` inside it: an index head width that divides 128 lanes, the heads
    whole lane groups, rows and keys in whole 128-row tiles."""
    _, rows, heads, e = qi.shape
    if _LANE % e or heads % (_LANE // e):
        return f"{heads} index heads of {e}: not whole groups of 128 // E heads to a block of lanes"
    if rows % 128 or length % 128:
        return f"{rows} rows against {length} keys: not whole 128-row tiles"
    return ""


def kernel_path(qi, ki) -> str:
    """``""`` where the Pallas kernels run (a TPU, inside their contract),
    else why the XLA forms do.  The one switch a test patches to put a call
    on the kernels through the interpreter."""
    if jax.default_backend() != "tpu":
        return f"backend={jax.default_backend()}"
    return kernels_outside_contract(qi, ki.shape[1])


def _announce(what: str, x, why_not: str) -> None:
    """ONE ``attention path:`` line a distinct call, as the attention's own: a job whose indexer fell to the XLA
    forms ([J, rows, L] per-head scores through HBM) says so where ``benchmark/run.py``'s ``expect`` reads it."""
    path = PATH_XLA_REFERENCE if why_not else PATH_PALLAS_INTERPRET if _use_interpret() else PATH_PALLAS_COMPILED
    announce_path(path, x, True, what + f"; {why_not}" * bool(why_not))


def _tile(n: int, most: int) -> int:
    return next(t for t in (most, 512, 256, 128) if t <= most and n % t == 0)


# ---- the index score ----


def index_scores_reference(qi, ki, w, offset):
    """``I`` [B, rows, L] float32 for the query rows ``offset ..`` (``qi``
    [B, rows, J, E], ``w`` [B, rows, J] float32) against every key ``ki``
    [B, L, E]; ``-inf`` after a query's own position, and no negative zero
    (a row of relus that are all 0 under negative weights)."""
    s = jnp.einsum("bqje,bke->bjqk", qi, ki, preferred_element_type=jnp.float32)
    scores = jnp.einsum("bjqk,bqj->bqk", jax.nn.relu(s), w.astype(jnp.float32))
    scores = jnp.where(scores == 0.0, 0.0, scores)
    return jnp.where(_causal(offset, scores.shape[1], scores.shape[2]), scores, -jnp.inf)


def _causal(offset, rows: int, length: int):
    return (offset + jnp.arange(rows))[:, None] >= jnp.arange(length)[None, :]


def _heads_of_a_group(x, e):
    """``x`` [t, 128] as each of its ``128 // e`` heads alone (the other heads' lanes zeroed)."""
    if e == _LANE:
        return [x]
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return [jnp.where((lane >= h * e) & (lane < (h + 1) * e), x, jnp.zeros_like(x)) for h in range(_LANE // e)]


def _visible(offset_ref, i, j, bq, bk):
    """(whether block (i, j) holds a key at or before one of its queries, the queries' first position, the keys')."""
    q0, k0 = offset_ref[0] + i * bq, j * bk
    return k0 <= q0 + bq - 1, q0, k0


def _score_kernel(offset_ref, qi_ref, ki_ref, w_ref, out_ref, *, e, heads):
    i, j = pl.program_id(1), pl.program_id(2)
    bq, bk = out_ref.shape[1:]
    visible, q0, k0 = _visible(offset_ref, i, j, bq, bk)

    @pl.when(jnp.logical_not(visible))
    def _():
        out_ref[0] = jnp.full((bq, bk), -jnp.inf, jnp.float32)

    @pl.when(visible)
    def _():
        ki, w = ki_ref[0], w_ref[0]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for g in range(heads * e // _LANE):
            for h, x in enumerate(_heads_of_a_group(qi_ref[0, :, g * _LANE:(g + 1) * _LANE], e)):
                jj = g * (_LANE // e) + h
                acc += w[:, jj:jj + 1] * jnp.maximum(_dot(x, ki, _NT), 0.0)
        acc = jnp.where(acc == 0.0, 0.0, acc)     # no negative zero
        q_pos = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        out_ref[0] = jnp.where(q_pos >= k_pos, acc, -jnp.inf)


def _score_dq_kernel(offset_ref, qi_ref, ki_ref, w_ref, g_ref, dq_ref, dw_ref, dq_s, dw_s, *, e, heads):
    i, j, n = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = g_ref.shape[1:]
    visible, _, _ = _visible(offset_ref, i, j, bq, bk)

    @pl.when(j == 0)
    def _():
        dq_s[...] = jnp.zeros(dq_s.shape, jnp.float32)
        dw_s[...] = jnp.zeros(dw_s.shape, jnp.float32)

    @pl.when(visible)
    def _():
        ki, w, g = ki_ref[0], w_ref[0], g_ref[0]
        per = _LANE // e
        lane = lax.broadcasted_iota(jnp.int32, (bq, _LANE), 1)
        dw = jnp.zeros((bq, _LANE), jnp.float32)
        for grp in range(heads // per):
            into = jnp.zeros((bq, _LANE), jnp.float32)
            for h, x in enumerate(_heads_of_a_group(qi_ref[0, :, grp * _LANE:(grp + 1) * _LANE], e)):
                jj = grp * per + h
                s = _dot(x, ki, _NT)
                dw += jnp.where(lane == jj, jnp.sum(g * jnp.maximum(s, 0.0), axis=1, keepdims=True), 0.0)
                ds = jnp.where(s > 0.0, g * w[:, jj:jj + 1], 0.0).astype(ki.dtype)
                # the key is tiled over the group's lanes: every head's lanes hold ds @ kI; this head keeps its own
                into = jnp.where((lane >= h * e) & (lane < (h + 1) * e), _dot(ds, ki, _NN), into)
            dq_s[:, grp * _LANE:(grp + 1) * _LANE] += into
        dw_s[...] += dw

    @pl.when(j == n - 1)
    def _():
        dq_ref[0] = dq_s[...].astype(dq_ref.dtype)
        dw_ref[0] = dw_s[...]


def _score_dk_kernel(offset_ref, qi_ref, ki_ref, w_ref, g_ref, dk_ref, dk_s, *, e, heads):
    j, i, n = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    bq, bk = g_ref.shape[1:]
    visible, _, _ = _visible(offset_ref, i, j, bq, bk)

    @pl.when(i == 0)
    def _():
        dk_s[...] = jnp.zeros(dk_s.shape, jnp.float32)

    @pl.when(visible)
    def _():
        ki, w, g = ki_ref[0], w_ref[0], g_ref[0]
        per = _LANE // e
        acc = jnp.zeros((bk, _LANE), jnp.float32)
        for grp in range(heads // per):
            for h, x in enumerate(_heads_of_a_group(qi_ref[0, :, grp * _LANE:(grp + 1) * _LANE], e)):
                s = _dot(x, ki, _NT)
                jj = grp * per + h
                ds = jnp.where(s > 0.0, g * w[:, jj:jj + 1], 0.0).astype(ki.dtype)
                acc += _dot(ds, x, _TN)     # the head's own lanes alone (x is zero elsewhere)
        dk_s[...] += acc

    @pl.when(i == n - 1)
    def _():
        dk_ref[0] = dk_s[...]


def _score_specs(b, rows, length, heads, e, queries_first: bool):
    bq, bk = _tile(rows, 512), _tile(length, 512)
    grid = (b, rows // bq, length // bk) if queries_first else (b, length // bk, rows // bq)
    ij = (lambda x, y: (x, y)) if queries_first else (lambda x, y: (y, x))

    def spec(block, index):
        return pl.BlockSpec(block, lambda bb, x, y, offset: index(bb, *ij(x, y)), memory_space=pltpu.VMEM)

    specs = dict(
        qi=spec((1, bq, heads * e), lambda bb, i, j: (bb, i, 0)),
        ki=spec((1, bk, _LANE), lambda bb, i, j: (bb, j, 0)),
        w=spec((1, bq, heads), lambda bb, i, j: (bb, i, 0)),
        pairs=spec((1, bq, bk), lambda bb, i, j: (bb, i, j)),
        dw=spec((1, bq, _LANE), lambda bb, i, j: (bb, i, 0)),
    )
    return grid, bq, bk, specs


def _cost(flops: int, operands=(), transcendentals: int = 0):
    return pl.CostEstimate(flops=flops, transcendentals=transcendentals, bytes_accessed=remat.nbytes(operands))


def _score_cost(b, rows, length, heads, operands=(), products: int = 1):
    """The score kernel's declared cost: ``products`` (1 forward, 2 in each gradient kernel) a head a pair over the
    causal half of the pairs, each contracted over a whole block of 128 lanes."""
    return _cost(products * 2 * b * rows * length * heads * _LANE // 2, operands)


def _launch(kernel, grid, in_specs, out_specs, out_shape, scratch, cost):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs, out_specs=out_specs, scratch_shapes=scratch,
        ),
        out_shape=out_shape, interpret=_use_interpret(), cost_estimate=cost,
    )


def _score_operands(qi, ki):
    """(qI as the projection wrote it [B, rows, J * E], the ONE index key tiled to a block of lanes [B, L, 128])."""
    b, rows, heads, e = qi.shape
    return qi.reshape(b, rows, heads * e), jnp.tile(ki, (1, 1, _LANE // e))


def _scores_by_kernel(qi, ki, w, offset):
    b, rows, heads, e = qi.shape
    length = ki.shape[1]
    grid, bq, bk, specs = _score_specs(b, rows, length, heads, e, True)
    flat, tiled = _score_operands(qi, ki)
    out = jax.ShapeDtypeStruct((b, rows, length), jnp.float32)
    return _launch(
        functools.partial(_score_kernel, e=e, heads=heads), grid, [specs["qi"], specs["ki"], specs["w"]], specs["pairs"], out, [],
        _score_cost(b, rows, length, heads, (flat, tiled, w, out)),
    )(jnp.reshape(offset, (1,)).astype(jnp.int32), flat, tiled, w)


def _score_grads_by_kernel(qi, ki, w, offset, g):
    """``(dqI, dkI, dw)`` of the score for its cotangent ``g`` [B, rows, L]; ``dkI`` in FLOAT32 (a caller that walks
    the queries in chunks sums the chunks' before it rounds)."""
    b, rows, heads, e = qi.shape
    length = ki.shape[1]
    flat, tiled = _score_operands(qi, ki)
    at = jnp.reshape(offset, (1,)).astype(jnp.int32)
    grid, bq, bk, specs = _score_specs(b, rows, length, heads, e, True)
    dq, dw = _launch(
        functools.partial(_score_dq_kernel, e=e, heads=heads), grid,
        [specs["qi"], specs["ki"], specs["w"], specs["pairs"]], [specs["qi"], specs["dw"]],
        [jax.ShapeDtypeStruct(flat.shape, flat.dtype), jax.ShapeDtypeStruct((b, rows, _LANE), jnp.float32)],
        [pltpu.VMEM((bq, heads * e), jnp.float32), pltpu.VMEM((bq, _LANE), jnp.float32)],
        _score_cost(b, rows, length, heads, (flat, tiled, w, g, flat), products=2),
    )(at, flat, tiled, w, g)
    grid, bq, bk, specs = _score_specs(b, rows, length, heads, e, False)
    dk = _launch(
        functools.partial(_score_dk_kernel, e=e, heads=heads), grid,
        [specs["qi"], specs["ki"], specs["w"], specs["pairs"]], specs["ki"],
        jax.ShapeDtypeStruct((b, length, _LANE), jnp.float32), [pltpu.VMEM((bk, _LANE), jnp.float32)],
        _score_cost(b, rows, length, heads, (flat, tiled, w, g), products=2),
    )(at, flat, tiled, w, g)
    # the heads of a lane group each added their own lanes: the ONE key's gradient is their sum
    dk = jnp.sum(dk.reshape(b, length, _LANE // e, e), axis=2)
    return dq.reshape(qi.shape), dk, dw[:, :, :heads].astype(w.dtype)


def _index_scores(qi, ki, w, offset):
    why_not = kernel_path(qi, ki)
    _announce(f"dsa_index keys={ki.shape[1]}", qi, why_not)
    with jax.named_scope("dsa_index"):
        if why_not:
            return index_scores_reference(qi, ki, w, offset)
        return _scores_by_kernel(qi, ki, w, offset)


@jax.custom_vjp
def index_scores(qi, ki, w, offset):
    """:func:`index_scores_reference`'s ``I`` [B, rows, L]: the Pallas
    kernels where :func:`kernel_path` allows, the XLA einsums elsewhere."""
    return _index_scores(qi, ki, w, offset)


def _index_scores_fwd(qi, ki, w, offset):
    return _index_scores(qi, ki, w, offset), (qi, ki, w, offset)


def _score_grads(qi, ki, w, offset, g):
    """``(dqI, dkI, dw)`` of :func:`index_scores` for its cotangent ``g``, which counts up to a query's own position
    alone; ``dqI`` and ``dw`` in their operands' types, ``dkI`` in float32 (:func:`_score_grads_by_kernel` has why)."""
    with jax.named_scope("dsa_index"):
        g = jnp.where(_causal(offset, g.shape[1], g.shape[2]), g, 0.0)
        if kernel_path(qi, ki):
            # the key widened and rounded back: the same scores, and its gradient comes in the float32 it is summed in
            plain = lambda qi, wide, w: index_scores_reference(qi, wide.astype(ki.dtype), w, offset)  # noqa: E731
            return jax.vjp(plain, qi, ki.astype(jnp.float32), w)[1](g)
        return _score_grads_by_kernel(qi, ki, w, offset, g)


def _index_scores_bwd(res, g):
    qi, ki, w, offset = res
    dq, dk, dw = _score_grads(qi, ki, w, offset, g)
    return dq, dk.astype(ki.dtype), dw, None


index_scores.defvjp(_index_scores_fwd, _index_scores_bwd)


# ---- the selection ----


def ordered_image(x):
    """uint32 whose unsigned order is the float32 order of ``x`` (``-inf``
    lowest; no NaN comes here): a negative number's bits all flipped, a
    positive one's sign bit set."""
    bits = lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(0x80000000))


def select_rows(scores, k):
    """int8 [..., rows, L]: for each row of ``scores`` [..., rows, L] (float32,
    ``-inf`` where a key may not be taken) the ``k`` [rows] largest, ties to
    the lower index.  ``k`` is at most the row's number of finite scores."""
    with jax.named_scope("dsa_select"):
        u = ordered_image(scores)
        k = jnp.broadcast_to(k.astype(jnp.int32), u.shape[:-1])
        count = lambda keep: jnp.sum(keep, axis=-1, dtype=jnp.int32)  # noqa: E731

        def bit(n, thr):
            candidate = thr | (jnp.uint32(1) << (31 - n).astype(jnp.uint32))
            return jnp.where(count(u >= candidate[..., None]) >= k, candidate, thr)

        # the k-th largest image: the largest value that at least k of the row reach
        thr = lax.fori_loop(0, 32, bit, jnp.zeros(u.shape[:-1], jnp.uint32))[..., None]
        above, equal = u > thr, u == thr
        owed = k - count(above)       # how many of the scores AT the threshold the row takes: at least one

        def lowest_ties(_):
            at = lax.broadcasted_iota(jnp.int32, u.shape, u.ndim - 1)
            bits = max(int(u.shape[-1] - 1).bit_length(), 1)

            def index_bit(n, last):
                # the largest index m with fewer than ``owed`` ties before it: the tie AT m is the last one taken
                candidate = last | (jnp.int32(1) << (bits - 1 - n))
                return jnp.where(count(equal & (at < candidate[..., None])) < owed, candidate, last)

            last = lax.fori_loop(0, bits, index_bit, jnp.zeros(u.shape[:-1], jnp.int32))
            return above | (equal & (at <= last[..., None]))

        tied = jnp.any(count(equal) != owed)
        return lax.cond(tied, lowest_ties, lambda _: above | equal, None).astype(jnp.int8)


def select_reference(scores, k, most: int):
    """:func:`select_rows` by ``lax.top_k`` (a sort on the TPU): the oracle; ``most``: the largest of ``k``."""
    length = scores.shape[-1]
    _, chosen = lax.top_k(scores, most)                                   # best first, ties to the lower index
    taken = jnp.arange(most) < jnp.broadcast_to(k, scores.shape[:-1])[..., None]
    hits = jax.nn.one_hot(jnp.where(taken, chosen, length), length + 1, dtype=jnp.int8)
    return jnp.sum(hits, axis=-2, dtype=jnp.int8)[..., :length]


def _chunks(x, chunk: int, axis: int = 1):
    """``x`` cut into ``chunk`` rows along ``axis``, the chunks leading (``lax.map``'s axis)."""
    n = x.shape[axis] // chunk
    return jnp.moveaxis(x.reshape(x.shape[:axis] + (n, chunk) + x.shape[axis + 1:]), axis, 0)


def _unchunk(x, axis: int = 1):
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


def chunk_rows(length: int, chunk: int) -> int:
    """Rows a step of the walk takes: ``chunk`` where it divides ``length``, else all."""
    return chunk if 0 < chunk < length and length % chunk == 0 else length


def select(qi, ki, w, topk: int, chunk: int):
    """``(mask, counts)``: int8 [B, L, L] whose row t keeps the ``min(t + 1,
    topk)`` keys s <= t of largest index score, and int32 [B, L / rows, L],
    how many queries of each walked chunk of ``rows`` (:func:`chunk_rows`)
    keep each key — what a kernel's block summary is summed from without
    reading the mask again.  No gradient (its operands' is stopped)."""
    qi, ki, w = (lax.stop_gradient(x) for x in (qi, ki, w))
    length = qi.shape[1]
    rows = chunk_rows(length, chunk)

    def of_a_chunk(part):
        qi_c, w_c, offset = part
        scores = index_scores(qi_c, ki, w_c, offset)
        keep = select_rows(scores, jnp.minimum(offset + jnp.arange(rows) + 1, topk))
        with jax.named_scope("dsa_select"):
            return keep, jnp.sum(keep, axis=1, dtype=jnp.int32)

    mask, counts = lax.map(of_a_chunk, (_chunks(qi, rows), _chunks(w, rows), jnp.arange(0, length, rows)))
    return _unchunk(mask), jnp.moveaxis(counts, 0, 1)


# ---- the indexer's loss ----


def head_summed_probs_reference(q, k, lse, mask):
    """float32 [B, rows, L]: ``sum_h exp(q_h . k_g(h) / sqrt(D) - lse_h)`` on
    the selected pairs, 0 elsewhere.  ``q`` [B, rows, H, D], ``k`` [B, L, G,
    D] (query head h reads key head ``h // (H / G)``), ``lse`` [B, H, rows]."""
    b, rows, heads, d = q.shape
    groups = k.shape[2]
    by_group = q.reshape(b, rows, groups, heads // groups, d)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", by_group, k, preferred_element_type=jnp.float32) * d ** -0.5
    p = jnp.exp(s - lse.reshape(b, groups, heads // groups, rows)[..., None])
    return jnp.where(mask != 0, jnp.sum(p, axis=(1, 2)), 0.0)


def _probs_kernel(summary_ref, q_ref, k_ref, lse_ref, mask_ref, p_ref, *, scale, nq, nk):
    b, i, j, h = (pl.program_id(a) for a in range(4))
    live = summary_ref[(b * nq + i) * nk + j] > 0

    @pl.when(h == 0)
    def _():
        p_ref[0] = jnp.zeros(p_ref.shape[1:], jnp.float32)

    @pl.when(live)
    def _():
        s = _dot(q_ref[0], k_ref[0], _NT) * scale - lse_ref[0, 0, :][:, None]
        p_ref[0] += jnp.exp(s)

    @pl.when(h == pl.num_programs(3) - 1)
    def _():
        # once, after the last head: an unselected pair's sum may be anything (inf too), and goes
        p_ref[0] = jnp.where(mask_ref[0].astype(jnp.int32) != 0, p_ref[0], 0.0)


def _probs_cost(b, rows, length, heads, d, operands=()):
    """The probabilities kernel's declared cost: a product and an exponential a head a pair over the causal half."""
    return _cost(2 * b * heads * rows * length * d // 2, operands, transcendentals=b * heads * rows * length // 2)


def _probs_by_kernel(q, k, lse, mask):
    b, rows, heads, d = q.shape
    length, groups = k.shape[1], k.shape[2]
    bq, bk = _tile(rows, 512), _tile(length, 1024)
    nq, nk = rows // bq, length // bk
    # queries first (ops/flash_attention.block_summary has why)
    by_key = jnp.sum(mask.reshape(b, nq, bq, length), axis=2, dtype=jnp.int32)
    summary = jnp.sum(by_key.reshape(b, nq, nk, bk), axis=-1).reshape(-1)
    ratio = heads // groups

    def spec(block, index):
        return pl.BlockSpec(block, lambda bb, i, j, h, summary: index(bb, i, j, h), memory_space=pltpu.VMEM)

    qk, kk = q.reshape(b, rows, heads * d), k.reshape(b, length, groups * d)
    lse = lse.reshape(b * heads, 1, rows)
    out = jax.ShapeDtypeStruct((b, rows, length), jnp.float32)
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=d ** -0.5, nq=nq, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b, nq, nk, heads),
            in_specs=[
                spec((1, bq, d), lambda bb, i, j, h: (bb, i, h)),
                spec((1, bk, d), lambda bb, i, j, h: (bb, j, h // ratio)),
                spec((1, 1, bq), lambda bb, i, j, h: (bb * heads + h, 0, i)),
                spec((1, bq, bk), lambda bb, i, j, h: (bb, i, j)),
            ],
            out_specs=spec((1, bq, bk), lambda bb, i, j, h: (bb, i, j)),
            scratch_shapes=[],
        ),
        out_shape=out, interpret=_use_interpret(), cost_estimate=_probs_cost(b, rows, length, heads, d, (qk, kk, lse, mask, out)),
    )(summary, qk, kk, lse, mask)


def probs_path(q, k) -> str:
    """``""`` where :func:`head_summed_probs` runs its Pallas kernel (a TPU, D = 128, whole 128-row tiles), else why not."""
    if jax.default_backend() != "tpu":
        return f"backend={jax.default_backend()}"
    return "" if q.shape[3] == _LANE and not (q.shape[1] % 128 or k.shape[1] % 128) else f"q{q.shape} k{k.shape}: D = 128 in whole 128-row tiles"


def head_summed_probs(q, k, lse, mask):
    """:func:`head_summed_probs_reference`'s sum: the Pallas kernel where :func:`probs_path` allows, XLA elsewhere."""
    why_not = probs_path(q, k)
    _announce(f"dsa_loss keys={k.shape[1]}", q, why_not)
    with jax.named_scope("dsa_loss"):
        if why_not:
            return head_summed_probs_reference(q, k, lse, mask)
        return _probs_by_kernel(q, k, lse, mask)


def kl_rows(scores, probs, mask):
    """[B, rows]: ``KL(p || softmax_S(I))`` of each row, ``p`` = ``probs``
    L1-normalised over the row's selected set S (``mask``), a constant."""
    with jax.named_scope("dsa_loss"):
        keep = mask != 0
        p = lax.stop_gradient(probs / jnp.sum(probs, axis=-1, keepdims=True))
        log_q = jax.nn.log_softmax(jnp.where(keep, scores, -jnp.inf), axis=-1)
        on = keep & (p > 0)
        return jnp.sum(jnp.where(on, p * (jnp.log(jnp.where(on, p, 1.0)) - jnp.where(on, log_q, 0.0)), 0.0), axis=-1)


def _walk(qi, ki, w, q, k, lse, mask, chunk: int, grads: bool):
    """The ONE walk of the pairs the loss takes, ``chunk`` query rows at a time: ``(loss, (dqI, dkI, dw))``, the
    gradients (``None`` without ``grads``) those of the loss itself, made in each chunk from the scores, the
    probabilities and the mask while they are alive there: the KL's rows' gradient to the scores (the mean's
    ``1 / (B L)`` in it; zero off the selected set) handed at once to the score's gradient (:func:`_score_grads`).
    ``dqI`` and ``dw`` leave chunk by chunk, ``dkI`` is the chunks' sum in float32; no [rows, L] array leaves a chunk."""
    b, length, heads, _ = q.shape
    rows = chunk_rows(length, chunk)
    lse = lse.reshape(b, heads, length)

    def of_a_chunk(dk, part):
        qi_c, w_c, q_c, lse_c, mask_c, offset = part
        scores = index_scores(qi_c, ki, w_c, offset)
        probs = head_summed_probs(q_c, k, lse_c, mask_c)
        if not grads:
            return dk, (jnp.sum(kl_rows(scores, probs, mask_c)), None, None)
        kl, to_scores = jax.vjp(lambda scores: kl_rows(scores, probs, mask_c), scores)
        dq_c, dk_c, dw_c = _score_grads(qi_c, ki, w_c, offset, *to_scores(jnp.full(kl.shape, 1.0 / (b * length), kl.dtype)))
        return dk + dk_c, (jnp.sum(kl), dq_c, dw_c)

    parts = (
        _chunks(qi, rows), _chunks(w, rows), _chunks(q, rows), _chunks(lse, rows, axis=2), _chunks(mask, rows),
        jnp.arange(0, length, rows),
    )
    dk, (kl, dq, dw) = lax.scan(of_a_chunk, jnp.zeros(ki.shape, jnp.float32) if grads else None, parts)
    return jnp.sum(kl) / (b * length), (_unchunk(dq), dk.astype(ki.dtype), _unchunk(dw)) if grads else None


def grads_work(qi, q) -> float:
    """What keeping the loss's gradients spares a rematerialised block (``ops/remat.py``'s FLOPs): the walk, which is
    the score kernel, the probabilities kernel and the score's two gradient kernels over every chunk, by the costs
    they declare."""
    b, length, heads, _ = qi.shape
    kernels = [_score_cost(b, length, length, heads), _probs_cost(b, length, length, q.shape[2], q.shape[3])]
    return sum(map(remat.kernel_work, kernels + 2 * [_score_cost(b, length, length, heads, products=2)]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _indexer_loss(qi, ki, w, q, k, lse, mask, chunk, keep=False):
    # a block's survey (``remat.trace_sites``) traces this primal and runs no forward rule: the site is told here, by
    # the three operands whose shapes and types the gradients have
    remat.site("dsa_index_grads", grads_work(qi, q), qi, ki, w, keep=False)
    return _walk(qi, ki, w, q, k, lse, mask, chunk, grads=False)[0]


def _indexer_loss_fwd(qi, ki, w, q, k, lse, mask, chunk, keep):
    loss, grads = _walk(qi, ki, w, q, k, lse, mask, chunk, grads=True)
    # A save site (ops/remat.py): a rematerialised block that keeps the three gradients walks the pairs no second time.
    return loss, remat.site("dsa_index_grads", grads_work(qi, q), *grads, keep=keep)


def _indexer_loss_bwd(chunk, keep, grads, g):
    # the loss is a scalar: its cotangent scales what the forward's walk made (exactly, at the coefficient 1.0)
    return (*((g * x).astype(x.dtype) for x in grads), None, None, None, None)


_indexer_loss.defvjp(_indexer_loss_fwd, _indexer_loss_bwd)


def indexer_loss(qi, ki, w, q, k, lse, mask, chunk: int):
    """``mean_t KL(p[t, .] || softmax_{S_t}(I[t, .]))`` over every row of
    the batch.  ``q`` [B, L, H, D], ``k`` [B, L, G, D] and ``lse`` [B * H, 1,
    L] are the attention's (its probabilities are a constant here: their
    gradient is stopped); the gradient reaches ``qi``, ``ki``, ``w``.  A
    ``custom_vjp``: differentiated, the forward's one walk makes the three
    gradients too (:func:`_walk`) and they are the residuals, the save site
    ``dsa_index_grads`` of a rematerialised block; the backward scales them."""
    q, k, lse = (lax.stop_gradient(x) for x in (q, k, lse))
    # ``remat.kept``: asked here, while the primal is traced (as the flash kernels ask)
    return _indexer_loss(qi, ki, w, q, k, lse, mask, chunk, remat.kept("dsa_index_grads"))
