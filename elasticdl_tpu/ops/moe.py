"""Token routing and a dropless mixture-of-experts feed-forward.

``route`` picks ``k`` of ``E`` experts a token (a softmax or a sigmoid over
all ``E``, then top-k, float32 throughout); ``expert_ffn`` runs every
(token, expert) slot whose expert it HOLDS through that expert's
feed-forward (gated under silu, gated under relu, or two matrices under relu
squared: ``ACTIVATIONS``) and sums a token's ``k`` results with the router's
weights.
DROPLESS: there is no capacity factor, every held slot is computed,
whatever the routing (the sum of the held groups' sizes equals the slots
the router sent to them; the trainer's ``moe_slots_computed`` and
``moe_slots_held`` counters say so at run time).

Which experts are held.  The layer is told a contiguous range ``[lo, lo +
n)`` of the router's ``E`` experts — ``n`` is the leading dimension of the
weights it is given — routes over all ``E``, and computes its own experts'
part of the result: a slot on an absent expert adds nothing.  That is what
expert parallelism asks of one chip (ROADMAP R7 adds the exchange); no code
stands in for the absent chips.  All of them held (``n = E``, ``lo = 0``) is
the same code.

How the rows move.  The ``T * k`` slots are sorted by expert
(``lax.sort_key_val``, as ``ops/table_grad.sort_updates`` sorts update rows
by table row); after the sort the held experts' slots are ONE contiguous run
of the sorted order, and only that run moves.  The row buffers hold ``C``
rows, a number read off the shapes (``held_rows_bound``: ``SLACK`` times the
held experts' even share ``T * k * n / E``, at most ``T * k``):

* The always-run tier takes the first ``C`` sorted positions of the run
  (``_held_window``): their token rows are gathered, three GROUPED matmuls
  (two for an expert of two matrices; ``_grouped_matmul``: uneven,
  data-dependent groups, one compiled program whatever the sizes) run the
  held experts' rows of the window — the rows
  past the run are a last group of no expert, which comes back zero — and
  each result row is weighted and summed into its token, at most ``k`` rows
  a token, in float32 (``_token_sum``: the rows sorted by token and one
  sweep of ``ops/table_grad.merge_sweep`` over the tokens' tiles).  The
  sweep reads the rows in the dtype the experts WROTE them (PR 68): the
  ``C`` bfloat16 rows are permuted into token order as they are, the
  router's weight rides into the kernel beside its row's token and is
  multiplied in there (exact products, a float32 sum), and no float32
  copy of the ``[C, D]`` rows is made, permuted or swept on the way to the
  tokens or back from them (the one float32 ``[C, D]`` array left is the
  weighted sum's gathered cotangent, float32 because the sum is).  The
  transpose of "gather a window's token rows" is that same sum without
  weights — the grouped matmuls' bfloat16 dx summed in float32 and rounded
  once, leaving the kernel as bfloat16 ``[T, D]`` — and the transpose of
  the weighted sum is the gather times the weight (``_rows_of_tokens`` /
  ``_sum_to_tokens`` spell both as ``custom_vjp``); the weights' gradient
  returns to ``[T, k]`` by a gather of scalars through the inverse order.
  No array of ``T * k`` rows exists in this path: what walks all ``T * k``
  slots is two int32 sorts, a ``searchsorted`` and those scalar gathers.
  (A float32 model's rows take the float32 sweep, weighted in XLA first.)
* Every one of a token's ``k`` slots may fall on a held expert, so the run
  can be longer than ``C``.  The rows past ``C`` are a second tier
  (``_overflow``): the same function on the same window of ``C`` rows,
  moved along the run by a loop that makes as many trips as the run has
  further windows — none while it fits the first tier — so the result is
  exact whatever the routing; it keeps no residuals (its backward runs a
  window's forward again).  ``Given`` counts the rows each tier's grouped
  matmuls were given: together the held slots.
* Where the buffers hold every slot (``C == T * k``: every expert held, or
  nearly) one window is the whole sorted order and the way back to the
  tokens is the inverse PERMUTATION: "gather by ``order``" out, "gather by
  its inverse" back and a sum over ``k`` (``_rows_out`` / ``_rows_back``,
  each the other's transpose).

Autodiff alone would emit a scatter-add of rows for every one of these
gathers, which XLA:TPU executes a row at a time (74 ns a row measured in PR
27; PR 34's step 0 has it 15 % behind the sweep at 18,432 rows of 2048).  No
scatter-add of rows anywhere.

Device scopes (``jax.named_scope``; ``benchmark/readers/op_ms_step.py``
reads them): ``moe_router`` here in ``route`` and in ``router_stats``,
``moe_dispatch`` (sort, sizes, the windows, the row gathers out and their
transposes), ``moe_experts`` (the grouped matmuls and the gate: silu, relu
or, for two matrices, relu squared — ``ACTIVATIONS``, a static argument of
``expert_ffn`` that every tier and the second tier's rerun carry),
``moe_combine`` (the weights, the sum into the tokens and its transpose).

The EXCHANGE of expert parallelism is not here: on a mesh every device
holds the same experts and routes its own tokens (the AllReduce strategy);
ROADMAP R7 builds it on ``expert_ffn``'s held range.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

from elasticdl_tpu.ops import table_grad


class Routing(NamedTuple):
    weights: jax.Array  # [T, k] float32: the chosen experts' weights
    choices: jax.Array  # [T, k] int32: expert of each slot, best first
    logits: jax.Array  # [T, E] float32: r = u Wg
    probs: jax.Array  # [T, E] float32: softmax(r), or sigmoid(r)


SCORING_FUNCS = ("softmax", "sigmoid")


def route(
    u: jax.Array,
    wg: jax.Array,
    k: int,
    *,
    scoring_func: str = "softmax",
    bias: Optional[jax.Array] = None,
    norm_topk_prob: bool = False,
    routed_scaling_factor: float = 1.0,
) -> Routing:
    """Scores over all experts (``scoring_func``: a softmax, or a sigmoid of
    each logit), then top-``k``; float32 whatever ``u`` is (a bfloat16
    router flips choices between near-equal experts).  ``bias`` [E] is
    added to the scores for the CHOICE only: it chooses, it never weighs
    (DeepSeek-V3's correction bias).  The ``k`` weights are the chosen
    scores as they are (``norm_topk_prob`` false: a softmax's sum to less
    than 1) or divided by their sum + 1e-20, times
    ``routed_scaling_factor``.  The defaults are OLMoE's published keys."""
    if scoring_func not in SCORING_FUNCS:
        raise ValueError(f"scoring_func {scoring_func!r} is not one of {SCORING_FUNCS}")
    with jax.named_scope("moe_router"):
        r = jnp.dot(
            u.astype(jnp.float32), wg.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        p = jax.nn.softmax(r, axis=-1) if scoring_func == "softmax" else jax.nn.sigmoid(r)
        _, choices = lax.top_k(p if bias is None else p + bias.astype(jnp.float32), k)
        # The chosen scores by a masked sum, not by top_k's values or a
        # gather: either's transpose is a scatter of T * k scalars into
        # [T, E], one element at a time on the TPU.
        chosen = choices[..., None] == lax.iota(jnp.int32, p.shape[-1])
        weights = jnp.sum(jnp.where(chosen, p[:, None, :], 0.0), axis=-1)
        if norm_topk_prob:
            weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
        if routed_scaling_factor != 1.0:
            weights = weights * routed_scaling_factor
        return Routing(weights, choices.astype(jnp.int32), r, p)


def router_stats(routing: Routing) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """What the two router losses need of one layer's routing, as SUMS over
    its tokens (the caller divides by the token count, over layers and
    devices): ``f`` [k, E] tokens whose i-th choice is e, ``p`` [E] summed
    probabilities, ``z`` the summed squared log-partition of the logits."""
    with jax.named_scope("moe_router"):
        n_experts = routing.probs.shape[-1]
        chosen = routing.choices[..., None] == lax.iota(jnp.int32, n_experts)
        f = jnp.sum(chosen.astype(jnp.float32), axis=0)
        p = jnp.sum(routing.probs, axis=0)
        z = jnp.sum(jnp.square(jax.nn.logsumexp(routing.logits, axis=-1)))
        return f, p, z


def _take(x: jax.Array, idx: jax.Array) -> jax.Array:
    return x.at[idx].get(mode="promise_in_bounds")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_out(u, order, inverse, k):
    """Token rows [T, D] -> slot rows in expert order [T * k, D]: slot
    ``order[j]`` belongs to token ``order[j] // k``."""
    del inverse
    with jax.named_scope("moe_dispatch"):
        return _take(u, order // k)


def _rows_out_fwd(u, order, inverse, k):
    return _rows_out(u, order, inverse, k), (order, inverse)


def _rows_out_bwd(k, res, g):
    order, inverse = res
    with jax.named_scope("moe_dispatch"):
        # The transpose of a gather by ``order`` is a gather by its inverse
        # (slot order again), then a token's k slots summed.
        slots = _take(g, inverse).reshape(-1, k, g.shape[-1])
        return jnp.sum(slots.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_rows_out.defvjp(_rows_out_fwd, _rows_out_bwd)


@jax.custom_vjp
def _rows_back(y, order, inverse):
    """Slot rows in expert order [T * k, D] -> slot order (token-major)."""
    del order
    with jax.named_scope("moe_dispatch"):
        return _take(y, inverse)


def _rows_back_fwd(y, order, inverse):
    return _rows_back(y, order, inverse), (order, inverse)


def _rows_back_bwd(res, g):
    order, inverse = res
    with jax.named_scope("moe_dispatch"):
        return _take(g, order), None, None


_rows_back.defvjp(_rows_back_fwd, _rows_back_bwd)


def sort_slots(choices: jax.Array, n_experts: int):
    """(order, inverse, sizes) of the ``T * k`` slots sorted by expert:
    ``order[j]`` is the slot at sorted position ``j`` (the sort is stable:
    an expert's slots stay in slot order), ``inverse``
    its inverse permutation, ``sizes`` [E] int32 the slots of each expert
    — the group sizes of the grouped matmuls, read off the SORTED keys so
    that they count exactly the rows the matmuls are given."""
    with jax.named_scope("moe_dispatch"):
        flat = choices.reshape(-1)
        slots = lax.iota(jnp.int32, flat.shape[0])
        experts, order = lax.sort_key_val(flat, slots)
        # The inverse permutation by a second sort (a scatter of 65,536
        # ints would be XLA's row-at-a-time scatter again).
        _, inverse = lax.sort_key_val(order, slots)
        starts = jnp.searchsorted(
            experts, lax.iota(jnp.int32, n_experts + 1), side="left"
        ).astype(jnp.int32)
        return order, inverse, starts[1:] - starts[:-1]


#: Tile sizes (rows, contraction, columns) of the grouped matmul, from step
#: 0 on a v5e (PERF.md, PR 30): at 65,536 x 2048 x 1024, 64 uneven groups,
#: forward + backward, 96 TFLOP/s against ``lax.ragged_dot``'s 70 and a
#: plain matmul's 151; (512, 2048, 1024) and (1024, 1024, 1024) do not fit
#: VMEM, the kernel's default (128, 128, 128) runs at 9.  At 2560-wide rows
#: (``smallthinker_job``: 2.5 contraction tiles of the up and gate products,
#: 2.5 column tiles of the down product; the kernel masks the ragged last
#: tile) it stays: step 0 of PR 69 on a v5e, ONE relu-gated layer forward +
#: backward at [16384, 2560], k = 6, 8 of 64 held, width 768 (12,459 rows
#: computed): (512, 1024, 1024) 13.89 ms, (512, 1280, 768) 13.79, (512, 1280,
#: 1280) 14.08, (512, 640, 1280) 14.75, (512, 512, 512) 14.81; (512, 2560,
#: 768) does not fit VMEM.  A contraction tile that divides 2560 buys 0.1 ms
#: a layer (0.7 %), inside a second process's noise: nothing is read off the
#: shapes, and the 2048-wide cells compile the program they compiled.
GMM_TILING = (512, 1024, 1024)


def _use_interpret() -> bool:
    """Off the TPU the same kernel runs under the Pallas interpreter (CPU
    tests, rehearsals), as ops/table_grad.py's sweep does."""
    return jax.default_backend() != "tpu"


def _grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, lo: int) -> jax.Array:
    """``x[rows of group e] @ w[e - lo]`` for every HELD group ``e`` in
    ``[lo, lo + n)``: [N, A] x [n, A, B] -> [N, B], ``sizes`` the
    (data-dependent) rows of each group of ``x``, held or not (all the
    router's ``E``, or a window's: the held ``n`` and its filler):
    megablox's grouped matmul (a Pallas kernel that walks the held groups'
    row tiles group by group and zeroes the rows of the others; its custom
    VJP runs the same kernel for dx and its transposed twin for dw), one
    compiled program whatever the sizes."""
    tm, tk, tn = GMM_TILING
    tiling = (math.gcd(x.shape[0], tm), min(tk, w.shape[1]), min(tn, w.shape[2]))
    return megablox.gmm(
        x, w, sizes, preferred_element_type=x.dtype, tiling=tiling,
        group_offset=jnp.int32(lo), interpret=_use_interpret(),
    )


#: What a gated expert's gate passes: ``act(x Wgate) * (x Wup)`` (``silu``: every family but one; ``relu``: SmallThinker's
#: sparse ReGLU).  An expert of TWO matrices (``w_gate`` None) is ``relu(x Wup)^2`` whatever this says.
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}


def _experts(x, w_gate, w_up, w_down, sizes, lo: int, activation: str = "silu"):
    """The held experts' feed-forward over rows grouped by expert: gated
    (``act(x Wgate) * (x Wup)``, ``act`` = ``ACTIVATIONS[activation]``), or,
    for experts of TWO matrices (``w_gate`` None), ``relu(x Wup)^2``; then
    ``Wdown``."""
    with jax.named_scope("moe_experts"):
        if w_gate is None:
            h = jnp.square(jax.nn.relu(_grouped_matmul(x, w_up, sizes, lo)))
        else:
            h = ACTIVATIONS[activation](_grouped_matmul(x, w_gate, sizes, lo)) * _grouped_matmul(x, w_up, sizes, lo)
        return _grouped_matmul(h, w_down, sizes, lo)


#: Room the always-run row buffers leave over the held experts' even share
#: ``T * k * n / E`` of the slots, from step 0 on a v5e (PERF.md, PR 34: one
#: layer forward + backward at [16384, 2048], k = 6, 16 of 128 experts held,
#: 12.6 % of the slots theirs; the parent's ``T * k``-row buffers 34.3 ms):
#: 1.25 -> 11.2 ms, 1.5 -> 12.0, 2 -> 13.4 with the second tier present and
#: making no trip: 0.8 ms a layer for a quarter of slack, in every step.
#: Each further window the held run takes costs some 13 ms (the whole run
#: held, five of them: 76.9 against 49.9).  So a quarter more slack pays
#: from one step in sixteen that would otherwise overflow: 1.5 covers a
#: chip whose experts draw half again their even share, which a router
#: still finding its balance does and a balanced one (the benchmark's
#: reads 12.05-12.24 % of 12.5) never does; 2 would buy the range
#: 18.75-25 % for 1.3 ms a layer of every step.
SLACK = 1.5

#: Token rows a grid step of the token sum builds (``ops/table_grad``'s
#: ``tile``): at 2048-wide float32 rows 128 fits the 16 MiB of VMEM a
#: kernel may scope on a v5e, 256 asks for 18.5 (compiled for a described
#: v5e, PR 34).  Bfloat16 chunk buffers leave room for 256, and it is SLOWER
#: (PERF.md, PR 68, step 0 at [49152, 2048] -> [32768, 2048]: the weighted
#: sum's kernel 0.87 ms at 128 and 1.11 at 256, the one-piece sum 0.65 and
#: 0.72): the one-hot is [tile, 128] and mostly zeros, so a tile twice as
#: tall does twice the MXU's work a chunk.  At 2560-wide rows (PR 69) 128
#: still compiles inside the 16 MiB (the whole step for a described v5e,
#: ``tests/benchmark/test_smallthinker_cell.py``): the chunk buffers are a
#: quarter wider and bfloat16.
TOKEN_TILE = 128


def held_rows_bound(n_slots: int, n_held: int, n_experts: int) -> int:
    """``C``: the rows of the always-run buffers, read off the shapes —
    ``SLACK`` times the held experts' even share of the ``n_slots = T * k``
    slots, rounded up to the grouped matmul's row tile (the one
    ``_grouped_matmul`` would take for ``n_slots`` rows), at most all of
    them."""
    tile = math.gcd(n_slots, GMM_TILING[0])
    want = math.ceil(SLACK * n_slots * n_held / n_experts)
    return min(n_slots, -(-want // tile) * tile)


# graftlint: allow[jit-shim] an inner jit, a trace cache inside the step's one compile (as megablox's gmm is), never a compile of its own
@functools.partial(jax.jit, static_argnames=("n_tokens",))
def _token_sum(rows: jax.Array, tok: jax.Array, n_tokens: int, weights: Optional[jax.Array] = None) -> jax.Array:
    """``zeros([n_tokens, D]).at[tok].add(rows)`` (``tok == n_tokens`` is
    dropped) without the scatter-add: the rows sorted by token and one
    sweep over the tokens' tiles (``ops/table_grad``), which reads the rows
    in the dtype they have, sums them in float32 and rounds the sum once to
    that dtype; with ``weights`` (float32, one a row) the sum is of
    ``weights[j] * rows[j]`` and stays float32.  Jitted: a model's layers
    and their backward passes share ONE trace and one lowered kernel a
    shape (a Pallas kernel is otherwise traced and lowered anew at every
    call: ``setup_s``)."""
    return table_grad.sweep_table_grad(tok, rows, n_tokens, weights, tile=min(TOKEN_TILE, n_tokens))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_of_tokens(u, tok, n_tokens):
    """Token rows [T, D] -> the rows of a window of the sorted slots [W, D]
    (``tok`` [W]: the token of each, ``n_tokens`` past the held run)."""
    with jax.named_scope("moe_dispatch"):
        return _take(u, jnp.minimum(tok, n_tokens - 1))


def _rows_of_tokens_fwd(u, tok, n_tokens):
    return _rows_of_tokens(u, tok, n_tokens), tok


def _rows_of_tokens_bwd(n_tokens, tok, g):
    with jax.named_scope("moe_dispatch"):
        return _token_sum(g, tok, n_tokens), None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _sum_to_tokens(y, w_rows, tok, n_tokens):
    """A window's rows ``y`` [W, D], as the experts wrote them, each times
    its weight ``w_rows`` [W] (float32) and summed into its token: [T, D]
    float32, at most ``k`` rows a token.  Unweighted it is the transpose of
    :func:`_rows_of_tokens`, and its own transpose is that gather."""
    with jax.named_scope("moe_combine"):
        return _token_sum(y, tok, n_tokens, w_rows)


def _sum_to_tokens_fwd(y, w_rows, tok, n_tokens):
    return _sum_to_tokens(y, w_rows, tok, n_tokens), (y, w_rows, tok)


def _sum_to_tokens_bwd(n_tokens, res, g):
    y, w_rows, tok = res
    with jax.named_scope("moe_combine"):
        rows = _take(g, jnp.minimum(tok, n_tokens - 1))
        rows = jnp.where((tok < n_tokens)[:, None], rows, 0)
        dy = (rows * w_rows[:, None]).astype(y.dtype)
        dw = jnp.sum(rows * y.astype(jnp.float32), axis=-1)
        return dy, dw.astype(w_rows.dtype), None


_sum_to_tokens.defvjp(_sum_to_tokens_fwd, _sum_to_tokens_bwd)


@jax.custom_vjp
def _weights_of_rows(weights, slots, inverse, first, count):
    """The router's weights [T, k] -> the weights of a window's rows [W]
    (``slots`` [W] their slots; the window starts at sorted position
    ``first`` and holds ``count`` rows of the held run, the rest read 0)."""
    valid = lax.iota(jnp.int32, slots.shape[0]) < count
    return jnp.where(valid, _take(weights.reshape(-1), slots), 0.0)


def _weights_of_rows_fwd(weights, slots, inverse, first, count):
    out = _weights_of_rows(weights, slots, inverse, first, count)
    # a [0, k] array carries the weights' shape and dtype, and no bytes
    return out, (inverse, first, count, weights[:0])


def _weights_of_rows_bwd(res, g):
    inverse, first, count, like = res
    # Back to [T, k] by a GATHER of scalars through the inverse order (a
    # scatter of scalars runs one element at a time on the TPU).
    at = inverse - first
    mine = (at >= 0) & (at < count)
    dw = jnp.where(mine, _take(g, jnp.clip(at, 0, g.shape[0] - 1)), 0.0)
    return dw.reshape(-1, like.shape[1]).astype(like.dtype), None, None, None, None


_weights_of_rows.defvjp(_weights_of_rows_fwd, _weights_of_rows_bwd)


def _held_window(u, weights, w_gate, w_up, w_down, order, inverse, start, ends, first, width: int, activation: str = "silu"):
    """Rows ``[first, first + width)`` of the held experts' run of the
    sorted slots (the run starts at sorted position ``start``; ``ends`` [n]
    are where each held expert's slots end in it), through their experts
    and summed into their tokens with the router's weights: ([T, D]
    float32, the rows the grouped matmuls were given)."""
    n_tokens, k = weights.shape
    with jax.named_scope("moe_dispatch"):
        clipped = jnp.clip(jnp.concatenate([jnp.zeros(1, jnp.int32), ends]), first, first + width)
        given = clipped[1:] - clipped[:-1]  # [n]: each held expert's rows in the window
        count = clipped[-1] - first
        # a last group of no expert takes the window's filler: the grouped
        # matmul zeroes its rows
        sizes = jnp.concatenate([given, (width - count)[None]])
        slots = lax.dynamic_slice(jnp.pad(order, (0, width)), (start + first,), (width,))
        tok = jnp.where(lax.iota(jnp.int32, width) < count, slots // k, n_tokens)
    y = _experts(_rows_of_tokens(u, tok, n_tokens), w_gate, w_up, w_down, sizes, 0, activation)
    with jax.named_scope("moe_combine"):
        w_rows = _weights_of_rows(weights, slots, inverse, start + first, count)
    return _sum_to_tokens(y, w_rows, tok, n_tokens), jnp.sum(given)


def _windows(ends, bound: int):
    """Windows of ``bound`` rows the held run takes: 1 (or 0) while it fits
    the first tier's buffers."""
    return (ends[-1] + bound - 1) // bound


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _overflow(acc, u, weights, w_gate, w_up, w_down, order, inverse, start, ends, bound, activation="silu"):
    """``acc`` plus the held run's rows PAST ``bound``: the second tier, the
    same window of ``bound`` rows moved along the run by a loop that makes
    no trip while the run fits the first tier's buffers.  Returns (the sum,
    the rows its grouped matmuls were given: 0 without a trip).  A
    ``custom_vjp`` that keeps its inputs and runs a window again in its
    backward: the step's live residuals are the first tier's alone.  A
    window of the first tier's shapes runs the first tier's kernels: ONE
    window of the other ``T * k - C`` rows needs eight more, 4.9 s of
    tracing, lowering and loading in a 61 s warm ``setup_s`` (PERF.md, PR
    34)."""
    def body(i, carry):
        acc, given = carry
        out, rows = _held_window(u, weights, w_gate, w_up, w_down, order, inverse, start, ends, i * bound, bound, activation)
        return acc + out, given + rows

    return lax.fori_loop(1, _windows(ends, bound), body, (acc, jnp.int32(0)))


def _overflow_fwd(acc, u, weights, w_gate, w_up, w_down, order, inverse, start, ends, bound, activation):
    args = (u, weights, w_gate, w_up, w_down, order, inverse, start, ends)
    return _overflow(acc, *args, bound, activation), args


def _overflow_bwd(bound, activation, res, g):
    *diff, order, inverse, start, ends = res
    g_out, _ = g

    def body(i, grads):
        def window(*diff):
            return _held_window(*diff, order, inverse, start, ends, i * bound, bound, activation)[0]

        # (trees: an expert of two matrices has None for its gate)
        return jax.tree.map(jnp.add, grads, jax.vjp(window, *diff)[1](g_out))

    grads = lax.fori_loop(1, _windows(ends, bound), body, jax.tree.map(jnp.zeros_like, tuple(diff)))
    return (g_out, *grads, None, None, None, None)


_overflow.defvjp(_overflow_fwd, _overflow_bwd)


class Given(NamedTuple):
    """The rows the grouped matmuls were given, by tier (int32 scalars):
    their sum is the slots computed, ``second`` the held slots that fell
    past the first tier's buffers."""
    first: jax.Array
    second: jax.Array


# graftlint: allow[jit-shim] an inner jit, a trace cache inside the step's one compile (as megablox's gmm is), never a compile of its own
@functools.partial(jax.jit, static_argnames=("bound", "activation"))
def _held_tiers(u, weights, w_gate, w_up, w_down, order, inverse, start, ends, bound: int, activation: str = "silu"):
    """Both tiers of the windowed path: ([T, D] float32, :class:`Given`).
    Jitted so that a model's expert layers, alike in shape, are traced,
    differentiated and lowered ONCE (a window, its transpose and the second
    tier's loops are some 1 s of tracing a layer otherwise: ``setup_s``)."""
    args = (u, weights, w_gate, w_up, w_down, order, inverse, start, ends)
    acc, first = _held_window(*args, 0, bound, activation)
    acc, second = _overflow(acc, *args, bound, activation)
    return acc, Given(first, second)


def expert_ffn(
    u: jax.Array,
    choices: jax.Array,
    weights: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    n_experts: Optional[int] = None,
    lo: int = 0,
    activation: str = "silu",
) -> Tuple[jax.Array, jax.Array, Given]:
    """``sum_i weights[t, i] * (act(u Wgate[e]) * (u Wup[e])) Wdown[e]``
    with ``e = choices[t, i]``, over the slots whose expert is HELD, for
    every token ``t``: ``u`` [T, D], ``choices`` / ``weights`` [T, k] over
    the router's ``n_experts`` (None: as many as are held), the held
    experts ``[lo, lo + n)``'s weights [n, D, F] / [n, F, D] already in the
    compute dtype.  ``activation`` (static: a key of ``ACTIVATIONS``) is the
    gate's ``act``, silu or relu.  ``w_gate`` None: experts of two matrices,
    ``relu(u Wup[e])^2 Wdown[e]``, through the same tiers and buffers.
    Returns (the result [T, D] in ``u``'s dtype, the slots
    [E] the router sent each of ITS experts, the rows the grouped matmuls
    were :class:`Given`: together the held slots, whatever the routing)."""
    n_tokens, k = choices.shape
    n_held = w_up.shape[0]
    n_experts = n_held if n_experts is None else n_experts
    if not 0 <= lo <= n_experts - n_held:
        raise ValueError(f"held experts [{lo}, {lo + n_held}) are not among the router's {n_experts}")
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is not one of {sorted(ACTIVATIONS)}")
    order, inverse, sizes = sort_slots(choices, n_experts)
    bound = held_rows_bound(n_tokens * k, n_held, n_experts)
    if bound == n_tokens * k:
        # The buffers hold every slot: one window is the whole sorted
        # order, and the way back is its inverse permutation.
        y = _experts(_rows_out(u, order, inverse, k), w_gate, w_up, w_down, sizes, lo, activation)
        y = _rows_back(y, order, inverse)
        with jax.named_scope("moe_combine"):
            y = y.reshape(n_tokens, k, -1).astype(jnp.float32)
            out = jnp.sum(y * weights[..., None], axis=1).astype(u.dtype)
        return out, sizes, Given(jnp.sum(sizes[lo:lo + n_held]), jnp.int32(0))
    with jax.named_scope("moe_dispatch"):
        start = jnp.sum(sizes[:lo])
        ends = jnp.cumsum(sizes[lo:lo + n_held])
    acc, given = _held_tiers(u, weights, w_gate, w_up, w_down, order, inverse, start, ends, bound, activation)
    with jax.named_scope("moe_combine"):
        return acc.astype(u.dtype), sizes, given
