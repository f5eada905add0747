"""Token routing and a dropless mixture-of-experts feed-forward.

``route`` picks ``k`` of ``E`` experts a token (a softmax or a sigmoid over
all ``E``, then top-k, float32 throughout); ``expert_ffn`` runs every
(token, expert) slot whose expert it HOLDS through that expert's gated
feed-forward and sums a token's ``k`` results with the router's weights.
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
by table row), the token rows are gathered into that order, three GROUPED
matmuls (``_grouped_matmul``: ``E`` groups of uneven, data-dependent size,
one compiled program whatever the sizes) run the held experts' run of the
sorted rows and no row more (the rows of absent experts come back zero), and
the results are gathered back by the inverse order and summed over ``k``.
The row buffers hold all ``T * k`` slots — the worst case the shapes allow:
every slot may fall on a held expert — so a share of the experts still
gathers every row (PERF.md section 7 has what that costs).  Both
directions are PERMUTATIONS, forward and backward: the transpose of
"gather by ``order``" is "gather by its inverse" (``_rows_out`` /
``_rows_back`` spell that as ``custom_vjp``; autodiff alone would emit a
scatter-add of ``[T * k, D]`` rows, which XLA:TPU executes a row at a time
— 74 ns a row measured in PR 27).  No scatter-add of rows anywhere.

Device scopes (``jax.named_scope``; ``benchmark/readers/op_ms_step.py``
reads them): ``moe_router`` here in ``route`` and in ``router_stats``,
``moe_dispatch`` (sort, sizes, both permutations, forward and backward),
``moe_experts`` (the grouped matmuls and the gate), ``moe_combine`` (the
weights and the sum over ``k``).

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
#: VMEM, the kernel's default (128, 128, 128) runs at 9.
GMM_TILING = (512, 1024, 1024)


def _use_interpret() -> bool:
    """Off the TPU the same kernel runs under the Pallas interpreter (CPU
    tests, rehearsals), as ops/table_grad.py's sweep does."""
    return jax.default_backend() != "tpu"


def _grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array, lo: int) -> jax.Array:
    """``x[rows of group e] @ w[e - lo]`` for every HELD group ``e`` in
    ``[lo, lo + n)``: [N, A] x [n, A, B] -> [N, B], ``sizes`` [E] the
    (data-dependent) rows of each of the router's groups, held or not:
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


def expert_ffn(
    u: jax.Array,
    choices: jax.Array,
    weights: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
    n_experts: Optional[int] = None,
    lo: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """``sum_i weights[t, i] * (silu(u Wgate[e]) * (u Wup[e])) Wdown[e]``
    with ``e = choices[t, i]``, over the slots whose expert is HELD, for
    every token ``t``: ``u`` [T, D], ``choices`` / ``weights`` [T, k] over
    the router's ``n_experts`` (None: as many as are held), the held
    experts ``[lo, lo + n)``'s weights [n, D, F] / [n, F, D] already in the
    compute dtype.  Returns (the result [T, D] in ``u``'s dtype, the slots
    [E] the router sent each of ITS experts: entries ``lo .. lo + n - 1``
    are the group sizes the matmuls ran)."""
    n_tokens, k = choices.shape
    n_held = w_gate.shape[0]
    n_experts = n_held if n_experts is None else n_experts
    if not 0 <= lo <= n_experts - n_held:
        raise ValueError(f"held experts [{lo}, {lo + n_held}) are not among the router's {n_experts}")
    order, inverse, sizes = sort_slots(choices, n_experts)
    x = _rows_out(u, order, inverse, k)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(_grouped_matmul(x, w_gate, sizes, lo)) * _grouped_matmul(x, w_up, sizes, lo)
        y = _grouped_matmul(h, w_down, sizes, lo)
    y = _rows_back(y, order, inverse)
    with jax.named_scope("moe_combine"):
        y = y.reshape(n_tokens, k, -1).astype(jnp.float32)
        out = jnp.sum(y * weights[..., None], axis=1).astype(u.dtype)
    return out, sizes
