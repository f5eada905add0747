"""Token routing and a dropless mixture-of-experts feed-forward.

``route`` picks ``k`` of ``E`` experts a token (softmax over all ``E``, then
top-k, float32 throughout); ``expert_ffn`` runs every (token, expert) slot
through its expert's gated feed-forward and sums a token's ``k`` results
with the router's weights.  DROPLESS: there is no capacity factor, every
slot is computed, whatever the routing (``sum(group sizes) == T * k``; the
trainer's ``moe_slots_computed`` counter says so at run time).

How the rows move.  The ``T * k`` slots are sorted by expert
(``lax.sort_key_val``, as ``ops/table_grad.sort_updates`` sorts update rows
by table row), the token rows are gathered into that order, three GROUPED
matmuls (``_grouped_matmul``: ``E`` groups of uneven, data-dependent size,
one compiled program whatever the sizes) run the experts, and the results
are gathered back by the inverse order and summed over ``k``.  Both
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

Expert PARALLELISM is not here: on a mesh every device holds all experts
and routes its own tokens (the AllReduce strategy); see ROADMAP R5.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox


class Routing(NamedTuple):
    weights: jax.Array  # [T, k] float32: softmax probabilities of the chosen
    choices: jax.Array  # [T, k] int32: expert of each slot, best first
    logits: jax.Array  # [T, E] float32: r = u Wg
    probs: jax.Array  # [T, E] float32: softmax(r)


def route(u: jax.Array, wg: jax.Array, k: int) -> Routing:
    """Softmax over all experts, then top-``k``; float32 whatever ``u`` is
    (a bfloat16 router flips choices between near-equal experts).  The
    ``k`` weights are the chosen probabilities as they are, NOT divided by
    their sum (``norm_topk_prob`` false): they sum to less than 1."""
    with jax.named_scope("moe_router"):
        r = jnp.dot(
            u.astype(jnp.float32), wg.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        p = jax.nn.softmax(r, axis=-1)
        _, choices = lax.top_k(p, k)
        # The chosen probabilities by a masked sum, not by top_k's values or
        # a gather: either's transpose is a scatter of T * k scalars into
        # [T, E], one element at a time on the TPU.
        chosen = choices[..., None] == lax.iota(jnp.int32, p.shape[-1])
        weights = jnp.sum(jnp.where(chosen, p[:, None, :], 0.0), axis=-1)
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


def _grouped_matmul(x: jax.Array, w: jax.Array, sizes: jax.Array) -> jax.Array:
    """``x[rows of group e] @ w[e]`` for every group: [N, A] x [E, A, B]
    -> [N, B], ``sizes`` [E] the (data-dependent) rows of each group:
    megablox's grouped matmul (a Pallas kernel that walks row tiles group
    by group; its custom VJP runs the same kernel for dx and its
    transposed twin for dw), one compiled program whatever the sizes."""
    tm, tk, tn = GMM_TILING
    tiling = (math.gcd(x.shape[0], tm), min(tk, w.shape[1]), min(tn, w.shape[2]))
    return megablox.gmm(
        x, w, sizes, preferred_element_type=x.dtype, tiling=tiling,
        interpret=_use_interpret(),
    )


def expert_ffn(
    u: jax.Array,
    choices: jax.Array,
    weights: jax.Array,
    w_gate: jax.Array,
    w_up: jax.Array,
    w_down: jax.Array,
) -> Tuple[jax.Array, jax.Array]:
    """``sum_i weights[t, i] * (silu(u Wgate[e]) * (u Wup[e])) Wdown[e]``
    with ``e = choices[t, i]``, for every token ``t``: ``u`` [T, D],
    ``choices`` / ``weights`` [T, k], expert weights [E, D, F] / [E, F, D]
    already in the compute dtype.  Returns (the result [T, D] in ``u``'s
    dtype, the group sizes [E] the matmuls ran)."""
    n_tokens, k = choices.shape
    order, inverse, sizes = sort_slots(choices, w_gate.shape[0])
    x = _rows_out(u, order, inverse, k)
    with jax.named_scope("moe_experts"):
        h = jax.nn.silu(_grouped_matmul(x, w_gate, sizes)) * _grouped_matmul(x, w_up, sizes)
        y = _grouped_matmul(h, w_down, sizes)
    y = _rows_back(y, order, inverse)
    with jax.named_scope("moe_combine"):
        y = y.reshape(n_tokens, k, -1).astype(jnp.float32)
        out = jnp.sum(y * weights[..., None], axis=1).astype(u.dtype)
    return out, sizes
