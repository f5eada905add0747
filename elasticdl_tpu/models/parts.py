"""What a decoder layer of ``moe_lm`` is made of.

A layer is a short tuple of ``(norm's parameter name, part)``; a PART owns a
parameter layout and the arithmetic over it: an attention
(``models/attentions.py``), a state-space mixer (``models/mamba.py``), a
feed-forward (``models/moe_lm.py``, beside the router's losses).  A part is
a frozen dataclass whose hyper-parameters ``model_spec`` binds, so two
layers of one kind are one value with one ``repr`` (``ops/remat.plan`` tells
blocks apart by it).  The model's init and its block walk the same tuple:
what layer ``i`` IS is stated once.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def rms_norm(x, scale, eps):
    # Statistics and arithmetic in f32, ONE downcast (transformer_lm's form).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * scale).astype(x.dtype)


class Draws:
    """The model's ONE key stream: ``n`` keys taken in the order the draws
    are made (which key a matrix is drawn from decides its every value)."""

    def __init__(self, rng, n: int, std: float):
        self._keys, self._std = iter(jax.random.split(rng, n)), std

    def normal(self, shape, scale: float = 1.0):
        return jax.random.normal(next(self._keys), shape, jnp.float32) * (self._std * scale)

    def uniform(self, shape, lo, hi):
        return jax.random.uniform(next(self._keys), shape, jnp.float32, lo, hi)


class Part:
    #: the step counters the part reports (``ModelSpec.step_counters``) with their gauges' help text:
    #: keys of :meth:`apply`'s stats or of :meth:`shape_counts`
    counters: Mapping[str, str] = {}
    #: it routes tokens to experts: its stats carry the router's sums besides (``moe_lm._routed_stats``)
    routes: bool = False
    #: its router has a correction bias, which the model's own rule moves after each step
    correction_bias: bool = False
    #: the norm's name of an EARLIER entry of its layer whose normed rows it reads too ("": none): the block calls the
    #: part's ``route(u, params)`` there and hands :meth:`apply` the result as ``routing`` (``moe_lm._block``)
    routes_on: str = ""

    def init(self, draw: Draws, d: int) -> Dict[str, Any]:
        """The part's parameters for a residual stream ``d`` wide."""
        raise NotImplementedError

    def apply(self, u, params, positions, axis, cast) -> Tuple[Any, Optional[Dict[str, Any]]]:
        """``(what the part adds to the stream, its stats or None)`` for the
        normed stream ``u`` [B, L, d]; ``params``: the layer's, ``axis``: the
        mesh axis that shards the sequence, ``cast``: a weight to the compute dtype."""
        raise NotImplementedError

    def shape_counts(self, batch: int, length: int) -> Dict[str, int]:
        """Counters that are a function of the shapes alone, taken OUTSIDE
        the block (a rematerialised block then carries no constant out)."""
        return {}
