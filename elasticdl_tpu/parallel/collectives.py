"""graftreduce — the collective layer every gradient/metric reduction
routes through (r15).

Before this module, every reduction in the jitted train/eval steps was a
raw flat ``lax.psum`` over the whole replica set, which bakes two costs
into the step itself:

- **topology blindness**: on a multi-host mesh a flat all-reduce drags
  every gradient byte across the expensive inter-host hop, even though
  the replicas within one host could pre-reduce over their cheap local
  interconnect first ("Highly Available Data Parallel ML training on
  Mesh Networks", PAPERS.md);
- **tail captivity**: the slowest contributor sets the collective's wall
  time — OptiReduce's (PAPERS.md) observation is that a tail-optimal
  AllReduce must be able to stop waiting *inside* the collective, not
  only at the task boundary where r13's gang deadline lives.

This module owns both answers behind one shim surface (the
``jax_compat`` stance: call sites spell the API once, enforced by the
graftlint ``collective-shim`` rule — raw ``lax.psum`` / ``lax.pmean`` /
``lax.psum_scatter`` outside this module and ``common/jax_compat.py``
are findings):

**Hierarchical reduce** (``--collective hierarchical|flat|auto``): the
data-parallel axis of size ``n`` factors into ``(n_host, n_local)``
sub-groups (``parallel/mesh.dp_factorization``: real process grouping,
or ``--collective_local_size`` to pin/emulate it).  A big-leaf psum then
runs in three phases over ``axis_index_groups``:

    1. intra-host reduce-scatter — each local replica ends holding
       1/n_local of its host's partial sum (the cheap hop);
    2. inter-host psum of that residue — only ``size/n_local`` elements
       per replica cross the host boundary (the whole point: inter-host
       bytes cut by the local fan-in);
    3. intra-host all-gather to reassemble the full reduced tensor.

The result equals the flat psum up to float reduction order
(tests/test_collectives.py bounds the divergence).  Leaves
below ``min_elems`` (loss scalars, metric means, masked counts) stay
single flat collectives — three launches for an 8-byte scalar would be
pure overhead.

**Timeout-bounded participation** (the subgroup weight): reductions can
exclude a straggling contributor and renormalize the mean over the
survivors (``sum / |G'|``).  The exclusion mask is a *traced input* to
the jitted step — ``contributor_weight`` reads this replica's 0/1 weight
out of a replicated ``[n_contributors]`` float vector — so changing the
excluded set never recompiles (pinned by test).  The worker's in-step
deadline gate (worker/worker.py ``_collective_gate``) and the trainer's
``set_active_contributors`` drive the mask; the math here only promises:
with an all-ones mask every formula reduces bit-for-bit to the pre-r15
spelling (multiplying by 1.0 is exact, and ``psum(1.0)`` over the axes
is exactly ``n``).

Composition: the r11 sharded-optimizer path keeps its ``psum_scatter``
(routed through this shim, flat on the wire — a grouped reduce-scatter
would permute the shard→replica mapping the optimizer's
``dynamic_slice`` depends on; see ``psum_scatter``'s docstring), while
its pre-scatter cross-axis psums and the replicated path's grad psums
pick up the hierarchical route.  The subgroup weight composes with both:
it scales contributions *before* any reduction, so exclusion and
hierarchy never see each other.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from elasticdl_tpu.common.jax_compat import axis_size

FLAT = "flat"
HIERARCHICAL = "hierarchical"
AUTO = "auto"
MODES = (FLAT, HIERARCHICAL, AUTO)

#: Leaves smaller than this reduce with ONE flat collective even under a
#: hierarchical topology: the 3-phase route saves inter-host bytes in
#: proportion to leaf size, and a scalar's 3 launches cost more than the
#: bytes they save.  Overridable per job (--collective_min_elems).
DEFAULT_MIN_ELEMS = 4096

Axes = Union[str, Sequence[str]]


def _as_axes(axes: Axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


class CollectiveTopology:
    """The static factorization one mesh's reduce axis resolves to.

    ``axis`` is the (outer, data-parallel) mesh axis whose ``n =
    n_host * n_local`` positions group into ``n_host`` hosts of
    ``n_local`` local replicas, contiguously: position ``h * n_local +
    l`` is local replica ``l`` of host ``h`` (exactly how
    ``jax.devices()`` orders a multi-process world — mesh.py).  The two
    group tables are the ``axis_index_groups`` of the 3-phase reduce.
    """

    def __init__(
        self,
        axis: str,
        n_host: int,
        n_local: int,
        min_elems: int = DEFAULT_MIN_ELEMS,
    ):
        self.axis = axis
        self.n_host = int(n_host)
        self.n_local = int(n_local)
        self.min_elems = int(min_elems)
        self.local_groups = [
            [h * self.n_local + l for l in range(self.n_local)]
            for h in range(self.n_host)
        ]
        self.cross_groups = [
            [h * self.n_local + l for h in range(self.n_host)]
            for l in range(self.n_local)
        ]

    @property
    def hierarchical(self) -> bool:
        """Both factors non-trivial — otherwise the 3-phase route
        degenerates to a flat reduce with extra launches."""
        return self.n_host > 1 and self.n_local > 1

    def describe(self) -> dict:
        return {
            "axis": self.axis,
            "n_host": self.n_host,
            "n_local": self.n_local,
            "hierarchical": self.hierarchical,
            "min_elems": self.min_elems,
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (
            f"CollectiveTopology({self.axis!r}, host={self.n_host}, "
            f"local={self.n_local})"
        )


def resolve_topology(
    mesh,
    axes: Sequence[str],
    mode: str = AUTO,
    local_size: int = 0,
    min_elems: int = DEFAULT_MIN_ELEMS,
) -> Optional[CollectiveTopology]:
    """Resolve the collective mode for one mesh: a CollectiveTopology
    (hierarchical route armed for the outer axis) or None (flat
    everything).

    ``mode``: ``flat`` never factors; ``hierarchical`` factors by
    ``local_size`` (or the mesh's real process grouping) and falls back
    to flat — loudly, via the returned None — when no valid
    factorization exists; ``auto`` goes hierarchical exactly when the
    mesh presents a real multi-host, multi-local-replica grouping (or an
    explicit ``local_size`` says to emulate one).
    """
    if mode not in MODES:
        raise ValueError(f"collective mode must be one of {MODES}, got {mode!r}")
    if mode == FLAT or not axes:
        return None
    from elasticdl_tpu.parallel.mesh import dp_factorization

    axis = axes[0]
    n = int(mesh.shape[axis])
    n_host, n_local = dp_factorization(mesh, axis, local_size=local_size)
    topo = CollectiveTopology(axis, n_host, n_local, min_elems=min_elems)
    if not topo.hierarchical:
        return None
    assert n_host * n_local == n
    return topo


def contributor_count(mesh, axes: Axes) -> int:
    """How many subgroup-mask slots this mesh's batch axes carry — the
    length of the ``active`` vector fed to the jitted step."""
    n = 1
    for a in _as_axes(axes):
        n *= int(mesh.shape[a])
    return n


def contributor_index(axes: Axes):
    """This replica's row-major linear index over ``axes`` — the slot it
    reads out of the replicated exclusion-mask vector.  Static axis
    sizes (jax_compat.axis_size), traced per-axis position."""
    idx = None
    for a in _as_axes(axes):
        pos = lax.axis_index(a)
        idx = pos if idx is None else idx * axis_size(a) + pos
    return idx


def contributor_weight(active, axes: Axes):
    """This replica's 0/1 participation weight: ``active`` is the
    replicated ``[n_contributors]`` float32 mask, indexed by
    ``contributor_index``.  Multiplying a contribution by this weight
    *is* the subgroup psum — excluded replicas still ride the wire (the
    device collective needs every participant to dispatch the same
    program) but contribute exactly zero, and every mean renormalizes
    by ``psum(weight)`` = |G'| instead of the static world size."""
    return active[contributor_index(axes)]


def _hier_reduce_leaf(x, topo: CollectiveTopology):
    """The 3-phase hierarchical all-reduce of ONE leaf over
    ``topo.axis`` (see module docstring).  Flattens, zero-pads to
    n_local divisibility, reduce-scatters within the host group, psums
    the residue across hosts, all-gathers locally, and restores the
    shape.  Padding with zeros is exact for a sum."""
    shape = x.shape
    flat = jnp.reshape(x, (-1,))
    pad = (-flat.size) % topo.n_local
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    part = lax.psum_scatter(
        flat,
        topo.axis,
        scatter_dimension=0,
        tiled=True,
        axis_index_groups=topo.local_groups,
    )
    part = lax.psum(part, topo.axis, axis_index_groups=topo.cross_groups)
    full = lax.all_gather(
        part,
        topo.axis,
        axis=0,
        tiled=True,
        axis_index_groups=topo.local_groups,
    )
    if pad:
        full = full[: int(np.prod(shape)) if shape else 1]
    return jnp.reshape(full, shape)


def leaf_elems(x) -> int:
    """Element count of one leaf (arrays, tracers, or ShapeDtypeStructs;
    shapeless scalars count 1) — the size the ``min_elems`` routing and
    the bytes model both judge, so they cannot drift."""
    shape = getattr(x, "shape", ())
    return int(np.prod(shape)) if shape else 1


def psum(x: Any, axes: Axes, topo: Optional[CollectiveTopology] = None):
    """Sum ``x`` over the named mesh axes.  With a hierarchical ``topo``
    covering one of the axes and a leaf big enough to pay for three
    launches, that axis reduces via the 3-phase grouped route; every
    other case is the flat ``lax.psum`` this shim replaces."""
    names = _as_axes(axes)
    if (
        topo is not None
        and topo.hierarchical
        and topo.axis in names
        and leaf_elems(x) >= topo.min_elems
    ):
        rest = tuple(a for a in names if a != topo.axis)
        if rest:
            x = lax.psum(x, rest)
        return _hier_reduce_leaf(x, topo)
    return lax.psum(x, names)


def pmean(x: Any, axes: Axes, topo: Optional[CollectiveTopology] = None):
    """Mean over the named axes — ``psum / n`` with the same routing as
    ``psum`` (the flat spelling's ``lax.pmean`` is just this with the
    division fused)."""
    names = _as_axes(axes)
    n = 1
    for a in names:
        n *= axis_size(a)
    return psum(x, names, topo) / n


def psum_scatter(
    x: Any,
    axis: str,
    *,
    scatter_dimension: int = 0,
    tiled: bool = True,
):
    """Reduce-scatter over ``axis`` — the r11 sharded-optimizer's grad
    combine, routed through the shim so the collective-shim rule can
    hold the line.  Deliberately flat on the wire: a grouped two-phase
    reduce-scatter lands shard ``l * n_host + h`` on replica
    ``h * n_local + l`` — a permutation of the ``shard == axis_index``
    contract the optimizer's ``dynamic_slice``/``all_gather`` pair
    depends on.  On a hierarchical mesh the scatter is already
    bandwidth-optimal per replica (each element crosses the wire once),
    so the hierarchy's win lives in the full-psum paths."""
    return lax.psum_scatter(
        x, axis, scatter_dimension=scatter_dimension, tiled=tiled
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_all_reduce(x: Any, axis: str):
    """Megatron's *g* operator — sum partial activations over the
    tensor-parallel axis (r20, the 2D ``(dp, tp)`` mesh).

    Forward: ``psum`` over ``axis`` (the one per-block activation
    all-reduce after each row-split matmul).  Backward: IDENTITY — the
    cotangent arriving at a psum output is already replicated across the
    tp ranks, and each rank's partial activation contributed linearly,
    so the true per-rank gradient is that replicated cotangent as-is.
    This must be a ``custom_vjp``: under the shim's
    ``check_vma=False`` shard_map JAX transposes psum to psum, which
    would multiply the replicated cotangent by ``tp``.

    Deliberately flat (no hierarchical route): the mesh places ``tp`` on
    the inner, cheap hop by construction (mesh.py), and the per-block
    activation is far below any inter-host residue worth scattering.
    """
    return lax.psum(x, axis)


def _tp_all_reduce_fwd(x, axis):
    return lax.psum(x, axis), None


def _tp_all_reduce_bwd(axis, _res, g):
    return (g,)


tp_all_reduce.defvjp(_tp_all_reduce_fwd, _tp_all_reduce_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_grad_sync(x: Any, axis: str):
    """Megatron's *f* operator — identity forward, psum over the
    tensor-parallel axis backward.

    Placed on a REPLICATED activation right before a column-split
    matmul: forward is a no-op (every tp rank already holds the full
    activation), but each rank's branch consumed it independently, so
    the activation's true gradient is the SUM of the per-rank partials.
    Without this, parameters upstream of the split (norm gains, the
    residual stream, embeddings) would see only one rank's partial and
    the dp-only gradient reduce would never repair it.
    """
    return x


def _tp_grad_sync_fwd(x, axis):
    return x, None


def _tp_grad_sync_bwd(axis, _res, g):
    return (lax.psum(g, axis),)


tp_grad_sync.defvjp(_tp_grad_sync_fwd, _tp_grad_sync_bwd)


def interhost_bytes_per_step(
    leaf_sizes: Sequence[int],
    n_replicas: int,
    topo: Optional[CollectiveTopology] = None,
    itemsize: int = 4,
) -> int:
    """Analytic per-replica inter-host bytes of one step's grad
    all-reduce over ``leaf_sizes`` (element counts of the dense leaves).

    Model (ring/tree equivalences, documented in docs/perf.md): a flat
    all-reduce moves ``2 * size * (n-1)/n`` elements per replica, and on
    a mesh whose ring crosses hosts every hop is potentially inter-host;
    the hierarchical route's only inter-host phase is the residue psum —
    ``2 * (size/n_local) * (n_host-1)/n_host`` per replica.  Leaves
    below ``min_elems`` take the flat route either way.  This is the
    number the ``edl_collective_interhost_bytes_total`` gauge advances
    by (it is the model, not a meter: no DCN has been measured)."""
    if n_replicas <= 1:
        return 0
    total = 0.0
    for size in leaf_sizes:
        if topo is not None and topo.hierarchical and size >= topo.min_elems:
            residue = -(-size // topo.n_local)  # ceil: padded shard
            total += 2.0 * residue * (topo.n_host - 1) / topo.n_host
        else:
            total += 2.0 * size * (n_replicas - 1) / n_replicas
    return int(total * itemsize)
