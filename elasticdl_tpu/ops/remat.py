"""What a rematerialised block keeps.

A model that rematerialises its blocks (``remat: true``) throws away every
activation of a block after the forward and computes it again in the
backward.  That is the right trade while memory is short and a waste while
it is not.  This module lets a block KEEP some of its activations, chosen by
bytes:

* **Save sites.**  A block tags the few tensors whose recomputation is a
  matmul or a kernel (:func:`site`, :func:`product`): a name
  (``jax.ad_checkpoint.checkpoint_name``, given only inside a block that
  keeps it), the bytes of the tensors and the work their recomputation
  costs.  Elementwise work (norms, activations, casts, residual adds) is
  never a site: it is recomputed, always.  A kept value is the very tensor the forward made, in
  the type it has: a kept block and a recomputed one give the same gradient.
* **The choice.**  :func:`plan` reads every layer's sites off one abstract
  trace of the block (``jax.eval_shape``, one per distinct signature) and
  :func:`choose` keeps them greedily by work spared per byte until a budget
  of bytes is spent.  Blocks are a Python loop, so every layer has its own
  keep-set.  The budget is ``ParallelContext.remat_keep_bytes``, which the
  trainer resolves from the device's memory before it traces the model
  (``parallel/trainer.py``); 0 (every CPU program) keeps nothing and wraps a
  block in the plain ``jax.checkpoint(block_fn)`` it always was.
* **The counts.**  The step's metrics carry ``remat_bytes_tagged`` /
  ``remat_bytes_kept`` off the :class:`Survey` of its trace (the worker's
  ``STEP_COUNTERS``): how far it engaged.

Work is counted in FLOPs the MXU would do: a product's ``2 m k n``; a
kernel's from the cost it declares to XLA (``pl.CostEstimate``), where every
score of an attention kernel (a transcendental in that estimate) adds
:data:`SCORE_WORK`, the vector work of its softmax the MXU does not do.
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
from jax.ad_checkpoint import checkpoint_name

#: MXU FLOPs' worth of vector work a score of an attention kernel costs
#: (mask, running maximum, exp, row sum, rescale, cast: the VPU's and the
#: EUP's, which the kernel's declared FLOPs leave out).  The forward kernels
#: of both LM cells run at a fifth to a quarter of the MXU's peak where
#: XLA's matmuls run at two thirds: the difference is 600 (flash, D = 64) to
#: 950 (EVA) FLOPs' time a score (PERF.md section 6, PR 38); 512 is under
#: both, so a kernel's output is never ranked above what it is worth.
SCORE_WORK = 512


@dataclasses.dataclass(frozen=True)
class Site:
    """One save site of one block: what keeping it costs and spares."""

    name: str
    nbytes: int
    work: float

    @property
    def work_per_byte(self) -> float:
        return self.work / max(self.nbytes, 1)


@dataclasses.dataclass
class Survey:
    """What a model's rematerialised blocks hold, as :func:`plan` saw it
    (per device): the bytes of every block's inputs (kept whatever the
    choice), each layer's sites and the names it keeps of them."""

    block_input_bytes: int = 0
    layers: List[Tuple[Site, ...]] = dataclasses.field(default_factory=list)
    keep: List[frozenset] = dataclasses.field(default_factory=list)
    #: blocks are not traced, they return zeros of their outputs' shapes
    shapes_only: bool = False
    #: the abstract traces :func:`plan` made (a survey opened with another's
    #: does not make them again)
    traces: Dict[Any, Any] = dataclasses.field(default_factory=dict)

    @property
    def tagged_bytes(self) -> int:
        return sum(s.nbytes for sites in self.layers for s in sites)

    @property
    def kept_bytes(self) -> int:
        return sum(s.nbytes for sites, names in zip(self.layers, self.keep) for s in sites if s.name in names)


_local = threading.local()


def nbytes(tree: Any) -> int:
    """Bytes of a tree of arrays or ``ShapeDtypeStruct``s."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def kept(name: str) -> bool:
    """Whether the block being traced keeps the site ``name``.  An op whose
    site is born inside a ``custom_vjp``'s forward rule asks here, while its
    primal is traced, and hands the answer to the rule as a static argument
    (``site(..., keep=...)``): jax runs the rule later, outside the block's trace."""
    return name in getattr(_local, "keeping", ())


def site(name: str, work: float, *tensors, keep: Optional[bool] = None):
    """Tag ``tensors`` (kept or recomputed TOGETHER) as the save site
    ``name`` of the block being traced; ``work``: the FLOPs their
    recomputation costs.  Returns them as they came (one, or a tuple)."""
    seen = getattr(_local, "sites", None)
    if seen is not None:
        seen.append(Site(name, nbytes(tensors), float(work)))
    if kept(name) if keep is None else keep:
        # Only inside a block that keeps it: every other trace (a block that
        # keeps nothing, a model that does not rematerialise) is to the byte
        # the program it was (a name lowers to no operation, but it moves
        # the numbers jax gives the module's private functions).
        tensors = tuple(checkpoint_name(t, name) for t in tensors)
    return tensors[0] if len(tensors) == 1 else tensors


def product(name: str, a, w):
    """``a @ w`` as the save site ``name``."""
    return site(name, 2 * a.size * w.shape[-1], a @ w)


def kernel_work(cost) -> float:
    """A kernel's work from the cost it declares (``pl.CostEstimate``)."""
    return cost.flops + SCORE_WORK * cost.transcendentals


def trace_sites(block_fn: Callable, *args) -> Tuple[Tuple[Site, ...], Any]:
    """``(the sites block_fn(*args) tags, its outputs' shapes)`` off an
    abstract trace of it (``args``: arrays or ``ShapeDtypeStruct``s)."""
    outer, _local.sites = getattr(_local, "sites", None), []
    try:
        out = jax.eval_shape(block_fn, *args)
        return tuple(_local.sites), out
    finally:
        _local.sites = outer


def choose(layers: Sequence[Sequence[Site]], budget: int) -> List[frozenset]:
    """Each layer's keep-set: sites taken by work spared per byte, the most
    first, while they fit what is left of ``budget`` bytes (a site that does
    not fit is passed over, a smaller one after it may).  Ties go to the
    later layer, whose kept tensors live the shortest (from its forward to
    its backward), then to the block's own order."""
    order = sorted(
        ((i, j) for i, sites in enumerate(layers) for j in range(len(sites))),
        key=lambda at: (-layers[at[0]][at[1]].work_per_byte, -at[0], at[1]),
    )
    keep: List[set] = [set() for _ in layers]
    left = max(int(budget), 0)
    for i, j in order:
        s = layers[i][j]
        if 0 < s.nbytes <= left:
            keep[i].add(s.name)
            left -= s.nbytes
    return [frozenset(names) for names in keep]


def rematerialised(block_fn: Callable, keep=()) -> Callable:
    """``block_fn`` rematerialised, keeping the sites named in ``keep``;
    none = ``jax.checkpoint(block_fn)``, the program it always was."""
    if not keep:
        return jax.checkpoint(block_fn)

    def keeping(*args):
        outer, _local.keeping = getattr(_local, "keeping", frozenset()), frozenset(keep)
        try:
            return block_fn(*args)
        finally:
            _local.keeping = outer

    return jax.checkpoint(keeping, policy=jax.checkpoint_policies.save_only_these_names(*sorted(keep)))


@contextlib.contextmanager
def survey(shapes_only: bool = False, traces: Optional[Dict] = None):
    """While open, :func:`plan` records what it sees and chooses into the
    yielded :class:`Survey` (the trainer reads the step's two counts off
    it).  ``shapes_only``: the blocks handed out are not traced a second
    time, they return zeros of their outputs' shapes (the trainer's
    ``jax.eval_shape`` of the model, before it knows the budget).
    ``traces``: an earlier survey's, of the same model in the same step."""
    outer, seen = getattr(_local, "survey", None), Survey(shapes_only=shapes_only, traces={} if traces is None else traces)
    _local.survey = seen
    try:
        yield seen
    finally:
        _local.survey = outer


def _identity(block_fn: Callable):
    """What tells two blocks apart: a partial's function and what it binds."""
    return (getattr(block_fn, "func", block_fn), repr(getattr(block_fn, "args", ())), repr(getattr(block_fn, "keywords", {})))


def plan(block_fns: Sequence[Callable], layer_args: Sequence[tuple], budget: int, inputs: int = 1) -> List[Callable]:
    """The blocks of a model whose layer ``i`` is ``block_fns[i](*layer_args[i])``
    (only the arguments' shapes are read), each rematerialised with the
    keep-set :func:`choose` gives it under ``budget`` bytes.  The first
    ``inputs`` arguments of a layer are what its checkpoint holds until the
    backward (the rest are parameters and constants).  One abstract trace a
    distinct block and signature of the arguments, whatever the number of layers."""
    import jax.numpy as jnp

    seen: Optional[Survey] = getattr(_local, "survey", None)
    traced = {} if seen is None else seen.traces

    def abstract(block_fn, args):
        shapes = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
        key = (_identity(block_fn), jax.tree.structure(shapes), tuple((s.shape, s.dtype) for s in jax.tree.leaves(shapes)))
        if key not in traced:
            traced[key] = trace_sites(block_fn, *shapes)
        return traced[key]

    layers = [abstract(block_fn, args)[0] for block_fn, args in zip(block_fns, layer_args)]
    keep = choose(layers, budget)
    if seen is not None:
        seen.layers.extend(layers)
        seen.keep.extend(keep)
        seen.block_input_bytes += sum(nbytes(args[:inputs]) for args in layer_args)
        if seen.shapes_only:
            zeros = lambda s: jnp.zeros(s.shape, s.dtype)  # noqa: E731
            return [lambda *args, block_fn=block_fn: jax.tree.map(zeros, abstract(block_fn, args)[1]) for block_fn in block_fns]
    # One callable a distinct block and keep-set: layers alike that keep the
    # same names are the same function to jax, traced and transposed once.
    blocks: Dict[Any, Callable] = {}
    planned = []
    for block_fn, names in zip(block_fns, keep):
        key = (_identity(block_fn), names)
        if key not in blocks:
            blocks[key] = rematerialised(block_fn, names)
        planned.append(blocks[key])
    return planned
