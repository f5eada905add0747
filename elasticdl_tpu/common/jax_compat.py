"""The one module through which the framework reaches jax's moving API
surface: ``shard_map``, ``axis_size``, ``distributed_initialize`` and the
jit wrappers.  graftlint's ``compat-shim`` / ``jit-shim`` rules route every
call site here, so a jax API migration lands in one file.  The installed
jax (0.9.0) spells all of these natively; there is no branch for any other
version.
"""

from __future__ import annotations

import jax
from jax import lax

from elasticdl_tpu.common import jitsan

shard_map = jax.shard_map
axis_size = lax.axis_size
distributed_initialize = jax.distributed.initialize


def jit_compiled(fun, name=None, expected_variants=1, **jit_kwargs):
    """``jax.jit`` through the shim, with a compile-stability declaration.

    ``name`` keys the jitsan registry (graftlint's jit-shim pass requires
    it at call sites — the gauge label ``edl_jit_compiles_total{fn=}``
    and the LINT artifact's budget table are only as good as the names);
    ``expected_variants`` declares how many times THIS returned callable
    may lower (distinct shapes/dtypes/static args).  With ``GRAFT_JITSAN``
    unset the declaration costs nothing: the plain jitted function comes
    back untouched.  Armed (tier-1-wide via tests/conftest.py), every
    lowering is counted and a lowering past the budget raises
    ``jitsan.JitSanViolation`` deterministically at the drifting call
    (common/jitsan.py).
    """
    if not jitsan.enabled():
        return jax.jit(fun, **jit_kwargs)
    return jitsan.wrap(
        jax.jit, fun, name=name, expected_variants=expected_variants,
        jit_kwargs=jit_kwargs,
    )


def jit_donating(fun, donate_argnums=(0,), name=None, expected_variants=1):
    """``jax.jit`` with input-buffer donation — the train-step spelling.

    One shim owns the donation kwarg so every donating step (train, scan)
    writes it identically and a jax API migration (``donate_argnums`` ->
    the ``donate_argnames`` world) lands here once instead of per call
    site.  Donation lets XLA alias the input state's buffers into the
    output state — without it every step holds two full copies of
    params + optimizer state resident
    (tests/test_trainer_allreduce.py pins the knob's off side).

    ``name=``/``expected_variants=`` declare the jitsan compile budget,
    exactly as in :func:`jit_compiled` — donation makes stable jit
    identity MORE load-bearing, not less (a retrace on a donating step
    re-lowers against already-consumed buffers' layouts).
    """
    if not jitsan.enabled():
        return jax.jit(fun, donate_argnums=donate_argnums)
    return jitsan.wrap(
        jax.jit, fun, name=name, expected_variants=expected_variants,
        jit_kwargs={"donate_argnums": donate_argnums},
    )
