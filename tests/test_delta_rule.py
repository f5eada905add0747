"""``ops/delta_rule``: the chunked gated delta rule against its
position-by-position reference, forward and every gradient, at chunks that do
and do not divide into sub-blocks, with decays at both ends of the published
range; the three seams, on the XLA path and on the kernels'; the same-sub-block
kernel pair (``ops/delta_rule_kernels.py`` under the interpreter) against the
XLA differences; which path a call takes; the gated norm a head."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import delta_rule as dr

#: a head's -exp(A_log): the published init's slowest, a middle one and its fastest
RATES = jnp.array([1.0, 8.0, 16.0])


def _operands(seed: int = 0, *, b=2, length=96, dk=8, dv=6, softplus_at: float = -4.0, dtype=jnp.float32):
    """l2-normalised q (scaled) and k, v, a log-decay a channel ``-rate x
    softplus(normal + softplus_at)`` and beta in (0, 1), seeded.
    ``softplus_at`` -4: decays near 1 (a channel keeps 0.98 a position at the
    slowest); +2: softplus about 2, the fastest head's channels lose exp(-32)
    and more a position, exp(-500) across a sub-block."""
    ks = jax.random.split(jax.random.key(seed), 5)
    heads = RATES.shape[0]
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (b, length, heads, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (b, length, heads, dk)))
    v = jax.random.normal(ks[2], (b, length, heads, dv))
    g = -RATES[None, None, :, None] * jax.nn.softplus(jax.random.normal(ks[3], (b, length, heads, dk)) + softplus_at)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, length, heads)))
    return tuple(t.astype(dtype) for t in (q, k, v)) + (g, beta)


def _close(got, want, rel):
    assert got.shape == want.shape
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) <= rel * float(jnp.max(jnp.abs(want)))


#: (chunk, L): 64 and 32 are whole sub-blocks of 16, 24 is not (padded to 32), 8 is under one
CHUNKS = [(64, 128), (32, 96), (24, 96), (8, 96)]


@functools.lru_cache(maxsize=None)
def _rule_and_recurrence(chunk: int, length: int):
    """``(the chunked rule, the recurrence)``, each ``operands -> (o, the last state, every gradient)`` as ONE jitted
    program: which end of the decays' range a case is at is in its operands' VALUES, so the two cases of a (chunk, L)
    read one compiled pair (each compiled its own: PR 66)."""
    weigh = jax.random.normal(jax.random.key(9), (2, length, RATES.shape[0], 6))
    every = (0, 1, 2, 3, 4)

    def rule(*a):
        o, aux = dr.delta_rule(*a, chunk=chunk, with_aux=True)
        return o, aux.state, jax.grad(lambda *a: jnp.sum(dr.delta_rule(*a, chunk=chunk) * weigh), argnums=every)(*a)

    def recurrence(*a):
        return *dr.delta_rule_reference(*a), jax.grad(lambda *a: jnp.sum(dr.delta_rule_reference(*a)[0] * weigh), argnums=every)(*a)

    return jax.jit(rule), jax.jit(recurrence)


@pytest.mark.parametrize("softplus_at", [-4.0, 2.0], ids=["decays_near_1", "decays_near_exp_-16_softplus"])
@pytest.mark.parametrize("chunk,length", CHUNKS, ids=[f"chunk{c}" for c, _ in CHUNKS])
def test_the_chunked_rule_is_the_recurrence_forward_and_in_every_gradient(chunk, length, softplus_at):
    args = _operands(length=length, softplus_at=softplus_at)
    assert (float(args[3].min()) < -50) == (softplus_at > 0)  # exp(50 x 16 positions) leaves float32: a whole-chunk quotient fails here
    assert args[2].shape == (2, length, RATES.shape[0], 6)
    rule, recurrence = _rule_and_recurrence(chunk, length)
    with jax.default_matmul_precision("highest"):  # ONE program a side: op by op, the gradients are thousands of dispatches
        o, state, got = rule(*args)
        want, last, ref = recurrence(*args)
    _close(o, want, 5e-6)
    _close(state, last, 5e-6)
    assert all(bool(jnp.all(jnp.isfinite(t))) for t in got)
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(jnp.max(jnp.abs(b))), name


def test_a_quotient_over_a_whole_chunk_would_overflow_where_the_sub_block_form_does_not():
    """Why the reference points exist: ``exp(G_r) / exp(G_i)`` over a chunk
    of 64 is ``0 / 0`` at the published range's fast end."""
    q, k, v, g, beta = _operands(length=64, softplus_at=2.0)
    cum = jnp.cumsum(g, axis=1)
    with np.errstate(all="ignore"):
        quotient = jnp.exp(cum[:, -1]) / jnp.exp(cum[:, 0])
    assert not bool(jnp.all(jnp.isfinite(quotient))) or float(jnp.min(jnp.exp(cum[:, 20]))) == 0.0
    assert bool(jnp.all(jnp.isfinite(dr.delta_rule(q, k, v, g, beta, chunk=64))))


def test_the_summed_log_decays_are_float32_whatever_the_operands_are():
    q, k, v, g, beta = _operands(dtype=jnp.bfloat16)
    o, aux = dr.delta_rule(q, k, v, g, beta, chunk=32, with_aux=True)
    assert o.dtype == jnp.bfloat16 and aux.log_decay.dtype == aux.state.dtype == jnp.float32
    want = np.asarray(g, np.float64).reshape(2, 3, 32, 3, 8).cumsum(2).reshape(g.shape)
    np.testing.assert_allclose(np.asarray(aux.log_decay, np.float64), want, rtol=2e-6)
    # bfloat16 operands stay near the float32 recurrence on the same operands
    ref, _ = dr.delta_rule_reference(q, k, v, g, beta)
    _close(o, ref, 3e-2)


def _onto_diagonal(q, k, cum):
    """``_same_sub_block`` and its product onto the diagonal, as
    ``_masks_of`` joins them on the XLA path: [.., C, C]."""
    lead, (size, dk) = cum.shape[:-2], cum.shape[-2:]
    blocks = lambda t: t.astype(jnp.float32).reshape(*lead, size // dr.SUB, dr.SUB, dk)  # noqa: E731
    onto = lambda t: jnp.einsum("...sri,st->...srti", t, jnp.eye(size // dr.SUB)).reshape(*lead, size, size)  # noqa: E731
    return tuple(onto(t) for t in dr._same_sub_block(blocks(q), blocks(k), blocks(cum)))


#: (lead, C): one 128-lane group of two chunks; three chunks of 32 (N = 96: padded to a group); one chunk of 128
KERNEL_TILES = [((2,), 64), ((3,), 32), ((1,), 128)]


#: every type and both ends of the decays' range at the cell's chunk; the other chunks at two corners of the four
KERNEL_CASES = [(lead, size, softplus_at, dtype) for lead, size in KERNEL_TILES for softplus_at in (-4.0, 2.0) for dtype in (jnp.float32, jnp.bfloat16)
                if size == 64 or (softplus_at < 0) == (dtype == jnp.float32)]


@functools.lru_cache(maxsize=None)
def _masks_and_their_gradients(lead: tuple, size: int):
    """``(the kernels', the XLA differences')``, each ``(q, k, cum) -> (both masks, the three gradients under dense
    cotangents)`` as ONE jitted program a tile (and, through jit's own cache, a type): which end of the decays' range a
    case is at is in ``cum``'s VALUES, so the cases of a tile and a type read one compiled pair (PR 66)."""
    weigh = tuple(jax.random.normal(key, (*lead, size, size)) for key in jax.random.split(jax.random.key(size), 5)[3:])

    def masks_and_gradients(masks):  # ONE program a side
        def both(*a):
            out, vjp = jax.vjp(masks, *a)
            return out, vjp(weigh)
        return jax.jit(both)

    return masks_and_gradients(lambda *a: dr._same_sub_block_kernels(*a, True)), masks_and_gradients(_onto_diagonal)


@pytest.mark.parametrize("lead,size,softplus_at,dtype", KERNEL_CASES, ids=[
    f"C{c}-{'decays_near_1' if at < 0 else 'decays_near_exp_-16_softplus'}-{jnp.dtype(dtype).name}" for _, c, at, dtype in KERNEL_CASES])
def test_the_kernel_pair_is_the_xla_differences_forward_and_in_all_three_gradients(lead, size, softplus_at, dtype):
    """``_same_sub_block_kernels`` under the interpreter against
    ``_same_sub_block`` onto the diagonal: both masks, and the gradients of
    ``q``, ``k`` and the sums under DENSE cotangents (what lies outside the
    sub-blocks is not read).  Near ``exp(-16 softplus)`` a sub-block's
    differences reach -500 and their negatives would overflow: the mask is
    applied before the ``exp`` and every number is finite."""
    ks = jax.random.split(jax.random.key(size), 5)
    q, k = (jax.random.normal(key, (*lead, size, 128)).astype(dtype) for key in ks[:2])
    cum = jnp.cumsum(-16.0 * jax.nn.softplus(jax.random.normal(ks[2], (*lead, size, 128)) + softplus_at), axis=-2)
    assert (float(cum.min()) < -800) == (softplus_at > 0)
    by_kernels, by_xla = _masks_and_their_gradients(lead, size)
    (got, got_grads), (want, want_grads) = by_kernels(q, k, cum), by_xla(q, k, cum)
    for g, w in zip(got, want):
        _close(g, w, 2e-6)
    for name, g, w in zip("q k cum".split(), got_grads, want_grads):
        assert g.dtype == w.dtype and bool(jnp.all(jnp.isfinite(g.astype(jnp.float32)))), name
        # q, k: one rounding to bfloat16, each path from its own float32 sum.  The sums' gradient adds and takes away
        # each pair's z: where all but a few pairs underflow, XLA's transpose is the looser of the two against
        # float64 (6e-7 of a largest 0.04, the kernels 2e-9)
        _close(g, w.astype(jnp.float32), 1e-2 if dtype == jnp.bfloat16 and name != "cum" else 5e-5 if name == "cum" else 5e-6)


def _system(keys: str, size: int, seed: int = 0, lead=(2, 3), dk: int = 8, n: int = 24):
    """``(a, rhs)`` as the rule builds them: ``a = tril(beta_r k_r . k_i,
    -1)`` [.., size, size] of l2-normalised keys.  ``random``: distinct keys,
    beta in (0, 1); ``repeated``: ONE key a system and beta near 1, so ``I +
    a`` is near the triangle of ones (its inverse is bidiagonal, the powers
    of ``a`` are binomials: 1e17 at 64 rows); ``alternating``: one key with
    alternating sign."""
    ks = jax.random.split(jax.random.key(seed), 3)
    k = jax.random.normal(ks[0], (*lead, size, dk))
    beta = jax.nn.sigmoid(jax.random.normal(ks[1], (*lead, size)))
    if keys != "random":
        k = jnp.broadcast_to(k[..., :1, :], k.shape)
        beta = 1.0 - 1e-3 * beta
    if keys == "alternating":
        k = k * jnp.where(jnp.arange(size) % 2, -1.0, 1.0)[:, None]
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    a = jnp.tril(beta[..., None] * jnp.einsum("...rc,...ic->...ri", k, k, precision="highest"), -1)
    return a, jax.random.normal(ks[2], (*lead, size, n))


SYSTEMS = [(keys, size) for keys in ("random", "repeated", "alternating") for size in (64, 32, 16, 8)]


@functools.lru_cache(maxsize=None)
def _gradients_through_the_solve(size: int):
    """``(through _solve, through XLA's triangular_solve)``, each ``(a, rhs) -> both gradients`` as ONE jitted program
    a size: the three kinds of keys are VALUES of ``a``, so they read one compiled pair (PR 66)."""
    weigh = jax.random.normal(jax.random.key(7), (2, 3, size, 24))
    xla = lambda a, rhs: jax.lax.linalg.triangular_solve(a, rhs, left_side=True, lower=True, unit_diagonal=True)  # noqa: E731
    through = lambda solve: jax.jit(jax.grad(lambda a, rhs: jnp.sum(solve(a, rhs) * weigh), argnums=(0, 1)))  # noqa: E731
    return through(dr._solve), through(xla)


@pytest.mark.parametrize("keys,size", sorted(SYSTEMS, key=lambda system: -system[1]), ids=lambda v: f"C{v}" if isinstance(v, int) else v)
def test_the_blocked_solve_is_the_float64_solve_and_its_gradients_are_the_triangular_solves(keys, size):
    """``_solve`` alone: blocked forward substitution on sub-blocks of 16 (a
    chunk under one sub-block is one diagonal block) against numpy's float64
    solve of ``I + a`` forward, and against XLA's ``triangular_solve`` at the
    highest precision in both gradients."""
    a, rhs = _system(keys, size)
    exact = np.linalg.solve(np.eye(size) + np.asarray(a, np.float64), np.asarray(rhs, np.float64))
    assert np.max(np.abs(np.asarray(dr._solve(a, rhs), np.float64) - exact)) <= 2e-6 * np.max(np.abs(exact))
    assert rhs.shape == (2, 3, size, 24)
    through_solve, through_xla = _gradients_through_the_solve(size)
    with jax.default_matmul_precision("highest"):
        got, ref = through_solve(a, rhs), through_xla(a, rhs)
    for g, r in zip(got, ref):
        _close(g, r, 1e-5)
    assert bool(jnp.all(jnp.triu(got[0]) == 0))  # the gradient lies where the operand is read


@pytest.mark.parametrize("size", [64, 8], ids=lambda v: f"C{v}")
def test_the_solve_does_not_read_what_lies_on_or_above_the_diagonal(size):
    a, rhs = _system("random", size)
    nan_above = jnp.where(jnp.arange(size)[:, None] <= jnp.arange(size)[None, :], jnp.nan, a)
    both = jax.jit(lambda a: (dr._solve(a, rhs),) + jax.grad(lambda a, rhs: jnp.sum(dr._solve(a, rhs) ** 2), argnums=(0, 1))(a, rhs))
    for got, want in zip(both(nan_above), both(a)):
        assert bool(jnp.all(jnp.isfinite(got))) and bool(jnp.all(got == want))


def test_without_the_delta_correction_the_rule_is_gated_linear_attention(monkeypatch):
    """``T = Diag(beta)``: ``S_t = Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T``
    inside a chunk — what the control ``no_delta_correction`` runs; with one
    chunk a sequence it is plain gated linear attention throughout."""
    q, k, v, g, beta = _operands(length=32)
    monkeypatch.setattr(dr, "_solve", lambda a, rhs: rhs)
    with jax.default_matmul_precision("highest"):
        got = dr.delta_rule(q, k, v, g, beta, chunk=32)

        def step(state, at):
            q_t, k_t, v_t, g_t, b_t = at
            state = jnp.exp(g_t)[..., None] * state + (b_t[..., None] * k_t)[..., :, None] * v_t[..., None, :]
            return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

        first = jnp.zeros((2, 3, 8, 6))
        _, want = jax.lax.scan(step, first, tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
    _close(got, jnp.moveaxis(want, 0, 1), 1e-5)


def test_a_sequence_that_is_not_whole_chunks_takes_the_stepwise_path():
    args = _operands(length=40)
    assert dr.rule_path(40, 16) == (dr.PATH_STEPWISE, "L = 40 is not whole chunks of 16") and dr.rule_path(48, 16) == (dr.PATH_CHUNKED, "")
    with jax.default_matmul_precision("highest"):
        o, aux = jax.jit(lambda *a: dr.delta_rule(*a, chunk=16, with_aux=True))(*args)
        want, last = jax.jit(dr.delta_rule_reference)(*args)
    _close(o, want, 1e-6)
    _close(aux.state, last, 1e-6)
    np.testing.assert_allclose(aux.log_decay, jnp.cumsum(args[3], axis=1), rtol=1e-5)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(dr.delta_rule(*a, chunk=16) ** 2), argnums=(0, 1, 2, 3, 4)))(*args)
    assert all(bool(jnp.all(jnp.isfinite(t))) and float(jnp.max(jnp.abs(t))) > 0 for t in grads)


def test_shapes_that_do_not_agree_are_refused():
    q, k, v, g, beta = _operands()
    with pytest.raises(ValueError, match="shapes do not agree"):
        dr.delta_rule(q, k, v, g[..., :4], beta)
    with pytest.raises(ValueError, match="shapes do not agree"):
        dr.delta_rule(q, k, v, g, beta[:, :, :2])


def test_rule_flops_counts_the_chunked_forms_products():
    # a head and position at chunk 64, dk = dv = 128: the two masks, the solve's triangle, P U, three dk x dv products
    assert dr.rule_flops(1, 1, 1, 128, 128, 64) == 4 * 64 * 128 + 64 * 256 + 2 * 64 * 128 + 6 * 128 * 128 == 163840
    assert dr.rule_flops(1, 8192, 32, 128, 128, 64) == 8192 * 32 * 163840


def test_the_gated_norm_a_head_norms_first_and_gates_with_a_sigmoid():
    ks = jax.random.split(jax.random.key(0), 3)
    o, gate = jax.random.normal(ks[0], (2, 5, 3, 8)), jax.random.normal(ks[1], (2, 5, 3, 8))
    gain = 1.0 + 0.3 * jax.random.normal(ks[2], (8,))
    got = dr.gated_head_norm(o, gate, gain, 1e-5)
    np.testing.assert_allclose(got, dr.gated_head_norm_reference(o, gate, gain, 1e-5), rtol=2e-6, atol=1e-6)
    # not ops/ssm.gated_group_norm (silu gate BEFORE the norm): a head's rms is the gain's before the gate
    ungated = dr.gated_head_norm(o, jnp.full_like(gate, 50.0), jnp.ones((8,)), 0.0)
    np.testing.assert_allclose(jnp.sqrt(jnp.mean(ungated ** 2, -1)), 1.0, rtol=1e-5)
    assert dr.gated_head_norm(o.astype(jnp.bfloat16), gate, gain, 1e-5).dtype == jnp.bfloat16


# -- the cases that patch which path the op takes: the file's LAST, together ---------------------------------------------
# ``_masks_of`` is a ``jax.checkpoint``, whose trace jax keeps by shapes, so a trace made while a case has the path
# patched (``mask_path`` by the interpreter, the backend it reads) must not answer for a case on another path.  jax's
# caches are dropped ONCE ahead of the first of these cases and ONCE behind the last (the module's end): each drop takes
# every compiled program of the worker with it, and until PR 66 there was one on both sides of each of the nine.  Among
# themselves the nine differ in what a trace is kept by: the operands' shapes or the chunk.


@pytest.fixture(scope="module")
def patched_paths():
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.fixture
def on_the_kernel_path(monkeypatch, patched_paths):
    """``mask_path`` answers as if a test had given ``interpret``: the
    same-sub-block masks of every call below are the Pallas kernels under the
    interpreter."""
    path = dr.mask_path
    monkeypatch.setattr(dr, "mask_path", lambda k, chunk, interpret=None: path(k, chunk, True))


def _kernel_operands(softplus_at: float = -4.0):
    """The smallest call inside the kernels' contract: ONE sequence of two
    chunks of 64, three heads (the three rates) of dk = 128."""
    return _operands(b=1, length=128, dk=128, dv=8, softplus_at=softplus_at)


@pytest.mark.parametrize("path", ["xla", "kernels"])
def test_the_three_seams_are_called_and_each_changes_the_result(monkeypatch, request, path):
    """The benchmark's controls swap ``_log_decays``, ``_carry`` and
    ``_solve`` by module attribute: each is looked up at call time, forward
    and backward, and each one's fault shows, with the same-sub-block masks
    in XLA and in the kernels (which consume the seam's sums and hand their
    masks to the seams' solve and carry)."""
    if path == "kernels":
        request.getfixturevalue("on_the_kernel_path")
        args = _kernel_operands()
    else:
        args = _operands(length=128)
    chunk = 64 if path == "kernels" else 32

    # ONE trace and ONE compile (a minute each on the kernels' path): every seam is swapped for itself with its fault
    # beside it, and ``on`` [3], an operand, says at run time which fault, if any, the result takes
    decays, carry, solve = dr._log_decays, dr._carry, dr._solve

    def read(on, *a):
        faults = {
            "_log_decays": lambda g, chunk: (lambda sums: jnp.where(on[0], sums.astype(jnp.bfloat16).astype(jnp.float32), sums))(decays(g, chunk)),
            "_carry": lambda *a, **kw: (lambda starts, last: (jnp.where(on[1], jnp.zeros_like(starts), starts), last))(*carry(*a, **kw)),
            "_solve": lambda a, rhs: jnp.where(on[2], rhs, solve(a, rhs)),
        }

        def loss(*a):
            o = dr.delta_rule(*a, chunk=chunk)
            return jnp.sum(o ** 2), o

        with monkeypatch.context() as patch:
            for name, fault in faults.items():
                patch.setattr(dr, name, fault)
            (_, o), grad = jax.value_and_grad(loss, argnums=1, has_aux=True)(*a)
        return o, grad

    read = jax.jit(read)
    sound, sound_grad = read(jnp.zeros(3, bool), *args)
    for at, name in enumerate(("_log_decays", "_carry", "_solve")):
        o, grad = read(jnp.arange(3) == at, *args)
        off = float(jnp.max(jnp.abs(o - sound)) / jnp.max(jnp.abs(sound)))
        off_grad = float(jnp.max(jnp.abs(grad - sound_grad)) / jnp.max(jnp.abs(sound_grad)))
        assert off > (1e-4 if name == "_log_decays" else 1e-2) and off_grad > 1e-4, (name, off, off_grad)


@functools.lru_cache(maxsize=None)
def _rule_on_the_kernel_path_and_recurrence():
    """``(the rule, the recurrence)`` at ``_kernel_operands``' shapes, each ``operands -> ((loss, (o, state)), every
    gradient)`` as ONE jitted program: both ends of the decays' range read one compiled pair (the rule's is traced by
    the first of the two cases, with the path patched, and no drop of the caches stands between them)."""
    weigh = jax.random.normal(jax.random.key(9), (1, 128, RATES.shape[0], 8))

    def read(rule):  # ONE program a side: the kernels are compiled for the interpreter once a pass
        def loss(*a):
            o, state = rule(*a)
            return jnp.sum(o * weigh), (o, state)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))

    return read(lambda *a: (lambda o, aux: (o, aux.state))(*dr.delta_rule(*a, chunk=64, with_aux=True))), read(dr.delta_rule_reference)


@pytest.mark.parametrize("softplus_at", [-4.0, 2.0], ids=["decays_near_1", "decays_near_exp_-16_softplus"])
def test_the_rule_on_the_kernel_path_is_the_recurrence_forward_and_in_every_gradient(on_the_kernel_path, softplus_at):
    args = _kernel_operands(softplus_at)
    assert "pallas_call" in str(jax.make_jaxpr(lambda *a: dr.delta_rule(*a, chunk=64))(*args)) and args[2].shape == (1, 128, RATES.shape[0], 8)
    rule, recurrence = _rule_on_the_kernel_path_and_recurrence()
    with jax.default_matmul_precision("highest"):
        (_, (o, state)), got = rule(*args)
        (_, (want, last)), ref = recurrence(*args)
        _close(o, want, 5e-6)
        _close(state, last, 1e-5)  # sums over 128 channels, sixteen times the other cases'
    for name, a, b in zip("q k v g beta".split(), got, ref):
        assert bool(jnp.all(jnp.isfinite(a))) and float(jnp.max(jnp.abs(a - b))) <= 5e-5 * float(jnp.max(jnp.abs(b))), name


@pytest.fixture
def path_lines(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    return lines


@pytest.mark.parametrize("backend,dk,chunk,path,why", [
    ("cpu", 128, 64, "xla-reference", "backend=cpu"),
    ("tpu", 64, 64, "xla-reference", "dk = 64 is not whole multiples of 128"),
    ("tpu", 128, 48, "xla-reference", "the padded chunk 48 is not 16, 32, 64 or 128"),
    ("tpu", 128, 8, "xla-reference", "the padded chunk 8 is not 16, 32, 64 or 128"),
    ("tpu", 128, 24, "pallas-compiled", ""),  # padded to 32
    ("tpu", 256, 64, "pallas-compiled", ""),
], ids=["off_the_tpu", "narrow_channels", "chunk_of_three_sub_blocks", "chunk_under_a_sub_block", "padded_chunk", "on_the_tpu_inside_the_contract"])
def test_the_backend_and_the_shapes_alone_choose_the_mask_path(monkeypatch, patched_paths, path_lines, backend, dk, chunk, path, why):
    """No flag: ``mask_path`` reads the backend, ``k``'s width and the chunk;
    the op's ``attention path:`` line says what it answered; asked for by
    name (``interpret``), the kernels refuse what is outside their contract."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    args = _operands(b=1, length=192, dk=dk)
    assert dr.mask_path(args[1], chunk) == (path, why)
    traced = str(jax.make_jaxpr(lambda *a: dr.delta_rule(*a, chunk=chunk))(*args))
    assert ("pallas_call" in traced) == (path != "xla-reference")
    (line,) = path_lines
    assert line == f"attention path: {path} (q=(1, 192, 3, {dk}) float32 causal=True; kda_mask chunk={chunk}{'; ' + why if why else ''})"
    if why and backend == "tpu":
        with pytest.raises(ValueError, match="outside their contract"):
            dr.mask_path(args[1], chunk, True)
    else:
        assert dr.mask_path(args[1], chunk, True) == ("pallas-interpret", "") and dr.mask_path(args[1], chunk, False) == ("pallas-compiled", "")
