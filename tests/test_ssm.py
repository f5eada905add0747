"""``ops/ssm.py`` against its plain references: the chunked scan (forward,
the last state, every gradient; the XLA einsums, and the Pallas kernels of
``ops/ssm_kernels.py`` under the interpreter), the causal depthwise
convolution and the gated norm over groups."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import remat, ssm


def _operands(seed, batch, length, heads, width, groups, state, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(ks[0], (batch, length, heads, width), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (batch, length, heads)) - 2.0)
    a = -jnp.exp(jax.random.uniform(ks[2], (heads,), minval=0.0, maxval=jnp.log(16.0)))
    b = jax.random.normal(ks[3], (batch, length, groups, state), jnp.float32)
    c = jax.random.normal(ks[4], (batch, length, groups, state), jnp.float32)
    d = jax.random.normal(ks[5], (heads,))
    g = jax.random.normal(ks[6], x.shape, jnp.float32)
    return (x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d), g


SHAPES = {
    "one_group": (1, 32, 2, 4, 1, 8, 8),
    "two_groups": (2, 48, 4, 8, 2, 16, 16),
    "one_chunk": (1, 16, 2, 4, 2, 8, 16),
    "heads_are_groups": (1, 24, 3, 4, 3, 4, 8),
}


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_scan_forward_and_last_state_match_the_recurrence(name):
    *shape, chunk = SHAPES[name]
    args, _ = _operands(0, *shape)
    y, aux = jax.jit(lambda *a: ssm.ssm_scan(*a, chunk=chunk, with_aux=True))(*args)  # ONE program a side, not a few dozen eager ones
    want, last = jax.jit(ssm.ssm_scan_reference)(*args)
    np.testing.assert_allclose(y, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(aux.state, last, rtol=2e-5, atol=2e-5)
    # the log-decays restart at every chunk
    log = np.asarray(args[1] * args[2]).reshape(shape[0], shape[1] // chunk, chunk, shape[2]).cumsum(2)
    np.testing.assert_allclose(aux.log_decay, log.reshape(aux.log_decay.shape), rtol=1e-5, atol=1e-6)


@functools.lru_cache(maxsize=None)
def _scan_gradients(name: str):
    """``(the chunked scan's, the recurrence's)`` gradients in all six operands at one shape, each ONE jitted program:
    the six cases of a shape read one compiled pair (each compiled two programs of its own: PR 66)."""
    *shape, chunk = SHAPES[name]
    args, g = _operands(1, *shape)
    every = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * g), argnums=tuple(range(6))))(*args)  # noqa: E731
    return every(lambda *a: ssm.ssm_scan(*a, chunk=chunk)), every(lambda *a: ssm.ssm_scan_reference(*a)[0])


@pytest.mark.parametrize("operand", range(6), ids=["x", "dt", "a", "b", "c", "d"])
@pytest.mark.parametrize("name", ["two_groups", "heads_are_groups"])
def test_scan_gradient_matches_the_recurrence(name, operand):
    got, want = _scan_gradients(name)
    np.testing.assert_allclose(got[operand], want[operand], rtol=2e-4, atol=2e-4)


def test_scan_last_state_carries_a_gradient():
    *shape, chunk = SHAPES["two_groups"]
    args, _ = _operands(2, *shape)
    # ssm_scan stops the aux's gradient; the inner op's second output carries one
    got = jax.jit(jax.grad(lambda x: jnp.sum(ssm._scan(x, *args[1:5], chunk, False)[1] ** 2)))(args[0])
    want = jax.jit(jax.grad(lambda x: jnp.sum(ssm.ssm_scan_reference(x, *args[1:5])[1] ** 2)))(args[0])
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_scan_in_bfloat16_stays_near_the_float32_recurrence():
    *shape, chunk = SHAPES["two_groups"]
    args, _ = _operands(3, *shape, dtype=jnp.bfloat16)
    y = ssm.ssm_scan(*args, chunk=chunk)
    want, _ = ssm.ssm_scan_reference(*args)
    assert y.dtype == jnp.bfloat16
    assert float(jnp.max(jnp.abs(y.astype(jnp.float32) - want)) / jnp.max(jnp.abs(want))) < 2e-2


def test_scan_over_a_long_decay_keeps_its_float32_island():
    # 512 positions of decays near 1: a bfloat16 cumulative sum would be off by whole units
    args, _ = _operands(4, 1, 512, 2, 4, 1, 8)
    args = (args[0], 0.01 * jnp.ones_like(args[1]), -jnp.ones_like(args[2]), *args[3:])
    y = ssm.ssm_scan(*args, chunk=128)
    np.testing.assert_allclose(y, ssm.ssm_scan_reference(*args)[0], rtol=1e-4, atol=1e-4)


def test_scan_refuses_a_sequence_that_is_not_whole_chunks():
    args, _ = _operands(0, 1, 40, 2, 4, 1, 8)
    with pytest.raises(ValueError, match="whole chunks of 16"):
        ssm.ssm_scan(*args, chunk=16)


def test_scan_refuses_heads_that_do_not_split_into_the_groups():
    args, _ = _operands(0, 1, 32, 3, 4, 1, 8)
    bad = (args[0], args[1], args[2], jnp.zeros((1, 32, 2, 8)), jnp.zeros((1, 32, 2, 8)), args[5])
    with pytest.raises(ValueError, match="3 heads in 2 groups"):
        ssm.ssm_scan(*bad, chunk=16)


@pytest.mark.parametrize("keep", [False, True], ids=["recomputed", "kept"])
def test_scan_under_a_rematerialised_block_gives_the_same_gradient(keep):
    *shape, chunk = SHAPES["two_groups"]
    args, g = _operands(5, *shape)

    def block(x):
        return jnp.sum(ssm.ssm_scan(x, *args[1:], chunk=chunk) * g)

    sites, _ = remat.trace_sites(block, args[0])
    assert [s.name for s in sites] == ["ssm_scan_out"]
    assert sites[0].work == ssm.scan_flops(*shape, chunk)
    # op by op on both sides, which is what holds the two to 1e-6 (they read 0.0): compiled whole, XLA fuses the two programs
    # each its own way and they read 6.7e-6 of a largest 15.8 apart
    got = jax.grad(remat.rematerialised(block, {"ssm_scan_out"} if keep else ()))(args[0])
    np.testing.assert_allclose(got, jax.grad(block)(args[0]), rtol=1e-6, atol=1e-6)


#: ONE shape inside the kernels' contract (ops/ssm.outside_contract): two
#: chunks of 128, 4 heads of 64 in 2 groups, a state of 128 columns.
KERNEL_SHAPE, KERNEL_CHUNK = (1, 256, 4, 64, 2, 128), 128
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
#: largest difference over the largest value, by the operands' type
NEAR = {"float32": 5e-4, "bfloat16": 2e-2}
PATHS = {"kernels": True, "xla": None}  # ``_scan``'s ``interpret``


def _off(got, want) -> float:
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32).reshape(np.shape(got))
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@functools.lru_cache(maxsize=None)
def _at_kernel_shape(dtype: str, path: str, seed: int = 6) -> dict:
    """y, the last state, the five operands' gradients of ``sum(y g) +
    sum(last^2)`` and x's gradient through the last state alone, by the
    kernels under the interpreter, the XLA einsums or the recurrence."""
    args, g = _operands(seed, *KERNEL_SHAPE, dtype=DTYPES[dtype])

    def scan(*operands):
        if path == "reference":
            return ssm.ssm_scan_reference(*operands)
        return ssm._scan(*operands, KERNEL_CHUNK, False, PATHS[path])

    def loss(*operands):
        y, last = scan(*operands)
        return jnp.sum(y.astype(jnp.float32) * g) + jnp.sum(last ** 2), (y, last)

    (_, (y, last)), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args[:5])
    through_last = jax.jit(jax.grad(lambda x: jnp.sum(scan(x, *args[1:5])[1] ** 2)))(args[0])
    return {"y": y, "last": last, **dict(zip(("x", "dt", "a", "b", "c"), grads)), "through_last": through_last}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernels_forward_and_last_state_match_the_recurrence(dtype):
    got, want = _at_kernel_shape(dtype, "kernels"), _at_kernel_shape(dtype, "reference")
    assert got["y"].dtype == DTYPES[dtype] and got["last"].dtype == jnp.float32
    assert _off(got["y"], want["y"]) < NEAR[dtype] and _off(got["last"], want["last"]) < NEAR[dtype]


@pytest.mark.parametrize("of", ["x", "dt", "a", "b", "c", "through_last"])
@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernels_gradient_matches_the_recurrence(dtype, of):
    got, want = _at_kernel_shape(dtype, "kernels")[of], _at_kernel_shape(dtype, "reference")[of]
    assert got.dtype == want.dtype and got.shape == want.shape
    assert _off(got, want) < NEAR[dtype], of


@pytest.mark.parametrize("dtype", sorted(DTYPES))
def test_kernel_path_matches_the_xla_path_on_the_same_operands(dtype):
    got, want = _at_kernel_shape(dtype, "kernels"), _at_kernel_shape(dtype, "xla")
    # float32: the same sums in another order; bfloat16: the same roundings, so mostly the same bits
    for name in got:
        assert _off(got[name], want[name]) < {"float32": 1e-4, "bfloat16": 1e-2}[dtype], name


@pytest.fixture
def path_lines(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    return lines


@pytest.mark.parametrize("backend,shape,chunk,path,why", [
    ("cpu", KERNEL_SHAPE, KERNEL_CHUNK, "xla-reference", "backend=cpu"),
    ("tpu", (1, 256, 4, 64, 2, 128), 64, "xla-reference", "chunk 64 and state 128 are not whole multiples of 128"),
    ("tpu", (1, 256, 4, 64, 2, 64), 128, "xla-reference", "chunk 128 and state 64 are not whole multiples of 128"),
    ("tpu", (1, 256, 6, 32, 2, 128), 128, "xla-reference", "a group's 3 heads of 32 are not whole lanes"),
    ("tpu", (1, 256, 64, 4, 2, 128), 128, "xla-reference", "a group's 32 heads of 4 are not whole lanes of 128 in whole sublanes"),
    ("tpu", KERNEL_SHAPE, KERNEL_CHUNK, "pallas-compiled", ""),
], ids=["off_the_tpu", "short_chunk", "narrow_state", "ragged_lanes", "ragged_sublanes", "on_the_tpu_inside_the_contract"])
def test_the_backend_and_the_shapes_alone_choose_the_path(monkeypatch, path_lines, backend, shape, chunk, path, why):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    args, _ = _operands(0, *shape)
    chosen, why_not = ssm.scan_path(args[0], args[3], chunk)
    assert chosen == path and why in why_not and bool(why_not) == (path == "xla-reference")
    if path == "xla-reference":
        # the einsums are traced, and the trace says so
        assert jax.eval_shape(lambda *a: ssm.ssm_scan(*a, chunk=chunk), *args).shape == args[0].shape
        (line,) = path_lines
        assert f"attention path: {path}" in line and "ssm_scan" in line and line.endswith(why_not + ")")
        # asked for by name (``interpret``), the kernels refuse what is outside their contract
        if backend == "tpu":
            with pytest.raises(ValueError, match="outside their contract"):
                jax.eval_shape(lambda *a: ssm._scan(*a, chunk, False, True), *args[:5])


def test_the_kernel_path_says_so(path_lines):
    args, _ = _operands(0, *KERNEL_SHAPE)
    jax.eval_shape(lambda *a: ssm._scan(*a, KERNEL_CHUNK, False, True), *args[:5])
    assert path_lines == ["attention path: pallas-interpret (q=(1, 256, 4, 64) float32 causal=True; ssm_scan groups=2 state=128 chunk=128)"]


def _forgetful(carry):
    def forgetful(ends, decay, first, reverse=False):
        starts, last = carry(ends, decay, first, reverse)
        return jnp.zeros_like(starts), last
    return forgetful


def _rounded(decays):
    return lambda *a: jax.lax.reduce_precision(decays(*a), exponent_bits=8, mantissa_bits=7)


@pytest.mark.parametrize("seam,fault", [("_carry", _forgetful), ("_log_decays", _rounded)], ids=["no_carried_state", "bfloat16_decay"])
def test_the_seams_the_benchmarks_controls_swap_bite_on_the_kernel_path(monkeypatch, seam, fault):
    """``nemotron3_super_tp4_ep64_l11_reference.py``'s ``faults`` swaps
    ``ssm._carry`` and ``ssm._log_decays`` by module attribute: the kernel
    path calls both, forward and backward, so each fault moves it as it
    moves the XLA path."""
    args, g = _operands(7, *KERNEL_SHAPE)

    def read(interpret):
        def loss(x, dt):
            y, _ = ssm._scan(x, dt, *args[2:5], KERNEL_CHUNK, False, interpret)
            return jnp.sum(y * g), y
        (_, y), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(*args[:2])
        return (y, *grads)

    sound = {path: read(interpret) for path, interpret in PATHS.items()}
    monkeypatch.setattr(ssm, seam, fault(getattr(ssm, seam)))
    faulty = {path: read(interpret) for path, interpret in PATHS.items()}
    for i, name in enumerate(("y", "dx", "ddt")):
        moved = {path: _off(faulty[path][i], sound[path][i]) for path in PATHS}
        assert min(moved.values()) > 1e-3, (name, moved)
        assert _off(faulty["kernels"][i], faulty["xla"][i]) < 1e-2 * min(moved.values()), (name, moved)


@pytest.mark.parametrize("operand", range(3), ids=["x", "taps", "bias"])
def test_conv_matches_the_positionwise_sum_forward_and_backward(operand):
    ks = jax.random.split(jax.random.key(0), 4)
    x, w, bias = jax.random.normal(ks[0], (2, 11, 6)), jax.random.normal(ks[1], (4, 6)), jax.random.normal(ks[2], (6,))
    g = jax.random.normal(ks[3], x.shape)
    np.testing.assert_allclose(ssm.causal_conv(x, w, bias), ssm.causal_conv_reference(x, w, bias), rtol=1e-5, atol=1e-5)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.causal_conv(*a) * g), argnums=operand))(x, w, bias)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.causal_conv_reference(*a) * g), argnums=operand))(x, w, bias)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_conv_sees_nothing_ahead_and_nothing_of_another_sequence():
    x = jnp.zeros((2, 8, 1)).at[0, 5, 0].set(1.0)
    y = ssm.causal_conv(x, jnp.arange(1.0, 5.0)[:, None], jnp.zeros((1,)))
    np.testing.assert_array_equal(np.asarray(y[0, :, 0]), [0, 0, 0, 0, 0, 4, 3, 2])
    np.testing.assert_array_equal(np.asarray(y[1]), 0)


@pytest.mark.parametrize("operand", range(3), ids=["y", "gate", "gain"])
def test_gated_group_norm_matches_the_groupwise_form(operand):
    ks = jax.random.split(jax.random.key(0), 4)
    y, z, gain = jax.random.normal(ks[0], (2, 5, 12)), jax.random.normal(ks[1], (2, 5, 12)), jax.random.normal(ks[2], (12,))
    g = jax.random.normal(ks[3], y.shape)
    got = ssm.gated_group_norm(y, z, gain, 3, 1e-5)
    np.testing.assert_allclose(got, ssm.gated_group_norm_reference(y, z, gain, 3, 1e-5), rtol=1e-5, atol=1e-5)
    # a group's norm reads its own channels alone
    moved = ssm.gated_group_norm(y.at[..., 8:].multiply(3.0), z, gain, 3, 1e-5)
    np.testing.assert_allclose(moved[..., :8], got[..., :8], rtol=1e-6, atol=1e-6)
    got = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.gated_group_norm(*a, 3, 1e-5) * g), argnums=operand))(y, z, gain)
    want = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.gated_group_norm_reference(*a, 3, 1e-5) * g), argnums=operand))(y, z, gain)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
