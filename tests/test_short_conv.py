"""``ops/short_conv``: a short-convolution chain (conv -> silu -> l2norm a
head) by the Pallas kernel pair (``ops/short_conv_kernels.py`` under the
interpreter) against the XLA chain of ``models/linear_attention.py`` as it
stands and against the convolution a position at a time in float32, the
output and EVERY gradient; the edges (a batch row's start, the first K - 1
positions, a head of zeros, more blocks than one either way); which path a
call takes."""

import jax
import jax.numpy as jnp
import pytest

from elasticdl_tpu.models import linear_attention as la
from elasticdl_tpu.ops import short_conv as sc
from elasticdl_tpu.ops import short_conv_kernels as kernels
from elasticdl_tpu.ops import ssm

TAPS = 4


def _operands(seed: int = 0, *, b=2, length=48, channels=512, dtype=jnp.float32, taps=TAPS):
    """``(t, taps, the weights of the loss's sum)``, seeded: the taps in the
    init's range, uniform(+- K^-1/2)."""
    ks = jax.random.split(jax.random.key(seed), 3)
    t = jax.random.normal(ks[0], (b, length, channels)).astype(dtype)
    w = jax.random.uniform(ks[1], (taps, channels), jnp.float32, -taps ** -0.5, taps ** -0.5)
    return t, w, jax.random.normal(ks[2], (b, length, channels))


def _reference(t, w, head_dim):
    """The chain a position at a time, float32 throughout."""
    y = ssm.causal_conv_reference(t, w, jnp.zeros((t.shape[-1],)))
    y = y / (1.0 + jnp.exp(-y))
    if head_dim is None:
        return y
    by_head = y.reshape(*y.shape[:-1], -1, head_dim)
    return (by_head / jnp.sqrt(jnp.sum(by_head * by_head, axis=-1, keepdims=True) + la.L2_EPS)).reshape(y.shape)


def _xla_chain(t, w, head_dim):
    return la._short_conv(t, w) if head_dim is None else la._short_conv_l2(t, w, head_dim)


def _kernels(t, w, head_dim):
    return sc.short_conv(t, w, head_dim, interpret=True)


def _read(chain, t, w, weigh, head_dim):
    """``(y, dt, dtaps)`` of ``sum(chain(t, w) * weigh)``: ONE program."""
    def loss(t, w):
        y = chain(t, w, head_dim)
        return jnp.sum(y.astype(jnp.float32) * weigh), y

    (_, y), (dt, dw) = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(t, w)
    return y, dt, dw


def _close(got, want, rel, what=""):
    assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got.astype(jnp.float32)))), what
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want.astype(jnp.float32)))) <= rel * float(jnp.max(jnp.abs(want.astype(jnp.float32)))), what


@pytest.fixture
def small_blocks(monkeypatch):
    """A grid step of 16 positions by 256 channels: [2, 48, 512] is three
    blocks of rows (a first, a middle and a last one) by two of lanes, a
    sequence."""
    monkeypatch.setattr(kernels, "_ROWS", 16)
    monkeypatch.setattr(kernels, "_COLS", 256)


@pytest.mark.parametrize("head_dim", [128, None], ids=["l2norm_a_head", "no_norm"])
@pytest.mark.parametrize("dtype,of_xla,of_f32", [(jnp.float32, 1e-5, 1e-5), (jnp.bfloat16, 2e-2, 6e-3)], ids=["float32", "bfloat16"])
def test_the_kernel_pair_is_the_xla_chain_and_the_float32_reference_forward_and_in_every_gradient(small_blocks, dtype, of_xla, of_f32, head_dim):
    """bfloat16 operands: the kernels round ONCE (2^-8 of a value), the XLA
    chain after the convolution, after the silu and after the norm."""
    t, w, weigh = _operands(dtype=dtype)
    assert "pallas_call" in str(jax.make_jaxpr(lambda t, w: _kernels(t, w, head_dim))(t, w))
    got = _read(_kernels, t, w, weigh, head_dim)
    assert got[0].dtype == got[1].dtype == dtype and got[2].dtype == jnp.float32
    for want, rel in ((_read(_xla_chain, t, w, weigh, head_dim), of_xla), (_read(_reference, t.astype(jnp.float32), w, weigh, head_dim), of_f32)):
        for name, a, b in zip(("y", "dt", "dtaps"), got, want):
            _close(a, b, rel, name)


@pytest.mark.parametrize("head_dim", [128, None], ids=["l2norm_a_head", "no_norm"])
def test_no_row_of_one_sequence_reaches_the_next_and_the_first_positions_see_zeros_before_them(small_blocks, head_dim):
    """A batch of two is two sequences: the second row's output and gradients
    are what it gives ALONE, whatever the first row ends in; and the first K
    - 1 positions are the taps' last ones on the positions there are."""
    t, w, weigh = _operands(3)
    t = t.at[0, -TAPS:].set(1e3)  # what a leak over the batch row's start would carry
    both, alone = _read(_kernels, t, w, weigh, head_dim), _read(_kernels, t[1:], w, weigh[1:], head_dim)
    for name, a, b in zip(("y", "dt"), both, alone):
        _close(a[1:], b, 1e-6, name)
    first = _reference(t[1:, :TAPS - 1], w, head_dim)  # the sequence cut after K - 1 positions: nothing before them
    _close(both[0][1:, :TAPS - 1], first, 1e-5)
    assert float(jnp.max(jnp.abs(first))) > 0


def test_a_head_whose_channels_are_all_zeros_is_normed_by_the_epsilon_alone(small_blocks):
    """``x rsqrt(sum(x^2) + 1e-6)`` at x = 0: the output 0, the gradient
    ``g sigmoid'(0) 1e3`` through the taps, finite, the reference's."""
    t, w, weigh = _operands(5)
    t = t.at[:, :, 128:256].set(0.0)
    got, want = _read(_kernels, t, w, weigh, 128), _read(_reference, t, w, weigh, 128)
    assert float(jnp.max(jnp.abs(got[0][:, :, 128:256]))) == 0.0
    assert float(jnp.max(jnp.abs(want[1][:, :, 128:256]))) > 10 * float(jnp.max(jnp.abs(want[1][:, :, :128])))
    for name, a, b in zip(("y", "dt", "dtaps"), got, want):
        _close(a, b, 1e-5, name)


def test_whole_blocks_of_the_real_size_and_a_wider_head_are_the_reference():
    """The module's own block (no patch): 1024 positions are two blocks of 512
    rows worked through in tiles of 128, a head of 256 channels two lane
    tiles' sum."""
    t, w, weigh = _operands(7, b=1, length=1024, channels=256)
    for name, a, b in zip(("y", "dt", "dtaps"), _read(_kernels, t, w, weigh, 256), _read(_xla_chain, t, w, weigh, 256)):
        _close(a, b, 2e-5, name)  # dtaps sums 1024 positions


@pytest.mark.parametrize("backend,shape,head_dim,taps,path,why", [
    ("cpu", (2, 48, 512), 128, 4, "xla-reference", "backend=cpu"),
    ("tpu", (2, 48, 512), 128, 4, "pallas-compiled", ""),
    ("tpu", (2, 48, 512), None, 4, "pallas-compiled", ""),
    ("tpu", (2, 48, 200), None, 4, "xla-reference", "C = 200 is not whole multiples of 128"),
    ("tpu", (2, 48, 512), 64, 4, "xla-reference", "a head of 64 is not whole multiples of 128 that divide C = 512"),
    ("tpu", (2, 48, 512), 384, 4, "xla-reference", "a head of 384 is not whole multiples of 128 that divide C = 512"),
    ("tpu", (2, 100, 512), 128, 4, "xla-reference", "L = 100 is not whole multiples of 16"),
    ("tpu", (2, 48, 512), 128, 18, "xla-reference", "K - 1 = 17 reaches past a halo of 16"),
    ("tpu", (48, 512), 128, 4, "xla-reference", "t (48, 512) is not [B, L, C]"),
], ids=["off_the_tpu", "on_the_tpu_inside_the_contract", "no_norm", "channels_not_whole_lanes", "narrow_head", "head_that_does_not_divide",
        "ragged_length", "taps_past_the_halo", "no_batch_axis"])
def test_the_backend_and_the_shapes_alone_choose_the_conv_path(monkeypatch, backend, shape, head_dim, taps, path, why):
    """No flag: ``conv_path`` reads the backend and the operands' shapes and
    names why not the kernels; asked for by name (``interpret``, or the op
    itself), the kernels refuse what is outside their contract."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    t, w = jax.ShapeDtypeStruct(shape, jnp.bfloat16), jax.ShapeDtypeStruct((taps, shape[-1]), jnp.float32)
    assert sc.conv_path(t, w, head_dim) == (path, why)
    if why and backend == "tpu":
        for asked in (lambda: sc.conv_path(t, w, head_dim, True), lambda: sc.short_conv(t, w, head_dim, interpret=True)):
            with pytest.raises(ValueError, match="outside their contract"):
                asked()
    else:
        assert sc.conv_path(t, w, head_dim, True) == ("pallas-interpret", "") and sc.conv_path(t, w, head_dim, False) == ("pallas-compiled", "")
