"""Pallas flash attention vs the XLA reference oracle — forward and VJP.

Runs the REAL kernel in pallas interpret mode on the CPU harness (one code
path everywhere; the chip runs the same kernel compiled).  The oracle is
``ops.ring_attention.attention_reference`` — the numerics standard the ring
path is also tested against.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops.flash_attention import flash_attention
from elasticdl_tpu.ops.ring_attention import attention_reference


def _qkv(dtype, b=2, l=256, h=2, d=64, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    shape = (b, l, h, d)
    return tuple(
        (jax.random.normal(k, shape) * 0.5).astype(dtype) for k in ks
    )


# (heads, head width): two 64-wide heads share the 128 lanes of a block (one
# head alone is padded with a zero head, four are two lane groups); a 128-wide
# head fills its block.  The second head's lanes are checked like the first's.
HEADS = [(1, 64), (2, 64), (4, 64), (1, 128), (2, 128)]
heads = pytest.mark.parametrize("h,d", HEADS, ids=[f"{h}x{d}" for h, d in HEADS])


@heads
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference_f32(causal, h, d):
    q, k, v = _qkv(jnp.float32, h=h, d=d)
    out = flash_attention(q, k, v, causal)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference_bf16(causal):
    q, k, v = _qkv(jnp.bfloat16)
    out = flash_attention(q, k, v, causal)
    ref = attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        causal=causal,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=3e-2, rtol=3e-2
    )


@heads
@pytest.mark.parametrize("causal", [False, True])
def test_vjp_matches_reference(causal, h, d):
    q, k, v = _qkv(jnp.float32, b=1, l=128, h=h, d=d, seed=3)
    cot = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal), cot)

    def loss_ref(q, k, v):
        return jnp.vdot(attention_reference(q, k, v, causal=causal), cot)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


def test_shape_contract_fails_loud():
    q, k, v = _qkv(jnp.float32, l=200)  # not a TQ multiple
    with pytest.raises(ValueError, match="flash_attention supports"):
        flash_attention(q, k, v, True)


# -- the block-pair walk's edges (PR 25) ------------------------------------
# L = 1024 with d = 64 is the benchmark cell's shape (one block, two forward
# and four backward sub-tiles); 384 is one block with a short last backward
# sub-tile; 128 is a single sub-tile; 1152 is two blocks of 640 over a sequence
# zero-padded to 1280, so partial results cross grid steps, the pair above the
# diagonal is skipped and the last block has a tail of padding.
LENGTHS = [128, 384, 1024, 1152]


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", LENGTHS)
@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"]
)
def test_forward_matches_reference_at_length(dtype, tol, l, causal):
    q, k, v = _qkv(dtype, b=1, l=l, h=2, d=64, seed=l)
    out = flash_attention(q, k, v, causal)
    ref = attention_reference(*_f32(q, k, v), causal=causal)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", LENGTHS)
@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 5e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"]
)
def test_vjp_matches_reference_at_length(dtype, tol, l, causal):
    q, k, v = _qkv(dtype, b=1, l=l, h=2, d=64, seed=l + 1)
    cot = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)

    def loss_flash(q, k, v):
        return jnp.vdot(flash_attention(q, k, v, causal).astype(jnp.float32), cot)

    def loss_ref(q, k, v):
        return jnp.vdot(attention_reference(q, k, v, causal=causal), cot)

    g_flash = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(*_f32(q, k, v))
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr),
            atol=tol, rtol=tol, err_msg=f"d{name}",
        )


@pytest.mark.parametrize("h,d", [(1, 64), (2, 64), (1, 128)], ids=["1x64", "2x64", "1x128"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block,l", [(128, 512), (256, 512), (256, 384)])
def test_small_blocks_carry_state_across_pairs(monkeypatch, block, l, causal, h, d):
    """Many pairs a head (blocks of 128 / 256): first/last visited pair, the
    skipped ones and the scratch carry, forward and VJP; L = 384 in blocks of
    256 leaves 128 positions of padding in the last block.  With two heads
    to a block each head carries its own state across the pairs."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", block)
    monkeypatch.setattr(fa, "_T_FWD", 128)
    monkeypatch.setattr(fa, "_T_BWD", 128)
    q, k, v = _qkv(jnp.float32, b=1, l=l, h=h, d=d, seed=block)
    cot = jax.random.normal(jax.random.key(2), q.shape, jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.vdot(attn(q, k, v), cot)

    flash = lambda q, k, v: fa.flash_attention(q, k, v, causal)  # noqa: E731
    ref = lambda q, k, v: attention_reference(q, k, v, causal=causal)  # noqa: E731
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5, rtol=2e-5)
    g_flash = jax.jit(jax.grad(loss(flash), argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.jit(jax.grad(loss(ref), argnums=(0, 1, 2)))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5, err_msg=f"d{name}")


@heads
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", [384, 1152])
def test_saved_lse_is_logsumexp_of_the_scores(l, causal, h, d):
    """dQ and dK/dV rebuild the probabilities from this residual: one row a
    head, [B*H, 1, L] (a head alone in its lane group has a zero head's row
    behind its own)."""
    from elasticdl_tpu.ops.flash_attention import _fwd_impl

    q, k, v = _qkv(jnp.float32, b=1, l=l, h=h, d=d, seed=7)
    lse = _fwd_impl(q, k, v, causal)[1][4][:h, :, :l]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * d**-0.5
    if causal:
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
    want = jax.nn.logsumexp(scores, axis=-1).reshape(h, 1, l)
    np.testing.assert_allclose(lse, want, atol=2e-5, rtol=2e-5)


def test_a_width_that_does_not_divide_the_lanes_takes_the_reference_path(monkeypatch):
    """d = 80 is outside the contract: on a TPU ``_local_attention`` says so
    in the path line and computes the XLA reference; called directly the
    kernel refuses."""
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = _qkv(jnp.float32, b=1, l=128, h=2, d=80)
    out = ring_attention._local_attention(q, k, v, True)
    (line,) = lines
    assert "attention path: xla-reference" in line
    assert "head_dim = 80 does not divide the 128 lanes" in line
    np.testing.assert_array_equal(out, attention_reference(q, k, v, causal=True))
    with pytest.raises(ValueError, match="head_dim = 80 does not divide"):
        flash_attention(q, k, v, True)


@pytest.mark.parametrize(
    "n_q,n_k,causal,want",
    [
        (8, 8, True, (36, 64)),     # L = 1024 in 128-wide tiles
        (8, 8, False, (64, 64)),
        (1, 1, True, (1, 1)),
        (2, 2, True, (3, 4)),       # L = 1024 in the forward's 512-row tiles
        (8, 2, True, (12, 16)),     # key tiles four query tiles wide
    ],
)
def test_key_tiles_counts_what_the_mask_leaves(n_q, n_k, causal, want):
    from elasticdl_tpu.ops.flash_attention import key_tiles

    assert key_tiles(n_q, n_k, causal) == want


@pytest.mark.parametrize("causal", [False, True])
def test_attention_path_line_reports_key_tiles(monkeypatch, causal):
    """The line's head is what benchmark/run.py and chip_smoke.py match."""
    import re

    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    q, k, v = _qkv(jnp.bfloat16, b=1, l=1024, h=1, d=64)
    flash_attention(q, k, v, causal)
    (line,) = lines
    assert re.search(r"attention path: ([\w-]+)", line).group(1) == "pallas-interpret"
    visited, total = map(int, re.search(r"key_tiles=(\d+)/(\d+)", line).groups())
    assert (visited < total) == causal
    assert "key_tiles=3/4 fwd, 10/16 bwd" in line or not causal
    assert line.endswith("heads_per_block=2)")


# -- a score of two products (PR 33) -----------------------------------------
# Latent attention: a head's score is a 128-wide product plus a rotary product
# whose KEY is one head shared by all, its value 128 wide.  The oracle is an
# explicit masked softmax over the concatenated 128 + R wide queries and keys,
# the shared key copied to every head.


def _rotary_inputs(dtype, b=1, l=256, h=2, r=64, seed=0):
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = ((jax.random.normal(ks[i], (b, l, h, 128)) * 0.5).astype(dtype) for i in range(3))
    q_rot = (jax.random.normal(ks[3], (b, l, h, r)) * 0.5).astype(dtype)
    k_rot = (jax.random.normal(ks[4], (b, l, r)) * 0.5).astype(dtype)
    return q, k, v, q_rot, k_rot


def _explicit_masked_softmax(q, k, v, q_rot, k_rot, causal):
    h = q.shape[2]
    q_full = jnp.concatenate([q, q_rot], -1)
    k_full = jnp.concatenate([k, jnp.repeat(k_rot[:, :, None, :], h, axis=2)], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q_full, k_full) / np.sqrt(q_full.shape[-1])
    if causal:
        l = scores.shape[-1]
        scores = jnp.where(jnp.tril(jnp.ones((l, l), bool)), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _rotary_grads(attn, args, cot):
    return jax.jit(jax.grad(lambda *a: jnp.vdot(attn(*a).astype(jnp.float32), cot), argnums=(0, 1, 2, 3, 4)))(*args)


ROTARY = [(2, 64), (4, 64), (4, 32), (1, 128)]
rotary = pytest.mark.parametrize("h,r", ROTARY, ids=[f"{h}x128+{r}" for h, r in ROTARY])


@rotary
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 5e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"])
def test_rotary_part_forward_and_gradients_match_an_explicit_masked_softmax(dtype, tol, causal, h, r):
    """Forward, dq, dk, dv, dq_rot and dk_rot — the shared key's gradient is
    the SUM over the heads that share it."""
    args = _rotary_inputs(dtype, h=h, r=r, seed=h + r)
    f32 = tuple(x.astype(jnp.float32) for x in args)
    cot = jax.random.normal(jax.random.key(9), args[0].shape, jnp.float32)
    flash = lambda q, k, v, qr, kr: flash_attention(q, k, v, causal, qr, kr)  # noqa: E731
    ref = lambda *a: _explicit_masked_softmax(*a, causal)  # noqa: E731
    np.testing.assert_allclose(np.asarray(flash(*args), np.float32), np.asarray(ref(*f32)), atol=tol, rtol=tol)
    for got, want, name in zip(_rotary_grads(flash, args, cot), _rotary_grads(ref, f32, cot),
                               ("dq", "dk", "dv", "dq_rot", "dk_rot")):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block,l", [(128, 512), (256, 384)])
def test_rotary_part_carries_state_across_pairs(monkeypatch, block, l, causal):
    """Many pairs a head, the head a grid axis BEFORE the key blocks: the
    first / last visited pair, the skipped ones, the scratch carry and the
    two heads that share a block of q_rot; L = 384 in blocks of 256 leaves
    padding in the last block."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", block)
    monkeypatch.setattr(fa, "_T_FWD", 128)
    monkeypatch.setattr(fa, "_T_BWD", 128)
    args = _rotary_inputs(jnp.float32, b=2, l=l, h=4, r=64, seed=block)
    cot = jax.random.normal(jax.random.key(2), args[0].shape, jnp.float32)
    flash = lambda q, k, v, qr, kr: fa.flash_attention(q, k, v, causal, qr, kr)  # noqa: E731
    ref = lambda *a: _explicit_masked_softmax(*a, causal)  # noqa: E731
    np.testing.assert_allclose(flash(*args), ref(*args), atol=2e-5, rtol=2e-5)
    for got, want, name in zip(_rotary_grads(flash, args, cot), _rotary_grads(ref, args, cot),
                               ("dq", "dk", "dv", "dq_rot", "dk_rot")):
        np.testing.assert_allclose(got, want, atol=5e-5, rtol=5e-5, err_msg=name)


def test_rotary_part_is_the_dispatchers_reference_too():
    """``attention_reference`` with the rotary part (the CPU's, the
    rehearsal's and the ring's path) is the explicit softmax, scale
    (128 + R)^-0.5."""
    args = _rotary_inputs(jnp.float32, h=2, r=64, seed=5)
    for causal in (False, True):
        np.testing.assert_allclose(
            attention_reference(*args[:3], causal, *args[3:]), _explicit_masked_softmax(*args, causal),
            atol=2e-6, rtol=2e-6,
        )


@pytest.mark.parametrize(
    "d,h,r,why",
    [(64, 2, 64, "a rotary part needs head_dim = 128"), (128, 3, 64, "H whole groups of 128 // R heads"),
     (128, 2, 48, "R a divisor of 128")],
)
def test_rotary_shapes_outside_the_contract_take_the_reference_path(monkeypatch, d, h, r, why):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ks = jax.random.split(jax.random.key(0), 5)
    q, k, v = (jax.random.normal(ks[i], (1, 128, h, d)) for i in range(3))
    q_rot, k_rot = jax.random.normal(ks[3], (1, 128, h, r)), jax.random.normal(ks[4], (1, 128, r))
    out = ring_attention._local_attention(q, k, v, True, q_rot, k_rot)
    (line,) = lines
    assert "attention path: xla-reference" in line and why in line and line.endswith(f"rotary={r})")
    np.testing.assert_array_equal(out, attention_reference(q, k, v, True, q_rot, k_rot))
    with pytest.raises(ValueError, match="a rotary part needs"):
        flash_attention(q, k, v, True, q_rot, k_rot)


def test_attention_path_line_names_the_rotary_width(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    flash_attention(*_rotary_inputs(jnp.bfloat16, l=128)[:3], True, *_rotary_inputs(jnp.bfloat16, l=128)[3:])
    (line,) = lines
    assert "attention path: pallas-interpret" in line and line.endswith("heads_per_block=1 rotary=64)")
