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


def _output_and_gradients(attn, cot):
    """``operands -> (attn's output, the gradients of <output, cot> in each)`` as ONE
    program: the interpreter's forward is traced and compiled once for both,
    where a call of its own and a ``jax.grad`` beside it made it twice."""
    def both(*operands):
        def loss(*operands):
            out = attn(*operands)
            return jnp.vdot(out.astype(jnp.float32), cot), out

        (_, out), grads = jax.value_and_grad(loss, argnums=tuple(range(len(operands))), has_aux=True)(*operands)
        return out, grads

    return jax.jit(both)


@pytest.mark.parametrize("h,d", [(1, 64), (2, 64), (1, 128)], ids=["1x64", "2x64", "1x128"])
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block,l", [(128, 384), (256, 512), (256, 384)])
def test_small_blocks_carry_state_across_pairs(monkeypatch, block, l, causal, h, d):
    """Many pairs a head (blocks of 128 / 256): first/last visited pair, the
    skipped ones and the scratch carry, forward and VJP; L = 384 in blocks of
    256 leaves 128 positions of padding in the last block.  With two heads
    to a block each head carries its own state across the pairs.  THREE
    blocks a side is the least that has a pair that is neither a row's first
    nor its last (384 in blocks of 128); two whole blocks of 256 are 512."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", block)
    monkeypatch.setattr(fa, "_T_FWD", 128)
    monkeypatch.setattr(fa, "_T_BWD", 128)
    q, k, v = _qkv(jnp.float32, b=1, l=l, h=h, d=d, seed=block)
    cot = jax.random.normal(jax.random.key(2), q.shape, jnp.float32)
    out, g_flash = _output_and_gradients(lambda q, k, v: fa.flash_attention(q, k, v, causal), cot)(q, k, v)
    want, g_ref = _output_and_gradients(lambda q, k, v: attention_reference(q, k, v, causal=causal), cot)(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
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
    out, grads = _output_and_gradients(lambda q, k, v, qr, kr: flash_attention(q, k, v, causal, qr, kr), cot)(*args)
    ref, ref_grads = _output_and_gradients(lambda *a: _explicit_masked_softmax(*a, causal), cot)(*f32)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref), atol=tol, rtol=tol)
    for got, want, name in zip(grads, ref_grads, ("dq", "dk", "dv", "dq_rot", "dk_rot")):
        assert got.shape == want.shape, name
        np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=tol, rtol=tol, err_msg=name)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block,l", [(128, 384), (256, 384)])
def test_rotary_part_carries_state_across_pairs(monkeypatch, block, l, causal):
    """Many pairs a head, the head a grid axis BEFORE the key blocks: the
    first / last visited pair, the skipped ones, the scratch carry and the
    two heads that share a block of q_rot; L = 384 in blocks of 256 leaves
    padding in the last block, and in blocks of 128 it is the three blocks
    a side that a pair neither first nor last of its row wants."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", block)
    monkeypatch.setattr(fa, "_T_FWD", 128)
    monkeypatch.setattr(fa, "_T_BWD", 128)
    args = _rotary_inputs(jnp.float32, b=2, l=l, h=4, r=64, seed=block)
    cot = jax.random.normal(jax.random.key(2), args[0].shape, jnp.float32)
    out, grads = _output_and_gradients(lambda q, k, v, qr, kr: fa.flash_attention(q, k, v, causal, qr, kr), cot)(*args)
    want, want_grads = _output_and_gradients(lambda *a: _explicit_masked_softmax(*a, causal), cot)(*args)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, name in zip(grads, want_grads, ("dq", "dk", "dv", "dq_rot", "dk_rot")):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5, err_msg=name)


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


# -- a window that moves with the query (PR 56) -------------------------------
# Position p sees the keys p - W < j <= p.  The kernels skip every (block,
# block) pair wholly outside the window; the pair a whole window back is the
# far edge, masked by the complement of the diagonal's mask.


def _window_case(monkeypatch, block, t_fwd, t_bwd):
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", block)
    monkeypatch.setattr(fa, "_T_FWD", t_fwd)
    monkeypatch.setattr(fa, "_T_BWD", t_bwd)
    return fa


@pytest.mark.parametrize(
    "block,l,window,h,d",
    [(128, 384, 128, 2, 64), (128, 640, 384, 1, 128)],
    ids=["one_block_back_two_heads_a_group", "three_of_five_blocks"],
)
def test_a_window_over_several_blocks_is_the_masked_softmax_and_one_key_either_way_is_not(monkeypatch, block, l, window, h, d):
    """Forward and VJP under a window SHORTER than the sequence and several
    blocks long, against the explicit mask: the first visited pair is the far
    edge (whose last row sees nothing: no NaN), the skipped pairs fetch
    nothing, the state is carried across the visited ones; sub-tiles smaller
    than a block so that a far-edge pair has both an unmasked and a masked
    piece.  A window of W - 1 or W + 1 keys is a different answer.  (A window
    of one block wants three blocks of sequence, no more: the last row of
    blocks then skips a pair, visits the far edge and ends on the diagonal.)"""
    fa = _window_case(monkeypatch, block, block // 2, block // 4)
    q, k, v = _qkv(jnp.float32, b=1, l=l, h=h, d=d, seed=window)
    cot = jax.random.normal(jax.random.key(2), q.shape, jnp.float32)
    ref = lambda w: lambda q, k, v: attention_reference(q, k, v, causal=True, window=w)  # noqa: E731
    out, g_flash = _output_and_gradients(lambda q, k, v: fa.flash_attention(q, k, v, True, window=window), cot)(q, k, v)

    @jax.jit  # the three explicit masks in ONE program
    def references(q, k, v):
        return [_output_and_gradients(ref(w), cot)(q, k, v) for w in (window, window - 1, window + 1)]

    (want, g_ref), (fewer, _), (more, g_more) = references(q, k, v)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for other in (fewer, more):
        assert float(jnp.max(jnp.abs(out - other))) > 1e-3
    for gf, gr, gm, name in zip(g_flash, g_ref, g_more, "qkv"):
        np.testing.assert_allclose(gf, gr, atol=5e-5, rtol=5e-5, err_msg=f"d{name}")
        assert float(jnp.max(jnp.abs(gf - gm))) > 1e-3, name


def test_a_window_visits_no_pair_wholly_outside_it(monkeypatch):
    """The plan at the cell's shape: L = 8192 in 8 blocks of 1024 under a
    window of two blocks visits 21 (block, block) pairs of 64 a pass, the
    causal rule alone 36; by sub-tiles the forward multiplies 17.5 blocks'
    worth of pairs and each backward kernel 15.75, where the window holds
    14.0 and full causal attention multiplies 24 / 22."""
    from elasticdl_tpu.ops import flash_attention as fa

    windowed, causal = fa._Plan((1, 8192, 32, 128), True, 0, 2048), fa._Plan((1, 8192, 32, 128), True)
    assert (windowed.n, windowed.rows, windowed.far, causal.far) == (8, 1024, 2, 0)
    visited = lambda plan: sum(  # noqa: E731
        1 for i in range(8) for j in range(8) if j <= i and (not plan.far or i - j <= plan.far))
    assert visited(windowed) == 21 and visited(causal) == 36
    blocks = lambda plan, *a: plan.pairs_computed(*a) / 1024**2  # noqa: E731
    assert (blocks(windowed, fa._T_FWD), blocks(windowed, fa._T_BWD), blocks(windowed, fa._T_BWD, False)) == (17.5, 15.75, 15.75)
    assert (blocks(causal, fa._T_FWD), blocks(causal, fa._T_BWD)) == (34.0, 33.0)
    assert fa.window_pairs_computed(8192, 2048) == (17.5 + 2 * 15.75) / 3 * 1024**2
    needed = 2048 * 2049 // 2 + 6144 * 2048
    assert needed / 1024**2 == pytest.approx(14.0, abs=1e-3) and needed <= 15.75 * 1024**2
    assert fa.key_tiles(16, 16, True, 4) == (70, 256) and fa.key_tiles(16, 16, True) == (136, 256)
    # outside the contract (not whole blocks of the plan) the XLA path multiplies every pair
    assert fa.window_pairs_computed(8192, 1000) == 8192 * 8192


def test_a_window_outside_the_contract_takes_the_reference_path_and_one_that_hides_nothing_is_dropped(monkeypatch):
    from elasticdl_tpu.ops import flash_attention as fa
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(fa, "_use_interpret", lambda: True)
    q, k, v = _qkv(jnp.float32, b=1, l=256, h=1, d=128)
    out = ring_attention._local_attention(q, k, v, True, window=100)  # ONE block of 256: a window of 100 is not whole blocks
    assert "attention path: xla-reference" in lines[-1] and "a window of 100 is not whole blocks of 256 rows" in lines[-1]
    assert lines[-1].endswith("window=100)")
    np.testing.assert_array_equal(out, attention_reference(q, k, v, causal=True, window=100))
    with pytest.raises(ValueError, match="a window of 100 is not whole blocks"):
        flash_attention(q, k, v, True, window=100)
    with pytest.raises(ValueError, match="a window is a causal call's"):
        flash_attention(q, k, v, False, window=128)
    # at least the sequence long: the full call, announced as one
    same = ring_attention._local_attention(q, k, v, True, window=256)
    assert "window" not in lines[-1] and "pallas" in lines[-1]
    np.testing.assert_array_equal(same, flash_attention(q, k, v, True))


def test_attention_path_line_names_the_window(monkeypatch):
    from elasticdl_tpu.ops import ring_attention

    fa = _window_case(monkeypatch, 128, 64, 32)
    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    q, k, v = _qkv(jnp.bfloat16, b=1, l=512, h=1, d=128)
    fa.flash_attention(q, k, v, True, window=256)
    (line,) = lines
    assert "attention path: pallas-interpret" in line and line.endswith("heads_per_block=1 window=256)")
    assert "key_tiles=30/64 fwd, 108/256 bwd" in line


#: sha256 of the jaxpr (forward AND gradient, the kernels' bodies in it) of the calls the older cells make — causal or not,
#: one block or several, two heads a lane group, a rotary part — and of EVA's at ``evabyte_job``'s shape, whose kernels take
#: ``_visible`` / ``_sub_tiles`` / ``_causal_mask`` from this module.  PINNED in PR 56 at the values its PARENT (3fa6822) gives:
#: the window was threaded through ``_visit``, ``_visible`` and ``_Plan`` and a call without one traces to the byte as it did.
#: The lowered steps ``tests/test_chip_lowering.py`` pins hold no kernel (lowered from the CPU the attention is the XLA
#: reference) and every EVA case here has ONE sub-tile a window: a ``_visible`` that paired EVA's sub-tiles with the positions
#: AFTER them passed all of tier-1 and read 1.3 on the chip's check.  A PR that changes a kernel's body on purpose re-pins.
KERNEL_JAXPR_SHA256 = {
    "flash causal [1, 8192, 16, 128]": "138bae355d569a0e",
    "flash causal [2, 1024, 16, 64]": "8e96fe343a8e22ea",
    "flash causal [1, 4096, 16, 128]": "523c9166f64bb995",
    "flash full [1, 1024, 4, 64]": "f8a1fcd73c9682e4",
    "flash causal [1, 8192, 32, 128] rotary 64": "d0886d638be895a1",
    "flash causal [1, 2048, 8, 128]": "d14fbf11fa3e7420",
    "flash causal [1, 384, 2, 64]": "c327d37e50e8a066",
    "eva [1, 16384, 16, 128] window 2048 chunk 16": "41c3b7b5b2b5960b",
}

_KERNEL_JAXPRS = """
import hashlib, json, re
import jax, jax.numpy as jnp
from elasticdl_tpu.ops import eva_attention as eva_ops, flash_attention as fa
bf, f32 = jnp.bfloat16, jnp.float32
def sha(fn, *shapes):
    text = str(jax.make_jaxpr(fn)(*(jax.ShapeDtypeStruct(s, dt) for s, dt in shapes)))
    return hashlib.sha256(text.encode()).hexdigest()[:16]
def flash(causal, rot):
    def loss(q, k, v, *r):
        return jnp.sum(fa.flash_attention(q, k, v, causal, **(dict(q_rot=r[0], k_rot=r[1]) if rot else {})).astype(f32) ** 2)
    return jax.grad(loss, argnums=tuple(range(5 if rot else 3)))
out = {}
for name in NAMES:
    kind, how, shape, *rot = re.match(r"(\\w+) (\\w+ )?(\\[[^\\]]*\\])(?: rotary (\\d+))?", name).groups()
    shape, rot = tuple(json.loads(shape)), int(rot[0] or 0)
    if kind == "flash":
        b, l, h, _ = shape
        rots = [((b, l, h, rot), bf), ((b, l, rot), bf)] if rot else []
        out[name] = sha(flash(how.strip() == "causal", rot), *[(shape, bf)] * 3, *rots)
    else:
        eva_ops._why_not_kernels = lambda *a: ""
        loss = lambda *a: jnp.sum(eva_ops.eva_attention(*a, window=2048, chunk=16).astype(f32) ** 2)
        out[name] = sha(jax.grad(loss, argnums=(0, 1, 2, 3, 4)), *[(shape, bf)] * 3, *[(shape[2:], f32)] * 2)
print("SHAS", json.dumps(out))
"""


@pytest.fixture(scope="module")
def kernel_jaxprs():
    """Traced in ONE fresh process (nothing runs: abstract operands), so no jit an earlier case cached names anything."""
    import json
    import os
    import re
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = f"NAMES = {sorted(KERNEL_JAXPR_SHA256)!r}\n" + _KERNEL_JAXPRS
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=root, capture_output=True, text=True, timeout=300, env=dict(os.environ, PYTHONPATH=root),
    )
    assert done.returncode == 0, done.stderr[-2000:]
    (shas,) = re.findall(r"^SHAS (.*)$", done.stdout, re.M)
    return json.loads(shas)


@pytest.mark.parametrize("call", sorted(KERNEL_JAXPR_SHA256))
def test_the_older_cells_calls_trace_to_the_pinned_kernels(kernel_jaxprs, call):
    assert kernel_jaxprs[call] == KERNEL_JAXPR_SHA256[call]
