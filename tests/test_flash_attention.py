"""Pallas flash attention vs the XLA reference oracle — forward and VJP.

Runs the REAL kernel in pallas interpret mode on the CPU harness (one code
path everywhere; the chip runs the same kernel compiled).  The oracle is
``ops.ring_attention.attention_reference`` — the numerics standard the ring
path is also tested against.
"""

import functools

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


def _f32(*xs):
    return tuple(x.astype(jnp.float32) for x in xs)


@functools.lru_cache(maxsize=None)
def _kernels_and_reference(causal: bool, h: int, d: int, b: int = 2, l: int = 256, dtype=jnp.float32, seed: int = 0):
    """``((output, gradients) by the kernels, the same by the reference on the float32 operands)`` at one shape: the
    forward case and the VJP case of a shape read ONE compiled pair (the forward was a program of its own, and the VJP
    case compiled it again inside its gradient's: PR 66)."""
    q, k, v = _qkv(dtype, b=b, l=l, h=h, d=d, seed=seed)
    cot = jax.random.normal(jax.random.key(9), q.shape, jnp.float32)
    got = _output_and_gradients(lambda q, k, v: flash_attention(q, k, v, causal), cot)(q, k, v)
    return got, _output_and_gradients(lambda q, k, v: attention_reference(q, k, v, causal=causal), cot)(*_f32(q, k, v))


@heads
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference_f32(causal, h, d):
    (out, _), (ref, _) = _kernels_and_reference(causal, h, d)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@heads
@pytest.mark.parametrize("causal", [False, True])
def test_vjp_matches_reference(causal, h, d):
    (_, g_flash), (_, g_ref) = _kernels_and_reference(causal, h, d)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            gf, gr, atol=5e-5, rtol=5e-5, err_msg=f"d{name}"
        )


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


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", LENGTHS)
@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"]
)
def test_forward_matches_reference_at_length(dtype, tol, l, causal):
    (out, _), (ref, _) = _kernels_and_reference(causal, 2, 64, b=1, l=l, dtype=dtype, seed=l)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("l", LENGTHS)
@pytest.mark.parametrize(
    "dtype,tol", [(jnp.float32, 5e-5), (jnp.bfloat16, 3e-2)], ids=["f32", "bf16"]
)
def test_vjp_matches_reference_at_length(dtype, tol, l, causal):
    (_, g_flash), (_, g_ref) = _kernels_and_reference(causal, 2, 64, b=1, l=l, dtype=dtype, seed=l)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf, np.float32), np.asarray(gr),
            atol=tol, rtol=tol, err_msg=f"d{name}",
        )


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
    assert "steps=1/1 key_tiles=" in line       # a head of one block: one step, one pair
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
    assert "steps=12/9 key_tiles=30/64 fwd, 108/256 bwd" in line      # four rows of w + 1 = 3 steps; rows 0 and 1 hold 1 and 2 pairs


# -- the grid holds the pairs a call's rule can hold (PR 65) --------------------
# A causal call's grid is the triangle folded (``folded_pair``), a window call's ``w + 1`` steps a row block
# (``window_pair``); ``_walk`` is the one place that reads a grid step back into its pair, for the BlockSpecs' index
# maps and for the kernels.  Evaluated here on plain ints, step by step over a plan's own grid.


def _plan_of(fa, n, w, rot=0):
    return fa._Plan((1, 128 * n, 128 // rot if rot else 1, 128), True, rot, 128 * w)


def _steps_of(fa, plan, before):
    """``[(own, other, holds_work, head)]`` of a lane group's grid steps in the order the grid runs them."""
    import itertools

    walk = lambda a, b, hh: tuple(  # noqa: E731
        int(x) for x in fa._walk(a, b, hh, n=plan.n, causal=True, far=plan.far, before=before, rot_heads=plan.rot and plan.heads))
    if plan.rot:
        return [walk(a, b, hh) for a, hh, b in itertools.product(*map(range, plan.grid[1:]))]
    return [walk(a, b, 0) for a, b in itertools.product(*map(range, plan.grid[1:3]))]


WALKS = [(n, w) for n in range(1, 10) for w in range(n)]       # w = 0: no window, the fold


def _runs(keys):
    """``keys`` with consecutive repeats dropped."""
    return [key for k, key in enumerate(keys) if k == 0 or key != keys[k - 1]]


@pytest.mark.parametrize("before", [True, False], ids=["rows_are_queries", "rows_are_keys"])
@pytest.mark.parametrize("n,w", WALKS, ids=[f"{n}_blocks" + f"_window_{w}" * bool(w) for n, w in WALKS])
def test_a_grid_visits_every_pair_its_rule_holds_once_and_in_row_order(monkeypatch, n, w, before):
    """Every (block, block) pair the rule holds is visited exactly once; a row's pairs are consecutive steps in
    ascending order of the paired block (so the carried state, the resident output block and the order of every sum are
    the square grid's); a step that holds no work sits on a live neighbour's pair (nothing is fetched for it) — under a
    window with rows as queries on the row's FIRST pair, whose blocks are so asked for a step early; the live steps are
    what ``_Plan.pairs_computed`` multiplies, counted in blocks, what ``key_tiles`` counts and what the path line says."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", 128)
    plan = _plan_of(fa, n, w)
    assert (plan.n, plan.far) == (n, w)
    assert plan.grid[1:] == ((1, 1) if n == 1 else (n, w + 1) if w else fa.folded_grid(n))
    steps = _steps_of(fa, plan, before)
    live = [(own, other) for own, other, work, _ in steps if work]
    held = [(i, j) if before else (j, i) for i in range(n) for j in range(n) if j <= i and (not w or i - j <= w)]
    assert sorted(live) == sorted(held) and len(set(live)) == len(live)
    assert len(_runs([own for own, _ in live])) == len({own for own, _ in live})     # a row's pairs are consecutive steps
    for own in {own for own, _ in live}:
        others = [other for o, other in live if o == own]
        assert others == sorted(others)
    width = plan.grid[2]
    for k, (own, other, work, _) in enumerate(steps):
        if work:
            continue
        row = steps[k - k % width:k - k % width + width]        # the grid row's steps: its live pairs before and after step k
        nearest = [s[:2] for s in row[:k % width] if s[2]][-1:] + [s[:2] for s in row[k % width + 1:] if s[2]][:1]
        assert (own, other) in nearest, (k, steps)
        if w and before:     # the idle steps of a window's first rows come AHEAD of the row's first live pair
            assert (own, other) == (own, 0) == nearest[0] and own < w
    assert len(live) == plan.pairs_computed(plan.rows, before) // plan.rows ** 2 == fa.key_tiles(n, n, True, w)[0]
    assert plan.steps == (len(steps), len(live))
    assert len(steps) - len(live) == (w * (w + 1) // 2 if w else (n % 2) * (n + 1) // 2 if n > 1 else 0)


@pytest.mark.parametrize("before", [True, False], ids=["rows_are_queries", "rows_are_keys"])
@pytest.mark.parametrize("heads", [2, 4])
@pytest.mark.parametrize("n", range(1, 10))
def test_the_folded_grid_keeps_a_rows_heads_together_where_the_head_comes_before_the_paired_block(monkeypatch, n, heads, before):
    """The rotary order: every head visits every causal pair once, a (row, head)'s pairs are consecutive and ascending
    (ONE slab of carried state), and a row's heads are consecutive — so the group's q_rot / dq_rot / dk_rot block is
    fetched and written once a row, as on the square grid; idle steps (odd ``n``) stay on the last head's last pair."""
    from elasticdl_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_BLOCK", 128)
    plan = _plan_of(fa, n, 0, rot=128 // heads)
    assert plan.grid[1:] == ((1, heads, 1) if n == 1 else (fa.folded_grid(n)[0], heads, n + 1))
    steps = _steps_of(fa, plan, before)
    live = [(own, hh, other) for own, other, work, hh in steps if work]
    held = [(i, j) if before else (j, i) for i in range(n) for j in range(i + 1)]
    assert sorted(live) == sorted((own, hh, other) for own, other in held for hh in range(heads)) and len(set(live)) == len(live)
    runs = _runs([s[:2] for s in live])
    assert len(runs) == n * heads                           # a (row, head)'s pairs in one run
    assert len(_runs([own for own, _ in runs])) == n        # and a row's heads in one
    for own, hh in runs:
        others = [other for o, h, other in live if (o, h) == (own, hh)]
        assert others == sorted(others)
    for k, (own, other, work, hh) in enumerate(steps):
        if not work:
            assert (own, other, hh) == (steps[k - 1][0], steps[k - 1][1], steps[k - 1][3])
    assert plan.steps == (len(steps) // heads, len(live) // heads)


GRIDS = [
    ("causal_a_head_a_lane_group", dict(h=1, d=128)),
    ("causal_two_heads_a_lane_group", dict(h=2, d=64)),
    ("rotary_two_heads_a_group", dict(h=2, d=128, r=64)),
    ("window_of_one_block", dict(h=1, d=128, w=1)),
    ("window_one_block_short_of_the_sequence_two_heads", dict(h=2, d=64, w=-1)),
]


@pytest.mark.parametrize("name,case", GRIDS, ids=[name for name, _ in GRIDS])
@pytest.mark.parametrize("n", [2, 3, 4])
def test_folded_and_window_grids_give_the_references_outputs_and_gradients(monkeypatch, n, name, case):
    """The interpreter over the folded grid and the window's ``w + 1`` steps a row at two, three (odd: idle steps) and
    four blocks a head, against the XLA reference: outputs and every gradient, at the limits this file has."""
    fa = _window_case(monkeypatch, 128, 64, 32)
    h, d, r, w = case["h"], case["d"], case.get("r", 0), case.get("w", 0) % n
    l = 128 * n
    if r:
        args = _rotary_inputs(jnp.float32, b=1, l=l, h=h, r=r, seed=n)
        attend = lambda *a: fa.flash_attention(*a[:3], True, *a[3:])  # noqa: E731
        reference = lambda *a: _explicit_masked_softmax(*a, True)     # noqa: E731
    else:
        args = _qkv(jnp.float32, b=1, l=l, h=h, d=d, seed=n)
        attend = lambda q, k, v: fa.flash_attention(q, k, v, True, window=128 * w or None)   # noqa: E731
        reference = lambda q, k, v: attention_reference(q, k, v, causal=True, window=128 * w or None)  # noqa: E731
    grid = fa._Plan(args[0].shape, True, r, 128 * w).grid
    assert (grid[1], grid[3 if r else 2]) == ((n, w + 1) if w else fa.folded_grid(n))
    cot = jax.random.normal(jax.random.key(2), args[0].shape, jnp.float32)
    out, grads = _output_and_gradients(attend, cot)(*args)
    want, want_grads = _output_and_gradients(reference, cot)(*args)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=2e-5)
    for got, ref, which in zip(grads, want_grads, ("dq", "dk", "dv", "dq_rot", "dk_rot")):
        np.testing.assert_allclose(got, ref, atol=5e-5, rtol=5e-5, err_msg=which)


#: sha256 of the jaxpr (forward AND gradient, the kernels' bodies in it) of the calls the older cells make — causal or not,
#: one block or several, two heads a lane group, a rotary part — and of EVA's at ``evabyte_job``'s shape, whose kernels take
#: ``_visible`` / ``_sub_tiles`` / ``_causal_mask`` from this module.  PINNED in PR 56 at the values its PARENT (3fa6822) gives:
#: the window was threaded through ``_visit``, ``_visible`` and ``_Plan`` and a call without one traces to the byte as it did.
#: The lowered steps ``tests/test_chip_lowering.py`` pins hold no kernel (lowered from the CPU the attention is the XLA
#: reference) and every EVA case here has ONE sub-tile a window: a ``_visible`` that paired EVA's sub-tiles with the positions
#: AFTER them passed all of tier-1 and read 1.3 on the chip's check.  A PR that changes a kernel's body on purpose re-pins.
#: RE-PINNED in PR 65 for the four calls of SEVERAL blocks a head (8192, 4096, 2048 and the rotary call: ``_visit`` reads its pair
#: from the folded grid, ``_walk``); the three of ONE block a head and EVA's are the values of PR 56's parent still.
KERNEL_JAXPR_SHA256 = {
    "flash causal [1, 8192, 16, 128]": "ccaecbe80d5b67fe",
    "flash causal [2, 1024, 16, 64]": "8e96fe343a8e22ea",
    "flash causal [1, 4096, 16, 128]": "c1cb096c83c47516",
    "flash full [1, 1024, 4, 64]": "f8a1fcd73c9682e4",
    "flash causal [1, 8192, 32, 128] rotary 64": "7f0a093e6e6fbf4e",
    "flash causal [1, 2048, 8, 128]": "6b6c9bc4bb49ff69",
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
