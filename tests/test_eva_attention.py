"""``ops/eva_attention``: the kernels (Pallas interpreter here) and the XLA
path against the plain float32 reference of the benchmark's configuration
(``benchmark/configs/evabyte_6b5_tp2_l4_reference.py``, steps 4-5), forward
and every gradient; what a query may and may not see; the controls the
chip run's check must catch.  CPU only."""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import eva_attention as eva_ops
from elasticdl_tpu.ops.flash_attention import flash_attention

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW, CHUNK, HEADS, D = 128, 16, 2, 128
#: two windows; three windows and a ragged tail that is NOT whole chunks; one window (plain causal)
LENGTHS = {"two_windows": 256, "three_windows_and_a_ragged_tail": 3 * 128 + 41, "one_window": 128}
LEAVES = ("o", "dq", "dk", "dv", "dphi", "dmu")


@functools.lru_cache(maxsize=None)
def reference():
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "configs", "evabyte_6b5_tp2_l4_reference.py")
    spec = importlib.util.spec_from_file_location("evabyte_reference", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def operands(l: int, dtype=jnp.float32, seed: int = 0):
    keys = jax.random.split(jax.random.key(seed + l), 6)
    q, k, v, g = (jax.random.normal(key, (1, l, HEADS, D), jnp.float32).astype(dtype) for key in keys[:4])
    phi, mu = (0.5 * jax.random.normal(key, (HEADS, D), jnp.float32) for key in keys[4:])
    return (q, k, v, phi, mu), g


def by_kernels(q, k, v, phi, mu, **how):
    """The public op with the kernels chosen (they run in the interpreter
    off the TPU): what ``eva_attention`` does on the chip."""
    real = eva_ops._why_not_kernels
    eva_ops._why_not_kernels = lambda *a: ""
    try:
        return eva_ops.eva_attention(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK, **how)
    finally:
        eva_ops._why_not_kernels = real


def by_xla(q, k, v, phi, mu, **how):
    return eva_ops.eva_attention(q, k, v, phi, mu, window=WINDOW, chunk=CHUNK, **how)


def by_reference(q, k, v, phi, mu, variant=""):
    with jax.default_matmul_precision("highest"):
        return reference().eva(*(jnp.asarray(t, jnp.float32) for t in (q, k, v, phi, mu)), WINDOW, CHUNK, variant)


def forward_and_gradients(fn, args, g):
    """``fn``'s ``o`` and its five operands' gradients under ``g``, as ONE
    program (which another worker of the suite finds compiled)."""
    def both(*operands):
        out, vjp = jax.vjp(fn, *operands)
        return (out,) + vjp(g)
    return jax.jit(both)(*args)


@functools.lru_cache(maxsize=None)
def results(path: str, case: str) -> dict:
    args, g = operands(LENGTHS[case])
    fn = {"kernels": by_kernels, "xla": by_xla, "reference": by_reference}[path]
    with jax.default_matmul_precision("highest"):
        return dict(zip(LEAVES, forward_and_gradients(fn, args, g)))


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("case", sorted(LENGTHS))
@pytest.mark.parametrize("path", ["kernels", "xla"])
def test_the_op_is_the_reference_forward_and_in_every_gradient(path, case, leaf):
    got, want = results(path, case)[leaf], results("reference", case)[leaf]
    assert got.shape == want.shape
    scale = float(jnp.max(jnp.abs(want)))
    if case == "one_window" and leaf in ("dphi", "dmu"):
        # nobody sees a summary of the only window: the vectors get no gradient
        assert scale == 0.0 and float(jnp.max(jnp.abs(got))) == 0.0
        return
    assert float(jnp.max(jnp.abs(got - want))) <= 5e-6 * scale, (path, case, leaf)


@pytest.mark.parametrize("case", sorted(LENGTHS))
@pytest.mark.parametrize("path", ["kernels", "xla"])
def test_with_lse_the_op_hands_out_the_same_o_and_each_querys_logsumexp(path, case):
    """``with_lse``: the float32 logsumexp over everything a query saw
    [B, H, L] (what the backward kernels read; the benchmark's check
    ``eva_lse`` holds the softmax's precision by it), cut to the ragged
    length like ``o``; the reference's own ``with_lse`` is the yardstick."""
    args, _ = operands(LENGTHS[case])
    op = by_kernels if path == "kernels" else by_xla
    with jax.default_matmul_precision("highest"):
        o, lse = op(*args, with_lse=True)
        # op by op like the call above, which is what makes the two the same bits (``results`` is ONE compiled program, and
        # reads 1.8e-7 of a largest 3.1 away)
        same_o = op(*args)
        want_o, want_lse = reference().eva(*args, WINDOW, CHUNK, with_lse=True)
    assert lse.shape == (1, HEADS, LENGTHS[case]) and lse.dtype == jnp.float32
    assert jnp.array_equal(o, same_o)
    assert float(jnp.max(jnp.abs(lse - want_lse))) <= 2e-5 and float(jnp.max(jnp.abs(o - want_o))) <= 5e-6 * float(jnp.max(jnp.abs(want_o)))


@functools.lru_cache(maxsize=None)
def _with_sub_tiles_of(t: int):
    """``results("kernels", "two_windows")`` with the kernels' sub-tiles cut to
    ``t`` rows, so that a window is SEVERAL (on the chip a window of 2048 is
    four of 512; at this file's window of 128 it is one, and which side of a
    sub-tile its window's other positions lie on is never asked)."""
    real = eva_ops._T_FWD, eva_ops._T_BWD
    eva_ops._T_FWD = eva_ops._T_BWD = t
    try:
        args, g = operands(LENGTHS["two_windows"])
        with jax.default_matmul_precision("highest"):
            return dict(zip(LEAVES, forward_and_gradients(by_kernels, args, g)))
    finally:
        eva_ops._T_FWD, eva_ops._T_BWD = real


@pytest.mark.parametrize("leaf", LEAVES)
def test_a_window_of_several_sub_tiles_is_the_reference_too(leaf):
    """PR 56's near miss: ``flash_attention._visible`` read the kernels' plain
    ``True`` for the diagonal pair as a window's far edge, so a sub-tile was
    paired with the window's positions AFTER it and not before: every case
    here passed (one sub-tile a window) and the chip's check read 1.3."""
    got, want = _with_sub_tiles_of(32)[leaf], results("reference", "two_windows")[leaf]
    assert float(jnp.max(jnp.abs(got - want))) <= 5e-6 * float(jnp.max(jnp.abs(want))), leaf


def test_one_window_is_flash_attention_causal_to_the_bit():
    """A sequence of one window that is one sub-tile: the same pieces in
    the same order as the flash kernel's one block, so the same bits.  (A
    window of several sub-tiles or pieces streams its softmax in another
    order than the flash kernels' 1024-row blocks do: equal to rounding,
    which the reference cases hold, not to the bit.)"""
    (q, k, v, phi, mu), _ = operands(128, jnp.bfloat16)
    got = by_kernels(q, k, v, phi, mu)
    assert jnp.array_equal(got, flash_attention(q, k, v, True))


@pytest.mark.parametrize("path", ["kernels", "xla"])
def test_a_query_sees_no_summary_of_its_own_or_a_later_window(path):
    """Keys and values of window 1's LAST chunk perturbed: window 0 and
    every query of window 1 before that chunk are unchanged to the bit
    (they see neither those positions nor a summary of their window);
    window 2 changes (it sees that chunk's summary).  The vectors perturbed:
    window 0 is unchanged."""
    fn = by_kernels if path == "kernels" else by_xla
    (q, k, v, phi, mu), _ = operands(3 * WINDOW)
    base = fn(q, k, v, phi, mu)
    first = 2 * WINDOW - CHUNK
    k2, v2 = k.at[:, first : 2 * WINDOW].add(1.0), v.at[:, first : 2 * WINDOW].add(1.0)
    moved = fn(q, k2, v2, phi, mu)
    assert jnp.array_equal(moved[:, :first], base[:, :first])
    assert float(jnp.max(jnp.abs(moved[:, 2 * WINDOW :] - base[:, 2 * WINDOW :]))) > 1e-3
    other = fn(q, k, v, phi + 1.0, mu - 1.0)
    assert jnp.array_equal(other[:, :WINDOW], base[:, :WINDOW])
    assert float(jnp.max(jnp.abs(other[:, WINDOW:] - base[:, WINDOW:]))) > 1e-3


@pytest.mark.parametrize("variant", ["no_summaries", "one_window_early", "bfloat16_softmax"])
def test_the_controls_of_the_chip_run_read_as_errors(variant):
    """The check ``eva_output`` (largest error of ``o`` over the largest
    ``|o|``) on the op and on what each control computes instead."""
    args, _ = operands(LENGTHS["three_windows_and_a_ragged_tail"])
    want = by_reference(*args)
    reading = lambda got: float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))  # noqa: E731
    assert reading(by_kernels(*args)) < 1e-5
    assert reading(by_reference(*args, variant=variant)) > (1e-3 if variant == "bfloat16_softmax" else 0.1)


def test_bfloat16_operands_stay_within_the_flash_kernels_tolerance():
    """bfloat16 q, k, v through the kernels against the float32 reference of
    the same (rounded) operands: 2e-2 of the largest value, the flash
    kernels' tolerance (a v5e read 2.7e-3 there)."""
    args, g = operands(LENGTHS["two_windows"], jnp.bfloat16)
    for got, ref in zip(forward_and_gradients(by_kernels, args, g), forward_and_gradients(by_reference, args, g.astype(jnp.float32))):
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))) <= 2e-2 * float(jnp.max(jnp.abs(ref)))


@pytest.mark.parametrize("l, window, chunk", [(16384, 2048, 16), (424, 128, 16), (128, 128, 16), (300, 64, 8)])
def test_pairs_counts_what_a_query_is_scored_against(l, window, chunk):
    exact, far = eva_ops.pairs(l, window, chunk)
    i = np.arange(l)
    assert exact == int(np.sum(i % window + 1))
    assert far == int(np.sum(i // window * (window // chunk)))
    if l == 16384:  # the cell's shape: 24.13 M pairs a head, against 134.2 M of full causal attention
        assert (exact, far) == (16785408, 7340032) and l * (l + 1) // 2 == 134225920


def test_the_kernels_contract_says_why_a_shape_is_outside_it():
    shape = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)  # noqa: E731
    wide = shape(1, 16384, 16, 128)
    assert eva_ops.outside_contract(wide, wide, wide, 2048, 16) == ""
    assert "head_dim = 64" in eva_ops.outside_contract(*[shape(1, 4096, 4, 64)] * 3, 2048, 16)
    assert "not self-attention" in eva_ops.outside_contract(wide, shape(1, 16384, 4, 128), wide, 2048, 16)
    assert "summaries a window" in eva_ops.outside_contract(wide, wide, wide, 2048, 32)
    # off the TPU the public op takes the XLA path and says so
    assert eva_ops._why_not_kernels(wide, wide, wide, 2048, 16) == f"backend={jax.default_backend()}"
    with pytest.raises(ValueError, match="whole windows"):
        eva_ops.eva_flash(*[jnp.zeros((1, 100, 1, 128))] * 3, *[jnp.zeros((1, 4, 1, 128))] * 2, 64)
