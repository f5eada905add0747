"""``moe_lm`` under ``KeyeVL2``'s keys (``sa_config``: Keye-VL-2.0's language
model) against its plain reference
(``benchmark/configs/keye_vl2_30b_a3b_ep8_l5_reference.py``): the loss, the
indexer's own loss, every gradient leaf and which loss reaches which
parameter; the selection (``ops/sparse_select``) against ``lax.top_k`` on
short rows, ties and chunks; the masked flash kernels, the score kernels and
the head-summed probabilities in the interpreter against their XLA forms,
forward and gradients; the indexer's loss's own gradients (made in its
forward's ONE walk of the pairs, a save site of the block) against
``jax.grad`` of the plain form, and the launches a training step makes of
the indexer's kernels; the eight expert shares that add up to the uncut
layer.  CPU only."""

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lm_family
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import sparse_select as ss

CONFIG = "keye_vl2_30b_a3b_ep8_l5"

#: KeyeVL2's keys at a small size, in the PUBLISHED spelling: 4 query heads over 2 key/value heads, an indexer of 4
#: heads of 16 over one key head that keeps 24 keys a query in a sequence of 128 (walked 32 rows at a time), 4 of 16
#: experts top-3.
SA = dict(indexer_head_dim=16, indexer_num_heads=4, indexer_num_kv_heads=1, kv_chunk_size=32, q_chunk_size=32, topk=24)
KEYS = dict(
    vocab_size=96, hidden_size=32, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    rope_theta=10000000, rms_norm_eps=1e-6, intermediate_size=48, moe_intermediate_size=24,
    num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=3, norm_topk_prob=True, sa_config=SA,
    tie_word_embeddings=False, decay_matrices_only=True, seq_len=128, learning_rate=3e-4, weight_decay=0.1,
    lr_warmup_steps=10, router_aux_loss_coef=0.0, router_z_loss_coef=0.0,
)
ATTENTION = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
INDEXER = ("idx_wq", "idx_wk", "idx_ww", "idx_norm", "idx_norm_bias")
EXPERTS = ("router", "w_gate", "w_up", "w_down")
NORMS = ("attn_norm", "ffn_norm")
LEAVES = ["tok_emb", "norm_f", "head"] + [f"blocks/b{i:02d}/{name}" for i in range(2) for name in NORMS + ATTENTION + INDEXER + EXPERTS]


def _moved(name, a, noise):
    """Gains that are not 1 (the per-head ones and the layernorm's too),
    matrices five times the init's scale — ``wo`` and ``w_down`` too, which
    the family draws smaller (``moe_lm.KEYE_VL2_INTO_STREAM``): a branch that
    writes nothing tests nothing."""
    if a.ndim == 1:
        return a + 0.3 * noise()
    return a * (5.0 / moe_lm.KEYE_VL2_INTO_STREAM if name in ("wo", "w_down") else 5.0)


reference = functools.partial(lm_family.reference, CONFIG)
_spec = functools.partial(lm_family.spec, KEYS)
_batch = functools.partial(lm_family.batch, KEYS)
_weights = functools.partial(lm_family.weights, move=_moved)
_layers, _leaf = lm_family.layers, lm_family.leaf


def _system(spec, batch):
    """``w -> (((loss, outputs), gradients), {loss's name: ITS gradient alone})``:
    the total the trainer differentiates and each of its two terms by itself,
    the three functions the cases below read — ONE program (the three share
    their forward in it), where each case used to compile its own."""
    def total(w):
        out = spec.apply(w, batch, train=True)
        return spec.loss(out, batch), out

    def one(which):
        def term(w):
            out = spec.apply(w, batch, train=True)
            return out["indexer_loss"] if which == "indexer_loss" else spec.metrics(out, batch)["ce"]
        return term

    return lambda w: (jax.value_and_grad(total, has_aux=True)(w), {which: jax.grad(one(which))(w) for which in ("lm_loss", "indexer_loss")})


def _plain(ref, keys, batch):
    """``w -> ((CE + L_I, (CE, L_I, logits)), gradients)`` of the plain reference."""
    import optax

    forward = ref.build(dict(keys))

    def total(w):
        z, _, loss_i = forward(w, batch["tokens"])
        ce = optax.softmax_cross_entropy_with_integer_labels(z, batch["labels"]).mean()
        return ce + loss_i, (ce, loss_i, z)

    return jax.value_and_grad(total, has_aux=True)


def _system_and_reference():
    return lm_family.system_and_reference(CONFIG, KEYS, _moved, _system, _plain)


def test_float32_system_gives_the_references_two_losses_and_gradient_in_every_leaf():
    spec, batch = _spec(), _batch()
    (((loss, out), grads), _), ((want, (ce, loss_i, want_logits)), want_grads) = _system_and_reference()
    assert float(jnp.max(jnp.abs(out["logits"] - want_logits))) <= 2e-5 * float(jnp.max(jnp.abs(want_logits)))
    assert float(loss_i) > 0.05 and abs(float(out["indexer_loss"]) - float(loss_i)) <= 1e-5 * float(loss_i)
    assert abs(float(loss) - float(want)) <= 1e-6 * float(want) and abs(float(want) - float(ce) - float(loss_i)) < 1e-6
    metrics = spec.metrics(out, batch)
    assert float(metrics["loss"]) == float(loss) and float(metrics["indexer_loss"]) == float(out["indexer_loss"])
    assert len(jax.tree.leaves(grads)) == len(LEAVES)
    for leaf in LEAVES:
        got, ref = _leaf(grads, leaf), _leaf(want_grads, leaf)
        assert got.shape == ref.shape and float(jnp.max(jnp.abs(ref))) > 0, leaf
        assert float(jnp.max(jnp.abs(got - ref))) <= 2e-4 * float(jnp.max(jnp.abs(ref))), leaf
    # the step counters are what the shapes give (two layers, 2 sequences, 4 heads: 24 x 25 / 2 + 104 x 24 selected pairs of
    # 128 x 129 / 2 a head; 104 queries a sequence drop keys), and what the XLA path multiplies: every pair
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | set(attentions.DSA_COUNTERS)
    assert float(metrics["dsa_pairs_causal"]) == 2 * 2 * 4 * (128 * 129 // 2)
    assert float(metrics["dsa_pairs_needed"]) == 2 * 2 * 4 * (24 * 25 // 2 + 104 * 24)
    assert float(metrics["dsa_rows_selecting"]) == 2 * 2 * 104
    assert float(metrics["dsa_pairs_computed"]) == 2 * 2 * 4 * 128 * 128


@pytest.mark.parametrize("which", ["lm_loss", "indexer_loss"])
def test_each_loss_reaches_its_own_parameters_and_exactly_none_of_the_others(which):
    """The LM loss's gradient is EXACTLY zero on every indexer parameter (its
    input is a stop-gradient and the selection is discrete), and the
    indexer's loss's on every other parameter, the embedding included."""
    ((_, of_the_total), alone), _ = _system_and_reference()
    for leaf in LEAVES:
        largest = float(jnp.max(jnp.abs(_leaf(alone[which], leaf))))
        own = (leaf.split("/")[-1] in INDEXER) == (which == "indexer_loss")
        assert (largest > 0) if own else (largest == 0.0), (leaf, largest)
    # and without its loss in the total (the references' control ``no_indexer_loss``) the indexer never trains: the
    # total's gradient on it is exactly zero (ONE program more: the total traced under the control; over 10 s for it)
    if which == "indexer_loss":
        spec, batch = _spec(), _batch()
        total = lambda w: spec.loss(spec.apply(w, batch, train=True), batch)  # noqa: E731
        with reference().faults("no_indexer_loss"):
            off = jax.jit(jax.grad(total))(_weights(spec))
        assert all(float(jnp.max(jnp.abs(off["blocks"]["b00"][name]))) == 0.0 for name in INDEXER)
        assert all(float(jnp.max(jnp.abs(of_the_total["blocks"]["b00"][name]))) > 0.0 for name in INDEXER)


def _scores(seed: int, rows: int, length: int, offset: int = 0, ties: bool = False):
    s = jax.random.normal(jax.random.key(seed), (2, rows, length))
    if ties:  # relu makes exact zeros, and a coarse grid makes equal scores everywhere (across chunks too)
        s = jnp.where(s < 0.3, 0.0, jnp.round(s * 4) / 4)
    return jnp.where(ss._causal(offset, rows, length), s, -jnp.inf)


@pytest.mark.parametrize("case", ["short_rows", "ties_at_the_threshold", "a_later_chunk_with_ties", "k_is_the_whole_row", "one_key"])
def test_the_selection_is_lax_top_k_on_short_rows_ties_and_chunks(case):
    rows, length, offset, ties, topk = {
        "short_rows": (64, 128, 0, False, 100),            # most rows have fewer than k candidates
        "ties_at_the_threshold": (64, 128, 0, True, 24),
        "a_later_chunk_with_ties": (32, 128, 96, True, 40),
        "k_is_the_whole_row": (64, 64, 0, True, 64),
        "one_key": (64, 128, 64, True, 1),
    }[case]
    scores = _scores(7, rows, length, offset, ties)
    k = jnp.minimum(offset + jnp.arange(rows) + 1, topk)
    got = jax.jit(ss.select_rows)(scores, k)
    want = ss.select_reference(scores, k, topk)
    assert got.dtype == jnp.int8 and bool(jnp.array_equal(got, want))
    np.testing.assert_array_equal(np.asarray(jnp.sum(got, -1)), np.broadcast_to(np.asarray(k), (2, rows)))
    assert not bool(jnp.any((got != 0) & ~ss._causal(offset, rows, length)))
    if ties and case != "k_is_the_whole_row":  # the case is one: some row has more scores at its threshold than it may take
        kth = jnp.sort(scores, -1)[..., ::-1][jnp.arange(2)[:, None], jnp.arange(rows)[None, :], k - 1]
        assert bool(jnp.any(jnp.sum(scores == kth[..., None], -1) + jnp.sum(scores > kth[..., None], -1) > k))


def _indexer_operands(seed: int = 0, b: int = 2, l: int = 256, j: int = 4, e: int = 64, dtype=jnp.bfloat16):
    keys = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(keys[0], (b, l, j, e)).astype(dtype), jax.random.normal(keys[1], (b, l, e)).astype(dtype),
            jax.random.normal(keys[2], (b, l, j)) * 0.1)


@pytest.mark.parametrize("chunk", [64, 128, 0])
def test_the_walked_selection_is_the_whole_ones_whatever_the_chunk(chunk):
    qi, ki, w = _indexer_operands()
    mask, counts = jax.jit(lambda *a: ss.select(*a, 32, chunk))(qi, ki, w)
    scores = ss.index_scores_reference(qi, ki, w, 0)
    assert bool(jnp.array_equal(mask, ss.select_reference(scores, jnp.minimum(jnp.arange(256) + 1, 32), 32)))
    rows = ss.chunk_rows(256, chunk)
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(jnp.sum(mask.reshape(2, 256 // rows, rows, 256), 2, dtype=jnp.int32)))
    # the kernels' block summary from the counts is the one from the mask
    np.testing.assert_array_equal(np.asarray(fa.block_summary(mask, 128, counts)), np.asarray(fa.block_summary(mask, 128)))


@contextlib.contextmanager
def _the_kernels_chosen():
    """The indexer's calls take their Pallas kernels, through the interpreter; jax's caches are dropped on both sides
    (the blocks' ``jax.checkpoint`` keeps traces by shape across a patched path)."""
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ss, "kernel_path", lambda qi, ki: ss.kernels_outside_contract(qi, ki.shape[1]))
        patch.setattr(ss, "probs_path", lambda q, k: "")
        jax.clear_caches()
        yield
    jax.clear_caches()


@pytest.fixture
def on_the_kernels():
    with _the_kernels_chosen():
        yield


@pytest.mark.parametrize("e,j", [(64, 4), (128, 2), (32, 8)])
def test_the_score_kernels_are_the_xla_score_and_its_gradients(on_the_kernels, e, j):
    qi, ki, w = _indexer_operands(1, l=256, j=j, e=e)
    offset = 128
    args = (qi[:, offset:], ki, w[:, offset:])
    got = jax.jit(lambda *a: ss.index_scores(*a, offset))(*args)
    want = ss.index_scores_reference(*args, offset)
    seen = jnp.isfinite(want)
    assert bool(jnp.array_equal(seen, jnp.isfinite(got))) and not bool(jnp.any(seen[:, 0, 130:]))
    assert float(jnp.max(jnp.abs(jnp.where(seen, got - want, 0.0)))) <= 1e-5 * float(jnp.max(jnp.where(seen, jnp.abs(want), 0.0)))
    cot = jnp.where(seen, jax.random.normal(jax.random.key(3), want.shape), 0.0)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(jnp.where(seen, ss.index_scores(*a, offset), 0.0) * cot), (0, 1, 2)))(*args)
    wants = jax.grad(lambda *a: jnp.sum(jnp.where(seen, ss.index_scores_reference(*a, offset), 0.0) * cot), (0, 1, 2))(*args)
    for got_g, want_g in zip(grads, wants):
        assert got_g.dtype == want_g.dtype
        # the kernels round ds to bfloat16 for the MXU and return bfloat16 gradients, as the flash kernels do
        assert float(jnp.max(jnp.abs(got_g.astype(jnp.float32) - want_g.astype(jnp.float32)))) <= 1e-2 * float(jnp.max(jnp.abs(want_g.astype(jnp.float32))))


def _attention_operands(seed: int, b: int, l: int, h: int, g: int, topk: int, recent: bool = False):
    """q, k, v, a selection of ``topk`` keys a query (random ones, or with
    ``recent`` mostly the latest: far blocks then hold no selected key), a key."""
    keys = jax.random.split(jax.random.key(seed), 5)
    q = jax.random.normal(keys[0], (b, l, h, 128)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(key, (b, l, g, 128)).astype(jnp.bfloat16) for key in keys[1:3])
    scores = jax.random.normal(keys[3], (b, l, l)) + (0.2 * jnp.arange(l)[None, None, :] if recent else 0.0)
    scores = jnp.where(ss._causal(0, l, l), scores, -jnp.inf)
    return q, k, v, ss.select_rows(scores, jnp.minimum(jnp.arange(l) + 1, topk)), keys[4]


@pytest.mark.parametrize("order", ["queries_first", "keys_first"])
@pytest.mark.parametrize("n", range(1, 18))
def test_the_folded_grid_visits_every_causal_pair_once_a_row_at_a_time_and_nothing_else(n, order):
    """The enumeration alone (``fa.folded_pair``; dK/dV's reading of it, ``fa._folded_key_pair``): grid
    ``(ceil(n / 2), n + 1)``; every pair ``j <= i`` exactly once; a row's pairs (dK/dV: a key block's) on
    consecutive steps, from its first to its last, ascending; idle steps only in the second run of an odd ``n``'s
    middle row (``n`` = 1 among them), staying on the pair before."""
    grid = fa.folded_grid(n)
    assert grid == ((n + 1) // 2, n + 1)
    r, c = (x.reshape(-1) for x in np.meshgrid(np.arange(grid[0]), np.arange(grid[1]), indexing="ij"))    # in the grid's order
    i, j, work = (np.asarray(x) for x in (fa.folded_pair if order == "queries_first" else fa._folded_key_pair)(r, c, n))
    steps = list(zip(i.tolist(), j.tolist()))
    visited = [pair for pair, w in zip(steps, work) if w]
    assert sorted(visited) == [(a, b) for a in range(n) for b in range(a + 1)]
    row, inner = (i, j) if order == "queries_first" else (j, i)
    for block in range(n):
        at = [s for s in range(len(steps)) if work[s] and row[s] == block]
        want = list(range(block + 1)) if order == "queries_first" else list(range(block, n))
        assert at == list(range(at[0], at[0] + len(at))) and [int(inner[s]) for s in at] == want, (block, at)
    idle = [s for s in range(len(steps)) if not work[s]]
    assert len(idle) == ((n + 1) // 2 if n % 2 else 0)
    assert idle == list(range(len(steps) - len(idle), len(steps))) and all(steps[s] == steps[s - 1] for s in idle)     # the tail of the middle row's grid row
    if idle:
        assert steps[idle[0]] == ((n // 2, n // 2) if order == "queries_first" else (n - 1, n // 2))     # the middle row's last pair


@pytest.mark.parametrize("shape,said", [((1, 128, 2, 128), "blocks=1x1 of 128 rows steps=2/1"), ((1, 384, 2, 128), "blocks=3x3 of 128 rows steps=8/6"),
                                        ((1, 16384, 32, 128), "mask=int8[16384,16384] blocks=16x16 of 1024 rows steps=136/136")])
def test_the_masked_calls_line_says_the_grids_steps_beside_its_pairs(monkeypatch, shape, said):
    from elasticdl_tpu.ops import ring_attention

    lines = []
    monkeypatch.setattr(ring_attention, "_log_once", lines.append)
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    jax.eval_shape(fa.masked_flash_attention, q, q, q, jax.ShapeDtypeStruct((1, shape[1], shape[1]), jnp.int8))      # traced, never run
    assert len(lines) == 1 and "attention path: pallas-interpret" in lines[0] and lines[0].endswith(said + ")"), lines
    plan = fa._MaskedPlan(shape)
    assert plan.grid == (shape[0] * shape[2], *fa.folded_grid(plan.n)) and said.endswith(f"steps={plan.grid[1] * plan.grid[2]}/{plan.pairs}")


#: (L, keys a query, rows of a block where the test sets them): one block (256); three blocks of 128 rows (384) with a
#: selection too small to reach every block, and with every causal key (the dense limit); EVEN n, which the contract
#: reaches only from L = 2,048 on, at a size the interpreter can run — four and six blocks of 128 rows, recent keys
#: only, so the far blocks of the late rows are visited steps that run no code — and two blocks of 1,024 rows as the contract cuts them
MASKED_CASES = [(256, 48, 0), (384, 40, 0), (384, 384, 0), (512, 40, 128), (768, 40, 128), (2048, 64, 0)]


@pytest.mark.parametrize("l,topk,rows", MASKED_CASES)
def test_the_masked_flash_kernels_are_the_xla_path_forward_and_gradients(monkeypatch, l, topk, rows):
    """Forward and the three gradients against ``selected_attention_reference`` (``MASKED_CASES``): rows that select
    nothing in a visited block, the carried state, skipped blocks inside the triangle, odd and even ``n``."""
    if rows:
        monkeypatch.setattr(fa, "masked_rows", lambda l: rows)
    recent = topk < l and l > 256
    b, h = (1, 2) if l > 1024 else (2, 4)
    q, k, v, mask, key = _attention_operands(0, b, l, h, h // 2, topk, recent=recent)
    wide = lambda t: jnp.repeat(t, 2, axis=2)  # noqa: E731
    cot = jax.random.normal(key, q.shape)

    def read(attend):  # ONE program a side: the output, the logsumexp and the three gradients (the forward was compiled twice: PR 66)
        def loss(q, k, v):
            o, lse = attend(q, wide(k), wide(v), mask)
            return jnp.vdot(o.astype(jnp.float32), cot), (o, lse)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(q, k, v)

    (_, (o, lse)), grads = read(fa.masked_flash_attention)
    (_, (want_o, want_lse)), wants = read(attentions.selected_attention_reference)
    assert o.dtype == jnp.bfloat16 and lse.shape == (b * h, 1, l)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want_o.astype(jnp.float32)))) <= 2e-2 * float(jnp.max(jnp.abs(want_o.astype(jnp.float32))))
    assert float(jnp.max(jnp.abs(lse - want_lse))) <= 1e-5 * float(jnp.max(jnp.abs(want_lse)))
    for got_g, want_g in zip(grads, wants):
        assert float(jnp.max(jnp.abs(got_g.astype(jnp.float32) - want_g.astype(jnp.float32)))) <= 2e-2 * float(jnp.max(jnp.abs(want_g.astype(jnp.float32))))
    if recent and l < 1024:
        # blocks that hold no selected key are skipped (the last queries' 40 keys are among the latest hundred), a
        # visited one may hold rows that select nothing in it, and the part's counter says what was multiplied
        summary = fa.block_summary(mask, 128)
        n = l // 128
        assert int(jnp.sum(summary[:, n - 1, 0])) == 0 and int(jnp.sum(summary > 0)) == 2 * (2 * n - 1)     # the diagonal and the block before it
        assert bool(jnp.any(jnp.sum(mask[:, 128:256, :128], -1) == 0)) and bool(jnp.any(jnp.sum(mask[:, 128:256, :128], -1) > 0))
        assert float(attentions.selected_attention_reference(q, wide(k), wide(v), mask)[1].max()) < 1e4


def test_the_control_on_the_masked_backward_breaks_dk_and_dv_and_leaves_the_forward_and_dq_alone():
    """``dkv_ignores_selection`` (the references' control that the configuration's ``grad_attention`` must catch on
    the chip): inside the masked dK/dV kernel every pair of a visited block counts as selected.  The output, the
    logsumexp and dQ are the sound kernels' to the bit; dK and dV are far off."""
    q, k, v, mask, key = _attention_operands(1, 2, 384, 4, 2, 40, recent=True)
    wide = lambda t: jnp.repeat(t, 2, axis=2)  # noqa: E731
    cot = jax.random.normal(key, q.shape)

    def run():
        def loss(q, k, v):
            o, lse = fa.masked_flash_attention(q, wide(k), wide(v), mask)
            return jnp.vdot(o.astype(jnp.float32), cot), (o, lse)
        return jax.jit(jax.value_and_grad(loss, (0, 1, 2), has_aux=True))(q, k, v)

    (_, (o, lse)), (dq, dk, dv) = run()
    with reference().faults("dkv_ignores_selection"):
        (_, (bad_o, bad_lse)), (bad_dq, bad_dk, bad_dv) = run()
    assert bool(jnp.array_equal(o, bad_o)) and bool(jnp.array_equal(lse, bad_lse)) and bool(jnp.array_equal(dq, bad_dq))
    off = lambda a, b: float(jnp.linalg.norm((a - b).astype(jnp.float32)) / jnp.linalg.norm(b.astype(jnp.float32)))  # noqa: E731
    assert off(bad_dk, dk) > 0.5 and off(bad_dv, dv) > 0.5, (off(bad_dk, dk), off(bad_dv, dv))
    assert fa._masked_dkv_kernel.__name__ == "_masked_dkv_kernel" and not hasattr(fa._masked_dkv_kernel, "__wrapped__")  # the patch is gone


def test_a_masked_call_outside_the_contract_says_why():
    q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    mask = jnp.ones((1, 256, 256), jnp.int8)
    assert "head_dim = 64" in fa.masked_outside_contract(q, q, q, mask)
    q = jnp.zeros((1, 256, 2, 128), jnp.bfloat16)
    assert fa.masked_outside_contract(q, q, q, mask) == ""
    assert "int8" in fa.masked_outside_contract(q, q, q, mask.astype(jnp.int32))
    assert "16384" in fa.masked_outside_contract(*(jnp.zeros((1, 200, 2, 128), jnp.bfloat16),) * 3, mask)
    with pytest.raises(ValueError, match="masked_flash_attention"):
        fa.masked_flash_attention(q, q, q, mask.astype(jnp.int32))


def test_the_head_summed_probabilities_and_the_loss_on_the_kernels_are_the_xla_forms(on_the_kernels):
    q, k, v, mask, key = _attention_operands(1, 2, 256, 4, 2, 48)
    _, lse = attentions.selected_attention_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), mask)
    got = ss.head_summed_probs(q[:, 128:], k, lse.reshape(2, 4, 256)[:, :, 128:], mask[:, 128:])
    want = ss.head_summed_probs_reference(q[:, 128:], k, lse.reshape(2, 4, 256)[:, :, 128:], mask[:, 128:])
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5 * float(jnp.max(want))
    np.testing.assert_allclose(np.asarray(jnp.sum(want, -1)), 4.0, rtol=2e-2)   # each head's probabilities sum to 1 over the set
    qi, ki, w = _indexer_operands(2)
    loss = lambda qi, ki, w: ss.indexer_loss(qi, ki, w, q, k, lse, mask, 128)  # noqa: E731
    value, grads = jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(qi, ki, w)
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ss, "kernel_path", lambda qi, ki: "the XLA forms")
        patch.setattr(ss, "probs_path", lambda q, k: "the XLA forms")
        want_value, wants = jax.jit(jax.value_and_grad(lambda qi, ki, w: ss.indexer_loss(qi, ki, w, q, k, lse, mask, 0), (0, 1, 2)))(qi, ki, w)
    assert float(value) > 0 and abs(float(value) - float(want_value)) <= 1e-5 * float(want_value)
    for got_g, want_g in zip(grads, wants):
        assert float(jnp.max(jnp.abs(got_g.astype(jnp.float32) - want_g.astype(jnp.float32)))) <= 2e-2 * float(jnp.max(jnp.abs(want_g.astype(jnp.float32))))


def test_the_references_masked_form_is_its_gather_of_the_selected_keys():
    """The reference attends the selected set as a dense softmax under the
    set's mask; the same numbers come from ``lax.top_k``'s indices and a
    gather of the selected keys and values, forward and gradients."""
    ref = reference()
    keys = jax.random.split(jax.random.key(5), 6)
    q = jax.random.normal(keys[0], (2, 64, 4, 8))
    k, v = jax.random.normal(keys[1], (2, 64, 2, 8)), jax.random.normal(keys[2], (2, 64, 2, 8))
    qi, ki, w = jax.random.normal(keys[3], (2, 64, 4, 16)), jax.random.normal(keys[4], (2, 64, 16)), jax.random.normal(keys[5], (2, 64, 4)) * 0.1
    cot = jax.random.normal(jax.random.key(6), q.shape)
    masked = lambda *a: (lambda o, li, _: jnp.vdot(o, cot) + li)(*ref.selected_attention(*a, 12, block=16))  # noqa: E731
    gathered = lambda *a: (lambda o, li: jnp.vdot(o, cot) + li)(*ref.gathered_attention(*a, 12))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        got, grads = jax.jit(jax.value_and_grad(masked, tuple(range(6))))(q, k, v, qi, ki, w)
        want, wants = jax.jit(jax.value_and_grad(gathered, tuple(range(6))))(q, k, v, qi, ki, w)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for got_g, want_g in zip(grads, wants):
        assert float(jnp.max(jnp.abs(got_g - want_g))) <= 1e-4 * float(jnp.max(jnp.abs(want_g)))


def test_the_eight_expert_shares_add_up_to_the_uncut_layer():
    """One layer whole (16 experts of a 16-wide router) against its eight
    shares of two experts each: what every chip computes alike — the norms,
    the attention over the selected keys, the indexer, the router — counted
    ONCE, the held experts' parts summed, is the uncut layer's output."""
    whole = _spec(experts_held=0, first_expert_held=0, num_hidden_layers=1)
    blk = _weights(whole)["blocks"]["b00"]
    x = jax.random.normal(jax.random.key(9), (2, 128, 32))
    block = functools.partial(moe_lm._block, positions=jnp.arange(128), axis=None, eps=1e-6, compute_dtype=jnp.float32)
    (layer,) = _layers(whole)
    shares = [_layers(_spec(experts_held=2, first_expert_held=2 * share, num_hidden_layers=1))[0] for share in range(8)]

    @jax.jit  # ONE program for the ten blocks (op by op each is a minute of small compiles)
    def summed(x, blk):
        want, _ = block(x, blk, layer=layer)
        alike, _ = block(x, blk, layer=layer[:1])      # the stream after the attention: every share's alike
        total = alike
        for share, held in enumerate(shares):
            mine = {**blk, **{name: blk[name][2 * share:2 * share + 2] for name in ("w_gate", "w_up", "w_down")}}
            total = total + (block(x, mine, layer=held)[0] - alike)
        return total, want, alike

    with jax.default_matmul_precision("highest"):
        total, want, alike = summed(x, blk)
    assert float(jnp.max(jnp.abs(total - want))) <= 1e-5 * float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(want - alike))) > 1e-2 * float(jnp.max(jnp.abs(want)))   # the experts add something


def test_the_parameters_are_the_published_shapes_and_the_family_follows_from_sa_config(monkeypatch):
    spec = _spec()
    shapes = jax.tree.map(lambda a: a.shape, jax.eval_shape(spec.init, jax.random.key(0)))
    blk = shapes["blocks"]["b01"]
    assert sorted(blk) == sorted(NORMS + ATTENTION + INDEXER + EXPERTS)
    assert blk["wq"] == blk["wo"][::-1] == (32, 32) and blk["wk"] == blk["wv"] == (32, 16) and blk["q_norm"] == blk["k_norm"] == (8,)
    assert blk["idx_wq"] == (32, 4 * 16) and blk["idx_wk"] == (32, 16) and blk["idx_ww"] == (32, 4) and blk["idx_norm"] == blk["idx_norm_bias"] == (16,)
    assert blk["router"] == (32, 16) and blk["w_up"] == (4, 32, 24)
    (attention, experts), _ = (tuple(entry[1] for entry in layer) for layer in _layers(spec))
    assert attention == attentions.IndexedSparseAttention(4, 2, 8, 1e7, 1e-6, 4, 16, 24, 32, into_stream=0.01)
    assert experts == moe_lm.RoutedExperts(moe_lm.Router(16, 3, 4, 4, (("norm_topk_prob", True),)), width=24, into_stream=0.01)
    assert spec.after_update is None     # no correction bias: a softmax router, greedy
    # the family draws the two matrices that write into the stream at ONE constant share of the others' scale, and no
    # other matrix: the same keys of the init's stream, so the same values times the share
    assert moe_lm.KEYE_VL2_INTO_STREAM == 0.01
    small = jax.jit(spec.init)(jax.random.key(0))["blocks"]["b01"]
    monkeypatch.setattr(moe_lm, "KEYE_VL2_INTO_STREAM", 1.0)
    whole = jax.jit(_spec().init)(jax.random.key(0))["blocks"]["b01"]
    for name in ("wo", "w_down"):
        np.testing.assert_allclose(np.asarray(small[name]), 0.01 * np.asarray(whole[name]), rtol=1e-6)
    assert all(bool(jnp.array_equal(small[name], whole[name])) for name in whole if name not in ("wo", "w_down"))
    with pytest.raises(TypeError, match="residual_init_scale"):
        _spec(residual_init_scale=0.5)
    with pytest.raises(TypeError, match="indexer_loss_coef"):
        _spec(indexer_loss_coef=0.0)
    decayed = moe_lm._is_decayed(jax.eval_shape(spec.init, jax.random.key(0)), moe_lm._NOT_MATRICES)
    assert not any(decayed["blocks"]["b01"][name] for name in NORMS + ("q_norm", "k_norm", "idx_norm", "idx_norm_bias")) and decayed["blocks"]["b01"]["idx_wq"]
    assert moe_lm._family(hybrid_override_pattern=None, attention_class="mha", linear_attn_config=None, kv_lora_rank=0, sa_config=SA) == "keye_vl2"
    with pytest.raises(ValueError, match="each name a family"):
        _spec(sliding_window=32)
    with pytest.raises(ValueError, match="no part of the 'keye_vl2' family reads"):
        _spec(num_dense_layers=1)
    with pytest.raises(ValueError, match="sa_config: its keys are"):
        _spec(sa_config={**SA, "window": 4})
    with pytest.raises(ValueError, match="ONE index key a position"):
        _spec(sa_config={**SA, "indexer_num_kv_heads": 2})


def test_bfloat16_compute_keeps_the_index_scores_the_router_and_the_losses_in_float32():
    spec = _spec("bfloat16")
    params, batch = spec.init(jax.random.key(0)), _batch()
    seen = {}
    real = ss.index_scores

    def tapped(qi, ki, w, offset):
        seen["operands"] = (qi.dtype, ki.dtype, w.dtype)
        seen["scores"] = real(qi, ki, w, offset).dtype
        return real(qi, ki, w, offset)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ss, "index_scores", tapped)
        out = jax.jit(lambda w: spec.apply(w, batch, train=False))(params)  # the tap reads the types where the model is traced
    assert seen == {"operands": (jnp.bfloat16, jnp.bfloat16, jnp.float32), "scores": jnp.float32}
    assert out["logits"].dtype == out["indexer_loss"].dtype == jnp.float32 and bool(jnp.isfinite(out["indexer_loss"]))


# ---- the indexer's loss: ONE walk of the pairs, its gradients made in the forward's and kept ----


#: The loss's cases at the least size that has what they assert, a path: ``(length, the chunk of the two-chunk walk,
#: topk, a topk no row reaches)``.  Two chunks: the walk's carry and the sums over chunks.  ``topk`` under the length:
#: rows before it are SHORTER than topk (they keep every causal key) and rows after it SELECT; over the length: every row
#: is shorter.  The XLA forms take any length (two chunks of 32); the kernels take whole 128-row tiles, rows and keys, so
#: two chunks are 256 rows and no less.
LOSS_SIZES = {False: (64, 32, 12, 80), True: (256, 128, 48, 300)}


@functools.lru_cache(maxsize=None)
def _loss_operands(kernels: bool, two_chunks: bool, every_row_shorter: bool, seed: int = 4):
    """``(the loss's own operands, its constants, the chunk)`` as the part hands them over: the indexer's own selection
    of ``topk`` keys a query (its relus make exact zeros: rows that tie there) and the logsumexps of the attention over
    it.  Made once a (path, size): on the path the CALLER has set (``kernels`` names it and keys the memo)."""
    l, chunk, topk, over = LOSS_SIZES[kernels]
    chunk, topk = chunk if two_chunks else 0, over if every_row_shorter else topk
    qi, ki, w = _indexer_operands(seed, l=l, dtype=jnp.bfloat16 if kernels else jnp.float32)
    q, k, v, _, _ = _attention_operands(seed + 1, 2, l, 4, 2, 1)
    mask, _ = ss.select(qi, ki, w, topk, chunk)
    assert bool(jnp.any(ss.index_scores_reference(qi, ki, w, 0) == 0.0))
    kept = np.asarray(jnp.sum(mask[0], -1))  # row t keeps min(t + 1, topk): the first rows are shorter than topk, later ones select
    assert kept[0] < topk and (kept < np.arange(1, l + 1)).any() != every_row_shorter
    _, lse = attentions.selected_attention_reference(q, jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2), mask)
    return (qi, ki, w), (q, k, lse, mask), chunk


def _plain_indexer_loss(qi, ki, w, q, k, lse, mask):
    """The loss with no ``custom_vjp`` and no walk: the XLA score, the XLA head sum, the KL's rows."""
    b, l, h, _ = q.shape
    probs = ss.head_summed_probs_reference(q, k, lse.reshape(b, h, l), mask)
    return jnp.sum(ss.kl_rows(ss.index_scores_reference(qi, ki, w, 0), probs, mask)) / (b * l)


def _launches(jaxpr, primitive: str = "pallas_call", into=None) -> dict:
    """The equations of one primitive in a jaxpr and everything it holds: Pallas launches by the kernel's name."""
    into = {} if into is None else into
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            name = eqn.params["jaxpr"].debug_info.func_name if primitive == "pallas_call" else primitive
            into[name] = into.get(name, 0) + 1
        for inner in jax.core.jaxprs_in_params(eqn.params):
            _launches(inner, primitive, into)
    return into


@pytest.fixture(scope="module", params=["xla", "kernels"])
def loss_path(request):
    """``(the comparison's limit, whether the kernels run)``: the XLA forms on float32 operands (a tight comparison), or
    the Pallas kernels through the interpreter on bfloat16 (they round ds to bfloat16 and return bfloat16 gradients, as
    the flash kernels do).  MODULE-scoped, so that pytest runs the cases of a path together (the three functions
    below stand together, and nothing that wants the XLA forms stands behind them in this file): the kernels are
    chosen (``_the_kernels_chosen``: jax's caches dropped on both sides) ONCE for the sixteen cases that take them, where
    ``on_the_kernels`` did it for each of them and took every compiled program of the worker along each time (PR 66)."""
    if request.param == "xla":
        yield 1e-4, False
        return
    with _the_kernels_chosen():
        yield 2e-2, True


@functools.lru_cache(maxsize=None)
def _own_gradients(kernels: bool, two_chunks: bool, every_row_shorter: bool):
    """``({cotangent: the loss's own gradients}, jax.grad of the plain form)`` at one size on one path: the cotangent
    is an ARGUMENT of ONE program, so the three cases of a size share its compile (each was a program of its own), and
    the plain form is differentiated once.  Called with the path set (``loss_path``)."""
    own, constants, chunk = _loss_operands(kernels, two_chunks, every_row_shorter)
    scaled = jax.jit(jax.grad(lambda cotangent, *a: cotangent * ss.indexer_loss(*a, *constants, chunk), (1, 2, 3)))
    got = {cotangent: scaled(cotangent, *own) for cotangent in (1.0, 0.0, 0.5)}
    return got, jax.jit(jax.grad(lambda *a: _plain_indexer_loss(*a, *constants), (0, 1, 2)))(*own)


@pytest.mark.parametrize("cotangent", [1.0, 0.0, 0.5])
@pytest.mark.parametrize("every_row_shorter", [False, True], ids=["topk48", "every_row_shorter_than_topk"])
@pytest.mark.parametrize("two_chunks", [False, True], ids=["one_chunk", "two_chunks"])
def test_the_losss_own_gradients_are_jax_grads_of_the_plain_form(loss_path, two_chunks, every_row_shorter, cotangent):
    limit, kernels = loss_path
    got, want = _own_gradients(kernels, two_chunks, every_row_shorter)
    for name, got_g, want_g in zip(("dqI", "dkI", "dw"), got[cotangent], want):
        assert got_g.dtype == want_g.dtype and got_g.shape == want_g.shape, name
        got_g, want_g = got_g.astype(jnp.float32), cotangent * want_g.astype(jnp.float32)
        assert float(jnp.max(jnp.abs(want_g))) > 0 or cotangent == 0.0, name
        if cotangent == 0.0:      # the references' control ``no_indexer_loss``: exactly nothing
            assert float(jnp.max(jnp.abs(got_g))) == 0.0, name
        assert float(jnp.max(jnp.abs(got_g - want_g))) <= limit * float(jnp.max(jnp.abs(want_g))), name


@pytest.mark.parametrize("two_chunks", [False, True], ids=["one_chunk", "two_chunks"])
def test_the_loss_nobody_differentiates_is_the_differentiated_calls_and_makes_no_gradient(loss_path, two_chunks):
    _, kernels = loss_path
    own, constants, chunk = _loss_operands(kernels, two_chunks, False)
    loss = lambda *a: ss.indexer_loss(*a, *constants, chunk)  # noqa: E731
    alone, (value, _) = jax.jit(loss)(*own), jax.jit(jax.value_and_grad(loss, (0, 1, 2)))(*own)
    want = _plain_indexer_loss(*own, *constants)
    assert float(alone) > 0 and abs(float(alone) - float(value)) <= 1e-6 * float(value) and abs(float(value) - float(want)) <= 1e-5 * float(want)
    # the primal walks without the score's gradient: the score and the head sum, one product of the pairs each
    primal, both = jax.make_jaxpr(loss)(*own).jaxpr, jax.make_jaxpr(jax.value_and_grad(loss, (0, 1, 2)))(*own).jaxpr
    if kernels:
        assert _launches(primal) == {"_score_kernel": 1, "_probs_kernel": 1}
        assert _launches(both) == {"_score_kernel": 1, "_probs_kernel": 1, "_score_dq_kernel": 1, "_score_dk_kernel": 1}
    else:
        # the score's two einsums and the head sum's one
        assert _launches(primal, "dot_general") == {"dot_general": 3} and _launches(both, "dot_general")["dot_general"] > 3


@pytest.mark.parametrize("two_chunks", [False, True], ids=["one_chunk", "two_chunks"])
def test_a_rematerialised_block_gives_the_same_gradients_with_the_losss_site_kept_and_not(loss_path, two_chunks, capsys):
    from elasticdl_tpu.ops import remat

    _, kernels = loss_path
    own, constants, chunk = _loss_operands(kernels, two_chunks, False)
    block = lambda *a: ss.indexer_loss(*a, *constants, chunk)  # noqa: E731
    (site,), _ = remat.trace_sites(block, *own)
    assert site.name == "dsa_index_grads" and site.nbytes == remat.nbytes(own) and site.work == ss.grads_work(own[0], constants[0]) > 0
    grads = {
        keep: jax.jit(jax.value_and_grad(remat.rematerialised(block, keep), (0, 1, 2)))(*own) for keep in ((), ("dsa_index_grads",))
    }
    (loss, not_kept), (kept_loss, kept) = grads[()], grads[("dsa_index_grads",)]
    assert float(loss) == float(kept_loss)
    for a, b in zip(not_kept, kept):     # the same arithmetic, fused by XLA in two programs
        assert a.dtype == b.dtype and float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))) <= 1e-5 * float(jnp.max(jnp.abs(b.astype(jnp.float32))))
    # kept, the block's backward holds the three gradients and walks nothing; not kept, it holds the block's inputs alone
    capsys.readouterr()
    for keep, named in (("dsa_index_grads",), 3), ((), 0):
        jax.ad_checkpoint.print_saved_residuals(remat.rematerialised(block, keep), *own)
        assert capsys.readouterr().out.count("named 'dsa_index_grads'") == named


@pytest.mark.parametrize("blocks,score,probs", [("plain", 2, 1), ("rematerialised_keeping_all", 2, 1), ("rematerialised_keeping_nothing", 4, 2)])
def test_a_training_step_walks_the_pairs_for_the_loss_once_a_layer(on_the_kernels, blocks, score, probs):
    """The mechanism's counter, on the CPU: in one training step of the small model, per layer, the score kernel is
    launched TWICE (the selection, the loss), the head-summed probabilities ONCE and the score's gradient pair once
    — three, two and one while the loss's backward walked the chunks again.  A rematerialised block that keeps its
    sites (the chip's) launches the same; one that keeps nothing walks once more in its recomputation, selection and all."""
    from elasticdl_tpu.ops.embedding import ParallelContext

    layers = 2
    sa = dict(SA, indexer_head_dim=32, q_chunk_size=128, kv_chunk_size=128)     # inside the score kernels' contract
    spec = _spec(sa_config=sa, seq_len=256, num_hidden_layers=layers, remat=blocks != "plain")
    ctx = ParallelContext(remat_keep_bytes=2**40 if blocks == "rematerialised_keeping_all" else 0)
    params, batch = jax.eval_shape(spec.init, jax.random.key(0)), _batch(l=256)
    step = jax.value_and_grad(lambda w: spec.loss(spec.apply(w, batch, train=True, ctx=ctx), batch))
    seen = _launches(jax.make_jaxpr(step)(params).jaxpr)
    indexer = {name: n for name, n in seen.items() if name.startswith(("_score", "_probs"))}
    assert indexer == {"_score_kernel": score * layers, "_probs_kernel": probs * layers, "_score_dq_kernel": layers, "_score_dk_kernel": layers}
