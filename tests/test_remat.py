"""What a rematerialised block keeps (``ops/remat.py``): the save sites of
``moe_lm`` and ``transformer_lm``, the chooser, the helper, the trainer's
budget and the step's two counts — all on the CPU, where the budget the
trainer resolves is 0 and a test hands one in."""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.common.config import DistributionStrategy, JobConfig
from elasticdl_tpu.models.spec import load_model_spec
from elasticdl_tpu.ops import remat
from elasticdl_tpu.ops.embedding import ParallelContext
from elasticdl_tpu.parallel import trainer as trainer_lib
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer

B, L = 2, 64


def _moe_lm():
    """Two dense layers of ``moe_lm`` under EvaByte's keys (the XLA path of
    the attention: q, k, v, the summaries, gate and up are its sites)."""
    return load_model_spec(
        "elasticdl_tpu.models", "moe_lm.model_spec", vocab_size=64, hidden_size=64,
        num_attention_heads=4, num_hidden_layers=2, layer_types=["dense", "dense"],
        intermediate_size=128, seq_len=L, attention_class="eva", window_size=32, chunk_size=4,
        compute_dtype="float32", remat=True,
    )


def _transformer_lm():
    return load_model_spec(
        "elasticdl_tpu.models", "transformer_lm.model_spec", vocab=64, dim=64, n_heads=4,
        n_layers=2, seq_len=L, max_seq=L, compute_dtype="float32", remat=True,
    )


MODELS = {"moe_lm": _moe_lm, "transformer_lm": _transformer_lm}


def _batch():
    tokens = jnp.arange(B * L).reshape(B, L) % 64
    return {"tokens": tokens, "labels": (tokens + 1) % 64}


def _loss_and_grads(spec, params, budget):
    """(loss, gradients, what ``plan`` saw) with ``budget`` bytes to keep."""
    ctx = ParallelContext(remat_keep_bytes=budget)

    def loss(p):
        return spec.loss(spec.apply(p, _batch(), train=True, ctx=ctx), _batch())

    with remat.survey() as held:
        value, grads = jax.jit(jax.value_and_grad(loss))(params)
    return value, grads, held


def _tagged(spec, params):
    return _loss_and_grads(spec, params, 0)[2]


# ------------------------------------------------------------ (i) the same gradient


@pytest.mark.parametrize("model", sorted(MODELS))
@pytest.mark.parametrize("share", [0.0, 0.4, 1.0], ids=["keep_nothing", "keep_some", "keep_all"])
def test_a_kept_block_differentiates_as_a_recomputed_one(model, share):
    """Loss and every gradient leaf under a keep-set against the plain
    ``jax.checkpoint`` of every block (budget 0): a kept value is the very
    tensor the forward made, so nothing moves past float32 rounding."""
    spec = MODELS[model]()
    params = spec.init(jax.random.key(0))
    loss0, grads0, seen = _loss_and_grads(spec, params, 0)
    assert seen.kept_bytes == 0 and seen.tagged_bytes > 0
    budget = int(share * seen.tagged_bytes)
    loss, grads, held = _loss_and_grads(spec, params, budget)
    assert held.kept_bytes <= budget
    if share == 1.0:
        assert held.kept_bytes == held.tagged_bytes
    if share == 0.4:
        assert 0 < held.kept_bytes < held.tagged_bytes
        assert len(set(held.keep)) == 2  # the later layer keeps more: its own keep-set
    np.testing.assert_allclose(loss, loss0, rtol=1e-6)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-7)


# ------------------------------------------------------------ (ii) the chooser


def _sites(*rows):
    return [tuple(remat.Site(name, nbytes, work) for name, nbytes, work in row) for row in rows]


LAYERS = _sites(
    [("q", 100, 1000.0), ("attn_out", 110, 3300.0), ("mlp_up", 400, 4000.0)],
    [("q", 100, 1000.0), ("attn_out", 110, 3300.0), ("mlp_up", 400, 4000.0)],
)


@pytest.mark.parametrize(
    "budget,expected",
    [
        (0, [set(), set()]),
        (-5, [set(), set()]),
        (109, [set(), {"q"}]),                                     # the dearest does not fit, a later one does
        (110, [set(), {"attn_out"}]),                              # one site: the most work a byte, the later layer
        (220, [{"attn_out"}, {"attn_out"}]),
        (720, [{"attn_out"}, {"attn_out", "mlp_up", "q"}]),         # ties go to the later layer, in the block's order
        (10**9, [{"attn_out", "mlp_up", "q"}] * 2),
    ],
)
def test_the_chooser_spends_bytes_on_the_most_work_a_byte(budget, expected):
    keep = remat.choose(LAYERS, budget)
    assert [set(names) for names in keep] == expected
    kept = sum(s.nbytes for sites, names in zip(LAYERS, keep) for s in sites if s.name in names)
    assert kept <= max(budget, 0)
    # order: nothing left out is worth more a byte than something kept, unless it did not fit
    left = max(budget, 0) - kept
    worst_kept = min((s.work_per_byte for sites, names in zip(LAYERS, keep) for s in sites if s.name in names), default=None)
    for sites, names in zip(LAYERS, keep):
        for s in sites:
            if s.name not in names and worst_kept is not None and s.work_per_byte > worst_kept:
                assert s.nbytes > left


def test_an_attention_kernels_work_counts_its_scores():
    """A kernel's work is read off the cost it declares to XLA: its FLOPs and
    ``SCORE_WORK`` for every score's softmax."""
    from jax.experimental import pallas as pl

    cost = pl.CostEstimate(flops=1000, transcendentals=10, bytes_accessed=1)
    assert remat.kernel_work(cost) == 1000 + 10 * remat.SCORE_WORK


@pytest.mark.parametrize("model", sorted(MODELS))
def test_budget_zero_is_the_program_it_was(model):
    """Budget 0 — every CPU program — wraps a block in the plain
    ``jax.checkpoint(block_fn)`` and gives no tensor a name: the jaxpr of
    the gradient is, to the character, the one of a model whose blocks are
    wrapped by hand, and no ``name`` equation is in it."""
    spec = MODELS[model]()
    params = spec.init(jax.random.key(0))

    def loss(p):
        return spec.loss(spec.apply(p, _batch(), train=True, ctx=ParallelContext()), _batch())

    ours = str(jax.make_jaxpr(jax.grad(loss))(params))
    assert "name[" not in ours and "policy=None" in ours
    by_hand = remat.plan
    try:
        wrapped = {}  # one checkpoint a distinct block: layers alike are one function to jax
        remat.plan = lambda block_fns, layer_args, budget, inputs=1: [
            wrapped.setdefault(repr(block_fn), jax.checkpoint(block_fn)) for block_fn in block_fns
        ]
        theirs = str(jax.make_jaxpr(jax.grad(loss))(params))
    finally:
        remat.plan = by_hand
    assert ours == theirs


def test_off_the_tpu_the_trainer_resolves_no_budget():
    """``memory_stats()`` is None on the CPU: the trainer makes no plan, the
    context's budget stays 0 and the step keeps nothing."""
    spec = _moe_lm()
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    assert spec.rematerialises
    assert trainer._new_keep_plan() is None and trainer.keep_plan is None
    assert trainer.ctx.remat_keep_bytes == 0
    state = trainer.init_state(jax.random.key(0))
    _, metrics = trainer.train_step(state, trainer.shard_batch(_batch()))
    assert float(metrics["remat_bytes_kept"]) == 0.0 < float(metrics["remat_bytes_tagged"])


def test_a_model_that_does_not_rematerialise_is_given_no_plan(monkeypatch):
    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: 16 * 2**30)
    spec = dataclasses.replace(_moe_lm(), rematerialises=False)
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    assert trainer._new_keep_plan() is None


# ------------------------------------------------------------ (iii) no second product


def _products(jaxpr_text: str, shape: str) -> int:
    return len(re.findall(rf"{re.escape(shape)} = dot_general", jaxpr_text))


def test_a_kept_gate_is_not_multiplied_again():
    """One layer of ``moe_lm``: the gradient's jaxpr holds ``u @ w_gate`` and
    ``u @ w_up`` ([B, L, f]) twice each when the block keeps nothing (the
    forward and the rematerialised repeat) and once each when it keeps
    them; the third [B, L, f] product, the backward's ``dy @ w_down^T``,
    stays."""
    spec = load_model_spec(
        "elasticdl_tpu.models", "moe_lm.model_spec", vocab_size=64, hidden_size=64,
        num_attention_heads=4, num_hidden_layers=1, layer_types=["dense"], intermediate_size=160,
        seq_len=L, attention_class="eva", window_size=32, chunk_size=4, compute_dtype="float32", remat=True,
    )
    params = spec.init(jax.random.key(0))

    def text(budget):
        ctx = ParallelContext(remat_keep_bytes=budget)
        loss = lambda p: spec.loss(spec.apply(p, _batch(), train=True, ctx=ctx), _batch())  # noqa: E731
        with remat.survey() as held:
            return str(jax.make_jaxpr(jax.grad(loss))(params)), held

    wide = f"f32[{B},{L},160]"
    nothing, held = text(0)
    assert _products(nothing, wide) == 2 * 2 + 1
    (sites,) = held.layers
    gate_and_up = sum(s.nbytes for s in sites if s.name.startswith("mlp_"))
    # gate and up are the largest sites at the same work a byte as q, k, v: a
    # budget of exactly their bytes keeps them only if the order says so, so
    # hand the chooser everything and check the names
    kept, held = text(10**9)
    assert {"mlp_gate", "mlp_up"} <= set(held.keep[0]) and gate_and_up == 2 * B * L * 160 * 4
    assert _products(kept, wide) == 2 + 1
    assert kept.count("name[name=mlp_gate]") >= 1


# ------------------------------------------------------------ (iv) the two counts


@pytest.mark.parametrize("model", sorted(MODELS))
def test_the_two_counts_ride_the_steps_metrics(model, monkeypatch):
    """With a budget (a described limit, as on the chip) the trainer resolves
    ``remat_keep_bytes`` from its estimate, the model keeps within it, and
    the step's metrics carry ``remat_bytes_tagged`` (all sites, all layers)
    and ``remat_bytes_kept``, which the worker sums as its STEP_COUNTERS."""
    from elasticdl_tpu.worker import worker

    spec = MODELS[model]()
    tagged = _tagged(spec, spec.init(jax.random.key(0)))
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    state = trainer.init_state(jax.random.key(0))
    batch = trainer.shard_batch(_batch())
    # a limit that leaves about half the sites' bytes over the estimate
    probe = trainer_lib.KeepPlan(line=0, aim=0)
    trainer_lib._resolve_keep_budget(spec, ParallelContext(), probe, state, _batch())  # one device: no axis to bind
    aim = probe.estimate + tagged.tagged_bytes // 2
    limit = trainer_lib.REMAT_HEADROOM + trainer_lib.REMAT_REFIT_MARGIN + aim
    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: limit)
    # XLA:CPU's own account of this toy is not what the line is about
    monkeypatch.setattr(trainer_lib, "compiled_bytes", lambda compiled: 0)
    _, metrics = trainer.train_step(state, batch)
    plan = trainer.keep_plan
    assert plan.line - trainer_lib.REMAT_REFIT_MARGIN == plan.aim == aim
    # the budget: what the aim leaves over the estimate, and the last block's
    # sites on top where a block's backward is the peak (these toys; not gpt2m_job)
    expected = trainer_lib.KeepPlan(line=plan.line, aim=plan.aim)
    trainer_lib._resolve_keep_budget(spec, ParallelContext(), expected, state, _batch())
    last = sum(s.nbytes for s in tagged.layers[-1])
    assert plan.estimate == probe.estimate and plan.budget == expected.budget == tagged.tagged_bytes // 2 + last
    assert float(metrics["remat_bytes_tagged"]) == tagged.tagged_bytes == plan.tagged
    assert 0 < float(metrics["remat_bytes_kept"]) == plan.kept <= plan.budget
    assert {"remat_bytes_tagged", "remat_bytes_kept"} <= set(worker.STEP_COUNTERS) & set(worker.COUNTER_GAUGES)


def test_a_step_over_the_line_is_compiled_again_with_less(monkeypatch):
    """The check that makes an underestimate cheap: a compiled step that
    reads over the line is built again with the overshoot and a margin off
    the aim, until it fits or keeps nothing; the log says how many compiles
    that cost."""
    spec = _moe_lm()
    tagged = _tagged(spec, spec.init(jax.random.key(0))).tagged_bytes
    mesh = create_mesh(jax.devices()[:1], num_devices=1)
    trainer = Trainer(spec, JobConfig(distribution_strategy=DistributionStrategy.ALLREDUCE), mesh)
    state = trainer.init_state(jax.random.key(0))
    monkeypatch.setattr(trainer_lib, "REMAT_REFIT_MARGIN", 7)
    monkeypatch.setattr(trainer_lib, "_BLOCK_HELD", 0.0)    # the budget is the aim less the estimate, no more
    probe = trainer_lib.KeepPlan(line=0, aim=0)
    trainer_lib._resolve_keep_budget(spec, ParallelContext(), probe, state, _batch())  # one device: no axis to bind
    line = probe.estimate + tagged + 7        # everything fits the estimate
    monkeypatch.setattr(trainer_lib, "device_bytes_limit", lambda devices: trainer_lib.REMAT_HEADROOM + line)
    readings = iter([line + tagged // 2, line + 1, line - 1])
    monkeypatch.setattr(trainer_lib, "compiled_bytes", lambda compiled: next(readings))
    said = []
    monkeypatch.setattr(trainer_lib.logger, "info", lambda msg, *args: said.append(msg % args))
    _, metrics = trainer.train_step(state, trainer.shard_batch(_batch()))
    plan = trainer.keep_plan
    assert plan.line == line and plan.aim == line - 7 - (tagged // 2 + 7) - (1 + 7)
    assert plan.budget == plan.aim - plan.estimate
    assert 0 < float(metrics["remat_bytes_kept"]) == plan.kept <= plan.budget < tagged
    compiles = [line_ for line_ in said if "the step compiled to" in line_]
    assert len(compiles) == 3 and compiles[-1].endswith("compile 3")
