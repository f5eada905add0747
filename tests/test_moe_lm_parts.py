"""The seam ``moe_lm`` is parted along: a layer is a tuple of ``(norm's
name, part)`` and the model follows from the list.  A part the file has
never heard of — defined HERE — goes between two real layers and trains,
with its parameter initialised, its gradient finite and its counter in the
step's metrics, through the function ``model_spec`` itself ends in: no edit
to ``_block``, ``_apply`` or the init.
"""

from __future__ import annotations

import dataclasses
import inspect

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common.config import JobConfig
from elasticdl_tpu.models import attentions, moe_lm
from elasticdl_tpu.models.parts import Part
from elasticdl_tpu.ops.embedding import ParallelContext
from elasticdl_tpu.parallel.mesh import create_mesh
from elasticdl_tpu.parallel.trainer import Trainer


@dataclasses.dataclass(frozen=True)
class Scaled(Part):
    """A toy mixer: the normed stream times ONE learned scalar."""

    start: float

    counters = {"toy_positions": "positions the toy mixer scaled, summed over layers, steps and devices"}

    def init(self, draw, d):
        return {"toy_scale": jnp.full((), self.start, jnp.float32)}

    def apply(self, u, params, positions, axis, cast):
        return u * cast(params["toy_scale"]), {"toy_positions": jnp.float32(u.shape[0] * u.shape[1])}


def _spec(layers):
    keys = {
        **moe_lm._DEFAULTS, "vocab_size": 64, "hidden_size": 32, "seq_len": 24, "compute_dtype": "float32",
        "learning_rate": 1e-2, "remat": True,
    }
    own = inspect.signature(moe_lm._spec_of_layers).parameters
    return moe_lm._spec_of_layers(layers, 8, **{key: keys[key] for key in own if key in keys})


def test_a_part_defined_outside_the_file_trains_between_two_real_layers():
    attention = attentions.QKNormAttention(n_heads=2, theta=1e4, eps=1e-5)
    experts = moe_lm.RoutedExperts(moe_lm.Router(n_experts=4, top_k=2, held=4, first_held=0), width=16)
    real = (("attn_norm", attention), ("ffn_norm", experts))
    spec = _spec([real, (("toy_norm", Scaled(0.5)),), (("attn_norm", attention), ("ffn_norm", moe_lm.GatedMLP(48)))])
    assert set(spec.step_counters) == set(moe_lm.MOE_COUNTERS) | {"toy_positions"} and spec.rematerialises
    params = spec.init(jax.random.key(0))
    assert sorted(params["blocks"]["b01"]) == ["toy_norm", "toy_scale"] and float(params["blocks"]["b01"]["toy_scale"]) == 0.5
    assert sorted(params["blocks"]["b02"]) == ["attn_norm", "ffn_norm", "k_norm", "q_norm", "w_down", "w_gate", "w_up", "wk", "wo", "wq", "wv"]
    tokens = jax.random.randint(jax.random.key(1), (2, 25), 0, 64)
    batch = {"tokens": np.asarray(tokens[:, :-1]), "labels": np.asarray(tokens[:, 1:])}

    # the block as a training step traces it: every layer rematerialised (ops/remat.plan, a block a layer)
    def loss(p):
        return spec.loss(spec.apply(p, batch, train=True, ctx=ParallelContext()), batch)

    grads = jax.jit(jax.grad(loss))(params)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))
    assert float(jnp.abs(grads["blocks"]["b01"]["toy_scale"])) > 0
    assert "policy=None" in str(jax.make_jaxpr(jax.grad(loss))(params))  # jax.checkpoint, keeping nothing
    trainer = Trainer(spec, JobConfig(), create_mesh(num_devices=1))
    state = trainer.init_state(jax.random.key(0))
    losses = []
    for _ in range(4):
        state, metrics = trainer.train_step(state, trainer.shard_batch(batch))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    assert float(metrics["toy_positions"]) == 2 * 24 and float(metrics["moe_slots"]) == 2 * 24 * 2
    assert float(state.params["blocks"]["b01"]["toy_scale"]) != 0.5


def test_two_parts_of_a_layer_may_not_name_a_parameter_alike():
    import pytest

    attention = attentions.QKNormAttention(n_heads=2, theta=1e4, eps=1e-5)
    spec = _spec([(("attn_norm", attention), ("ffn_norm", attention))])
    with pytest.raises(ValueError, match="name a parameter alike"):
        jax.eval_shape(spec.init, jax.random.key(0))
