"""What the per-family model files share (``test_keye_vl2.py``,
``test_latent_moe.py``, ``test_kimi_linear.py``, ``test_nemotron_h.py``,
``test_evabyte.py``, ``test_trinity_mini.py``): the spec from a family's
``KEYS``, seeded weights moved off the init's symmetries, the batch, a leaf by
its path, the family's plain reference (``benchmark/configs/*_reference.py``:
no code shared with the model or ``ops/``), and ONE memo of the two sides'
losses and gradients a (configuration, dtype, extra keys) — each side one
``jax.jit``, computed once a process and read by every case that asserts on
logits, loss, slots, counters or gradients.

Not collected (no ``test_`` in its name), no base class, no registry, no case
made from a table: a family's cases stay in its file under their names.
``tests/README.md`` has the rule this serves: a whole-model case reads the
memo and does not ``jax.jit`` the model again."""

from __future__ import annotations

import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.models.spec import load_model_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402


@functools.lru_cache(maxsize=None)
def reference(config: str):
    """The plain reference of ``benchmark/configs/<config>.json``, loaded once a process."""
    return resolve.load_module(os.path.join(BENCH_DIR, "configs", f"{config}_reference.py"))


def spec(keys: dict, dtype: str = "float32", **kw):
    return load_model_spec("elasticdl_tpu.models", "moe_lm.model_spec", compute_dtype=dtype, **{**keys, **kw})


def layers(model_spec):
    """The family's parts, a tuple a layer, as its builder made them."""
    return model_spec.init.keywords["layers"]


def weights(model_spec, move, seed: int = 0):
    """Seeded weights away from the init's symmetries.  ``move(name, a,
    noise)`` gives a leaf's value from the init's ``a``; ``noise()`` draws a
    standard normal of its shape from the next key of ONE stream, so which
    leaves draw (and in which order: the tree's) decides every value."""
    params = model_spec.init(jax.random.key(seed))
    keys = iter(jax.random.split(jax.random.key(seed + 1), len(jax.tree.leaves(params))))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: move(path[-1].key, a, lambda: jax.random.normal(next(keys), a.shape)), params)


def batch(keys: dict, b: int = 2, seed: int = 0, l: int | None = None):
    l = keys["seq_len"] if l is None else l
    toks = np.random.default_rng(seed).integers(0, keys["vocab_size"], (b, l + 1)).astype(np.int32)
    return {"tokens": jnp.asarray(toks[:, :-1]), "labels": jnp.asarray(toks[:, 1:])}


def leaf(tree, path: str):
    for key in path.split("/"):
        tree = tree[key]
    return tree


def loss_gradients_and_outputs(model_spec, data):
    """``w -> ((loss, gradients), outputs)`` of the system: the training
    forward's loss differentiated, and the evaluation forward's outputs."""
    def system(w):
        return jax.value_and_grad(lambda w: model_spec.loss(model_spec.apply(w, data, train=True), data))(w), model_spec.apply(w, data)

    return system


def reference_loss_and_gradients(ref, keys: dict, data):
    """``w -> ((loss, (logits, slots)), gradients)`` of a plain reference
    whose ``build(keys)`` gives ``forward(w, tokens) -> (logits, slots)``."""
    import optax

    forward = ref.build(dict(keys))

    def ref_loss(w):
        z, slots = forward(w, data["tokens"])
        return optax.softmax_cross_entropy_with_integer_labels(z, data["labels"]).mean(), (z, slots)

    return jax.value_and_grad(ref_loss, has_aux=True)


_BOTH_SIDES: dict = {}


def _both_sides(config: str, keys: dict, move, system=loss_gradients_and_outputs, plain=reference_loss_and_gradients,
                dtype: str = "float32", **extra):
    memo = (config, dtype, system.__qualname__, plain.__qualname__, repr(sorted(extra.items())))
    if memo not in _BOTH_SIDES:
        keys = {**keys, **extra}
        model_spec = spec(keys, dtype)
        params, data = weights(model_spec, move), batch(keys)
        reference_program = jax.jit(plain(reference(config), keys, data))
        with jax.default_matmul_precision("highest"):
            _BOTH_SIDES[memo] = jax.jit(system(model_spec, data))(params), reference_program(params), reference_program
    return _BOTH_SIDES[memo]


def system_and_reference(*args, **kwargs):
    """``(what the system gave, what the plain reference gave)`` on the
    family's seeded weights and batch — ``(config, keys, move, system=...,
    plain=..., dtype=..., **extra keys)``: ``system(spec, batch)`` and
    ``plain(reference, keys, batch)`` each make ONE function of the weights,
    compiled as ONE program under ``highest`` matmul precision (op by op,
    three times the seconds for the same bits) and run once a process; a
    second worker of the suite finds both in the run's compile cache."""
    return _both_sides(*args, **kwargs)[:2]


def reference_program(*args, **kwargs):
    """The compiled function of the weights that gave the reference's side
    (the same arguments): a case that steps the reference along beside the
    trainer calls THIS, under ``highest`` precision, and compiles nothing."""
    return _both_sides(*args, **kwargs)[2]
