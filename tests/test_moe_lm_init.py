"""``moe_lm.model_spec(...).init`` pinned: under each benchmark
configuration's keys the tree it gives at the PUBLISHED widths (names,
shapes, dtypes; ``jax.eval_shape``, nothing is drawn) and, at cut widths
under the same keys, every VALUE ``init(key(0))`` draws.  The plain
references take this tree and every cell starts from these draws, so a PR
that rearranges the init (which part draws from which key of the stream)
shows here before a chip sees it.  PINNED in PR 45 at the values the PARENT
commit (9611bdf) gives; a PR that changes a draw on purpose re-pins and says so.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax
import numpy as np
import pytest

from elasticdl_tpu.models.spec import load_model_spec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: configuration -> (leaves, parameters, sha256 of the listing) at the published widths
PUBLISHED = {
    "olmoe_1b_7b_l1": (15, 625616896, "f62dd4dfae43ce8386a98308310b6fa962f8ba3b0844c4cc5be5dc79d512eabd"),
    "kanana2_30b_a3b_ep8_l5": (73, 575955968, "df2b8c731dfdd69ed0565d597d706a1210c925082dc1b4fce258e82835c978f1"),
    "evabyte_6b5_tp2_l4": (47, 687132672, "0cdee88e365032dc813c887419a92b5b76ed01410ca612750bd07ebe979970ba"),
    "nemotron3_super_tp4_ep64_l11": (98, 773582304, "75ee50aac776c8def43cc8f2f5b399647d4e7cc6088de3e59e27ca952a4fd043"),
}
#: configuration -> the keys that cut it to a size the CPU draws in a moment
#: (each a key the configuration's file sets: the same family, smaller)
CUT = {
    "olmoe_1b_7b_l1": dict(
        vocab_size=256, hidden_size=64, num_attention_heads=4, num_hidden_layers=2, num_experts=8,
        num_experts_per_tok=2, intermediate_size=32, seq_len=128,
    ),
    "kanana2_30b_a3b_ep8_l5": dict(
        vocab_size=256, hidden_size=64, num_attention_heads=4, num_hidden_layers=3, num_experts=16, experts_held=4,
        first_expert_held=4, num_experts_per_tok=3, intermediate_size=96, moe_intermediate_size=32, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, seq_len=128,
    ),
    "evabyte_6b5_tp2_l4": dict(
        hidden_size=64, num_attention_heads=4, heads_held=2, num_hidden_layers=2, layer_types=["dense", "dense"],
        intermediate_size=96, window_size=64, chunk_size=8, seq_len=203,
    ),
    "nemotron3_super_tp4_ep64_l11": dict(
        vocab_size=96, hidden_size=32, num_hidden_layers=5, hybrid_override_pattern="EMEM*", mamba_num_heads=8,
        mamba_heads_held=4, mamba_head_dim=8, n_groups=4, ssm_state_size=8, chunk_size=16, num_attention_heads=8,
        heads_held=2, head_dim=8, num_experts=16, experts_held=4, first_expert_held=4, num_experts_per_tok=5,
        moe_latent_size=16, moe_intermediate_size=24, moe_shared_expert_intermediate_size=40, seq_len=48,
    ),
}
#: configuration -> sha256 of the cut model's leaves, in the listing's order
CUT_VALUES = {
    "olmoe_1b_7b_l1": "0045acaf3858685b67816e0d561f219a160aea16699a7eed99669aa0f162d3f4",
    "kanana2_30b_a3b_ep8_l5": "b0384e5d0c73816cc3f3ad0b2d82c7c1473743fc5aeb4f96ba3b1e8256a56de6",
    "evabyte_6b5_tp2_l4": "b64ac4c2eff0434004186b593ca94b6c2e04729c6cb3c15ee33159d38501e120",
    "nemotron3_super_tp4_ep64_l11": "60f3088a61cbfab60e6e722f55407654fce7f8253cb93fb8d3febf95c3634429",
}


def _spec(config: str, **cut):
    with open(os.path.join(ROOT, "benchmark", "configs", config + ".json")) as f:
        published = json.load(f)
    assert published["model_def"] == "moe_lm.model_spec" and set(cut) <= set(published["model_params"])
    return load_model_spec("elasticdl_tpu.models", published["model_def"], **{**published["model_params"], **cut})


def _leaves(tree):
    """[(``blocks/b00/wq``, leaf)] in the tree's own (sorted) order."""
    return [
        ("/".join(str(key.key) for key in path), leaf)
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


@pytest.mark.parametrize("config", sorted(PUBLISHED))
def test_the_published_widths_give_the_pinned_tree(config):
    leaves = _leaves(jax.eval_shape(_spec(config).init, jax.random.key(0)))
    listing = "\n".join(f"{name} {tuple(leaf.shape)} {leaf.dtype}" for name, leaf in leaves)
    got = (len(leaves), sum(int(np.prod(leaf.shape)) for _, leaf in leaves), hashlib.sha256(listing.encode()).hexdigest())
    assert got == PUBLISHED[config], listing


@pytest.mark.parametrize("config", sorted(CUT_VALUES))
def test_the_cut_widths_draw_the_pinned_values(config):
    digest = hashlib.sha256()
    for name, leaf in _leaves(_spec(config, **CUT[config]).init(jax.random.key(0))):
        digest.update(name.encode() + np.ascontiguousarray(np.asarray(leaf)).tobytes())
    assert digest.hexdigest() == CUT_VALUES[config]
