"""Operations a training step of the ``moe_lm`` decoder needs under
DeepSeek-V3's keys (latent attention, shared experts, leading dense layers,
a share of the routed experts held), from shapes.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every block's forward in the backward pass), and
neither are the experts a token is not routed to, nor the slots routed to
experts that are held elsewhere.

- ``active_matmul_params``: parameters that multiply a token's activations.
  Per layer the latent attention's ``wq`` (d x H x (nope + rot)), ``wkv_a``
  (d x (rank + rot)), ``wkv_b`` (rank x H x (nope + v)), ``wo`` (H x v x d);
  the leading dense layers' gated MLP (3 x d x intermediate_size); per
  expert layer the router (d x E), the shared experts (3 x d x n_shared x
  f) and the EXPECTED share of a token's ``top_k`` slots that falls on a
  held expert (top_k x held / E = 0.75 at 6 x 16 / 128) times an expert's 3
  x d x f; once the untied head (d x vocab).  The token look-up is a gather,
  the norms and the rotary turn are elementwise: neither counts.
- The flash kernels' FLOPs a visited (query, key) pair of a head, the
  query/key width (nope + rot = 192) and the value width (v = 128) apart:
  forward 2 x (192 + 128) = 640 — the score and the value product;
  backward 2 x (192 + 128 + 128 + 192 + 192) = 1,664 — the score again,
  dP, dV, dQ, dK.  A causal mask visits half of the B x H x L^2 pairs.
  ``flash_unit_flops`` = those pairs over the minibatch (ONE FLOP a pair);
  a forward call needs ``flash_fwd_units`` = 640 of it, the backward pair
  ``flash_bwd_units`` = 1,664, booked on its first kernel (dQ) and 0 on the
  second (``transformer_lm_flops``' convention).
- ``attention_flops_per_token`` = layers x H x L / 2 x 3 x 640: the model's
  need, forward and twice that backward (dP, dV, dQ, dK = 1,280; the score
  computed AGAIN is the kernels' way of not storing it, a recomputation,
  and counts in their roofline share only), as ``olmoe_flops`` counts it.
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term.
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x d x f.
  ``moe_slots_per_step`` = minibatch x L x top_k x expert layers (all the
  routers' slots); ``expert_flops_per_step`` is the EXPECTATION (slots x
  held / E x a slot's): ``expert_mxu_pct.mla`` does not read it, it counts
  the slots the steps really computed.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    layers, heads = int(p["num_hidden_layers"]), int(p["num_attention_heads"])
    nope, rot, v = int(p["qk_nope_head_dim"]), int(p["qk_rope_head_dim"]), int(p["v_head_dim"])
    rank = int(p["kv_lora_rank"])
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_tok"])
    held = int(p.get("experts_held") or experts)
    f_dense, f = int(p["intermediate_size"]), int(p["moe_intermediate_size"])
    dense_layers = min(int(p.get("first_k_dense_replace", 0)), layers)
    moe_layers = layers - dense_layers
    batch = int(traffic["minibatch_size"])
    attention_params = d * heads * (nope + rot) + d * (rank + rot) + rank * heads * (nope + v) + heads * v * d
    slots_held_per_token = top_k * held / experts
    per_expert_layer = d * experts + 3 * d * int(p.get("n_shared_experts", 0)) * f + slots_held_per_token * 3 * d * f
    active = layers * attention_params + dense_layers * 3 * d * f_dense + moe_layers * per_expert_layer + d * vocab
    fwd_pair, bwd_pair = 2 * (nope + rot + v), 2 * (2 * (nope + rot) + 2 * v + (nope + rot))
    attention = layers * heads * seq // 2 * 3 * fwd_pair
    slots = batch * seq * top_k * moe_layers
    per_slot = 3 * 3 * 2 * d * f
    return {
        "active_matmul_params": active,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * active + attention,
        "flash_unit_flops": batch * heads * seq * seq // 2,
        "flash_fwd_units": fwd_pair,
        "flash_bwd_units": bwd_pair,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots,
        "expert_flops_per_slot": per_slot,
        "expert_flops_per_step": slots * held / experts * per_slot,
    }
