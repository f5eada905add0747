"""Operations a training step of the ``moe_lm`` decoder needs, from shapes.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every block's forward in the backward pass), and
neither are the experts a token is not routed to (56 of 64 at top-8).

- ``active_matmul_params``: parameters that multiply a token's
  activations.  Per layer: attention ``wq``, ``wk``, ``wv``, ``wo``
  (4 x d x d), the router (d x E), and ``num_experts_per_tok`` experts of
  ``w_gate``, ``w_up``, ``w_down`` (3 x d x f each); once: the untied
  output head (d x vocab).  The token look-up is a gather, the norms and
  the rotary turn are elementwise: neither counts.  At OLMoE's widths and
  one layer: 16,777,216 + 131,072 + 8 x 6,291,456 + 103,022,592 =
  170,262,528.
- ``attention_flops_per_token`` = 6 x L x d per layer: QK^T and PV are 2
  matmuls of 2 x L x d FLOPs a token forward, twice that backward, and a
  causal mask needs half (as ``transformer_lm_flops``).
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term.
- ``flash_unit_flops`` = B x H x L^2 x hd, one causal L x L matmul against
  the head dimension over the minibatch; a flash forward needs 2 units, the
  backward pair 5, booked on its first kernel (``transformer_lm_flops``).
- ``expert_flops_per_step``: the grouped matmuls' needed work in a step,
  forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x slots x d
  x f per routed layer, slots = minibatch x L x num_experts_per_tok.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, f, vocab = int(p["hidden_size"]), int(p["intermediate_size"]), int(p["vocab_size"])
    layers, heads, seq = int(p["num_hidden_layers"]), int(p["num_attention_heads"]), int(p["seq_len"])
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_tok"])
    batch = int(traffic["minibatch_size"])
    per_layer = 4 * d * d + d * experts + top_k * 3 * d * f
    active = layers * per_layer + d * vocab
    attention = 6 * seq * d * layers
    slots = batch * seq * top_k
    return {
        "active_matmul_params": active,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * active + attention,
        "flash_unit_flops": batch * heads * seq * seq * (d // heads),
        "flash_fwd_units": 2,
        "flash_bwd_units": 5,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots * layers,
        "expert_flops_per_step": 3 * 3 * 2 * slots * d * f * layers,
    }
