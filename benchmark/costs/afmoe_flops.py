"""Operations and bytes a training step of the ``moe_lm`` decoder needs under
``afmoe``'s keys (Trinity: attention under a window that moves with the query
on the ``sliding_attention`` layers, full causal attention on the
``full_attention`` ones, H query heads over G key/value heads, a gate on the
output; leading dense layers, a share of the routed experts held), from
shapes alone: the same whatever implements a kernel.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every layer's forward in the backward pass), nor the
experts a token is not routed to, nor the slots routed to experts held
elsewhere, nor a (query, key) pair the mask hides — whatever a kernel's
tiles multiply.

- ``active_matmul_params``: parameters that multiply a token's activations.
  An attention layer's ``wq``, ``wz`` (the gate) and ``wo`` (d x H x hd each)
  and ``wk``, ``wv`` (d x G x hd each: the key/value heads are G, repeated
  or not); the leading dense layers' gated MLP (3 x d x intermediate_size);
  an expert layer's router (d x E), shared experts (3 x d x f each) and the
  EXPECTED share of a token's ``top_k`` slots that falls on a held expert
  (top_k x held / E = 1 at 8 x 16 / 128) times an expert's 3 x d x f; once
  the untied head (d x vocab).  The token look-up is a gather; the norms
  (a part's two, a head's), the rotary turn, the repeat and the gate's
  sigmoid are elementwise: none counts.
- The attention's FLOPs a visible (query, key) pair of a head: forward 2 x
  (hd + hd) = 512 at hd 128 (q . k and p . v), backward 2 x 5 x hd = 1,280
  (the score again, dp, dq, dk, dv).  ``pairs_window`` = W (W + 1) / 2 + (L
  - W) W, the pairs INSIDE the windows of a sequence of L a head (every
  query's own key included); ``pairs_full`` = L^2 / 2 (the causal half, as
  the other flash cells count it).
- ``window_unit_flops`` = minibatch x H x pairs_window: ONE FLOP a pair
  inside the windows, whatever the kernels visit; ``window_fwd_units`` 512,
  ``window_bwd_units`` 1,280 booked on the backward's first kernel (dQ),
  ``window_bwd_second_units`` 0 (dK/dV: its time counts, its FLOPs are in
  the pair).  ``flash_unit_flops`` / ``flash_*_units``: the same for the
  FULL layers' calls, over pairs_full.
- ``attention_flops_per_token`` = 3 x 512 x H x (sliding layers x
  pairs_window + full layers x pairs_full) / L.
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term.
- ``attn_glue_bytes_per_step``: what the norms a head, the rotary turn, the
  key/value repeat and the output's gate have to move, every layer, forward
  and backward once each, bfloat16, C_q = H x hd and C_k = G x hd a position:
  forward the norm (and turn) of q and of k in one pass each (2 C_q + 2
  C_k), the repeat of k and of v (2 (C_k + C_q)) and the gate (o, z in, the
  product out: 3 C_q); backward 3 C_q + 3 C_k, 2 (C_q + C_k) and 5 C_q.
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x d x f.
  ``moe_slots_per_step`` = minibatch x L x top_k x expert layers (all the
  routers' slots); ``expert_flops_per_step`` is the EXPECTATION.
- ``params_attention`` / ``params_dense_ffn`` / ``params_expert_ffn`` /
  ``params_total``: every parameter held (matrices, the gains a head, a
  part's two norms, the correction bias), for the sizing arithmetic in the
  configuration's file.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    heads, kv_heads, hd = int(p["num_attention_heads"]), int(p["num_key_value_heads"]), int(p["head_dim"])
    kinds, window = list(p["layer_types"]), int(p["sliding_window"])
    layers = int(p["num_hidden_layers"])
    n_sliding, n_full = kinds.count("sliding_attention"), kinds.count("full_attention")
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_tok"])
    held = int(p.get("experts_held") or experts)
    f_dense, f, shared = int(p["intermediate_size"]), int(p["moe_intermediate_size"]), int(p.get("num_shared_experts", 0))
    dense_layers = min(int(p.get("num_dense_layers", 0)), layers)
    moe_layers = layers - dense_layers
    batch = int(traffic["minibatch_size"])

    c_q, c_k = heads * hd, kv_heads * hd
    attention_matmul = 3 * d * c_q + 2 * d * c_k
    expert = 3 * d * f
    expert_outside = d * experts + shared * expert
    active = (
        layers * attention_matmul + dense_layers * 3 * d * f_dense
        + moe_layers * (expert_outside + top_k * held / experts * expert) + d * vocab
    )
    params = {
        "attention": attention_matmul + 2 * hd + 2 * d,  # q_norm, k_norm; attn_norm, post_attn_norm
        "dense_ffn": 3 * d * f_dense + 2 * d,  # ffn_norm, post_ffn_norm
        "expert_ffn": expert_outside + experts + held * expert + 2 * d,  # the correction bias; ffn_norm, post_ffn_norm
    }
    reach = min(window, seq)
    pairs_window = reach * (reach + 1) // 2 + (seq - reach) * reach
    pairs_full = seq * seq // 2
    fwd_pair, bwd_pair = 2 * 2 * hd, 2 * 5 * hd
    attention = 3 * fwd_pair * heads * (n_sliding * pairs_window + n_full * pairs_full) // seq
    glue_bytes_position = 2 * ((2 + 2 + 3 + 3 + 2 + 5) * c_q + (2 + 2 + 3 + 2) * c_k)
    slots = batch * seq * top_k * moe_layers
    per_slot = 3 * 3 * 2 * d * f
    return {
        "active_matmul_params": active,
        "params_attention": params["attention"],
        "params_dense_ffn": params["dense_ffn"],
        "params_expert_ffn": params["expert_ffn"],
        "params_total": (
            layers * params["attention"] + dense_layers * params["dense_ffn"] + moe_layers * params["expert_ffn"] + 2 * vocab * d + d
        ),
        "pairs_window": pairs_window,
        "pairs_full": pairs_full,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * active + attention,
        "attn_glue_bytes_per_step": batch * seq * layers * glue_bytes_position,
        "window_unit_flops": batch * heads * pairs_window,
        "window_fwd_units": fwd_pair,
        "window_bwd_units": bwd_pair,
        "window_bwd_second_units": 0,
        "flash_unit_flops": batch * heads * pairs_full,
        "flash_fwd_units": fwd_pair,
        "flash_bwd_units": bwd_pair,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots,
        "expert_flops_per_slot": per_slot,
        "expert_flops_per_step": slots * held / experts * per_slot,
    }
