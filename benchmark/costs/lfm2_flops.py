"""Operations and bytes a training step of the ``moe_lm`` decoder needs under
``lfm2_moe``'s keys (LFM2: a double-gated short convolution on the ``conv``
layers, grouped-query attention with a norm a head and the rotary turn on the
``full_attention`` ones, leading dense layers, a share of the routed experts
held, a TIED head), from shapes alone: the same whatever implements a kernel.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every layer's forward in the backward pass), nor the
experts a token is not routed to, nor the slots routed to experts held
elsewhere, nor a (query, key) pair the causal mask hides.

- ``active_matmul_params``: parameters that multiply a token's activations.
  A convolution layer's ``W_in`` (d x 3d) and ``W_out`` (d x d); an attention
  layer's ``wq``, ``wo`` (d x H x hd each) and ``wk``, ``wv`` (d x G x hd each);
  the leading dense layers' gated MLP (3 x d x intermediate_size); an expert
  layer's router (d x E) and the EXPECTED share of a token's ``top_k`` slots
  that falls on a held expert (top_k x held / E = 1 at 4 x 8 / 32) times an
  expert's 3 x d x f; once the head (d x vocab: the tied table multiplies a
  token's activations once, as a head; the look-up is a gather).  The norms
  (a part's, a head's), the rotary turn, the repeat and the operator's two
  gates and K taps (2 + 2 K multiply-adds a channel: 16 k of 100 M a token)
  are elementwise: none counts.
- ``gated_conv_bytes_per_step``: what the operator ``C * conv(B * z)`` has to
  move, every convolution layer, forward and backward ONCE each, bfloat16, a
  position and channel: forward three reads (B, C, z) and one write (8
  bytes), backward four reads (B, C, z and the cotangent) and three writes
  (14 bytes): 22 bytes an element, minibatch x L x d elements a layer; the
  taps and their gradient (K x d float32) are nothing beside them.
- The attention's FLOPs a visible (query, key) pair of a head: forward 2 x
  (hd + hd) = 256 at hd 64, backward 2 x 5 x hd = 640.  ``flash_unit_flops``
  = minibatch x H x L^2 / 2: ONE FLOP a pair of the causal half;
  ``flash_fwd_units`` 256, ``flash_bwd_units`` 640 booked on the backward's
  first kernel (dQ), ``flash_bwd_second_units`` 0 (dK/dV: its time counts,
  its FLOPs are in the pair).
- ``attention_flops_per_token`` = 3 x 256 x H x attention layers x L / 2.
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term.
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x d x f.
  ``moe_slots_per_step`` = minibatch x L x top_k x expert layers (all the
  routers' slots); ``expert_flops_per_step`` is the EXPECTATION.
- ``params_conv`` / ``params_attention`` / ``params_dense_ffn`` /
  ``params_expert_ffn`` / ``params_total``: every parameter held (matrices,
  the taps, the gains a head, a layer's two norms, the correction bias), for
  the sizing arithmetic in the configuration's file.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    heads, kv_heads = int(p["num_attention_heads"]), int(p["num_key_value_heads"])
    hd, taps = d // heads, int(p["conv_L_cache"])
    kinds, layers = list(p["layer_types"]), int(p["num_hidden_layers"])
    n_conv, n_attention = kinds.count("conv"), kinds.count("full_attention")
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_tok"])
    held = int(p.get("experts_held") or experts)
    f_dense, f = int(p["intermediate_size"]), int(p["moe_intermediate_size"])
    dense_layers = min(int(p.get("num_dense_layers", 0)), layers)
    moe_layers = layers - dense_layers
    batch = int(traffic["minibatch_size"])
    tied = bool(p.get("tie_word_embeddings"))

    conv_matmul = 4 * d * d
    attention_matmul = 2 * d * heads * hd + 2 * d * kv_heads * hd
    expert = 3 * d * f
    active = (
        n_conv * conv_matmul + n_attention * attention_matmul + dense_layers * 3 * d * f_dense
        + moe_layers * (d * experts + top_k * held / experts * expert) + d * vocab
    )
    params = {
        "conv": conv_matmul + taps * d + d,  # the taps; operator_norm
        "attention": attention_matmul + 2 * hd + d,  # q_norm, k_norm; operator_norm
        "dense_ffn": 3 * d * f_dense + d,  # ffn_norm
        "expert_ffn": d * experts + experts + held * expert + d,  # the correction bias; ffn_norm
    }
    pairs_full = seq * seq // 2
    fwd_pair, bwd_pair = 2 * 2 * hd, 2 * 5 * hd
    attention = 3 * fwd_pair * heads * n_attention * pairs_full // seq
    slots = batch * seq * top_k * moe_layers
    per_slot = 3 * 3 * 2 * d * f
    return {
        "active_matmul_params": active,
        "params_conv": params["conv"],
        "params_attention": params["attention"],
        "params_dense_ffn": params["dense_ffn"],
        "params_expert_ffn": params["expert_ffn"],
        "params_total": (
            n_conv * params["conv"] + n_attention * params["attention"] + dense_layers * params["dense_ffn"]
            + moe_layers * params["expert_ffn"] + (1 if tied else 2) * vocab * d + d
        ),
        "pairs_full": pairs_full,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * active + attention,
        "gated_conv_bytes_per_step": batch * seq * d * n_conv * 2 * (4 + 7),
        "flash_unit_flops": batch * heads * pairs_full,
        "flash_fwd_units": fwd_pair,
        "flash_bwd_units": bwd_pair,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots,
        "expert_flops_per_slot": per_slot,
        "expert_flops_per_step": slots * held / experts * per_slot,
    }
