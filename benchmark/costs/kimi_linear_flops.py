"""Operations and bytes a training step of the ``moe_lm`` decoder needs under
``kimi_linear``'s keys (Kimi Delta Attention on ``kda_layers``, latent
attention without a rotary turn on ``full_attn_layers``, a leading dense
layer, a share of the routed experts held), from shapes alone: the same
whatever implements a kernel.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every layer's forward in the backward pass), nor the
experts a token is not routed to, nor the slots routed to experts held
elsewhere.

- ``active_matmul_params``: parameters that multiply a token's activations.
  A KDA layer's ``Wq``, ``Wk``, ``Wv``, ``Wo`` (d x H x dk each way), the two
  low-rank pairs (2 x (d x dk + dk x H x dk): the decay's and the output
  gate's) and ``Wb`` (d x H); a latent-attention layer's ``wq`` (d x H x (nope
  + rot)), ``wkv_a`` (d x (rank + rot)), ``wkv_b`` (rank x H x (nope + v)),
  ``wo`` (H x v x d); the leading dense layers' gated MLP (3 x d x
  intermediate_size); an expert layer's router (d x E), shared expert (3 x d
  x f) and the EXPECTED share of a token's ``top_k`` slots that falls on a
  held expert (top_k x held / E = 0.25 at 8 x 8 / 256) times an expert's 3 x
  d x f; once the untied head (d x vocab).  The token look-up is a gather;
  the norms, the convolutions' 4 taps, silu, l2norm, softplus and the gates
  are elementwise: none counts.
- The op's needed FLOPs a position and KDA layer, forward
  (``kda_scan_flops_per_position``), **in its CHUNKED form at chunk 64** (a
  position-by-position recurrence needs 6 x dk x dv a head and no MXU; the
  chunked form is what any implementation on this hardware computes): a head
  the two masks' products (A and P: 2 x chunk x dk each), the solve's
  triangle (chunk x (dk + dv)), ``W S_0`` and the state's read-out (2 x dk x
  dv each), ``P U`` (2 x chunk x dv) and the chunk's end state (2 x dk x dv):
  ``ops/delta_rule.rule_flops``' count.  Backward twice that.
  ``kda_scan_flops_per_step`` = minibatch x L x KDA layers x 3 x that.
- The op's needed bytes (``kda_scan_bytes_per_step``): q, k, v (bfloat16),
  the log-decay g (float32, a channel each) and beta (float32) read and o
  written once forward; backward reads them and do and writes dq, dk, dv,
  dg, dbeta: twice the forward's; KDA layers.
- ``kda_glue_bytes_per_step``: what the three convolutions (+ silu), the two
  l2norms, the decay's softplus and the gated norm a head have to move, KDA
  layers, forward + backward once each: a convolution reads and writes its
  channels (2 C forward, 3 C backward, bfloat16, C = H x dk, three of them);
  an l2norm reads and writes (2 C forward, 3 C backward, two of them); the
  softplus reads C bfloat16 and writes C float32 (6 bytes a channel forward,
  the same backward); the gated norm reads o and the gate and writes (3 C
  forward, 5 C backward).
- The flash kernels' FLOPs a visited (query, key) pair of a head, the
  query/key width (nope + rot = 192) and the value width (v = 128) apart:
  forward 2 x (192 + 128) = 640, backward 2 x (192 + 128 + 128 + 192 + 192) =
  1,664 (``kanana2_flops``' count: the same kernels and operand lists; the
  rotary columns are there, unturned).  ``flash_unit_flops`` = the causal half
  of minibatch x H x L^2 pairs, ONE FLOP a pair; the backward pair booked on
  its first kernel.
- ``attention_flops_per_token`` = latent layers x H x L / 2 x 3 x 640.
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term +
  3 x the op's forward FLOPs a position x KDA layers.
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x d x f.
  ``moe_slots_per_step`` = minibatch x L x top_k x expert layers (all the
  routers' slots); ``expert_flops_per_step`` is the EXPECTATION.
- ``params_kda_mixer`` / ``params_latent_mixer`` / ``params_dense_ffn`` /
  ``params_expert_ffn`` / ``params_total``: every parameter held (matrices,
  taps, vectors, gains, a layer's two norms with its mixer and feed-forward
  one each), for the sizing arithmetic in the configuration's file.
"""

KDA_CHUNK = 64


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    layers, heads = int(p["num_hidden_layers"]), int(p["num_attention_heads"])
    kda = p["linear_attn_config"]
    n_kda, n_full = len(kda["kda_layers"]), len(kda["full_attn_layers"])
    kda_heads, hd, taps = int(kda["num_heads"]), int(kda["head_dim"]), int(kda["short_conv_kernel_size"])
    inner = kda_heads * hd
    nope, rot, v = int(p["qk_nope_head_dim"]), int(p["qk_rope_head_dim"]), int(p["v_head_dim"])
    rank = int(p["kv_lora_rank"])
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_token"])
    held = int(p.get("experts_held") or experts)
    f_dense, f, shared = int(p["intermediate_size"]), int(p["moe_intermediate_size"]), int(p.get("num_shared_experts", 0))
    dense_layers = min(int(p.get("first_k_dense_replace", 0)), layers)
    moe_layers = layers - dense_layers
    batch = int(traffic["minibatch_size"])

    kda_matmul = 4 * d * inner + 2 * (d * hd + hd * inner) + d * kda_heads
    latent_matmul = d * heads * (nope + rot) + d * (rank + rot) + rank * heads * (nope + v) + heads * v * d
    expert = 3 * d * f
    expert_outside = d * experts + shared * expert
    active = (
        n_kda * kda_matmul + n_full * latent_matmul + dense_layers * 3 * d * f_dense
        + moe_layers * (expert_outside + top_k * held / experts * expert) + d * vocab
    )
    params = {
        "kda_mixer": kda_matmul + 3 * taps * inner + inner + kda_heads + hd + d,  # taps, dt_bias, A_log, the norm's gain, attn_norm
        "latent_mixer": latent_matmul + rank + d,  # kv_norm, attn_norm
        "dense_ffn": 3 * d * f_dense + d,  # ffn_norm
        "expert_ffn": expert_outside + experts + held * expert + d,  # the correction bias, ffn_norm
    }
    scan_position = kda_heads * (4 * KDA_CHUNK * hd + KDA_CHUNK * 2 * hd + 2 * KDA_CHUNK * hd + 6 * hd * hd)
    scan_bytes_position = 3 * 2 * inner + 4 * inner + 4 * kda_heads + 2 * inner  # q, k, v | g | beta | o
    glue_bytes_position = 3 * 2 * (2 + 3) * inner + 2 * 2 * (2 + 3) * inner + 2 * 6 * inner + 2 * (3 + 5) * inner
    fwd_pair, bwd_pair = 2 * (nope + rot + v), 2 * (2 * (nope + rot) + 2 * v + (nope + rot))
    attention = n_full * heads * seq // 2 * 3 * fwd_pair
    slots = batch * seq * top_k * moe_layers
    per_slot = 3 * 3 * 2 * d * f
    positions = batch * seq
    return {
        "active_matmul_params": active,
        "params_kda_mixer": params["kda_mixer"],
        "params_latent_mixer": params["latent_mixer"],
        "params_dense_ffn": params["dense_ffn"],
        "params_expert_ffn": params["expert_ffn"],
        "params_total": (
            n_kda * params["kda_mixer"] + n_full * params["latent_mixer"] + dense_layers * params["dense_ffn"]
            + moe_layers * params["expert_ffn"] + 2 * vocab * d + d
        ),
        "attention_flops_per_token": attention,
        "kda_scan_flops_per_position": scan_position,
        "train_flops_per_token": 6 * active + attention + 3 * n_kda * scan_position,
        "kda_scan_flops_per_step": positions * n_kda * 3 * scan_position,
        "kda_scan_bytes_per_step": positions * n_kda * 3 * scan_bytes_position,
        "kda_glue_bytes_per_step": positions * n_kda * glue_bytes_position,
        "flash_unit_flops": batch * heads * seq * seq // 2,
        "flash_fwd_units": fwd_pair,
        "flash_bwd_units": bwd_pair,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots,
        "expert_flops_per_slot": per_slot,
        "expert_flops_per_step": slots * held / experts * per_slot,
    }
