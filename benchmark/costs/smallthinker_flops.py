"""Operations and bytes a training step of the ``moe_lm`` decoder needs under
``smallthinker``'s keys (SmallThinker-21BA3B: attention over the last
``sliding_window_size`` keys on the layers ``sliding_window_layout`` marks 1 and
over every earlier key on those it marks 0, H query heads over G key/value
heads with no norm a head and no gate, the rotary turn where ``rope_layout``
says; relu-gated experts in EVERY layer behind a router that reads the rows
the attention reads, a share of them held), from shapes alone: the same
whatever implements a kernel.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every layer's forward in the backward pass), nor the
experts a token is not routed to, nor the slots routed to experts held
elsewhere, nor a (query, key) pair the mask hides — whatever a kernel's
tiles multiply.

- ``active_matmul_params``: parameters that multiply a token's activations.
  A layer's ``wq`` and ``wo`` (d x H x hd each), ``wk``, ``wv`` (d x G x hd
  each: the key/value heads are G, repeated or not), its router (d x E) and
  the EXPECTED share of a token's ``top_k`` slots that falls on a held expert
  (top_k x held / E = 0.75 at 6 x 8 / 64) times an expert's 3 x d x f; once
  the untied head (d x vocab).  The token look-up is a gather; the norms, the
  rotary turn, the repeat, the relu and the gate's product are elementwise:
  none counts.
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
- ``attn_glue_bytes_per_step``: what the rotary turn and the key/value
  repeat have to move (the part has no norm a head and no gate), forward and
  backward once each, bfloat16, C_q = H x hd and C_k = G x hd a position: a
  layer that turns reads and writes q and k (2 C_q + 2 C_k) each way; every
  layer repeats k and v (2 (C_k + C_q)) and sums the repeats' cotangents back
  (2 (C_q + C_k)).
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 3 projections x 3 (forward, dx, dw) x 2 x d x f.
  ``moe_slots_per_step`` = minibatch x L x top_k x layers (all the routers'
  slots); ``expert_flops_per_step`` is the EXPECTATION.
- ``params_layer`` / ``params_total``: every parameter held (matrices and
  the two gains a layer; the final gain), for the sizing arithmetic in the
  configuration's file.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    heads, kv_heads, hd = int(p["num_attention_heads"]), int(p["num_key_value_heads"]), int(p["head_dim"])
    windows = [int(x) for x in p["sliding_window_layout"]]
    turns = [int(x) for x in (p.get("rope_layout") or windows)]
    layers, window = int(p["num_hidden_layers"]), int(p["sliding_window_size"])
    n_sliding, n_full = sum(windows), layers - sum(windows)
    experts, top_k = int(p["moe_num_primary_experts"]), int(p["moe_num_active_primary_experts"])
    held, f = int(p.get("experts_held") or experts), int(p["moe_ffn_hidden_size"])
    batch = int(traffic["minibatch_size"])

    c_q, c_k = heads * hd, kv_heads * hd
    attention_matmul = 2 * d * c_q + 2 * d * c_k
    expert = 3 * d * f
    active = layers * (attention_matmul + d * experts + top_k * held / experts * expert) + d * vocab
    params_layer = attention_matmul + d * experts + held * expert + 2 * d  # attn_norm, ffn_norm
    reach = min(window, seq)
    pairs_window = reach * (reach + 1) // 2 + (seq - reach) * reach
    pairs_full = seq * seq // 2
    fwd_pair, bwd_pair = 2 * 2 * hd, 2 * 5 * hd
    attention = 3 * fwd_pair * heads * (n_sliding * pairs_window + n_full * pairs_full) // seq
    glue_bytes_position = 2 * (sum(turns) * 2 * (2 * c_q + 2 * c_k) + layers * 2 * (2 * c_q + 2 * c_k))
    slots = batch * seq * top_k * layers
    per_slot = 3 * 3 * 2 * d * f
    return {
        "active_matmul_params": active,
        "params_layer": params_layer,
        "params_total": layers * params_layer + 2 * vocab * d + d,
        "pairs_window": pairs_window,
        "pairs_full": pairs_full,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * active + attention,
        "attn_glue_bytes_per_step": batch * seq * glue_bytes_position,
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
