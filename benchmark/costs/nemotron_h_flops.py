"""Operations and bytes a training step of the ``moe_lm`` decoder needs under
``nemotron_h``'s keys (a layer is a Mamba-2 mixer, a LatentMoE or an
attention with fewer key/value heads, read from the pattern; a share of the
heads and of the routed experts held), from shapes alone: the same whatever
implements a kernel.

Model FLOPs of the work a TOKEN needs: recomputation is not counted
(``remat`` recomputes every layer's forward in the backward pass), nor the
experts a token is not routed to, nor the slots routed to experts held
elsewhere.

- ``active_matmul_params``: parameters that multiply a token's activations.
  An ``M`` layer's ``W_in`` (d x (2 inner + 2 G N + H), the HELD heads and
  groups) and ``W_out`` (inner x d); a ``*`` layer's ``wq``, ``wo`` (d x held
  heads x hd each way) and ``wk``, ``wv`` (d x held key/value heads x hd); an
  ``E`` layer's router (d x E), the two latent projections (2 x d x latent),
  the shared expert (2 x d x shared width: two matrices under relu squared)
  and the EXPECTED share of a token's ``top_k`` slots that falls on a held
  expert (top_k x held / E = 0.34375 at 22 x 8 / 512) times an expert's 2 x
  latent x f; once the untied head (d x vocab).  The token look-up is a
  gather; the norms, the convolution's 4 taps, the gates and the softplus
  are elementwise: none counts.
- The scan's needed FLOPs a position and layer, forward
  (``ssm_scan_flops_per_position``): ``C B^T`` inside a chunk (2 x chunk x N
  a group), the masked product with x (2 x chunk x P a head), the chunk's
  end state and the carried state's read-out (2 x P x N a head each): the
  chunked form's own count at the published chunk (a position-by-position
  recurrence needs 3 x P x N a head and no MXU).  Backward twice that.
  ``ssm_scan_flops_per_step`` = minibatch x L x M layers x 3 x that.
- The scan's needed bytes (``ssm_scan_bytes_per_step``): x, B, C, dt read
  and y written once forward (bfloat16, dt float32); backward reads them and
  dy and writes dx, dB, dC, ddt: twice the forward's; M layers.
- ``ssm_scan_needed_s`` x the peaks is not here: the reader takes the LARGER
  of the two times (``readers/scope_roofline_larger.py``).
- ``ssm_glue_bytes_per_step``: what the convolution (+ silu) and the gated
  group norm have to move, M layers, forward + backward once each, bfloat16:
  conv forward reads and writes xBC (2 C), backward reads g and x and writes
  dx (3 C), C = inner + 2 G N; norm forward reads y, z and writes (3 inner),
  backward reads y, z, g and writes dy, dz (5 inner).
- The flash kernels' FLOPs a visited (query, key) pair of a held query head
  at head width hd: forward 2 x 2 x hd = 512, backward 2 x 5 x hd = 1,280
  (``transformer_lm_flops``' convention: ``flash_unit_flops`` = the causal
  half of minibatch x heads x L^2 pairs, ONE FLOP a pair; the backward pair
  booked on its first kernel).  The key/value heads are repeated to the
  queries' ahead of the kernels, which changes no pair.
- ``attention_flops_per_token`` = * layers x held heads x L / 2 x 3 x 512.
- ``train_flops_per_token`` = 6 x active_matmul_params + the attention term +
  3 x the scan's forward FLOPs a position x M layers.
- ``expert_flops_per_slot``: what the grouped matmuls need for ONE computed
  slot, forward + backward: 2 projections x 3 (forward, dx, dw) x 2 x latent
  x f.  ``moe_slots_per_step`` = minibatch x L x top_k x E layers (all the
  routers' slots); ``expert_flops_per_step`` is the EXPECTATION.
- ``params_m_layer`` / ``params_attention_layer`` / ``params_e_layer`` /
  ``params_total``: every parameter held (matrices, taps, vectors, gains),
  for the sizing arithmetic in the configuration's file.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, vocab, seq = int(p["hidden_size"]), int(p["vocab_size"]), int(p["seq_len"])
    pattern = p["hybrid_override_pattern"]
    n_m, n_a, n_e = pattern.count("M"), pattern.count("*"), pattern.count("E")
    heads_all, width, state = int(p["mamba_num_heads"]), int(p["mamba_head_dim"]), int(p["ssm_state_size"])
    heads = int(p.get("mamba_heads_held") or heads_all)
    groups = heads * int(p["n_groups"]) // heads_all
    chunk, taps = int(p["chunk_size"]), int(p["conv_kernel"])
    inner, conv_dim = heads * width, heads * width + 2 * groups * state
    hd = int(p["head_dim"])
    q_heads = int(p.get("heads_held") or p["num_attention_heads"])
    kv_heads = int(p.get("kv_heads_held") or p.get("num_key_value_heads") or p["num_attention_heads"])
    experts, top_k = int(p["num_experts"]), int(p["num_experts_per_tok"])
    held = int(p.get("experts_held") or experts)
    latent, f, shared = int(p["moe_latent_size"]), int(p["moe_intermediate_size"]), int(p["moe_shared_expert_intermediate_size"])
    batch = int(traffic["minibatch_size"])

    m_matmul = d * (inner + conv_dim + heads) + inner * d
    a_matmul = 2 * d * q_heads * hd + 2 * d * kv_heads * hd
    e_outside = d * experts + 2 * d * latent + 2 * d * shared
    expert = 2 * latent * f
    active = n_m * m_matmul + n_a * a_matmul + n_e * (e_outside + top_k * held / experts * expert) + d * vocab
    params = {
        "M": m_matmul + taps * conv_dim + conv_dim + 3 * heads + inner + d,
        "*": a_matmul + d,
        "E": e_outside + experts + held * expert + d,
    }
    scan_position = 2 * (chunk * state * groups + chunk * width * heads + 2 * width * state * heads)
    scan_bytes_position = 2 * (inner + 2 * groups * state) + 4 * heads + 2 * inner  # x, B, C | dt | y
    fwd_pair, bwd_pair = 2 * 2 * hd, 2 * 5 * hd
    attention = n_a * q_heads * seq // 2 * 3 * fwd_pair
    slots = batch * seq * top_k * n_e
    per_slot = 2 * 3 * 2 * latent * f
    positions = batch * seq
    return {
        "active_matmul_params": active,
        "params_m_layer": params["M"],
        "params_attention_layer": params["*"],
        "params_e_layer": params["E"],
        "params_total": n_m * params["M"] + n_a * params["*"] + n_e * params["E"] + 2 * vocab * d + d,
        "attention_flops_per_token": attention,
        "ssm_scan_flops_per_position": scan_position,
        "train_flops_per_token": 6 * active + attention + 3 * n_m * scan_position,
        "ssm_scan_flops_per_step": positions * n_m * 3 * scan_position,
        "ssm_scan_bytes_per_step": positions * n_m * 3 * scan_bytes_position,
        "ssm_glue_bytes_per_step": positions * n_m * 2 * ((2 + 3) * conv_dim + (3 + 5) * inner),
        "flash_unit_flops": batch * q_heads * seq * seq // 2,
        "flash_fwd_units": fwd_pair,
        "flash_bwd_units": bwd_pair,
        "flash_bwd_second_units": 0,
        "moe_slots_per_step": slots,
        "expert_flops_per_slot": per_slot,
        "expert_flops_per_step": slots * held / experts * per_slot,
    }
