"""Operations a decoder-only transformer's training step needs, from shapes.

Model FLOPs, recomputation not counted (``remat`` recomputes every block's
forward pass in the backward pass; those operations are not in here).

- ``matmul_params``: parameters that multiply activations: per block
  ``wqkv`` (d x 3d), ``wo`` (d x d), ``w1`` (d x 4d), ``w2`` (4d x d), and
  the tied output head (vocab x d).  The token and position look-ups are
  gathers and the norms are elementwise: neither counts.  (The published
  354.8 M of GPT-2-medium counts biases, LayerNorm and positions too.)
- ``train_flops_per_token`` = 6 x matmul_params + the causal attention
  term: QK^T and PV are 2 matmuls of 2 x L x d FLOPs per token per layer
  at full attention, forward; backward is twice that; a causal mask needs
  half: 3 x 2 x 2 x L x d / 2 = 6 x L x d per token per layer.
  (``tools/bench_all.py`` left this term out.)
- ``flash_unit_flops``: one causal L x L matmul against the head dimension
  over the whole minibatch, B x H x L^2 x D.  A flash forward call needs 2
  units (S = QK^T, O = PV); the backward needs 5 (S again, dV, dP, dQ,
  dK) however many kernels it is split into: the program splits it in two
  (dQ; dK and dV), the 5 units are booked on the first and 0 on the second.
"""


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    d, layers, vocab = int(p["dim"]), int(p["n_layers"]), int(p["vocab"])
    heads, seq = int(p["n_heads"]), int(p["seq_len"])
    batch = int(traffic["minibatch_size"])
    matmul_params = layers * 12 * d * d + vocab * d
    attention = 6 * seq * d * layers
    return {
        "matmul_params": matmul_params,
        "attention_flops_per_token": attention,
        "train_flops_per_token": 6 * matmul_params + attention,
        "flash_unit_flops": batch * heads * seq * seq * (d // heads),
        "flash_fwd_units": 2,
        "flash_bwd_units": 5,
        "flash_bwd_second_units": 0,
    }
