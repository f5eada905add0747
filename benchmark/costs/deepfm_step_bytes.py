"""Bytes one DeepFM training step NEEDS to move through HBM, from shapes.

What the algorithm needs, not what the program does: a step touches at
most ``minibatch x 26`` logical table rows of ``embedding_dim + 1`` floats
(the FM vector and the first-order weight).  Each touched row is read once
by the gather, its gradient is written once and read once, and Adam reads
the row and its two moments and writes all three back: 9 passes over the
touched rows.  The dense part (first-order dense weights and the MLP) is
read by the forward and the backward pass and goes through the same seven
Adam passes: 9 passes too.  Activations are left out (they are small next
to either and could stay on chip).  A dense sweep of the whole table, which
is what the program does today, moves two orders of magnitude more.
"""

NUM_DENSE = 13
NUM_CAT = 26
PASSES = 9


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    dim = int(p["embedding_dim"]) + 1
    rows = int(traffic["minibatch_size"]) * NUM_CAT
    row_bytes = dim * 4
    widths = [NUM_CAT * int(p["embedding_dim"]) + NUM_DENSE] + [int(h) for h in p["hidden"]] + [1]
    mlp = sum(a * b + b for a, b in zip(widths, widths[1:]))
    dense_params = mlp + NUM_DENSE + 1
    table_rows = NUM_CAT * int(p["buckets_per_feature"])
    return {
        "rows_touched_per_step": rows,
        "row_bytes": row_bytes,
        "dense_params": dense_params,
        "step_bytes": PASSES * (rows * row_bytes + dense_params * 4),
        "table_rows": table_rows,
        "table_bytes_logical": table_rows * row_bytes,
    }
