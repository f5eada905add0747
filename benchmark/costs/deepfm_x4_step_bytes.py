"""Bytes one DeepFM training step NEEDS on each chip of a row-sharded
deployment, from shapes: through that chip's HBM, and across chips.

What the algorithm needs, not what the program does.  The global minibatch
is split evenly over ``config["chips"]`` chips; the table's rows are split
evenly too, and ids are uniform, so each chip SERVES ``minibatch x 26 /
chips`` row lookups a step, whoever asked.

HBM, per chip (``step_bytes_chip``): each served row of ``embedding_dim +
1`` floats is read once by the gather, its gradient is written once and
read once, and Adam reads the row and its two moments and writes all three
back: 9 passes over the served rows, as in ``deepfm_step_bytes``.  The
dense part is replicated, so every chip makes the same 9 passes over all
of it.

Across chips, per chip (``cross_chip_bytes_step``): of the ``minibatch x
26 / chips`` lookups a chip ASKS for, the share ``(chips - 1) / chips``
lives elsewhere.  Each such lookup sends its id out (4 bytes) and gets its
vector back, and the backward pass sends the id and the vector's gradient
out again: 2 x (4 + row bytes).  The dense gradients' all-reduce moves
``2 x (chips - 1) / chips`` of the dense parameters' bytes per chip more
(``dense_allreduce_bytes_chip``, a ring's count).
"""

NUM_DENSE = 13
NUM_CAT = 26
PASSES = 9
ID_BYTES = 4


def compute(config: dict, traffic: dict) -> dict:
    p = config["model_params"]
    chips = int(config["chips"])
    dim = int(p["embedding_dim"]) + 1
    row_bytes = dim * 4
    lookups_chip = int(traffic["minibatch_size"]) * NUM_CAT // chips
    widths = [NUM_CAT * int(p["embedding_dim"]) + NUM_DENSE] + [int(h) for h in p["hidden"]] + [1]
    dense_params = sum(a * b + b for a, b in zip(widths, widths[1:])) + NUM_DENSE + 1
    table_rows = NUM_CAT * int(p["buckets_per_feature"])
    remote = lookups_chip * (chips - 1) // chips
    return {
        "chips": chips,
        "rows_served_per_step_chip": lookups_chip,
        "row_bytes": row_bytes,
        "dense_params": dense_params,
        "step_bytes_chip": PASSES * (lookups_chip * row_bytes + dense_params * 4),
        "lookups_remote_per_step_chip": remote,
        "cross_chip_bytes_step": remote * 2 * (ID_BYTES + row_bytes),
        "dense_allreduce_bytes_chip": 2 * (chips - 1) * dense_params * 4 // chips,
        "table_rows": table_rows,
        "table_rows_chip": table_rows // chips,
        "table_bytes_logical": table_rows * row_bytes,
    }
