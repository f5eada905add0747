"""The one general traffic generator: a traffic file's ``generator`` block in,
a directory of recordio files out, everything drawn from ``--seed``.

Vectorised numpy throughout (the program's own ``data/synthetic.py`` loops
in Python at ~30 us a record; a Criteo task is 65,536 records).  The record
formats are the program's wire formats, written here from their
descriptions in ``data/recordio.py`` and ``data/codecs.py`` so that a later
PR cannot change the yardstick by changing the program's generator:

    recordio file   8-byte magic, then per record
                    [uint32 length][uint32 crc32(payload)][payload]
    text file       one record a line (``"container": "text"``)
    criteo_tsv      label \\t 13 decimal ints \\t 26 ids as 8 hex digits
    lm_tokens       seq_len + 1 little-endian int32 token ids

An epoch of the job is ONE file of ``tasks_per_file`` tasks: the
``distinct_tasks`` generated tasks' records written over and over.  The
master starts an epoch only when every task of the last one has been
reported, which drains the worker's pipeline (0.17 s on the chip, PR 23);
a real Criteo epoch is ~690 tasks, so the file is long enough that no
boundary falls inside a run, instead of one every ``distinct_tasks`` tasks.
One file, not many: every file costs the program one scan of its record
index at first touch (once a process since PR 36: 0.67 s for the 32-task
Criteo file, linear in its bytes; PERF.md, Findings).
"""
from __future__ import annotations

import os
import zlib

import numpy as np

RECORDIO_MAGIC = b"EDLRIO\x00\x01"
_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_DEC = np.frombuffer(b"0123456789", np.uint8)


def frame_text(payloads: np.ndarray) -> bytes:
    """One record a line (the Criteo-Kaggle dump's own container)."""
    n, length = payloads.shape
    lines = np.empty((n, length + 1), np.uint8)
    lines[:, :length] = payloads
    lines[:, length] = ord("\n")
    return lines.tobytes()


def frame_recordio(payloads: np.ndarray) -> bytes:
    """``payloads``: uint8 [n, length], one fixed-length record a row."""
    n, length = payloads.shape
    framed = np.empty((n, 8 + length), np.uint8)
    framed[:, 8:] = payloads
    header = framed[:, :8].view("<u4")
    header[:, 0] = length
    header[:, 1] = np.fromiter(
        (zlib.crc32(row) for row in framed[:, 8:]), np.uint32, count=n
    )
    return framed.tobytes()


def _draw_ids(rng, shape, dist: dict) -> np.ndarray:
    """Raw categorical ids as uint32.  The model hashes them into its
    buckets, which keeps multiplicities, so a skew drawn here is the skew
    the table sees."""
    kind = dist["kind"]
    if kind == "uniform":
        return rng.integers(0, 1 << 32, shape, dtype=np.uint64).astype(np.uint32)
    if kind == "zipf":
        # Bounded Zipf over ``support`` ranks by inverse CDF; each field
        # (column) gets its own rank -> id scramble so that the hot ids of
        # different fields are unrelated.
        support = int(dist["support"])
        cdf = np.cumsum(np.arange(1, support + 1, dtype=np.float64) ** -float(dist["exponent"]))
        cdf /= cdf[-1]
        ranks = np.searchsorted(cdf, rng.random(shape)).astype(np.uint32)
        salt = rng.integers(1, 1 << 32, shape[-1], dtype=np.uint64).astype(np.uint32)
        return ranks * np.uint32(2654435761) + salt
    raise ValueError(f"unknown id distribution {kind!r}")


def criteo_tsv(rng, n: int, params: dict) -> np.ndarray:
    """n Criteo-Kaggle lines at a fixed width of 287 bytes: dense features
    are ints in [0, 1000) written with three digits (leading zeros parse as
    the same number), labels follow a planted logistic rule on two dense
    features so that the loss can fall."""
    dense = rng.integers(0, 1000, (n, 13))
    cats = _draw_ids(rng, (n, 26), params["ids"])
    score = 0.002 * dense[:, 0] - 0.001 * dense[:, 1] - 0.3
    label = (rng.random(n) < 1.0 / (1.0 + np.exp(-score))).astype(np.uint8)
    out = np.full((n, 1 + 13 * 4 + 26 * 9), ord("\t"), np.uint8)
    out[:, 0] = _DEC[label]
    for digit, div in enumerate((100, 10, 1)):
        out[:, 2 + digit : 2 + 13 * 4 : 4] = _DEC[(dense // div) % 10]
    base = 1 + 13 * 4 + 1
    for nibble in range(8):
        out[:, base + nibble :: 9] = _HEX[(cats >> np.uint32(28 - 4 * nibble)) & np.uint32(15)]
    return out


def lm_tokens(rng, n: int, params: dict) -> np.ndarray:
    """n sequences of seq_len + 1 uniform random token ids."""
    toks = rng.integers(0, int(params["vocab"]), (n, int(params["seq_len"]) + 1), dtype=np.int32)
    return np.ascontiguousarray(toks.astype("<i4")).view(np.uint8).reshape(n, -1)


GENERATORS = {"criteo_tsv": criteo_tsv, "lm_tokens": lm_tokens}


CONTAINERS = {
    "recordio": (".rio", RECORDIO_MAGIC, frame_recordio),
    "text": (".tsv", b"", frame_text),
}


def generate(out_dir: str, traffic: dict, seed: int) -> dict:
    """Write the job's training file under ``out_dir``; returns its shape
    (records per task, tasks per epoch, bytes)."""
    gen = traffic["generator"]
    records_per_task = int(traffic["minibatch_size"]) * int(traffic["minibatches_per_task"])
    tasks, distinct = int(gen["tasks_per_file"]), int(gen["distinct_tasks"])
    if tasks % distinct:
        raise ValueError("tasks_per_file must be a multiple of distinct_tasks")
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 0x6EDB]))
    payloads = GENERATORS[gen["kind"]](rng, records_per_task * distinct, gen)
    suffix, magic, frame = CONTAINERS[gen["container"]]
    block = frame(payloads)
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "part-00000" + suffix)
    with open(path, "wb") as f:
        f.write(magic)
        for _ in range(tasks // distinct):
            f.write(block)
    return {
        "first_file": path,
        "records_per_task": records_per_task,
        "tasks_per_epoch": tasks,
        "record_bytes": int(payloads.shape[1]),
        "file_bytes": os.path.getsize(path),
    }
