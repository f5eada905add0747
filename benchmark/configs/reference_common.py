"""What the plain references share: reading the first task's records from
the recordio file (format: ``datagen.py``) and the command line."""

from __future__ import annotations

import argparse
import json
import struct


def parse_args():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True, help="the configuration, as JSON")
    ap.add_argument("--traffic", required=True, help="the traffic mix, as JSON")
    ap.add_argument("--data", required=True, help="the job's first recordio file")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    return json.loads(args.config), json.loads(args.traffic), args.data, args.out


def read_records(path: str, n: int) -> list:
    """The first ``n`` payloads of a recordio file, or the first ``n``
    lines of a text file."""
    out = []
    with open(path, "rb") as f:
        if f.read(8) != b"EDLRIO\x00\x01":
            f.seek(0)
            return [f.readline().rstrip(b"\n") for _ in range(n)]
        for _ in range(n):
            length, _crc = struct.unpack("<II", f.read(8))
            out.append(f.read(length))
    return out


def device_report() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind}
