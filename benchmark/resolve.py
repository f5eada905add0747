"""Find a cell's files by the names in BENCHMARK.json.

Nothing here knows a cell, a model or a metric: a later PR adds a
configuration, a traffic mix, a per-layer metric, a reader or a cost model
by adding files and BENCHMARK.json entries, and this module finds them.

    configs/<config>.json            sizes, flags, source (path from BENCHMARK.json "file")
    configs/<config>_reference.py    the plain float32 reference beside it
    traffic/<traffic>.json           the parameters the general generator reads
    metrics/<metric>.json            unit, layer, moves, reader, params (its cells: BENCHMARK.json "workloads")
    readers/<reader>.py              read(ctx, params) -> number or None
    costs/<model>.py                 operations and bytes from shapes
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


class ResolveError(Exception):
    """A name in BENCHMARK.json that no file answers to."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one benchmark file by path (readers, costs, references)."""
    if not os.path.isfile(path):
        raise ResolveError(f"no such module file: {path}")
    name = "edlbench_" + re.sub(r"\W", "_", os.path.relpath(path, BENCH_DIR))
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Bench:
    """BENCHMARK.json plus the files its names point at, rooted at
    ``root`` (the checkout; tests point it at a temporary copy)."""

    def __init__(self, root: str = ROOT):
        self.root = os.path.abspath(root)
        self.spec = load_json(os.path.join(self.root, "BENCHMARK.json"))
        self.dir = os.path.join(self.root, self.spec["paths"][0])

    def _one(self, group: str, name: str) -> dict:
        found = [e for e in self.spec[group] if e["name"] == name]
        if len(found) != 1:
            known = sorted(e["name"] for e in self.spec[group])
            raise ResolveError(f"{group} has no entry {name!r}; known: {known}")
        return found[0]

    def cell(self, name: str) -> dict:
        return self._one("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._one("configs", name)
        config = load_json(os.path.join(self.root, entry["file"]))
        config["name"] = name
        return config

    def reference_path(self, name: str) -> str:
        entry = self._one("configs", name)
        return os.path.join(self.root, entry["file"][: -len(".json")] + "_reference.py")

    def traffic(self, name: str) -> dict:
        path = os.path.join(self.dir, "traffic", name + ".json")
        if not os.path.isfile(path):
            raise ResolveError(f"traffic mix {name!r}: no file {path}")
        traffic = load_json(path)
        traffic["name"] = name
        return traffic

    def metrics_of(self, cell: str, group: str) -> list:
        """The BENCHMARK.json metric entries reported in ``cell``."""
        return [
            m for m in self.spec[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def metric_file(self, name: str) -> dict:
        path = os.path.join(self.dir, "metrics", name + ".json")
        if not os.path.isfile(path):
            raise ResolveError(f"per-layer metric {name!r}: no file {path}")
        return load_json(path)

    def reader(self, name: str):
        return load_module(os.path.join(self.dir, "readers", name + ".py"))

    def costs(self, name: str):
        return load_module(os.path.join(self.dir, "costs", name + ".py"))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(os.path.join(self.dir, "peaks.json"))
        if device_kind not in table or device_kind.startswith("_"):
            raise ResolveError(
                f"device kind {device_kind!r} is not in peaks.json "
                f"({sorted(k for k in table if not k.startswith('_'))})"
            )
        return table[device_kind]
