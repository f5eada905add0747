"""PR 39: one ``per_layer`` entry and one file a METRIC, not a (metric,
cell) pair.  The 128 entries the benchmark had before it (the contract's
limit) were mostly per-cell copies of one file; they fold onto ``workloads``
lists, and the ledger's per-layer keys change name once.  ``RENAMED`` holds,
for every one of the 128 old names, the name that reports its reading now,
the cells the old entry listed, and the old file's ``reader`` and ``params``
(``data/renamed_pr39.json``, written from the parent commit's files when the
fold was made, not read from git here).  CPU only."""

from __future__ import annotations

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(ROOT, "benchmark")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import resolve  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
with open(os.path.join(HERE, "data", "renamed_pr39.json")) as f:
    RENAMED = json.load(f)

#: Retired, not folded: the reading lives on under a metric of ANOTHER reader
#: that gave the same number on every line of the ledger (PERF.md section 6, PR 39).
RETIRED = {"peak_hbm_gib.ex", "peak_hbm_gib.tok", "init_state_s.ex4"}
#: Said in prose, or read by no reader: not what a reading is made from.
PROSE = ("why", "how")

#: The files that still carry ``cells`` (and four of them ``params["how"]``):
#: tests OUTSIDE ``paths`` read the key, and a ``benchmark`` PR may edit no
#: file there.  file -> the test that pins it.  When those tests let go, the
#: key goes and this table with it (PERF.md section 7).
PINNED = {
    **{f"{family}.exz": "tests/test_deepfm_zipf_cell.py" for family in (
        "step_ms", "step_roofline_pct", "device_idle_pct", "host_loop_pct", "prep_wait_pct",
        "starved_dispatch_pct", "compiles_in_window", "hbm_peak_reported_gib")},
    **{name: "tests/test_deepfm_zipf_cell.py" for name in (
        "step_ms.ex", "step_roofline_pct.ex", "device_idle_pct.ex", "host_loop_pct.ex", "prep_wait_pct.ex",
        "starved_dispatch_pct.ex4", "compiles_in_window.ex4", "hbm_peak_reported_gib.ex4",
        "table_grad_ms_step.ex", "table_grad_ms_step.ex4", "table_grad_sweep_pct.ex", "table_grad_sweep_pct.ex4")},
    **{name: "tests/test_table_apply.py" for name in (
        "table_apply_ms_step.ex", "table_apply_ms_step.ex4", "table_apply_fused_pct.ex", "table_apply_fused_pct.ex4")},
    "moe_slots_overflow_pct.mla": "tests/test_latent_moe.py",
}
PINNED_HOW = {"table_grad_sweep_pct.ex", "table_grad_sweep_pct.ex4", "table_apply_fused_pct.ex", "table_apply_fused_pct.ex4"}


def _read_from(spec: dict) -> dict:
    return {k: v for k, v in spec.get("params", {}).items() if k not in PROSE}


def test_the_table_covers_the_128_entries_the_benchmark_had():
    assert len(RENAMED) == 128 and RETIRED <= set(RENAMED)
    assert {old for old, row in RENAMED.items() if row["new"] != old} >= RETIRED


@pytest.mark.parametrize("old", sorted(RENAMED))
def test_an_old_name_reads_on_under_its_new_name_in_every_cell_it_had(old):
    """The new entry lists every cell the old one listed, and its file's
    reader and parameters are the old file's: the same reading under
    another name."""
    bench = resolve.Bench(ROOT)
    row = RENAMED[old]
    (entry,) = [m for m in bench.spec["per_layer"] if m["name"] == row["new"]]
    assert set(row["cells"]) <= set(entry["workloads"])
    spec = bench.metric_file(row["new"])
    assert spec["name"] == row["new"]
    if row["new"] != old:
        assert old not in [m["name"] for m in bench.spec["per_layer"]]
        assert not os.path.exists(os.path.join(BENCH_DIR, "metrics", old + ".json"))
    if old not in RETIRED:
        assert spec["reader"] == row["reader"]
        assert _read_from(spec) == _read_from(row)


def test_one_entry_one_file_and_the_cells_are_the_entrys_list_alone():
    bench = resolve.Bench(ROOT)
    names = [m["name"] for m in bench.spec["per_layer"]]
    files = sorted(f[: -len(".json")] for f in os.listdir(os.path.join(BENCH_DIR, "metrics")) if f.endswith(".json"))
    assert sorted(names) == files
    assert len(names) <= 128  # the contract's limit; 84 at PR 39
    cells = {w["name"] for w in bench.spec["workloads"]}
    for entry in bench.spec["per_layer"]:
        assert entry["workloads"] and set(entry["workloads"]) <= cells, entry["name"]
        spec = bench.metric_file(entry["name"])
        # `cells` and `how` only where a test outside `paths` still reads them; `cells` is the list as PR 39 left it
        assert ("cells" in spec) == (entry["name"] in PINNED), entry["name"]
        assert ("how" in spec.get("params", {})) == (entry["name"] in PINNED_HOW), entry["name"]
        if "cells" in spec:
            assert entry["workloads"][: len(spec["cells"])] == spec["cells"], entry["name"]
    for name, pinned_by in PINNED.items():
        assert name in names, (name, pinned_by)


def test_a_suffix_says_which_end_to_end_metric_an_entry_moves():
    """``.tok`` moves ``tokens_per_s_chip`` and ``.ex`` (with the DeepFM
    cells' ``.ex4`` / ``.exz`` that stay apart) ``examples_per_s_chip``;
    ``setup_*`` moves ``setup_s``.  No suffix names a cell any more: ``.moe``,
    ``.mla``, ``.eva`` stay on metrics that only that architecture has."""
    bench = resolve.Bench(ROOT)
    rate_cells = {m["name"]: set(m.get("workloads", [])) for m in bench.spec["end_to_end"]}
    for entry in bench.spec["per_layer"]:
        name, moves = entry["name"], entry["moves"]
        if name.endswith(".tok"):
            assert moves == "tokens_per_s_chip", name
        if name.endswith((".ex", ".ex4", ".exz")):
            assert moves == "examples_per_s_chip", name
        if name.startswith("setup_"):
            assert moves == "setup_s", name
        else:
            assert set(entry["workloads"]) <= rate_cells[moves], name
