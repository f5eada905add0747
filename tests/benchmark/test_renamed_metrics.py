"""PR 39: one ``per_layer`` entry and one file a METRIC, not a (metric,
cell) pair.  The 128 entries the benchmark had before it (the contract's
limit) were mostly per-cell copies of one file; they fold onto ``workloads``
lists, and the ledger's per-layer keys change name once.  ``RENAMED`` holds,
for every one of the 128 old names, the name that reports its reading now,
the cells the old entry listed, and the old file's ``reader`` and ``params``
(``data/renamed_pr39.json``, written from the parent commit's files when the
fold was made, not read from git here).

PR 63 folded the sixteen twins that tests outside ``paths`` had held apart
until PR 61 (the eight ``.exz``, seven ``.ex4`` and ``attn_proj_ms_step.swa``:
112 -> 96 entries), so the keys change name once more: ``RENAMED_63``
(``data/renamed_pr63.json``) holds those sixteen the same way, and the rows of
PR 39's table whose name went with them point at the survivor.  Since then no
metric file carries ``cells`` or ``params["how"]``, and ONE standing case
keeps a twin from coming back.  CPU only."""

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
with open(os.path.join(HERE, "data", "renamed_pr63.json")) as f:
    RENAMED_63 = json.load(f)

#: Retired, not folded: the reading lives on under a metric of ANOTHER reader
#: that gave the same number on every line of the ledger (PERF.md section 6, PR 39).
RETIRED = {"peak_hbm_gib.ex", "peak_hbm_gib.tok", "init_state_s.ex4"}
#: Said in prose, or read by no reader: not what a reading is made from.
PROSE = ("why", "how")

#: The growth rehearsal (test_benchmark_yardstick.py) runs this module on grown copies of the tree, and what its added cells
#: BRING there are copies of entries that are there under names of their own (``<metric>.<tag>``): twins by construction.
on_the_tree_itself = pytest.mark.skipif("EDL_BENCH_GROWTH_REHEARSAL" in os.environ, reason="the rehearsal's own entries are copies")


def _read_from(spec: dict) -> dict:
    return {k: v for k, v in spec.get("params", {}).items() if k not in PROSE}


def test_the_table_covers_the_128_entries_the_benchmark_had():
    assert len(RENAMED) == 128 and RETIRED <= set(RENAMED)
    assert {old for old, row in RENAMED.items() if row["new"] != old} >= RETIRED


def test_the_second_table_holds_the_sixteen_twins_that_went_and_the_first_points_past_them():
    assert len(RENAMED_63) == 16 and all(row["new"] != old for old, row in RENAMED_63.items())
    # a name PR 39 kept and PR 63 folded is not a `new` of the first table any more: one step from any old name to a live one
    assert not {row["new"] for row in RENAMED.values()} & set(RENAMED_63)
    assert set(RENAMED_63) <= set(RENAMED) | {"attn_proj_ms_step.swa"}  # the one twin that came after PR 39 (PR 56)
    survivors = {row["new"] for row in RENAMED_63.values()}
    assert survivors <= {m["name"] for m in resolve.Bench(ROOT).spec["per_layer"]} and len(survivors) == 13


@pytest.mark.parametrize("old,table", [(old, "pr39") for old in sorted(RENAMED)] + [(old, "pr63") for old in sorted(RENAMED_63)])
def test_an_old_name_reads_on_under_its_new_name_in_every_cell_it_had(old, table):
    """The new entry lists every cell the old one listed, and its file's
    reader and parameters are the old file's: the same reading under
    another name."""
    bench = resolve.Bench(ROOT)
    row = {"pr39": RENAMED, "pr63": RENAMED_63}[table][old]
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


def test_no_metric_file_lists_cells_and_none_says_how_a_counter_is_read():
    """``BENCHMARK.json``'s ``workloads`` is the ONE place a metric's cells
    live (PR 39).  25 files kept a ``cells`` key, and four of them
    ``params["how"]``, for tests outside ``paths`` that read them; since
    PR 61 none does, and PR 63 dropped both keys (the reader's name says how
    a counter is read)."""
    bench = resolve.Bench(ROOT)
    for entry in bench.spec["per_layer"]:
        spec = bench.metric_file(entry["name"])
        assert "cells" not in spec and "how" not in spec.get("params", {}), entry["name"]
        assert set(entry) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}, entry["name"]


@on_the_tree_itself
def test_no_two_entries_read_the_same_thing():
    """No two entries of ``per_layer`` agree in reader, parameters (less the
    prose), unit, direction and ``moves``: such a pair is ONE metric under
    two names, and its second cell belongs in the first entry's
    ``workloads``.  This comparison is what found PR 63's sixteen; it stands
    so that a later PR's cell joins an entry that is there and brings
    entries only for what no entry reads."""
    bench = resolve.Bench(ROOT)
    read = {}
    for entry in bench.spec["per_layer"]:
        spec = bench.metric_file(entry["name"])
        what = json.dumps([spec["reader"], _read_from(spec), entry["unit"], entry["better"], entry["moves"]], sort_keys=True)
        read.setdefault(what, []).append(entry["name"])
    assert [names for names in read.values() if len(names) > 1] == []


def test_a_suffix_says_which_end_to_end_metric_an_entry_moves():
    """``.tok`` moves ``tokens_per_s_chip`` and ``.ex`` (with the ``.ex4``
    entries of what only the sharded table has) ``examples_per_s_chip``;
    ``setup_*`` moves ``setup_s``.  No suffix names a cell any more: ``.moe``,
    ``.mla``, ``.eva`` stay on metrics that only that architecture has."""
    bench = resolve.Bench(ROOT)
    rate_cells = {m["name"]: set(m.get("workloads", [])) for m in bench.spec["end_to_end"]}
    for entry in bench.spec["per_layer"]:
        name, moves = entry["name"], entry["moves"]
        if name.endswith(".tok"):
            assert moves == "tokens_per_s_chip", name
        if name.endswith((".ex", ".ex4")):
            assert moves == "examples_per_s_chip", name
        if name.startswith("setup_"):
            assert moves == "setup_s", name
        else:
            assert set(entry["workloads"]) <= rate_cells[moves], name
