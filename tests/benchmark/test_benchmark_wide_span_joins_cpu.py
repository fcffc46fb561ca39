"""The direct-address join sizes its table from the span of the live build
keys (ISSUE 38), so a dimension that a filter compacted joins directly on
one chip as it already did under a mesh: both streamed cells read the same
``direct_joins_per_pass`` and no ``sorted_joins_per_pass``, and the power mix
of query7 and query3 holds no sort-based join.

One case of ``test_benchmark_join_paths_cpu.py`` pins what this PR changes
and no file here may be edited: ``streamed_scan_sf1`` at two SORTED joins a
morsel. ``tests/conftest.py`` marks it as expected to fail, strictly; it is
restated here. Read on the CPU from traced runs, as that file does: the
streamed cells over the scratch copies of their configurations that
``test_benchmark_tight_morsels_cpu.py`` makes (``--scale 0.1``), the power
mix cut to two of its units at SF0.01. (The file's name sorts last in this
directory on purpose: that file's power-mix case needs two passes inside a
2 s window on a CPU that six workers share, so this file's three cell runs
are dispatched after it, not beside it.)"""
import copy
import json

import pytest
from bench_helpers import manifest, run_cell
from test_benchmark_cell_streamed_x4_cpu import window_stats  # noqa: F401
from test_benchmark_tight_morsels_cpu import (CELLS, MORSELS,  # noqa: F401
                                              small_manifest)

from benchmark import run, traffic

M = manifest()
METRICS = ("direct_joins_per_pass", "sorted_joins_per_pass")
#: query3's morsel program joins the morsel to a filtered ``item`` and a
#: filtered ``date_dim``; query9's holds no join
JOINS_A_MORSEL = 2


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_both_streamed_cells_join_every_morsel_directly(
        cell, small_manifest, capsys, window_stats):
    """On one chip both filters compact their dimension to a few rows whose
    keys still span the dimension: the table is the span's bucket, not four
    times the survivors', and the join is direct — as under a mesh, where a
    replica compacts nothing and the span always fitted."""
    rc = run.main(["--manifest", small_manifest, "--workload", cell,
                   "--seed", str(2 ** 31 + 381), "--seconds", "1",
                   "--trace", "1", "--platform", "cpu", "--scale", "0.1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert tuple(got[name] for name in METRICS) == (
        {"value": JOINS_A_MORSEL * MORSELS, "unit": "count"},
        {"value": 0, "unit": "count"})
    # the tables' sizes are caps like any other: sized from the first whole
    # pass, never a re-record, nothing compiled inside the window
    assert got["tight_morsels_per_pass"]["value"] == 2 * MORSELS
    assert got["morsel_re_records_per_pass"]["value"] == 0
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0
    assert window_stats and all(
        st["mode"] == "streaming" and st["morsels"] == MORSELS
        for st in window_stats)


def test_the_power_mix_of_query7_and_query3_holds_no_sorted_join(tmp_path):
    """query7's star of four dimensions (``customer_demographics`` filtered
    to a seventieth of its 1,920,800 keys) and query3's two filtered
    dimensions: seven JoinNodes a pass at SF0.01, every one direct (PR 35
    read five of the seven)."""
    mix = dict(traffic.load_json("traffic", "power_pass_5u"),
               units=["query7", "query3"])
    (tmp_path / "two_units.json").write_text(json.dumps(mix))
    m = copy.deepcopy(M)
    next(c for c in m["workloads"]
         if c["name"] == "power_resident_sf1")["traffic"] = \
        str(tmp_path / "two_units")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    rc, line, err = run_cell("--manifest", str(tmp_path / "BENCHMARK.json"),
                             "--workload", "power_resident_sf1", "--seed",
                             str(2 ** 31 + 38), "--seconds", "1", "--trace",
                             "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert tuple(got[name] for name in METRICS) == (
        {"value": 7, "unit": "count"}, {"value": 0, "unit": "count"})
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0
