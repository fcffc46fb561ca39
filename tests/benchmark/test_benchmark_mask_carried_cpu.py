"""``mask_carried_filters_per_pass`` (ISSUE 31): one data file over the
``counter`` reader, appended to ``per_layer`` for the three pass-loop cells
that run query9, and read on the CPU from traced runs: ``power_resident_sf1``
as the driver runs it at SF0.01, both streamed cells over the scratch copies
of their configurations that ``test_benchmark_tight_morsels_cpu.py`` makes
(smaller morsels, ``--scale 0.1``).

One case of that file pins what this PR changes and no file here may be
edited: that ``tight_morsels_per_pass`` is the LAST of ``per_layer``.
``tests/conftest.py`` marks it as expected to fail, strictly; it is restated
here relative to the committed manifest."""
import json

import pytest
from bench_helpers import manifest, run_cell, span_metric_problems
from test_benchmark_cell_streamed_x4_cpu import window_stats  # noqa: F401
from test_benchmark_tight_morsels_cpu import (CELLS, MORSELS,  # noqa: F401
                                              PR28, small_manifest)

from benchmark import drivers, readers, run

M = manifest()
METRIC = "mask_carried_filters_per_pass"
TIGHT = "tight_morsels_per_pass"
FILTERS = 15        # query9's scalar subqueries, each over one filter
WORKLOADS = ["power_resident_sf1", "streamed_scan_sf1",
             "streamed_scan_sf1_x4"]


def test_the_metric_is_data_appended_after_tight_morsels_per_pass():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(PR28[0])
    assert names[at:at + len(PR28) + 2] == PR28 + [TIGHT, METRIC]
    listed = {m["name"]: m for m in M["per_layer"]}
    assert listed[METRIC] == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device programs",
        "moves": "pass_s", "workloads": WORKLOADS}
    assert readers.load_metric(METRIC) == {
        "layer": "device programs", "unit": "count", "moves": "pass_s",
        "reader": "counter",
        "args": {"name": "mask_carried_filters", "per": "pass",
                 "absent_is_zero": True}}
    # every listed cell reports the end-to-end metric this one moves
    by_name = {c["name"] for c in M["workloads"]}
    assert set(WORKLOADS) <= by_name
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    assert set(WORKLOADS) <= set(pass_s.get("workloads", by_name))
    # what the stale case of test_benchmark_tight_morsels_cpu.py guarded
    assert listed[TIGHT] == {
        "name": TIGHT, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "device programs",
        "moves": "pass_s", "workloads": list(CELLS)}
    assert readers.load_metric(TIGHT)["args"] == {
        "name": "tight_morsel_replays", "per": "pass", "absent_is_zero": True}
    assert span_metric_problems(M) == []


def test_a_program_without_the_counter_reads_zero_and_does_not_raise():
    """The parent commit has no ``mask_carried_filters``: its traced line
    reads 0 there, as the four-chip cell's does on this commit."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 4
    obs.counters = {"compiles": 0}
    assert readers.read_all([METRIC], obs) == {METRIC: 0.0}
    obs.counters["mask_carried_filters"] = 4 * FILTERS
    assert readers.read_all([METRIC], obs) == {METRIC: float(FILTERS)}


def test_the_power_cell_carries_query9s_fifteen_masks_a_pass():
    rc, line, err = run_cell("--workload", "power_resident_sf1", "--seed",
                             str(2 ** 31 + 31), "--seconds", "2", "--trace",
                             "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert got[METRIC] == {"value": FILTERS, "unit": "count"}
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_timed_morsel_of_query9_carries_its_masks_on_one_chip_only(
        cell, small_manifest, capsys, window_stats):
    rc = run.main(["--manifest", small_manifest, "--workload", cell,
                   "--seed", str(2 ** 31 + 311), "--seconds", "1",
                   "--trace", "1", "--platform", "cpu", "--scale", "0.1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # a replica never compacted: the rule takes nothing out under a mesh
    want = 0 if cell.endswith("_x4") else FILTERS * MORSELS
    assert got[METRIC] == {"value": want, "unit": "count"}
    assert got[TIGHT]["value"] == 2 * MORSELS
    assert got["morsel_re_records_per_pass"]["value"] == 0
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0
    assert window_stats and all(
        st["mode"] == "streaming" and st["morsels"] == MORSELS
        and st.get("re_records", 0) == 0 for st in window_stats)
