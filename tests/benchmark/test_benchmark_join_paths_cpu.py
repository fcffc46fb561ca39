"""``direct_joins_per_pass`` and ``sorted_joins_per_pass`` (ISSUE 35): two
data files over the ``counter`` reader, appended to ``per_layer`` for the
four cells that report ``pass_s``, and read on the CPU from traced runs: the
power mix cut to two of its units (a scratch copy named by its absolute
path, SF0.01) and both streamed cells over the scratch copies of their
configurations that ``test_benchmark_tight_morsels_cpu.py`` makes (smaller
morsels, ``--scale 0.1``).

One case of ``test_benchmark_cell_strata_cpu.py`` pins what this PR changes
and no file here may be edited: that PR 32's four counters are the LAST of
``per_layer``. ``tests/conftest.py`` marks it as expected to fail, strictly;
it is restated here relative to the committed manifest."""
import copy
import json

import pytest
from bench_helpers import manifest, run_cell, span_metric_problems
from test_benchmark_cell_streamed_x4_cpu import window_stats  # noqa: F401
from test_benchmark_tight_morsels_cpu import (CELLS, MORSELS,  # noqa: F401
                                              small_manifest)

from benchmark import drivers, readers, run, traffic

M = manifest()
METRICS = {"direct_joins_per_pass": ("direct_joins", "higher"),
           "sorted_joins_per_pass": ("sorted_joins", "lower")}
PR32 = {"window_nodes_per_pass": "window_nodes",
        "rollup_sets_per_pass": "rollup_sets",
        "setop_nodes_per_pass": "setop_nodes",
        "outer_joins_per_pass": "outer_joins"}
STRATA = "power_stratified_sf1"
WORKLOADS = ["power_resident_sf1", "streamed_scan_sf1",
             "streamed_scan_sf1_x4", STRATA]


def test_the_two_metrics_are_data_appended_after_pr_32s_four_counters():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(next(iter(PR32)))
    assert names[at:at + len(PR32) + len(METRICS)] == \
        list(PR32) + list(METRICS)
    listed = {m["name"]: m for m in M["per_layer"]}
    by_name = {c["name"] for c in M["workloads"]}
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    # every cell that reports pass_s, and no other
    assert WORKLOADS == pass_s["workloads"] and set(WORKLOADS) <= by_name
    for name, (counter, better) in METRICS.items():
        assert listed[name] == {
            "name": name, "unit": "count", "better": better,
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": WORKLOADS}
        assert readers.load_metric(name) == {
            "layer": "device programs", "unit": "count", "moves": "pass_s",
            "reader": "counter",
            "args": {"name": counter, "per": "pass",
                     "absent_is_zero": True}}
    # what the stale case of test_benchmark_cell_strata_cpu.py guarded
    for name, counter in PR32.items():
        assert listed[name] == {
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": [STRATA]}
        assert readers.load_metric(name)["args"] == {
            "name": counter, "per": "pass", "absent_is_zero": True}
    assert span_metric_problems(M) == []


def test_a_program_without_the_counters_reads_zero_and_does_not_raise():
    """The parent commit has neither counter: its traced line reads 0."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 4
    obs.counters = {"compiles": 0}
    assert readers.read_all(list(METRICS), obs) == dict.fromkeys(METRICS, 0.0)
    obs.counters.update(direct_joins=4 * 7, sorted_joins=4 * 2)
    assert readers.read_all(list(METRICS), obs) == {
        "direct_joins_per_pass": 7.0, "sorted_joins_per_pass": 2.0}


def test_the_power_mix_joins_directly_and_counts_the_same_in_every_pass(
        tmp_path):
    """query7 (a star of four dimensions) and query3 of ``power_pass_5u``,
    as the driver runs the cell: the direct path is taken, and a pass's
    counts are whole numbers — the same static counts every pass."""
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
                             str(2 ** 31 + 35), "--seconds", "2", "--trace",
                             "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 4                   # two passes or more
    got = line["metrics"]
    direct = got["direct_joins_per_pass"]
    assert direct["unit"] == "count" and direct["value"] > 0
    for name in METRICS:
        assert got[name]["value"] == int(got[name]["value"])
    # both units join: seven JoinNodes a pass at SF0.01, five of them direct
    assert direct["value"] + got["sorted_joins_per_pass"]["value"] == 7
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0


#: (direct, sorted) joins a pass. query3's morsel program joins the morsel to
#: a filtered ``date_dim`` and a filtered ``item``, query9's holds no join. On
#: one chip both filters compact, the lookup table is sized from what is left
#: (4 x its bucket) and the dimension's whole key span no longer fits: the
#: sort-based path. A mesh replica compacts nothing, the build side keeps the
#: dimension's capacity and its span fits: the direct path.
STREAMED = {"streamed_scan_sf1": (0, 2 * MORSELS),
            "streamed_scan_sf1_x4": (2 * MORSELS, 0)}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_streamed_mix_counts_a_morsels_joins_once_a_dispatch(
        cell, small_manifest, capsys, window_stats):
    rc = run.main(["--manifest", small_manifest, "--workload", cell,
                   "--seed", str(2 ** 31 + 351), "--seconds", "1",
                   "--trace", "1", "--platform", "cpu", "--scale", "0.1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    # every morsel of every pass dispatches query3's two joins once: four
    # replicas run one dispatch
    assert tuple(got[name] for name in METRICS) == tuple(
        {"value": n, "unit": "count"} for n in STREAMED[cell])
    assert got["morsel_re_records_per_pass"]["value"] == 0
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert window_stats and all(
        st["mode"] == "streaming" and st["morsels"] == MORSELS
        for st in window_stats)
