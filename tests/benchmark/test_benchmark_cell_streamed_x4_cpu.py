"""The committed four-chip cell ``streamed_scan_sf1_x4``: its manifest
entries, its configuration beside the one-chip one's, and a run on the CPU
over four of conftest's virtual devices. Under SF1 the committed engine
block streams nothing (SF0.1's ``store_sales`` has 288K rows), so the run
takes a scratch copy of the configuration with smaller morsels and a lower
threshold, at ``--scale 0.1``, under the committed cell's name and metrics.

The manifest's rules for any number of cells are restated here relative to
what is committed: four cases of the older files count from "three cells,
none on four chips" (``conftest.py`` beside this file names them)."""
import copy
import json

import pytest
from bench_helpers import (ACCEPTED, manifest, run_cell, shape_problems,
                           span_metric_problems)
from test_benchmark_manifest import _with_cells

from benchmark import drivers, run, traffic

CELL, CONFIG, ONE_CHIP = ("streamed_scan_sf1_x4", "nds_sf1_streamed_x4",
                          "nds_sf1_streamed")
NEW_METRICS = ["stage_sharded_ms_per_pass", "collective_ms_per_pass",
               "collective_mb_per_pass", "morsel_re_records_per_pass"]
M = manifest()


def test_the_cell_stands_after_the_accepted_three_on_four_chips():
    cells = M["workloads"]
    assert [c["name"] for c in cells[:len(ACCEPTED)]] == \
        [a[0] for a in ACCEPTED]
    cell = cells[len(ACCEPTED)]
    assert cell == dict(cell, name=CELL, config=CONFIG,
                        traffic="streamed_pass_q3q9", chips=4)
    assert shape_problems(M) == [] and span_metric_problems(M) == []
    assert sum(c["chips"] == 4 for c in cells) == 1
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["scale", "units", "engine", "executors"]
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    assert CELL in pass_s["workloads"]


def test_the_four_new_metrics_are_data_over_the_readers_that_are_there():
    listed = {m["name"]: m for m in M["per_layer"]}
    assert list(listed)[-len(NEW_METRICS):] == NEW_METRICS
    for name in NEW_METRICS:
        assert listed[name]["moves"] == "pass_s"
        assert CELL in listed[name]["workloads"]
    assert listed["morsel_re_records_per_pass"]["workloads"] == [
        "streamed_scan_sf1", CELL]
    # the one-chip staging span is not this cell's
    assert CELL not in listed["stage_ms_per_pass"]["workloads"]


def test_the_configuration_is_the_one_chip_one_but_for_the_sharding():
    mine = traffic.load_json("configs", CONFIG)
    base = traffic.load_json("configs", ONE_CHIP)
    differing = {k for k in set(mine) | set(base)
                 if mine.get(k) != base.get(k)}
    assert differing == {"name", "chips", "engine", "deployment",
                         "guarantees", "reduced_why", "source", "assumed"}
    assert mine["engine"] == dict(base["engine"], mesh_shards=4)
    assert mine["chips"] == 4 and mine["guarantees"][:2] == base["guarantees"]
    assert "four chips" in mine["guarantees"][2]
    assert set(mine["reduced_why"]) == set(base["reduced_why"]) | {
        "executors"}


def _with_added(monkeypatch, four: int, one: int) -> dict:
    """The committed manifest plus ``four`` cells on four chips and ``one``
    on one chip, over configuration files that exist only here."""
    return _with_cells(monkeypatch, [(4, 4, 4)] * four + [(1, 1, 0)] * one)


def test_cells_appended_after_this_one_keep_the_manifests_rules(monkeypatch):
    """What ``test_benchmark_add_by_files`` appends — one cell on one chip,
    one on four — after the committed cells, however many those are."""
    m = _with_added(monkeypatch, four=1, one=1)
    assert [c["chips"] for c in m["workloads"]] == \
        [c["chips"] for c in M["workloads"]] + [4, 1]
    assert shape_problems(m) == []


N = len(M["workloads"])
FOUR = sum(c["chips"] == 4 for c in M["workloads"])
OVER = (N + 4) // 2 + 1         # one four-chip cell too many among N + 4


@pytest.mark.parametrize("four,one,says", [
    (12 - FOUR, 12 - (N - FOUR), []),
    (OVER - FOUR, 4 - (OVER - FOUR),
     [f"{OVER} of {N + 4} cells ask for 4 chips"]),
    (0, 25 - N, ["25 cells, at most 24"]),
], ids=["half_of_24", "one_over_half", "25_cells"])
def test_the_half_rule_and_the_24_count_from_what_is_committed(
        monkeypatch, four, one, says):
    assert shape_problems(_with_added(monkeypatch, four, one)) == says


@pytest.fixture(scope="module")
def small_manifest(tmp_path_factory):
    """The committed manifest with the cell pointed at a scratch copy of its
    configuration: ``chunk_rows`` 65,536 and ``out_of_core_min_rows``
    100,000, so that SF0.1's ``store_sales`` streams in 5 morsels. The copy
    lies beside the scratch manifest and is named by its absolute path
    (``load_json`` joins the name onto ``configs/``): nothing is written
    under ``benchmark/``, whose listings ``test_benchmark_add_by_files``
    compares on another worker."""
    tmp = tmp_path_factory.mktemp("bench_x4")
    doc = traffic.load_json("configs", CONFIG)
    doc["engine"] = dict(doc["engine"], chunk_rows=65536,
                         out_of_core_min_rows=100000)
    (tmp / "small.json").write_text(json.dumps(doc))
    m = copy.deepcopy(M)
    next(c for c in m["workloads"] if c["name"] == CELL)["config"] = \
        str(tmp / "small")
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    yield str(tmp / "BENCHMARK.json")
    from nds_tpu.obs.trace import TRACER
    TRACER.configure(enabled=False)


@pytest.fixture
def window_stats(monkeypatch):
    """``last_exec_stats`` of every statement of the window, in order."""
    from nds_tpu.engine import Session
    sql, window, seen = Session.sql, drivers.PassLoop.window, []

    def spy(self, query, *a, **kw):
        table = sql(self, query, *a, **kw)
        seen.append(dict(self.last_exec_stats))
        return table

    def from_the_windows_start(self, seconds):
        del seen[:]
        return window(self, seconds)
    monkeypatch.setattr(Session, "sql", spy)
    monkeypatch.setattr(drivers.PassLoop, "window", from_the_windows_start)
    return seen


def _run(capsys, manifest_path, trace, seed):
    rc = run.main(["--manifest", manifest_path, "--workload", CELL,
                   "--seed", str(seed), "--seconds", "1", "--trace",
                   str(trace), "--platform", "cpu", "--scale", "0.1"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_the_cell_runs_sharded_and_answers_as_the_reference(
        small_manifest, capsys, window_stats):
    rc, line = _run(capsys, small_manifest, 0, 2 ** 31 + 281)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["attempted"] == len(window_stats) >= 2
    assert sorted(line["metrics"]) == ["pass_s", "setup_s"]
    assert line["device"]["count"] >= 4
    for st in window_stats:
        assert st["mode"] == "streaming" and st["mesh_shards"] == 4
        assert st["sharded_groups"] >= 1 and st["morsels"] == 5
        assert st.get("re_records", 0) == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0


def test_the_traced_run_prints_the_four_new_metrics(small_manifest, capsys,
                                                    window_stats):
    rc, line = _run(capsys, small_manifest, 1, 2 ** 31 + 282)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert set(NEW_METRICS) <= set(got) and "pass_s" not in got
    assert got["stage_sharded_ms_per_pass"]["value"] > 0
    assert got["collective_ms_per_pass"]["value"] > 0
    assert got["morsel_re_records_per_pass"]["value"] == 0
    # the counter is the statements' ExecStats.collective_bytes, summed
    passes = len(window_stats) / 2
    by_unit = sorted({st["collective_bytes"] for st in window_stats})
    assert len(by_unit) == 2 and by_unit[0] < 100e3 < 5e6 < by_unit[1]
    assert got["collective_mb_per_pass"]["value"] == pytest.approx(
        sum(st["collective_bytes"] for st in window_stats) / 1e6 / passes,
        rel=1e-12)
    assert got["collective_mb_per_pass"]["unit"] == "MB"
    # the accepted streamed metrics that need no device trace read here too
    assert {"upload_mb_per_pass", "fetch_mb_per_pass", "merge_ms_per_pass",
            "dispatch_host_ms_per_pass", "device_wait_ms_per_pass",
            "window_compiles.pass", "plan_s"} <= set(got)
    assert "stage_ms_per_pass" not in got


def test_the_cell_as_the_driver_runs_it(small_manifest):
    """A new process: it refuses on one device, and runs on four."""
    args = ("--manifest", small_manifest, "--workload", CELL, "--seed",
            "283", "--seconds", "1", "--trace", "0")
    rc, line, err = run_cell(*args, scale="0.1")
    assert rc == run.EXIT_NO_DEVICE and line is None
    assert "the cell needs 4 chips, JAX found 1" in err
    rc, line, err = run_cell(*args, devices=4, scale="0.1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
