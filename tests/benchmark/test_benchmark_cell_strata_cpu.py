"""The committed cell ``power_stratified_sf1`` (ISSUE 32): its manifest
entries, its configuration beside ``nds_sf1_resident``'s, ONE traced run of
it on the CPU at SF0.01 that every case here shares, and its lower-precision
control over a scratch mix of two of its units.

The counters' constants are what the five plans hold in the programs a
timed pass dispatches: query20's and query86's windows (query57's two stand
in its CTE's program, which the segment cache serves after its first run),
query86's three grouping sets, query38's two INTERSECTs, query93's outer
join. query57 stands for stratum 5 in query47's place, and strata 6 (query22,
ROLLUP over ``inventory``) and 7 (query76, UNION ALL) are not in the mix yet
(PERF.md, PR 32): their templates and references wait under
``benchmark/units``."""
import contextlib
import copy
import io
import json

import pytest
from bench_helpers import (ACCEPTED, RESULT_KEYS, manifest, run_cell,
                           shape_problems, span_metric_problems)

from benchmark import drivers, readers, run, traffic

CELL, CONFIG, TWIN = ("power_stratified_sf1", "nds_sf1_strata",
                      "nds_sf1_resident")
MIX = "power_pass_strata"
UNITS = ["query20", "query86", "query38", "query93", "query57"]
COUNTERS = {"window_nodes_per_pass": ("window_nodes", 2),
            "rollup_sets_per_pass": ("rollup_sets", 3),
            "setop_nodes_per_pass": ("setop_nodes", 2),
            "outer_joins_per_pass": ("outer_joins", 1)}
#: the pass cells' metrics that a resident cell reports (the device trace's
#: three only where the trace holds a device plane: never on the CPU)
SHARED = ["load_s", "first_pass_s", "record_s", "compile_s",
          "window_compiles.pass", "window_xla_compiles.pass", "plan_s",
          "dispatch_host_ms_per_pass", "device_wait_ms_per_pass",
          "table_upload_s", "xla_trace_lower_s", "xla_compile_s",
          "fetch_mb_per_pass"]
TRACED = ["device_busy_ms_per_pass", "device_idle_pct.pass", "scan_roofline"]
M = manifest()


def test_the_cell_stands_after_the_accepted_four_on_one_chip():
    cells = M["workloads"]
    assert [c["name"] for c in cells[:len(ACCEPTED)]] == \
        [a[0] for a in ACCEPTED]
    assert cells[3]["name"] == "streamed_scan_sf1_x4"
    assert cells[4] == dict(cells[4], name=CELL, config=CONFIG, traffic=MIX,
                            chips=1)
    assert shape_problems(M) == [] and span_metric_problems(M) == []
    assert sum(c["chips"] == 4 for c in cells) == 1
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert M["configs"].index(entry) == 4
    assert entry["reduced"] == ["scale", "units"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for text in (entry["source"], entry["why"], cells[4]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    for number in (20, 86, 38, 93, 57):
        assert str(number) in entry["source"]
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    assert pass_s["workloads"][-1] == CELL and pass_s["bound"] == 0.05


def test_the_configuration_is_the_resident_one_over_other_units():
    mine = traffic.load_json("configs", CONFIG)
    base = traffic.load_json("configs", TWIN)
    differing = {k for k in set(mine) | set(base)
                 if mine.get(k) != base.get(k)}
    assert differing == {"name", "source", "deployment", "guarantees",
                         "reduced_why", "tables_on_device", "assumed",
                         "strata_left_out"}
    for key in ("engine", "precision", "limits", "control", "want_modes",
                "scale", "chips"):
        assert mine[key] == base[key]
    assert mine["guarantees"][:2] == base["guarantees"]
    assert "window, rollup, set operation and outer join" in \
        mine["guarantees"][2]
    assert mine["units"] == len(UNITS) == len(mine["assumed"])
    assert sorted(mine["assumed"]) == sorted(UNITS)
    assert set(mine["reduced_why"]) == {"scale", "units"}
    assert sorted(mine["tables_on_device"]) == [
        "catalog_sales", "store_returns", "store_sales", "web_sales"]
    assert sorted(mine["strata_left_out"]) == ["query22", "query76"]
    mix = traffic.load_json("traffic", MIX)
    assert mix["driver"] == "pass_loop" and mix["units"] == UNITS
    assert mix["param_seed"] == 1
    assert mix["trace_slice_s"] <= M["run_seconds"] / 2


def test_the_four_counters_are_data_over_the_reader_that_is_there():
    listed = {m["name"]: m for m in M["per_layer"]}
    assert list(listed)[-len(COUNTERS):] == list(COUNTERS)
    for name, (counter, _n) in COUNTERS.items():
        assert listed[name] == {
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": [CELL]}
        assert readers.load_metric(name) == {
            "layer": "device programs", "unit": "count", "moves": "pass_s",
            "reader": "counter",
            "args": {"name": counter, "per": "pass",
                     "absent_is_zero": True}}
    for name in SHARED + TRACED:
        assert listed[name]["workloads"][-1] == CELL
    # pinned to other cells by tests that no cell PR may edit
    for name in ("mask_carried_filters_per_pass", "tight_morsels_per_pass",
                 "morsel_re_records_per_pass", "stage_ms_per_pass"):
        assert CELL not in listed[name]["workloads"]


def test_a_program_without_the_counters_reads_zero_and_does_not_raise():
    """The parent commit moves none of the four: its traced line reads 0."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 3
    obs.counters = {"compiles": 0}
    assert readers.read_all(list(COUNTERS), obs) == dict.fromkeys(
        COUNTERS, 0.0)
    obs.counters.update({c: 3 * n for c, n in COUNTERS.values()})
    assert readers.read_all(list(COUNTERS), obs) == {
        name: float(n) for name, (_c, n) in COUNTERS.items()}


@pytest.mark.parametrize("unit", UNITS)
def test_a_units_parameters_are_the_mixs_whatever_the_seed(unit):
    mix = traffic.load_json("traffic", MIX)
    by_seed = [{s.unit: s for s in traffic.statements(mix, seed)}
               for seed in (1, 2 ** 31 + 32)]
    a, b = (d[unit] for d in by_seed)
    assert a.params and a.sql == b.sql and "[" not in a.sql
    assert a.params == traffic.instantiate(unit, mix["param_seed"]).params


@pytest.fixture(scope="module")
def traced_run():
    """The cell's one run here: traced, in this process, with every timed
    statement's ``last_exec_stats`` beside the result line."""
    from nds_tpu.engine import Session
    from nds_tpu.obs.trace import TRACER
    sql, window, seen = Session.sql, drivers.PassLoop.window, []

    def spy(self, query, *a, **kw):
        table = sql(self, query, *a, **kw)
        seen.append(dict(self.last_exec_stats))
        return table

    def from_the_windows_start(self, seconds):
        del seen[:]
        return window(self, seconds)
    out = io.StringIO()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Session, "sql", spy)
        mp.setattr(drivers.PassLoop, "window", from_the_windows_start)
        try:
            with contextlib.redirect_stdout(out):
                rc = run.main(["--workload", CELL, "--seed",
                               str(2 ** 31 + 321), "--seconds", "2",
                               "--trace", "1", "--platform", "cpu",
                               "--scale", "0.01"])
        finally:
            TRACER.configure(enabled=False)
    return rc, json.loads(out.getvalue().strip().splitlines()[-1]), seen


def test_the_cell_answers_as_the_references_do(traced_run):
    rc, line, _stats = traced_run
    assert rc == 0 and list(line)[:5] == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= len(UNITS)
    assert line["attempted"] % len(UNITS) == 0          # whole passes
    compared = line["compared"]
    assert compared["wrong_cells"] == {"value": 0, "limit": 0}
    assert compared["decimal_err"] == {"value": 0.0, "limit": 0}
    assert compared["float_rel_err"]["value"] <= 1e-12 == \
        compared["float_rel_err"]["limit"]


def test_every_timed_statement_ran_compiled_and_left_the_device_never(
        traced_run):
    _rc, line, stats = traced_run
    assert len(stats) == line["attempted"]
    for st in stats:
        assert st["mode"] == "compiled"
        assert not st.get("nojit_reason") and not st.get("fallback_reasons")


@pytest.mark.parametrize("name", list(COUNTERS))
def test_a_counter_reads_its_plans_constant(traced_run, name):
    _rc, line, _stats = traced_run
    assert line["metrics"][name] == {"value": COUNTERS[name][1],
                                     "unit": "count"}


def test_the_window_compiled_nothing(traced_run):
    got = traced_run[1]["metrics"]
    assert got["window_compiles.pass"]["value"] == 0
    assert got["window_xla_compiles.pass"]["value"] == 0


@pytest.mark.parametrize("name", SHARED)
def test_a_pass_cells_metric_is_reported(traced_run, name):
    got = traced_run[1]["metrics"]
    assert name in got and got[name]["value"] >= 0
    assert "pass_s" not in got                  # per-layer metrics only


def test_the_device_traces_metrics_are_read_where_there_is_a_device_plane(
        traced_run):
    """On the CPU the trace has no device plane, so the three are left out;
    where one is read it is a share that is one."""
    _rc, line, _stats = traced_run
    got = line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    if line["device"]["busy_s"] > 0:
        assert set(TRACED) <= set(got)
        assert 0 < got["scan_roofline"]["value"] < 100
    else:
        assert not set(TRACED) & set(got)
    assert got["device_wait_ms_per_pass"]["value"] > \
        got["dispatch_host_ms_per_pass"]["value"] > 0


def test_the_lower_precision_control_comes_out_not_correct(tmp_path):
    """--decimal f64 in the place of the exact configuration, over a scratch
    mix of two of the cell's units named by its absolute path: nothing is
    written under ``benchmark/``."""
    mix = dict(traffic.load_json("traffic", MIX), units=["query93",
                                                         "query20"])
    (tmp_path / "two_units.json").write_text(json.dumps(mix))
    m = copy.deepcopy(M)
    next(c for c in m["workloads"] if c["name"] == CELL)["traffic"] = \
        str(tmp_path / "two_units")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(m))
    rc, line, err = run_cell("--manifest", str(tmp_path / "BENCHMARK.json"),
                             "--workload", CELL, "--seed", "323",
                             "--seconds", "1", "--trace", "0", "--control",
                             "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    compared = line["compared"]
    assert compared["decimal_err"]["value"] > 0 or \
        compared["float_rel_err"]["value"] > 1e-12
    assert sorted(line["metrics"]) == ["pass_s", "setup_s"]
