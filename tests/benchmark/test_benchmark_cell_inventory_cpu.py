"""The committed cell ``power_inventory_sf1`` (ISSUE 42): its manifest
entries, its configuration beside ``nds_sf1_resident``'s, the four row
counters as data over the ``counter`` reader, and ONE traced run of the cell
on the CPU at SF0.01 that every case here shares, with its lower-precision
control run after it in the same process.

The run goes over a scratch copy of the mix with another ``param_seed``: at
SF0.01 ``item`` holds 180 rows and the committed draw of four manufacturers
meets none of them, so query37 and query82 answer nothing and the control —
bfloat16 over the one DECIMAL the four answers hold, ``i_current_price`` —
has nothing to move. Draw 224 gives each a row.

The counters' constants are what a spy counts while each program is traced:
the capacity of every scan, of the probe side of every join by the path it
took, and of every expansion. They are compared with what the traced line
reads, a pass being one dispatch of each of the four programs.

Three cases of the older test files pin what appending this cell changes, in
files no cell PR may edit: that ``power_stratified_sf1`` is the LAST cell
to report ``pass_s``, that the join counters list exactly four cells, and
the half rule's arithmetic over "at most five cells committed".
``tests/conftest.py`` marks them as expected to fail, strictly; they are
restated here relative to the committed manifest."""
import contextlib
import copy
import io
import json
import threading

import pytest
from bench_helpers import (ACCEPTED, RESULT_KEYS, manifest, shape_problems,
                           span_metric_problems)
from test_benchmark_manifest import _with_cells

from benchmark import drivers, readers, run, traffic

CELL, CONFIG, TWIN = ("power_inventory_sf1", "nds_sf1_inventory",
                      "nds_sf1_resident")
MIX = "power_pass_inventory"
UNITS = ["query72", "query21", "query37", "query82"]
STRATA = "power_stratified_sf1"
#: the cells that reported pass_s before this one, in the manifest's order
BEFORE = ["power_resident_sf1", "streamed_scan_sf1", "streamed_scan_sf1_x4",
          STRATA]
#: metric -> (counter, better)
ROWS = {"scan_mrows_per_pass": ("scan_rows", "lower"),
        "direct_probe_mrows_per_pass": ("direct_probe_rows", "higher"),
        "sorted_probe_mrows_per_pass": ("sorted_probe_rows", "lower"),
        "expanded_join_mrows_per_pass": ("expanded_join_rows", "lower")}
#: the resident pass cells' metrics that this cell reports too
SHARED = ["load_s", "first_pass_s", "record_s", "compile_s",
          "window_compiles.pass", "window_xla_compiles.pass", "plan_s",
          "dispatch_host_ms_per_pass", "device_wait_ms_per_pass",
          "table_upload_s", "xla_trace_lower_s", "xla_compile_s",
          "fetch_mb_per_pass", "outer_joins_per_pass",
          "direct_joins_per_pass", "sorted_joins_per_pass"]
TRACED = ["device_busy_ms_per_pass", "device_idle_pct.pass", "scan_roofline"]
M = manifest()
LISTED = {m["name"]: m for m in M["per_layer"]}


# -- the manifest ---------------------------------------------------------------

def test_the_cell_stands_after_the_accepted_five_on_one_chip():
    cells = M["workloads"]
    assert [c["name"] for c in cells[:len(ACCEPTED)]] == \
        [a[0] for a in ACCEPTED]
    assert [c["name"] for c in cells[3:5]] == ["streamed_scan_sf1_x4", STRATA]
    assert cells[5] == dict(cells[5], name=CELL, config=CONFIG, traffic=MIX,
                            chips=1)
    assert shape_problems(M) == [] and span_metric_problems(M) == []
    assert sum(c["chips"] == 4 for c in cells) == 1
    entry = next(c for c in M["configs"] if c["name"] == CONFIG)
    assert M["configs"].index(entry) == 5
    assert entry["reduced"] == ["scale", "units"]
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"
    for text in (entry["source"], entry["why"], cells[5]["why"]):
        assert 1 <= len(text) <= 200 and "\n" not in text
    for number in (72, 21, 37, 82):
        assert str(number) in entry["source"]
    assert "inventory" in entry["source"] and "q72" in cells[5]["why"]


def test_pass_s_lists_the_cell_after_the_four_that_were_there():
    """What the stale case of ``test_benchmark_cell_strata_cpu.py`` guarded:
    the strata cell reports ``pass_s`` under the bound that was there, right
    after the four-chip cell; this one after it."""
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    assert pass_s["workloads"][:5] == BEFORE + [CELL]
    assert pass_s["bound"] == 0.05 and pass_s["source"] == "host_clock"
    strata = next(c for c in M["configs"] if c["name"] == "nds_sf1_strata")
    assert M["configs"].index(strata) == 4
    assert M["workloads"][4]["name"] == STRATA
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup             # every cell reports it


def test_the_join_counters_list_every_pass_cell_in_the_manifests_order():
    """What the stale case of ``test_benchmark_join_paths_cpu.py`` guarded,
    counted from the committed manifest: PR 32's four counters and PR 35's
    two stand together in that order as data over the ``counter`` reader,
    the two list every cell that reports ``pass_s`` and no other, and of the
    four only ``outer_joins_per_pass`` gained this cell (query72's two)."""
    pr32 = {"window_nodes_per_pass": "window_nodes",
            "rollup_sets_per_pass": "rollup_sets",
            "setop_nodes_per_pass": "setop_nodes",
            "outer_joins_per_pass": "outer_joins"}
    pr35 = {"direct_joins_per_pass": ("direct_joins", "higher"),
            "sorted_joins_per_pass": ("sorted_joins", "lower")}
    names = list(LISTED)
    at = names.index("window_nodes_per_pass")
    assert names[at:at + 6] == list(pr32) + list(pr35)
    pass_s = next(m for m in M["end_to_end"] if m["name"] == "pass_s")
    assert pass_s["workloads"][:4] == BEFORE
    assert set(pass_s["workloads"]) <= {c["name"] for c in M["workloads"]}
    for name, (counter, better) in pr35.items():
        assert LISTED[name] == {
            "name": name, "unit": "count", "better": better,
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": pass_s["workloads"]}
        assert readers.load_metric(name) == {
            "layer": "device programs", "unit": "count", "moves": "pass_s",
            "reader": "counter",
            "args": {"name": counter, "per": "pass",
                     "absent_is_zero": True}}
    for name, counter in pr32.items():
        cells = [STRATA, CELL] if name == "outer_joins_per_pass" \
            else [STRATA]
        assert LISTED[name] == {
            "name": name, "unit": "count", "better": "higher",
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": cells}
        assert readers.load_metric(name)["args"] == {
            "name": counter, "per": "pass", "absent_is_zero": True}


N = len(M["workloads"])
FOUR = sum(c["chips"] == 4 for c in M["workloads"])


@pytest.mark.parametrize("added,says", [
    # as many four-chip cells as bring them to half of the cells: allowed
    (lambda: N - 2 * FOUR, None),
    # one more than that: refused
    (lambda: N - 2 * FOUR + 1, "{four} of {cells} cells ask for 4 chips"),
], ids=["half", "one_over_half"])
def test_the_half_rule_counts_from_what_is_committed(monkeypatch, added,
                                                     says):
    """What the stale case of ``test_benchmark_cell_streamed_x4_cpu.py``
    guarded (its arithmetic adds four cells in all, which holds up to five
    committed cells): k four-chip cells appended to the N committed ones,
    F of them on four chips, are allowed iff F + k <= (N + k) // 2."""
    k = added()
    assert k >= 1
    m = _with_cells(monkeypatch, [(4, 4, 4)] * k)
    want = [] if says is None else [says.format(four=FOUR + k, cells=N + k)]
    assert shape_problems(m) == want
    assert (FOUR + k <= (N + k) // 2) == (says is None)


# -- the configuration ----------------------------------------------------------

def test_the_configuration_is_the_resident_one_over_the_inventory_units():
    mine = traffic.load_json("configs", CONFIG)
    base = traffic.load_json("configs", TWIN)
    differing = {k for k in set(mine) | set(base)
                 if mine.get(k) != base.get(k)}
    assert differing == {"name", "source", "deployment", "units",
                         "guarantees", "precision", "control", "reduced_why",
                         "tables_on_device", "assumed", "units_left_out"}
    for key in ("engine", "limits", "want_modes", "scale", "chips", "schema",
                "data_seed", "units_in_source"):
        assert mine[key] == base[key]
    assert mine["engine"] == {"chunk_rows": 4194304,
                              "out_of_core_min_rows": 48000000,
                              "decimal_physical": "i64"}
    assert mine["limits"] == {"wrong_cells": 0, "decimal_err": 0,
                              "float_rel_err": 1e-12}
    assert mine["guarantees"][:2] == base["guarantees"]
    assert "outer join" in mine["guarantees"][2]
    assert "no result cache and no segment cache" in mine["guarantees"][3]
    assert mine["units"] == len(UNITS) == len(mine["assumed"])
    assert sorted(mine["assumed"]) == sorted(UNITS)
    assert set(mine["reduced_why"]) == {"scale", "units"}
    assert mine["tables_on_device"] == {
        "inventory": 11745000, "catalog_sales": 1439080,
        "store_sales": 2880875, "catalog_returns": 144445}
    assert sorted(mine["units_left_out"]) == ["query22",
                                              "query39a_query39b"]
    for why in mine["units_left_out"].values():
        assert "C9" in why
    mix = traffic.load_json("traffic", MIX)
    assert mix["driver"] == "pass_loop" and mix["units"] == UNITS
    assert mix["param_seed"] == 1
    assert mix["trace_slice_s"] <= M["run_seconds"] / 2


def test_the_control_is_the_one_that_can_fail_and_says_what_caught_it():
    """The statements do no decimal arithmetic, so the other exact cells'
    control (``--decimal f64``) answers the same; the kept one rounds the
    reference's non-exact cells to bfloat16, and the file names the limit,
    the column and the units that catch it."""
    mine = traffic.load_json("configs", CONFIG)
    assert mine["control"] == {"kind": "reference_bf16"}
    assert mine["precision"]["decimal"] == "exact_i64"
    lower = mine["precision"]["lower"]
    for word in ("bfloat16", "decimal_err", "i_current_price", "query37",
                 "query82", "f64"):
        assert word in lower, word


# -- the four metrics -----------------------------------------------------------

def test_the_four_row_counters_are_data_appended_after_what_was_there():
    names = list(LISTED)
    assert names[-len(ROWS):] == list(ROWS)
    assert names[-len(ROWS) - 1] == "decode_view_cols_per_pass"
    for name, (counter, better) in ROWS.items():
        assert LISTED[name] == {
            "name": name, "unit": "Mrows", "better": better,
            "source": "program_counter", "layer": "device programs",
            "moves": "pass_s", "workloads": BEFORE + [CELL]}
        assert readers.load_metric(name) == {
            "layer": "device programs", "unit": "Mrows", "moves": "pass_s",
            "reader": "counter",
            "args": {"name": counter, "per": "pass", "divide": 1e6,
                     "absent_is_zero": True}}
    for name in SHARED + TRACED:
        assert LISTED[name]["workloads"][-1] == CELL
        assert STRATA in LISTED[name]["workloads"]
    # the strata's plan shapes and the streamed cells' metrics are not ours
    for name in ("window_nodes_per_pass", "rollup_sets_per_pass",
                 "setop_nodes_per_pass", "mask_carried_filters_per_pass",
                 "tight_morsels_per_pass", "stage_ms_per_pass",
                 "decode_view_cols_per_pass"):
        assert CELL not in LISTED[name]["workloads"]
    # nothing that stood before lost its file or changed what it moves
    for m in M["per_layer"]:
        assert readers.load_metric(m["name"])["moves"] == m["moves"]


def test_a_program_without_the_counters_reads_zero_and_does_not_raise():
    """The parent commit moves none of the four: its traced line reads 0."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 4
    obs.counters = {"direct_joins": 12}
    assert readers.read_all(list(ROWS), obs) == dict.fromkeys(ROWS, 0.0)
    obs.counters.update(scan_rows=4 * 12_582_912, expanded_join_rows=4 * 8)
    got = readers.read_all(list(ROWS), obs)
    assert got["scan_mrows_per_pass"] == 12.582912
    assert got["expanded_join_mrows_per_pass"] == 8e-6
    assert got["direct_probe_mrows_per_pass"] == 0.0


@pytest.mark.parametrize("unit", UNITS)
def test_a_units_parameters_are_the_mixs_whatever_the_seed(unit):
    mix = traffic.load_json("traffic", MIX)
    by_seed = [{s.unit: s for s in traffic.statements(mix, seed)}
               for seed in (1, 2 ** 31 + 42)]
    a, b = (d[unit] for d in by_seed)
    assert a.params and a.sql == b.sql and "[" not in a.sql
    assert a.params == traffic.instantiate(unit, mix["param_seed"]).params
    if unit != "query72":       # a date inside TPC-DS's own range
        assert 1998 <= int(a.params["YEAR"]) <= 2002
        assert f"'{a.params['YEAR']}-0{a.params['MONTH']}-" in a.sql


# -- one traced run, and the control after it -------------------------------------

class Spy:
    """Counts, while a program is traced, the capacities the four counters
    sum — from outside the code that sums them: per ``CompiledQuery`` (its
    module's name) and trace, since ``precompile_parallel`` traces programs
    side by side on threads."""

    def __init__(self, mp):
        from nds_tpu.engine.jax_backend.executor import (CompiledQuery,
                                                         JaxExecutor)
        self.local = threading.local()
        self.traced: dict = {}      # module -> {"scan": .., "direct": ..}
        self.dispatched: dict = {}  # module -> (times, cq.join_paths)
        spy = self

        def patch(cls, name, wrap):
            real = getattr(cls, name)
            mp.setattr(cls, name, lambda self, *a, **kw: wrap(real, self,
                                                              *a, **kw))

        def trace(real, cq, *a, **kw):
            spy.local.now = spy.traced[cq.module_name] = dict.fromkeys(
                ("scan", "direct", "sorted", "expanded"), 0)
            try:
                return real(cq, *a, **kw)
            finally:
                spy.local.now = None

        def add(what, rows):
            now = getattr(spy.local, "now", None)
            if now is not None:
                now[what] += rows

        def scan(real, ex, node):
            out = real(ex, node)
            add("scan", out.capacity)
            return out

        def fast(real, ex, node, left, right, *a, **kw):
            out = real(ex, node, left, right, *a, **kw)
            if out is not None:
                add("direct", left.capacity)
                spy.local.direct = True
            return out

        def join(real, ex, node, left, right):
            spy.local.direct = False
            out = real(ex, node, left, right)
            if node.kind != "cross" and not spy.local.direct:
                add("sorted", left.capacity)
            return out

        def expand(real, ex, *a, **kw):
            out = real(ex, *a, **kw)
            add("expanded", out[0].capacity)
            return out

        def dispatch(real, cq):
            times = spy.dispatched.get(cq.module_name, (0, None))[0]
            spy.dispatched[cq.module_name] = (times + 1, cq.join_paths)
            return real(cq)

        patch(CompiledQuery, "_trace", trace)
        patch(CompiledQuery, "count_dispatch", dispatch)
        patch(JaxExecutor, "_run_scan", scan)
        patch(JaxExecutor, "_fast_join", fast)
        patch(JaxExecutor, "_join", join)
        patch(JaxExecutor, "_expand_combine", expand)


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    """(result line, timed statements' stats, spy, control's result line):
    the cell traced over the scratch mix, in this process, and then its
    control — the same programs, found compiled."""
    from nds_tpu.engine import Session
    from nds_tpu.obs.trace import TRACER
    tmp = tmp_path_factory.mktemp("inventory_cell")
    mix = dict(traffic.load_json("traffic", MIX), param_seed=224)
    (tmp / "draw_224.json").write_text(json.dumps(mix))
    m = copy.deepcopy(M)
    next(c for c in m["workloads"] if c["name"] == CELL)["traffic"] = \
        str(tmp / "draw_224")
    (tmp / "BENCHMARK.json").write_text(json.dumps(m))
    sql, window, seen = Session.sql, drivers.PassLoop.window, []

    def stats_spy(self, query, *a, **kw):
        table = sql(self, query, *a, **kw)
        seen.append(dict(self.last_exec_stats))
        return table

    def from_the_windows_start(self, seconds):
        del seen[:]
        spy.dispatched.clear()
        return window(self, seconds)

    def main(*more):
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(["--manifest", str(tmp / "BENCHMARK.json"),
                           "--workload", CELL, "--seed", str(2 ** 31 + 421),
                           "--platform", "cpu", "--scale", "0.01", *more])
        assert rc == 0
        return json.loads(out.getvalue().strip().splitlines()[-1])
    with pytest.MonkeyPatch.context() as mp:
        spy = Spy(mp)
        mp.setattr(Session, "sql", stats_spy)
        mp.setattr(drivers.PassLoop, "window", from_the_windows_start)
        try:
            line = main("--seconds", "2", "--trace", "1")
        finally:
            TRACER.configure(enabled=False)
        stats, dispatched = list(seen), dict(spy.dispatched)
        control = main("--seconds", "0.5", "--trace", "0", "--control", "1")
    spy.dispatched = dispatched
    return line, stats, spy, control


def test_the_cell_answers_as_the_references_do(runs):
    line, _stats, _spy, _control = runs
    assert list(line)[:5] == RESULT_KEYS
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= len(UNITS)
    assert line["attempted"] % len(UNITS) == 0          # whole passes
    compared = line["compared"]
    assert compared["wrong_cells"] == {"value": 0, "limit": 0}
    assert compared["decimal_err"] == {"value": 0.0, "limit": 0}
    assert compared["float_rel_err"] == {"value": 0.0, "limit": 1e-12}


def test_every_timed_statement_ran_compiled_and_left_the_device_never(runs):
    line, stats, _spy, _control = runs
    assert len(stats) == line["attempted"]
    for st in stats:
        assert st["mode"] == "compiled"
        assert not st.get("nojit_reason") and not st.get("fallback_reasons")


def test_the_lower_precision_control_comes_out_not_correct(runs):
    """bfloat16 moves the prices query37 and query82 pass through (24.03
    reads 24.0, 14.82 reads 14.8125) and nothing else: ``decimal_err`` is
    the limit that catches it, no cell is wrong."""
    line, _stats, _spy, control = runs
    assert control["correct"] is False
    assert control["attempted"] % len(UNITS) == 0
    assert control["failed"] == control["attempted"] // 2   # 2 of 4 units
    compared = control["compared"]
    assert compared["decimal_err"]["value"] >= 0.75 > \
        compared["decimal_err"]["limit"] == 0
    assert compared["wrong_cells"]["value"] == 0
    assert compared["float_rel_err"]["value"] == 0.0
    assert sorted(control["metrics"]) == ["pass_s", "setup_s"]
    assert line["correct"] is True


def test_a_pass_dispatches_the_four_programs_once_each(runs):
    line, _stats, spy, _control = runs
    passes = line["attempted"] // len(UNITS)
    assert sorted(spy.dispatched) == sorted(
        f"nds_{unit}_root" for unit in UNITS)
    for times, _paths in spy.dispatched.values():
        assert times == passes


@pytest.mark.parametrize("name,what", [
    ("scan_mrows_per_pass", "scan"), ("direct_probe_mrows_per_pass",
                                      "direct"),
    ("sorted_probe_mrows_per_pass", "sorted"),
    ("expanded_join_mrows_per_pass", "expanded")])
def test_a_row_counter_reads_what_the_spy_counted(runs, name, what):
    line, _stats, spy, _control = runs
    rows = sum(spy.traced[module][what] for module in spy.dispatched)
    assert rows > 0
    assert line["metrics"][name] == {"value": rows / 1e6, "unit": "Mrows"}


@pytest.mark.parametrize("unit", UNITS)
def test_a_programs_tuple_is_the_spys_and_zero_where_it_has_no_such_node(
        runs, unit):
    _line, _stats, spy, _control = runs
    module = f"nds_{unit}_root"
    counted = spy.traced[module]
    paths = spy.dispatched[module][1]
    assert paths[2:] == (counted["scan"], counted["direct"],
                         counted["sorted"], counted["expanded"])
    assert paths[0] > 0 and counted["direct"] > 0 and counted["scan"] > 0
    # a join that took neither path is a cross join: none here
    assert (paths[1] == 0) == (counted["sorted"] == 0)
    if unit == "query72":       # the expanding join goes through the sort
        assert paths[1] > 0 and counted["expanded"] > 0
    if unit == "query21":       # a star of three dimensions: nothing expands
        assert paths[1] == 0 and paths[4:] == (0, 0)


def test_the_join_and_outer_join_counters_read_the_plans_constants(runs):
    line, _stats, spy, _control = runs
    got = line["metrics"]
    assert got["outer_joins_per_pass"]["value"] == 2        # query72's two
    assert got["direct_joins_per_pass"]["value"] == sum(
        paths[0] for _n, paths in spy.dispatched.values())
    assert got["sorted_joins_per_pass"]["value"] == sum(
        paths[1] for _n, paths in spy.dispatched.values())


def test_the_window_compiled_nothing(runs):
    got = runs[0]["metrics"]
    assert got["window_compiles.pass"]["value"] == 0
    assert got["window_xla_compiles.pass"]["value"] == 0


@pytest.mark.parametrize("name", SHARED)
def test_a_pass_cells_metric_is_reported(runs, name):
    got = runs[0]["metrics"]
    assert name in got and got[name]["value"] >= 0
    assert "pass_s" not in got                  # per-layer metrics only


def test_the_device_traces_metrics_are_read_where_there_is_a_device_plane(
        runs):
    """On the CPU the trace has no device plane, so the three are left out;
    where one is read it is a share that is one."""
    line = runs[0]
    got = line["metrics"]
    assert {"busy_s", "window_s"} <= set(line["device"])
    if line["device"]["busy_s"] > 0:
        assert set(TRACED) <= set(got)
        assert 0 < got["scan_roofline"]["value"] < 100
    else:
        assert not set(TRACED) & set(got)
    assert got["device_wait_ms_per_pass"]["value"] > \
        got["dispatch_host_ms_per_pass"]["value"] > 0
