"""BENCHMARK.json and every data file it names load and cross-reference."""
import json
import os
import re

import pytest
from bench_helpers import BENCH, ROOT, manifest

from benchmark import drivers, readers, run, traffic

M = manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
E2E = {m["name"]: m for m in M["end_to_end"]}
CELLS = {c["name"]: c for c in M["workloads"]}


def reports(metric: dict) -> list:
    """The cells that report a metric."""
    return metric.get("workloads", list(CELLS))


def test_top_level_keys_and_command():
    assert sorted(M) == sorted(["command", "paths", "run_seconds", "configs",
                                "workloads", "end_to_end", "per_layer"])
    assert M["command"] == ["python3", "benchmark/run.py"]
    assert M["paths"] == ["benchmark", "tests/benchmark"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 << 10


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = M["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 180 + 1200 <= 43200


def test_cells_in_the_issues_order_on_one_chip():
    assert list(CELLS) == ["power_resident_sf1", "streamed_scan_sf1",
                           "served_dash_sf1"]
    assert all(c["chips"] == 1 for c in CELLS.values())
    pairs = [(c["config"], c["traffic"]) for c in CELLS.values()]
    assert len(set(pairs)) == len(pairs)


@pytest.mark.parametrize("cfg", M["configs"], ids=lambda c: c["name"])
def test_config_file(cfg):
    assert sorted(cfg) == ["file", "name", "reduced", "source", "why"]
    assert NAME.match(cfg["name"])
    assert cfg["file"].startswith("benchmark/configs/")
    assert 1 <= len(cfg["source"]) <= 200 and 1 <= len(cfg["why"]) <= 200
    with open(os.path.join(ROOT, cfg["file"])) as f:
        doc = json.load(f)
    assert doc["name"] == cfg["name"]
    # every cut is stated, none is a width, the guarantees and the stated
    # precision (what the lower-precision control undercuts) are there
    assert set(cfg["reduced"]) == set(doc["reduced_why"])
    for key in cfg["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden|width)$", key)
    assert doc["guarantees"] and doc["precision"]["decimal"] in (
        "exact_i64", "float32")
    assert doc["precision"]["lower"] and doc["control"]["kind"] in (
        "engine", "reference_bf16")
    assert doc["limits"]["wrong_cells"] == 0
    if doc["precision"]["decimal"] == "exact_i64":
        assert doc["limits"]["decimal_err"] == 0
    assert any(c["config"] == cfg["name"] for c in CELLS.values())


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda c: c["name"])
def test_cell_files(cell):
    assert sorted(cell) == ["chips", "config", "name", "traffic", "why"]
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert cell["config"] in {c["name"] for c in M["configs"]}
    mix = traffic.load_json("traffic", cell["traffic"])
    assert mix["driver"] in drivers.DRIVERS
    for unit in mix["units"]:
        for ext in (".tpl", ".py"):
            assert os.path.isfile(os.path.join(BENCH, "units", unit + ext))
    # the seed instantiates every statement, with nothing left to fill
    for st in traffic.statements(mix, 2 ** 31 + 11):
        assert "[" not in st.sql and st.params
    # the cell reports setup_s, another end-to-end metric and a layer metric
    mine = [m["name"] for m in M["end_to_end"] if cell["name"] in reports(m)]
    assert "setup_s" in mine and len(mine) >= 2
    assert any(cell["name"] in reports(m) for m in M["per_layer"])


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source",
                           "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.25
    assert set(reports(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    spec = readers.load_metric(metric["name"])
    assert spec["reader"] in readers.READERS
    for key in ("layer", "unit", "moves"):
        assert spec[key] == metric[key]
    # it moves one end-to-end metric, which every cell it lists reports
    moved = E2E[metric["moves"]]
    assert set(reports(metric)) <= set(reports(moved))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_names_are_unique():
    for group in (M["configs"], M["workloads"],
                  M["end_to_end"] + M["per_layer"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


def test_peaks_table_and_unknown_device_kind():
    v5e = run.peak_for("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12 and "TPU v5e" in v5e["source"]
    with pytest.raises(KeyError):
        run.peak_for("TPU v9 imaginary")


@pytest.mark.parametrize("kind,name", [("configs", "nope"),
                                       ("traffic", "nope")])
def test_unknown_data_file_is_an_error(kind, name):
    with pytest.raises(SystemExit):
        traffic.load_json(kind, name)


def test_unknown_metric_unit_and_workload_are_errors():
    with pytest.raises(SystemExit):
        readers.load_metric("nope")
    with pytest.raises(SystemExit):
        traffic.instantiate("query_nope", 1)
    with pytest.raises(SystemExit):
        run.find_cell(M, "nope")


def test_the_seed_orders_the_work_and_the_mix_fixes_the_parameters():
    mix = traffic.load_json("traffic", "power_pass_5u")
    a, b = traffic.statements(mix, 123), traffic.statements(mix, 123)
    assert [s.sql for s in a] == [s.sql for s in b]
    orders = {tuple(s.unit for s in traffic.statements(mix, seed))
              for seed in range(40)}
    assert len(orders) > 10                      # another order ...
    c = traffic.statements(mix, 2 ** 31 + 124)
    assert {s.unit: s.sql for s in a} == {s.unit: s.sql for s in c}  # same work
    other = dict(mix, param_seed=mix["param_seed"] + 1)
    assert {s.unit: s.params for s in traffic.statements(other, 123)} != \
        {s.unit: s.params for s in a}
    walks = [traffic.client_walk(3, c) for c in range(4)]
    assert walks == [[0, 1, 2], [1, 2, 0], [2, 0, 1], [0, 1, 2]]
