"""The trace reduction on the small trace recorded on the v5e
(benchmark/testdata/record_small_trace.py: three bursts of five steps of one
jitted program, 50 ms sleeps between) and on hand-made planes."""
import os

import pytest
from bench_helpers import BENCH

from benchmark import readers, trace_reduce

TRACE = os.path.join(BENCH, "testdata", "small.xplane.pb")
WINDOW_S = 0.15669313899999793     # the recorder's clock around the slice


def test_recorded_trace_busy_union_idle_share_and_top_ops():
    r = trace_reduce.reduce_file(TRACE, WINDOW_S)
    # 15 runs of jit_small_step at about 54 us each
    assert r["busy_s"] == pytest.approx(0.00080408, rel=1e-6)
    assert r["idle_pct"] == pytest.approx(99.4868, abs=1e-3)
    ops = dict(r["device_ops"])
    assert list(ops)[0] == "jit_small_step/fusion"
    assert ops["jit_small_step/fusion"] == pytest.approx(0.000199779,
                                                         rel=1e-5)
    assert set(ops) == {"jit_small_step/" + n for n in (
        "fusion", "fusion.1", "fusion.2", "fusion.3", "copy-start",
        "copy-done")}
    # the two sleeps between the three bursts are the idle time, and the
    # host annotation names them
    gaps = dict(r["idle_gaps"])
    assert r["idle_gaps"][0][0] == "bench.sleep"
    assert gaps["bench.sleep"] == pytest.approx(0.104, abs=0.005)


def test_union_counts_overlap_once_and_gaps_take_the_covering_annotation():
    ops = [(0, 100, "%a = f32[] add()"), (50, 150, "%b = f32[] mul()"),
           (400, 500, "%a = f32[] add()")]
    mods = [(0, 150, "jit_f(123)"), (400, 500, "jit_g(9)")]
    notes = [(0, 1000, trace_reduce.SLICE_ANNOTATION),
             (140, 390, "bench.fetch:q"), (500, 1000, "bench.sql:q")]
    r = trace_reduce.reduce_planes([{"XLA Ops": ops, "XLA Modules": mods}],
                                   notes, window_s=1000e-9)
    assert r["busy_s"] == pytest.approx(250e-9)
    assert r["idle_pct"] == pytest.approx(75.0)
    assert dict(r["device_ops"]) == pytest.approx(
        {"jit_f/a": 100e-9, "jit_f/b": 100e-9, "jit_g/a": 100e-9})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"bench.fetch:q": 250e-9, "bench.sql:q": 500e-9})


def test_two_chips_average_and_no_ops_reads_nothing():
    one = {"XLA Ops": [(0, 100, "%a = x")], "XLA Modules": [(0, 100, "m")]}
    two = {"XLA Ops": [(0, 300, "%a = x")], "XLA Modules": [(0, 300, "m")]}
    r = trace_reduce.reduce_planes([one, two], [], window_s=400e-9)
    assert r["busy_s"] == pytest.approx(200e-9)
    empty = trace_reduce.reduce_planes([], [], window_s=1.0)
    assert empty["busy_s"] == 0 and empty["device_ops"] == []
    obs = readers.Observations(trace=True)
    obs.trace_summary = empty
    assert readers.trace(obs, "idle_pct") is None     # nothing, not a 0


def test_no_share_over_100_is_printed():
    obs = readers.Observations(trace=True)
    obs.device_kind = "TPU v5 lite"
    obs.slice_work = 1
    obs.trace_summary = {"busy_s": 1e-3, "window_s": 1.0, "idle_pct": 99.9}
    obs.scan_reads = [("query9", "store_sales", 819_000)]     # 1 us at peak
    assert readers.trace(obs, "scan_roofline_pct", per="pass") == \
        pytest.approx(0.1)
    obs.scan_reads = [("query9", "store_sales", 819_000_000_000)]
    with pytest.raises(ValueError):
        readers.trace(obs, "scan_roofline_pct", per="pass")
    obs.trace_summary["idle_pct"] = 101.0
    with pytest.raises(ValueError):
        readers.trace(obs, "idle_pct")
    obs.device_kind = "TPU v9 imaginary"
    obs.trace_summary["idle_pct"] = 50.0
    obs.scan_reads = [("query9", "store_sales", 819_000)]
    with pytest.raises(KeyError):
        readers.trace(obs, "scan_roofline_pct", per="pass")
