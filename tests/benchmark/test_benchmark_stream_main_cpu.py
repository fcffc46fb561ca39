"""The streamed cells' main-thread metrics (ISSUE 39): eight data files over
the ``span_sum`` and ``counter`` readers, appended to ``per_layer`` for both
streamed cells, and ``batched_reqs_per_req`` for the served cell; read on the
CPU from a traced run of each streamed cell over the scratch copies of their
configurations that ``test_benchmark_tight_morsels_cpu.py`` makes (smaller
morsels, ``--scale 0.1``), and of the served cell as the driver runs it."""
import json

import pytest
from bench_helpers import manifest, run_cell, span_metric_problems
from test_benchmark_tight_morsels_cpu import (CELLS, MORSELS,  # noqa: F401
                                              small_manifest)

from benchmark import drivers, readers, run

M = manifest()
STREAMED = list(CELLS)
MAIN_THREAD = ["plan", "morsel.decode", "morsel.stage_sync", "morsel.exec",
               "morsel.stage_wait", "morsel.partials", "merge.partials",
               "finalize"]
#: metric -> (the spans its file names, its layer)
SPAN_FILES = {
    "decode_ms_per_pass": (["morsel.decode"], "staging"),
    "decode_read_ms_per_pass": (["morsel.read"], "staging"),
    "decode_convert_ms_per_pass": (["morsel.from_arrow"], "staging"),
    "stage_sync_ms_per_pass": (["morsel.stage_sync"], "staging"),
    "stage_wait_ms_per_pass": (["morsel.stage_wait"], "staging"),
    "partials_ms_per_pass": (["morsel.partials"], "merge and materialize"),
    "stream_main_ms_per_pass": (MAIN_THREAD, "staging"),
}
MB, BATCHED = "decode_mb_per_pass", "batched_reqs_per_req"
EIGHT = list(SPAN_FILES)[:3] + [MB] + list(SPAN_FILES)[3:]
LISTED = {m["name"]: m for m in M["per_layer"]}


def test_the_nine_are_appended_after_what_was_there_in_the_issues_order():
    names = [m["name"] for m in M["per_layer"]]
    assert names[-9:] == EIGHT + [BATCHED]
    assert names[-10] == "sorted_joins_per_pass"
    assert span_metric_problems(M) == []
    # nothing that stood before lists fewer cells, and none lost its file
    for m in M["per_layer"][:-9]:
        assert readers.load_metric(m["name"])["moves"] == m["moves"]


@pytest.mark.parametrize("name", list(SPAN_FILES))
def test_a_span_metrics_manifest_row_equals_its_data_file(name):
    spans, layer = SPAN_FILES[name]
    assert LISTED[name] == {
        "name": name, "unit": "ms", "better": "lower",
        "source": "program_span", "layer": layer, "moves": "pass_s",
        "workloads": STREAMED}
    assert readers.load_metric(name) == {
        "layer": layer, "unit": "ms", "moves": "pass_s",
        "reader": "span_sum",
        "args": {"spans": spans, "phase": "window", "scale": "ms",
                 "per": "pass"}}


def test_the_two_counter_metrics_rows_equal_their_data_files():
    assert LISTED[MB] == {
        "name": MB, "unit": "MB", "better": "lower",
        "source": "program_counter", "layer": "staging", "moves": "pass_s",
        "workloads": STREAMED}
    assert readers.load_metric(MB) == {
        "layer": "staging", "unit": "MB", "moves": "pass_s",
        "reader": "counter",
        "args": {"name": "bytes_decoded", "per": "pass", "divide": 1e6,
                 "absent_is_zero": True}}
    assert LISTED[BATCHED] == {
        "name": BATCHED, "unit": "ratio", "better": "higher",
        "source": "program_counter", "layer": "front door and service",
        "moves": "served_qps", "workloads": ["served_dash_sf1"]}
    assert readers.load_metric(BATCHED) == {
        "layer": "front door and service", "unit": "ratio",
        "moves": "served_qps", "reader": "counter",
        "args": {"name": "service_batched_queries", "per": "request",
                 "absent_is_zero": True}}


def test_the_main_threads_union_leaves_the_staging_threads_span_out():
    """``morsel.stage`` runs beside the dispatch on another thread: in the
    union it would cover a hole of the main thread. The synchronous stage
    is in through ``morsel.stage_sync``, which wraps it."""
    spans = readers.load_metric("stream_main_ms_per_pass")["args"]["spans"]
    assert "morsel.stage" not in spans and "morsel.stage_sharded" not in spans
    assert "exec.wait" not in spans and "morsel.exec" in spans
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 2
    obs.window_ts = (0.0, 1e6)

    def ev(name, ts_ms, dur_ms):
        return {"ph": "X", "name": name, "ts": ts_ms * 1e3,
                "dur": dur_ms * 1e3}
    obs.spans = [ev("morsel.decode", 0, 100), ev("morsel.read", 10, 40),
                 ev("morsel.stage_sync", 100, 50), ev("morsel.stage", 101, 48),
                 ev("morsel.exec", 160, 40),
                 # the staging thread, over a 30 ms hole of the main thread
                 ev("morsel.stage", 150, 200),
                 ev("morsel.partials", 230, 10)]
    got = readers.read_all(["stream_main_ms_per_pass", "decode_ms_per_pass",
                            "decode_read_ms_per_pass"], obs)
    assert got == {"stream_main_ms_per_pass": (100 + 50 + 40 + 10) / 2,
                   "decode_ms_per_pass": 50.0,
                   "decode_read_ms_per_pass": 20.0}


def test_a_program_without_the_spans_leaves_them_out_and_reads_zero_bytes():
    """The parent commit has neither the six spans nor ``bytes_decoded``:
    its traced line lacks the span metrics, and the counters read 0."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 4
    obs.window_ts = (0.0, 1e6)
    obs.spans = [{"ph": "X", "name": "query", "ts": 5.0, "dur": 100.0}]
    obs.counters = {"morsels": 24}
    assert readers.read_all(EIGHT + [BATCHED], obs) == {MB: 0.0, BATCHED: 0.0}
    obs.counters = {"bytes_decoded": 8_000_000,
                    "service_batched_queries": 3}
    assert readers.read_all([MB, BATCHED], obs) == {MB: 2.0, BATCHED: 0.75}


@pytest.fixture
def pass_seconds(monkeypatch):
    """``pass_s`` of the window, which a traced line does not print. The
    profiler's slice is held open to the window's end: in a window this
    short the harness would stop it after the last pass and take the pause
    off the passes' time (``paused`` is subtracted whenever it was spent)."""
    end_to_end, seen = drivers.PassLoop.end_to_end, []
    monkeypatch.setattr(drivers.TraceSlice, "due", lambda self: False)

    def spy(self, w, wrong):
        values = end_to_end(self, w, wrong)
        seen.append(values["pass_s"])
        return values
    monkeypatch.setattr(drivers.PassLoop, "end_to_end", spy)
    return seen


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_both_traced_streamed_cells_print_the_eight_and_they_nest(
        cell, small_manifest, capsys, pass_seconds):
    rc = run.main(["--manifest", small_manifest, "--workload", cell,
                   "--seed", str(2 ** 31 + 391), "--seconds", "1",
                   "--trace", "1", "--platform", "cpu", "--scale", "0.1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(EIGHT) <= set(got) and BATCHED not in got
    assert all(line["metrics"][k]["unit"] == LISTED[k]["unit"]
               for k in EIGHT)
    read, convert = got["decode_read_ms_per_pass"], \
        got["decode_convert_ms_per_pass"]
    decode, main = got["decode_ms_per_pass"], got["stream_main_ms_per_pass"]
    pass_ms, = pass_seconds
    pass_ms *= 1e3
    assert 0 < read and 0 < convert
    assert read + convert <= decode <= main <= pass_ms
    # the two halves are the decode but for the generator's own steps
    assert read + convert >= 0.9 * decode
    # one synchronous stage a statement, the others behind the dispatch
    assert 0 < got["stage_sync_ms_per_pass"] < main
    assert 0 <= got["stage_wait_ms_per_pass"] < main
    assert 0 < got["partials_ms_per_pass"] < main
    # SF0.1's store_sales: the same Arrow bytes a pass on one chip and four
    assert got[MB] == pytest.approx(19.728824, rel=1e-9)
    # what stood before still prints, and nothing compiled in the window
    staged = "stage_sharded_ms_per_pass" if cell.endswith("_x4") \
        else "stage_ms_per_pass"
    assert got[staged] > got["stage_sync_ms_per_pass"]
    assert got["merge_ms_per_pass"] > 0
    assert got["tight_morsels_per_pass"] == 2 * MORSELS
    assert got["morsel_re_records_per_pass"] == 0
    assert got["window_compiles.pass"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0


def test_the_served_cell_prints_how_many_requests_rode_a_batch():
    rc, line, err = run_cell("--workload", "served_dash_sf1", "--seed",
                             str(2 ** 31 + 392), "--seconds", "4",
                             "--trace", "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert got[BATCHED]["unit"] == "ratio"
    # a share of the window's requests: none rode a batch, or up to all
    assert 0.0 <= got[BATCHED]["value"] <= 1.0 + 4 / line["attempted"]
    assert not set(EIGHT) & set(got)
