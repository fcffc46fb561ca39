"""``decode_view_cols_per_pass`` (ISSUE 40): one data file over the
``counter`` reader, appended to ``per_layer`` for both streamed cells, and
read on the CPU from a traced run of each cell over the scratch copies of
their configurations that ``test_benchmark_tight_morsels_cpu.py`` makes
(smaller morsels, ``--scale 0.1``).

One case of ``test_benchmark_stream_main_cpu.py`` pins what this PR changes
and no file here may be edited: that PR 39's nine metrics are the LAST of
``per_layer``. ``tests/conftest.py`` marks it as expected to fail, strictly;
it is restated here relative to the committed manifest."""
import json

import pytest
from bench_helpers import manifest, span_metric_problems
from test_benchmark_stream_main_cpu import BATCHED, EIGHT
from test_benchmark_tight_morsels_cpu import (CELLS, MORSELS,  # noqa: F401
                                              small_manifest)

from benchmark import drivers, readers, run

M = manifest()
METRIC = "decode_view_cols_per_pass"
#: a morsel of query3 holds ss_sold_date_sk, ss_item_sk, ss_ext_sales_price
#: and one of query9 ss_sold_date_sk, ss_quantity, ss_ext_discount_amt,
#: ss_net_paid: integers and exact-i64 decimal128(7,2), all by buffer view
MORSEL_COLUMNS = 3 + 4
#: the merged partials on their way back from Arrow, once a statement:
#: query3's g0, g2, sum__s (decimal128(22,2)), sum__n (its g1 is a string
#: at this scale; at SF1 late materialization groups by the item's key and
#: all five convert by view: 3 x 7 + 30 = 51 a pass on the chip);
#: query9's fifteen members, five count__cs and ten avg__s / avg__n pairs
MERGED_COLUMNS = 4 + (5 * 1 + 10 * 2)


def test_the_metric_is_data_appended_after_pr_39s_nine():
    names = [m["name"] for m in M["per_layer"]]
    at = names.index(EIGHT[0])
    assert names[at - 1] == "sorted_joins_per_pass"
    assert names[at:at + 10] == EIGHT + [BATCHED, METRIC]
    listed = {m["name"]: m for m in M["per_layer"]}
    assert listed[METRIC] == {
        "name": METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "staging", "moves": "pass_s",
        "workloads": list(CELLS)}
    assert readers.load_metric(METRIC) == {
        "layer": "staging", "unit": "count", "moves": "pass_s",
        "reader": "counter",
        "args": {"name": "arrow_view_columns", "per": "pass",
                 "absent_is_zero": True}}
    assert span_metric_problems(M) == []
    # what the stale case of test_benchmark_stream_main_cpu.py guarded:
    # nothing that stood before lost its file or changed what it moves
    for m in M["per_layer"][:at + 9]:
        assert readers.load_metric(m["name"])["moves"] == m["moves"]


def test_a_program_without_the_counter_reads_zero_and_does_not_raise():
    """The parent commit has no ``arrow_view_columns``: its traced line
    reads 0 there."""
    obs = readers.Observations(trace=True)
    obs.window = drivers.Window()
    obs.window.work = 4
    obs.counters = {"bytes_decoded": 8_000_000}
    assert readers.read_all([METRIC], obs) == {METRIC: 0.0}
    obs.counters["arrow_view_columns"] = 4 * 26
    assert readers.read_all([METRIC], obs) == {METRIC: 26.0}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_streamed_column_of_a_pass_converts_by_buffer_view(
        cell, small_manifest, capsys):
    rc = run.main(["--manifest", small_manifest, "--workload", cell,
                   "--seed", str(2 ** 31 + 401), "--seconds", "1",
                   "--trace", "1", "--platform", "cpu", "--scale", "0.1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    got = line["metrics"]
    assert got[METRIC]["unit"] == "count"
    assert got[METRIC]["value"] == MORSELS * MORSEL_COLUMNS + MERGED_COLUMNS
    assert got["decode_mb_per_pass"]["value"] == \
        pytest.approx(19.728824, rel=1e-9)
    assert got["tight_morsels_per_pass"]["value"] == 2 * MORSELS
    assert got["morsel_re_records_per_pass"]["value"] == 0
    assert got["window_compiles.pass"]["value"] == 0
    assert line["compared"]["wrong_cells"]["value"] == 0
    assert line["compared"]["decimal_err"]["value"] == 0
