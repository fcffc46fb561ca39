"""The per-layer metrics that read the program's newer spans and counters
(``exec.args`` / ``exec.wait`` / ``exec.fetch``, ``xla.trace`` / ``xla.lower``
/ ``xla.compile``, ``xla_compiles``, ``service/lane_idle``,
``frontdoor/reply``): the traced power and served cells, as the driver runs
them, on the CPU at SF0.01, print each of them."""
import json
import os

import pytest
from bench_helpers import BENCH, manifest, run_cell

NEW = ["plan_s", "dispatch_host_ms_per_pass", "device_wait_ms_per_pass",
       "table_upload_s", "xla_trace_lower_s", "xla_compile_s",
       "window_xla_compiles.pass", "window_xla_compiles.served",
       "exec_ms_p50", "lane_idle_ms_per_req", "materialize_ms_per_req",
       "reply_ms_per_req"]


def new_metrics_of(cell: str) -> set:
    return {m["name"] for m in manifest()["per_layer"]
            if m["name"] in NEW and cell in m["workloads"]}


def test_the_new_metrics_are_the_last_entries_and_span_or_counter_read():
    tail = manifest()["per_layer"][-len(NEW):]
    assert [m["name"] for m in tail] == NEW
    for m in tail:
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        counter = spec["reader"] == "counter"
        assert m["source"] == ("program_counter" if counter
                               else "program_span")
        assert spec["reader"] in ("span_sum", "counter", "ticket_p50")


@pytest.mark.parametrize("cell,seconds,sometimes", [
    ("power_resident_sf1", "3", set()),
    # a request is materialized on its client's thread only when it rode
    # a batched dispatch, which a short window on the CPU need not hold
    ("served_dash_sf1", "5", {"materialize_ms_per_req"}),
])
def test_traced_cell_prints_every_new_metric(cell, seconds, sometimes):
    rc, line, err = run_cell("--workload", cell, "--seed", "2147483659",
                             "--seconds", seconds, "--trace", "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is True and line["failed"] == 0
    want = new_metrics_of(cell)
    assert want and want - sometimes <= set(line["metrics"]), \
        sorted(want - set(line["metrics"]))
    for name in want & set(line["metrics"]):
        assert line["metrics"][name]["value"] >= 0
    # what waits for the device contains what the host does around it
    if cell == "power_resident_sf1":
        m = line["metrics"]
        assert m["device_wait_ms_per_pass"]["value"] > \
            m["dispatch_host_ms_per_pass"]["value"] > 0
        assert m["xla_compile_s"]["value"] > 0
        assert m["xla_trace_lower_s"]["value"] > 0
