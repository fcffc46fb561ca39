"""The committed served cell, as the driver runs it, on the CPU at SF0.01:
the front door in the harness process, four client processes, the volley
warm-up, the traced run's per-layer metrics."""
from bench_helpers import RESULT_KEYS, manifest, run_cell


def test_served_cell_traced_run():
    rc, line, err = run_cell("--workload", "served_dash_sf1", "--seed", "77",
                             "--seconds", "5", "--trace", "1")
    assert rc == 0, err[-2000:]
    assert list(line) == RESULT_KEYS + ["breakdown", "compared"]
    assert line["correct"] is True and line["attempted"] >= 4
    mine = {m["name"] for m in manifest()["per_layer"]
            if "served_dash_sf1" in m["workloads"]}
    assert set(line["metrics"]) <= mine
    # what needs no device trace is read on the CPU too; nothing compiled
    # inside the window
    assert {"served_ms_p95", "queue_ms_p50", "plan_ms_p50", "load_s",
            "record_s", "window_compiles.served"} <= set(line["metrics"])
    assert line["metrics"]["window_compiles.served"]["value"] == 0
    # no device ran an operation here, so no share is printed (never a 0)
    assert "device_idle_pct.served" not in line["metrics"]
    assert line["device"]["busy_s"] == 0 and line["device"]["window_s"] > 0
    assert set(line["compared"]) == {"wrong_cells", "float_rel_err"}
