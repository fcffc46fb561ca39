"""A later PR adds a configuration, a traffic mix and a per-layer metric as
new files plus manifest entries, editing nothing that is there: this test
does exactly that (scratch files, removed again) and runs the new cell on
the CPU at SF0.01 — then runs it once more with the timed path broken
underneath, and sees ``correct`` come out false."""
import json
import os

import pytest
from bench_helpers import BENCH, RESULT_KEYS, manifest

from benchmark import run

SCRATCH = {
    "configs/zz_scratch_cfg.json": {
        "name": "zz_scratch_cfg", "scale": "0.01", "units": 1,
        "engine": {"chunk_rows": 65536, "out_of_core_min_rows": 48000000,
                   "decimal_physical": "i64"},
        "want_modes": ["compiled"],
        "precision": {"decimal": "exact_i64"},
        "control": {"kind": "engine",
                    "engine": {"decimal_physical": "f64"}},
        "limits": {"wrong_cells": 0, "decimal_err": 0,
                   "float_rel_err": 1e-13}},
    "traffic/zz_scratch_mix.json": {
        "driver": "pass_loop", "units": ["query9"], "param_seed": 5,
        "trace_slice_s": 0.5},
    "layer_metrics/zz_scratch_query_ms.json": {
        "layer": "device programs", "unit": "ms", "moves": "pass_s",
        "reader": "span_sum",
        "args": {"spans": ["query"], "phase": "window", "scale": "ms",
                 "per": "pass"}},
}


@pytest.fixture(scope="module")
def scratch_manifest(tmp_path_factory):
    before = {d: set(os.listdir(os.path.join(BENCH, d)))
              for d in ("configs", "traffic", "layer_metrics")}
    for rel, doc in SCRATCH.items():
        with open(os.path.join(BENCH, rel), "w") as f:
            json.dump(doc, f)
    m = manifest()
    m["configs"].append({"name": "zz_scratch_cfg", "source": "scratch",
                         "file": "benchmark/configs/zz_scratch_cfg.json",
                         "reduced": [], "why": "scratch"})
    m["workloads"].append({"name": "zz_scratch_cell",
                           "config": "zz_scratch_cfg",
                           "traffic": "zz_scratch_mix", "chips": 1,
                           "why": "scratch"})
    for metric in m["end_to_end"]:
        if metric["name"] == "pass_s":
            metric["workloads"].append("zz_scratch_cell")
    m["per_layer"].append({"name": "zz_scratch_query_ms", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "device programs", "moves": "pass_s",
                           "workloads": ["zz_scratch_cell"]})
    path = tmp_path_factory.mktemp("bench") / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    try:
        yield str(path)
    finally:
        from nds_tpu.obs.trace import TRACER
        TRACER.configure(enabled=False)
        for rel in SCRATCH:
            os.remove(os.path.join(BENCH, rel))
        for d, names in before.items():
            assert set(os.listdir(os.path.join(BENCH, d))) == names


def _run(capsys, manifest_path, trace, seed=41):
    rc = run.main(["--manifest", manifest_path, "--workload",
                   "zz_scratch_cell", "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace), "--platform", "cpu",
                   "--scale", "0.01"])
    out = capsys.readouterr().out.strip().splitlines()
    return rc, json.loads(out[-1])


def test_new_files_make_a_cell_that_runs(scratch_manifest, capsys):
    rc, line = _run(capsys, scratch_manifest, trace=0)
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert list(line)[:5] == RESULT_KEYS and list(line)[-1] == "compared"
    assert sorted(line["metrics"]) == ["pass_s", "setup_s"]
    assert line["metrics"]["pass_s"]["value"] > 0
    assert line["device"]["platform"] == "cpu"
    assert set(line["compared"]) == {"wrong_cells", "decimal_err",
                                     "float_rel_err"}
    for number in line["compared"].values():
        assert number["value"] <= number["limit"]


def test_the_new_span_metric_is_read_in_the_traced_run(scratch_manifest,
                                                       capsys):
    rc, line = _run(capsys, scratch_manifest, trace=1)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["zz_scratch_query_ms"]["value"] > 0
    assert line["metrics"]["zz_scratch_query_ms"]["unit"] == "ms"
    assert "pass_s" not in line["metrics"]      # per-layer metrics only
    assert set(line["device"]) >= {"busy_s", "window_s"}


def test_a_broken_timed_path_comes_out_not_correct(scratch_manifest, capsys,
                                                   monkeypatch):
    """The answer altered where it is produced: every Session.sql result
    loses nothing but has one number doubled."""
    from nds_tpu.engine import Session
    real = Session.sql

    def broken(self, query, *a, **kw):
        table = real(self, query, *a, **kw)
        col = table.columns[0]
        col.data = col.data * 2
        return table
    monkeypatch.setattr(Session, "sql", broken)
    rc, line = _run(capsys, scratch_manifest, trace=0, seed=42)
    assert rc == 0 and line["correct"] is False
    assert line["failed"] == line["attempted"] > 0
    worst = line["compared"]["float_rel_err"]
    assert worst["value"] > worst["limit"]
