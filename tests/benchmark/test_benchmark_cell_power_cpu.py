"""The committed power cell, as the driver runs it (a new process), on the
CPU at SF0.01; and the refusals."""
import os
import shutil
import subprocess
import sys

from bench_helpers import BENCH, RESULT_KEYS, ROOT, manifest, run_cell


def test_power_cell_prints_the_contracts_line():
    rc, line, err = run_cell("--workload", "power_resident_sf1", "--seed",
                             str(2 ** 31 + 7), "--seconds", "2",
                             "--trace", "0")
    assert rc == 0, err[-2000:]
    assert list(line) == RESULT_KEYS + ["compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 5
    assert sorted(line["metrics"]) == ["pass_s", "setup_s"]
    for name, m in line["metrics"].items():
        assert m["value"] > 0 and m["unit"] == "s"
    assert sorted(line["device"]) == ["count", "kind", "memory_peak_bytes",
                                      "platform"]
    # the numbers compared, each beside its limit, close standard error too
    assert err.strip().splitlines()[-1].startswith("compared: wrong_cells=0")


def test_the_lower_precision_control_comes_out_not_correct():
    """--decimal f64, the engine's own lower-precision path, in the place of
    the exact-decimal configuration: the comparison has to fail it."""
    rc, line, err = run_cell("--workload", "power_resident_sf1", "--seed",
                             "43", "--seconds", "1", "--trace", "0",
                             "--control", "1")
    assert rc == 0, err[-2000:]
    assert line["correct"] is False
    assert any(n["value"] > n["limit"] for n in line["compared"].values())


def test_no_tpu_no_result():
    """Without --platform cpu the run refuses: another exit code than 0 and
    not a line on standard output."""
    rc, line, err = run_cell("--workload", "power_resident_sf1", "--seed",
                             "1", "--seconds", "1", "--trace", "0",
                             cpu=False)
    assert rc not in (0, None) and line is None
    assert "needs platform 'tpu'" in err


def test_unknown_workload_is_an_error():
    rc, line, _err = run_cell("--workload", "nope", "--seed", "1",
                              "--seconds", "1", "--trace", "0")
    assert rc != 0 and line is None


def test_benchmark_alone_without_the_program_refuses(tmp_path):
    """In a directory that holds only BENCHMARK.json and the paths."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    assert manifest()["paths"][0] == "benchmark"
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "power_resident_sf1", "--seed", "1", "--seconds", "1", "--trace",
         "0", "--platform", "cpu"], cwd=tmp_path, text=True,
        capture_output=True, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0 and proc.stdout.strip() == ""
