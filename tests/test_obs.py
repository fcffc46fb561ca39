"""Observability layer (nds_tpu/obs): span tracer, metrics registry,
device-time attribution, typed ExecStats, logging channel.

Acceptance-backed properties:
- disabled tracer hooks are near-free (the <2% bench-slice overhead bound
  rests on the disabled path doing no allocation/locking);
- a traced query produces a WELL-FORMED span tree (every span closed,
  every parent id resolvable) that exports to valid Chrome trace-event
  JSON (Perfetto-loadable);
- metrics counters move correctly under the fault-injection smoke run;
- ExecStats is built in one place with a dict view identical to the
  legacy untyped ``last_exec_stats`` keys, and records EVERY prefetch
  error.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.obs import device_time as dt
from nds_tpu.obs import log as obs_log
from nds_tpu.obs import metrics as om
from nds_tpu.obs.stats import ExecStats
from nds_tpu.obs.trace import (NULL_SPAN, TRACER, span_tree,
                               validate_chrome_trace)
from nds_tpu.resilience import FAULTS, FaultError, FaultSpec, RetryPolicy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_off():
    """Every test starts from a disabled, empty tracer."""
    TRACER.configure(enabled=False)
    yield
    TRACER.configure(enabled=False)


def make_session(**cfg_kwargs) -> Session:
    s = Session(EngineConfig(**cfg_kwargs))
    rng = np.random.default_rng(11)
    t = pa.table({
        "k": pa.array(rng.integers(0, 7, 5000), type=pa.int32()),
        "v": pa.array(rng.integers(0, 1000, 5000), type=pa.int64()),
    })
    s.register_arrow("t", t)
    return s


QUERY = "SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t GROUP BY k ORDER BY k"


# -- tracer: disabled path ----------------------------------------------------

def test_disabled_span_is_shared_noop():
    assert not TRACER.enabled
    sp = TRACER.span("anything", rows=1)
    assert sp is NULL_SPAN
    with sp as inner:
        inner.set(bytes=2)
    assert TRACER.events() == []


def test_disabled_span_overhead_is_negligible():
    """The <2% bench bound rests on this: a disabled hook must cost
    ~an attribute read. 200k calls in well under a second leaves orders
    of magnitude of headroom against ms-scale engine operations."""
    t0 = time.perf_counter()
    for _ in range(200_000):
        with TRACER.span("x", table="t", rows=5):
            pass
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, f"disabled spans too slow: {elapsed:.2f}s/200k"


def test_disabled_run_records_nothing():
    s = make_session()
    s.sql(QUERY, backend="jax")
    assert TRACER.events() == []
    assert TRACER.open_spans() == []


# -- tracer: enabled lifecycle ------------------------------------------------

def test_span_tree_well_formed_for_real_query():
    TRACER.configure(enabled=True)
    s = make_session(verify_plans="per-pass")
    for _ in range(3):   # record -> compile+run -> compiled
        s.sql(QUERY, backend="jax", label="obs_q")
    assert TRACER.open_spans() == [], "unclosed spans"
    events = TRACER.events()
    names = {e["name"] for e in events}
    # the lifecycle phases the tentpole promises all appear
    for expected in ("query", "parse", "plan", "plan.pass", "plan.verify",
                     "record", "exec", "upload"):
        assert expected in names, f"missing {expected!r} span in {names}"
    tree = span_tree(events)      # raises on a dangling parent id
    roots = tree.get(0, [])
    assert len(roots) >= 3        # one "query" root per sql() call
    for e in events:
        if e.get("ph") == "X":
            assert e["dur"] >= 0
            assert e["ts"] >= 0
    # parse/plan nest under a query root
    by_sid = {e["sid"]: e for e in events if e.get("ph") == "X"}
    parse = next(e for e in events if e["name"] == "parse")
    chain = []
    cur = parse
    while cur.get("parent"):
        cur = by_sid[cur["parent"]]
        chain.append(cur["name"])
    assert "query" in chain


def test_span_attrs_and_error_marking():
    TRACER.configure(enabled=True)
    with pytest.raises(RuntimeError):
        with TRACER.span("boom", table="t") as sp:
            sp.set(rows=4)
            raise RuntimeError("x")
    (event,) = TRACER.events()
    assert event["args"]["table"] == "t"
    assert event["args"]["rows"] == 4
    assert event["args"]["error"] == "RuntimeError"
    assert TRACER.open_spans() == []


def test_spans_from_worker_threads_are_recorded():
    import threading
    TRACER.configure(enabled=True)
    barrier = threading.Barrier(4)   # all spans open concurrently, so the
    #                                  OS cannot recycle thread identities

    def work():
        barrier.wait()
        with TRACER.span("worker.span"):
            barrier.wait()

    ts = [threading.Thread(target=work) for _ in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    events = [e for e in TRACER.events() if e["name"] == "worker.span"]
    assert len(events) == 4
    assert len({e["tid"] for e in events}) == 4
    span_tree(TRACER.events())


# -- tracer: exporters --------------------------------------------------------

def test_chrome_trace_export_is_valid(tmp_path):
    TRACER.configure(enabled=True)
    s = make_session()
    s.sql(QUERY, backend="jax", label="chrome_q")
    path = TRACER.write_chrome_trace(str(tmp_path / "trace.json"))
    n = validate_chrome_trace(path)
    assert n >= 4
    with open(path) as f:
        doc = json.load(f)
    assert isinstance(doc["traceEvents"], list)
    assert doc["displayTimeUnit"] == "ms"
    # every complete event Perfetto needs: name/ph/ts/dur/pid/tid
    for e in doc["traceEvents"]:
        if e["ph"] == "X":
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["dur"], (int, float))


def test_jsonl_export_and_aggregate(tmp_path):
    TRACER.configure(enabled=True)
    with TRACER.span("a"):
        with TRACER.span("b"):
            pass
    with TRACER.span("a"):
        pass
    path = TRACER.write_jsonl(str(tmp_path / "events.jsonl"))
    lines = [json.loads(ln) for ln in open(path)]
    assert len(lines) == 3
    agg = TRACER.aggregate()
    assert agg["a"]["count"] == 2
    assert agg["b"]["count"] == 1
    assert agg["a"]["total_ms"] >= agg["a"]["max_ms"]


def test_trace_report_cli_on_trace_and_bench_json(tmp_path):
    TRACER.configure(enabled=True)
    with TRACER.span("cli.span", table="t"):
        pass
    trace = TRACER.write_chrome_trace(str(tmp_path / "t.json"))
    script = os.path.join(REPO, "scripts", "trace_report.py")
    out = subprocess.run([sys.executable, script, trace],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "cli.span" in out.stdout
    bench = {"metric": "m", "value": 1.0, "unit": "ms", "vs_baseline": 1.0,
             "device_time_programs": [
                 {"program": "q1/root", "runs": 3, "device_ms": 30.0,
                  "mean_ms": 10.0, "max_ms": 12.0, "roofline_frac": 0.01}],
             "attribution_frac": {"q1": 0.97},
             "metrics": {"queries_run": 3}}
    bpath = tmp_path / "bench.json"
    bpath.write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, script, str(bpath)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "q1/root" in out.stdout
    assert "queries_run" in out.stdout


# -- metrics registry ---------------------------------------------------------

def test_counter_and_gauge_basics():
    reg = om.MetricsRegistry()
    c = reg.counter("c", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("g")
    g.set(7)
    g.add(-2)
    assert g.value == 5
    assert reg.snapshot() == {"c": 5, "g": 5}
    assert reg.describe()["c"] == "help text"
    with pytest.raises(TypeError):
        reg.gauge("c")
    assert reg.delta({"c": 2}) == {"c": 3, "g": 5}


def test_counters_are_thread_safe():
    import threading
    reg = om.MetricsRegistry()
    c = reg.counter("n")

    def work():
        for _ in range(10_000):
            c.inc()

    ts = [threading.Thread(target=work) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert c.value == 80_000


def test_query_metrics_move_through_session():
    before = om.METRICS.snapshot()
    s = make_session()
    for _ in range(3):
        s.sql(QUERY, backend="jax")
    d = om.METRICS.delta(before)
    assert d.get("queries_run") == 3
    assert d.get("program_cache_misses", 0) >= 1   # first sighting records
    assert d.get("program_cache_hits", 0) >= 2     # replays hit the cache
    assert d.get("compiles", 0) >= 1


def test_fault_injection_smoke_moves_counters():
    """The resilience smoke path: an armed fault fires (counted), the
    retry policy retries over it (counted), and the run completes."""
    before = om.METRICS.snapshot()
    spec = FAULTS.arm(FaultSpec(point="query.run", match="obs_smoke",
                                times=2))
    try:
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            FAULTS.fire("query.run", "obs_smoke")
            return "ok"

        policy = RetryPolicy(max_attempts=5, backoff_s=0.0)
        assert policy.call(flaky, sleep=lambda _s: None) == "ok"
    finally:
        FAULTS.disarm(spec)
    d = om.METRICS.delta(before)
    assert d.get("fault_point_firings") == 2
    assert d.get("retries") == 2
    assert calls["n"] == 3


def test_exhausted_retries_still_counted():
    before = om.METRICS.snapshot()

    def always_fails():
        raise FaultError("nope")

    policy = RetryPolicy(max_attempts=3, backoff_s=0.0)
    with pytest.raises(FaultError):
        policy.call(always_fails, sleep=lambda _s: None)
    # 3 attempts = 2 retries (the first try is not a retry)
    assert om.METRICS.delta(before).get("retries") == 2


# -- device-time attribution --------------------------------------------------

def test_program_registry_table_and_roofline():
    reg = dt.ProgramRegistry()
    reg.record_run("q9/root", 10.0)
    reg.record_run("q9/root", 30.0)
    reg.record_run("q1/root", 5.0)
    reg.record_cost("q9/root", {"flops": 1e6, "bytes accessed": 4e6})
    reg.record_cost("q1/root", {"flops": 2e3, "bytes accessed": 1e3})
    # no bandwidth given (a CPU run): no roofline under a device metric's name
    assert all("roofline_frac" not in r for r in reg.table())
    with pytest.raises(dt.UnknownDeviceError, match="cpu"):
        dt.peak_hbm_gbps("cpu")
    assert dt.peak_hbm_gbps("TPU v5 lite") == 819.0
    rows = reg.table(bw_gbps=100.0)
    assert [r["program"] for r in rows] == ["q9/root", "q1/root"]
    top = rows[0]
    assert top["runs"] == 2
    assert top["device_ms"] == 40.0
    assert top["mean_ms"] == 20.0
    assert top["max_ms"] == 30.0
    # roofline = (bytes / bw) / mean_run_s = (4e6/1e11) / 0.020 = 0.002
    assert abs(top["roofline_frac"] - 0.002) < 1e-6
    assert dt.coverage(rows, 50.0) == pytest.approx(0.9)
    text = dt.format_table(rows)
    assert "q9/root" in text and "roofline" in text


def test_compiled_runs_attribute_device_time():
    before = dt.PROGRAMS.snapshot()
    s = make_session()
    for _ in range(3):
        s.sql(QUERY, backend="jax", label="attr_q")
    after = dt.PROGRAMS.snapshot()
    new = {k: v for k, v in after.items() if k not in before}
    assert any(k.startswith("attr_q") for k in new), new
    st = next(v for k, v in new.items() if k.startswith("attr_q"))
    assert st.runs >= 2          # compile+run + compiled replay
    assert st.device_ms > 0


# -- ExecStats ----------------------------------------------------------------

def test_exec_stats_executor_dict_view_matches_legacy():
    st = ExecStats.from_executor(
        {"mode": "compiled", "device_ms": 1.5, "custom_key": 7},
        fallbacks=["ScanNode: no"])
    d = st.to_dict()
    assert d["mode"] == "compiled"
    assert d["device_ms"] == 1.5
    assert d["custom_key"] == 7            # unknown keys pass through
    assert d["fallback_reasons"] == ["ScanNode: no"]
    assert "jobs" not in d                 # unset streaming fields dropped
    assert "segments" not in d


def test_exec_stats_streaming_records_all_prefetch_errors():
    st = ExecStats.streaming(
        jobs=1, morsels=4, morsel_rows=1024, re_records=0, shared_scan=True,
        scan_passes=1, tables_streamed=1, branches_served=2, fused_groups=1,
        bytes_uploaded=100, morsels_per_table={"fact": 4}, narrow_lanes=True,
        lane_spec={"fact": {"fk": "u16"}},
        prefetch_error_details=["OSError: a", "OSError: b", "OSError: c"])
    d = st.to_dict()
    assert d["mode"] == "streaming"
    assert d["prefetch_errors"] == 3               # legacy count key
    assert d["prefetch_error"] == "OSError: a"     # legacy first-error key
    assert d["prefetch_error_details"] == ["OSError: a", "OSError: b",
                                           "OSError: c"]
    assert d["lane_spec"] == {"fact": {"fk": "u16"}}


def test_session_installs_typed_stats_both_paths(tmp_path):
    import pyarrow.parquet as pq
    # streaming path
    rng = np.random.default_rng(3)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, 50, 30_000), type=pa.int32()),
        "v": pa.array(rng.integers(0, 100, 30_000), type=pa.int64())})
    path = os.path.join(str(tmp_path), "fact.parquet")
    pq.write_table(fact, path, row_group_size=4096)
    s = Session(EngineConfig(chunk_rows=4096, out_of_core_min_rows=10_000))
    s.register_parquet("fact", path)
    s.sql("SELECT fk, SUM(v) FROM fact GROUP BY fk", backend="jax")
    assert s.last_exec_stats_typed is not None
    assert s.last_exec_stats_typed.mode == "streaming"
    assert s.last_exec_stats == s.last_exec_stats_typed.to_dict()
    assert s.last_exec_stats["morsels"] == s.last_exec_stats_typed.morsels
    # in-core path on the same session
    s2 = make_session()
    s2.sql(QUERY, backend="jax")
    assert s2.last_exec_stats_typed.mode in ("record", "compile+run",
                                             "compiled", "adopted")
    assert s2.last_exec_stats == s2.last_exec_stats_typed.to_dict()


# -- logging ------------------------------------------------------------------

def test_log_verbosity_gates_info(capsys):
    import logging
    logger = obs_log.configure(verbosity=0, force=True)
    assert logger.level == logging.WARNING
    logger = obs_log.configure(verbosity=2, force=True)
    assert logger.level == logging.DEBUG
    child = obs_log.get_logger("bench")
    assert child.name == "nds_tpu.bench"
    # restore the env-driven default for other tests
    obs_log.configure(force=True)


# -- report schema ------------------------------------------------------------

def test_bench_report_schema_version_and_host_capture():
    from nds_tpu.report import SCHEMA_VERSION, BenchReport
    os.environ["NDS_TPU_TEST_SECRET"] = "hunter2"
    try:
        rep = BenchReport(EngineConfig(), app_name="obs-test")
    finally:
        del os.environ["NDS_TPU_TEST_SECRET"]
    assert rep.summary["schemaVersion"] == SCHEMA_VERSION
    host = rep.summary["env"]["host"]
    import socket
    assert host["host_id"] != socket.gethostname()   # never the raw name
    assert len(host["host_id"]) == 10
    assert host["python"]
    assert rep.summary["env"]["envVars"]["NDS_TPU_TEST_SECRET"] == \
        "*********(redacted)"
    rep.record_metrics({"queries_run": 2})
    assert rep.summary["metrics"] == {"queries_run": 2}
