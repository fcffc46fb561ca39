"""System tables + durable query log (ISSUE 15).

Acceptance-backed properties — all COUNT-shaped (no wall budgets: this
host is 1-core and timing tests flake):

- every ``system.*`` table's column names AND dtypes are FROZEN (schema
  pins) — operators script against them;
- the query-log ring and its JSONL sink hold the SAME rows (ring<->file
  equivalence), and the JSONL sink rotates size-capped with monotonic
  filenames and bounded file retention;
- snapshots are atomic cuts: readers racing 8 writer threads through the
  SQL path never observe a torn multi-counter row;
- the service serves ``system.*`` statements AROUND admission (works
  with the queue full / the service under pressure) with STRICT-ZERO
  device/planner counter movement;
- disabled mode adds zero counters (query_log_rows / query_log_rotations
  / system_queries all stay 0 on a plain workload);
- ``scripts/slo_report.py`` and ``scripts/metrics_server.py`` work as
  CLIs (the server on an OS-assigned ephemeral port).
"""
import json
import os
import subprocess
import sys
import threading
import urllib.error
import urllib.parse
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.engine.arrow_bridge import to_arrow
from nds_tpu.obs import system_tables as st
from nds_tpu.obs.metrics import METRICS
from nds_tpu.obs.query_log import COLUMNS, QUERY_LOG, read_jsonl
from nds_tpu.service import QueryService, ServiceConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _log_off():
    """Every test starts from a disabled, empty query log."""
    QUERY_LOG.configure(enabled=False, capacity=4096, path="", clear=True)
    yield
    QUERY_LOG.configure(enabled=False, capacity=4096, path="", clear=True)


def _rows(table) -> list[dict]:
    return to_arrow(table).to_pylist()


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    fact = pa.table({
        "k": pa.array(rng.integers(0, 5, 4000), type=pa.int64()),
        "v": pa.array(rng.integers(0, 100, 4000), type=pa.int64())})
    return fact


def make_session(data, **cfg) -> Session:
    s = Session(EngineConfig(**cfg))
    s.register_arrow("fact", data)
    return s


# -- schema pins --------------------------------------------------------------

def test_system_table_schemas_frozen():
    """The full column-name/dtype reference operators script against.
    Changing any of these is a deliberate, test-visible act."""
    expect = {
        "system.query_log": (
            ("ts", "seq", "source", "label", "tenant", "template",
             "trace_id", "status", "error", "wall_ms", "queue_ms",
             "plan_ms", "exec_ms", "materialize_ms", "rows",
             "bytes_uploaded", "mode", "cache_mode", "mesh_shards",
             "morsels", "mem_peak_bytes", "node_stats", "preempted"),
            ("float", "int", "str", "str", "str", "str", "int", "str",
             "str", "float", "float", "float", "float", "float", "int",
             "int", "str", "str", "int", "int", "int", "str", "int")),
        "system.metrics": (
            ("name", "kind", "value", "help"),
            ("str", "str", "float", "str")),
        "system.histograms": (
            ("name", "series", "tenant", "template", "le_ms", "count",
             "cum_count", "total_count", "sum_ms", "min_ms", "max_ms"),
            ("str", "str", "str", "str", "float", "int", "int", "int",
             "float", "float", "float")),
        "system.programs": (
            ("fingerprint", "hits", "compiles", "strikes", "volatile",
             "nojit", "decisions"),
            ("str", "int", "int", "int", "bool", "bool", "int")),
        "system.result_cache": (
            ("entry", "template", "backend", "rows", "hits", "stored_at",
             "tables", "ivm"),
            ("str", "str", "str", "int", "int", "float", "str", "bool")),
        "system.device_memory": (("metric", "bytes"), ("str", "int")),
        "system.flight": (
            ("seq", "t_ms", "event", "label", "tenant", "reason",
             "latency_ms", "detail"),
            ("int", "float", "str", "str", "str", "str", "float", "str")),
        "system.tables": (
            ("name", "generation", "est_rows", "columns", "unique_cols"),
            ("str", "int", "int", "int", "str")),
        "system.snapshots": (
            ("version", "timestamp_ms", "committer", "tables",
             "table_count", "current", "pinned"),
            ("int", "int", "str", "str", "int", "bool", "bool")),
    }
    assert set(st.SYSTEM_SCHEMAS) == set(expect)
    for name, (cols, dts) in expect.items():
        assert st.SYSTEM_SCHEMAS[name] == (cols, dts), name
    # the query_log table IS the log's frozen row schema
    assert st.SYSTEM_SCHEMAS["system.query_log"][0] == \
        tuple(c for c, _ in COLUMNS)


def test_every_system_table_snapshots_with_its_schema(data):
    s = make_session(data)
    s.sql("SELECT k, COUNT(*) AS n FROM fact GROUP BY k ORDER BY k",
          label="seed")
    for name, (cols, _dts) in st.SYSTEM_SCHEMAS.items():
        arrow = st.snapshot_arrow(name, s)
        assert tuple(arrow.column_names) == cols, name


# -- session path: log rows + SQL over them -----------------------------------

def test_session_statement_logs_one_row_with_context(data):
    QUERY_LOG.configure(enabled=True, clear=True)
    s = make_session(data, query_log=True)
    res = s.sql("SELECT k, COUNT(*) AS n, SUM(v) AS sv FROM fact "
                "GROUP BY k ORDER BY k", label="inv1")
    rows = QUERY_LOG.rows()
    assert len(rows) == 1
    r = rows[0]
    assert r["source"] == "session" and r["label"] == "inv1"
    assert r["status"] == "ok" and r["rows"] == res.num_rows
    assert r["wall_ms"] is not None and r["wall_ms"] > 0
    assert r["mode"]            # record/compiled/... never empty
    assert r["mem_peak_bytes"] is not None


def test_sql_over_system_query_log_group_by_tenant(data):
    QUERY_LOG.configure(enabled=True, clear=True)
    s = make_session(data, query_log=True)
    for i in range(3):
        s.sql(f"SELECT k, COUNT(*) AS n FROM fact WHERE v > {i} "
              "GROUP BY k ORDER BY k", label=f"q{i}")
    got = _rows(s.sql("SELECT status, COUNT(*) AS n "
                      "FROM system.query_log GROUP BY status"))
    assert got == [{"status": "ok", "n": 3}]
    # filters + projection over the log
    labels = _rows(s.sql("SELECT label FROM system.query_log "
                         "WHERE label = 'q1'"))
    assert labels == [{"label": "q1"}]


def test_system_statement_not_logged_and_does_not_clobber_stats(data):
    QUERY_LOG.configure(enabled=True, clear=True)
    s = make_session(data, query_log=True)
    s.sql("SELECT k FROM fact WHERE v < 3", label="base")
    stats_before = s.last_exec_stats
    n0 = len(QUERY_LOG.rows())
    s.sql("SELECT name, value FROM system.metrics")
    assert len(QUERY_LOG.rows()) == n0     # polls never log themselves
    assert s.last_exec_stats is stats_before   # nor clobber stats views


def test_mixed_system_and_user_tables_rejected(data):
    s = make_session(data)
    with pytest.raises(ValueError, match="cannot join user tables"):
        s.sql("SELECT * FROM system.metrics m, fact f")
    with pytest.raises(ValueError, match="system.* tables only"):
        s.system_query("SELECT k FROM fact")


def test_dotted_name_in_literal_takes_normal_path(data):
    """A statement merely CONTAINING 'system.' routes normally."""
    s = make_session(data)
    res = s.sql("SELECT k FROM fact WHERE v < 5", label="plain")
    assert res.num_rows >= 0
    # string literal mentioning the prefix: still the normal path
    before = METRICS.snapshot().get("system_queries", 0)
    s.sql("SELECT k, COUNT(*) AS n FROM fact GROUP BY k ORDER BY k",
          label="system.decoy")      # label only, not SQL: no routing
    assert METRICS.snapshot().get("system_queries", 0) == before


# -- ring <-> JSONL equivalence + rotation ------------------------------------

def test_ring_and_jsonl_hold_identical_rows(tmp_path, data):
    path = str(tmp_path / "ql.jsonl")
    QUERY_LOG.configure(enabled=True, path=path, flush_every=2,
                        clear=True)
    s = make_session(data)
    for i in range(5):
        s.sql(f"SELECT k, COUNT(*) AS n FROM fact WHERE v >= {i} "
              "GROUP BY k ORDER BY k", label=f"eq{i}")
    QUERY_LOG.flush()
    assert read_jsonl(path) == QUERY_LOG.rows()


def test_jsonl_rotation_caps_and_monotonic_names(tmp_path, data):
    path = str(tmp_path / "rot.jsonl")
    # tiny cap: every flush rolls the file
    QUERY_LOG.configure(enabled=True, path=path, max_bytes=600,
                        max_files=2, flush_every=1, clear=True)
    before = METRICS.snapshot().get("query_log_rotations", 0)
    for i in range(12):
        QUERY_LOG.record(None, source="session", label=f"r{i}",
                         wall_ms=1.0)
    QUERY_LOG.flush()
    rotations = METRICS.snapshot()["query_log_rotations"] - before
    assert rotations >= 3
    kept = sorted(p for p in os.listdir(tmp_path)
                  if p.startswith("rot.jsonl."))
    # retention: at most max_files rotated files survive, and the
    # surviving suffixes are the HIGHEST (monotonic — newest kept)
    assert len(kept) <= 2
    suffixes = sorted(int(p.rsplit(".", 1)[1]) for p in kept)
    assert suffixes == sorted(suffixes) and suffixes[-1] == rotations
    # every surviving row parses and carries the frozen schema
    for p in kept + ["rot.jsonl"]:
        for row in read_jsonl(str(tmp_path / p)):
            assert set(row) == {c for c, _ in COLUMNS}


def test_flight_dump_retention_and_monotonic_filenames(tmp_path):
    from nds_tpu.obs.flight import FlightRecorder
    fr = FlightRecorder()
    fr.configure(enabled=True, dump_dir=str(tmp_path),
                 trip_cooldown_s=0.0, max_dumps=3)
    for i in range(8):
        fr.record("admit", label=f"q{i}")
        fr.trip(f"reason{i}")
    files = sorted(os.listdir(tmp_path))
    assert len(files) == 3                       # oldest-first eviction
    seqs = [int(f.split("_")[1]) for f in files]
    assert seqs == sorted(seqs) == [6, 7, 8]     # monotonic, newest kept
    # bytes cap: newest dump always survives
    fr2 = FlightRecorder()
    d2 = tmp_path / "b"
    fr2.configure(enabled=True, dump_dir=str(d2), trip_cooldown_s=0.0,
                  max_dump_bytes=300)
    for i in range(5):
        for j in range(8):
            fr2.record("admit", label=f"x{i}_{j}", pad="y" * 30)
        fr2.trip(f"r{i}")
    survivors = sorted(os.listdir(d2))
    assert survivors                              # newest kept
    assert len(survivors) < 5                     # older ones evicted
    assert survivors[-1].startswith("flight_00005_")


# -- atomic cut under concurrent writers --------------------------------------

def test_readers_never_see_torn_counter_rows_under_8_writers(data):
    """8 writer threads bump a counter PAIR atomically (under
    METRICS.locked()); SQL readers over system.metrics must always see
    a == b — the registry-lock snapshot contract, exercised through the
    full system-table path."""
    s = make_session(data)
    a = METRICS.counter("tw_pair_a", "torn-read probe (tests)")
    b = METRICS.counter("tw_pair_b", "torn-read probe (tests)")
    a._reset(), b._reset()
    stop = threading.Event()
    torn: list[tuple] = []

    def writer():
        while not stop.is_set():
            with METRICS.locked():
                a.inc()
                b.inc()

    def reader():
        for _ in range(25):
            got = {r["name"]: r["value"] for r in _rows(s.system_query(
                "SELECT name, value FROM system.metrics "
                "WHERE name = 'tw_pair_a' OR name = 'tw_pair_b'"))}
            if got["tw_pair_a"] != got["tw_pair_b"]:
                torn.append((got["tw_pair_a"], got["tw_pair_b"]))

    writers = [threading.Thread(target=writer) for _ in range(8)]
    readers = [threading.Thread(target=reader) for _ in range(2)]
    for t in writers + readers:
        t.start()
    for t in readers:
        t.join()
    stop.set()
    for t in writers:
        t.join()
    assert not torn, f"torn counter rows observed: {torn[:5]}"


# -- service path: admission bypass + strict-zero pins ------------------------

def test_service_system_bypass_strict_zero_counters(data):
    QUERY_LOG.configure(enabled=True, clear=True)
    s = make_session(data, query_log=True)
    with QueryService(s) as svc:
        for i in range(3):
            svc.sql(f"SELECT k, COUNT(*) AS n FROM fact WHERE v > {i} "
                    "GROUP BY k ORDER BY k", label=f"w{i}",
                    tenant="dash")
        before = METRICS.snapshot()
        got = _rows(svc.sql("SELECT tenant, COUNT(*) AS n "
                            "FROM system.query_log GROUP BY tenant"))
        hist = _rows(svc.sql(
            "SELECT series, total_count FROM system.histograms "
            "WHERE name = 'service_latency_ms' AND tenant = 'dash'"))
        delta = METRICS.delta(before)
    assert got == [{"tenant": "dash", "n": 3}]
    assert hist and all(r["total_count"] >= 1 for r in hist)
    # STRICT-ZERO: polls moved NOTHING but the system_queries counter —
    # no admission, no planner samples, no device dispatch, no compiles
    assert delta.pop("system_queries") == 2
    gated = {k: v for k, v in delta.items() if not k.endswith("_ms")}
    assert gated == {}, f"system polls perturbed counters: {gated}"


def test_service_system_bypass_works_when_queue_is_full(data):
    """Observability during overload: with max_pending saturated and
    normal submits REJECTED, system polls still answer."""
    from nds_tpu.resilience import AdmissionRejected
    s = make_session(data)
    with QueryService(s, ServiceConfig(max_pending=1)) as svc:
        with svc.hold_dispatch():
            t1 = svc.submit("SELECT k, COUNT(*) AS n FROM fact "
                            "GROUP BY k ORDER BY k", label="held")
            with pytest.raises(AdmissionRejected):
                svc.submit("SELECT COUNT(*) AS n FROM fact",
                           label="shed")
            poll = svc.submit("SELECT name, value FROM system.metrics "
                              "WHERE name = 'service_rejected'",
                              label="poll")
            assert poll.done()           # completed synchronously
            rows = _rows(poll.result(timeout=5))
            assert rows[0]["value"] >= 1
        t1.result(timeout=120)


def test_service_ticket_rows_carry_tenant_phases_and_errors(data):
    QUERY_LOG.configure(enabled=True, clear=True)
    s = make_session(data, query_log=True)
    with QueryService(s) as svc:
        svc.sql("SELECT k, COUNT(*) AS n FROM fact GROUP BY k "
                "ORDER BY k", label="ok1", tenant="dash")
        with pytest.raises(Exception):
            svc.sql("SELECT nope FROM fact", label="bad1",
                    tenant="dash")
    rows = {r["label"]: r for r in QUERY_LOG.rows()}
    ok = rows["ok1"]
    assert ok["source"] == "service" and ok["tenant"] == "dash"
    assert ok["status"] == "ok" and ok["wall_ms"] > 0
    assert ok["queue_ms"] is not None and ok["plan_ms"] is not None
    assert ok["exec_ms"] is not None and ok["rows"] is not None
    bad = rows["bad1"]
    assert bad["status"] != "ok" and bad["error"]
    # exactly one row per ticket: no session-side duplicates
    assert len(QUERY_LOG.rows()) == 2


def test_system_programs_and_tables_rows(data):
    s = make_session(data)
    tpl = ("SELECT k, COUNT(*) AS n FROM fact WHERE v BETWEEN {a} AND "
           "{b} GROUP BY k ORDER BY k")
    for i in range(3):                  # record -> compile -> replay
        s.sql(tpl.format(a=1, b=50), label="progs")
    progs = _rows(s.sql("SELECT fingerprint, compiles, strikes "
                        "FROM system.programs"))
    assert progs and all(len(r["fingerprint"]) > 8 for r in progs)
    assert any(r["compiles"] >= 1 for r in progs)
    assert all(r["strikes"] == 0 for r in progs)
    tabs = _rows(s.sql("SELECT name, generation, columns "
                       "FROM system.tables"))
    assert tabs == [{"name": "fact", "generation": 1, "columns": 2}]


def test_system_result_cache_rows(data):
    from nds_tpu.engine.result_cache import ResultCacheConfig
    s = make_session(data)
    with QueryService(s, ServiceConfig(
            result_cache=ResultCacheConfig())) as svc:
        sql = ("SELECT k, COUNT(*) AS n FROM fact GROUP BY k ORDER BY k")
        svc.sql(sql, label="c1")
        svc.sql(sql, label="c2")         # exact hit
        rows = _rows(svc.sql("SELECT entry, hits, backend "
                             "FROM system.result_cache"))
    assert len(rows) == 1
    assert rows[0]["hits"] >= 1 and rows[0]["backend"] == "jax"


def test_system_flight_rows(data):
    from nds_tpu.obs.flight import FLIGHT
    FLIGHT.configure(enabled=True, clear=True)
    try:
        s = make_session(data)
        with QueryService(s) as svc:
            svc.sql("SELECT COUNT(*) AS n FROM fact", label="fl1")
            got = _rows(svc.sql(
                "SELECT event, COUNT(*) AS n FROM system.flight "
                "GROUP BY event"))
        events = {r["event"]: r["n"] for r in got}
        assert events.get("admit", 0) >= 1
        assert events.get("complete", 0) >= 1
    finally:
        FLIGHT.configure(enabled=False, clear=True)


# -- disabled mode: zero counters ---------------------------------------------

def test_disabled_mode_moves_no_new_counters(data):
    before = METRICS.snapshot()
    s = make_session(data)             # query_log NOT enabled
    for i in range(3):
        s.sql(f"SELECT k, COUNT(*) AS n FROM fact WHERE v > {i} "
              "GROUP BY k ORDER BY k", label=f"d{i}")
    with QueryService(s) as svc:
        svc.sql("SELECT COUNT(*) AS n FROM fact", label="d3")
    delta = METRICS.delta(before)
    for name in ("query_log_rows", "query_log_rotations",
                 "system_queries"):
        assert delta.get(name, 0) == 0, name
    assert QUERY_LOG.rows() == []


# -- CLIs ---------------------------------------------------------------------

def _make_log_jsonl(path, data):
    QUERY_LOG.configure(enabled=True, path=str(path), flush_every=1,
                        clear=True)
    s = make_session(data, query_log=True)
    with QueryService(s) as svc:
        for i, tenant in enumerate(["dash", "dash", "batch"]):
            svc.sql(f"SELECT k, COUNT(*) AS n FROM fact WHERE v > {i} "
                    "GROUP BY k ORDER BY k", label=f"c{i}",
                    tenant=tenant)
    QUERY_LOG.flush()


def test_slo_report_cli(tmp_path, data):
    log = tmp_path / "ql.jsonl"
    _make_log_jsonl(log, data)
    out_json = tmp_path / "slo.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "slo_report.py"),
         str(log), "--slo_ms", "60000", "--target", "0.9",
         "--windows", "300,3600", "--json", str(out_json)],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr
    rep = json.loads(out_json.read_text())
    by_tenant = {r["tenant"]: r for r in rep["rows"]}
    assert by_tenant["dash"]["count"] == 2
    assert by_tenant["batch"]["count"] == 1
    assert by_tenant["(all)"]["count"] == 3
    # generous SLO: everything attains, burn 0
    assert all(r["met"] for r in rep["rows"])
    assert by_tenant["(all)"]["burn"]["5m"] == 0.0


def test_metrics_server_cli_ephemeral_port(tmp_path, data):
    log = tmp_path / "ql.jsonl"
    _make_log_jsonl(log, data)
    proc = subprocess.Popen(
        [sys.executable,
         os.path.join(REPO, "scripts", "metrics_server.py"),
         "--port", "0", "--query_log", str(log)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline().strip()
        assert line.startswith("serving on http://"), line
        base = line.split("serving on ", 1)[1]
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            health = json.load(r)
        assert health["status"] == "ok"
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            prom = r.read().decode()
        assert "queries_run_total" in prom
        sql = urllib.parse.quote(
            "SELECT tenant, COUNT(*) AS n FROM system.query_log "
            "GROUP BY tenant")
        with urllib.request.urlopen(f"{base}/query?sql={sql}",
                                    timeout=30) as r:
            doc = json.load(r)
        assert doc["columns"] == ["tenant", "n"]
        assert sorted(doc["rows"]) == [["batch", 1], ["dash", 2]]
        # user tables refused over the wire
        bad = urllib.parse.quote("SELECT * FROM store_sales")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/query?sql={bad}", timeout=30)
        assert ei.value.code == 403
    finally:
        proc.terminate()
        proc.wait(timeout=30)


def test_service_metrics_port_scrape(data):
    """ServiceConfig.metrics_port=0: the service owns the endpoint's
    lifetime and the bound port reads back from the server object."""
    s = make_session(data)
    svc = QueryService(s, ServiceConfig(metrics_port=0))
    with svc:
        svc.sql("SELECT COUNT(*) AS n FROM fact", label="mp")
        port = svc.metrics_server.port
        assert port > 0
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz", timeout=30) as r:
            assert json.load(r)["status"] == "ok"
    assert svc.metrics_server is None      # stopped with the service


# -- obs_report --gate --------------------------------------------------------

def test_obs_report_compare_gate_and_allow(tmp_path):
    """--gate exits 1 on a >20% '!' regression; --allow waives it."""
    good = {"value": 100.0, "metrics": {"compiles": 10}}
    bad = {"value": 180.0, "metrics": {"compiles": 31}}
    a, b = tmp_path / "r1.json", tmp_path / "r2.json"
    a.write_text(json.dumps(good))
    b.write_text(json.dumps(bad))
    script = os.path.join(REPO, "scripts", "obs_report.py")

    def run(*extra):
        return subprocess.run(
            [sys.executable, script, "--compare", str(a), str(b),
             *extra], capture_output=True, text=True, timeout=120)

    flagged = run("--gate")
    assert flagged.returncode == 1
    assert "GATE FAIL" in flagged.stderr
    assert "wall_ms (slice total)@r2" in flagged.stderr
    waived = run("--gate", "--allow",
                 "wall_ms (slice total),compiles")
    assert waived.returncode == 0, waived.stderr
    assert "GATE OK" in waived.stderr
    clean = subprocess.run(
        [sys.executable, script, "--compare", str(a), str(a), "--gate"],
        capture_output=True, text=True, timeout=120)
    assert clean.returncode == 0
