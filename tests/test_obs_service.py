"""Service-grade observability (ISSUE 11): histogram metrics, trace
propagation across the query service's thread hops, and the flight
recorder.

Acceptance-backed properties:
- ``Histogram.quantile`` honors its DOCUMENTED error bound (within a
  factor sqrt(BUCKET_RATIO) of the exact sample quantile) on randomized
  samples; snapshots merge associatively and diff into window views;
- a batched service ticket's span tree is parent-linked from one
  ``service/ticket`` root through queue -> plan -> lane_wait -> dispatch
  -> materialize across three OS threads;
- ``MetricsRegistry.snapshot`` is one atomic cut (multi-metric updates
  under ``locked()`` can never tear);
- the flight-recorder ring drops oldest-first at capacity and auto-dumps
  on fault firings and rejection storms.
"""
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.obs import metrics as om
from nds_tpu.obs.flight import FLIGHT, FlightRecorder
from nds_tpu.obs.trace import TRACER, span_tree
from nds_tpu.resilience import FAULTS, FaultError, FaultSpec
from nds_tpu.service import QueryService, ServiceConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BOUND = om.BUCKET_RATIO ** 0.5


@pytest.fixture(autouse=True)
def _obs_off():
    """Every test starts from a disabled tracer and flight recorder."""
    TRACER.configure(enabled=False)
    FLIGHT.configure(enabled=False, clear=True)
    yield
    TRACER.configure(enabled=False)
    FLIGHT.configure(enabled=False, clear=True)


# -- histogram: quantile error bound ------------------------------------------

@pytest.mark.parametrize("dist", ["lognormal", "uniform", "bimodal"])
def test_histogram_quantile_within_documented_bound(dist):
    rng = np.random.default_rng(hash(dist) % 2**32)
    if dist == "lognormal":
        vals = np.exp(rng.normal(2.0, 1.5, 4000))
    elif dist == "uniform":
        vals = rng.uniform(0.05, 5000.0, 4000)
    else:
        vals = np.concatenate([rng.uniform(0.5, 2.0, 2000),
                               rng.uniform(800.0, 900.0, 2000)])
    h = om.Histogram("t")
    for v in vals:
        h.observe(float(v))
    sv = sorted(float(v) for v in vals)
    for p in (0.0, 0.01, 0.25, 0.5, 0.9, 0.95, 0.99, 1.0):
        q = h.quantile(p)
        exact = om.exact_quantile(sv, p)
        assert exact / BOUND - 1e-9 <= q <= exact * BOUND + 1e-9, \
            f"{dist} p{p}: hist {q} vs exact {exact} (bound x{BOUND:.3f})"
    # exact fields are exact, not bucketed
    assert h.count == len(vals)
    assert h.quantile(0.0) == pytest.approx(min(sv))
    assert h.quantile(1.0) == pytest.approx(max(sv))
    assert h.sum == pytest.approx(sum(sv), rel=1e-9)


def test_histogram_empty_and_single_sample_edges():
    assert om.Histogram("e").quantile(0.5) is None
    assert om.Histogram("e").snapshot()["count"] == 0
    one = om.Histogram("o")
    one.observe(3.7)
    # min/max clamp: a one-sample histogram is EXACT at every p
    for p in (0.0, 0.01, 0.5, 0.99, 1.0):
        assert one.quantile(p) == 3.7
    snap = one.snapshot()
    assert snap["min"] == snap["max"] == 3.7
    # values beyond the last bucket land in overflow and stay quantilable
    big = om.Histogram("b")
    big.observe(1e9)
    assert big.quantile(0.5) == 1e9
    assert big.snapshot()["buckets"][-1][0] is None


def test_histogram_merge_associative_and_equals_union():
    def mk(seed, n):
        h = om.Histogram("m")
        rng = np.random.default_rng(seed)
        for v in rng.uniform(0.001, 50_000.0, n):
            h.observe(float(v))
        return h.snapshot()

    a, b, c = mk(1, 300), mk(2, 217), mk(3, 55)
    m1 = om.merge_snapshots(om.merge_snapshots(a, b), c)
    m2 = om.merge_snapshots(a, om.merge_snapshots(b, c))
    assert m1 == m2                         # associativity
    assert om.merge_snapshots(a, b) == om.merge_snapshots(b, a)
    # merged == histogram of the concatenated samples
    h = om.Histogram("u")
    for seed, n in ((1, 300), (2, 217), (3, 55)):
        rng = np.random.default_rng(seed)
        for v in rng.uniform(0.001, 50_000.0, n):
            h.observe(float(v))
    union = h.snapshot()
    assert m1["count"] == union["count"]
    assert m1["buckets"] == union["buckets"]
    assert m1["min"] == union["min"] and m1["max"] == union["max"]
    assert m1["sum"] == pytest.approx(union["sum"], abs=1e-3)


def test_histogram_diff_is_window_view():
    h = om.Histogram("w")
    rng = np.random.default_rng(9)
    first = rng.uniform(1.0, 100.0, 500)
    second = rng.uniform(50.0, 5000.0, 300)
    for v in first:
        h.observe(float(v))
    before = h.snapshot()
    for v in second:
        h.observe(float(v))
    win = om.diff_snapshot(h.snapshot(), before)
    only = om.Histogram("w2")
    for v in second:
        only.observe(float(v))
    assert win["count"] == 300
    assert win["buckets"] == only.snapshot()["buckets"]
    # window quantiles honor the bound against the window's exact samples
    sv = sorted(float(v) for v in second)
    for p in (0.5, 0.99):
        q = om.quantile_from_snapshot(win, p)
        exact = om.exact_quantile(sv, p)
        assert exact / BOUND <= q <= exact * BOUND * (1 + 1e-9)


def test_histogram_thread_safety_under_hammering():
    h = om.Histogram("conc")

    def work(seed):
        rng = np.random.default_rng(seed)
        for v in rng.uniform(0.1, 1000.0, 10_000):
            h.observe(float(v))

    ts = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = h.snapshot()
    assert snap["count"] == 80_000
    assert sum(n for _le, n in snap["buckets"]) == 80_000


# -- registry: labels, namespaces, atomic snapshots ---------------------------

def test_registry_labeled_series_and_percentiles_view():
    reg = om.MetricsRegistry()
    reg.histogram("lat_ms", "family help")
    for tenant, vals in (("a", [10, 20, 30]), ("b", [500, 600, 700])):
        for v in vals:
            reg.histogram("lat_ms", tenant=tenant, template="t1").observe(v)
            reg.histogram("lat_ms").observe(v)
    hists = reg.histograms()
    assert "lat_ms" in hists
    assert "lat_ms{template=t1,tenant=a}" in hists
    assert hists["lat_ms{template=t1,tenant=a}"]["labels"] == \
        {"tenant": "a", "template": "t1"}
    # children inherit the family help; describe lists the family once
    assert reg.histogram("lat_ms", tenant="a", template="t1").help == \
        "family help"
    assert reg.describe()["lat_ms"] == "family help"
    rows = reg.percentiles("lat_ms", ps=(0.5, 0.99))
    assert rows[0]["labels"] == {}                  # all-traffic row first
    assert rows[0]["count"] == 6
    assert rows[1]["labels"].get("tenant") == "b"   # slowest labeled first
    assert rows[1]["count"] == 3
    assert rows[1]["p99"] >= rows[1]["p50"] > 100


def test_registry_series_cap_overflows_to_base():
    reg = om.MetricsRegistry()
    orig = om.HISTOGRAM_MAX_SERIES
    om.HISTOGRAM_MAX_SERIES = 4
    try:
        for i in range(10):
            reg.histogram("h", tenant=f"t{i}").observe(1.0)
    finally:
        om.HISTOGRAM_MAX_SERIES = orig
    hists = reg.histograms()
    labeled = [k for k in hists if "{" in k]
    assert len(labeled) <= 4
    # the overflow observations landed in the base series, not the void
    assert hists["h"]["count"] == 10 - len(labeled)


def test_counter_and_histogram_namespaces_coexist():
    reg = om.MetricsRegistry()
    c = reg.counter("q_wait_ms", "total")
    c.inc(5)
    reg.histogram("q_wait_ms", "distribution").observe(5.0)
    assert reg.snapshot()["q_wait_ms"] == 5          # scalar view
    assert reg.histograms()["q_wait_ms"]["count"] == 1
    with pytest.raises(TypeError):
        reg.gauge("q_wait_ms")                       # scalar clash still typed


def test_snapshot_is_atomic_cut_across_metrics():
    """The satellite fix: a snapshot can never observe metric A's update
    from a logical event without metric B's when the writer holds the
    registry value lock."""
    reg = om.MetricsRegistry()
    a, b = reg.counter("a"), reg.counter("b")
    stop = threading.Event()
    torn = []

    def writer():
        while not stop.is_set():
            with reg.locked():
                a.inc()
                b.inc()

    def reader():
        for _ in range(2000):
            snap = reg.snapshot()
            if snap["a"] != snap["b"]:
                torn.append(snap)

    w = threading.Thread(target=writer)
    r = threading.Thread(target=reader)
    w.start()
    r.start()
    r.join()
    stop.set()
    w.join()
    assert torn == [], f"torn snapshots: {torn[:3]}"


def test_export_prometheus_structure():
    reg = om.MetricsRegistry()
    reg.counter("runs", "run counter").inc(3)
    reg.gauge("depth").set(7)
    h = reg.histogram("lat_ms", "latency", tenant="x")
    for v in (1.0, 2.0, 400.0):
        h.observe(v)
    text = reg.export_prometheus()
    assert "# TYPE runs_total counter" in text
    assert "runs_total 3" in text
    assert "depth 7" in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_count{tenant="x"} 3' in text
    assert 'lat_ms_sum{tenant="x"} 403.0' in text
    # bucket counts are CUMULATIVE and end at +Inf == count
    lines = [ln for ln in text.splitlines() if ln.startswith("lat_ms_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts)
    assert lines[-1].endswith(" 3") and 'le="+Inf"' in lines[-1]


def test_exact_quantile_nearest_rank():
    vals = sorted(float(i) for i in range(1, 101))
    assert om.exact_quantile(vals, 0.0) == 1.0
    assert om.exact_quantile(vals, 1.0) == 100.0
    assert om.exact_quantile(vals, 0.5) == 51.0   # round(0.5*99)=50 -> idx 50
    assert om.exact_quantile([], 0.5) == 0.0


# -- service integration: spans, histograms, stats ----------------------------

N_FACT, N_DIM = 20_000, 50
TPL = ("SELECT grp, COUNT(*) AS n, SUM(qty) AS tq FROM fact "
       "JOIN dim ON fk = dk WHERE qty BETWEEN {a} AND {b} "
       "GROUP BY grp ORDER BY grp")
#: no hoistable literals -> no shared fingerprint -> the serial lane
SERIAL_SQL = "SELECT grp, COUNT(*) AS n FROM dim GROUP BY grp ORDER BY grp"


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, N_DIM, N_FACT), type=pa.int64()),
        "qty": pa.array(rng.integers(1, 100, N_FACT), type=pa.int64())})
    dim = pa.table({"dk": pa.array(np.arange(N_DIM), type=pa.int64()),
                    "grp": pa.array((np.arange(N_DIM) % 7)
                                    .astype(np.int64))})
    return {"fact": fact, "dim": dim}


def make_session(data):
    s = Session(EngineConfig())
    s.register_arrow("fact", data["fact"])
    s.register_arrow("dim", data["dim"])
    return s


def hold_batch(svc, texts, timeout=30.0):
    """Submit texts under a held lane; return tickets once all are ready."""
    with svc.hold_dispatch():
        tickets = [svc.submit(sql, label=f"t{i}", tenant="dash")
                   for i, sql in enumerate(texts)]
        t0 = time.time()
        while time.time() - t0 < timeout:
            with svc._cv:
                if len(svc._ready) >= len(tickets):
                    break
            time.sleep(0.005)
    return tickets


def test_service_span_tree_parent_linked_across_thread_hops(data):
    TRACER.configure(enabled=True)
    session = make_session(data)
    with QueryService(session, ServiceConfig(max_batch=8)) as svc:
        svc.sql(TPL.format(a=5, b=60), label="warm")
        svc.sql(TPL.format(a=5, b=60), label="warm")
        tickets = hold_batch(
            svc, [TPL.format(a=5 + i, b=60 + i) for i in range(4)])
        for t in tickets:
            t.result(timeout=120)
    assert TRACER.open_spans() == [], "unclosed spans"
    events = TRACER.events()
    tree = span_tree(events)            # raises on dangling parents
    by_sid = {e["sid"]: e for e in events}
    for t in tickets:
        assert t.trace_id > 0
        root = by_sid[t.trace_id]
        assert root["name"] == "service/ticket"
        assert root["args"]["tenant"] == "dash"
        assert root["args"]["latency_ms"] > 0
        kids = [by_sid[sid] for sid in tree.get(t.trace_id, [])]
        names = {k["name"] for k in kids}
        assert {"service/queue", "service/plan", "service/lane_wait",
                "service/dispatch", "service/materialize"} <= names, names
        # the three thread hops: client (root+queue), planner worker
        # (plan), device lane (dispatch) are distinct OS threads
        tids = {root["tid"]} | {k["tid"] for k in kids}
        assert len(tids) >= 3, f"expected >=3 threads, saw {tids}"
        dispatch = next(k for k in kids if k["name"] == "service/dispatch")
        assert dispatch["args"]["batched_with"] == 3
        assert dispatch["args"]["batch_rows"] == 4      # no duplicates
        assert dispatch["args"]["dedup"] == 0
        # ExecStats joins the stats record to this subtree
        assert t.stats.trace_id == t.trace_id
        assert t.stats.to_dict()["trace_id"] == t.trace_id


def test_service_serial_lane_nests_session_spans_under_ticket(data):
    TRACER.configure(enabled=True)
    session = make_session(data)
    with QueryService(session) as svc:
        ticket = svc.submit(SERIAL_SQL, label="serial", tenant="ten")
        ticket.result(timeout=120)
    events = TRACER.events()
    by_sid = {e["sid"]: e for e in events}
    query = next(e for e in events if e["name"] == "query"
                 and e.get("args", {}).get("label") == "serial")
    chain = []
    cur = query
    while cur.get("parent"):
        cur = by_sid[cur["parent"]]
        chain.append(cur["name"])
    assert chain[0] == "service/dispatch"
    assert chain[-1] == "service/ticket"
    assert ticket.stats.mode != "batched"


def test_lane_idle_never_overlaps_a_dispatch(data):
    """service/lane_idle is the lane thread waiting for a ticket: on that
    thread, as a profiler annotation too, and never while it dispatches."""
    TRACER.configure(enabled=True)
    session = make_session(data)
    with QueryService(session, ServiceConfig(max_batch=8)) as svc:
        for i in range(3):
            svc.sql(TPL.format(a=5, b=60), label="warm")
            time.sleep(0.06)
        tickets = hold_batch(
            svc, [TPL.format(a=5 + i, b=60 + i) for i in range(3)])
        for t in tickets:
            t.result(timeout=120)
    events = [e for e in TRACER.events() if e.get("ph") == "X"]
    idle = [e for e in events if e["name"] == "service/lane_idle"]
    busy = [e for e in events if e["name"] == "service/dispatch"]
    assert idle and busy
    assert len({e["tid"] for e in idle}) == 1           # the lane thread
    assert {e["tid"] for e in idle} <= {e["tid"] for e in busy}
    assert sum(e["dur"] for e in idle) >= 100_000       # it did wait
    for i in idle:
        for b in busy:
            assert i["ts"] + i["dur"] <= b["ts"] + 1.0 or \
                b["ts"] + b["dur"] <= i["ts"] + 1.0, (i, b)


def test_frontdoor_reply_is_parent_linked_to_its_ticket(data):
    from nds_tpu.service import FrontDoorServer
    from nds_tpu.service.frontdoor import FlightClient
    TRACER.configure(enabled=True)
    session = make_session(data)
    with QueryService(session) as svc:
        server = FrontDoorServer(svc, host="127.0.0.1", port=0)
        server.start()
        try:
            with FlightClient("127.0.0.1", server.port,
                              timeout_s=120.0) as client:
                table, resp = client.query(SERIAL_SQL, label="wire")
        finally:
            server.stop()
    assert table.num_rows > 0 and resp["stats"]["exec_ms"] > 0
    events = TRACER.events()
    span_tree(events)                   # no dangling parent
    by_sid = {e["sid"]: e for e in events}
    reply = [e for e in events if e["name"] == "frontdoor/reply"]
    assert len(reply) == 1
    root = by_sid[reply[0]["parent"]]
    assert root["name"] == "service/ticket"
    assert root["args"]["label"] == reply[0]["args"]["label"] == "wire"
    # the reply follows the ticket's completion, on the connection's thread
    assert reply[0]["ts"] >= root["ts"]


def test_service_records_histograms_per_tenant_and_template(data):
    session = make_session(data)
    before = {k: v["count"]
              for k, v in om.METRICS.histograms().items()}
    with QueryService(session, ServiceConfig(max_batch=8)) as svc:
        svc.sql(TPL.format(a=5, b=60), label="warm", tenant="t_a")
        svc.sql(TPL.format(a=5, b=60), label="warm", tenant="t_a")
        tickets = hold_batch(
            svc, [TPL.format(a=5 + i, b=60 + i) for i in range(3)])
        for t in tickets:
            t.result(timeout=120)
    hists = om.METRICS.histograms()

    def grew(name, labels=None):
        for key, snap in hists.items():
            if snap["name"] != name:
                continue
            if labels is not None and snap.get("labels") != labels:
                continue
            if snap["count"] > before.get(key, 0):
                return True
        return False

    template = tickets[0].template
    assert template and template == tickets[0].fp[:12]
    for fam in ("service_latency_ms", "service_queue_wait_ms",
                "service_plan_ms", "service_exec_ms",
                "service_materialize_ms"):
        assert grew(fam), f"{fam} base series did not move"
    assert grew("service_latency_ms",
                {"tenant": "dash", "template": template})
    # the live SLO view ranks the tenant rows
    rows = om.METRICS.percentiles("service_latency_ms")
    assert any(r["labels"].get("tenant") == "dash" for r in rows)


def test_tracing_disabled_service_records_no_spans(data):
    session = make_session(data)
    with QueryService(session) as svc:
        t = svc.submit(SERIAL_SQL, label="dark")
        t.result(timeout=120)
    assert TRACER.events() == []
    assert t.trace_id == 0
    assert t.stats.trace_id is None
    assert "trace_id" not in t.stats.to_dict()


def test_detached_span_cross_thread_begin_end():
    TRACER.configure(enabled=True)
    root = TRACER.span("root.detached", label="x").begin()
    out = {}

    def child():
        with TRACER.span("child", parent=root.sid):
            out["tid"] = threading.get_ident()

    th = threading.Thread(target=child)
    th.start()
    th.join()
    root.end()
    events = TRACER.events()
    child_e = next(e for e in events if e["name"] == "child")
    root_e = next(e for e in events if e["name"] == "root.detached")
    assert child_e["parent"] == root_e["sid"]
    assert child_e["tid"] == out["tid"] != root_e["tid"]
    span_tree(events)


# -- flight recorder ----------------------------------------------------------

def test_flight_ring_overflow_keeps_most_recent():
    fr = FlightRecorder(capacity=100)
    fr.configure(enabled=True, clear=True)
    for i in range(250):
        fr.record("admit", i=i)
    events = fr.events()
    assert len(events) == 100
    assert [e["i"] for e in events] == list(range(150, 250))
    assert events[0]["seq"] == 151 and events[-1]["seq"] == 250
    # monotonic timestamps
    ts = [e["t_ms"] for e in events]
    assert ts == sorted(ts)


def test_flight_disabled_records_nothing_and_is_cheap():
    fr = FlightRecorder()
    t0 = time.perf_counter()
    for _ in range(200_000):
        fr.record("admit", label="x")
    assert time.perf_counter() - t0 < 2.0
    assert fr.events() == []


def test_flight_fault_point_triggers_dump(tmp_path):
    FLIGHT.configure(enabled=True, dump_dir=str(tmp_path), clear=True)
    FLIGHT.record("admit", label="q1", tenant="a")
    spec = FAULTS.arm(FaultSpec(point="query.run", match="flight_q",
                                times=1))
    try:
        with pytest.raises(FaultError):
            FAULTS.fire("query.run", "flight_q")
    finally:
        FAULTS.disarm(spec)
    assert len(FLIGHT.dumps) == 1
    lines = [json.loads(ln) for ln in open(FLIGHT.dumps[0])]
    kinds = [e["event"] for e in lines]
    assert kinds == ["admit", "fault", "trip"]
    fault = lines[1]
    assert fault["point"] == "query.run"
    assert fault["detail"] == "flight_q"
    assert lines[2]["reason"] == "fault"
    # a second firing inside the cooldown records but does not re-dump
    spec = FAULTS.arm(FaultSpec(point="query.run", match="flight_q",
                                times=1))
    try:
        with pytest.raises(FaultError):
            FAULTS.fire("query.run", "flight_q")
    finally:
        FAULTS.disarm(spec)
    assert len(FLIGHT.dumps) == 1


def test_flight_reject_storm_triggers_dump(tmp_path, data):
    FLIGHT.configure(enabled=True, dump_dir=str(tmp_path),
                     reject_storm=5, reject_window_s=30.0, clear=True)
    session = make_session(data)
    svc = QueryService(session, ServiceConfig(max_pending=1)).start()
    try:
        with svc.hold_dispatch():
            svc.submit(SERIAL_SQL, label="occupier")
            from nds_tpu.resilience import AdmissionRejected
            for i in range(6):
                with pytest.raises(AdmissionRejected):
                    svc.submit(SERIAL_SQL, label=f"r{i}", tenant="storm")
    finally:
        svc.close()
    assert len(FLIGHT.dumps) == 1
    lines = [json.loads(ln) for ln in open(FLIGHT.dumps[0])]
    rejects = [e for e in lines if e["event"] == "reject"]
    assert len(rejects) >= 5
    assert rejects[0]["reason"] == "queue_full"
    assert rejects[0]["limit"] == 1
    trip = next(e for e in lines if e["event"] == "trip")
    assert trip["reason"] == "reject_storm"


def test_service_lifecycle_lands_in_flight_ring(data):
    FLIGHT.configure(enabled=True, clear=True)
    session = make_session(data)
    with QueryService(session, ServiceConfig(max_batch=8)) as svc:
        svc.sql(TPL.format(a=5, b=60), label="warm")
        svc.sql(TPL.format(a=5, b=60), label="warm")
        tickets = hold_batch(
            svc, [TPL.format(a=5 + i, b=60 + i) for i in range(3)])
        for t in tickets:
            t.result(timeout=120)
    kinds = [e["event"] for e in FLIGHT.events()]
    for k in ("admit", "plan", "batch", "complete"):
        assert k in kinds, f"missing {k} in {set(kinds)}"
    batch = next(e for e in FLIGHT.events() if e["event"] == "batch")
    assert batch["queries"] == 3 and batch["dedup"] == 0
    done = [e for e in FLIGHT.events() if e["event"] == "complete"]
    assert all(e["latency_ms"] > 0 for e in done)
    assert any(e.get("batched_with") == 2 for e in done)


# -- CLI summarizers ----------------------------------------------------------

def test_trace_report_on_flight_jsonl_and_service_trace(tmp_path, data):
    FLIGHT.configure(enabled=True, clear=True)
    TRACER.configure(enabled=True)
    session = make_session(data)
    with QueryService(session) as svc:
        svc.sql(SERIAL_SQL, label="cli_q", tenant="cli")
    fpath = FLIGHT.dump_jsonl(str(tmp_path / "flight.jsonl"))
    tpath = TRACER.write_chrome_trace(str(tmp_path / "trace.json"))
    script = os.path.join(REPO, "scripts", "trace_report.py")
    out = subprocess.run([sys.executable, script, fpath],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "flight recorder" in out.stdout
    assert "cli" in out.stdout and "complete" in out.stdout
    out = subprocess.run([sys.executable, script, tpath],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "service/ticket" in out.stdout
    assert "service tickets by tenant" in out.stdout
    assert "slowest" in out.stdout


def test_obs_report_on_histogram_artifact_and_flight(tmp_path):
    reg = om.MetricsRegistry()
    for tenant, base in (("a", 10.0), ("b", 900.0)):
        for i in range(20):
            reg.histogram("service_latency_ms", "lat", tenant=tenant,
                          template="tpl1").observe(base + i)
            reg.histogram("service_latency_ms").observe(base + i)
    artifact = tmp_path / "metrics.json"
    artifact.write_text(json.dumps(reg.export_json()))
    script = os.path.join(REPO, "scripts", "obs_report.py")
    out = subprocess.run([sys.executable, script, str(artifact)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "service_latency_ms" in out.stdout
    assert "tenant=b" in out.stdout          # slowest labeled row present
    out = subprocess.run([sys.executable, script, str(artifact),
                          "--prometheus"], capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert 'service_latency_ms_bucket{template="tpl1",tenant="a",le=' \
        in out.stdout
    fr = FlightRecorder()
    fr.configure(enabled=True, clear=True)
    fr.record("complete", label="x", tenant="t", latency_ms=12.0)
    fpath = fr.dump_jsonl(str(tmp_path / "fl.jsonl"))
    out = subprocess.run([sys.executable, script, fpath],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "flight recorder" in out.stdout


# -- metrics gate -------------------------------------------------------------

def test_metrics_gate_compare_logic():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import metrics_gate as mg

    base = {"compiles": 4, "queries_run": 10, "morsels": 16}
    assert mg.compare(base, {"compiles": 4, "queries_run": 10,
                             "morsels": 16}) == []
    # generous bands: small absolute drift and <=2x ratio pass
    assert mg.compare(base, {"compiles": 6, "queries_run": 18,
                             "morsels": 30}) == []
    v = mg.compare(base, {"compiles": 40, "queries_run": 10,
                          "morsels": 16})
    assert len(v) == 1 and "compiles" in v[0]
    v = mg.compare(base, {"queries_run": 10, "morsels": 16})
    assert len(v) == 1 and "MISSING" in v[0]
    # strict-zero metrics fail on ANY movement
    v = mg.compare(base, {"compiles": 4, "queries_run": 10, "morsels": 16,
                          "replay_mismatches": 1})
    assert len(v) == 1 and "STRICT-ZERO" in v[0]
    gated, report = mg.gated_view({"compiles": 3, "host_decode_ms": 9.1,
                                   "bytes_uploaded": 100})
    assert "compiles" in gated
    assert "host_decode_ms" in report and "bytes_uploaded" in report


@pytest.mark.slow
def test_metrics_gate_end_to_end_passes_on_tree():
    script = os.path.join(REPO, "scripts", "metrics_gate.py")
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=600)
    assert out.returncode == 0, out.stderr
    assert "metrics_gate: OK" in out.stderr
