"""Concurrent query service (nds_tpu/service): admission control, async
scheduling, the shared cross-client program cache, and compatible-plan
batching.

The contract under test is the acceptance bar of the service itself:
every result a client receives must be BIT-IDENTICAL to running the same
SQL alone on a fresh single-caller Session — through batched dispatches
(one compiled program over a stacked parameter matrix), through the
serial lane (record/adopt/replay, streaming), under concurrent clients,
racing live EngineConfig toggles, and beside deadline-expired neighbors
failing typed."""
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.obs.metrics import METRICS
from nds_tpu.resilience import AdmissionRejected, DeadlineExceeded
from nds_tpu.service import QueryService, ServiceConfig
from nds_tpu.service.service import ServiceClosed

N_FACT, N_DIM = 20_000, 50

#: one parameterized template (int + float aggregates: float sums prove
#: the batched lax.map dispatch is bit-identical even where order could
#: bite) instantiated with different literal values per "client"
TPL = ("SELECT grp, COUNT(*) AS n, SUM(qty) AS tq, SUM(price) AS tp "
       "FROM fact JOIN dim ON fk = dk WHERE qty BETWEEN {a} AND {b} "
       "GROUP BY grp ORDER BY grp")
#: a second, structurally different template (incompatible fingerprint)
TPL2 = ("SELECT fk, MAX(qty) AS mq FROM fact WHERE qty < {a} "
        "GROUP BY fk ORDER BY fk LIMIT 5")


def q1(a, b):
    return TPL.format(a=a, b=b)


def q2(a):
    return TPL2.format(a=a)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(7)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, N_DIM, N_FACT), type=pa.int64()),
        "qty": pa.array(rng.integers(1, 100, N_FACT), type=pa.int64()),
        "price": pa.array(np.round(rng.uniform(1, 50, N_FACT), 2)),
    })
    dim = pa.table({"dk": pa.array(np.arange(N_DIM), type=pa.int64()),
                    "grp": pa.array((np.arange(N_DIM) % 7)
                                    .astype(np.int64))})
    return {"fact": fact, "dim": dim}


def make_session(data, **cfg_kw):
    s = Session(EngineConfig(**cfg_kw))
    s.register_arrow("fact", data["fact"])
    s.register_arrow("dim", data["dim"])
    return s


@pytest.fixture()
def serial_ref(data):
    """Fresh single-caller session: the bit-identity oracle."""
    ref_session = make_session(data)
    cache = {}

    def ref(sql):
        if sql not in cache:
            cache[sql] = ref_session.sql(sql, label="ref").to_pylist()
        return cache[sql]
    return ref


def wait_ready(svc, n, timeout=10.0):
    """Block until the planner stage has n tickets parked at the (held)
    device lane — deterministic batch accumulation."""
    t0 = time.time()
    while time.time() - t0 < timeout:
        with svc._cv:
            if len(svc._ready) >= n:
                return
        time.sleep(0.01)
    raise AssertionError(f"planner stage never readied {n} tickets")


def warm(svc, sql):
    """Two executions: record, then compile + publish the shared program."""
    svc.sql(sql, label="warm")
    svc.sql(sql, label="warm")


# -- batching ----------------------------------------------------------------

def test_batched_dispatch_bit_identical(data, serial_ref):
    session = make_session(data)
    params = [(5 + i, 60 + i) for i in range(5)]
    with QueryService(session, ServiceConfig(max_batch=8)) as svc:
        warm(svc, q1(*params[0]))
        before = METRICS.snapshot()
        with svc.hold_dispatch():
            tickets = [svc.submit(q1(a, b), label=f"c{i}")
                       for i, (a, b) in enumerate(params)]
            wait_ready(svc, len(tickets))
        for t, (a, b) in zip(tickets, params):
            assert t.result(timeout=60).to_pylist() == serial_ref(q1(a, b))
            assert t.stats.mode == "batched"
            assert t.stats.batched_with == len(params) - 1
            assert t.stats.queue_wait_ms is not None
            assert t.stats.queue_wait_ms >= 0
            # the dict view carries the service keys too (bench JSON path)
            d = t.stats.to_dict()
            assert d["batched_with"] == len(params) - 1
            assert "queue_wait_ms" in d
        delta = METRICS.delta(before)
        assert delta.get("service_batches", 0) >= 1
        assert delta.get("service_batched_queries", 0) == len(params)
        # ONE batched dispatch compiled once; the per-row programs did not
        assert delta.get("compiles", 0) <= 1


def test_batch_dedups_identical_parameters(data, serial_ref):
    session = make_session(data)
    with QueryService(session, ServiceConfig()) as svc:
        warm(svc, q1(3, 77))
        with svc.hold_dispatch():
            tickets = [svc.submit(q1(3, 77), label=f"dup{i}")
                       for i in range(4)]
            wait_ready(svc, 4)
        want = serial_ref(q1(3, 77))
        for t in tickets:
            assert t.result(timeout=60).to_pylist() == want
            assert t.stats.mode == "batched"
            assert t.stats.batched_with == 3


def test_unwarmed_batch_falls_back_serial_and_correct(data, serial_ref):
    """No published shared program yet: the batched lookup misses, the
    group serves serially through record/replay, results stay exact."""
    session = make_session(data)
    params = [(2, 40), (3, 50), (4, 60)]
    with QueryService(session, ServiceConfig()) as svc:
        with svc.hold_dispatch():
            tickets = [svc.submit(q1(a, b)) for a, b in params]
            wait_ready(svc, len(tickets))
        for t, (a, b) in zip(tickets, params):
            assert t.result(timeout=60).to_pylist() == serial_ref(q1(a, b))
            assert t.stats.mode != "batched"


def test_incompatible_templates_do_not_cobatch(data, serial_ref):
    session = make_session(data)
    with QueryService(session, ServiceConfig()) as svc:
        warm(svc, q1(5, 60))
        warm(svc, q2(30))
        with svc.hold_dispatch():
            ta = [svc.submit(q1(5 + i, 60 + i)) for i in range(2)]
            tb = [svc.submit(q2(30 + i)) for i in range(2)]
            wait_ready(svc, 4)
        for i, t in enumerate(ta):
            assert t.result(60).to_pylist() == serial_ref(q1(5 + i, 60 + i))
        for i, t in enumerate(tb):
            assert t.result(60).to_pylist() == serial_ref(q2(30 + i))
        # each template batched only with its own kind
        assert all(t.stats.batched_with == 1 for t in ta + tb
                   if t.stats.mode == "batched")


# -- shared cross-client program cache ---------------------------------------

def test_cross_client_adoption_no_recompile(data):
    """The Nth client's NEW text of a warmed template re-traces and
    re-compiles nothing: the shared-fingerprint entry (schedule + program)
    is adopted, compile count stays flat."""
    session = make_session(data)
    with QueryService(session, ServiceConfig()) as svc:
        warm(svc, q1(7, 70))
        before = METRICS.snapshot()
        svc.sql(q1(8, 71), label="client2")   # new text, same template
        svc.sql(q1(9, 72), label="client3")
        delta = METRICS.delta(before)
        assert delta.get("compiles", 0) == 0
        assert delta.get("programs_adopted", 0) >= 2


# -- concurrent correctness ---------------------------------------------------

def test_concurrent_clients_bit_identical(data, serial_ref):
    session = make_session(data)
    texts = [q1(5 + i % 4, 60 + i % 4) for i in range(8)] + \
        [q2(25 + i % 3) for i in range(4)]
    want = {s: serial_ref(s) for s in texts}
    results: dict = {}
    errors: list = []
    with QueryService(session, ServiceConfig(plan_workers=2)) as svc:
        warm(svc, q1(5, 60))

        def client(i, sql):
            try:
                results[(i, sql)] = svc.sql(sql, label=f"cl{i}",
                                            timeout=120).to_pylist()
            except Exception as e:      # surfaced below
                errors.append((i, sql, e))

        threads = [threading.Thread(target=client, args=(i, s))
                   for i, s in enumerate(texts)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    assert not errors, errors
    for (i, sql), got in results.items():
        assert got == want[sql], f"client {i} drifted on {sql!r}"


def test_streamed_query_through_service(data, tmp_path):
    """Out-of-core queries take the serial lane (session streaming path)
    and stay exact vs a fresh single-caller session under the SAME
    streaming config (f64 partial-merge order is config-determined);
    the planner stage excludes them from batching."""
    path = str(tmp_path / "fact.parquet")
    pq.write_table(data["fact"], path, row_group_size=4096)
    cfg = dict(out_of_core=True, out_of_core_min_rows=10_000,
               chunk_rows=4096)

    def streaming_session():
        s = Session(EngineConfig(**cfg))
        s.register_parquet("fact", path)
        s.register_arrow("dim", data["dim"])
        return s

    sql = q1(10, 90)
    want = streaming_session().sql(sql, label="ref").to_pylist()
    session = streaming_session()
    with QueryService(session, ServiceConfig()) as svc:
        t = svc.submit(sql, label="streamed")
        got = t.result(timeout=120).to_pylist()
        assert t.stats.mode == "streaming"
        assert t.stats.queue_wait_ms is not None
        # live encoded_exec toggle racing a fresh submission: the stream
        # cache invalidates by config fingerprint and the encoded/plain
        # layouts are bit-identical by contract
        session.config.encoded_exec = False
        t2 = svc.submit(sql, label="streamed-plain")
        got_plain = t2.result(timeout=120).to_pylist()
        assert t2.stats.mode == "streaming"
        assert got_plain == got
    assert got == want


# -- admission control + deadlines -------------------------------------------

def test_queue_full_typed_rejection(data):
    session = make_session(data)
    with QueryService(session, ServiceConfig(max_pending=2)) as svc:
        with svc.hold_dispatch():
            t1 = svc.submit(q1(5, 60))
            t2 = svc.submit(q1(6, 61))
            before = METRICS.snapshot()
            with pytest.raises(AdmissionRejected) as ei:
                svc.submit(q1(7, 62))
            assert ei.value.depth == 2 and ei.value.limit == 2
            assert METRICS.delta(before).get("service_rejected") == 1
        assert t1.result(60) is not None
        assert t2.result(60) is not None


def test_deadline_expires_in_queue_neighbors_complete(data, serial_ref):
    session = make_session(data)
    with QueryService(session, ServiceConfig()) as svc:
        warm(svc, q1(5, 60))
        with svc.hold_dispatch():
            doomed = svc.submit(q1(6, 61), deadline_s=0.05, tenant="t-low")
            neighbors = [svc.submit(q1(7 + i, 62 + i)) for i in range(2)]
            wait_ready(svc, 1)
            time.sleep(0.2)        # the doomed ticket's budget expires
        before_err = None
        try:
            doomed.result(timeout=60)
        except DeadlineExceeded as e:
            before_err = e
        assert before_err is not None and "t-low" in str(before_err)
        for i, t in enumerate(neighbors):
            assert t.result(60).to_pylist() == serial_ref(q1(7 + i, 62 + i))


def test_tenant_deadline_mapping(data):
    session = make_session(data)
    cfg = ServiceConfig(tenant_deadlines={"impatient": 0.01},
                        default_deadline_s=0.0)
    with QueryService(session, cfg) as svc:
        with svc.hold_dispatch():
            doomed = svc.submit(q1(5, 60), tenant="impatient")
            ok = svc.submit(q1(5, 60), tenant="patient")
            time.sleep(0.1)
        with pytest.raises(DeadlineExceeded):
            doomed.result(timeout=60)
        assert ok.result(60) is not None


def test_closed_service_rejects_typed(data):
    session = make_session(data)
    svc = QueryService(session, ServiceConfig())
    with pytest.raises(ServiceClosed):
        svc.submit(q1(5, 60))          # never started
    svc.start()
    svc.sql(q1(5, 60))
    svc.close()
    with pytest.raises(AdmissionRejected):
        svc.submit(q1(5, 60))


# -- service-backed throughput streams ---------------------------------------

def test_throughput_service_streams(data, serial_ref, tmp_path):
    """Two throughput streams through one shared service: per-stream time
    logs keep the power-run contract (scrape-able sentinels), elapsed
    computes, and the shared session served both."""
    from nds_tpu.throughput import (_run_stream_service, scrape_log,
                                    stream_log_path, throughput_elapsed)

    session = make_session(data)
    stream_text = "\n".join(
        f"-- start query {i + 1} using template query{i + 1}.tpl\n"
        + q1(5 + i, 60 + i) for i in range(2))
    sf = tmp_path / "stream.sql"
    sf.write_text(stream_text)
    logs = [stream_log_path(str(tmp_path), i) for i in range(2)]
    with QueryService(session, ServiceConfig()) as svc:
        threads = [threading.Thread(
            target=_run_stream_service, args=(svc, str(sf), log))
            for log in logs]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    for log in logs:
        start, end = scrape_log(log)
        assert end >= start
    assert throughput_elapsed(logs) >= 0.0


# -- open loop at scale (slow: the 100-client run) ---------------------------

@pytest.mark.slow
def test_open_loop_100_clients(data, serial_ref):
    """100 concurrent clients, mixed templates, parameter pools shared
    across clients (dashboard shape): every response bit-identical to
    serial, no hangs, batching engaged."""
    session = make_session(data)
    pool = [q1(5 + i, 60 + i) for i in range(8)] + \
        [q2(20 + i) for i in range(4)]
    want = {s: serial_ref(s) for s in pool}
    errors: list = []
    done = [0]
    lock = threading.Lock()
    with QueryService(session, ServiceConfig(max_pending=512,
                                             max_batch=32)) as svc:
        warm(svc, pool[0])
        warm(svc, pool[8])

        def client(cid):
            rng = np.random.default_rng(cid)
            for _ in range(3):
                sql = pool[int(rng.integers(0, len(pool)))]
                try:
                    got = svc.sql(sql, label=f"open{cid}",
                                  timeout=300).to_pylist()
                    if got != want[sql]:
                        errors.append((cid, sql, "drift"))
                except Exception as e:
                    errors.append((cid, sql, e))
                with lock:
                    done[0] += 1

        before = METRICS.snapshot()
        threads = [threading.Thread(target=client, args=(cid,))
                   for cid in range(100)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
    assert not errors, errors[:5]
    assert done[0] == 300
    delta = METRICS.delta(before)
    assert delta.get("service_batches", 0) >= 1
    assert delta.get("service_batched_queries", 0) >= 10
