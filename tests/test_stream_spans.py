"""The streamed pass accounts for its own wall (ISSUE 39): the spans of the
main thread's time in ``Session.iter_morsels`` / ``_stream_group``, the
``bytes_decoded`` counter beside them, and ``xplane.idle_gaps``, which
splits a device gap among the spans open on the thread that dispatches.
"""
import threading

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session, arrow_bridge
from nds_tpu.obs import metrics as om
from nds_tpu.obs import xplane
from nds_tpu.obs.trace import TRACER, span_tree

ROWS, CHUNK = 10_000, 3_000
MORSELS = 4                       # 3,000 + 3,000 + 3,000 + 1,000 rows
QUERY = ("SELECT k, SUM(v) AS sv, COUNT(*) AS c FROM fact "
         "GROUP BY k ORDER BY k")
NEW_SPANS = ("morsel.decode", "morsel.read", "morsel.from_arrow",
             "morsel.stage_sync", "morsel.stage_wait", "morsel.partials")


@pytest.fixture(autouse=True)
def _tracer_off():
    TRACER.configure(enabled=False)
    yield
    TRACER.configure(enabled=False)


def fact() -> pa.Table:
    rng = np.random.default_rng(39)
    return pa.table({"k": pa.array(rng.integers(0, 7, ROWS)),
                     "v": pa.array(rng.integers(0, 100, ROWS))})


@pytest.fixture
def session():
    """A session whose statement streams ``fact`` in four morsels, seen
    twice already: the programs are the tight ones and nothing compiles."""
    s = Session(EngineConfig(out_of_core_min_rows=1000, chunk_rows=CHUNK))
    s.register_arrow("fact", fact())
    for _ in range(3):
        s.sql(QUERY)
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["morsels"] == MORSELS
    return s


def traced(session):
    TRACER.configure(enabled=True)
    before = om.METRICS.snapshot()
    result = session.sql(QUERY)
    events = [e for e in TRACER.events() if e.get("ph") == "X"]
    TRACER.configure(enabled=False, clear=False)
    return result, events, om.METRICS.delta(before)


def named(events, name):
    return [e for e in events if e["name"] == name]


def inside(child, parent, slack_us=0.2):
    return child["ts"] >= parent["ts"] - slack_us and \
        child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + slack_us


@pytest.mark.parametrize("name,count,parent", [
    # one wait a morsel and the one that finds the source at its end
    ("morsel.decode", MORSELS + 1, "query"),
    ("morsel.read", MORSELS + 1, "morsel.decode"),
    ("morsel.from_arrow", MORSELS, "morsel.decode"),
    # the first morsel of the group alone is staged by the main thread
    ("morsel.stage_sync", 1, "query"),
    # every morsel but the last has a successor on the staging thread
    ("morsel.stage_wait", MORSELS - 1, "query"),
    ("morsel.partials", MORSELS, "query"),
])
def test_each_span_once_a_morsel_under_its_parent(session, name, count,
                                                  parent):
    _result, events, _delta = traced(session)
    span_tree(events)                         # every parent resolves
    by_sid = {e["sid"]: e for e in events}
    got = named(events, name)
    assert len(got) == count
    main = named(events, "query")[0]["tid"]
    for e in got:
        up = by_sid[e["parent"]]
        assert up["name"] == parent and inside(e, up)
        assert e["tid"] == main               # the thread that dispatches
    assert TRACER.open_spans() == []


def test_the_spans_attributes_say_what_was_decoded_and_staged(session):
    _result, events, _delta = traced(session)
    sizes = [3000, 3000, 3000, 1000]
    assert [e["args"]["rows"] for e in named(events, "morsel.decode")] == \
        sizes + [0]
    reads = named(events, "morsel.read")
    assert [e["args"]["rows"] for e in reads] == sizes + [0]
    assert all(e["args"]["table"] == "fact" for e in reads)
    assert sum(e["args"]["batches"] for e in reads) >= 1
    assert [(e["args"]["rows"], e["args"]["columns"])
            for e in named(events, "morsel.from_arrow")] == \
        [(n, 2) for n in sizes]
    sync, = named(events, "morsel.stage_sync")
    assert sync["cat"] == "upload"
    assert sync["args"] == {"table": "fact", "prefetch_error": False}
    # the stage it pays is the span that was there, as its child; the other
    # three are the staging thread's, roots of their own thread
    stages = named(events, "morsel.stage")
    assert len(stages) == MORSELS
    assert [e["parent"] == sync["sid"] for e in stages].count(True) == 1
    assert sum(e["tid"] != sync["tid"] for e in stages) == MORSELS - 1
    assert [e["args"]["morsel"]
            for e in named(events, "morsel.stage_wait")] == [0, 1, 2]
    for e in named(events, "morsel.partials"):
        assert e["args"] == {"members": 1, "rows": 7}


def test_decode_is_its_read_and_its_conversion_and_the_old_clock(session):
    _result, events, _delta = traced(session)
    decodes = named(events, "morsel.decode")
    for d in decodes:
        kids = [e for e in events if e["parent"] == d["sid"]]
        assert [k["name"] for k in kids] in (
            ["morsel.read", "morsel.from_arrow"], ["morsel.read"])
        assert sum(k["dur"] for k in kids) <= d["dur"] + 0.4
    # one clock feeds ExecStats.host_decode_ms; the span wraps the same
    # statement, so the two agree within a millisecond a morsel
    span_ms = sum(d["dur"] for d in decodes) / 1e3
    stats_ms = session.last_exec_stats["host_decode_ms"]["fact"]
    assert abs(span_ms - stats_ms) < 1.0 * MORSELS
    assert span_ms >= stats_ms - 0.01      # the span is the outer of the two


def test_bytes_decoded_counts_the_parts_handed_to_from_arrow(session):
    _result, events, delta = traced(session)
    table = fact()
    parts = sum(table.slice(at, CHUNK).nbytes for at in range(0, ROWS, CHUNK))
    assert parts == ROWS * 16                 # two int64 columns, no nulls
    assert delta["bytes_decoded"] == parts
    assert sum(e["args"]["bytes"]
               for e in named(events, "morsel.read")) == parts
    assert "bytes_decoded" in om.METRICS.describe()


def test_view_columns_count_the_morsels_columns_and_the_merge(session):
    """Every column the statement decodes is an integer: each morsel's two
    become engine columns by buffer view, and so do the four of the merged
    partials (`g0`, `sum(v)__s`, `sum(v)__n`, `count(1)__cs`) on their way
    back from Arrow; nothing takes another path."""
    _result, events, delta = traced(session)
    assert [(e["args"]["viewed"], e["args"]["fallback"])
            for e in named(events, "morsel.from_arrow")] == [(2, 0)] * MORSELS
    assert delta["arrow_view_columns"] == MORSELS * 2 + 4
    assert delta.get("arrow_fallback_columns", 0) == 0
    assert {"arrow_view_columns", "arrow_fallback_columns"} <= \
        set(om.METRICS.describe())
    # the counters are always on, like bytes_decoded
    before = om.METRICS.snapshot()
    session.sql(QUERY)
    assert om.METRICS.delta(before)["arrow_view_columns"] == MORSELS * 2 + 4


def test_tracer_off_records_nothing_and_answers_the_same(session):
    TRACER.clear()
    before = om.METRICS.snapshot()
    off = session.sql(QUERY)
    assert TRACER.events() == [] and TRACER.open_spans() == []
    # the counter is always on, like host_decode_ms
    assert om.METRICS.delta(before)["bytes_decoded"] == ROWS * 16
    on, events, _delta = traced(session)
    assert {e["name"] for e in events} >= set(NEW_SPANS)
    assert arrow_bridge.to_arrow(on).equals(arrow_bridge.to_arrow(off))


def test_a_failed_prefetch_is_a_synchronous_stage_that_says_so(
        session, monkeypatch):
    from nds_tpu.engine.jax_backend import device
    pack, main = device.pack_table, threading.get_ident()

    def fail_off_the_main_thread(*a, **kw):
        if threading.get_ident() != main:
            raise OSError("staging thread lost its buffer")
        return pack(*a, **kw)
    monkeypatch.setattr(device, "pack_table", fail_off_the_main_thread)
    want = arrow_bridge.to_arrow(session.sql(QUERY))
    result, events, _delta = traced(session)
    assert arrow_bridge.to_arrow(result).equals(want)
    assert len(session.last_exec_stats["prefetch_error_details"]) == \
        MORSELS - 1
    syncs = named(events, "morsel.stage_sync")
    assert [e["args"]["prefetch_error"] for e in syncs] == \
        [False] + [True] * (MORSELS - 1)


def test_a_mid_stream_failure_leaves_no_span_open(session, monkeypatch):
    """The second morsel's partials fail while the staging thread holds the
    third: the ``finally`` joins it under a span, and every span closes."""
    to_arrow, calls = arrow_bridge.to_arrow, []

    def fail_at_the_second_morsel(table):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("partials lost")
        return to_arrow(table)
    monkeypatch.setattr(arrow_bridge, "to_arrow", fail_at_the_second_morsel)
    TRACER.configure(enabled=True)
    with pytest.raises(RuntimeError, match="partials lost"):
        session.sql(QUERY)
    assert TRACER.open_spans() == []
    events = TRACER.events()
    assert [e["args"].get("error") for e in named(events, "morsel.partials")] \
        == [None, "RuntimeError"]
    # the join after the first morsel, and the finally's after the failure
    assert [e["args"]["morsel"]
            for e in named(events, "morsel.stage_wait")] == [0, 1]
    monkeypatch.undo()
    TRACER.configure(enabled=False)
    assert session.sql(QUERY).num_rows == 7   # and the statement still runs


def test_the_seven_spans_nothing_read_are_gone():
    import inspect

    from nds_tpu.engine import result_cache, streaming
    for module in (result_cache, streaming):
        assert "TRACER" not in inspect.getsource(module)


# -- xplane.idle_gaps on plain lists ------------------------------------------

MS = 1_000_000


def trace_of(spans, *chips):
    """``xplane.read``'s shape from (start_ms, end_ms, name, thread) spans
    and, per chip, the (start_ms, end_ms) intervals it was busy."""
    return {"start_unix_ns": None,
            "spans": [(s * MS, e * MS, n, t) for s, e, n, t in spans],
            "devices": [{"XLA Ops": [(s * MS, e * MS, f"%op.{i}")
                                     for i, (s, e) in enumerate(busy)]}
                        for busy in chips]}


def gaps_ms(trace):
    return {name: pytest.approx(s * 1e3) for name, s in
            xplane.idle_gaps(trace)}


def test_a_gap_goes_to_the_child_for_its_interval_and_to_the_parent_for_the_rest():
    # the device idles from 10 to 110 ms; the dispatching thread is inside
    # its statement all along and inside a decode from 20 to 80
    t = trace_of([(0, 200, "nds.query:q9", 1),
                  (20, 80, "nds.morsel.decode", 1),
                  (30, 50, "nds.morsel.read", 1),
                  (109.5, 130, "nds.exec.wait", 1)],
                 [(0, 10), (110, 120)])
    assert gaps_ms(t) == {"nds.morsel.decode": 40.0, "nds.morsel.read": 20.0,
                          "nds.query:q9": 39.5, "nds.exec.wait": 0.5}
    got = xplane.idle_gaps(t)
    assert [name for name, _s in got][0] == "nds.morsel.decode"
    assert [s for _n, s in got] == sorted((s for _n, s in got), reverse=True)


def test_another_threads_span_takes_nothing():
    # the staging thread's span covers the whole gap; the device waits for
    # the thread that opens the next exec.wait, which has no span for 30 ms
    t = trace_of([(0, 200, "nds.morsel.stage", 2),
                  (40, 100, "nds.morsel.from_arrow", 1),
                  (100, 130, "nds.exec.wait", 1)],
                 [(0, 10), (110, 120)])
    assert gaps_ms(t) == {"nds.morsel.from_arrow": 60.0,
                          "nds.exec.wait": 10.0, "unannotated": 30.0}


def test_the_dispatching_thread_is_the_one_that_opens_the_next_dispatch():
    # two lanes: thread 1 dispatches the program that ends the first gap,
    # thread 2 the one that ends the second (a labelled collective)
    t = trace_of([(0, 60, "nds.plan:a", 1), (59.8, 70, "nds.exec.wait", 1),
                  (0, 200, "nds.finalize:b", 2),
                  (159.9, 170, "nds.collective:b", 2)],
                 [(0, 10), (60, 100), (160, 170)])
    assert gaps_ms(t) == {"nds.plan:a": 49.8, "nds.exec.wait": 0.2,
                          "nds.finalize:b": 59.9, "nds.collective:b": 0.1}


def test_no_span_is_unannotated_and_no_device_is_no_gap():
    t = trace_of([], [(0, 10), (110, 120), (150, 160)])
    assert gaps_ms(t) == {"unannotated": 130.0}
    # spans, and no dispatch among them: nothing says whom the device awaits
    t = trace_of([(0, 200, "nds.query:q", 1)], [(0, 10), (110, 120)])
    assert gaps_ms(t) == {"unannotated": 100.0}
    assert xplane.idle_gaps(trace_of([(0, 5, "nds.query:q", 1)])) == []
    assert xplane.idle_gaps(trace_of([], [(0, 10)])) == []


def test_two_chips_give_the_mean():
    spans = [(0, 200, "nds.query:q3", 1), (20, 60, "nds.morsel.read", 1),
             (109.9, 130, "nds.exec.wait", 1)]
    # chip 0 idles 10-110, chip 1 idles 50-110
    t = trace_of(spans, [(0, 10), (110, 120)], [(0, 50), (110, 120)])
    assert gaps_ms(t) == {
        "nds.morsel.read": (40.0 + 10.0) / 2,
        "nds.query:q3": (59.9 + 49.9) / 2,
        "nds.exec.wait": 0.1}


def test_a_child_that_outlasts_its_parent_by_a_rounding_keeps_the_order():
    # host events are rounded to the nanosecond each: a child may end a
    # hair after its parent; no time is counted twice and none is lost
    t = {"start_unix_ns": None,
         "spans": [(0, 100 * MS, "nds.morsel.decode", 1),
                   (50 * MS, 100 * MS + 3, "nds.morsel.from_arrow", 1),
                   (100 * MS + 5, 150 * MS, "nds.exec.wait", 1)],
         "devices": [{"XLA Ops": [(0, 10 * MS, "%a"),
                                  (120 * MS, 130 * MS, "%b")]}]}
    got = dict(xplane.idle_gaps(t))
    assert sum(got.values()) == pytest.approx(0.110)
    assert got["nds.morsel.decode"] == pytest.approx(0.040)
    assert got["nds.morsel.from_arrow"] == pytest.approx(0.050, abs=1e-8)
    assert got["unannotated"] == pytest.approx(2e-9)
