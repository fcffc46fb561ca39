"""Observability layer (nds_tpu/obs): span tracer, metrics registry,
the program's names on the device trace, typed ExecStats, logging channel.

Acceptance-backed properties:
- disabled tracer hooks are near-free (the <2% bench-slice overhead bound
  rests on the disabled path doing no allocation/locking);
- a traced query produces a WELL-FORMED span tree (every span closed,
  every parent id resolvable) that exports to valid Chrome trace-event
  JSON (Perfetto-loadable);
- metrics counters move correctly under the fault-injection smoke run;
- every plan program is the HLO module ``jit_nds_<query>_<unit>`` under a
  name that is the same in every process, its instructions carry the plan
  node and kernel as ``op_name``, and the scopes change no instruction;
- a traced run's spans are ``nds.*`` host events of a ``jax.profiler``
  trace, within a millisecond after the recorded clock anchor;
- ExecStats is built in one place with a dict view identical to the
  legacy untyped ``last_exec_stats`` keys, and records EVERY prefetch
  error.
"""
import contextlib
import glob
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
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


def test_disabled_new_call_sites_allocate_nothing():
    """The hooks this layer grew since (the exec.* children, complete(),
    the lane's idle span) are the same shared no-op when off: no span
    object, no event, no profiler annotation, no listener."""
    import tracemalloc
    assert not TRACER.enabled and not TRACER._xla_listening
    for _ in range(100):            # warm every code path first
        with TRACER.span("exec.wait", cat="device"):
            pass
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(2000):
        for name in ("exec.args", "exec.wait", "exec.fetch",
                     "service/lane_idle", "frontdoor/reply"):
            with TRACER.span(name, cat="device") as sp:
                assert sp is NULL_SPAN
        TRACER.complete("xla.compile", 1.0, 2.0, cat="xla", label="f")
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(d.size_diff for d in after.compare_to(before, "filename")
                if "obs/trace.py" in str(d.traceback))
    assert grown < 1024, f"disabled hooks allocated {grown} bytes"
    assert TRACER.events() == []


def test_tracer_never_imports_jax():
    """A process that has not imported jax (the front door's clients) gets
    spans and no annotations; obs/trace.py and obs/metrics.py import no
    jax, on or off."""
    code = (
        "import sys\n"
        "from nds_tpu.obs.trace import TRACER\n"
        "from nds_tpu.obs import metrics\n"
        "with TRACER.span('off'): pass\n"
        "TRACER.configure(enabled=True)\n"
        "with TRACER.span('on', label='x'): pass\n"
        "TRACER.complete('xla.compile', 1.0, 2.0)\n"
        "assert [e['name'] for e in TRACER.events()] == "
        "['on', 'xla.compile']\n"
        "assert not TRACER._xla_listening\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr


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
    # the clock anchor first (a metadata event), then the three spans
    assert lines[0]["ph"] == "M" and lines[0]["name"] == "clock"
    assert set(lines[0]["args"]) >= {"epoch_perf_counter_s", "epoch_unix_s"}
    assert [ln["name"] for ln in lines[1:]] == ["b", "a", "a"]
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
             "spans": {"exec": {"count": 3, "total_ms": 30.0,
                                "max_ms": 12.0}},
             "metrics": {"queries_run": 3}}
    bpath = tmp_path / "bench.json"
    bpath.write_text(json.dumps(bench))
    out = subprocess.run([sys.executable, script, str(bpath)],
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr
    assert "exec" in out.stdout
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


# -- the program's names on the device side -----------------------------------

from nds_tpu.engine.jax_backend.executor import program_name  # noqa: E402

HOISTED = ("SELECT k, COUNT(*) AS c, SUM(v) AS sv FROM t WHERE v > {lit} "
           "GROUP BY k ORDER BY k")


@pytest.mark.parametrize("label,fp,want", [
    ("query9/root", None, "nds_query9_root"),
    ("query9", None, "nds_query9_root"),
    ("query9/seg:3fa91c02", None, "nds_query9_seg_3fa91c02"),
    ("query9/morsel:store_sales#1", None, "nds_query9_morsel_store_sales_1"),
    ("q1a2b3c4d/root", "0123456789abcdef", "nds_plan0123456789ab_root"),
    ("q1a2b3c4d/morsel:t", "0123456789abcdef", "nds_plan0123456789ab_morsel_t"),
])
def test_program_name_from_label_or_fingerprint(label, fp, want):
    assert program_name(label, fp) == want


def test_program_name_charset_and_length():
    name = program_name("weird label: SELECT * FROM t/" + "x" * 200)
    assert re.fullmatch(r"[A-Za-z0-9_]{1,64}", name)
    assert name == program_name("weird label: SELECT * FROM t/" + "x" * 200)
    assert name != program_name("weird label: SELECT * FROM t/" + "x" * 201)
    assert program_name("peak of the \u00e9t\u00e9/root") == \
        "nds_peak_of_the_t_root"


def _module_names(session) -> list:
    return sorted(ent["cq"].module_name
                  for ent in session._jax_executor()._plans.values()
                  if isinstance(ent, dict) and ent.get("cq") is not None)


def _compiled(session, sql, label=None) -> list:
    for _ in range(2):          # record, then compile+run
        session.sql(sql, backend="jax", label=label)
    return _module_names(session)


def test_module_name_is_the_same_in_every_process():
    """An unlabelled statement is named from its parameterized plan's
    fingerprint: the same name from two fresh executors, from a child
    process, and for two literals of the one hoisted program (a name from
    the SQL text's hash would compile once per literal)."""
    from nds_tpu.engine.jax_backend.executor import clear_shared_programs
    a = _compiled(make_session(), HOISTED.format(lit=10))
    clear_shared_programs()
    b = _compiled(make_session(), HOISTED.format(lit=10))
    clear_shared_programs()
    c = _compiled(make_session(), HOISTED.format(lit=500))
    assert a == b == c and len(a) == 1
    assert re.fullmatch(r"nds_plan[0-9a-f]{12}_root", a[0]), a
    code = (
        "import sys; sys.path.insert(0, 'tests')\n"
        "import conftest, test_obs as t\n"
        "print('NAMES', t._compiled(t.make_session(), "
        "t.HOISTED.format(lit=77)))\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    line = next(ln for ln in out.stdout.splitlines()
                if ln.startswith("NAMES"))
    assert line == f"NAMES {a}"
    # a label the caller gave is the name, whatever the literal
    clear_shared_programs()
    assert _compiled(make_session(), HOISTED.format(lit=10),
                     label="query9") == ["nds_query9_root"]


def _lowered(session):
    je = session._jax_executor()
    ent = next(e for e in je._plans.values()
               if isinstance(e, dict) and e.get("cq") is not None)
    cq = ent["cq"]
    return cq._fn.lower(*cq._args(je._scans_for(ent),
                                  ent.get("params", ())))


def test_replayed_plan_is_named_and_scoped():
    s = make_session()
    _compiled(s, QUERY, label="obs_q")
    lowered = _lowered(s)
    assert lowered.as_text().startswith("module @jit_nds_obs_q_root ")
    ops = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    scoped = [o for o in ops if o.startswith("jit(nds_obs_q_root)/")]
    # the plan node (verify.node_labels' TypeName#k) and the kernel
    assert any(re.search(r"/AggregateNode#\d+/", o) for o in scoped), ops
    assert any(re.search(r"/SortNode#\d+/(\w+/)*sort_perm/", o)
               for o in scoped), scoped


def test_scopes_change_no_instruction(monkeypatch):
    """Scopes are debug locations: with debug info stripped (what JAX's
    compile-cache key hashes) the lowered program is the same text with
    and without them."""
    import jax
    from nds_tpu.engine.jax_backend.executor import clear_shared_programs
    s = make_session()
    _compiled(s, QUERY, label="obs_q")
    with_scopes = _lowered(s)
    assert "AggregateNode#" in with_scopes.as_text(debug_info=True)
    clear_shared_programs()
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    s2 = make_session()
    _compiled(s2, QUERY, label="obs_q")
    without = _lowered(s2)
    assert "AggregateNode#" not in without.as_text(debug_info=True)
    assert with_scopes.as_text() == without.as_text()


def test_exec_children_lie_inside_exec_and_cover_it():
    TRACER.configure(enabled=True)
    s = make_session()
    for _ in range(4):
        s.sql(QUERY, backend="jax", label="obs_q")
    events = TRACER.events()
    execs = [e for e in events if e["name"] == "exec"]
    assert len(execs) == 3
    for ex in execs:
        kids = [e for e in events if e.get("parent") == ex["sid"]
                and e["name"].startswith("exec.")]
        assert [k["name"] for k in kids] == ["exec.args", "exec.wait",
                                             "exec.fetch"]
        for k in kids:
            assert k["ts"] >= ex["ts"]
            assert k["ts"] + k["dur"] <= ex["ts"] + ex["dur"] + 0.2
        assert sum(k["dur"] for k in kids) >= 0.95 * ex["dur"], (ex, kids)


# -- the tracer on the profiler's clock ---------------------------------------

def test_spans_are_host_events_of_a_profile_within_a_millisecond(tmp_path):
    """A CPU jax.profiler trace over a traced query: every mirrored span is
    an ``nds.*`` host event whose interval agrees with the tracer's own
    ``ts``/``dur`` after the recorded anchor."""
    import jax
    from nds_tpu.obs import xplane
    s = make_session()
    for _ in range(2):
        s.sql(QUERY, backend="jax", label="prof_q")
    TRACER.configure(enabled=True)
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        for _ in range(3):
            s.sql(QUERY, backend="jax", label="prof_q")
        with TRACER.span("detached.parent") as parent:
            sp = TRACER.span("detached", parent=parent.sid).begin()
            sp.end()
    finally:
        jax.profiler.stop_trace()
    trace = xplane.read(glob.glob(
        str(tmp_path / "prof" / "plugins" / "profile" / "*" /
            "*.xplane.pb"))[0])
    names = {n for _s, _e, n, _thread in trace["spans"]}
    assert {"nds.query:prof_q", "nds.exec:prof_q", "nds.exec.wait",
            "nds.exec.fetch", "nds.detached.parent"} <= names, names
    assert "nds.detached" not in names      # begin()/end() is not mirrored
    mirrored = [e for e in TRACER.events()       # live context-manager spans
                if e["name"] != "detached" and e["cat"] != "xla"]
    assert len(trace["spans"]) == len(mirrored)
    check = xplane.clock_check(trace, TRACER.events(), TRACER.clock())
    assert check["matched"] == len(trace["spans"])
    assert check["max_start_ms"] < 1.0 and check["max_dur_ms"] < 1.0, check
    # the same through the operator's tool, from the exported files
    chrome = TRACER.write_chrome_trace(str(tmp_path / "t.json"))
    assert json.load(open(chrome))["clock"] == TRACER.clock()
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_report.py"),
         "--xplane", glob.glob(str(tmp_path / "prof" / "plugins" /
                                   "profile" / "*" / "*.xplane.pb"))[0],
         chrome], capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "device time by program" in out.stdout
    assert "'matched': %d" % len(trace["spans"]) in out.stdout


def test_complete_places_a_finished_span_through_the_anchor():
    TRACER.configure(enabled=True)
    with TRACER.span("outer") as outer:
        t0 = time.time()
        time.sleep(0.002)
        TRACER.complete("done", t0, t0 + 0.001, cat="xla", label="f")
    done = next(e for e in TRACER.events() if e["name"] == "done")
    out = next(e for e in TRACER.events() if e["name"] == "outer")
    assert done["parent"] == outer.sid and done["args"] == {"label": "f"}
    assert abs(done["dur"] - 1000.0) < 1.0
    assert out["ts"] - 1000.0 <= done["ts"] <= out["ts"] + out["dur"]


# -- XLA's own events ---------------------------------------------------------

def test_xla_events_become_spans_and_counters():
    import jax
    import jax.numpy as jnp
    from jax import monitoring
    from jax._src.monitoring import \
        get_event_time_span_listeners as listeners
    for _ in range(3):              # however often: one listener
        TRACER.configure(enabled=True)
    assert listeners().count(TRACER._on_xla_span) == 1
    om.install_xla_counters()
    om.install_xla_counters()
    before = om.METRICS.snapshot()

    def fresh(x):                   # a program no cache has seen traced
        return jnp.cumsum(x * 3.0 + float(time.time_ns() % 997))
    fresh.__name__ = "nds_test_fresh"
    jax.jit(fresh)(jnp.arange(16.0)).block_until_ready()
    d = om.METRICS.delta(before)
    assert d.get("xla_compiles", 0) >= 1
    assert d.get("xla_cache_hits", 0) + d.get("xla_cache_misses", 0) >= 1
    by_name = {}
    for e in TRACER.events():
        by_name.setdefault(e["name"], []).append(e)
    for name in ("xla.trace", "xla.lower", "xla.compile"):
        mine = [e for e in by_name.get(name, [])
                if "nds_test_fresh" in e["args"]["label"]]
        assert mine and all(e["dur"] >= 0 and e["cat"] == "xla"
                            for e in mine), (name, by_name.keys())
    # each cache event counts once (one listener however often installed)
    before = om.METRICS.snapshot()
    monitoring.record_event("/jax/compilation_cache/cache_hits")
    monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert om.METRICS.delta(before) == {"xla_cache_hits": 1,
                                        "xla_cache_misses": 1}
    # off: the span listener is gone, the counters stay
    TRACER.configure(enabled=False)
    assert listeners().count(TRACER._on_xla_span) == 0
    before = om.METRICS.snapshot()
    jax.jit(lambda x: x - 41.5)(jnp.arange(4.0)).block_until_ready()
    assert om.METRICS.delta(before).get("xla_compiles", 0) >= 1
    assert TRACER.events() == []


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


def test_streaming_path_plans_under_a_plan_span_once(tmp_path):
    """What the streamed cell's ``plan_s`` reads: the morsel path's own
    parse and plan, in the first statement and in no warm one."""
    import pyarrow.parquet as pq
    rng = np.random.default_rng(3)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, 50, 30_000), type=pa.int32()),
        "v": pa.array(rng.integers(0, 100, 30_000), type=pa.int64())})
    path = os.path.join(str(tmp_path), "fact.parquet")
    pq.write_table(fact, path, row_group_size=4096)
    TRACER.configure(enabled=True)
    s = Session(EngineConfig(chunk_rows=4096, out_of_core_min_rows=10_000))
    s.register_parquet("fact", path)
    for _ in range(2):
        s.sql("SELECT fk, SUM(v) FROM fact GROUP BY fk", backend="jax",
              label="obs_stream")
        assert s.last_exec_stats["mode"] == "streaming"
    events = [e for e in TRACER.events() if e.get("ph") == "X"]
    plans = [e for e in events if e["name"] == "plan"]
    assert len(plans) == 1 and plans[0]["args"]["label"] == "obs_stream"
    parse = [e for e in events if e["name"] == "parse"]
    assert len(parse) == 1 and parse[0]["parent"] == plans[0]["sid"]
    by_sid = {e["sid"]: e for e in events}
    assert by_sid[plans[0]["parent"]]["name"] == "query"


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


def test_bytes_fetched_counts_what_a_dispatch_returns(monkeypatch):
    """bytes_fetched moves by the nbytes of what a program dispatch's
    device_get returned (result and check scalars), and by exactly zero
    where no program is dispatched: the host backend, the record pass."""
    import jax
    from nds_tpu.engine.jax_backend.executor import CompiledQuery
    inside, returned, outputs = [], [], []
    device_get, run = jax.device_get, CompiledQuery.run

    def spy_get(x):
        out = device_get(x)
        if inside:
            returned.append(sum(leaf.nbytes
                                for leaf in jax.tree_util.tree_leaves(out)))
        return out

    def spy_run(self, *args, **kw):
        inside.append(self)
        try:
            outputs.append(run(self, *args, **kw))
            return outputs[-1]
        finally:
            inside.pop()

    monkeypatch.setattr(jax, "device_get", spy_get)
    monkeypatch.setattr(CompiledQuery, "run", spy_run)
    s = make_session()
    before = om.BYTES_FETCHED.value
    s.sql(QUERY, backend="numpy")
    s.sql(QUERY, backend="jax")                 # the record pass
    assert om.BYTES_FETCHED.value == before and returned == []
    for dispatch in (1, 2):
        out = s.sql(QUERY, backend="jax")
        assert len(returned) == dispatch
        assert om.BYTES_FETCHED.value - before == sum(returned)
    # the output table (7 groups in bucket(7) = 8 rows) and one i32 check
    # scalar per decision of the schedule
    cq = s._jax_exec._plans[("sql", QUERY)]["cq"]
    assert out.num_rows == 7 and outputs[-1].capacity == 8
    assert returned[-1] == 4 * len(cq.decisions) + sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(outputs[-1]))
    assert "bytes_fetched" in om.METRICS.describe()


# -- the plan shapes a dispatched program holds (ISSUE 32) ---------------------

SHAPE_COUNTERS = ("window_nodes", "rollup_sets", "setop_nodes", "outer_joins",
                  "star_joins")


@pytest.mark.parametrize("sql,want", [
    ("SELECT k, v, RANK() OVER (PARTITION BY k ORDER BY v) AS r FROM t "
     "WHERE v < 50 ORDER BY k, v", (1, 0, 0, 0, 0)),
    ("SELECT k, SUM(v) AS sv FROM t GROUP BY ROLLUP (k) ORDER BY k",
     (0, 2, 0, 0, 0)),
    ("SELECT k FROM t WHERE v < 100 INTERSECT SELECT k FROM t WHERE v > 900 "
     "UNION ALL SELECT k FROM t WHERE v = 500", (0, 0, 2, 0, 0)),
    ("SELECT t.k, u.w FROM t LEFT OUTER JOIN u ON t.k = u.k WHERE t.v < 20 "
     "ORDER BY 1, 2", (0, 0, 0, 1, 0)),
    ("SELECT t.k, COUNT(*) AS c FROM t, u WHERE t.k = u.k GROUP BY t.k "
     "ORDER BY 1", (0, 0, 0, 0, 0)),
], ids=["window", "rollup", "setops", "outer_join", "none"])
def test_plan_shape_counters_move_by_the_programs_static_counts(sql, want):
    """window_nodes / rollup_sets / setop_nodes / outer_joins / star_joins
    move at each dispatch of a compiled program by what its plan holds, and
    by nothing where no program is dispatched: the host backend, the record
    pass."""
    s = make_session()
    s.register_arrow("u", pa.table({
        "k": pa.array([0, 1, 2, 9], type=pa.int32()),
        "w": pa.array([10, 11, 12, 19], type=pa.int64())}))

    def moved(before):
        d = om.METRICS.delta(before)
        return tuple(d.get(name, 0) for name in SHAPE_COUNTERS)

    before = om.METRICS.snapshot()
    oracle = s.sql(sql, backend="numpy").to_pylist()
    s.sql(sql, backend="jax")                   # the record pass
    assert moved(before) == (0,) * len(SHAPE_COUNTERS)
    for dispatch in (1, 2):
        got = s.sql(sql, backend="jax")
        assert s.last_exec_stats["mode"] in ("compiled", "compile+run")
        assert moved(before) == tuple(dispatch * n for n in want)
    assert got.to_pylist() == oracle
    cq = s._jax_exec._plans[("sql", sql)]["cq"]
    assert cq.plan_shapes == want
    for name in SHAPE_COUNTERS:
        assert name in om.METRICS.describe()
