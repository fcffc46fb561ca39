"""Multi-chip sharded morsel execution (EngineConfig.mesh_shards).

Every streamed scan group's morsels partition across data-parallel
replicas of the device mesh: one row-sharded packed upload per morsel,
the same compiled per-morsel program replayed per replica via shard_map,
and ONE all_gather of the decomposed partials before the unchanged
host-side merge (engine/jax_backend/shard_exec.py). The conftest forces
an 8-virtual-device CPU mesh, so these tests exercise the real shard_map
programs + collectives without a TPU slice.

Contracts pinned here:
- BIT-IDENTICAL results at mesh_shards in {1, 2, 4, 8} vs the single-chip
  path (integer/decimal partials are order-independent — the exact-decimal
  measured configuration), including the skewed case where the last morsel
  holds fewer rows than the shard count (whole replicas all-dead);
- mesh_shards unset/1 leaves the single-chip path untouched (no mesh
  stats, no sharded programs);
- a second sighting's programs are sized from the max over replicas and
  morsels of the first whole pass's checks (tight_morsel_replays), and the
  gathered partials shrink with them;
- collective accounting (collective_bytes / collective_ms, the
  `collective_bytes` counter, a `collective` span that ends before the
  fetch begins), a morsel that overflows the sharded schedule re-recorded
  and counted (`morsel_re_records`, `replay_mismatches`), and per-shard
  device-time attribution labels ("<q>/morsel:<t>@mesh<n>" /
  "<q>/gather:<t>@mesh<n>") are observable;
- independent SQLite oracle agreement for the sharded path.
"""
import sqlite3

import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session

N_FACT, N_DIM = 30_000, 200
CHUNK = 4_096

STAR = ("SELECT d.grp, COUNT(*) AS c, SUM(f.qty) AS sq, MIN(f.amt) AS lo, "
        "MAX(f.amt) AS hi, AVG(f.qty) AS aq, MAX(f.price) AS mp "
        "FROM fact f JOIN dim d ON f.fk = d.dk "
        "WHERE f.day BETWEEN 10 AND 300 GROUP BY d.grp ORDER BY d.grp")

# q9-class: several scalar-subquery aggregates over the same big table —
# one shared-scan group, multiple members, fused multi-output program
SUBQ = ("SELECT (SELECT COUNT(*) FROM fact WHERE day < 100) AS a, "
        "(SELECT SUM(qty) FROM fact WHERE day >= 100) AS b, "
        "(SELECT MAX(amt) FROM fact WHERE day < 200) AS m "
        "FROM dim WHERE dk = 0")

# q10-class: semi join whose BUILD side holds the big scan (synthesized
# distinct-key aggregate streams, join patched to the materialized keys)
SEMI = ("SELECT d.grp, COUNT(*) AS c FROM dim d "
        "WHERE EXISTS (SELECT 1 FROM fact f WHERE f.fk = d.dk "
        "AND f.day < 50) GROUP BY d.grp ORDER BY d.grp")


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(11)
    qty = rng.integers(1, 50, N_FACT).astype(object)
    qty[rng.random(N_FACT) < 0.05] = None        # NULLs: sum_guarded merge
    fact = pa.table({
        "fk": pa.array(rng.integers(0, N_DIM + 9, N_FACT),
                       type=pa.int32()),
        "qty": pa.array(list(qty), type=pa.int32()),
        "amt": pa.array(rng.integers(100, 100000, N_FACT)
                        .astype(np.int64)),
        "price": pa.array(np.round(rng.uniform(1, 100, N_FACT), 2)),
        "day": pa.array(rng.integers(0, 365, N_FACT), type=pa.int32()),
    })
    dim = pa.table({"dk": pa.array(np.arange(N_DIM), type=pa.int32()),
                    "grp": pa.array((np.arange(N_DIM) % 13)
                                    .astype(np.int32))})
    return {"fact": fact, "dim": dim}


def make_session(data, mesh_shards=0, chunk=CHUNK, fact=None, **cfg):
    config = EngineConfig(out_of_core=True, chunk_rows=chunk,
                          out_of_core_min_rows=10_000,
                          mesh_shards=mesh_shards, **cfg)
    s = Session(config)
    s.register_arrow("fact", fact if fact is not None else data["fact"])
    s.register_arrow("dim", data["dim"])
    return s


def run(data, sql, mesh_shards=0, label=None, **kw):
    s = make_session(data, mesh_shards=mesh_shards, **kw)
    t = s.sql(sql, backend="jax",
              label=label or f"mesh{mesh_shards}")
    return t, dict(s.last_exec_stats)


def rows_of(t):
    return sorted(tuple(r) for r in t.to_pylist())


@pytest.fixture(scope="module")
def baseline(data):
    out = {}
    for key, sql in (("star", STAR), ("subq", SUBQ), ("semi", SEMI)):
        t, st = run(data, sql, mesh_shards=0, label=f"base_{key}")
        assert st.get("mode") == "streaming", (key, st.get("mode"))
        assert "mesh_shards" not in st
        out[key] = rows_of(t)
    return out


@pytest.mark.parametrize("n", [1, 2, 4, 8])
def test_star_bit_identity_across_shard_counts(data, baseline, n):
    t, st = run(data, STAR, mesh_shards=n, label=f"star{n}")
    assert rows_of(t) == baseline["star"]
    assert st["mode"] == "streaming"
    if n <= 1:
        # 1/unset = the single-chip path exactly: no mesh stats recorded
        assert "mesh_shards" not in st
        assert "collective_bytes" not in st
    else:
        assert st["mesh_shards"] == n
        assert st["sharded_groups"] == 1
        assert st["collective_bytes"] > 0
        assert st["collective_ms"] >= 0
        assert st.get("re_records", 0) == 0


def test_fused_multi_member_group_shards(data, baseline):
    """q9-class scalar-subquery battery: one shared-scan group, several
    member plans, ONE fused sharded multi-output program per morsel."""
    t, st = run(data, SUBQ, mesh_shards=8, label="subq8")
    assert rows_of(t) == baseline["subq"]
    assert st["mesh_shards"] == 8
    assert st["fused_groups"] == 1
    assert st["branches_served"] >= 2


def test_semi_join_build_side_shards(data, baseline):
    t, st = run(data, SEMI, mesh_shards=8, label="semi8")
    assert rows_of(t) == baseline["semi"]
    assert st["mesh_shards"] == 8


def test_skewed_last_morsel_smaller_than_shard_count(data):
    """Last morsel holds 3 rows < 8 shards: trailing replicas see
    all-dead blocks; results stay bit-identical."""
    n_rows = 3 * CHUNK + 3
    fact = data["fact"].slice(0, n_rows)
    base, st0 = run(data, STAR, mesh_shards=0, fact=fact, label="skew0")
    assert st0["mode"] == "streaming" and st0["morsels"] == 4
    t, st = run(data, STAR, mesh_shards=8, fact=fact, label="skew8")
    assert rows_of(t) == rows_of(base)
    assert st["mesh_shards"] == 8
    assert st.get("re_records", 0) == 0


def test_unfused_groups_shard(data, baseline):
    """Fusion budget exceeded: per-member sharded programs over the same
    row-sharded staged buffer."""
    t, st = run(data, SUBQ, mesh_shards=8,
                stream_fusion_max_branches=1, label="subq8uf")
    assert rows_of(t) == baseline["subq"]
    assert st["mesh_shards"] == 8
    assert st["fused_groups"] == 0


def test_wide_layout_shards(data, baseline):
    """--no_narrow_lanes: the wide packed layout also uploads row-sharded
    (or falls back to the per-leaf sharded DTable) bit-identically."""
    t, st = run(data, STAR, mesh_shards=4, narrow_lanes=False,
                label="star4wide")
    assert rows_of(t) == baseline["star"]
    assert st["mesh_shards"] == 4


def test_device_time_attribution_labels(data, baseline):
    """The sharded morsel's two programs under their own names: the spans'
    labels on the host side, the HLO modules on the device trace's."""
    from nds_tpu.obs.trace import TRACER
    TRACER.configure(enabled=True)
    try:
        run(data, STAR, mesh_shards=8, label="attr")
        events = TRACER.events()
    finally:
        TRACER.configure(enabled=False)
    labels = {e["name"]: e["args"]["label"] for e in events
              if e["name"] in ("exec", "collective")}
    assert labels["exec"].startswith("attr/morsel:fact") and \
        labels["exec"].endswith("@mesh8"), labels
    assert labels["collective"].startswith("attr/gather:fact") and \
        labels["collective"].endswith("@mesh8"), labels
    modules = {e["args"]["label"] for e in events
               if e["name"] == "xla.compile"}
    import re
    for half in ("local", "gather"):
        assert any(re.fullmatch(rf"jit\(nds_attr_morsel_fact(_\d+)?_{half}\)",
                                m) for m in modules), modules
    kids = {e["name"] for e in events if e["name"].startswith("exec.")}
    assert kids == {"exec.args", "exec.wait", "exec.fetch"}


def test_an_overflowing_sharded_morsel_is_re_recorded_and_counted(
        data, baseline, monkeypatch):
    """One morsel's checks overflow the sharded schedule: the session
    re-records that morsel eagerly (on one chip), the answer stays what the
    one-chip path gives, and the statement and the registry both say so."""
    from nds_tpu.engine.jax_backend.executor import ReplayMismatch
    from nds_tpu.engine.jax_backend.shard_exec import ShardedMorselQuery
    from nds_tpu.obs.metrics import METRICS
    verify, raised = ShardedMorselQuery._verify, []

    def overflow_once(self, checks_host):
        if not raised:
            raised.append(True)
            raise ReplayMismatch("sharded capacity overflow: forced")
        verify(self, checks_host)
    monkeypatch.setattr(ShardedMorselQuery, "_verify", overflow_once)
    before = METRICS.snapshot()
    t, st = run(data, STAR, mesh_shards=4, label="rerec4")
    moved = METRICS.delta(before)
    assert raised and rows_of(t) == baseline["star"]
    assert st["mesh_shards"] == 4 and st["sharded_groups"] == 1
    assert st["re_records"] == 1
    assert moved["morsel_re_records"] == 1
    assert moved["replay_mismatches"] == 1
    # the re-recorded morsel gathered nothing: the others still did
    assert moved["collective_bytes"] == st["collective_bytes"] > 0


@pytest.mark.parametrize("n", [2, 4])
def test_a_second_sighting_is_sized_from_every_replicas_checks(
        data, baseline, n, monkeypatch):
    """The first sighting's programs hold every cap at the replica's bound;
    the second's hold the largest value any replica of any morsel (or the
    record pass) saw, so the gathered partials shrink and nothing
    overflows. Rows stay the one-chip path's, bit for bit."""
    from nds_tpu.engine.jax_backend.device import bucket
    from nds_tpu.engine.jax_backend.shard_exec import ShardedMorselQuery
    from nds_tpu.engine.streaming import shard_capacity
    from nds_tpu.obs.metrics import METRICS
    verify, seen = ShardedMorselQuery._verify, []

    def spy(self, checks_host):
        verify(self, checks_host)
        seen.append([np.asarray(a).copy() for a in checks_host])
    monkeypatch.setattr(ShardedMorselQuery, "_verify", spy)
    s = make_session(data, mesh_shards=n)
    morsels = -(-N_FACT // CHUNK)

    def sight():
        before = METRICS.snapshot()
        t = s.sql(STAR, backend="jax", label=f"tight{n}")
        assert rows_of(t) == baseline["star"]
        (state,) = s._stream_cache[STAR]["gstates"]
        return METRICS.delta(before), dict(s.last_exec_stats), state

    first, st1, state = sight()
    assert len(seen) == morsels and st1["mesh_shards"] == n
    assert first.get("tight_morsel_replays", 0) == 0
    (cq,), (raw,) = state["cqs"], state["raw"]
    assert state["tight"] is True
    want = [max(int(v), max(int(d[i].max()) for d in seen))
            for i, (_k, v) in enumerate(raw)]
    caps = [(v, w) for (k, v), w in zip(cq.decisions, want) if k == "cap"]
    assert caps and all(v == w for v, w in caps)
    assert all(v <= shard_capacity(CHUNK, n) for v, _w in caps)
    assert bucket(caps[-1][0]) == bucket(13)        # the 13 groups of STAR
    # replicas differ, so one replica's checks alone would not have done
    assert any(int(d[i].min()) < int(d[i].max())
               for d in seen for i in range(len(raw)))

    second, st2, state2 = sight()
    assert state2["cqs"] == [cq]
    assert second["tight_morsel_replays"] == morsels
    assert second.get("morsel_re_records", 0) == 0
    assert st2.get("re_records", 0) == 0 and st2["mesh_shards"] == n
    assert second["compiles"] == 2              # local + gather, once
    assert st2["collective_bytes"] * 10 < st1["collective_bytes"]
    assert second["collective_bytes"] == st2["collective_bytes"]
    assert second["bytes_fetched"] < first["bytes_fetched"]
    third, _st3, _state3 = sight()
    assert third.get("compiles", 0) == 0
    assert third["tight_morsel_replays"] == morsels


# query9's shape over the streamed table: every member a keyless count / sum /
# avg over one `between` filter. On one chip such a filter carries its mask
# instead of compacting (JaxExecutor._maybe_compact); a replica never
# compacted, so the rule takes nothing out there and its counter stays.
MASKQ = ("SELECT (SELECT COUNT(*) FROM fact WHERE day BETWEEN 0 AND 60) AS a, "
         "(SELECT AVG(amt) FROM fact WHERE day BETWEEN 0 AND 60) AS b, "
         "(SELECT SUM(qty) FROM fact WHERE day BETWEEN 61 AND 120) AS c "
         "FROM dim WHERE dk = 0")


def _mask_sightings(data, n: int, label: str):
    """Two sightings of MASKQ at `n` shards: rows, the group's schedule
    shapes (first sighting's, tight) and what mask_carried_filters moved."""
    from nds_tpu.engine.streaming import schedule_shape
    from nds_tpu.obs.metrics import METRICS
    s = make_session(data, mesh_shards=n)
    before = METRICS.snapshot()
    rows, shapes = [], []
    for _ in range(2):
        rows.append(rows_of(s.sql(MASKQ, backend="jax", label=label)))
        st = dict(s.last_exec_stats)
        assert st["mode"] == "streaming" and st.get("re_records", 0) == 0
        assert st.get("mesh_shards", 1) == max(n, 1)
        (state,) = s._stream_cache[MASKQ]["gstates"]
        shapes.append([schedule_shape(cq.decisions) for cq in state["cqs"]])
    assert state["tight"] is True and rows[0] == rows[1]
    moved = METRICS.delta(before)
    assert moved.get("morsel_re_records", 0) == 0
    return rows[0], shapes, moved.get("mask_carried_filters", 0)


@pytest.mark.parametrize("n", [2, 4])
def test_a_replica_never_compacted_so_the_mask_rule_takes_nothing_out(
        data, n, monkeypatch):
    """Rows bit-identical to the one-chip run, whose three exempt filters a
    morsel move the counter; at `n` shards it stays 0, and the schedule has
    the shape it has with the rule held off, position for position."""
    from nds_tpu.engine.jax_backend import executor as X
    morsels = -(-N_FACT // CHUNK)
    one_rows, one_shapes, one_moved = _mask_sightings(data, 0, f"mask1_{n}")
    assert one_moved == 2 * 3 * morsels
    rows, shapes, moved = _mask_sightings(data, n, f"mask{n}")
    assert rows == one_rows and moved == 0
    # the same decisions in the same positions as on one chip
    assert [[k for k, _v in sh] for sh in shapes[0]] == \
        [[k for k, _v in sh] for sh in one_shapes[0]]
    X.clear_shared_programs()
    monkeypatch.setattr(X, "_mask_carrying_filters", lambda plan: frozenset())
    off_rows, off_shapes, off_moved = _mask_sightings(data, n, f"mask{n}")
    assert off_rows == rows and off_shapes == shapes and off_moved == 0
    one_off = _mask_sightings(data, 0, f"mask1_{n}")
    assert one_off == (one_rows, one_shapes, 0)


def test_the_collective_span_ends_before_the_fetch_begins(data, baseline):
    """Tracer on: every `collective` span covers the gather program alone
    and the gathered partials' copy to the host is the `exec.fetch` after
    it; the `collective_bytes` counter moves by what the statement says."""
    from nds_tpu.obs.metrics import METRICS
    from nds_tpu.obs.trace import TRACER
    before = METRICS.snapshot()
    TRACER.configure(enabled=True)
    try:
        t, st = run(data, STAR, mesh_shards=4, label="parted4")
        events = TRACER.events()
    finally:
        TRACER.configure(enabled=False)
    assert rows_of(t) == baseline["star"]
    assert METRICS.delta(before)["collective_bytes"] == \
        st["collective_bytes"] > 0
    spans = sorted((e for e in events if e.get("ph") == "X" and
                    e["name"] in ("collective", "exec.fetch")),
                   key=lambda e: e["ts"])
    gathers = [i for i, e in enumerate(spans) if e["name"] == "collective"]
    assert len(gathers) == st["morsels"]
    for i in gathers:
        # exec.fetch (the checks), collective, exec.fetch (the partials)
        assert spans[i - 1]["name"] == spans[i + 1]["name"] == "exec.fetch"
        assert spans[i]["ts"] + spans[i]["dur"] <= spans[i + 1]["ts"]
        assert spans[i]["args"]["bytes"] > 0


def test_sharded_vs_sqlite_oracle(data):
    """Independent-oracle agreement for the sharded path (own parser,
    planner, executor — catches shared-frontend bugs the single-vs-sharded
    differential cannot)."""
    conn = sqlite3.connect(":memory:")
    for name, t in (("fact", data["fact"]), ("dim", data["dim"])):
        cols = ", ".join(f'"{c}"' for c in t.column_names)
        conn.execute(f"CREATE TABLE {name} ({cols})")
        rows = list(zip(*[t.column(c).to_pylist()
                          for c in t.column_names]))
        conn.executemany(
            f"INSERT INTO {name} VALUES "
            f"({','.join('?' * len(t.column_names))})", rows)
    conn.commit()
    got, st = run(data, STAR, mesh_shards=8, label="oracle8")
    assert st["mesh_shards"] == 8
    want = sorted(tuple(r) for r in conn.execute(STAR).fetchall())
    got_rows = []
    for r in rows_of(got):
        got_rows.append(tuple(
            float(v) if hasattr(v, "as_tuple") else v for v in r))
    for g, w in zip(got_rows, want):
        assert len(g) == len(w)
        for gv, wv in zip(g, w):
            if isinstance(gv, float) or isinstance(wv, float):
                assert gv == pytest.approx(wv, rel=1e-9)
            else:
                assert gv == wv
    assert len(got_rows) == len(want)


def test_stream_cache_keys_on_shard_count(data):
    """Toggling mesh_shards on a live session must not replay cached
    single-chip streaming state (stream-cache key includes the count)."""
    s = make_session(data, mesh_shards=0)
    t0 = s.sql(STAR, backend="jax", label="toggle")
    assert "mesh_shards" not in s.last_exec_stats
    s.config.mesh_shards = 8
    t1 = s.sql(STAR, backend="jax", label="toggle")
    assert s.last_exec_stats.get("mesh_shards") == 8
    assert rows_of(t0) == rows_of(t1)
    s.config.mesh_shards = 0
    t2 = s.sql(STAR, backend="jax", label="toggle")
    assert "mesh_shards" not in s.last_exec_stats
    assert rows_of(t2) == rows_of(t0)


@pytest.mark.slow
def test_sf001_nds_queries_sharded_vs_single(tmp_path_factory):
    """Real NDS templates at SF0.01 on the 8-virtual-device mesh: the
    bench-slice queries must be bit-identical sharded vs single-chip in
    the measured EXACT-decimal configuration (integer partials merge
    order-independently; f64 decimals would reassociate sums), and agree
    with the independent SQLite oracle under the validator's epsilon
    policy. GSPMD-compile-heavy (slow marker: runs in the full CI test
    stage)."""
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(__file__).parent))
    from sqlite_oracle import load_database, normalize_rows, sort_rows, \
        to_sqlite_sql

    from nds_tpu import datagen, streams, validate
    from nds_tpu.engine import arrow_bridge
    from nds_tpu.power import setup_tables

    data_dir = str(tmp_path_factory.mktemp("mesh_sf001") / "d")
    datagen.generate_data_local(data_dir, 0.01, parallel=2, overwrite=True)
    conn = load_database(data_dir)

    def session_for(n):
        # csv registration estimates every table at 10k rows, so the
        # threshold goes under that: single-big-scan plans (query9's
        # store_sales-only scalar-subquery branches) then stream and
        # shard; multi-big-scan joins stay in-core — recorded per query
        cfg = EngineConfig(out_of_core=True, chunk_rows=8192,
                           out_of_core_min_rows=5_000, mesh_shards=n,
                           decimal_physical="i64")
        s = Session(cfg)
        setup_tables(s, data_dir, "csv")
        return s

    single, sharded = session_for(0), session_for(8)
    streamed_sharded = 0
    for number in (3, 7, 9):
        sql = streams.instantiate(number, stream=0, rngseed=31415)
        name = f"query{number}"
        t0 = single.sql(sql, backend="jax", label=name)
        t1 = sharded.sql(sql, backend="jax", label=name)
        st = dict(sharded.last_exec_stats)
        if st.get("mesh_shards"):
            streamed_sharded += 1
        # csv registration loads decimals as f64 (arrow_schema(use_decimal
        # =False)), so float sums reassociate across partial granularities
        # — compare floats at ULP-scale tolerance here; STRICT bit-identity
        # is pinned by the fast synthetic tests above and by the bench's
        # mesh scaling run over the exact-decimal parquet warehouse
        r0 = sort_rows(normalize_rows([tuple(r) for r in t0.to_pylist()]))
        r1 = sort_rows(normalize_rows([tuple(r) for r in t1.to_pylist()]))
        assert len(r0) == len(r1), f"{name}: sharded row count drifted"
        for a, b in zip(r0, r1):
            assert len(a) == len(b)
            for va, vb in zip(a, b):
                if isinstance(va, float) and isinstance(vb, float):
                    assert va == pytest.approx(vb, rel=1e-12, abs=1e-9), \
                        f"{name} drifted sharded: {a} != {b}"
                else:
                    assert va == vb, f"{name} drifted sharded: {a} != {b}"
        want = sort_rows(normalize_rows(
            conn.execute(to_sqlite_sql(sql)).fetchall()))
        at = arrow_bridge.to_arrow(t1)
        got = sort_rows(normalize_rows(list(zip(
            *[c.to_pylist() for c in at.columns])) if at.num_columns
            else []))
        assert len(got) == len(want), f"{name}: row count vs sqlite"
        for g, w in zip(got, want):
            assert validate.row_equal(w, g, name, list(t1.names)), \
                f"{name}: sqlite {w} != engine {g}"
    # at least one bench-slice query must have actually sharded (query9's
    # scalar-subquery battery streams store_sales at this threshold)
    assert streamed_sharded >= 1


# -- keyless aggregates under the replica mesh --------------------------------
# Their group count is a static 1 in the per-replica schedule too: nothing for
# inflate_schedule(decisions, shard_cap) to raise, bucket(1) partial rows per
# replica on the all_gather instead of shard_cap.

def keyless_sql(where: str) -> str:
    return (f"SELECT (SELECT COUNT(*) FROM fact WHERE {where}) AS c, "
            f"(SELECT SUM(amt) FROM fact WHERE {where}) AS sa, "
            f"(SELECT AVG(qty) FROM fact WHERE {where}) AS aq "
            "FROM dim WHERE dk = 0")


KEYLESS_WHERE = {
    "live": "pos >= 0",
    # the per-replica schedule is recorded on the first morsel's first slice
    "recorded_slice_all_filtered_out": f"pos >= {CHUNK}",
    "empty_input": "pos < 0",
}


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_member"])
@pytest.mark.parametrize("case", sorted(KEYLESS_WHERE))
def test_keyless_aggregates_under_two_shards(data, case, fuse, monkeypatch):
    from nds_tpu.engine.jax_backend.executor import JaxExecutor
    fact = data["fact"].append_column(
        "pos", pa.array(np.arange(N_FACT), type=pa.int32()))
    sql = keyless_sql(KEYLESS_WHERE[case])
    pos = np.arange(N_FACT)
    m = {"live": pos >= 0, "recorded_slice_all_filtered_out": pos >= CHUNK,
         "empty_input": pos < 0}[case]
    qty = np.array([np.nan if q is None else q
                    for q in fact.column("qty").to_pylist()])[m]
    amt = fact.column("amt").to_numpy()[m]
    want = [(int(m.sum()), int(amt.sum()) if m.any() else None,
             float(np.nanmean(qty)) if m.any() else None)]

    cap_nodes = []
    decide_cap = JaxExecutor._decide_cap

    def spy_cap(self, scalar):
        if self._rec is not None and self._rec.mode == "record":
            cap_nodes.append(type(self._cur_node).__name__)
        return decide_cap(self, scalar)

    monkeypatch.setattr(JaxExecutor, "_decide_cap", spy_cap)
    cfg = {} if fuse else {"stream_fusion_max_branches": 1}
    s = make_session(data, mesh_shards=2, fact=fact, **cfg)
    assert rows_of(s.sql(sql, backend="numpy")) == want
    got = s.sql(sql, backend="jax", label=f"keyless2_{case}")
    st = dict(s.last_exec_stats)
    assert rows_of(got) == want
    assert st["mode"] == "streaming" and st["mesh_shards"] == 2
    assert st["fused_groups"] == (1 if fuse else 0)
    assert st.get("re_records", 0) == 0
    assert cap_nodes and "AggregateNode" not in cap_nodes, cap_nodes
    # 3 members of bucket(1) = 8 partial rows a replica, a few columns each:
    # under 1 KB a morsel where shard_cap-sized partials moved over 100 KB
    assert 0 < st["collective_bytes"] < 1024 * st["morsels"]
    single, st0 = run(data, sql, mesh_shards=0, fact=fact,
                      label=f"keyless0_{case}", **cfg)
    assert rows_of(single) == want and "mesh_shards" not in st0
