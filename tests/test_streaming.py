"""Out-of-core morsel streaming (engine/streaming): bounded-memory
aggregation over a large scan, one compiled program for every morsel,
host-merged partials — vs the in-core oracle."""
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.engine.streaming import (adapt_schedule, inflate_schedule,
                                      try_streaming_plan)

N_FACT, N_DIM = 50_000, 300
CHUNK = 4_096  # forces ~13 morsels


def make_session(tmp_path, out_of_core=True):
    rng = np.random.default_rng(5)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, N_DIM + 9, N_FACT), type=pa.int32()),
        "qty": pa.array(rng.integers(1, 50, N_FACT), type=pa.int32()),
        "price": pa.array(np.round(rng.uniform(1, 100, N_FACT), 2)),
        "day": pa.array(rng.integers(0, 365, N_FACT), type=pa.int32()),
    })
    # inject some nulls into qty
    mask = rng.random(N_FACT) < 0.05
    qty = fact.column("qty").to_numpy(zero_copy_only=False).astype(object)
    qty[mask] = None
    fact = fact.set_column(1, "qty", pa.array(list(qty), type=pa.int32()))
    dim = pa.table({"dk": pa.array(np.arange(N_DIM), type=pa.int32()),
                    "grp": pa.array((np.arange(N_DIM) % 13).astype(np.int32))})
    path = os.path.join(str(tmp_path), "fact.parquet")
    pq.write_table(fact, path, row_group_size=8192)
    cfg = EngineConfig(out_of_core=out_of_core, chunk_rows=CHUNK,
                       out_of_core_min_rows=10_000)
    s = Session(cfg)
    s.register_parquet("fact", path)
    s.register_arrow("dim", dim)
    return s


QUERY = """
SELECT d.grp, COUNT(*) AS cnt, COUNT(f.qty) AS cq, SUM(f.qty) AS sq,
       AVG(f.price) AS ap, MIN(f.price) AS lo, MAX(f.price) AS hi
FROM fact f JOIN dim d ON f.fk = d.dk
WHERE f.day < 200
GROUP BY d.grp
ORDER BY d.grp
"""


def rows_of(t):
    return [tuple(round(v, 6) if isinstance(v, float) else v for v in r)
            for r in t.to_pylist()]


def test_streaming_matches_incore(tmp_path):
    s = make_session(tmp_path)
    oracle = s.sql(QUERY, backend="numpy")
    streamed = s.sql(QUERY, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["morsels"] == -(-N_FACT // CHUNK)
    assert rows_of(oracle) == rows_of(streamed)


def test_streaming_global_aggregate(tmp_path):
    s = make_session(tmp_path)
    q = "SELECT COUNT(*), SUM(qty), AVG(price) FROM fact WHERE day >= 100"
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert rows_of(oracle) == rows_of(streamed)


def test_ineligible_plans_run_incore(tmp_path):
    s = make_session(tmp_path)
    # distinct agg is not streamable
    q = "SELECT COUNT(DISTINCT fk) FROM fact"
    oracle = s.sql(q, backend="numpy")
    got = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] != "streaming"
    assert rows_of(oracle) == rows_of(got)


def test_eligibility_rules():
    from nds_tpu.engine.planner import Catalog, Planner
    from nds_tpu.sql import parse_sql

    catalog = Catalog({
        "big": (["k", "v"], ["int", "float"], 10_000_000),
        "small": (["k", "g"], ["int", "int"], 100),
    })
    est = {"big": 10_000_000, "small": 100}.get

    def plan(sql):
        return Planner(catalog).plan_query(parse_sql(sql))

    ok = try_streaming_plan(
        plan("SELECT g, SUM(v) FROM big JOIN small ON big.k = small.k "
             "GROUP BY g"), est, 1 << 20)
    assert ok is not None and ok.big_table == "big"
    # rollup IS streamable (round-3: per-prefix partials merged on
    # (group cols..., __grouping_id))
    rp = try_streaming_plan(
        plan("SELECT k, SUM(v) FROM big GROUP BY ROLLUP(k)"),
        est, 1 << 20)
    assert rp is not None and rp.partial_plan.rollup
    # windows ABOVE the aggregate are streamable (they run over merged
    # partials in the final phase); windows BELOW it are not
    assert try_streaming_plan(
        plan("SELECT g, s, rank() OVER (ORDER BY s DESC) FROM "
             "(SELECT g, SUM(v) s FROM big JOIN small ON big.k = small.k "
             "GROUP BY g) t"), est, 1 << 20) is not None
    # big table on the build side of a right join: not streamable
    assert try_streaming_plan(
        plan("SELECT g, SUM(v) FROM big RIGHT JOIN small ON big.k = small.k "
             "GROUP BY g"), est, 1 << 20) is None
    # two big tables: not streamable
    catalog2 = Catalog({"a": (["k"], ["int"], 10_000_000),
                        "b": (["k"], ["int"], 10_000_000)})
    assert try_streaming_plan(
        Planner(catalog2).plan_query(
            parse_sql("SELECT COUNT(*) FROM a JOIN b ON a.k = b.k")),
        {"a": 10_000_000, "b": 10_000_000}.get, 1 << 20) is None


def test_streaming_rollup_matches_incore(tmp_path):
    s = make_session(tmp_path)
    q = ("SELECT d.grp, f.day % 2 AS parity, SUM(f.qty) AS sq, "
         "COUNT(*) AS cnt FROM fact f JOIN dim d ON f.fk = d.dk "
         "WHERE f.day < 120 GROUP BY ROLLUP(d.grp, f.day % 2) "
         "ORDER BY d.grp, parity")
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats.get("re_records", 0) == 0
    assert sorted(rows_of(oracle), key=repr) == \
        sorted(rows_of(streamed), key=repr)


def test_streaming_window_above_agg(tmp_path):
    s = make_session(tmp_path)
    q = ("SELECT grp, sq, RANK() OVER (ORDER BY sq DESC) rk FROM "
         "(SELECT d.grp AS grp, SUM(f.qty) AS sq FROM fact f "
         "JOIN dim d ON f.fk = d.dk GROUP BY d.grp) t ORDER BY rk, grp")
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert rows_of(oracle) == rows_of(streamed)


def test_pack_table_roundtrip():
    """Packed morsel upload (one data matrix + one mask matrix) must be
    value-identical to the per-column path, including f64 bitcasts, i32
    widening, nulls, and the alive mask."""
    import numpy as np
    import pyarrow as pa
    from nds_tpu.engine import arrow_bridge
    from nds_tpu.engine.jax_backend.device import (pack_table, to_device,
                                                   to_host, unpack_table)

    rng = np.random.default_rng(4)
    n = 1000
    t = arrow_bridge.from_arrow(pa.table({
        "i": pa.array([None if k % 13 == 0 else int(v) for k, v in
                       enumerate(rng.integers(-5, 5, n))], type=pa.int64()),
        "f": pa.array(rng.normal(size=n)),
        "d": pa.array(rng.integers(0, 30, n), type=pa.int32()),
        "dt": pa.array([None if k % 17 == 0 else int(v) for k, v in
                        enumerate(rng.integers(10000, 11000, n))],
                       type=pa.date32()),
    }), dec_as_int=True)
    packed = pack_table(t, capacity=2048)
    assert packed is not None
    got = to_host(unpack_table(packed))
    want = to_host(to_device(t, capacity=2048))
    for a, b in zip(got.columns, want.columns):
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))
        np.testing.assert_array_equal(a.validity, b.validity)


def make_union_session(tmp_path):
    """Two big fact channels + a small one (q2/q5-class UNION ALL shape)."""
    rng = np.random.default_rng(9)
    cfg = EngineConfig(out_of_core=True, chunk_rows=CHUNK,
                       out_of_core_min_rows=10_000)
    s = Session(cfg)
    for name, n in (("ch_a", 30_000), ("ch_b", 25_000)):
        t = pa.table({
            "fk": pa.array(rng.integers(0, N_DIM, n), type=pa.int32()),
            "amt": pa.array(rng.integers(1, 500, n), type=pa.int64()),
        })
        path = os.path.join(str(tmp_path), f"{name}.parquet")
        pq.write_table(t, path, row_group_size=8192)
        s.register_parquet(name, path)
    small = pa.table({
        "fk": pa.array(rng.integers(0, N_DIM, 400), type=pa.int32()),
        "amt": pa.array(rng.integers(1, 500, 400), type=pa.int64()),
    })
    s.register_arrow("ch_small", small)
    dim = pa.table({"dk": pa.array(np.arange(N_DIM), type=pa.int32()),
                    "grp": pa.array((np.arange(N_DIM) % 7).astype(np.int32))})
    s.register_arrow("dim", dim)
    return s


UNION_AGG = """
SELECT d.grp, COUNT(*) AS cnt, SUM(u.amt) AS total
FROM (SELECT fk, amt FROM ch_a
      UNION ALL SELECT fk, amt FROM ch_b
      UNION ALL SELECT fk, amt FROM ch_small) u
JOIN dim d ON u.fk = d.dk
GROUP BY d.grp
ORDER BY d.grp
"""


def test_union_branch_streaming(tmp_path):
    """q2/q4/q5-class multi-fact-channel aggregate: each UNION ALL branch
    streams independently (VERDICT r4 #1)."""
    s = make_union_session(tmp_path)
    oracle = s.sql(UNION_AGG, backend="numpy")
    streamed = s.sql(UNION_AGG, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["morsels"] >= \
        -(-30_000 // CHUNK) + -(-25_000 // CHUNK)
    assert rows_of(oracle) == rows_of(streamed)


def test_aggregate_below_join_streams(tmp_path):
    """q2-class: the streamable aggregate sits BELOW a join — the old
    top-path rule rejected it; find_streaming_jobs materializes the
    subtree and the remaining join runs in-core."""
    s = make_session(tmp_path)
    q = """
    SELECT a.grp, a.sq, b.sq
    FROM (SELECT d.grp, SUM(f.qty) sq FROM fact f JOIN dim d ON f.fk = d.dk
          WHERE f.day < 180 GROUP BY d.grp) a
    JOIN (SELECT d.grp, SUM(f.qty) sq FROM fact f JOIN dim d ON f.fk = d.dk
          WHERE f.day >= 180 GROUP BY d.grp) b
    ON a.grp = b.grp
    ORDER BY a.grp
    """
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["jobs"] == 2
    assert rows_of(oracle) == rows_of(streamed)


def test_small_side_subquery_streams(tmp_path):
    """q6/q8-class: an aggregate subquery over a SMALL table must not block
    streaming of the big scan (the unsupported-node gate is scoped to
    subtrees containing the big scan)."""
    s = make_session(tmp_path)
    q = """
    SELECT d.grp, COUNT(*) FROM fact f JOIN dim d ON f.fk = d.dk
    WHERE f.price > (SELECT AVG(price) FROM fact WHERE day < 0) + 0
      AND f.fk IN (SELECT dk FROM dim WHERE grp < 20)
    GROUP BY d.grp ORDER BY d.grp
    """
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    # the outer aggregate itself cannot claim the big scan (the big-table
    # scalar subquery would embed a full scan per morsel), but the
    # SUBQUERY aggregates stream as their own jobs
    assert s.last_exec_stats["mode"] == "streaming"
    assert rows_of(oracle) == rows_of(streamed)


def test_partial_compaction_bounds_memory(tmp_path):
    """High-cardinality groups with a tiny compaction bound: results stay
    exact through repeated combine passes."""
    s = make_session(tmp_path)
    s.config.stream_compact_rows = 2_000
    q = ("SELECT fk, day, COUNT(*) c, SUM(qty) sq, AVG(price) ap "
         "FROM fact GROUP BY fk, day ORDER BY fk, day LIMIT 500")
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert rows_of(oracle) == rows_of(streamed)


def test_scalar_subquery_streaming(tmp_path):
    """q9-class: scalar-subquery aggregates over the big table stream as
    independent jobs; the outer (tiny) plan runs in-core."""
    s = make_session(tmp_path)
    q = """
    SELECT d.grp,
           CASE WHEN (SELECT COUNT(*) FROM fact WHERE day < 100) > 10
                THEN (SELECT AVG(price) FROM fact WHERE day < 100)
                ELSE (SELECT AVG(price) FROM fact WHERE day >= 100) END AS v
    FROM dim d WHERE d.dk < 3
    """
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["jobs"] == 3
    assert rows_of(oracle) == rows_of(streamed)


def test_semi_join_big_build_streaming(tmp_path):
    """q10/q16-class: EXISTS over the big table = semi join with a big
    BUILD side; the right side streams as a distinct-key set."""
    s = make_session(tmp_path)
    q = """
    SELECT d.grp, COUNT(*) FROM dim d
    WHERE EXISTS (SELECT 1 FROM fact f WHERE f.fk = d.dk AND f.day < 50)
    GROUP BY d.grp ORDER BY d.grp
    """
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["jobs"] == 1
    assert rows_of(oracle) == rows_of(streamed)


def test_not_in_big_build_streaming(tmp_path):
    """Null-aware anti join (NOT IN) with a big build side: the NULL group
    must survive the streamed dedup."""
    s = make_session(tmp_path)
    q = ("SELECT COUNT(*) FROM dim "
         "WHERE dk NOT IN (SELECT fk FROM fact WHERE day < 30)")
    oracle = s.sql(q, backend="numpy")
    streamed = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert rows_of(oracle) == rows_of(streamed)


# -- keyless aggregates: one group by construction ---------------------------
# A q9-class statement: scalar subqueries with no GROUP BY over the streamed
# table. Their group count is a static 1, not a recorded capacity decision, so
# inflate_schedule has nothing to raise to the morsel bound and the compiled
# morsel program sums exact integers by the masked reduce, into bucket(1) rows.

KEYLESS_MORSELS = 3
# morsel i holds pos in [i * CHUNK, (i + 1) * CHUNK): a predicate on pos
# filters whole morsels out — the first is the one the schedule is recorded on
KEYLESS_WHERE = {
    "every_morsel_live": "pos >= 0",
    "recorded_morsel_all_filtered_out": f"pos >= {CHUNK}",
    "last_morsel_all_filtered_out": f"pos < {2 * CHUNK}",
    "empty_input": "pos < 0",
}


def keyless_session(fuse: bool, **cfg) -> Session:
    from decimal import Decimal
    n = KEYLESS_MORSELS * CHUNK
    rng = np.random.default_rng(26)
    qty = rng.integers(1, 100, n).astype(object)
    qty[rng.random(n) < 0.05] = None
    cents = rng.integers(0, 100_000, n)
    sales = pa.table({
        "pos": pa.array(np.arange(n), type=pa.int32()),
        "qty": pa.array(list(qty), type=pa.int32()),
        "amt": pa.array([Decimal(int(c)).scaleb(-2) for c in cents],
                        type=pa.decimal128(7, 2)),
        "price": pa.array(np.round(rng.uniform(1, 100, n), 2)),
    })
    cfg = {"out_of_core_min_rows": 10_000, **cfg}
    s = Session(EngineConfig(
        out_of_core=True, chunk_rows=CHUNK, decimal_physical="i64",
        stream_fusion_max_branches=0 if fuse else 1, **cfg))
    s.register_arrow("sales", sales)
    s.register_arrow("one", pa.table({"k": pa.array([1], type=pa.int32())}))
    return s


def keyless_query(where: str, value: str = "amt") -> str:
    return f"""
    SELECT (SELECT COUNT(*) FROM sales WHERE {where}) AS c,
           (SELECT COUNT(qty) FROM sales WHERE {where} AND qty < 50) AS cq,
           (SELECT SUM(qty) FROM sales WHERE {where}) AS sq,
           (SELECT AVG({value}) FROM sales WHERE {where}) AS av,
           (SELECT MAX(qty) FROM sales WHERE {where} AND qty < 50) AS mx
    FROM one WHERE k = 1
    """


def run_spied(s: Session, q: str, monkeypatch):
    """Run `q` streamed; returns (result, the node type of every capacity
    decision the record passes made, [(program, abstract args, output)] of
    every morsel dispatch)."""
    import jax
    from nds_tpu.engine.jax_backend.executor import (CompiledQuery,
                                                     JaxExecutor)
    cap_nodes, dispatches = [], []
    decide_cap, run = JaxExecutor._decide_cap, CompiledQuery.run

    def spy_cap(self, scalar):
        if self._rec is not None and self._rec.mode == "record":
            cap_nodes.append(type(self._cur_node).__name__)
        return decide_cap(self, scalar)

    def spy_run(self, scans, values=(), **kw):
        out = run(self, scans, values, **kw)
        if "/morsel:" in self.label:
            specs = jax.tree_util.tree_map(
                lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                self._args(scans, values))
            dispatches.append((self, specs, out))
        return out

    monkeypatch.setattr(JaxExecutor, "_decide_cap", spy_cap)
    monkeypatch.setattr(CompiledQuery, "run", spy_run)
    result = s.sql(q, backend="jax")
    assert s.last_exec_stats["mode"] == "streaming"
    assert s.last_exec_stats["morsels"] == KEYLESS_MORSELS
    assert s.last_exec_stats.get("re_records", 0) == 0
    return result, cap_nodes, dispatches


def agg_apply_ops(dispatches) -> set:
    """op_names under AggregateNode#k/agg_apply in the compiled text of
    every distinct morsel program that was dispatched."""
    import re
    ops = set()
    for cq, specs in {id(cq): (cq, specs)
                      for cq, specs, _ in dispatches}.values():
        text = cq._fn.lower(*specs).compile().as_text()
        ops |= {o for o in re.findall(r'op_name="([^"]*)"', text)
                if re.search(r"/AggregateNode#\d+/agg_apply/", o)}
    return ops


@pytest.mark.parametrize("fuse", [True, False],
                         ids=["fused", "per_member"])
@pytest.mark.parametrize("case", sorted(KEYLESS_WHERE))
def test_keyless_aggregates_record_no_group_capacity(case, fuse,
                                                     monkeypatch):
    from nds_tpu.engine.jax_backend.device import bucket
    s = keyless_session(fuse)
    q = keyless_query(KEYLESS_WHERE[case])
    oracle = s.sql(q, backend="numpy")
    streamed, cap_nodes, dispatches = run_spied(s, q, monkeypatch)
    assert rows_of(streamed) == rows_of(oracle)
    if case == "empty_input":
        # the empty input's one row: counts 0, every other aggregate NULL
        assert rows_of(streamed) == [(0, 0, None, None, None)]
    # the schedule holds the filters' compactions and nothing of the
    # aggregates, so there is nothing for inflate_schedule to raise
    assert cap_nodes and "AggregateNode" not in cap_nodes, cap_nodes
    members = 5
    assert len(dispatches) == KEYLESS_MORSELS * (1 if fuse else members)
    partials = [t for _cq, _specs, out in dispatches
                for t in (out if fuse else [out])]
    assert len(partials) == KEYLESS_MORSELS * members
    assert {t.capacity for t in partials} == {bucket(1)}
    ops = agg_apply_ops(dispatches)
    assert any("reduce" in o for o in ops), ops
    assert not [o for o in ops if "scatter" in o], ops


def test_keyless_float_aggregate_keeps_segment_sum_into_one_bucket(
        monkeypatch):
    """A float operand keeps jax.ops.segment_sum in record and replay alike
    (the ULP rule above kernels._MASKED_SEG_MAX): the keyless change gives it
    bucket(1) segments instead of the morsel bound, and no other path."""
    from nds_tpu.engine.jax_backend.device import bucket
    s = keyless_session(fuse=True)
    q = keyless_query("pos >= 0", value="price")
    oracle = s.sql(q, backend="numpy")
    streamed, cap_nodes, dispatches = run_spied(s, q, monkeypatch)
    assert rows_of(streamed) == rows_of(oracle)
    assert "AggregateNode" not in cap_nodes
    assert {t.capacity for _cq, _specs, out in dispatches
            for t in out} == {bucket(1)}
    assert [o for o in agg_apply_ops(dispatches) if "scatter" in o]


# -- a second sighting replays programs sized from the first whole pass ------
# A q3-shaped statement (fact ⋈ filtered date ⋈ filtered item, GROUP BY), two
# branches over one streamed table so that a scan group has two members. The
# first sighting provisions every capacity at the morsel bound; what its
# replays saw sizes every later one (Session._stream_group, tighten).

TIGHT_MORSELS = 4
TIGHT_ROWS = TIGHT_MORSELS * CHUNK


def tight_fact(dk=None, n=TIGHT_ROWS, seed=29) -> pa.Table:
    rng = np.random.default_rng(seed)
    if dk is None:
        dk = rng.integers(0, 365, n)
    return pa.table({
        "dk": pa.array(dk, type=pa.int32()),
        "ik": pa.array(rng.integers(0, 1000, n), type=pa.int32()),
        "price": pa.array(rng.integers(1, 10_000, n).astype(np.int64))})


def tight_session(fuse: bool, fact: pa.Table = None, **cfg) -> Session:
    d = np.arange(365)
    i = np.arange(1000)
    cfg = {"out_of_core_min_rows": 10_000, **cfg}
    s = Session(EngineConfig(
        out_of_core=True, chunk_rows=CHUNK,
        stream_fusion_max_branches=0 if fuse else 1, **cfg))
    s.register_arrow("fact", tight_fact() if fact is None else fact)
    s.register_arrow("date_dim", pa.table({
        "d": pa.array(d, type=pa.int32()),
        "moy": pa.array((d // 31 + 1).astype(np.int32)),
        "yr": pa.array((d % 3 + 2000).astype(np.int32))}))
    s.register_arrow("item", pa.table({
        "i": pa.array(i, type=pa.int32()),
        "man": pa.array((i % 40).astype(np.int32)),
        "brand": pa.array((i % 16).astype(np.int32))}))
    return s


def _tight_branch(agg: str, moy: int) -> str:
    return (f"SELECT yr, brand, {agg} AS v FROM fact JOIN date_dim ON dk = d "
            f"JOIN item ON ik = i WHERE moy = {moy} AND man = 7 "
            "GROUP BY yr, brand")


TIGHT_Q = (f"SELECT a.yr, a.brand, a.v AS sp, b.v AS cnt "
           f"FROM ({_tight_branch('SUM(price)', 11)}) a "
           f"JOIN ({_tight_branch('COUNT(*)', 12)}) b "
           "ON a.yr = b.yr AND a.brand = b.brand ORDER BY a.yr, a.brand")

TIGHT_COUNTERS = ("compiles", "bytes_fetched", "tight_morsel_replays",
                  "morsel_re_records", "replay_mismatches")


def sighting(s: Session, monkeypatch, q: str = TIGHT_Q):
    """One streamed run of `q` held against the numpy oracle. Returns what
    the counters moved by and, per morsel dispatch, the program's cap
    values and the capacities of the partial tables it returned."""
    from nds_tpu.engine.jax_backend.executor import CompiledQuery
    from nds_tpu.obs.metrics import METRICS
    dispatches = []
    run = CompiledQuery.run

    def spy_run(self, scans, values=(), **kw):
        out = run(self, scans, values, **kw)
        if "/morsel:" in self.label:
            caps = [int(v) for k, v in self.decisions if k == "cap"]
            outs = out if isinstance(out, tuple) else (out,)
            dispatches.append((caps, [t.capacity for t in outs]))
        return out

    oracle = rows_of(s.sql(q, backend="numpy"))
    before = METRICS.snapshot()
    with monkeypatch.context() as m:
        m.setattr(CompiledQuery, "run", spy_run)
        got = rows_of(s.sql(q, backend="jax", label="tight"))
    moved = METRICS.delta(before)
    assert s.last_exec_stats["mode"] == "streaming"
    assert got == oracle and got
    return {k: moved.get(k, 0) for k in TIGHT_COUNTERS}, dispatches


def group_state(s: Session, q: str = TIGHT_Q) -> dict:
    (state,) = s._stream_cache[q]["gstates"]
    return state


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_member"])
def test_second_sighting_replays_what_the_first_pass_saw(fuse, monkeypatch):
    from nds_tpu.engine.jax_backend.device import bucket
    from nds_tpu.engine.streaming import adapt_schedule
    s = tight_session(fuse)
    programs = 1 if fuse else 2
    first, d1 = sighting(s, monkeypatch)
    assert len(d1) == TIGHT_MORSELS * programs
    assert all(c == bucket(CHUNK) for caps, _outs in d1 for c in caps)
    assert first["tight_morsel_replays"] == 0
    assert first["compiles"] == programs

    state = group_state(s)
    assert state["tight"] is True and len(state["cqs"]) == programs
    for cq, raw, obs in zip(state["cqs"], state["raw"], state["obs"]):
        assert cq.decisions == adapt_schedule(raw, CHUNK, obs)
        for (kind, v), (_k, actual), seen in zip(cq.decisions, raw, obs):
            assert seen >= actual
            assert v == (seen if kind == "cap" else actual)

    second, d2 = sighting(s, monkeypatch)
    assert len(d2) == len(d1)
    assert all(c < CHUNK // 2 for caps, _outs in d2 for c in caps)
    for caps, outs in d2:
        # a partial table holds its aggregate's observed groups
        if fuse:
            assert set(outs) <= {bucket(c) for c in caps}
        else:
            assert outs == [bucket(caps[-1])]
        assert max(outs) <= bucket(16 * 3)      # brands x years
    assert second["tight_morsel_replays"] == TIGHT_MORSELS
    assert second["morsel_re_records"] == second["replay_mismatches"] == 0
    assert second["compiles"] == programs       # the tight programs, once
    assert second["bytes_fetched"] * 20 < first["bytes_fetched"]

    third, d3 = sighting(s, monkeypatch)
    assert d3 == d2
    assert third["compiles"] == 0
    assert third["tight_morsel_replays"] == TIGHT_MORSELS
    assert third["bytes_fetched"] == second["bytes_fetched"]
    assert group_state(s)["cqs"] == state["cqs"]    # replaced once


def test_only_the_last_morsel_has_survivors(monkeypatch):
    """The recorded morsel joins nothing (every date key of the first three
    morsels falls outside both filtered months), so its actuals are 0: the
    tight caps come from what the last morsel's replay saw."""
    rng = np.random.default_rng(31)
    dk = np.concatenate([np.zeros(TIGHT_ROWS - CHUNK, dtype=np.int64),
                         rng.integers(300, 365, CHUNK)])
    s = tight_session(True, fact=tight_fact(dk=dk))
    first, _d1 = sighting(s, monkeypatch)
    state = group_state(s)
    (raw,), (obs,) = state["raw"], state["obs"]
    grew = [seen > actual for (kind, actual), seen in zip(raw, obs)
            if kind == "cap"]
    assert any(grew) and state["tight"] is True
    second, d2 = sighting(s, monkeypatch)
    assert second["morsel_re_records"] == second["replay_mismatches"] == 0
    assert second["tight_morsel_replays"] == TIGHT_MORSELS
    assert all(c < CHUNK // 2 for caps, _outs in d2 for c in caps)
    assert second["bytes_fetched"] < first["bytes_fetched"]


def test_reregistered_table_starts_at_the_bound_again(monkeypatch):
    from nds_tpu.engine.jax_backend.device import bucket
    s = tight_session(True)
    sighting(s, monkeypatch)
    second, _d = sighting(s, monkeypatch)
    assert second["tight_morsel_replays"] == TIGHT_MORSELS
    grown = tight_fact(n=TIGHT_ROWS + 2 * CHUNK, seed=37)
    s.register_arrow("fact", grown)     # the generation moves: new entry
    again, d = sighting(s, monkeypatch)
    assert len(d) == TIGHT_MORSELS + 2
    assert all(c == bucket(CHUNK) for caps, _outs in d for c in caps)
    assert again["tight_morsel_replays"] == 0
    assert again["morsel_re_records"] == 0
    after, d2 = sighting(s, monkeypatch)
    assert after["tight_morsel_replays"] == TIGHT_MORSELS + 2
    assert all(c < CHUNK // 2 for caps, _outs in d2 for c in caps)


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_member"])
def test_an_overflowing_tight_replay_goes_back_to_the_bound(fuse,
                                                            monkeypatch):
    """Observed maxima patched down to nothing: the first tight replay
    overflows, that morsel re-records eagerly, the rest of the pass and
    every later sighting run at the bound, and nothing tightens again."""
    from nds_tpu.engine.jax_backend.device import bucket
    s = tight_session(fuse)
    sighting(s, monkeypatch)
    state = group_state(s)
    for cq in state["cqs"]:             # built, not yet traced
        cq.decisions = [(k, 0 if k == "cap" else v) for k, v in cq.decisions]
    programs = len(state["cqs"])
    second, d2 = sighting(s, monkeypatch)
    assert second["morsel_re_records"] == second["replay_mismatches"] == 1
    assert second["tight_morsel_replays"] == 0
    assert s.last_exec_stats["re_records"] == 1
    # the morsels after the overflow ran whole, at the bound
    at_bound = [caps for caps, _outs in d2
                if all(c == bucket(CHUNK) for c in caps)]
    assert len(at_bound) == (TIGHT_MORSELS - 1) * programs
    assert state["tight"] is False
    third, d3 = sighting(s, monkeypatch)
    assert len(d3) == TIGHT_MORSELS * programs
    assert all(c == bucket(CHUNK) for caps, _outs in d3 for c in caps)
    assert third["morsel_re_records"] == third["tight_morsel_replays"] == 0
    assert third["compiles"] == 0 and state["tight"] is False


def test_a_resident_statement_keeps_its_recorded_schedule():
    """The in-core path is untouched: a resident statement's program is
    built from its record pass's actuals under the name it had, whatever
    the sighting."""
    from nds_tpu.obs.metrics import METRICS
    s = tight_session(True, out_of_core_min_rows=10 * TIGHT_ROWS)
    q = _tight_branch("SUM(price)", 11) + " ORDER BY yr, brand"
    oracle = rows_of(s.sql(q, backend="numpy"))
    before = METRICS.snapshot()
    seen = []
    for _ in range(3):
        assert rows_of(s.sql(q, backend="jax", label="res")) == oracle
        assert s.last_exec_stats["mode"] != "streaming"
        ents = [e for e in s._jax_executor()._plans.values()
                if e.get("cq") is not None]
        seen.append([(e["cq"].module_name, list(e["cq"].decisions))
                     for e in ents])
    assert seen[1] and seen[1] == seen[2]
    (name, decisions), = seen[2]
    assert name == "nds_res_root"
    caps = [v for k, v in decisions if k == "cap"]
    assert caps and max(caps) < CHUNK       # actuals, never a bound
    assert METRICS.delta(before).get("tight_morsel_replays", 0) == 0


# -- schedule adaptation unit ------------------------------------------------

def test_adapt_schedule_falls_back_and_clamps():
    dec = [("exact", 3), ("cap", 7), ("cap", 2)]
    # no observations / structural drift -> plain morsel inflation
    assert adapt_schedule(dec, 4096, None) == inflate_schedule(dec, 4096)
    assert adapt_schedule(dec, 4096, [3, 7]) == inflate_schedule(dec, 4096)
    # observed maxima replace the morsel bound, record actual still floors
    adapted = adapt_schedule(dec, 4096, [3, 100, 1])
    assert adapted == [("exact", 3), ("cap", 100), ("cap", 2)]


# -- a filter that only feeds keyless integer aggregates carries its mask -----
# query9's shape: scalar subqueries, each count(*) / avg over one `between`
# filter of the fact table. Each filter keeps about a fifth of the rows, so
# its survivors' bucket is under half the capacity and _maybe_compact would
# sort the capacity and gather every column — for a masked reduce into one
# group, which repays neither. Such a filter hands on its narrowed alive mask
# (JaxExecutor._maybe_compact, _mask_carrying_filters); its cap decision stays
# in the schedule.

MASK_MEMBERS = 6


def mask_query(where: str = "") -> str:
    """`where` adds a second FilterNode under each member's, which feeds a
    filter and so compacts as ever (it keeps whole morsels or none)."""
    def bucket_of(lo: int, hi: int, over: int) -> str:
        w = (where and f"{where} AND ") + f"qty BETWEEN {lo} AND {hi}"
        return (f"CASE WHEN (SELECT COUNT(*) FROM sales WHERE {w}) > {over} "
                f"THEN (SELECT AVG(amt) FROM sales WHERE {w}) "
                f"ELSE (SELECT AVG(qty) FROM sales WHERE {w}) END")
    return (f"SELECT {bucket_of(1, 20, 100)} AS b1, "
            f"{bucket_of(21, 40, 1 << 30)} AS b2 FROM one WHERE k = 1")


def compaction_ops(text: str) -> list:
    """What _maybe_compact leaves in a program under a FilterNode's scope:
    compaction_perm's sort and the gather of the columns."""
    import re
    return sorted(o for o in set(re.findall(r'"(jit\([^"]*)"', text))
                  if re.search(r"/FilterNode#\d+/(compaction_perm/|gather)",
                               o))


def resident_program(s: Session, q: str, label: str = "mask"):
    """Run `q` in-core, recorded and then replayed compiled; returns (rows, the
    CompiledQuery, its lowered text with scopes, its compiled text)."""
    for _ in range(2):
        got = s.sql(q, backend="jax", label=label)
    assert s.last_exec_stats["mode"] == "compile+run"
    je = s._jax_executor()
    (ent,) = [e for e in je._plans.values() if e.get("cq") is not None]
    cq = ent["cq"]
    lowered = cq._fn.lower(*cq._args(je._scans_for(ent),
                                     ent.get("params", ())))
    return (rows_of(got), cq, lowered.as_text(debug_info=True),
            lowered.compile().as_text())


def resident_session() -> Session:
    return keyless_session(True, out_of_core_min_rows=1 << 30)


def test_a_query9_shaped_resident_statement_compacts_no_filter(monkeypatch):
    from nds_tpu.engine.jax_backend import executor as X
    from nds_tpu.obs.metrics import METRICS
    q = mask_query()
    s = resident_session()
    oracle = rows_of(s.sql(q, backend="numpy"))
    before = METRICS.snapshot()
    rows, cq, traced, compiled = resident_program(s, q)
    assert rows == oracle and rows[0][0] is not None
    assert cq.mask_carried == MASK_MEMBERS
    assert METRICS.delta(before)["mask_carried_filters"] == MASK_MEMBERS
    assert not compaction_ops(traced) and not compaction_ops(compiled)
    # the same statement with the rule held off: the same answer from the
    # same schedule (every filter's cap is still a decision), and the
    # compactions the rule took out
    X.clear_shared_programs()
    monkeypatch.setattr(X, "_mask_carrying_filters", lambda plan: frozenset())
    s_off = resident_session()
    before = METRICS.snapshot()
    rows_off, cq_off, traced_off, compiled_off = resident_program(s_off, q)
    assert rows_off == oracle and cq_off.mask_carried == 0
    assert METRICS.delta(before).get("mask_carried_filters", 0) == 0
    assert cq.decisions == cq_off.decisions
    assert [k for k, _v in cq.decisions] == ["cap"] * (MASK_MEMBERS + 1)
    assert cq.module_name == cq_off.module_name
    # (a compaction only count(*) consumes is dead code to JAX's lowering
    # already: the four avg members' stay, in the compiled text too)
    for text in (traced_off, compiled_off):
        assert len({o.split("/compaction_perm/")[0]
                    for o in compaction_ops(text)
                    if "/compaction_perm/" in o}) == MASK_MEMBERS - 2


RULE_OFF = {
    "float_operand":
        "SELECT (SELECT AVG(price) FROM sales WHERE qty BETWEEN 1 AND 20) "
        "AS a FROM one WHERE k = 1",
    "count_distinct":
        "SELECT (SELECT COUNT(DISTINCT qty) FROM sales WHERE pos < 2000) "
        "AS a FROM one WHERE k = 1",
    "keyed_aggregate":
        "SELECT qty, SUM(amt) AS s FROM sales WHERE qty BETWEEN 1 AND 20 "
        "GROUP BY qty ORDER BY qty",
    "filter_under_a_join":
        "SELECT COUNT(*) AS c, SUM(amt) AS s FROM sales JOIN one ON k = qty "
        "WHERE pos < 2000",
    # the CTE's filter runs once for two parents: a keyless SUM and a sort
    "second_parent_is_a_sort":
        "WITH f AS (SELECT pos, qty, amt FROM sales "
        "WHERE qty BETWEEN 1 AND 20) "
        "SELECT (SELECT SUM(amt) FROM f) AS a, (SELECT MAX(pos) FROM "
        "(SELECT pos FROM f ORDER BY amt, pos LIMIT 5) t) AS b "
        "FROM one WHERE k = 1",
}


@pytest.mark.parametrize("case", sorted(RULE_OFF))
def test_the_filter_still_compacts_where_the_rule_does_not_hold(case):
    from nds_tpu.obs.metrics import METRICS
    q = RULE_OFF[case]
    s = resident_session()
    oracle = rows_of(s.sql(q, backend="numpy"))
    before = METRICS.snapshot()
    rows, cq, traced, compiled = resident_program(s, q)
    assert rows == oracle and rows
    assert cq.mask_carried == 0
    assert METRICS.delta(before).get("mask_carried_filters", 0) == 0
    assert compaction_ops(traced) and compaction_ops(compiled)
    if case == "second_parent_is_a_sort":
        # one filter, one decision, two parents
        assert len({o.split("/FilterNode#")[1].split("/")[0]
                    for o in compaction_ops(traced)}) == 1


def test_avg_without_x64_sums_in_float_and_keeps_the_compaction():
    """x64 off, kernels.agg_apply averages in float: the segment path, which
    a compaction does repay. count(*) and an integer sum stay exempt."""
    import jax
    from nds_tpu.engine.jax_backend.executor import (_mask_carrying_filters,
                                                     _masked_reduction)
    from nds_tpu.engine.plan import AggregateNode, FilterNode, iter_plan_nodes
    from nds_tpu.engine.planner import Planner
    from nds_tpu.sql import parse_sql
    s = resident_session()
    plan = Planner(s._catalog()).plan_query(parse_sql(mask_query()))
    aggs = [n for n in iter_plan_nodes(plan) if isinstance(n, AggregateNode)]
    filters = [n for n in iter_plan_nodes(plan) if isinstance(n, FilterNode)]
    assert len(aggs) == MASK_MEMBERS and len(filters) == MASK_MEMBERS + 1
    assert jax.config.read("jax_enable_x64")
    assert all(_masked_reduction(a) for a in aggs)
    assert len(_mask_carrying_filters(plan)) == MASK_MEMBERS
    with jax.enable_x64(False):
        exempt = [a for a in aggs if _masked_reduction(a)]
        assert [[x.func for x in a.aggs] for a in exempt] == \
            [["count_star"]] * 2
        assert len(_mask_carrying_filters(plan)) == 2


def test_a_shared_projection_needs_every_parent_to_be_a_reduction():
    """execute() memoises by id(node): a filter (or a projection over it)
    that two parents share runs once, so one parent of another kind — a
    sort, the root, an expression — and it compacts."""
    from nds_tpu.engine.jax_backend.executor import _mask_carrying_filters
    from nds_tpu.engine.plan import (AggregateNode, AggSpec, BCol, BLit,
                                     BScalarSubquery, FilterNode, ProjectNode,
                                     ScanNode, SetOpNode, SortKey, SortNode)
    qty, amt = BCol("int", 0, "qty"), BCol("dec2", 1, "amt")
    kw = {"out_names": ["qty", "amt"], "out_dtypes": ["int", "dec2"]}

    def scan():
        return ScanNode("sales", ["qty", "amt"], **kw)

    def keyless(child, *aggs):
        return AggregateNode(child, [], list(aggs), out_names=["v"],
                             out_dtypes=["int"])

    f = FilterNode(scan(), BLit("bool", True), **kw)
    proj = ProjectNode(f, [qty, amt], **kw)
    total = keyless(proj, AggSpec("sum", amt))
    count = keyless(f, AggSpec("count_star", None))
    both = SetOpNode("union", True, total, count, out_names=["v"],
                     out_dtypes=["int"])
    assert _mask_carrying_filters(both) == {id(f)}
    # a third parent that sorts the projection
    sort = SortNode(proj, [SortKey(qty)], **kw)
    union = SetOpNode("union", True, both, keyless(sort, AggSpec("min", qty)),
                      out_names=["v"], out_dtypes=["int"])
    assert _mask_carrying_filters(union) == frozenset()
    # the root, and a subquery expression's plan, consume as they are
    assert _mask_carrying_filters(f) == frozenset()
    assert _mask_carrying_filters(proj) == frozenset()
    sub = ProjectNode(scan(), [BScalarSubquery("int", f)], **kw)
    assert _mask_carrying_filters(keyless(sub, AggSpec("count_star", None))) \
        == frozenset()
    # a keyed or a distinct reduction is no such consumer
    keyed = AggregateNode(f, [qty], [AggSpec("sum", amt)],
                          out_names=["qty", "v"], out_dtypes=["int", "dec2"])
    assert _mask_carrying_filters(keyed) == frozenset()
    assert _mask_carrying_filters(
        keyless(f, AggSpec("count", qty, distinct=True))) == frozenset()


def mask_sighting(s: Session, q: str, monkeypatch):
    """One streamed run of `q` against the numpy oracle: what the counters
    moved by, and run_spied's dispatches."""
    from nds_tpu.obs.metrics import METRICS
    oracle = rows_of(s.sql(q, backend="numpy"))
    before = METRICS.snapshot()
    with monkeypatch.context() as m:
        got, _cap_nodes, dispatches = run_spied(s, q, m)
    assert rows_of(got) == oracle
    moved = METRICS.delta(before)
    return ({k: moved.get(k, 0) for k in TIGHT_COUNTERS +
             ("mask_carried_filters",)}, dispatches, rows_of(got))


@pytest.mark.parametrize("fuse", [True, False], ids=["fused", "per_member"])
def test_a_tight_morsel_program_compacts_no_exempt_filter(fuse, monkeypatch):
    """The second sighting's caps (about a fifth of a morsel) are under half
    the morsel's capacity: the tight program would compact in every member,
    where the inflated one never could. It carries the masks instead, and
    the third morsel, which the predicate on pos empties for every member,
    still yields each member's one row."""
    from nds_tpu.engine.jax_backend.device import bucket
    from nds_tpu.engine.streaming import schedule_shape
    s = keyless_session(fuse)
    q = mask_query(f"pos < {2 * CHUNK}")
    per_sighting = KEYLESS_MORSELS * MASK_MEMBERS
    first, _d, rows = mask_sighting(s, q, monkeypatch)
    assert rows[0][0] is not None and rows[0][1] is not None
    assert first["tight_morsel_replays"] == 0
    assert first["mask_carried_filters"] == per_sighting
    states = s._stream_cache[q]["gstates"]
    assert [st["tight"] for st in states] == [True]
    for cq in states[0]["cqs"]:
        # a member's decisions: the filter on pos, then the exempt one
        caps = [v for k, v in cq.decisions if k == "cap"][1::2]
        assert caps and all(2 * bucket(v) < bucket(CHUNK) for v in caps)
    shape = [schedule_shape(cq.decisions) for cq in states[0]["cqs"]]

    second, dispatches, _rows = mask_sighting(s, q, monkeypatch)
    assert second["tight_morsel_replays"] == KEYLESS_MORSELS
    assert second["mask_carried_filters"] == per_sighting
    assert second["morsel_re_records"] == second["replay_mismatches"] == 0
    assert second["compiles"] == (1 if fuse else MASK_MEMBERS)
    programs = {id(cq): (cq, specs) for cq, specs, _out in dispatches}
    assert len(programs) == (1 if fuse else MASK_MEMBERS)
    for cq, specs in programs.values():
        assert cq in states[0]["cqs"]
        assert cq.mask_carried == (MASK_MEMBERS if fuse else 1)
        lowered = cq._fn.lower(*specs)
        assert not compaction_ops(lowered.as_text(debug_info=True))
        assert not compaction_ops(lowered.compile().as_text())
    # every morsel, the emptied third too, hands back one row a member
    partials = [t for _cq, _specs, out in dispatches
                for t in (out if fuse else [out])]
    assert len(partials) == per_sighting
    assert [int(np.asarray(t.alive).sum()) for t in partials] == \
        [1] * per_sighting

    third, _d, _rows = mask_sighting(s, q, monkeypatch)
    assert third["compiles"] == 0
    assert third["tight_morsel_replays"] == KEYLESS_MORSELS
    assert third["mask_carried_filters"] == per_sighting
    assert [schedule_shape(cq.decisions)
            for cq in s._stream_cache[q]["gstates"][0]["cqs"]] == shape


def test_the_streamed_schedule_is_what_it_was_without_the_rule(monkeypatch):
    """The exempt filter's cap stays a decision: first-sighting and tight
    schedules have the shape they had, position for position."""
    from nds_tpu.engine.jax_backend import executor as X
    from nds_tpu.engine.streaming import schedule_shape
    q = mask_query()

    def shapes():
        s = keyless_session(True)
        out = []
        for _ in range(2):
            mask_sighting(s, q, monkeypatch)
            (state,) = s._stream_cache[q]["gstates"]
            out.append(([schedule_shape(cq.decisions)
                         for cq in state["cqs"]], state["raw"], state["obs"]))
        return out

    on = shapes()
    X.clear_shared_programs()
    monkeypatch.setattr(X, "_mask_carrying_filters", lambda plan: frozenset())
    assert shapes() == on
