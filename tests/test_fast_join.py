"""The direct-address join (``JaxExecutor._fast_join``, ISSUE 35).

It reads its lookup table once per probe row and decides the match from the
range test on the keys and the table's entry alone: the build key is no
longer gathered back to confirm it. What makes that exact is the recorded
decision ``span_ok & unique & cnt_r > 0``, so three things are pinned here:

- every join kind against the host ``Executor`` on the same plan, x64 on and
  off, over key sets that sit where address arithmetic wraps — and the eager
  record pass against the compiled replay, row for row;
- a replay over a build side that has left the recorded decision (a
  duplicate key, a span past the table) raises ``ReplayMismatch`` in all
  three replay paths and hands out no rows;
- the lowered program of a three-dimension star holds one gather per direct
  join besides the payload columns (the parent held two).

Tables of a dozen rows, programs of one join: about 0.2 s a case.
"""
import re

import jax
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session
from nds_tpu.engine.column import Column, Table
from nds_tpu.engine.executor import Executor
from nds_tpu.engine.jax_backend import to_host
from nds_tpu.engine.jax_backend.device import to_device
from nds_tpu.engine.jax_backend.executor import (BatchedQuery, CompiledQuery,
                                                 JaxExecutor, ReplayMismatch)
from nds_tpu.engine.plan import (BCall, BCol, BLit, FilterNode, JoinNode,
                                 ScanNode, iter_plan_nodes, parameterize_plan)
from nds_tpu.engine.verify import node_labels
from nds_tpu.obs.metrics import METRICS


def col(vals, dtype="int") -> Column:
    data = np.array([0 if v is None else v for v in vals], dtype=np.int64)
    valid = np.array([v is not None for v in vals], dtype=bool)
    return Column(dtype, data, None if valid.all() else valid)


def scan(table: str, names: list, out: list = None) -> ScanNode:
    return ScanNode(table, list(names), out_names=list(out or names),
                    out_dtypes=["int"] * len(names))


def rows_of(t) -> list:
    return (t if isinstance(t, Table) else to_host(t)).to_pylist()


def null_low(row: tuple) -> tuple:
    return tuple((v is not None, v or 0) for v in row)


# -- every kind, both widths, the key sets where arithmetic wraps -------------

#: name -> (kind, residual, null_aware)
KINDS = {
    "inner": ("inner", False, False),
    "left": ("left", False, False),
    "semi": ("semi", False, False),
    "anti": ("anti", False, False),
    "anti_null_aware": ("anti", False, True),
    "inner_residual": ("inner", True, False),
    "left_residual": ("left", True, False),
    "semi_residual": ("semi", True, False),
    "anti_residual": ("anti", True, False),
}


def key_sets(x64: bool) -> dict:
    """name -> (build keys, probe keys, takes the direct path). Eight build
    rows at most, so the lookup table has 32 entries (4 x the 8-row
    bucket)."""
    ii = np.iinfo(np.int64 if x64 else np.int32)
    lo, hi = int(ii.min), int(ii.max)
    # rmin + 2^31 (an i32 difference wraps to -2^31) and, under x64, rmin -
    # 2^63 reinterpreted (rmin + 2^63 mod 2^64)
    far = [-5 + 2 ** 31] + ([-5 + 2 ** 63] if x64 else [])
    return {
        # build keys at the dtype's lower end, holes, a NULL on either side;
        # probes: hits, a hole, one past rmax, the other extreme
        "dtype_min": ([lo, lo + 1, lo + 3, None, lo + 6],
                      [lo, lo + 2, lo + 3, hi, None, lo + 6, lo + 7, 0,
                       lo + 1, lo + 1], True),
        # the upper end; a probe one below rmin and at the other extreme
        "dtype_max": ([hi, hi - 2, hi - 5, hi - 6],
                      [hi, hi - 1, hi - 2, lo, hi - 7, 0, None, hi - 5],
                      True),
        # negative keys, a sparse build, probes one below rmin, one above
        # rmax and at wrap distance
        "negative_sparse": ([-5, -3, 0, 2, None, 9],
                            [-6, -5, -4, -3, 10, 9, None, 0, 1, 2, 2] + far,
                            True),
        # the widest span the table holds: rmax - rmin == limit - 1
        "span_just_fits": ([7, 38, 20], [7, 38, 39, 6, 20, 21], True),
        # the three ways off the path: both must run the sort-based join
        "duplicate_build": ([1, 2, 2, 3], [0, 1, 2, 3, 4, 2], False),
        "span_too_wide": ([0, 1, 32], [0, 1, 2, 32, 33, -1], False),
        # rmax - rmin wraps to -1 here: the span is tested without it
        "both_extremes": ([lo, hi], [lo, hi, 0, lo + 1, hi - 1], False),
    }


KEY_SETS = sorted(key_sets(True))


def join_plan(kind: str, residual: bool, null_aware: bool) -> JoinNode:
    names = ["k", "v", "bk", "w"] if kind in ("inner", "left") else ["k", "v"]
    return JoinNode(
        scan("p", ["k", "v"]), scan("b", ["k", "w"], ["bk", "w"]), kind,
        [BCol("int", 0, "k")], [BCol("int", 0, "bk")],
        residual=BCall("bool", "lt", [BCol("int", 1, "v"),
                                      BCol("int", 3, "w")])
        if residual else None,
        null_aware=null_aware, out_names=names,
        out_dtypes=["int"] * len(names))


@pytest.mark.parametrize("keys", KEY_SETS)
@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_kind_answers_as_the_host_executor_does(kind, x64, keys):
    build, probe, direct = key_sets(x64)[keys]
    tables = {
        "p": Table(["k", "v"], [col(probe),
                                col([10 * i for i in range(len(probe))])]),
        # w straddles v, so a residual v < w keeps some matches and drops
        # others
        "b": Table(["k", "w"], [col(build),
                                col([25 * (i % 3) + 5
                                     for i in range(len(build))])]),
    }
    plan = join_plan(*KINDS[kind])
    want = Executor(tables.__getitem__).execute(plan).to_pylist()
    with jax.enable_x64(x64):
        ex = JaxExecutor(tables.__getitem__)
        out, decisions, scan_keys = ex.record_plan(plan)
        eager = rows_of(out)
        cq = CompiledQuery(plan, decisions, scan_keys)
        got = rows_of(cq.run(ex._scans_for({"scan_keys": scan_keys})))
        key_dtype = ex._scan_cache["p//k,v"].cols[0].data.dtype
    assert key_dtype == (np.int64 if x64 else np.int32)
    # the record pass and the replay: the same rows in the same order
    assert got == eager
    assert sorted(got, key=null_low) == sorted(want, key=null_low)
    # the first decision is the probe's; the replay counted the path it took
    assert decisions[0] == ("exact", int(direct))
    assert cq.join_paths == ((1, 0) if direct else (0, 1))
    assert ex.join_paths == cq.join_paths


# -- what guards the match is the schedule check ------------------------------

#: the build side as recorded, and after it left the recorded decision; eight
#: rows at most, so the capacity (and the program's shapes) stays 8
RECORDED = ([3, 4, 5, 6, 9], [30, 40, 50, 60, 90])
DRIFTED = {
    # key 9 became a second 4: every probe 4 would read whichever row the
    # scatter left in the table
    "duplicate_key": ([3, 4, 5, 6, 4], [30, 40, 50, 60, 90]),
    # key 9 became 40: past the 32-entry table, its address clips onto 34's
    "span_past_limit": ([3, 4, 5, 6, 40], [30, 40, 50, 60, 90]),
    # no live build row at all
    "no_live_row": ([None] * 5, [30, 40, 50, 60, 90]),
}
PROBE = ([3, 4, 4, 9, 34, 40, 7, None], [1, 2, 3, 4, 5, 6, 7, 8])


def build_table(which) -> Table:
    keys, w = which
    return Table(["k", "w"], [col(keys), col(w)])


def drift_plan():
    """p filtered on a hoisted literal (the batched path needs a parameter)
    joined to b."""
    p = scan("p", ["k", "v"])
    f = FilterNode(p, BCall("bool", "ge", [BCol("int", 1, "v"),
                                           BLit("int", 0)]),
                   out_names=["k", "v"], out_dtypes=["int", "int"])
    plan = JoinNode(f, scan("b", ["k", "w"], ["bk", "w"]), "inner",
                    [BCol("int", 0, "k")], [BCol("int", 0, "bk")],
                    out_names=["k", "v", "bk", "w"], out_dtypes=["int"] * 4)
    return parameterize_plan(plan)


def one_chip(batched: bool):
    """(run(build table) -> rows, the drift message's prefix) for
    CompiledQuery.run or BatchedQuery.run over a recorded program."""
    tables = {"p": Table(["k", "v"], [col(PROBE[0]), col(PROBE[1])]),
              "b": build_table(RECORDED)}
    pplan, values, dtypes = drift_plan()
    assert dtypes == ["int"]
    ex = JaxExecutor(tables.__getitem__)
    _out, decisions, scan_keys = ex.record_plan(pplan, tuple(values))
    cq = CompiledQuery(pplan, decisions, scan_keys,
                       param_dtypes=tuple(dtypes))
    scans = ex._scans_for({"scan_keys": scan_keys})

    def run(build: Table):
        live = dict(scans, **{"b//k,w": to_device(build)})
        if batched:
            return [rows_of(t) for t in
                    BatchedQuery(cq, 2).run(live, [(0,), (2,)])]
        return rows_of(cq.run(live, (0,)))
    return run, "batched exact decision drift" if batched \
        else "exact decision drift"


def mesh_replay():
    """The same for ShardedMorselQuery.run: p is the morsel, row-sharded
    over four virtual chips, b a replicated dimension."""
    from nds_tpu.engine.jax_backend.shard_exec import (ShardedMorselQuery,
                                                       stage_sharded)
    from nds_tpu.engine.streaming import inflate_schedule
    from nds_tpu.parallel import make_mesh
    mesh, shard_cap = make_mesh(4), 8
    morsel = Table(["k", "v"], [col(PROBE[0] * 4),
                                col(list(range(len(PROBE[0]) * 4)))])
    tables = {"p": morsel.slice(0, shard_cap), "b": build_table(RECORDED)}
    plan = JoinNode(scan("p", ["k", "v"]), scan("b", ["k", "w"], ["bk", "w"]),
                    "inner", [BCol("int", 0, "k")], [BCol("int", 0, "bk")],
                    out_names=["k", "v", "bk", "w"], out_dtypes=["int"] * 4)
    ex = JaxExecutor(tables.__getitem__)
    _out, decisions, scan_keys = ex.record_plan(plan, shard_local=True)
    smq = ShardedMorselQuery(plan, inflate_schedule(decisions, shard_cap),
                             scan_keys, mesh, "p//k,v")
    staged = stage_sharded(morsel, mesh, shard_cap)

    def run(build: Table):
        return rows_of(smq.run({"p//k,v": staged,
                                "b//k,w": to_device(build)}))
    return run, "sharded exact decision drift"


PATHS = {"compiled": lambda: one_chip(False), "batched": lambda: one_chip(True),
         "mesh": mesh_replay}


@pytest.mark.parametrize("drift", sorted(DRIFTED))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_replay_over_a_drifted_build_side_re_records_and_returns_no_rows(
        path, drift):
    run, message = PATHS[path]()
    # over the build side it was recorded on, the replay answers
    want = [(3, 1, 3, 30), (4, 2, 4, 40), (4, 3, 4, 40), (9, 4, 9, 90)]
    got = run(build_table(RECORDED))
    if path == "batched":
        assert got == [want, want[1:]]          # v >= 0, v >= 2
    elif path == "mesh":
        assert sorted((k, bk, w) for k, _v, bk, w in got) == \
            sorted((k, bk, w) for k, _v, bk, w in want * 4)
    else:
        assert got == want
    with pytest.raises(ReplayMismatch, match=message):
        run(build_table(DRIFTED[drift]))


def test_a_second_literal_that_drifts_the_build_side_is_answered_by_a_new_record():
    """End to end through Session.sql: the statement's second literal lets
    a duplicate key through the build side's filter. The shared program's
    replay is thrown away (replay_mismatches moves), the re-record answers
    as the host backend does."""
    s = Session(EngineConfig())
    s.register_arrow("p", pa.table({
        "k": pa.array(PROBE[0], type=pa.int64()),
        "v": pa.array(PROBE[1], type=pa.int64())}))
    s.register_arrow("b", pa.table({
        "k": pa.array([3, 4, 5, 6, 4, 9], type=pa.int64()),
        "w": pa.array([30, 40, 50, 60, 70, 90], type=pa.int64())}))
    tpl = ("SELECT p.k, p.v, b.w FROM p JOIN b ON p.k = b.k "
           "WHERE b.w <> {w} ORDER BY 1, 2, 3")
    unique, duplicate = tpl.format(w=70), tpl.format(w=90)
    for _ in range(3):
        got = s.sql(unique, backend="jax").to_pylist()
    assert s.last_exec_stats["mode"] == "compiled"
    assert got == s.sql(unique, backend="numpy").to_pylist() == \
        [(3, 1, 30), (4, 2, 40), (4, 3, 40), (9, 4, 90)]
    cq = s._jax_exec._plans[("sql", unique)]["cq"]
    assert cq.join_paths == (1, 0)
    before = METRICS.snapshot()
    got = s.sql(duplicate, backend="jax").to_pylist()
    moved = METRICS.delta(before)
    assert moved.get("replay_mismatches", 0) == 1
    assert s.last_exec_stats["mode"] == "record"
    assert got == s.sql(duplicate, backend="numpy").to_pylist() == \
        [(3, 1, 30), (4, 2, 40), (4, 2, 70), (4, 3, 40), (4, 3, 70)]


# -- the mechanism, from the lowered program ----------------------------------

GATHER = re.compile(
    r'"stablehlo\.gather"\(.*?\) -> tensor<(\d+)x\w+> loc\((#loc\d+)\)')
LOC = re.compile(r'^(#loc\d+) = loc\("([^"]*)"', re.M)


def test_each_direct_join_of_a_star_lowers_to_one_gather_and_its_payload():
    """fact x three dimensions (inner, semi, left): in the StableHLO each
    JoinNode's own scope holds the lookup-table gather and two gathers
    (data, validity) per payload column the join hands on — and no gather
    of the build key. The parent held one more per join."""
    rng = np.random.default_rng(35)
    n = 100
    tables = {
        "fact": Table(["k1", "k2", "k3", "v"],
                      [col(rng.integers(0, 12, n)), col(rng.integers(5, 30, n)),
                       col(rng.integers(-4, 9, n)), col(np.arange(n))]),
        "d1": Table(["k", "a"], [col(range(10)), col(range(100, 110))]),
        "d2": Table(["k"], [col(range(8, 28, 2))]),
        "d3": Table(["k", "b", "c"], [col(range(-3, 6)), col(range(9)),
                                      col(range(50, 59))]),
    }
    j1 = JoinNode(scan("fact", ["k1", "k2", "k3", "v"]),
                  scan("d1", ["k", "a"], ["d1k", "a"]), "inner",
                  [BCol("int", 0, "k1")], [BCol("int", 0, "d1k")],
                  out_names=["k1", "k2", "k3", "v", "d1k", "a"],
                  out_dtypes=["int"] * 6)
    j2 = JoinNode(j1, scan("d2", ["k"], ["d2k"]), "semi",
                  [BCol("int", 1, "k2")], [BCol("int", 0, "d2k")],
                  out_names=list(j1.out_names), out_dtypes=["int"] * 6)
    j3 = JoinNode(j2, scan("d3", ["k", "b", "c"], ["d3k", "b", "c"]), "left",
                  [BCol("int", 2, "k3")], [BCol("int", 0, "d3k")],
                  out_names=list(j1.out_names) + ["d3k", "b", "c"],
                  out_dtypes=["int"] * 9)
    want = Executor(tables.__getitem__).execute(j3).to_pylist()
    ex = JaxExecutor(tables.__getitem__)
    _out, decisions, scan_keys = ex.record_plan(j3)
    cq = CompiledQuery(j3, decisions, scan_keys)
    scans = ex._scans_for({"scan_keys": scan_keys})
    got = rows_of(cq.run(scans))
    assert sorted(got, key=null_low) == sorted(want, key=null_low) and got
    assert cq.join_paths == (3, 0)

    text = cq._fn.lower(*cq._args(scans, ())).as_text(debug_info=True)
    names = dict(LOC.findall(text))
    labels = node_labels(j3)
    per_join: dict = {}
    for _rows, loc in GATHER.findall(text):
        owner = re.search(r"(JoinNode#\d+)/gather$", names[loc])
        if owner:        # a gather in the join's own scope, no kernel's
            per_join[owner.group(1)] = per_join.get(owner.group(1), 0) + 1
    joins = [n for n in iter_plan_nodes(j3) if isinstance(n, JoinNode)]
    assert len(joins) == 3
    for node in joins:
        payload = 2 * len(node.right.out_names) \
            if node.kind in ("inner", "left") else 0
        assert per_join[labels[id(node)]] == 1 + payload, (
            labels[id(node)], node.kind, per_join)
    assert sorted(per_join) == sorted(labels[id(n)] for n in joins)


# -- the two counters ---------------------------------------------------------

@pytest.mark.parametrize("sql,want", [
    ("SELECT t.k, u.w FROM t JOIN u ON t.k = u.k ORDER BY 1, 2", (1, 0)),
    ("SELECT t.k, u.w FROM t JOIN u ON t.k = u.k AND t.v = u.w "
     "ORDER BY 1, 2", (0, 1)),
    ("SELECT t.k, d.w FROM t JOIN d ON t.k = d.k ORDER BY 1, 2", (0, 1)),
    ("SELECT t.k FROM t WHERE t.k IN (SELECT k FROM u) AND t.v IN "
     "(SELECT w FROM d) ORDER BY 1", (1, 1)),
    ("SELECT k, COUNT(*) AS c FROM t GROUP BY k ORDER BY 1", (0, 0)),
], ids=["direct", "composite_key", "duplicate_build", "one_of_each", "none"])
def test_join_path_counters_move_by_the_programs_static_counts(sql, want):
    """direct_joins / sorted_joins move at each dispatch of a compiled
    program by the paths its joins took, and by nothing in the record pass
    or on the host backend."""
    rng = np.random.default_rng(11)
    s = Session(EngineConfig())
    s.register_arrow("t", pa.table({
        "k": pa.array(rng.integers(0, 7, 500), type=pa.int64()),
        "v": pa.array(rng.integers(0, 40, 500), type=pa.int64())}))
    s.register_arrow("u", pa.table({
        "k": pa.array([0, 1, 2, 9], type=pa.int64()),
        "w": pa.array([10, 11, 12, 19], type=pa.int64())}))
    s.register_arrow("d", pa.table({
        "k": pa.array([1, 1, 2, 5], type=pa.int64()),
        "w": pa.array([10, 11, 11, 19], type=pa.int64())}))

    def moved(before):
        d = METRICS.delta(before)
        return d.get("direct_joins", 0), d.get("sorted_joins", 0)

    before = METRICS.snapshot()
    oracle = s.sql(sql, backend="numpy").to_pylist()
    s.sql(sql, backend="jax")                   # the record pass
    assert moved(before) == (0, 0)
    for dispatch in (1, 2):
        got = s.sql(sql, backend="jax")
        assert s.last_exec_stats["mode"] in ("compiled", "compile+run")
        assert moved(before) == tuple(dispatch * n for n in want)
    assert got.to_pylist() == oracle
    assert s._jax_exec._plans[("sql", sql)]["cq"].join_paths == want
    for name in ("direct_joins", "sorted_joins"):
        assert name in METRICS.describe()
