"""The direct-address join (``JaxExecutor._fast_join``, ISSUES 35 and 38).

It reads its lookup table once per probe row and decides the match from the
range test on the keys and the table's entry alone: the build key is no
longer gathered back to confirm it (ISSUE 35). The table is sized from the
span of the live build keys, not from the build side's capacity (ISSUE 38),
so a dimension that a filter thinned to a few rows over a wide span joins
directly. What makes both exact is the recorded pair of decisions — the
exact ``unique & cnt_r > 0 & span <= 2^24`` and, under it, the span as a
``cap`` — so these things are pinned here:

- every join kind against the host ``Executor`` on the same plan, x64 on and
  off, over key sets that sit where address arithmetic wraps — and the eager
  record pass against the compiled replay, row for row;
- the same over a filtered dimension of 18 survivors whose keys span 17,000,
  and at the memory bound's two sides;
- a replay over a build side that has left the recorded decisions (a
  duplicate key, no live row: the exact one; a span past the recorded
  bucket: the ``cap``) raises ``ReplayMismatch`` in all three replay paths
  and hands out no rows; a span that moves inside its bucket replays;
- the lowered program of a three-dimension star holds one gather per direct
  join besides the payload columns (PR 35's parent held two).

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
    """name -> (build keys, probe keys, takes the direct path). The lookup
    table has the ladder bucket of the build keys' span as its size."""
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
        # the bucket's edge: a span of 32 fills a 32-entry table, rmax -
        # rmin == limit - 1
        "span_just_fits": ([7, 38, 20], [7, 38, 39, 6, 20, 21], True),
        # one key more than that: 33 keys' span in the next bucket (48).
        # Four times the 8-row build side held 32 and refused it (PR 35)
        "span_too_wide": ([0, 1, 32], [0, 1, 2, 32, 33, -1], True),
        # the three ways off the path: each must run the sort-based join
        "duplicate_build": ([1, 2, 2, 3], [0, 1, 2, 3, 4, 2], False),
        # one key past the memory bound: a span of 2^24 + 1
        "span_past_bound": ([0, 1, 1 << 24],
                            [0, 1, 2, 1 << 24, (1 << 24) + 1, -1], False),
        # rmax - rmin wraps to -1 here: the span is tested without it
        "both_extremes": ([lo, hi], [lo, hi, 0, lo + 1, hi - 1], False),
    }


KEY_SETS = sorted(key_sets(True))


def join_plan(kind: str, residual: bool, null_aware: bool,
              build=None) -> JoinNode:
    """p(k, v) joined to ``build`` (b(bk, w) unless given; its columns start
    bk, w) on k = bk, with the residual v < w."""
    build = build or scan("b", ["k", "w"], ["bk", "w"])
    names = ["k", "v"] + (list(build.out_names)
                          if kind in ("inner", "left") else [])
    return JoinNode(
        scan("p", ["k", "v"]), build, kind,
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
    # the first decision is the probe's, and under it the span sizes the
    # table; the replay counted the path it took
    assert decisions[0] == ("exact", int(direct))
    if direct:
        live = [k for k in build if k is not None]
        assert decisions[1] == ("cap", max(live) - min(live) + 1)
    assert cq.join_paths[:2] == ((1, 0) if direct else (0, 1))
    assert ex.join_paths == cq.join_paths


# -- a filter thins a dimension's rows, not the span of its keys --------------

def thinned_star(kind: str, residual: bool, null_aware: bool):
    """(tables, plan): p joined to a dimension of 18,000 surrogate keys that
    a filter on ``a`` thins to 18 (every thousandth, from 7): the build side
    compacts to a 24-row bucket whose keys span 17,001."""
    n = 18_000
    dk = np.arange(1, n + 1)
    keep = (dk % 1000 == 7)
    survivors = dk[keep].tolist()
    assert len(survivors) == 18
    probe = survivors[::2] * 2 + [6, 8, 1008, 17_008, 18_000, 0, -1, None,
                                  survivors[-1], 2 ** 31 - 1]
    tables = {
        "p": Table(["k", "v"], [col(probe),
                                col([10 * i for i in range(len(probe))])]),
        "b": Table(["k", "w", "a"], [col(dk), col(25 * (dk % 17) + 5),
                                     col(keep.astype(np.int64))]),
    }
    dim = FilterNode(scan("b", ["k", "w", "a"], ["bk", "w", "a"]),
                     BCall("bool", "eq", [BCol("int", 2, "a"),
                                          BLit("int", 1)]),
                     out_names=["bk", "w", "a"], out_dtypes=["int"] * 3)
    plan = join_plan(kind, residual, null_aware, build=dim)
    return tables, plan


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_filtered_dimension_of_few_rows_and_a_wide_span_joins_directly(kind):
    tables, plan = thinned_star(*KINDS[kind])
    want = Executor(tables.__getitem__).execute(plan).to_pylist()
    ex = JaxExecutor(tables.__getitem__)
    out, decisions, scan_keys = ex.record_plan(plan)
    eager = rows_of(out)
    cq = CompiledQuery(plan, decisions, scan_keys)
    got = rows_of(cq.run(ex._scans_for({"scan_keys": scan_keys})))
    assert got == eager and got
    assert sorted(got, key=null_low) == sorted(want, key=null_low)
    # the filter's survivors (compacted: 4 x their bucket is 96 entries),
    # the join's eligibility, the span that sizes its table
    assert decisions[:3] == [("cap", 18), ("exact", 1), ("cap", 17_001)]
    assert cq.join_paths == ex.join_paths and cq.join_paths[:2] == (1, 0)


@pytest.mark.parametrize("top,direct", [((1 << 24) - 1, True),
                                        (1 << 24, False)],
                         ids=["at_the_bound", "past_it"])
def test_the_memory_bound_is_a_span_of_2_to_the_24(top, direct):
    """Two i32 tables of 2^24 entries, 64 MB each, are the most the join
    builds: one key more and it sorts."""
    tables = {"p": Table(["k", "v"], [col([0, 1, 5, top, top + 1, -1]),
                                      col(list(range(6)))]),
              "b": Table(["k", "w"], [col([0, 5, top]), col([7, 8, 9])])}
    plan = join_plan("inner", False, False)
    ex = JaxExecutor(tables.__getitem__)
    out, decisions, scan_keys = ex.record_plan(plan)
    cq = CompiledQuery(plan, decisions, scan_keys)
    got = rows_of(cq.run(ex._scans_for({"scan_keys": scan_keys})))
    assert got == rows_of(out) == [(0, 0, 0, 7), (5, 2, 5, 8),
                                   (top, 3, top, 9)]
    assert decisions[0] == ("exact", int(direct))
    assert cq.join_paths[:2] == ((1, 0) if direct else (0, 1))


# -- what guards the match is the schedule check ------------------------------

#: the build side as recorded (a span of 7: an 8-entry table over the keys 3
#: to 10), and after it left a recorded decision, with the check that throws
#: the replay away; eight rows at most, so the capacity (and the program's
#: shapes) stays 8
RECORDED = ([3, 4, 5, 6, 9], [30, 40, 50, 60, 90])
DRIFTED = {
    # key 9 became a second 4: every probe 4 would read whichever row the
    # scatter left in the table
    "duplicate_key": (([3, 4, 5, 6, 4], [30, 40, 50, 60, 90]),
                      "exact decision drift"),
    # key 9 became 40: a span of 38, past the 8-entry table. The key is in
    # no entry, and the probe 40 would read the entry of its clipped address
    "span_past_limit": (([3, 4, 5, 6, 40], [30, 40, 50, 60, 90]),
                        "capacity overflow"),
    # no live build row at all
    "no_live_row": (([None] * 5, [30, 40, 50, 60, 90]),
                    "exact decision drift"),
}
#: key 9 became 10: a span of 8, the table's last entry
MOVED = ([3, 4, 5, 6, 10], [30, 40, 50, 60, 100])
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
    """(run(build table) -> rows, the prefix of this path's drift messages)
    for CompiledQuery.run or BatchedQuery.run over a recorded program."""
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
    return run, "batched " if batched else ""


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
    # smq keeps a dimension's replicated copy by the id() of what it was
    # given (the session's scan cache keeps those alive): hold every build
    # side, or a freed one's id names the next and its copy is served again
    held = []

    def run(build: Table):
        held.append(to_device(build))
        return rows_of(smq.run({"p//k,v": staged, "b//k,w": held[-1]}))
    return run, "sharded "


PATHS = {"compiled": lambda: one_chip(False), "batched": lambda: one_chip(True),
         "mesh": mesh_replay}


#: what the replay answers over the build side it was recorded on
WANT = [(3, 1, 3, 30), (4, 2, 4, 40), (4, 3, 4, 40), (9, 4, 9, 90)]


def assert_rows(path: str, got, want: list) -> None:
    if path == "batched":
        assert got == [want, want[1:]]          # v >= 0, v >= 2
    elif path == "mesh":
        assert sorted((k, bk, w) for k, _v, bk, w in got) == \
            sorted((k, bk, w) for k, _v, bk, w in want * 4)
    else:
        assert got == want


@pytest.mark.parametrize("drift", sorted(DRIFTED))
@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_replay_over_a_drifted_build_side_re_records_and_returns_no_rows(
        path, drift):
    run, prefix = PATHS[path]()
    assert_rows(path, run(build_table(RECORDED)), WANT)
    drifted, message = DRIFTED[drift]
    with pytest.raises(ReplayMismatch, match="^" + prefix + message):
        run(build_table(drifted))


@pytest.mark.parametrize("path", sorted(PATHS))
def test_a_build_side_whose_span_moves_inside_its_bucket_replays(path):
    """The span is a ``cap``, not an ``exact``: 7 recorded, 8 met, one
    8-entry table. The same program answers, from the table's last entry
    too."""
    run, _prefix = PATHS[path]()
    assert_rows(path, run(build_table(RECORDED)), WANT)
    # probe 9 no longer matches; nothing probes 10
    assert_rows(path, run(build_table(MOVED)), WANT[:3])


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
    assert cq.join_paths[:2] == (1, 0)
    before = METRICS.snapshot()
    got = s.sql(duplicate, backend="jax").to_pylist()
    moved = METRICS.delta(before)
    assert moved.get("replay_mismatches", 0) == 1
    assert s.last_exec_stats["mode"] == "record"
    assert got == s.sql(duplicate, backend="numpy").to_pylist() == \
        [(3, 1, 30), (4, 2, 40), (4, 2, 70), (4, 3, 40), (4, 3, 70)]


def test_a_second_literal_replays_inside_the_span_bucket_and_re_records_past_it():
    """End to end through Session.sql: the literal decides which build keys
    survive. 100 and 101 record a span of 2, an 8-entry table; a literal
    that lets 105 through (a span of 6) replays the same program; one that
    lets 1000 through (901) fails the ``cap`` check, is thrown away and
    re-recorded, and both answer as the host backend does."""
    s = Session(EngineConfig())
    s.register_arrow("p", pa.table({
        "k": pa.array([100, 101, 105, 1000, 105, 99, None, 107],
                      type=pa.int64()),
        "v": pa.array(list(range(8)), type=pa.int64())}))
    s.register_arrow("b", pa.table({
        "k": pa.array([100, 101, 105, 1000], type=pa.int64()),
        "w": pa.array([1, 2, 3, 4], type=pa.int64())}))
    tpl = ("SELECT p.k, p.v, b.w FROM p JOIN b ON p.k = b.k "
           "WHERE b.w <= {w} ORDER BY 1, 2, 3")
    narrow, wider, widest = (tpl.format(w=w) for w in (2, 3, 4))
    for _ in range(3):
        got = s.sql(narrow, backend="jax").to_pylist()
    assert s.last_exec_stats["mode"] == "compiled"
    assert got == s.sql(narrow, backend="numpy").to_pylist() == \
        [(100, 0, 1), (101, 1, 2)]
    cq = s._jax_exec._plans[("sql", narrow)]["cq"]
    assert cq.join_paths[:2] == (1, 0)
    assert ("cap", 2) in cq.decisions

    before = METRICS.snapshot()
    got = s.sql(wider, backend="jax").to_pylist()
    assert METRICS.delta(before).get("replay_mismatches", 0) == 0
    assert s.last_exec_stats["mode"] == "compiled"
    assert s._jax_exec._plans[("sql", wider)]["cq"] is cq
    assert got == s.sql(wider, backend="numpy").to_pylist() == \
        [(100, 0, 1), (101, 1, 2), (105, 2, 3), (105, 4, 3)]

    before = METRICS.snapshot()
    got = s.sql(widest, backend="jax").to_pylist()
    assert METRICS.delta(before).get("replay_mismatches", 0) == 1
    assert s.last_exec_stats["mode"] == "record"
    assert got == s.sql(widest, backend="numpy").to_pylist() == \
        [(100, 0, 1), (101, 1, 2), (105, 2, 3), (105, 4, 3), (1000, 3, 4)]
    # the re-record's schedule holds the span it met, and stays direct
    again = s._jax_exec._plans[("sql", widest)]
    assert ("cap", 901) in again["decisions"]
    assert s.sql(widest, backend="jax").to_pylist() == got
    assert s._jax_exec._plans[("sql", widest)]["cq"].join_paths[:2] == (1, 0)


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
    assert cq.join_paths[:2] == (3, 0)

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
    assert s._jax_exec._plans[("sql", sql)]["cq"].join_paths[:2] == want
    for name in ("direct_joins", "sorted_joins"):
        assert name in METRICS.describe()


@pytest.mark.parametrize("late_mat_min_rows,joins", [(1 << 20, 2), (1000, 4)],
                         ids=["two_joins", "late_materialized"])
def test_query3s_shape_over_a_small_star_joins_directly(late_mat_min_rows,
                                                        joins):
    """The fact joined to two dimensions that their filters thin (``item``
    to a hundredth, ``date_dim`` to a twelfth): both build sides compact,
    neither's span shrinks, and every join of the program — with late
    materialization the two above the aggregate too — is direct."""
    rng = np.random.default_rng(38)
    ni, nd, nf, day0 = 2000, 3000, 20_000, 2_415_022
    s = Session(EngineConfig(late_mat_min_rows=late_mat_min_rows))
    s.register_arrow("item", pa.table({
        "i_item_sk": pa.array(np.arange(1, ni + 1), type=pa.int64()),
        "i_brand_id": pa.array(rng.integers(1, 40, ni), type=pa.int64()),
        "i_brand": pa.array([f"brand#{b}" for b in rng.integers(1, 40, ni)]),
        "i_manufact_id": pa.array(rng.integers(1, 100, ni),
                                  type=pa.int64())}))
    s.register_arrow("date_dim", pa.table({
        "d_date_sk": pa.array(np.arange(day0, day0 + nd), type=pa.int64()),
        "d_year": pa.array(1900 + np.arange(nd) // 365, type=pa.int64()),
        "d_moy": pa.array(1 + (np.arange(nd) // 30) % 12, type=pa.int64())}))
    s.register_arrow("store_sales", pa.table({
        "ss_sold_date_sk": pa.array(rng.integers(day0, day0 + nd, nf),
                                    type=pa.int64()),
        "ss_item_sk": pa.array(rng.integers(1, ni + 1, nf), type=pa.int64()),
        "ss_ext_sales_price": pa.array(rng.integers(1, 10_000, nf),
                                       type=pa.int64())}))
    sql = ("SELECT dt.d_year, item.i_brand_id brand_id, item.i_brand brand, "
           "SUM(ss_ext_sales_price) sum_agg "
           "FROM date_dim dt, store_sales, item "
           "WHERE dt.d_date_sk = store_sales.ss_sold_date_sk "
           "AND store_sales.ss_item_sk = item.i_item_sk "
           "AND item.i_manufact_id = 28 AND dt.d_moy = 11 "
           "GROUP BY dt.d_year, item.i_brand, item.i_brand_id "
           "ORDER BY dt.d_year, sum_agg DESC, brand_id LIMIT 100")
    want = s.sql(sql, backend="numpy").to_pylist()
    for _ in range(2):
        got = s.sql(sql, backend="jax").to_pylist()
    assert got == want and got
    assert s.last_exec_stats["mode"] in ("compiled", "compile+run")
    assert s._jax_exec._plans[("sql", sql)]["cq"].join_paths[:2] == (joins, 0)
