"""The four row counters beside ``direct_joins`` / ``sorted_joins`` (ISSUE
42): ``scan_rows``, ``direct_probe_rows``, ``sorted_probe_rows`` and
``expanded_join_rows``.

Each is a sum of capacities — shapes of the traced program, so Python
integers fixed when the program is built (``JaxExecutor.join_paths``, handed
to ``CompiledQuery`` / ``ShardedMorselQuery`` as ``join_paths``) — and moves
once a dispatch by that sum: a batched dispatch once whatever rows ride it,
a sharded morsel once and by the local program's rows, not once a replica.
The eager record pass and the host backend move none. Tables of a dozen
rows, programs of one join. The last case is ISSUE 43's ``star_joins``: a
plan-shape count beside them, moved the same way.
"""
import jax
import pytest
from test_fast_join import (PROBE, RECORDED, build_table, col, join_plan,
                            mesh_replay, one_chip, rows_of, scan)

from nds_tpu.engine.column import Table
from nds_tpu.engine.executor import Executor
from nds_tpu.engine.jax_backend.device import bucket
from nds_tpu.engine.jax_backend.executor import (CompiledQuery, JaxExecutor,
                                                 count_join_paths)
from nds_tpu.engine.plan import JoinNode, iter_plan_nodes
from nds_tpu.obs.metrics import METRICS

COUNTERS = ("direct_joins", "sorted_joins", "scan_rows", "direct_probe_rows",
            "sorted_probe_rows", "expanded_join_rows")
#: ten probe rows stand in a 16-row buffer, a build side of up to eight rows
#: in an 8-row one
PROBE_KEYS = [1, 2, 2, 3, 5, 5, 5, 8, None, 0]
UNIQUE = [1, 2, 3, 5]
#: 2 twice and 5 three times: the probe's 2, 2 and 5, 5, 5 match 4 + 9 rows
DUPLICATES = [1, 2, 2, 5, 5, 5]
MATCHES = 1 + 2 * 2 + 1 * 0 + 3 * 3          # of PROBE_KEYS in DUPLICATES


def moved(before) -> tuple:
    d = METRICS.delta(before)
    return tuple(d.get(name, 0) for name in COUNTERS)


def tables(build: list) -> dict:
    return {"p": Table(["k", "v"], [col(PROBE_KEYS),
                                    col(list(range(len(PROBE_KEYS))))]),
            "b": Table(["k", "w"], [col(build), col([7] * len(build))])}


def cross_plan() -> JoinNode:
    return JoinNode(scan("p", ["k", "v"]), scan("b", ["k", "w"], ["bk", "w"]),
                    "cross", [], [], out_names=["k", "v", "bk", "w"],
                    out_dtypes=["int"] * 4)


#: name -> (plan, build keys, (direct, sorted, scan, direct probe, sorted
#: probe, expanded) of the compiled program)
CASES = {
    # one gather a probe row: the probe's 16-row buffer, no expansion
    "direct_inner": (lambda: join_plan("inner", False, False), UNIQUE,
                     (1, 0, 16 + 8, 16, 0, 0)),
    "direct_left_outer": (lambda: join_plan("left", False, False), UNIQUE,
                          (1, 0, 16 + 8, 16, 0, 0)),
    "direct_semi": (lambda: join_plan("semi", False, False), UNIQUE,
                    (1, 0, 16 + 8, 16, 0, 0)),
    # duplicates on the build side: through the sort, and the 14 matched
    # pairs are materialised in a 16-row buffer
    "sorted_inner_expands": (lambda: join_plan("inner", False, False),
                             DUPLICATES, (0, 1, 16 + 8, 0, 16,
                                          bucket(MATCHES))),
    "sorted_left_outer_expands": (lambda: join_plan("left", False, False),
                                  DUPLICATES, (0, 1, 16 + 8, 0, 16,
                                               bucket(MATCHES))),
    # a semi join without a residual needs the match counts alone
    "sorted_semi_counts_only": (lambda: join_plan("semi", False, False),
                                DUPLICATES, (0, 1, 16 + 8, 0, 16, 0)),
    # with one it expands, evaluates and reduces
    "sorted_semi_residual_expands": (
        lambda: join_plan("semi", True, False), DUPLICATES,
        (0, 1, 16 + 8, 0, 16, bucket(MATCHES))),
    # a cross join is neither path: every probe row times the six build rows
    "cross_expands": (cross_plan, DUPLICATES,
                      (0, 0, 16 + 8, 0, 0, bucket(10 * 6))),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_compiled_program_counts_its_rows_once_a_dispatch(case):
    make, build, want = CASES[case]
    assert MATCHES == 14
    plan, data = make(), tables(build)
    oracle = Executor(data.__getitem__).execute(plan).to_pylist()
    with jax.enable_x64(True):
        ex = JaxExecutor(data.__getitem__)
        before = METRICS.snapshot()
        out, decisions, scan_keys = ex.record_plan(plan)
        assert moved(before) == (0,) * 6        # the record pass moves none
        assert ex.join_paths == want            # ... and sums the same rows
        cq = CompiledQuery(plan, decisions, scan_keys)
        assert cq.join_paths == (0, 0)          # not traced yet: zeros
        scans = ex._scans_for({"scan_keys": scan_keys})
        for dispatch in (1, 2, 3):
            got = rows_of(cq.run(scans))
            assert moved(before) == tuple(dispatch * n for n in want)
    assert cq.join_paths == want
    assert sorted(map(repr, got)) == sorted(map(repr, oracle))
    assert rows_of(out) == got


def test_a_short_tuple_reads_as_zeros():
    """``shard_exec`` and ``CompiledQuery`` start at ``(0, 0)`` and hand the
    tuple through as they find it: the four row counters read what is not
    there as nothing."""
    before = METRICS.snapshot()
    count_join_paths((0, 0))
    count_join_paths((2, 1))
    assert moved(before) == (2, 1, 0, 0, 0, 0)
    count_join_paths((1, 0, 24, 16, 0, 0))
    assert moved(before) == (3, 1, 24, 16, 0, 0)
    described = METRICS.describe()
    for name in COUNTERS:
        assert name in described


def test_a_batched_dispatch_counts_once_whatever_rows_ride_it():
    """Two parameter rows in one stacked dispatch: one program ran."""
    run, _prefix = one_chip(batched=True)
    before = METRICS.snapshot()
    got = run(build_table(RECORDED))
    assert len(got) == 2
    # p and b stand in 8-row buffers; the filter under the join compacts p
    # into a buffer of the same rung
    assert moved(before) == (1, 0, 8 + 8, 8, 0, 0)
    run(build_table(RECORDED))
    assert moved(before) == (2, 0, 2 * 16, 2 * 8, 0, 0)


def test_the_unbatched_dispatch_of_the_same_program_counts_the_same():
    run, _prefix = one_chip(batched=False)
    before = METRICS.snapshot()
    run(build_table(RECORDED))
    assert moved(before) == (1, 0, 8 + 8, 8, 0, 0)


def test_a_sharded_morsel_counts_the_local_programs_rows_once_a_dispatch():
    """Four replicas each probe their 8-row block of the 32-row morsel into
    the replicated 8-row dimension: one dispatch, the local program's rows,
    not four times them."""
    run, _prefix = mesh_replay()
    before = METRICS.snapshot()
    got = run(build_table(RECORDED))
    assert len(got) == 4 * 4 and len(PROBE[0]) == 8
    assert moved(before) == (1, 0, 8 + 8, 8, 0, 0)
    run(build_table(RECORDED))
    assert moved(before) == (2, 0, 2 * 16, 2 * 8, 0, 0)


#: two facts, each with a unique-key dimension of its own, joined on a key
#: neither side holds once (ISSUE 43): the planner joins f2 to d2 first and
#: hands that tree to ONE join as its build side
TWO_STARS = ("SELECT d1.a, d2.b, COUNT(*) AS c FROM f1, d1, f2, d2 "
             "WHERE f1.k1 = d1.k AND f2.k2 = d2.k AND f1.j = f2.j "
             "GROUP BY d1.a, d2.b ORDER BY 1, 2")
ONE_STAR = ("SELECT d1.a, d2.b, COUNT(*) AS c FROM f1, d1, d2 "
            "WHERE f1.k1 = d1.k AND f1.j = d2.k "
            "GROUP BY d1.a, d2.b ORDER BY 1, 2")


@pytest.mark.parametrize("sql,want", [(TWO_STARS, 1), (ONE_STAR, 0)],
                         ids=["two_stars", "one_star"])
def test_star_joins_counts_a_star_build_join_once_a_dispatch(sql, want):
    """``star_joins`` moves at each dispatch by the JoinNodes of the program
    whose build side is a star's own join tree (``CompiledQuery.
    plan_shapes``): 1 for a fact-to-fact join of two stars, 0 for a
    statement whose joins are all fact-to-dimension; the record pass and
    the host backend move none."""
    import pyarrow as pa

    from nds_tpu.engine import Session
    s = Session()
    ints = pa.int64()
    s.register_arrow("f1", pa.table({
        "k1": pa.array([0, 1, 2, 1, 0, 2, 1, 3], type=ints),
        "j": pa.array([5, 5, 6, 7, 6, 5, 7, 9], type=ints)}), est_rows=800)
    s.register_arrow("f2", pa.table({
        "k2": pa.array([1, 0, 1, 2, 0], type=ints),
        "j": pa.array([5, 6, 6, 7, 8], type=ints)}), est_rows=500)
    for name in ("d1", "d2"):
        s.register_arrow(name, pa.table({
            "k": pa.array([0, 1, 2, 5, 6], type=ints),
            "a" if name == "d1" else "b":
                pa.array([10, 11, 12, 15, 16], type=ints)}),
            unique_cols=("k",))
    before = METRICS.snapshot()
    oracle = s.sql(sql, backend="numpy").to_pylist()
    assert len(oracle) > 1
    s.sql(sql, backend="jax")                   # the record pass
    assert METRICS.delta(before).get("star_joins", 0) == 0
    for dispatch in (1, 2):
        got = s.sql(sql, backend="jax")
        assert s.last_exec_stats["mode"] in ("compiled", "compile+run")
        assert METRICS.delta(before).get("star_joins", 0) == dispatch * want
    assert got.to_pylist() == oracle
    cq = s._jax_exec._plans[("sql", sql)]["cq"]
    assert cq.plan_shapes[-1] == want
    stars = [n for n in iter_plan_nodes(cq.plan)
             if isinstance(n, JoinNode) and n.star_build]
    assert len(stars) == want
    for n in stars:
        assert isinstance(n.right, JoinNode) and len(n.left_keys) == 1
    assert "star_joins" in METRICS.describe()
