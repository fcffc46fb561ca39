"""Whole-plan record/replay compilation (engine/jax_backend/executor).

The engine's steady-state contract: the second execution of a query (same
table registrations) runs as ONE jitted XLA program whose capacities come
from the recorded schedule, verified by device-computed check scalars.
"""
import numpy as np
import pyarrow as pa
import pytest

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session


QUERY = """
SELECT d.grp, COUNT(*) AS cnt, SUM(f.qty) AS tq, AVG(f.price) AS ap,
       MAX(f.price) AS mp,
       RANK() OVER (ORDER BY SUM(f.qty) DESC) AS rk
FROM fact f JOIN dim d ON f.fk = d.dk
WHERE f.day BETWEEN 30 AND 120 AND f.qty > 5
GROUP BY d.grp ORDER BY d.grp
"""


def star_session(n_fact=20000, n_dim=500):
    rng = np.random.default_rng(7)
    fact = pa.table({
        "fk": pa.array(rng.integers(0, n_dim + 20, n_fact), type=pa.int32()),
        "qty": pa.array(rng.integers(1, 100, n_fact), type=pa.int32()),
        "price": pa.array(np.round(rng.uniform(0.5, 999.0, n_fact), 2)),
        "day": pa.array(rng.integers(0, 365, n_fact), type=pa.int32()),
    })
    dim = pa.table({"dk": pa.array(np.arange(n_dim), type=pa.int32()),
                    "grp": pa.array((np.arange(n_dim) % 23).astype(np.int32))})
    s = Session()
    s.register_arrow("fact", fact)
    s.register_arrow("dim", dim)
    return s


def assert_tables_equal(a, b, rtol=1e-9):
    assert a.num_rows == b.num_rows
    for name, ca, cb in zip(a.names, a.columns, b.columns):
        assert ca.validity.tolist() == cb.validity.tolist(), name
        va = np.asarray(ca.data, dtype=float)[ca.validity]
        vb = np.asarray(cb.data, dtype=float)[cb.validity]
        assert np.allclose(va, vb, rtol=rtol), name


def test_compiled_replay_matches_oracle_and_record():
    s = star_session()
    oracle = s.sql(QUERY, backend="numpy")
    first = s.sql(QUERY, backend="jax")       # record pass
    second = s.sql(QUERY, backend="jax")      # compile + run
    third = s.sql(QUERY, backend="jax")       # steady state
    ent = s._jax_exec._plans[("sql", QUERY)]
    assert ent["cq"] is not None and not ent["nojit"], ent.get("nojit_reason")
    assert s.last_exec_stats["mode"] == "compiled"
    assert s.last_exec_stats["device_ms"] > 0
    assert_tables_equal(oracle, first, rtol=1e-6)
    assert_tables_equal(first, second)
    assert_tables_equal(second, third)


def test_schedule_invalidation_on_data_change():
    s = star_session()
    s.sql(QUERY, backend="jax")
    s.sql(QUERY, backend="jax")
    assert s._jax_exec._plans[("sql", QUERY)]["cq"] is not None
    # re-registering a table bumps the generation: new executor, no stale plan
    rng = np.random.default_rng(8)
    s.register_arrow("fact", pa.table({
        "fk": pa.array(rng.integers(0, 520, 40000), type=pa.int32()),
        "qty": pa.array(rng.integers(1, 100, 40000), type=pa.int32()),
        "price": pa.array(rng.uniform(0.5, 999.0, 40000)),
        "day": pa.array(rng.integers(0, 365, 40000), type=pa.int32()),
    }))
    oracle = s.sql(QUERY, backend="numpy")
    result = s.sql(QUERY, backend="jax")
    assert_tables_equal(oracle, result, rtol=1e-6)


def test_replay_mismatch_detection():
    from nds_tpu.engine.jax_backend.executor import (ReplayMismatch,
                                                     _verify_schedule)
    _verify_schedule([("cap", 10), ("exact", 1)], [10, 1])
    _verify_schedule([("cap", 10)], [16])       # within bucket slack
    with pytest.raises(ReplayMismatch):
        _verify_schedule([("cap", 10)], [17])   # beyond bucket(10)=16
    with pytest.raises(ReplayMismatch):
        _verify_schedule([("exact", 0)], [1])


def test_jit_plans_off():
    cfg = EngineConfig(jit_plans=False)
    s = star_session()
    s.config = cfg
    s.sql(QUERY, backend="jax")
    s.sql(QUERY, backend="jax")
    assert s._jax_exec._plans == {}


@pytest.mark.slow  # 8-virtual-device whole-plan compile
def test_mesh_sharded_compiled_run():
    """8-virtual-device SPMD: fact scan row-sharded, plan GSPMD-partitioned."""
    import jax

    cfg = EngineConfig(mesh_shape=(8,), shard_min_rows=1024)
    s = star_session(n_fact=1 << 15)
    s.config = cfg
    s._jax_exec = None  # rebuild executor with the mesh
    oracle = s.sql(QUERY, backend="numpy")
    s.sql(QUERY, backend="jax")
    result = s.sql(QUERY, backend="jax")
    assert_tables_equal(oracle, result, rtol=1e-6)
    ex = s._jax_exec
    fact_keys = [k for k in ex._scan_cache if k.startswith("fact//")]
    assert fact_keys
    spec = ex._scan_cache[fact_keys[0]].cols[0].data.sharding.spec
    assert len(spec) == 1 and spec[0] == "shards"


# -- keyless aggregates: the group count is a static 1 -----------------------

def keyless_table(n: int) -> pa.Table:
    rng = np.random.default_rng(3)
    return pa.table({
        "g": pa.array(rng.integers(0, 5, n), type=pa.int32()),
        "h": pa.array(rng.integers(0, 3, n), type=pa.int32()),
        "v": pa.array(rng.integers(0, 1000, n), type=pa.int64())})


def keyless_expected(t: pa.Table, shape: str) -> list:
    """The answers by plain numpy, independent of the engine."""
    g, h, v = (t.column(c).to_numpy() for c in ("g", "h", "v"))
    if shape == "resident":
        m = v < 500
        return [(int(m.sum()), int(v[m].sum()), float(v[m].mean()),
                 int(v[m].min()))]
    if shape == "resident_empty":
        return [(0, None, None, None)]
    rows = [(None, None, len(v), int(v.sum()))]       # the grand total
    for gi in np.unique(g):
        rows.append((int(gi), None, int((g == gi).sum()),
                     int(v[g == gi].sum())))
        for hi in np.unique(h[g == gi]):
            m = (g == gi) & (h == hi)
            rows.append((int(gi), int(hi), int(m.sum()), int(v[m].sum())))
    return rows


KEYLESS_SQL = {
    "resident": "SELECT COUNT(*) c, SUM(v) sv, AVG(v) av, MIN(v) lo "
                "FROM t WHERE v < 500",
    "resident_empty": "SELECT COUNT(*) c, SUM(v) sv, AVG(v) av, MIN(v) lo "
                      "FROM t WHERE v < 0",
    "rollup_total": "SELECT g, h, COUNT(*) c, SUM(v) sv FROM t "
                    "GROUP BY ROLLUP(g, h)",
}


# 20000 rows take _aggregate_sorted (its k == 0 level is _aggregate_one too)
@pytest.mark.parametrize("n", [2000, 20000])
@pytest.mark.parametrize("shape", sorted(KEYLESS_SQL))
def test_keyless_aggregate_resident_and_rollup_grand_total(shape, n):
    t = keyless_table(n)
    s = Session()
    s.register_arrow("t", t)
    q = KEYLESS_SQL[shape]
    want = sorted(keyless_expected(t, shape), key=repr)
    assert sorted(s.sql(q, backend="numpy").to_pylist(), key=repr) == want
    for mode in ("record", "compile+run", "compiled"):
        assert sorted(s.sql(q, backend="jax").to_pylist(), key=repr) == want
    assert s.last_exec_stats["mode"] == "compiled"
    cq = s._jax_exec._plans[("sql", q)]["cq"]
    agg_caps = [v for (kind, v), node in zip(cq.decisions, cq.decision_nodes)
                if kind == "cap" and (node or "").startswith("AggregateNode")]
    # only the keyed grouping sets decide a capacity: (g, h) and (g)
    assert agg_caps == ([15, 5] if shape == "rollup_total" else [])
