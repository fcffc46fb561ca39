"""Pallas kernel property suite (ISSUE 7).

Under JAX_PLATFORMS=cpu (conftest) the kernels run in Pallas INTERPRET
mode — the real kernel bodies execute, so tier-1 CI proves the code paths
the TPU will compile. Three layers:

- kernel-level: each pallas_kernels entry point vs the XLA lowering it
  replaces, bit-identical over randomized (values, validity, alive,
  capacity-pad) inputs including all-NULL, all-dead, single-group and
  max-capacity edges;
- engine-level: kernels.py dispatch seams with the op flags on vs off,
  and full Session SQL against the numpy oracle backend (ops.py);
- workload-level (slow marks): the on/off bit-identity differential
  through the independent SQLite oracle for q9/q22/q67/q95 at SF0.01 —
  the attribution-table target queries.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from nds_tpu.config import EngineConfig
from nds_tpu.engine import Session, arrow_bridge
from nds_tpu.engine.jax_backend import kernels
from nds_tpu.engine.jax_backend import pallas_kernels as pk

ALL_OPS = frozenset({"sort", "groupby", "gather"})


@pytest.fixture(autouse=True)
def _ops_off_after():
    """Every test leaves the thread-local op set empty: other suites in
    the same process must keep measuring the pure XLA lowering."""
    yield
    pk.set_active(frozenset())


def test_probe_interpret_under_cpu():
    mode, reason = pk.probe()
    assert mode == "interpret"


def test_parse_ops_validates():
    assert pk.parse_ops("sort,gather") == frozenset({"sort", "gather"})
    assert pk.parse_ops(("groupby",)) == frozenset({"groupby"})
    assert pk.parse_ops("sort, bogus") == frozenset({"sort"})   # dropped
    assert pk.parse_ops(None) == frozenset()
    assert pk.parse_ops("") == frozenset()


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,dtype", [
    (1, jnp.int64), (2, jnp.int64), (5, jnp.int32), (64, jnp.int64),
    (1000, jnp.int32), (4096, jnp.int64), (6144, jnp.int32)])
def test_sort_pairs_matches_stable_sort(n, dtype):
    rng = np.random.default_rng(n)
    key = jnp.asarray(rng.integers(-9, 9, n), dtype)     # heavy ties
    # sentinel block: dead rows ride iinfo.max exactly like the engine
    key = key.at[: n // 3].set(jnp.iinfo(dtype).max)
    idx = jnp.arange(n, dtype=jnp.int32)
    pk.set_active(ALL_OPS)
    got_k, got_i = pk.sort_pairs(key, idx)
    want_k, want_i = lax.sort((key, idx), num_keys=1, is_stable=True)
    assert jnp.array_equal(got_k, want_k)
    assert jnp.array_equal(got_i, want_i)


def test_sort_pairs_all_equal_and_sorted_inputs():
    pk.set_active(ALL_OPS)
    n = 1000
    idx = jnp.arange(n, dtype=jnp.int32)
    for key in (jnp.zeros(n, jnp.int64),
                jnp.arange(n, dtype=jnp.int64),
                jnp.arange(n, 0, -1).astype(jnp.int64)):
        got = pk.sort_pairs(key, idx)
        want = lax.sort((key, idx), num_keys=1, is_stable=True)
        assert jnp.array_equal(got[0], want[0])
        assert jnp.array_equal(got[1], want[1])


def test_sort_pairs_under_jit():
    pk.set_active(ALL_OPS)
    rng = np.random.default_rng(3)
    key = jnp.asarray(rng.integers(0, 5, 4096), jnp.int64)
    idx = jnp.arange(4096, dtype=jnp.int32)
    got = jax.jit(pk.sort_pairs)(key, idx)
    want = lax.sort((key, idx), num_keys=1, is_stable=True)
    assert jnp.array_equal(got[0], want[0])
    assert jnp.array_equal(got[1], want[1])


@pytest.mark.parametrize("cap", [1, 2, 64, 1000, pk.GROUPBY_MAX_SEGMENTS])
def test_seg_reduce_matches_segment_ops(cap):
    rng = np.random.default_rng(cap)
    n = 4096
    # gid includes the dead-row sentinel (== cap): contributes nothing
    gid = jnp.asarray(rng.integers(0, cap + 1, n), jnp.int32)
    d_int = jnp.asarray(rng.integers(-1000, 1000, n), jnp.int64)
    d_f = jnp.asarray(rng.uniform(-5, 5, n), jnp.float64)
    pk.set_active(ALL_OPS)
    s, mn, mx, fmn = pk.seg_reduce_multi(
        [(d_int, "sum"), (d_int, "min"), (d_int, "max"), (d_f, "min")],
        gid, cap)
    sg = jnp.where(gid < cap, gid, cap)
    assert jnp.array_equal(s, jax.ops.segment_sum(d_int, sg,
                                                  num_segments=cap))
    assert jnp.array_equal(mn, jax.ops.segment_min(d_int, sg,
                                                   num_segments=cap))
    assert jnp.array_equal(mx, jax.ops.segment_max(d_int, sg,
                                                   num_segments=cap))
    assert jnp.array_equal(fmn, jax.ops.segment_min(d_f, sg,
                                                    num_segments=cap))


def test_seg_reduce_all_dead_and_single_group():
    pk.set_active(ALL_OPS)
    n, cap = 300, 8
    d = jnp.arange(n, dtype=jnp.int64)
    # all dead: every gid at the sentinel -> sum 0, min/max at identity
    dead = jnp.full(n, cap, jnp.int32)
    s = pk.seg_reduce(d, dead, cap, "sum")
    mn = pk.seg_reduce(d, dead, cap, "min")
    assert jnp.array_equal(s, jnp.zeros(cap, jnp.int64))
    assert jnp.array_equal(mn, jax.ops.segment_min(
        d, jnp.where(dead < cap, dead, cap), num_segments=cap))
    # single group
    one = jnp.zeros(n, jnp.int32)
    assert int(pk.seg_reduce(d, one, 1, "sum")[0]) == int(d.sum())


def test_seg_supported_gates():
    d_int = jnp.zeros(10, jnp.int64)
    d_f = jnp.zeros(10, jnp.float64)
    assert pk.seg_supported(d_int, 16, "sum")
    assert not pk.seg_supported(d_f, 16, "sum")          # float sum order
    assert pk.seg_supported(d_f, 16, "min")
    assert not pk.seg_supported(d_int, pk.GROUPBY_MAX_SEGMENTS + 1, "sum")
    assert not pk.seg_supported(d_int, 0, "sum")
    assert not pk.seg_supported(jnp.zeros(10, bool), 16, "max")


def test_take_many_dtypes_and_fallback():
    rng = np.random.default_rng(11)
    pk.set_active(ALL_OPS)
    srcs = [jnp.asarray(rng.integers(0, 1 << 30, 1000), jnp.int64),
            jnp.asarray(rng.random(1000) < 0.5),             # bool
            jnp.asarray(rng.random(1000), jnp.float64),
            jnp.asarray(rng.integers(0, 100, 1000), jnp.int32)]
    # over-budget source: falls back to the XLA gather inside take_many
    big = jnp.asarray(rng.integers(0, 9, (pk.GATHER_SRC_BYTES // 8) + 1),
                      jnp.int64)
    for n_idx in (1, 7, 777, 5000):                      # non-block-multiple
        idx = jnp.asarray(rng.integers(0, 1000, n_idx), jnp.int32)
        out = pk.take_many(srcs + [big[:1000]], idx)
        for got, s in zip(out, srcs + [big[:1000]]):
            assert got.dtype == s.dtype
            assert jnp.array_equal(got, s[idx])
    bidx = jnp.asarray(rng.integers(0, big.shape[0], 64), jnp.int32)
    assert jnp.array_equal(pk.take(big, bidx), big[bidx])
    assert not pk.gather_supported(big)


# ---------------------------------------------------------------------------
# engine dispatch seams: flag on vs off, bit-identical
# ---------------------------------------------------------------------------

def _rand_col(rng, n, null_frac=0.1, dtype=jnp.int64, lo=-50, hi=50):
    data = jnp.asarray(rng.integers(lo, hi, n), dtype)
    valid = jnp.asarray(rng.random(n) >= null_frac)
    return jnp.where(valid, data, jnp.zeros((), dtype)), valid


@pytest.mark.parametrize("case", ["random", "all_null", "all_dead",
                                  "single_group", "cap_edge"])
def test_dense_rank_packsort_on_off(case):
    rng = np.random.default_rng(17)
    n = 12288 if case == "cap_edge" else 9000     # >= 1<<13 packsort gate
    data, valid = _rand_col(rng, n)
    alive = jnp.asarray(rng.random(n) < 0.8)
    if case == "all_null":
        valid = jnp.zeros(n, bool)
    elif case == "all_dead":
        alive = jnp.zeros(n, bool)
    elif case == "single_group":
        data, valid = jnp.zeros(n, jnp.int64), jnp.ones(n, bool)
    outs = []
    for ops in (frozenset(), ALL_OPS):
        pk.set_active(ops)
        gid, ng = kernels.dense_rank_packsort([data], [valid], alive)
        outs.append((np.asarray(gid), int(ng)))
    pk.set_active(frozenset())
    assert outs[0][1] == outs[1][1]
    assert np.array_equal(outs[0][0], outs[1][0])


def test_compaction_build_side_unscatter_on_off():
    rng = np.random.default_rng(23)
    n = 9000                                  # above SORT_MIN_ROWS
    alive = jnp.asarray(rng.random(n) < 0.6)
    gid = jnp.asarray(rng.integers(0, 64, n), jnp.int32)
    vals = jnp.asarray(rng.uniform(-1, 1, n), jnp.float64)
    bval = jnp.asarray(rng.random(n) < 0.5)
    res = []
    for ops in (frozenset(), ALL_OPS):
        pk.set_active(ops)
        perm, cnt = kernels.compaction_perm(alive)
        sg, bperm = kernels.build_side(gid, alive)
        un = kernels.unscatter(perm, (vals, bval))
        res.append((np.asarray(perm), int(cnt), np.asarray(sg),
                    np.asarray(bperm), np.asarray(un[0]), np.asarray(un[1])))
    pk.set_active(frozenset())
    for a, b in zip(*res):
        assert np.array_equal(a, b)


@pytest.mark.parametrize("func", ["count_star", "count", "sum", "min",
                                  "max", "avg", "stddev_samp"])
def test_agg_apply_on_off(func):
    rng = np.random.default_rng(abs(hash(func)) % 1000)
    n, cap = 6000, 37                         # above GROUPBY_MIN_ROWS
    data, valid = _rand_col(rng, n)
    alive = jnp.asarray(rng.random(n) < 0.7)
    gid = jnp.where(alive, jnp.asarray(rng.integers(0, cap, n), jnp.int32),
                    cap)
    arg = None if func == "count_star" else (data, valid)
    res = []
    for ops in (frozenset(), ALL_OPS):
        pk.set_active(ops)
        vals, v = kernels.agg_apply(gid, alive, func, arg, cap)
        res.append((np.asarray(vals), np.asarray(v)))
    pk.set_active(frozenset())
    assert np.array_equal(res[0][0], res[1][0]), func   # bit-identical
    assert np.array_equal(res[0][1], res[1][1]), func


# ---------------------------------------------------------------------------
# session level: SQL on/off vs the numpy oracle (ops.py)
# ---------------------------------------------------------------------------

def _mk_tables(rng, n_fact=9_100, n_dim=300):
    # n_fact sits above the 1<<13 packsort gate but buckets to a small
    # capacity: the session tests exercise every pallas seam while keeping
    # first-compile of the sort network cheap for the tier-1 budget
    import pyarrow as pa
    qty = rng.integers(1, 50, n_fact).astype(object)
    qty[rng.random(n_fact) < 0.07] = None
    fact = pa.table({
        "fk": pa.array(rng.integers(0, n_dim + 9, n_fact),
                       type=pa.int32()),
        "qty": pa.array(list(qty), type=pa.int32()),
        "price": pa.array(np.round(rng.uniform(1, 100, n_fact), 2)),
        "day": pa.array(rng.integers(0, 365, n_fact), type=pa.int32()),
    })
    dim = pa.table({"dk": pa.array(np.arange(n_dim), type=pa.int32()),
                    "grp": pa.array((np.arange(n_dim) % 13)
                                    .astype(np.int32))})
    return fact, dim


Q_AGG = ("SELECT d.grp, COUNT(*) c, SUM(f.qty) s, MIN(f.day) mn, "
         "MAX(f.price) mx, AVG(f.qty) a FROM fact f JOIN dim d "
         "ON f.fk = d.dk WHERE f.day < 300 GROUP BY d.grp ORDER BY d.grp")
Q_WINDOW = ("SELECT dk, grp, RANK() OVER (PARTITION BY grp ORDER BY dk) r "
            "FROM dim ORDER BY grp, dk")
Q_TOPK = ("SELECT fk, qty FROM fact WHERE qty IS NOT NULL "
          "ORDER BY qty DESC, fk LIMIT 50")


def _rows(t):
    return [tuple(r) for r in arrow_bridge.to_arrow(t).to_pylist()]


@pytest.fixture(scope="module")
def tables():
    return _mk_tables(np.random.default_rng(7))


def _session(tables, ops):
    fact, dim = tables
    s = Session(EngineConfig(pallas_ops=tuple(sorted(ops))))
    s.register_arrow("fact", fact)
    s.register_arrow("dim", dim)
    return s


@pytest.mark.parametrize("q", [Q_AGG, Q_WINDOW, Q_TOPK])
def test_sql_on_off_bit_identity_and_oracle(tables, q):
    """Flag on/off bit-identity across record AND compiled replay, plus
    the ops.py numpy-oracle differential."""
    got = {}
    for name, ops in (("off", ()), ("on", ("sort", "groupby", "gather"))):
        s = _session(tables, ops)
        # run 1 records eagerly, run 2 replays the compiled program: the
        # pair pins record-vs-compiled bit-identity per mode
        runs = [_rows(s.sql(q, backend="jax")) for _ in range(2)]
        assert runs[0] == runs[1], (name, "replay drift")
        if ops:
            assert s.last_exec_stats.get("pallas_ops") == \
                ["gather", "groupby", "sort"]
            assert "pallas_fallback_reason" not in s.last_exec_stats
        got[name] = runs[0]
    assert got["on"] == got["off"], "pallas on/off differ"
    s = _session(tables, ())
    assert got["on"] == _rows(s.sql(q, backend="numpy"))


def test_live_toggle_invalidates_programs(tables):
    """Flipping pallas_ops on a LIVE session must re-record (the cached
    programs embed the kernel choice), still bit-identically."""
    s = _session(tables, ())
    a = _rows(s.sql(Q_AGG, backend="jax"))
    s.config.pallas_ops = ("sort", "gather")
    b = _rows(s.sql(Q_AGG, backend="jax"))
    assert s.last_exec_stats.get("mode") in ("record", "adopted")
    assert s.last_exec_stats.get("pallas_ops") == ["gather", "sort"]
    s.config.pallas_ops = ()
    c = _rows(s.sql(Q_AGG, backend="jax"))
    assert a == b == c


def test_requested_op_on_unusable_platform_is_loud(tables, monkeypatch):
    """A requested kernel that cannot run is an error naming the op and the
    reason — never a quiet XLA substitute, a host fallback, or an eager
    rescue (PallasLoweringError is none of the executor's nojit errors)."""
    monkeypatch.setattr(pk, "_PROBE", ("off", "no TPU pallas lowering on "
                                       "backend 'fake'"))
    s = _session(tables, ("sort", "groupby", "gather"))
    with pytest.raises(pk.PallasLoweringError, match="backend 'fake'"):
        s.sql(Q_AGG, backend="jax")
    # nothing requested -> nothing probed: the XLA path is untouched
    assert _session(tables, ()).sql(Q_AGG, backend="jax").num_rows > 0


def test_mosaic_refusal_names_the_kernel(monkeypatch):
    """On a TPU each kernel signature compiles standalone once; a compiler
    refusal surfaces as PallasLoweringError naming kernel + message (faked
    here: non-interpret mode on the CPU backend cannot lower Mosaic)."""
    monkeypatch.setattr(pk, "_PROBE", ("tpu", ""))
    pk._gather_call.cache_clear()
    try:
        with pytest.raises(pk.PallasLoweringError,
                           match=r"pallas kernel gather\[n=4096"):
            pk.take(jnp.arange(5000, dtype=jnp.int32),
                    jnp.zeros(4096, jnp.int32))
    finally:
        pk._gather_call.cache_clear()


def test_streaming_path_on_off(tables):
    """The out-of-core morsel path executes through its own executor: the
    flag must reach it (stream-config key) and stay bit-identical."""
    fact, dim = tables
    got = {}
    for name, ops in (("off", ()), ("on", ("sort", "groupby", "gather"))):
        cfg = EngineConfig(pallas_ops=ops, out_of_core=True,
                           chunk_rows=4096, out_of_core_min_rows=5_000)
        s = Session(cfg)
        s.register_arrow("fact", fact)
        s.register_arrow("dim", dim)
        q = ("SELECT d.grp, SUM(f.qty) s FROM fact f JOIN dim d "
             "ON f.fk = d.dk GROUP BY d.grp ORDER BY d.grp")
        got[name] = _rows(s.sql(q, backend="jax"))
        assert s.last_exec_stats["mode"] == "streaming"
        if ops:
            assert s.last_exec_stats.get("pallas_ops")
            # the cached morsel programs must CARRY the op set: their
            # compiled replay otherwise silently traces with kernels off
            sent = s._stream_cache[q]
            for st in sent["gstates"]:
                assert st["cqs"], "no morsel programs recorded"
                for cq in st["cqs"]:
                    assert cq.pallas_ops == frozenset(ops)
    assert got["on"] == got["off"]


def test_pallas_metrics_move(tables):
    from nds_tpu.obs.metrics import METRICS
    before = {k: v for k, v in METRICS.snapshot().items()
              if k.startswith("pallas_")}
    s = _session(tables, ("sort", "gather"))
    s.sql(Q_AGG, backend="jax")
    after = {k: v for k, v in METRICS.snapshot().items()
             if k.startswith("pallas_")}
    assert after["pallas_sort_calls"] > before.get("pallas_sort_calls", 0)
    assert after["pallas_gather_calls"] > before.get("pallas_gather_calls", 0)


# ---------------------------------------------------------------------------
# workload level: SQLite-oracle on/off differential, attribution targets
# (SF0.01; slow — the full-suite CI test stage runs them, tier-1 does not)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def nds_env(tmp_path_factory):
    from nds_tpu import datagen
    from nds_tpu.power import setup_tables
    from sqlite_oracle import load_database
    data = str(tmp_path_factory.mktemp("pallas_nds") / "d")
    datagen.generate_data_local(data, 0.01, parallel=4, overwrite=True)
    conn = load_database(data)

    def mk(ops):
        s = Session(EngineConfig(pallas_ops=ops))
        setup_tables(s, data, "csv")
        return s
    return mk, conn


@pytest.mark.slow
@pytest.mark.parametrize("number", [9, 22, 67, 95])
def test_nds_query_on_off_sqlite_differential(nds_env, number):
    from nds_tpu import streams, validate
    from sqlite_oracle import normalize_rows, sort_rows, to_sqlite_sql
    mk, conn = nds_env
    sql = streams.instantiate(number, stream=0, rngseed=778)
    name = f"query{number}"
    expected = conn.execute(to_sqlite_sql(sql)).fetchall()
    rows = {}
    for label, ops in (("off", ()), ("on", ("sort", "groupby", "gather"))):
        s = mk(ops)
        t = s.sql(sql, backend="jax", label=name)
        at = arrow_bridge.to_arrow(t)
        rows[label] = [tuple(r[c] for c in at.column_names)
                       for r in at.to_pylist()]
        if ops:
            assert "pallas_fallback_reason" not in s.last_exec_stats
        names = list(t.names)
    assert rows["on"] == rows["off"], f"{name}: pallas on/off differ"
    rows_e = sort_rows(normalize_rows(expected))
    rows_a = sort_rows(normalize_rows(rows["on"]))
    assert len(rows_e) == len(rows_a), name
    for re_, ra_ in zip(rows_e, rows_a):
        assert validate.row_equal(re_, ra_, name, names), \
            f"{name}: sqlite {re_} != engine {ra_}"
